"""Device time per layer of the program, from the scope path of each op.

The program names its layers with ``jax.named_scope`` (``repro.trace.SCOPES``).
The name stack lands in each HLO instruction's ``op_name`` metadata, through
``jvp``, ``transpose`` and remat, so a backward op carries the scope of its
forward op (``transpose(jvp(model.attn))``). An op counts under the innermost
of the scopes in ``LAYERS`` on its path, and a fusion carries its root's path.
The readers ``attention_ms``, ``mlp_ms``, ``vocab_ms``, ``agg_ms`` and
``optimizer_ms`` take the leaf ops of one layer each, and ``unscoped_ms`` those
under none: together they partition the step's device op time. An
asynchronous collective counts for its start and done ops, not for its time
in flight.

The paths come from the HLO of the cell's train step, compiled again from
its shapes for the chips the run used (``step_hlo``): the persistent compile
cache hands back the program that ran, whose HLO text names each instruction
as the profiler names its event. The events of a TPU trace carry no
``op_name``, and ``trace.load`` keeps five fields of an op; where a recording
keeps a sixth, the path (``tests/data/*.scoped.json.gz``), that is used
instead. An instruction the compiler added carries no path of its own and
takes its neighbours' scope (``hlo_paths``). An op whose name, opcode or type
the HLO does not match counts as unscoped. Where no op carries a scope (a
program that names none, or a trace of another backend) every reader
returns None.
"""
from __future__ import annotations

import collections
import re

import jax
import jax.numpy as jnp

from chipbench import trace

LAYERS = {
    "attention_ms": ("model.attn",),
    "mlp_ms": ("model.mlp",),
    "vocab_ms": ("model.embed", "model.head"),
    "agg_ms": ("agg", "agg.encode", "agg.psum", "agg.decode"),
    "optimizer_ms": ("optim",),
}
UNSCOPED = "unscoped_ms"
_LAYER_OF = {scope: metric for metric, scopes in LAYERS.items() for scope in scopes}
_WRAPPER = re.compile(r"^(?:[\w\-]+\()+")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_REF = re.compile(r"%([\w.\-]+)")
_HOPS = 8  # how far an instruction without a path looks for its neighbours' scope


def innermost(path: str) -> str | None:
    """The last component of an ``op_name`` path that is a scope of ``LAYERS``.
    A transformation wraps a component: ``transpose(jvp(model.attn))``."""
    found = None
    for part in path.split("/"):
        part = _WRAPPER.sub("", part).rstrip(")")
        if part in _LAYER_OF:
            found = part
    return found


def hlo_paths(hlo_text: str) -> dict:
    """{instruction name: (opcode, output type, path)} of an HLO module's text,
    parsed as ``trace.parse_op`` parses a trace event's name.

    The path is the instruction's ``op_name`` where that is a name stack of
    the traced program (``jit(...)/...``). The compiler's own instructions
    carry none (layout and memory-space copies, prefetches, zero fills), and
    a copy of an argument carries the argument's name. Such an instruction
    takes the path of the nearest instruction that consumes its result and
    has a scope, passing through others like it; where none does, of the
    nearest that produces its operands; else the path is empty."""
    ops, edges = {}, {"users": collections.defaultdict(list), "operands": {}}
    for line in hlo_text.splitlines():
        line = line.strip().removeprefix("ROOT ")
        if not line.startswith("%") or " = " not in line:
            continue
        name, _, _, opcode, out_type = trace.parse_op(line, 0, 0)
        m = _OP_NAME.search(line)
        path = m.group(1) if m and m.group(1).startswith("jit(") else None
        ops[name] = (opcode, out_type, path)
        edges["operands"][name] = _REF.findall(line.partition(" = ")[2])
        for operand in edges["operands"][name]:
            edges["users"][operand].append(name)

    def nearest(start, way):
        seen, frontier = {start}, [start]
        for _ in range(_HOPS):
            reached = [n for f in frontier for n in edges[way].get(f, ()) if n in ops and n not in seen]
            seen.update(reached)
            for n in reached:
                if ops[n][2] is not None and innermost(ops[n][2]):
                    return ops[n][2]
            frontier = [n for n in reached if ops[n][2] is None]
        return None

    return {name: (opcode, out_type, path if path is not None else
                   nearest(name, "users") or nearest(name, "operands") or "")
            for name, (opcode, out_type, path) in ops.items()}


def step_hlo(cell: dict, devices) -> str:
    """The HLO text of ``cell``'s train step as ``chipbench.program.Trainer``
    compiles it, lowered from shapes and shardings alone."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench import program
    from repro.core.agg import AggConfig
    from repro.launch.train import build_step, state_shardings
    from repro.optim import optimizers
    from repro.runtime.elastic import make_mesh_for
    from repro.sharding import rules

    cfg, batch = cell["cfg"], cell["global_batch"]
    mesh = make_mesh_for(devices=list(devices))
    mcfg = program.model_config(cfg)
    opt = {k: cfg["optimizer"][k] for k in program._OPT_KEYS}
    model, opt_cfg, step_fn = build_step(mcfg, mesh, AggConfig(**cell["agg"]), batch,
                                         opt_overrides=opt)
    pshard, oshard = state_shardings(model, mcfg, mesh, opt_cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(lambda p: optimizers.init(p, opt_cfg), params)

    def placed(a, sharding):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    tokens = jax.ShapeDtypeStruct((batch, cell["seq_len"]), jnp.int32, sharding=NamedSharding(
        mesh, P(*rules.batch_pspec(mesh, batch), None)))
    lowered = step_fn.lower(jax.tree.map(placed, params, pshard),
                            jax.tree.map(placed, opt_state, oshard), {"tokens": tokens})
    return lowered.compile().as_text()


def op_paths(ctx) -> dict | None:
    """{op name: (opcode, output type, path)} for the run's ops: from the
    ops where the recording kept their paths, else from the compiled step on
    a TPU, else None. Worked out once per ``ctx``."""
    if not hasattr(ctx, "scope_paths"):
        ctx.scope_paths = {op[0]: (op[3], op[4], op[5]) for ops in ctx.devices for op in ops
                           if len(op) > 5} or None
        devices = jax.devices()[:ctx.chips]
        if ctx.scope_paths is None and devices[0].platform == "tpu":
            ctx.scope_paths = hlo_paths(step_hlo(ctx.cell, devices))
    return ctx.scope_paths


def layer_of(op, paths: dict) -> str:
    """The metric that ``op`` counts under: one of ``LAYERS`` or ``UNSCOPED``."""
    opcode, out_type, path = paths.get(op[0], (None, None, ""))
    if (opcode, out_type) != (op[3], op[4]):
        return UNSCOPED
    return _LAYER_OF.get(innermost(path), UNSCOPED)


def layer_ms(ctx, metric: str) -> float | None:
    """Device time per step and chip, in ms, of the leaf ops that count under
    ``metric``; None where no op of the traced window carries a scope."""
    paths = op_paths(ctx)
    if not paths or not ctx.per_chip_s(
            lambda ops: sum(op[2] for op in ops if layer_of(op, paths) != UNSCOPED)):
        return None
    return ctx.per_chip_s(lambda ops: sum(op[2] for op in ops if layer_of(op, paths) == metric)) / ctx.steps * 1e3
