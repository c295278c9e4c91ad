"""Span tracer and scope contract (src/repro/trace, DESIGN.md §13): nesting
and ordering, tag propagation, the sync boundary, the JSONL schema round-trip,
ring-buffer capacity, the disabled path (no allocation, no clock read), host
spans on the profiler's clock, and the named scopes the compiled train step
carries in its op metadata."""
import glob
import json
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import trace
from repro.trace import export, tracer


@pytest.fixture(autouse=True)
def _clean_global():
    """Every test leaves the process-global tracer disabled."""
    yield
    trace.disable()


# ---------------------------------------------------------------------------
# span recording: nesting, ordering, tags
# ---------------------------------------------------------------------------


def test_nesting_parent_depth_and_order():
    tr = tracer.Tracer()
    with tr.span("outer", job=1):
        with tr.span("mid"):
            with tr.span("inner"):
                pass
        with tr.span("mid2"):
            pass
    spans = tr.spans
    # records land at span END -> innermost first, outer last
    assert [s["name"] for s in spans] == ["inner", "mid", "mid2", "outer"]
    by = {s["name"]: s for s in spans}
    assert by["outer"]["parent"] == -1 and by["outer"]["depth"] == 0
    assert by["mid"]["parent"] == by["outer"]["id"]
    assert by["inner"]["parent"] == by["mid"]["id"]
    assert by["inner"]["depth"] == 2
    assert by["mid2"]["parent"] == by["outer"]["id"]
    # children are contained in the parent's interval
    assert by["outer"]["ts"] <= by["inner"]["ts"]
    assert by["inner"]["ts"] + by["inner"]["dur"] \
        <= by["outer"]["ts"] + by["outer"]["dur"] + 1e-9


def test_tags_at_open_and_late_tag():
    tr = tracer.Tracer()
    with tr.span("s", bucket=3, phase="encode") as sp:
        sp.tag(rounds=7)
    (s,) = tr.spans
    assert s["tags"] == {"bucket": 3, "phase": "encode", "rounds": 7}


def test_sync_blocks_and_marks():
    tr = tracer.Tracer()
    with tr.span("s") as sp:
        out = sp.sync(jnp.arange(8) * 2)
    assert np.array_equal(np.asarray(out), np.arange(8) * 2)
    assert tr.spans[0]["synced"] is True
    with tr.span("t"):
        pass
    assert tr.spans[1]["synced"] is False


def test_sync_inside_jit_trace_is_not_marked():
    """Under a jit trace the value is a Tracer — sync must not block (it
    cannot) and must not claim the duration is a device time."""
    tr = tracer.Tracer()

    @jax.jit
    def f(x):
        with tr.span("inside") as sp:
            return sp.sync(x * 2)

    f(jnp.ones(4))
    inside = [s for s in tr.spans if s["name"] == "inside"]
    assert inside and all(not s["synced"] for s in inside)


def test_threads_get_independent_stacks():
    tr = tracer.Tracer()
    done = threading.Event()

    def worker():
        with tr.span("w"):
            done.wait(1.0)

    t = threading.Thread(target=worker)
    with tr.span("main"):
        t.start()
        done.set()
        t.join()
    by = {s["name"]: s for s in tr.spans}
    assert by["w"]["parent"] == -1  # not nested under main's span
    assert by["w"]["tid"] != by["main"]["tid"]


def test_ring_capacity_drops_oldest():
    tr = tracer.Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert [s["name"] for s in tr.spans] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped == 6


# ---------------------------------------------------------------------------
# the global switch + disabled-path overhead
# ---------------------------------------------------------------------------


def test_global_enable_disable_round_trip():
    assert not trace.enabled()
    assert trace.span("x") is tracer.NULL_SPAN
    tr = trace.enable()
    assert trace.enabled() and trace.get() is tr
    with trace.span("y", k=1):
        pass
    assert tr.spans[0]["name"] == "y"
    trace.disable()
    assert not trace.enabled()
    with trace.span("z"):
        pass
    assert len(tr.spans) == 1  # nothing recorded after disable


def test_null_span_is_falsy_noop():
    sp = trace.span("whatever", a=1)
    assert not sp
    with sp as inner:
        inner.tag(b=2)
        assert inner.sync(123) == 123


def _agg_fn(tree):
    """A jitted fpisa aggregation of ``tree`` over a one-device data mesh."""
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.core.agg import AggConfig, Aggregator

    mesh = compat.make_mesh((1,), ("data",))
    agg = Aggregator(AggConfig(strategy="fpisa", backend="jnp",
                               bucket_bytes=1 << 16), ("data",))
    return jax.jit(compat.shard_map(
        agg.allreduce_tree, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), tree),),
        out_specs=jax.tree.map(lambda _: P(), tree), check_vma=False))


def test_disabled_overhead_under_one_percent_of_agg_step(monkeypatch):
    """What lets spans live permanently on the hot paths: with tracing off,
    ``span()`` hands back the shared ``NULL_SPAN`` (no allocation) and reads
    no clock; and a compiled step crosses no span site, so even with tracing
    on it records nothing and reads no clock."""
    reads = []
    real = tracer.perf_counter
    monkeypatch.setattr(tracer, "perf_counter", lambda: reads.append(1) or real())

    assert not trace.enabled()
    for _ in range(1000):
        with trace.span("hot", phase="encode") as sp:
            assert sp is tracer.NULL_SPAN
            sp.sync(None)
    assert reads == []

    rng = np.random.default_rng(0)
    tree = {f"l{i}": jnp.asarray((rng.standard_normal(n) * 0.01)
                                 .astype(np.float32))
            for i, n in enumerate((4096, 777, 2048))}
    tr = trace.enable()
    fn = _agg_fn(tree)
    jax.block_until_ready(fn(tree))  # traces and compiles
    for _ in range(3):
        jax.block_until_ready(fn(tree))
    assert tr.spans == [] and reads == []


# ---------------------------------------------------------------------------
# export schema round-trips
# ---------------------------------------------------------------------------


def test_jsonl_round_trip_and_schema_header(tmp_path):
    tr = tracer.Tracer()
    with tr.span("a", phase="encode", elems=256) as sp:
        sp.sync(jnp.ones(4))
    path = tmp_path / "t.jsonl"
    export.write_jsonl(tr, path)
    header, spans = export.read_jsonl(path)
    assert header["schema"] == tracer.SCHEMA_VERSION
    assert header["kind"] == "repro-trace"
    assert header["clock"] == "perf_counter"
    assert len(spans) == 1
    rec = tr.spans[0]
    assert spans[0] == json.loads(json.dumps(rec))  # value-faithful


def test_read_jsonl_rejects_wrong_kind_and_schema(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"kind": "not-a-trace", "schema": 1}\n')
    with pytest.raises(ValueError, match="kind"):
        export.read_jsonl(p)
    p.write_text('{"kind": "repro-trace", "schema": 999}\n')
    with pytest.raises(ValueError, match="schema"):
        export.read_jsonl(p)


def test_enabled_span_enters_trace_annotation_and_trace_out_dir_captures(
        tmp_path, monkeypatch):
    """An enabled span enters a ``jax.profiler.TraceAnnotation`` of its name
    for its whole duration; ``--trace-out <dir>`` wraps the run in a profiler
    capture that holds the host spans, and a ``.jsonl`` path still writes the
    span JSONL."""
    import argparse

    events = []

    class Recorder:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    with monkeypatch.context() as m:
        m.setattr(tracer, "TraceAnnotation", Recorder)
        tr = tracer.Tracer()
        with tr.span("outer"):
            with tr.span("inner"):
                pass
    assert events == [("enter", "outer"), ("enter", "inner"),
                      ("exit", "inner"), ("exit", "outer")]

    ap = argparse.ArgumentParser()
    trace.add_trace_args(ap)
    capture = tmp_path / "capture"
    session = trace.from_args(ap.parse_args(["--trace-out", str(capture)]))
    with trace.span("train.step"):
        jax.block_until_ready(jnp.arange(8) * 2)
    assert session.finish() == str(capture)
    (path,) = glob.glob(str(capture / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in jax.profiler.ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert "train.step" in names

    session = trace.from_args(ap.parse_args(["--trace-out", str(tmp_path / "t.jsonl")]))
    with trace.span("train.fetch"):
        pass
    session.finish()
    _, spans = export.read_jsonl(tmp_path / "t.jsonl")
    assert [s_["name"] for s_ in spans] == ["train.fetch"]


# ---------------------------------------------------------------------------
# instrumented seams actually record
# ---------------------------------------------------------------------------


def _innermost(op_name: str):
    """The innermost of ``trace.SCOPES`` on an ``op_name`` path (a transform
    wraps a component: ``transpose(jvp(model.attn))``)."""
    found = None
    for part in op_name.split("/"):
        part = re.sub(r"^(\w+\()+", "", part).rstrip(")")
        if part in trace.SCOPES:
            found = part
    return found


def _op_names(hlo_text: str) -> list[tuple[str, str]]:
    """[(opcode, op_name)] of every instruction of an HLO text with an op_name."""
    out = []
    for line in hlo_text.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        opcode = re.search(r" ([a-z][\w\-.]*)\(", " " + line.partition(" = ")[2])
        if name and opcode:
            out.append((opcode.group(1), name.group(1)))
    return out


def test_aggregator_facade_emits_spans():
    """The compiled aggregation carries the ``agg`` scopes: every op of the
    fpisa all-reduce of a tree sits under ``agg``, split into encode, psum
    and decode, with the integer psum under ``agg.psum``."""
    rng = np.random.default_rng(0)
    tree = {f"l{i}": jnp.asarray(rng.standard_normal(n).astype(np.float32))
            for i, n in enumerate((4096, 777))}
    text = _agg_fn(tree).lower(tree).compile().as_text()
    ops = [(op, name) for op, name in _op_names(text) if name.startswith("jit(")]
    assert ops and all("/agg/" in name for _, name in ops)
    assert {_innermost(name) for _, name in ops} >= {"agg.encode", "agg.psum", "agg.decode"}
    assert any(op == "all-reduce" and _innermost(name) == "agg.psum" for op, name in ops)
    assert any(op == "all-reduce" and _innermost(name) == "agg.encode" for op, name in ops)


def test_scope_names_only_the_program_scopes():
    with trace.scope("agg.psum"):
        pass
    with pytest.raises(ValueError, match="unknown scope"):
        trace.scope("agg.allreduce")


def test_compiled_train_step_carries_every_scope():
    """A smoke dense train step with fpisa aggregation on a one-device mesh,
    over sequences long enough for the flash-attention kernel, compiled:
    its op metadata names every scope of ``trace.SCOPES``, a backward
    (transpose) op under ``model.attn``, and an all-reduce under
    ``agg.psum``."""
    from repro.configs import get_smoke_config
    from repro.core.agg import AggConfig
    from repro.launch.train import build_step, init_state
    from repro.runtime.elastic import make_mesh_for

    cfg = get_smoke_config("qwen1.5-0.5b")
    mesh = make_mesh_for(devices=jax.devices()[:1])
    model, opt_cfg, step = build_step(cfg, mesh, AggConfig(strategy="fpisa", backend="jnp"), 2)
    params, opt = init_state(model, cfg, mesh, opt_cfg)
    text = step.lower(params, opt, {"tokens": jnp.zeros((2, 2048), jnp.int32)}).compile().as_text()
    ops = _op_names(text)
    assert {_innermost(name) for _, name in ops} >= set(trace.SCOPES)
    assert any("transpose(" in name and _innermost(name) == "model.attn" for _, name in ops)
    assert any(op == "all-reduce" and _innermost(name) == "agg.psum" for op, name in ops)


def test_switchsim_emits_rounds_tag():
    from repro import switchsim as ss
    from repro.core import switch as sw

    trace.enable()
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((2, 64)).astype(np.float32)
    s = sw.FpisaSwitch(sw.SwitchConfig(num_workers=2, num_slots=4,
                                       elems_per_packet=32))
    ss.run_aggregation(s, vecs, seed=1)
    spans = [s_ for s_ in trace.get().spans
             if s_["name"] == "switchsim.run_aggregation"]
    assert spans and spans[0]["tags"]["rounds"] >= 1
    assert spans[0]["tags"]["phase"] == "switch"
