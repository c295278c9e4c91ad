"""Training launcher: end-to-end loop with checkpoint/restart, health
monitoring, and FPISA gradient aggregation.

Usage (CPU-scale example — see examples/train_lm.py for a driver):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --steps 50 --agg fpisa --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
from time import perf_counter

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.core.agg import AggConfig, add_agg_args
from repro.launch.compile_cache import use_compile_cache
from repro.trace import add_trace_args
from repro.trace import from_args as trace_from_args
from repro.data.pipeline import ShardedLoader, SyntheticCorpus
from repro.models.registry import build, param_count
from repro.optim import optimizers
from repro.runtime import checkpoint as ckpt
from repro.runtime.elastic import make_mesh_for
from repro.runtime.health import HealthMonitor
from repro.sharding import rules
from repro.train.step import make_train_step


def opt_config(cfg, opt_overrides: dict | None = None) -> optimizers.OptConfig:
    """The model config's optimizer, with ``opt_overrides`` applied."""
    return optimizers.OptConfig(**{"name": cfg.optimizer, "lr": cfg.learning_rate,
                                   **(opt_overrides or {})})


def state_shardings(model, cfg, mesh, opt_cfg: optimizers.OptConfig):
    """(param shardings, optimizer-state shardings) that ``train_loop``
    keeps the training state in, step after step."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = rules.param_pspecs(shapes, cfg, mesh)
    ostate = rules.named(mesh, rules.opt_pspecs(pspecs, shapes, mesh))
    return rules.named(mesh, pspecs), optimizers.OptState(
        step=NamedSharding(mesh, P()), m=ostate,
        v=ostate if opt_cfg.name == "adamw" else None)


def build_step(cfg, mesh, agg: AggConfig, global_batch: int, *,
               opt_overrides: dict | None = None, diagnostics: bool = False):
    """(model, opt_cfg, step_fn): the model, the optimizer config and the
    jitted train step that ``train_loop`` runs.

    The step returns params and optimizer state in the shardings it takes
    them in (``state_shardings``), so every step runs the one compiled
    program, and it donates them: the update reuses their buffers instead of
    holding two copies. ``diagnostics`` adds the per-replica and aggregated
    gradients to the step's metrics (``repro.train.step.make_train_step``)."""
    model = build(cfg)
    opt_cfg = opt_config(cfg, opt_overrides)
    pshard, oshard = state_shardings(model, cfg, mesh, opt_cfg)
    step_fn = jax.jit(make_train_step(model, mesh, agg, opt_cfg, global_batch,
                                      diagnostics=diagnostics),
                      donate_argnums=(0, 1), out_shardings=(pshard, oshard, None))
    return model, opt_cfg, step_fn


def init_state(model, cfg, mesh, opt_cfg: optimizers.OptConfig, seed: int = 0):
    """(params, opt_state): a random init made under jit straight into
    ``state_shardings``, so no device holds more than its share at any
    point."""
    pshard, oshard = state_shardings(model, cfg, mesh, opt_cfg)
    params = jax.jit(model.init, out_shardings=pshard)(jax.random.PRNGKey(seed))
    opt_state = jax.jit(lambda p: optimizers.init(p, opt_cfg), out_shardings=oshard)(params)
    return params, opt_state


def train_loop(cfg, *, steps: int, global_batch: int, seq_len: int,
               agg: AggConfig | None = None,
               agg_strategy: str = "fpisa", agg_backend: str = "auto",
               agg_chunk: int = 0, agg_bucket_bytes: int = 0,
               ckpt_dir: str | None = None,
               ckpt_every: int = 50, mesh=None, log_every: int = 10,
               opt_overrides: dict | None = None, seed: int = 0):
    """Plain (non-elastic) training loop.

    Aggregation is configured by ONE ``AggConfig`` (``agg``); the loose
    ``agg_*`` keyword args are retained for backwards compatibility and are
    ignored when ``agg`` is given."""
    mesh = mesh or make_mesh_for()
    if agg is None:
        agg = AggConfig(strategy=agg_strategy, backend=agg_backend,
                        chunk_elems=agg_chunk, bucket_bytes=agg_bucket_bytes)
    model, opt_cfg, step_fn = build_step(cfg, mesh, agg, global_batch,
                                         opt_overrides=opt_overrides)
    params, opt_state = init_state(model, cfg, mesh, opt_cfg, seed)

    start_step = 0
    saver = None
    if ckpt_dir:
        saver = ckpt.AsyncCheckpointer(ckpt_dir)
        latest = ckpt.latest_step(ckpt_dir)
        if latest is not None:
            try:
                # atomic bundle: params + opt always come from the SAME step
                trees, extra = ckpt.restore_bundle(
                    ckpt_dir, latest, {"params": params, "opt": opt_state})
                host_params, host_opt = trees["params"], trees["opt"]
            except ValueError:
                # pre-bundle layout (params at <dir>, opt at <dir>_opt) from
                # an older run — restore it once; the next save commits a
                # bundle and the split dirs stop mattering
                host_params, extra = ckpt.restore(ckpt_dir, latest, params)
                host_opt, _ = ckpt.restore(ckpt_dir + "_opt", latest, opt_state)
            pshard, oshard = state_shardings(model, cfg, mesh, opt_cfg)
            params = jax.device_put(host_params, pshard)
            opt_state = jax.device_put(host_opt, oshard)
            start_step = latest + 1
            print(f"[train] resumed from step {latest}")

    loader = ShardedLoader(SyntheticCorpus(cfg.vocab_size, seed), global_batch, seq_len)
    bspec = rules.batch_pspec(mesh, global_batch)
    health = HealthMonitor(hosts=[0])

    print(f"[train] {cfg.name}: {param_count(params)/1e6:.1f}M params, "
          f"mesh={dict(mesh.shape)}, agg={agg.strategy}")
    history = []
    for step in range(start_step, steps):
        t0 = perf_counter()
        batch = {"tokens": jax.device_put(
            loader.batch_at(step)["tokens"], NamedSharding(mesh, P(*bspec, None)))}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        dt = perf_counter() - t0
        health.heartbeat(0, dt)
        history.append(loss)
        if step % log_every == 0 or step == steps - 1:
            tok_s = global_batch * seq_len / dt
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {tok_s:,.0f} tok/s")
        if saver and step > 0 and step % ckpt_every == 0:
            saver.save_bundle(step, {"params": params, "opt": opt_state},
                              {"loss": loss})
    if saver:
        saver.wait()
    return params, opt_state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    add_agg_args(ap)  # the shared --agg-* flags (repro.core.agg)
    add_trace_args(ap)  # the shared --trace-* flags (repro.trace)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fault-plan", default="",
                    help="fault-injection spec, e.g. 'kill:2@5' or "
                         "'kill:2@5,revive:2@20,slow:3@4x6' — routes the run "
                         "through the elastic controller "
                         "(repro/runtime/controller.py): heartbeats, switch-"
                         "slot reclamation, re-mesh + bit-identical resume")
    ap.add_argument("--num-hosts", type=int, default=None,
                    help="logical worker / host count for the elastic "
                         "controller (default: one per device); implies the "
                         "controller path even without --fault-plan")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    try:
        agg = AggConfig.from_args(args)
    except ValueError as e:
        ap.error(str(e))
    session = trace_from_args(args)
    try:
        if args.fault_plan or args.num_hosts:
            if agg.chunk_elems:
                ap.error("--agg-chunk is not supported on the elastic "
                         "controller path (stacked aggregation; use "
                         "--bucket-bytes instead)")
            from repro.runtime.controller import run_controller

            run_controller(cfg, steps=args.steps,
                           global_batch=args.global_batch,
                           seq_len=args.seq_len, agg=agg,
                           num_hosts=args.num_hosts, ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every,
                           fault_plan=args.fault_plan)
            return
        train_loop(cfg, steps=args.steps, global_batch=args.global_batch,
                   seq_len=args.seq_len, agg=agg,
                   ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    finally:
        session.finish()


if __name__ == "__main__":
    main()
