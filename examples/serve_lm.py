"""Serving example: greedy decode over a Poisson request trace with either
engine — ``--engine static`` (lockstep batches, dense per-slot KV) or
``--engine continuous`` (continuous batching over the paged KV cache,
repro.serve.scheduler). Both see the same load-generated workload and both
aggregate their serving telemetry across the data axis through the same
Aggregator facade the trainers use (the shared ``--agg-*`` flags) — one
aggregation surface for the whole repo.

Run:  PYTHONPATH=src python examples/serve_lm.py [--agg-strategy fpisa]
      PYTHONPATH=src python examples/serve_lm.py --smoke --engine continuous
"""
import argparse
from time import perf_counter

import jax

from repro.configs import get_smoke_config
from repro.core.agg import AggConfig, add_agg_args
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import build, param_count
from repro.serve.engine import ServeEngine
from repro.serve.loadgen import PoissonLoadGen, latency_report
from repro.serve.scheduler import ContinuousEngine
from repro.trace import add_trace_args
from repro.trace import from_args as trace_from_args


def main():
    ap = argparse.ArgumentParser()
    add_agg_args(ap)  # the shared --agg-* flags (repro.core.agg)
    add_trace_args(ap)  # the shared --trace-* flags (repro.trace)
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="static", help="serving engine to demo")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + short trace (CI serve-smoke size)")
    ap.add_argument("--requests", type=int, default=None,
                    help="trace length (default 8, smoke 6)")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="Poisson arrival rate, requests per scheduler step")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()
    try:
        agg = AggConfig.from_args(args)
    except ValueError as e:
        ap.error(str(e))

    cfg = get_smoke_config("internlm2-20b").with_(num_layers=4, d_model=128,
                                                  num_heads=8, num_kv_heads=2)
    slots, max_len, page = 4, 128, 16
    n_req, prompt_lens, max_new = 8, (4, 8, 16), (8, 16)
    if args.smoke:
        cfg = cfg.with_(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2)
        slots, max_len, page = 3, 32, 8
        n_req, prompt_lens, max_new = 6, (4, 8), (4, 8)
    if args.requests is not None:
        n_req = args.requests

    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    print(f"serving {cfg.name}: {param_count(params)/1e6:.1f}M params, "
          f"engine={args.engine}, telemetry agg={agg.strategy}")

    lg = PoissonLoadGen(rate=args.rate, prompt_lens=prompt_lens,
                        max_new=max_new, vocab_size=cfg.vocab_size,
                        seed=args.seed)
    trace = lg.trace(n_req)

    session = trace_from_args(args)
    t0 = perf_counter()
    if args.engine == "continuous":
        eng = ContinuousEngine(model, params, num_slots=slots,
                               max_len=max_len, page_size=page, agg=agg)
        results = eng.run_trace(trace)
    else:
        # static engine serves the same requests as one closed queue (it has
        # no notion of arrival times — every request is present up front)
        eng = ServeEngine(model, params, batch_size=slots, max_len=max_len,
                          agg=agg)
        results = eng.run([r for _, r in trace])
    dt = perf_counter() - t0
    session.finish()

    total_new = sum(len(r.tokens) for r in results)
    print(f"{n_req} requests, {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s incl. compile)")
    if args.engine == "continuous":
        rep = latency_report(eng.latency_stats(), slo_ttft=2 * slots,
                             slo_tpot=1.5)
        print("latency (scheduler-step units): " +
              ", ".join(f"{k}={v:.2f}" for k, v in rep.items()))
        print(f"paged KV peak: {eng.cache.peak_pages_in_use} pages "
              f"({eng.cache.peak_pages_in_use * page} tok) vs dense "
              f"{eng.cache.dense_equivalent_tokens} tok")
    print(f"telemetry (aggregated via {eng.aggregator}): {eng.telemetry}")
    for r in results[:3]:
        print(f"  rid={r.rid} -> {r.tokens[:8].tolist()}...")


if __name__ == "__main__":
    main()
