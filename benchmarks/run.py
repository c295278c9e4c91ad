"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see each module's docstring for
what it reproduces and the paper's claim it is checked against). A module
that raises prints an ``ERROR`` row, the rest still run, and the exit code is
non-zero.
"""
import sys
import traceback
from time import perf_counter

from repro.launch.compile_cache import use_compile_cache

MODULES = [
    "tab1_alu_cost",
    "fig7_gradient_ratio",
    "fig8_error_dist",
    "fig9_convergence",
    "fig10_goodput",
    "fig11_e2e_speedup",
    "fig13_queries",
    "fig_recovery",
    "fig_contention",
    "fig_serve",
    "tab3_resource_util",
    "roofline",
    "fig_autotune",
]


def main() -> int:
    use_compile_cache()
    print("name,us_per_call,derived")
    only = sys.argv[1:] or None
    failed = []
    for name in MODULES:
        if only and name not in only:
            continue
        t0 = perf_counter()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            mod.run()
            print(f"{name}.wall,{(perf_counter()-t0)*1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001 — report, keep going
            traceback.print_exc()
            print(f"{name}.wall,{(perf_counter()-t0)*1e6:.0f},ERROR:{type(e).__name__}")
            failed.append(name)
    if failed:
        print(f"FAILED: {' '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
