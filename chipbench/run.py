"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for. The last line of standard output is the result as one JSON object;
the numbers that decide ``correct`` are the last lines of standard error. With
no TPU, or fewer chips than the cell asks for, it exits 3 and prints no
result. JAX's persistent compilation cache is kept in ``.jax_cache`` at the
root of the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says).
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness

    try:
        devices = harness.chips(harness.load_cell(args.workload)["chips"])
    except harness.NoChip as e:
        import jax

        d = jax.devices()
        print(f"[chipbench {d[0].platform} {d[0].device_kind} x{len(d)}] {e}", file=sys.stderr)
        return 3
    harness.use_cache()
    result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace), t0=T0,
                         devices=devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
