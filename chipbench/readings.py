"""Read the numbers that decide ``correct`` over many seeds in one process:
what the limits in a cell's file are set from. The benchmark's runs do not
run this.

    python3 chipbench/readings.py --workload <name> --seeds 1,2,3 [--program] [--control]

For each seed, the reference's first steps, then against them: with
``--program`` the program's (the lower reading of each number); with
``--control`` the reference at fp8 and the faults a train cell can have,
planted in the reference put in the program's place: half of the batch left
out, and on more than one chip the exchange between chips left out (the
upper reading). A step that hands its state back unchanged reads 1 by
construction and needs no run. One JSON line per seed and reading.
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import check, harness

    cell = harness.load_cell(args.workload)
    devices = harness.chips(cell["chips"])
    harness.use_cache()
    kinds = [("fp8", {"precision": "fp8"}), ("half", {"variant": "half"})]
    if cell["chips"] > 1:
        kinds.append(("one_replica", {"variant": "one_replica"}))
    for seed in (int(s) for s in args.seeds.split(",")):
        got = {}
        if args.program:
            from chipbench.program import Trainer

            trainer = Trainer(cell["cfg"], cell, seed, devices)
            got["program"] = harness.first_steps(trainer, harness.token_source(cell, seed))
            trainer.free()
            del trainer
            gc.collect()
        want = harness.reference_steps(cell, seed, devices)
        if args.control:
            for name, kw in kinds:
                got[name] = harness.reference_steps(cell, seed, devices, **kw)
        for name, numbers in got.items():
            worst = {k: max(check.leaf_gaps(numbers, want, k).items(), key=lambda kv: kv[1])[0]
                     for k in ("grad_norms", "change_norms")}
            line = {"workload": args.workload, "seed": seed, "reading": name,
                    **check.readings(numbers, want), "worst_leaf": worst,
                    "losses": numbers["losses"], "reference_losses": want["losses"]}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
