"""The comparison that decides ``correct``: the program's first steps against
the reference's, number by number, each against the limit in the cell's file.

Three numbers, each the worst of its kind:

- ``loss_gap``: over the first steps, |program loss - reference loss| over
  the reference loss;
- ``grad_norm_gap``: over the leaves, |program norm - reference norm| of the
  first gradient as AdamW took it, over the larger of the reference leaf's
  norm and the median leaf's;
- ``change_norm_gap``: the same, of each leaf's change after the first steps.

A leaf whose reference gradient is under a thousandth of the median leaf's
moves by round-off alone and is left out of both leaf numbers.
"""
from __future__ import annotations

import math
import statistics

RULE = 1e-3  # a leaf counts when its reference gradient is at least this share of the median


def leaf_gaps(prog: dict, ref: dict, kind: str) -> dict:
    """{leaf: gap} of ``kind`` (``grad_norms`` or ``change_norms``) over the
    leaves that count."""
    gmed = statistics.median(ref["grad_norms"].values())
    leaves = [p for p, g in ref["grad_norms"].items() if g >= RULE * gmed]
    a, b = prog[kind], ref[kind]
    median = statistics.median(b[p] for p in leaves)
    return {p: abs(a[p] - b[p]) / max(b[p], median) for p in leaves}


def readings(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``losses``, ``grad_norms`` and ``change_norms``."""
    out = {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])),
        "grad_norm_gap": max(leaf_gaps(prog, ref, "grad_norms").values()),
        "change_norm_gap": max(leaf_gaps(prog, ref, "change_norms").values()),
    }
    return {k: (v if math.isfinite(v) else math.inf) for k, v in out.items()}


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """(all within their limits, {name: {"value", "limit"}}). A number that is
    not finite, or has no limit, fails."""
    table = {k: {"value": v, "limit": limits.get(k)} for k, v in values.items()}
    ok = all(t["limit"] is not None and math.isfinite(t["value"]) and t["value"] <= t["limit"]
             for t in table.values())
    return ok, table
