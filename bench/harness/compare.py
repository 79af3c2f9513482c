"""The comparisons that decide ``correct``: each gives numbers, and each
number has a limit of its own, set in the cell's workload file from the
readings recorded in PERF.md (sound runs of the program over a dozen
seeds; the control and the planted faults).

Training, over the checked updates:

* ``loss_gap``: the largest relative gap between the program's loss and
  the reference's, over the updates.
* ``change_gap``: over the leaves, the largest gap between the norm of a
  leaf's change over the checked updates in the program and in the
  reference, against the reference's norm of that leaf or of the median
  leaf, whichever is larger.

Leaves whose reference gradient (the first update's, as AdamW received
it) is under a thousandth of the median leaf's move under Adam by
round-off alone; they are left out by that rule, whatever their name.
No gradient is compared, by its worst leaf, its median leaf or its norm:
at these random weights bf16 rounding moves each of them as far as a
fault does. PERF.md gives their readings and why.

Serving, over a sample of delivered requests, in units of each query's
reference norm:

* ``rank_gap``: the widest gap by which the reference score of the id
  served at a rank lies below the reference's own score at that rank.
* ``score_gap``: the widest gap between a served score and the reference
  score of the served id.
* ``repeated_ids``: how many served ids repeat an id served earlier in the
  same list (a top-k that returns one row twice); exact, limit 0.
"""

from __future__ import annotations

import numpy as np

TINY_GRAD = 1e-3


def leaf_gaps(prog: dict, ref: dict, keep) -> dict:
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep}


def worst_leaf(prog: dict, ref: dict, keep) -> float:
    return max(leaf_gaps(prog, ref, keep).values())


def kept_leaves(ref_grad: dict):
    med = float(np.median(list(ref_grad.values())))
    return [k for k, v in ref_grad.items() if v >= TINY_GRAD * med]


def train_readings(program: dict, ref: dict) -> dict:
    keep = kept_leaves(ref["grad1"])
    lp, lr = np.asarray(program["losses"], float), np.asarray(ref["losses"], float)
    if lp.shape != lr.shape:
        return {"loss_gap": float("inf"), "change_gap": float("inf")}
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "change_gap": float(worst_leaf(program["change"], ref["change"], keep)),
    }


def repeats(ids) -> int:
    """Served ids (not the empty slot -1) that repeat one earlier in their list."""
    ids = np.asarray(ids)
    s = np.sort(ids, axis=1)
    return int(np.sum((s[:, 1:] == s[:, :-1]) & (s[:, 1:] >= 0)))


def serve_readings(served_ids, served_s, ref: dict) -> dict:
    norm = ref["q_norm"][:, None]
    served_ref = ref["served_ref_s"]
    ok = (np.asarray(served_ids) >= 0) & np.isfinite(served_ref)
    if not ok.all():
        return {"rank_gap": float("inf"), "score_gap": float("inf"),
                "repeated_ids": float(repeats(served_ids))}
    return {
        "rank_gap": float(np.max((ref["top_s"] - served_ref) / norm)),
        "score_gap": float(np.max(np.abs(np.asarray(served_s) - served_ref) / norm)),
        "repeated_ids": float(repeats(served_ids)),
    }


def with_limits(readings: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits[k]} for k, v in readings.items()}


def all_within(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
