"""The numbers that decide ``correct``: each compares what the timed path
produced with the plain reference and is held to a limit of its own
(``bench/limits/<cell>.json``, with the readings each limit was set from).
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

LIMITS = Path(__file__).resolve().parent / "limits"
# a reference leaf whose gradient norm is under this share of the median
# leaf's moves by round-off alone (a key offset under softmax); it is left
# out of the leaf comparisons
ROUND_OFF_LEAF = 1e-3


def leaf_gap(got: dict, want: dict, keep: set) -> float:
    """Worst leaf: |‖got‖ - ‖want‖| over the larger of ‖want‖ and the median
    leaf's ‖want‖.  ``got``/``want`` map leaf path -> norm."""
    if set(got) != set(want):
        raise ValueError(f"leaves differ: {sorted(set(got) ^ set(want))}")
    median = float(np.median([want[k] for k in keep]))
    return float(np.max([abs(got[k] - want[k]) / max(want[k], median)
                         for k in sorted(keep)]))


def kept_leaves(ref_grad_norms: dict) -> set:
    """Leaves the leaf comparisons hold: all but round-off-only ones."""
    median = float(np.median(list(ref_grad_norms.values())))
    return {k for k, v in ref_grad_norms.items() if v >= ROUND_OFF_LEAF * median}


def pred_gap(got, want) -> float:
    """max |got - want| over max |want|, for one cloud's predictions."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def rms_gap(got, want) -> float:
    """rms(got - want) over rms(want), for one cloud's predictions."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.sqrt(np.mean((got - want) ** 2) / max(np.mean(want ** 2), 1e-60)))


def rel_gap(got: float, want: float) -> float:
    if not np.isfinite(got):
        return float("inf")
    return abs(got - want) / max(abs(want), 1e-30)


def train_numbers(prog: dict, ref: dict) -> dict:
    """A training cell's numbers.  ``prog`` and ``ref`` each hold the first
    steps from one start: ``losses`` per step; ``grad_norms`` (leaf path ->
    norm of the first gradient as the optimizer gets it); ``change_norms``
    (leaf path -> norm of the parameters' change over those steps).  ``ref``
    also holds ``gaps``, each step's largest selection replay gap, or None
    where the model selects no blocks."""
    keep = kept_leaves(ref["grad_norms"])
    out = {
        "loss_gap": float(np.max([rel_gap(a, b) for a, b in
                                  zip(prog["losses"], ref["losses"])])),
        "grad_gap": leaf_gap(prog["grad_norms"], ref["grad_norms"], keep),
        "update_gap": leaf_gap(prog["change_norms"], ref["change_norms"], keep),
    }
    if ref["gaps"] is not None:
        out["selection_gap"] = float(np.max(ref["gaps"]))
    return out


def load_limits(cell: str) -> dict:
    """{number: limit} for a cell; a cell without a limits file has none
    set, and no run of it is correct."""
    path = LIMITS / f"{cell}.json"
    if not path.is_file():
        return {}
    return {k: v["limit"] for k, v in json.loads(path.read_text()).items()}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers the limits
    name: correct when each is there and at or under its limit.  A number
    with no limit is not compared; a cell with no limits is never correct."""
    checks = {k: {"value": float(numbers.get(k, float("nan"))), "limit": v}
              for k, v in limits.items()}
    ok = bool(checks) and all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
