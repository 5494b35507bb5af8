"""The comparisons that decide `correct`. Each number has a limit of its own
in the cell's file (`limits`), set from readings on the chip (PERF.md)."""
from __future__ import annotations

import numpy as np


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    gap = np.abs(prog - ref) / np.maximum(ref, np.median(ref))
    if keep is not None:
        gap = gap[keep]
    return float(gap.max())


def train_numbers(prog: dict, ref: dict) -> dict:
    """loss_gap: the widest relative gap of a step's loss. grad_gap: worst
    leaf of the first gradient's norm. change_gap: worst leaf of the norm of
    the parameters' change, over the leaves whose reference gradient is at
    least a thousandth of the median leaf's (the others move by round-off
    alone under Adam)."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    moved = ref["grad_norms"] >= 1e-3 * np.median(ref["grad_norms"])
    return {"loss_gap": float(loss_gap),
            "grad_gap": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
            "change_gap": worst_leaf_gap(prog["change_norms"], ref["change_norms"], moved)}


def judge(numbers: dict, limits: dict) -> dict:
    """{name: {value, limit, ok}} for every number that has a limit. A number
    that is not finite fails."""
    out = {}
    for name, limit in limits.items():
        value = float(numbers[name])
        out[name] = {"value": value, "limit": limit,
                     "ok": bool(np.isfinite(value) and value <= limit)}
    return out
