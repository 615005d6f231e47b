"""The numbers that decide ``correct``, each held to its own limit.

Training (per cell, over the first calls the set-up drives):

* ``grad_gap`` — the gradient as the optimizer got it over the first
  call's steps, read back out of the state after that call (local leaves:
  (l0 - l) / gamma_l; shared leaves: the node-summed perturbation, state
  change minus those rounds' noise, over -gamma_s), by the median leaf;
* ``change_gap`` — every leaf's change over that call, by the median leaf,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (they move by round-off alone).

A leaf's gap is | |x|_prog - |x|_ref | over the larger of that leaf's
reference norm and the median leaf's. The median leaf and not the worst:
over a call's second step the tied embedding's gradient can spike on some
seeds, and how far is chaotic in the matmul precision, so the worst leaf
swings from seed to seed where the median leaf is steady (PERF.md).

Consensus: ``y_gap`` — every node's corrected estimate y_i at the end of a
sampled job, worst element over the job's largest reference value.
"""
from __future__ import annotations

import numpy as np

MIN_GRAD_SHARE = 1e-3


def leaf_gaps(prog: dict[str, float], ref: dict[str, float],
              leaves=None) -> list[float]:
    """Each leaf's gap; an empty list where a reading is not finite."""
    leaves = list(ref) if leaves is None else list(leaves)
    median = float(np.median([ref[k] for k in ref]))
    if not np.all(np.isfinite([prog[k] for k in leaves])):
        return []
    return [abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
            for k in leaves]


def median_gap(prog: dict[str, float], ref: dict[str, float],
               leaves=None) -> float:
    gaps = leaf_gaps(prog, ref, leaves)
    return float(np.median(gaps)) if gaps else float("inf")


def moving_leaves(ref_grad: dict[str, float]) -> list[str]:
    median = float(np.median(list(ref_grad.values())))
    return [k for k, v in ref_grad.items() if v >= MIN_GRAD_SHARE * median]


def train_numbers(prog: dict, ref: dict) -> dict[str, float]:
    """``prog`` / ``ref``: {"grad": {leaf: norm}, "change": {leaf: norm}}.

    The checked steps' losses are not compared: the program's
    default-precision matmuls move them as far from the reference as the
    bfloat16 control does (PERF.md), so no limit separates the two."""
    return {
        "grad_gap": median_gap(prog["grad"], ref["grad"]),
        "change_gap": median_gap(prog["change"], ref["change"],
                                 moving_leaves(ref["grad"])),
    }


def y_gap(y_prog: np.ndarray, y_ref: np.ndarray) -> float:
    y_prog = np.asarray(y_prog, np.float64)
    y_ref = np.asarray(y_ref, np.float64)
    if y_prog.shape != y_ref.shape or not np.all(np.isfinite(y_prog)):
        return float("inf")
    return float(np.max(np.abs(y_prog - y_ref)) / np.max(np.abs(y_ref)))


def checks(numbers: dict[str, float], limits: dict[str, float]) -> dict:
    """{name: {"value", "limit"}} for every limited number; a number with
    no limit is a fault of the cell's files, not a pass."""
    missing = sorted(set(numbers) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing}")
    return {k: {"value": float(v), "limit": float(limits[k])}
            for k, v in numbers.items()}


def passed(check: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in check.values())
