"""The numbers that decide ``correct`` in a training cell.

The program's first steps (taken in set-up, through the same trainer
object and call as the window) are compared with the plain reference's
on the same weights and rows.  A leaf is one parameter tensor, or one
layer's slice of a tensor stacked over layers.

- ``loss_gap``: the largest |program - reference| / reference over the
  checked steps' losses.
- ``grad_gap``: the first clipped gradient, as the optimizer got it (the
  first moment after one step, over 1 - b1): the largest
  | |g_prog| - |g_ref| | over the larger of |g_ref| and the median leaf's
  |g_ref|, over all leaves.
- ``grad_gap_large``: the same over the leaves of at least
  ``LARGE_LEAF`` elements (the projections and the embedding).  A small
  leaf's norm rests on few elements (mamba2's per-layer ``A_log``,
  ``dt_bias`` and ``D`` hold 32), so its rounding noise sets the worst
  leaf of ``grad_gap`` and hides a coarser precision; a large leaf's
  norm averages over many elements, and separates it.
- ``update_gap``: as ``grad_gap``, for the parameters' change over the
  checked steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).
- ``window_steps_wrong``: steps of the timed window that the trainer did
  not report, reported out of order, or with a non-finite loss.  Exact.
- ``rows_wrong``: checked steps whose rows, as the program's data
  pipeline fed them, are not the traffic generator's rows that the
  reference trains on.  Exact.

Each cell's file ``bench/workloads/<cell>.json`` names the numbers it
holds and their limits.
"""

from __future__ import annotations

import math
import statistics

ROUNDOFF_GRAD = 1e-3
LARGE_LEAF = 2 ** 16


def worst_leaf_gap(prog: dict, ref: dict, keep=None,
                   med=None) -> tuple[float, str]:
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))[:5]}")
    keys = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in keys) if med is None else med
    worst, at = 0.0, ""
    for k in keys:
        g = abs(prog[k] - ref[k]) / max(ref[k], med)
        if not math.isfinite(g) or g > worst:
            worst, at = (math.inf if not math.isfinite(g) else g), k
    return worst, at


def readings(prog: dict, ref: dict) -> dict:
    """prog / ref: {"losses": [...], "grad": {leaf: norm},
    "update": {leaf: norm}}; ref also {"sizes": {leaf: elements}}, prog
    also {"window_steps_wrong": int, "rows_wrong": int}."""
    gaps = [abs(p - r) / abs(r) if math.isfinite(p) else math.inf
            for p, r in zip(prog["losses"], ref["losses"], strict=True)]
    grad_gap, grad_at = worst_leaf_gap(prog["grad"], ref["grad"])
    med = statistics.median(ref["grad"].values())
    large = {k for k, n in ref["sizes"].items() if n >= LARGE_LEAF}
    large_gap, large_at = worst_leaf_gap(prog["grad"], ref["grad"],
                                         keep=large, med=med)
    moved = {k for k, g in ref["grad"].items() if g >= ROUNDOFF_GRAD * med}
    update_gap, update_at = worst_leaf_gap(prog["update"], ref["update"],
                                           keep=moved)
    return {"window_steps_wrong": prog["window_steps_wrong"],
            "rows_wrong": prog["rows_wrong"],
            "loss_gap": max(gaps), "grad_gap": grad_gap,
            "grad_gap_large": large_gap, "update_gap": update_gap,
            "_where": {"grad_gap": grad_at, "grad_gap_large": large_at,
                       "update_gap": update_at,
                       "left_out": sorted(set(ref["grad"]) - moved)}}


def decide(values: dict, limits: dict) -> tuple[bool, dict]:
    """Every limited number must be finite and at most its limit."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = values[name]
        good = isinstance(v, (int, float)) and math.isfinite(v) and v <= limit
        ok &= good
        checks[name] = {"value": v, "limit": limit}
    return ok, checks
