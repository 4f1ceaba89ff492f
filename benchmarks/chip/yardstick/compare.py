"""The numbers that decide ``correct``: what the timed path produced against the reference."""
from __future__ import annotations

import math

import numpy as np

from .reference import HIST_EDGES

FLOAT_FIELDS = ("resp_sum", "resp_sq", "resp_min", "resp_max", "comp_sum", "busy_sum",
                "saved_sum", "class_resp_sum")
HIST_FIELDS = ("hist", "class_hist")


def _rel(a, b, scale=None) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    s = np.maximum(np.abs(a), np.abs(b)) if scale is None else scale
    with np.errstate(invalid="ignore", divide="ignore"):
        rel = np.where(a == b, 0.0, np.abs(a - b) / s)
    return float(np.nan_to_num(rel, nan=np.inf).max())


def stream_numbers(got: dict, ref: dict) -> dict:
    """``stat_rel``: the widest relative gap of a float accumulator, over reps and classes
    (``saved_sum``, a difference of two sums, is scaled by the charged worker-seconds).
    ``hist_moved``: the share of jobs whose response lands in another histogram bin, or
    is missing, in the pooled or the per-class histogram."""
    rel = 0.0
    for f in FLOAT_FIELDS:
        scale = None
        if f == "saved_sum":
            scale = np.maximum(np.abs(np.asarray(ref["busy_sum"], np.float64)),
                               np.abs(np.asarray(got[f], np.float64)))
        rel = max(rel, _rel(got[f], ref[f], scale))
    total = max(int(np.asarray(ref["hist"]).sum()), 1)
    moved = max(
        0.5 * float(np.abs(np.asarray(got[f], np.int64) - np.asarray(ref[f], np.int64)).sum())
        / total
        for f in HIST_FIELDS
    )
    return {"stat_rel": rel, "hist_moved": moved}


def _bin(x: float) -> float:
    """Index of a histogram-quantile value on the edge grid (fractional off the grid)."""
    if not math.isfinite(x) or x <= 0:
        return math.inf
    step = math.log(HIST_EDGES[1] / HIST_EDGES[0])
    return math.log(x / HIST_EDGES[0]) / step


def _slo_regret(got: dict, ref: dict) -> float:
    """What the program's pick costs over the reference's, as a share of the latter;
    infinite where the reference finds the pick infeasible or finds one where it found none."""
    pick, best = got["best"], ref["best"]
    if pick is None or best is None:
        return 0.0 if pick == best else math.inf
    c = ref["candidates"][pick]
    if not c["feasible"]:
        return math.inf
    return max(0.0, _rel(c["cost"], ref["candidates"][best]["cost"])
               if c["cost"] > ref["candidates"][best]["cost"] else 0.0)


def slo_numbers(got: dict, ref: dict) -> dict:
    """``stat_rel``: the widest relative gap of a candidate's cost or mean response, or of
    the cost of the program's pick over the reference's pick.
    ``quantile_bins``: the most histogram bins by which a candidate's achieved
    quantile differs; a candidate missing from either side counts as infinitely far."""
    if set(got["candidates"]) != set(ref["candidates"]):
        return {"stat_rel": math.inf, "quantile_bins": math.inf}
    rel, bins = _slo_regret(got, ref), 0.0
    for key, r in ref["candidates"].items():
        g = got["candidates"][key]
        rel = max(rel, _rel(g["cost"], r["cost"]), _rel(g["mean"], r["mean"]))
        if g["achieved"] != r["achieved"]:
            bins = max(bins, abs(_bin(g["achieved"]) - _bin(r["achieved"])))
    return {"stat_rel": rel, "quantile_bins": bins}


def frontier_numbers(got: dict, ref: dict) -> dict:
    """``frontier_rel``: the widest relative gap of a candidate's mean or CoV of job time,
    or of the reference's mean at the program's pick over the reference's best mean."""
    rel = 0.0
    for k in ("mean", "cov"):
        for g, r in zip(got[k], ref[k], strict=True):
            rel = max(rel, _rel(g, r) if math.isfinite(g) or math.isfinite(r) else 0.0)
    means = dict(zip(ref["B"], ref["mean"], strict=True))
    best = min(means.values())
    return {"frontier_rel": max(rel, _rel(means[got["pick"]], best))}
