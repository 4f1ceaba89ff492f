"""Plain reference of the symmetric gang-pool stream: numpy, one job at a time.

Semantics (the system's stated contract for ``simulate_stream`` and the
tail-SLO planner): a cluster of ``gangs`` disjoint pools of ``b * r``
workers.  Jobs arrive in time order; each goes to the pool that frees
earliest (ties: the lowest index, or under ``balanced`` the least placed
load), waits until it is free, and runs ``b`` batches of ``r`` replicas.
A batch ends with its first replica, the job with its last batch.  The
pool is held until the job ends when redundant replicas are cancelled,
else until its last replica ends.  Charged worker-seconds are every
replica's full duration, or ``r`` times each batch's winner under
cancellation.

Nothing here imports the program.  Times are absolute, in ``dtype``
(float64 for the reference, bfloat16 for the control), with every
operation rounded to it.  Service draws follow the system's documented
draw law: rep ``s`` of a stream seeded ``seed`` draws row-major uniforms
from ``default_rng(SeedSequence((seed, version, s)))`` and inverts each
source job's empirical distribution.
"""
from __future__ import annotations

import math

import numpy as np

from .generate import STREAM_VERSION

HIST_EDGES = np.logspace(-3.0, 6.0, 128)
HIST_BINS = HIST_EDGES.size + 1


def control_dtype(stated: str):
    """The precision below the one a configuration states: the control's."""
    import ml_dtypes

    return {"float64": np.float32, "float32": ml_dtypes.bfloat16}[stated]


def ecdf_draws(seed: int, n_reps: int, sources: list, job_ids: np.ndarray, n_slots: int):
    """(n_reps, n_jobs, n_slots) float64 service draws by the documented draw law."""
    srt = [np.sort(np.asarray(s, np.float64)) for s in sources]
    sizes = np.array([s.size for s in srt])
    m = sizes[job_ids][:, None]
    out = np.empty((n_reps, job_ids.size, n_slots))
    for rep in range(n_reps):
        rng = np.random.default_rng(np.random.SeedSequence((int(seed), STREAM_VERSION, rep)))
        idx = np.minimum((rng.random((job_ids.size, n_slots)) * m).astype(np.int64), m - 1)
        for k, s in enumerate(srt):
            rows = job_ids == k
            out[rep, rows] = s[idx[rows]]
    return out


def gang_pools(arrivals, draws, scales, job_ids, n_classes, *, b, r, gangs, cancel, balanced,
               dtype=np.float64) -> dict:
    """Run the stream; return the per-rep accumulators as float64/int arrays."""
    dt = np.dtype(dtype)
    n_reps, n_jobs, _ = draws.shape
    rows = np.arange(n_reps)
    edges = HIST_EDGES.astype(dt).astype(np.float64)
    free = np.zeros((n_reps, gangs), dt)
    load = np.zeros((n_reps, gangs), dt)
    zero, r_dt = dt.type(0), dt.type(r)
    count = np.zeros(n_reps, np.int64)
    sums = {k: np.zeros(n_reps, dt) for k in ("resp_sum", "resp_sq", "comp_sum", "busy_sum",
                                              "saved_sum")}
    resp_min = np.full(n_reps, np.inf, dt)
    resp_max = np.full(n_reps, -np.inf, dt)
    hist = np.zeros((n_reps, HIST_BINS), np.int64)
    class_count = np.zeros((n_reps, n_classes), np.int64)
    class_resp_sum = np.zeros((n_reps, n_classes), dt)
    class_hist = np.zeros((n_reps, n_classes, HIST_BINS), np.int64)
    d_all = draws.astype(dt)
    for j in range(n_jobs):
        a = dt.type(arrivals[j])
        d = (d_all[:, j, :] * dt.type(scales[j])).reshape(n_reps, b, r)
        bmin = d.min(axis=-1)
        t = bmin.max(axis=-1)
        planned = np.zeros(n_reps, dt)
        for k in range(b * r):
            planned = planned + d.reshape(n_reps, -1)[:, k]
        if cancel:
            hold, won = t, np.zeros(n_reps, dt)
            for k in range(b):
                won = won + bmin[:, k]
            busy = r_dt * won
        else:
            hold, busy = d.max(axis=(-2, -1)), planned
        fmin = free.min(axis=1)
        if balanced:
            g = np.argmin(np.where(free == fmin[:, None], load.astype(np.float64), np.inf), axis=1)
        else:
            g = np.argmin(free, axis=1)
        wait = np.maximum(fmin - a, zero)
        resp = wait + t
        free[rows, g] = a + wait + hold
        load[rows, g] = load[rows, g] + planned
        count += 1
        sums["resp_sum"] = sums["resp_sum"] + resp
        sums["resp_sq"] = sums["resp_sq"] + resp * resp
        sums["comp_sum"] = sums["comp_sum"] + t
        sums["busy_sum"] = sums["busy_sum"] + busy
        sums["saved_sum"] = sums["saved_sum"] + (planned - busy)
        resp_min = np.minimum(resp_min, resp)
        resp_max = np.maximum(resp_max, resp)
        bins = np.searchsorted(edges, resp.astype(np.float64), side="right")
        c = job_ids[j]
        hist[rows, bins] += 1
        class_count[:, c] += 1
        class_resp_sum[:, c] = class_resp_sum[:, c] + resp
        class_hist[rows, c, bins] += 1
    out = {k: v.astype(np.float64) for k, v in sums.items()}
    out.update(
        count=count, resp_min=resp_min.astype(np.float64), resp_max=resp_max.astype(np.float64),
        hist=hist, class_count=class_count, class_resp_sum=class_resp_sum.astype(np.float64),
        class_hist=class_hist,
    )
    return out


def quantile(stats: dict, q: float) -> float:
    """Pooled histogram quantile: the upper edge of the bin holding the k-th response."""
    h = stats["hist"].sum(axis=0)
    total = int(h.sum())
    k = max(int(math.ceil(q * total)), 1)
    idx = int(np.searchsorted(np.cumsum(h), k))
    return float(stats["resp_max"].max()) if idx >= HIST_EDGES.size else float(HIST_EDGES[idx])


# --------------------------------------------------------------------------
# the tail-SLO planner's semantics
# --------------------------------------------------------------------------


def pareto_samples(seed: int, sigma: float, alpha: float, n: int, index: int = 0) -> np.ndarray:
    """The planner's trace stand-in for a Pareto service law: ``n`` seeded draws."""
    rng = np.random.default_rng(np.random.SeedSequence((int(seed), 0x510, index)))
    u = rng.uniform(low=np.finfo(np.float64).tiny, high=1.0, size=n)
    return sigma * u ** (-1.0 / alpha)


def poisson_arrivals(seed: int, rate: float, n_jobs: int, n_sources: int):
    """Exponential gaps at ``rate`` and a uniform source per arrival."""
    rng = np.random.default_rng(np.random.SeedSequence((int(seed), STREAM_VERSION, 0x510)))
    arrivals = np.cumsum(rng.exponential(scale=1.0 / rate, size=n_jobs))
    return arrivals, rng.integers(0, n_sources, size=n_jobs)


def divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def slo_grid(n_workers: int, schedulers) -> list[tuple]:
    """(scheduler, pool width or None, B): B over the whole cluster, or per pool width."""
    grid = []
    for sched in schedulers:
        if sched == "fifo_gang":
            grid += [(sched, None, b) for b in divisors(n_workers)]
        else:
            for w in divisors(n_workers)[:-1]:
                grid += [(sched, w, b) for b in divisors(w)]
    return grid


def plan_slo(seed: int, *, n_workers, sigma, alpha, rate, quantile_q, target_s, n_jobs, n_reps,
             schedulers, size_dependent, cancel, dtype=np.float64) -> dict:
    """Every candidate's (achieved quantile, cost, mean response, feasible), and the pick."""
    samples = pareto_samples(seed, sigma, alpha, 4000)
    arrivals, job_ids = poisson_arrivals(seed, rate, n_jobs, 1)
    scales_n = float(samples.size)
    out = {}
    for sched, w, b in slo_grid(n_workers, schedulers):
        pool = n_workers if w is None else w
        r = pool // b
        draws = ecdf_draws(seed, n_reps, [samples], job_ids, b * r)
        scales = np.full(n_jobs, scales_n / b if size_dependent else 1.0)
        st = gang_pools(arrivals, draws, scales, job_ids, 1, b=b, r=r,
                        gangs=n_workers // pool, cancel=cancel,
                        balanced=sched == "balanced", dtype=dtype)
        achieved = quantile(st, quantile_q)
        out[(sched, w, b)] = {
            "achieved": achieved,
            "cost": float(st["busy_sum"].mean()),
            "mean": float(st["resp_sum"].sum() / max(int(st["count"].sum()), 1)),
            "feasible": achieved <= target_s,
        }
    order = sorted(out, key=lambda k: (not out[k]["feasible"], out[k]["cost"], out[k]["mean"]))
    return {"candidates": out, "best": order[0] if out[order[0]]["feasible"] else None}
