"""Plain reference of the hedged fan-out cluster that ``plan_cluster`` scores: one lane at a time.

Semantics (the system's stated contract for speculative backups under the
packed space-sharing scheduler, with cancellation and no churn): a stream
of jobs, all queued at time 0, runs on ``n`` workers of fixed speeds.  A
queued job starts, first come first served, as soon as ``width`` workers
are free and unallocated; it takes the lowest-id ones.  With ``B`` batches
and ``r = width // B`` replicas, the worker of rank ``k`` runs a replica
of batch ``k mod B`` on the job's ``k``-th draw times ``n_tasks / B`` over
its speed; the rest of the allocation idles.  A batch ends with its first
replica, which cancels the others; the job ends with its last batch and
frees its allocation.

Hedging (MapReduce backup tasks): the durations of a job's finished
batches are its observations.  Once it has ``min_observations`` of them
and fewer than ``max_backups`` backups, a batch whose youngest replica
started at ``y`` lags once ``now - y > theta x m``, ``m`` the lower median
of the observations.  Checks fall on the heartbeat grid ``k x interval``:
the next one is the first grid point strictly after both ``y + theta x m``
(the earliest such crossing of any job with a worker at hand) and the
last event.  A check backs up the first lagging batch (jobs, then batches,
in order) on the job's lowest-id idle worker, else on the lowest-id idle
unallocated worker, which joins the job's allocation; one backup a check.
The backup races the batch's replicas under the same first-wins rule.  A
completion and a check at the same instant take the completion first.

Draws follow the system's documented per-lane law: lane ``i`` of a plan
seeded ``seed`` draws from ``default_rng(SeedSequence((seed, i)))`` the
service draws, then (no churn, so no rescue draws) the backup draws, the
``k``-th backup of the lane on worker ``w`` taking row ``k``, column ``w``;
:func:`draw_layout` states the shapes.  Times are absolute, every
operation rounded to the lane's precision.  Nothing here imports the
program.
"""
from __future__ import annotations

import bisect
import heapq
import math

import numpy as np

from .epoch_reference import _pow2, pareto

INF = math.inf


def draw_layout(n_workers: int, n_jobs: int, max_backups: int) -> tuple[int, int, int]:
    """``(jobs_pad, n_pad, spec_cap)``: the shapes a lane draws at, the contract with the
    program.  Service draws are ``(jobs_pad, n_pad)`` (jobs padded to a multiple of 32, a
    power of two under 32; workers to a multiple of 4); backup draws follow,
    ``(spec_cap, n_pad)`` with one row per possible backup, ``jobs_pad x max_backups``.
    For ``fanout100_hedged`` (200 workers, 96 jobs, 2 backups): (96, 200, 192)."""
    jobs_pad = _pow2(n_jobs) if n_jobs < 32 else -(-n_jobs // 32) * 32
    n_pad = max(4, -(-n_workers // 4) * 4)
    return jobs_pad, n_pad, jobs_pad * max_backups


def lane_draws(seed: int, lane: int, *, sigma, alpha, jobs_pad, n_pad, spec_cap):
    """(service draws, backup draws) of one lane."""
    rng = np.random.default_rng(np.random.SeedSequence((int(seed), int(lane))))
    tau = pareto(rng, sigma, alpha, (jobs_pad, n_pad))
    tau_spec = pareto(rng, sigma, alpha, (spec_cap, n_pad))
    return tau, tau_spec


def run_lane(tau, tau_spec, *, n_workers, width, b0, speeds, n_tasks, n_jobs, theta, interval,
             min_observations, max_backups, rnd=float) -> tuple[np.ndarray, np.ndarray, int]:
    """One lane's (starts, finishes, backups launched)."""
    n = n_workers
    speeds = [rnd(s) for s in speeds]
    n_tasks, th, iv = rnd(n_tasks), rnd(theta), rnd(interval)
    starts, fins = np.full(n_jobs, INF), np.full(n_jobs, INF)
    owner = [None] * n  # the job a worker is allocated to; unallocated workers idle
    busy = [False] * n  # runs a live replica
    alloc: dict = {}  # job -> its workers
    idle: dict = {}  # job -> how many of its workers idle
    live: dict = {}  # (job, batch) -> [(worker, start)] of its live replicas
    obs: dict = {}  # job -> sorted durations of its finished batches
    used: dict = {}  # job -> backups launched
    left: dict = {}  # job -> batches not finished
    n_b: dict = {}  # job -> B
    young: dict = {}  # job -> heap of (a replica's start, batch); stale entries skipped
    ends: list = []  # heap of (end, placement order, job, batch, start)
    order = [0]
    queue = list(range(n_jobs))
    n_free, k_spec, now = n, 0, 0.0

    def scale(b):
        return rnd(n_tasks / rnd(b))

    def place(w, j, b, t, draw):
        dur = rnd(rnd(rnd(draw) * scale(n_b[j])) / speeds[w])
        busy[w] = True
        live[(j, b)].append((w, t))
        heapq.heappush(ends, (rnd(t + dur), order[0], j, b, t))
        heapq.heappush(young[j], (t, b))
        order[0] += 1

    def dispatch(t):
        """Start queued jobs while ``width`` unallocated workers are free (only a job's
        end frees such workers here: no churn, and a backup takes an idle one)."""
        nonlocal n_free
        req = min(max(width, 1), n)
        while queue and n_free >= req:
            j = queue.pop(0)
            b = min(max(b0, 1), req)
            r = req // b
            n_b[j], left[j], obs[j], used[j], young[j] = b, b, [], 0, []
            alloc[j] = [w for w in range(n) if owner[w] is None][:req]
            idle[j] = req - b * r
            n_free -= req
            for bb in range(b):
                live[(j, bb)] = []
            for k, w in enumerate(alloc[j]):
                owner[w] = j
                if k < b * r:
                    place(w, j, k % b, t, tau[j][k])
            starts[j] = t

    def youngest(j):
        """The earliest start, over the job's unfinished batches, of a batch's youngest
        replica: the job's earliest crossing is there."""
        h = young[j]
        while h:
            y, b = h[0]
            reps = live.get((j, b))
            if reps and max(s for _, s in reps) == y:
                return y
            heapq.heappop(h)
        return None

    def median(j):
        o = obs[j]
        return o[(len(o) - 1) // 2] if len(o) >= min_observations else None

    def next_check():
        best = INF
        for j in sorted(alloc):
            m = median(j)
            if used[j] >= max_backups or m is None or (idle[j] == 0 and n_free == 0):
                continue
            y = youngest(j)
            if y is None:
                continue
            k = max(math.floor(rnd(rnd(y + rnd(th * m)) / iv)), math.floor(rnd(now / iv))) + 1
            best = min(best, rnd(k * iv))
        return best

    def check(t):
        nonlocal k_spec, n_free
        for j in sorted(alloc):
            m = median(j)
            if used[j] >= max_backups or m is None:
                continue
            for b in range(n_b[j]):
                reps = live.get((j, b))
                if not reps or rnd(t - max(s for _, s in reps)) <= rnd(th * m):
                    continue
                if idle[j]:
                    w = min(w for w in alloc[j] if not busy[w])
                    idle[j] -= 1
                elif n_free:
                    w = min(w for w in range(n) if owner[w] is None)
                    owner[w] = j
                    alloc[j].append(w)
                    n_free -= 1
                else:
                    break
                place(w, j, b, t, tau_spec[min(k_spec, len(tau_spec) - 1)][w])
                used[j] += 1
                k_spec += 1
                return

    dispatch(0.0)
    while ends:
        if (ends[0][2], ends[0][3]) not in live:
            heapq.heappop(ends)  # cancelled: its batch already finished
            continue
        t_c = ends[0][0]
        t_s = next_check()
        # a check lies strictly after the last event; where the precision rounds the
        # grid point onto it (the control's bfloat16 at large times), none can be made
        if now < t_s < t_c:
            now = t_s
            check(t_s)
            continue
        t, _, j, b, s = heapq.heappop(ends)
        now = t
        bisect.insort(obs[j], rnd(t - s))
        reps = live.pop((j, b))
        for w, _ in reps:
            busy[w] = False
        idle[j] += len(reps)
        left[j] -= 1
        if left[j] == 0:  # every replica of the job has ended: its allocation is idle
            fins[j] = t
            for w in alloc.pop(j):
                owner[w] = None
            n_free += idle.pop(j)
            dispatch(t)
    return starts, fins, k_spec


def plan_cluster(seed: int, *, n_workers, candidates, n_reps, jobs_per_stream, sigma, alpha,
                 speeds, width, n_tasks, theta, interval, min_observations, max_backups,
                 rnd=float) -> dict:
    """Every candidate's frontier (mean and CoV of the finished jobs' compute times) and
    the mean-optimal B, from streams of ``jobs_per_stream`` jobs packed ``width`` workers
    each, with hedging."""
    n_jobs = max(1, min(int(jobs_per_stream), int(n_reps)))
    streams = math.ceil(n_reps / n_jobs)
    jobs_pad, n_pad, spec_cap = draw_layout(n_workers, n_jobs, max_backups)
    means, covs = [], []
    for ci, b in enumerate(candidates):
        times = []
        for rep in range(streams):
            tau, tau_spec = lane_draws(seed, ci * streams + rep, sigma=sigma, alpha=alpha,
                                       jobs_pad=jobs_pad, n_pad=n_pad, spec_cap=spec_cap)
            st, fin, _ = run_lane(
                tau, tau_spec, n_workers=n_workers, width=width, b0=b, speeds=speeds,
                n_tasks=n_tasks, n_jobs=n_jobs, theta=theta, interval=interval,
                min_observations=min_observations, max_backups=max_backups, rnd=rnd)
            with np.errstate(invalid="ignore"):
                times.append(np.where(np.isfinite(fin), fin - st, INF))
        t = np.concatenate(times)
        t = t[np.isfinite(t)]
        m = float(t.mean()) if t.size else INF
        means.append(m)
        covs.append(float(t.std() / m) if t.size and m > 0 else INF)
    return {"B": [int(b) for b in candidates], "pick": int(candidates[int(np.argmin(means))]),
            "mean": means, "cov": covs}
