"""Plain reference of the churned gang cluster that ``plan_cluster`` scores: one lane at a time.

Semantics (the system's stated contract for the dynamic planner): a
stream of jobs, all queued at time 0, runs on ``n`` workers that fail and
rejoin.  A job is gang-dispatched once the cluster is free: the first
``B * r`` alive workers in id order, ``r = alive // B``; the worker of
alive-rank ``k`` runs a replica of batch ``k mod B`` that takes the job's
``k``-th draw times ``n_tasks / B`` over its speed.  A batch ends with its
first replica; the job ends with its last batch; without cancellation the
cluster is free once every replica has ended.  A failure kills the
worker's replicas; a batch left with none is rescued onto the alive
worker that frees first (its current replicas' end, or now), drawing the
next rescue draw, oldest request first.  Churn stops once the last job is
recorded.  Times are absolute, every operation rounded to ``dtype``.

Draws follow the system's documented per-lane law: lane ``i`` of a plan
seeded ``seed`` draws from ``default_rng(SeedSequence((seed, i)))`` the
service draws, then the rescue draws, then each worker's alternating
up/down times.  One stream feeds all three, so which number a job or a
churn event gets depends on the shapes the system draws at, padding
included: :func:`draw_layout` states them.  A change to the program that
draws at other shapes (another job, worker or rescue-buffer size, or a
separate stream per kind of draw) changes every plan a seed gives, and the
reference has to move with it.  Nothing here imports the program.
"""
from __future__ import annotations

import math

import numpy as np

INF = math.inf


def pareto(rng, sigma: float, alpha: float, shape) -> np.ndarray:
    u = rng.uniform(low=np.finfo(np.float64).tiny, high=1.0, size=shape)
    return sigma * u ** (-1.0 / alpha)


def lane_draws(seed: int, lane: int, *, sigma, alpha, jobs_pad, n_pad, resc_cap, n_workers,
               fail_rate, mean_downtime, pairs):
    """(service draws, rescue draws, churn events as (time, worker, up) in time order)."""
    rng = np.random.default_rng(np.random.SeedSequence((int(seed), int(lane))))
    tau = pareto(rng, sigma, alpha, (jobs_pad, n_pad))
    tau_resc = pareto(rng, sigma, alpha, (resc_cap, n_pad))
    ups = rng.exponential(1.0 / fail_rate, (n_workers, pairs))
    downs = rng.exponential(mean_downtime, (n_workers, pairs))
    t = np.cumsum(np.stack([ups, downs], axis=-1).reshape(n_workers, 2 * pairs), axis=-1)
    events = sorted(
        ((t[w, k], w, k % 2 == 1) for w in range(n_workers) for k in range(2 * pairs)),
        key=lambda ev: ev[0],
    )
    return tau, tau_resc, events


def run_lane(tau, tau_resc, events, *, n_workers, b0, speeds, n_tasks, n_jobs, max_steps,
             rnd=float) -> tuple[np.ndarray, np.ndarray]:
    """One lane's (starts, finishes); unfinished jobs stay at inf.  ``rnd`` rounds a value
    to the lane's precision; a lane stops after ``max_steps`` actions (rescue, dispatch or
    event), as the system's lanes do."""
    n = n_workers
    alive = [True] * n
    # replica slots: gang slot w runs on worker w; rescue slot n + b rescues batch b
    rp_batch = [0] * n + list(range(n))
    rp_worker = list(range(n)) + [0] * n
    rp_start, rp_end, rp_live = [0.0] * (2 * n), [INF] * (2 * n), [False] * (2 * n)
    done, done_t = [True] * n, [-INF] * n
    pending, pend_t = [False] * n, [INF] * n
    resc_k, cursor, e, q = 0, 0.0, 0, 0
    active, job_b, qa = False, 1, 0
    starts, fins = np.full(n_jobs, INF), np.full(n_jobs, INF)
    speeds = [rnd(s) for s in speeds]
    n_tasks = rnd(n_tasks)

    def scale(b):
        return rnd(n_tasks / rnd(b))

    events = [(rnd(t), w, up) for t, w, up in events]
    for _ in range(max_steps):
        if q >= n_jobs and not active:
            break
        t_next = events[e][0] if e < len(events) else INF
        # a pending rescue goes first, onto the alive worker that frees first
        if active and any(pending):
            free = []
            for w in range(n):
                ends = [rp_end[s] for s in range(2 * n) if rp_live[s] and rp_worker[s] == w]
                f = max(max(ends, default=-INF), cursor) if alive[w] else INF
                free.append(f if f <= t_next else INF)
            w = min(range(n), key=lambda i: (free[i], i))
            if free[w] < INF:
                b = min((i for i in range(n) if pending[i]), key=lambda i: (pend_t[i], i))
                k = min(resc_k, len(tau_resc) - 1)
                dur = rnd(rnd(rnd(tau_resc[k][b]) * scale(job_b)) / speeds[w])
                s = n + b
                rp_worker[s], rp_start[s], rp_end[s], rp_live[s] = w, free[w], rnd(free[w] + dur), True
                pending[b] = False
                resc_k += 1
                continue
        # commit every replica and batch that ends by the next churn event
        win = [INF] * n
        for s in range(2 * n):
            if rp_live[s]:
                win[rp_batch[s]] = min(win[rp_batch[s]], rp_end[s])
        t_new = -INF
        for s in range(2 * n):
            if rp_live[s] and rp_end[s] <= t_next:
                rp_live[s] = False
                t_new = max(t_new, rp_end[s])
        for b in range(n):
            if not done[b] and win[b] <= t_next:
                done[b], done_t[b] = True, win[b]
        if active and all(done):
            fin = max(done_t[:job_b])
            fins[qa] = fin
            cursor = max(cursor, t_new, fin)
            active = False
            pending = [False] * n
        else:
            cursor = max(cursor, t_new)
        # gang-dispatch the next job once the cluster is free
        n_alive = sum(alive)
        if not active and q < n_jobs and n_alive and not any(rp_live) and cursor < t_next:
            b = min(max(b0 if b0 > 0 else n_alive, 1), n_alive)
            r = n_alive // b
            rank = 0
            for w in range(n):
                rp_live[w] = False
                if alive[w] and rank < b * r:
                    dur = rnd(rnd(rnd(tau[q][rank]) * scale(b)) / speeds[w])
                    rp_batch[w], rp_start[w], rp_end[w], rp_live[w] = rank % b, cursor, rnd(cursor + dur), True
                if alive[w]:
                    rank += 1
            for i in range(n):
                rp_live[n + i] = False
                done[i], done_t[i] = i >= b, (-INF if i >= b else INF)
            active, job_b, qa = True, b, q
            starts[q] = cursor
            q += 1
            continue
        # otherwise apply the next churn event (none once every job is recorded)
        if e < len(events) and (q < n_jobs or active):
            t_ev, w, up = events[e]
            if not up and alive[w]:
                alive[w] = False
                hit = set()
                for s in range(2 * n):
                    if rp_live[s] and rp_worker[s] == w:
                        rp_live[s] = False
                        hit.add(rp_batch[s])
                for b in hit:
                    if not done[b] and not any(rp_live[s] and rp_batch[s] == b for s in range(2 * n)):
                        pending[b], pend_t[b] = True, t_ev
            elif up and not alive[w]:
                alive[w] = True
            cursor = max(cursor, t_ev, 0.0)
        e += 1
    return starts, fins


def _pow2(x: int) -> int:
    return 1 << (max(int(x), 1) - 1).bit_length()


def draw_layout(n_workers: int, n_jobs: int, pairs: int) -> tuple[int, int, int, int]:
    """The shapes a lane draws at, and its step budget: the contract with the program.

    Service draws are ``(jobs_pad, n_pad)``: jobs padded to a multiple of 32
    (a power of two under 32), workers to a multiple of 4; job ``q``'s
    ``k``-th draw is element ``q * n_pad + k`` of the stream.  Rescue draws
    follow, ``(resc_cap, n_pad)`` with ``resc_cap`` half the churn events
    padded to a power of two (at least 8); the churn times come after them.
    A lane stops after ``max_steps`` actions, the budget rounded up to 16s.
    For ``pareto16`` (16 workers, 96 jobs, 128 pairs): (96, 16, 4096, 12400).
    """
    jobs_pad = _pow2(n_jobs) if n_jobs < 32 else -(-n_jobs // 32) * 32
    n_pad = max(4, -(-n_workers // 4) * 4)
    ev_pad = _pow2(2 * pairs * n_workers + 1)
    resc_cap = max(8, ev_pad // 2)
    max_steps = -(-(jobs_pad + ev_pad + resc_cap + 2) // 16) * 16
    return jobs_pad, n_pad, resc_cap, max_steps


def plan_cluster(seed: int, *, n_workers, candidates, n_reps, jobs_per_stream, sigma, alpha,
                 speeds, fail_rate, mean_downtime, pairs, width=None, rnd=float) -> dict:
    """Every candidate's frontier (mean and CoV of the finished jobs' compute times) and
    the mean-optimal B, from streams of ``jobs_per_stream`` jobs: serial gangs, or with
    ``width`` jobs packed side by side on ``width`` workers each.  A batch's work is
    ``n_workers / B`` tasks (the size-dependent batch law)."""
    n_jobs = max(1, min(int(jobs_per_stream), int(n_reps)))
    streams = math.ceil(n_reps / n_jobs)
    jobs_pad, n_pad, resc_cap, max_steps = draw_layout(n_workers, n_jobs, pairs)
    means, covs = [], []
    for ci, b in enumerate(candidates):
        times = []
        for rep in range(streams):
            tau, tau_resc, events = lane_draws(
                seed, ci * streams + rep, sigma=sigma, alpha=alpha, jobs_pad=jobs_pad,
                n_pad=n_pad, resc_cap=resc_cap, n_workers=n_workers, fail_rate=fail_rate,
                mean_downtime=mean_downtime, pairs=pairs)
            kw = dict(n_workers=n_workers, b0=b, speeds=speeds, n_tasks=n_workers,
                      n_jobs=n_jobs, max_steps=max_steps, rnd=rnd)
            if width is None:
                st, fin = run_lane(tau, tau_resc, events, **kw)
            else:
                st, fin = run_space_lane(tau, tau_resc, events, width=width, **kw)
            with np.errstate(invalid="ignore"):
                times.append(np.where(np.isfinite(fin), fin - st, INF))
        t = np.concatenate(times)
        t = t[np.isfinite(t)]
        m = float(t.mean()) if t.size else INF
        means.append(m)
        covs.append(float(t.std() / m) if t.size and m > 0 else INF)
    return {"B": [int(b) for b in candidates], "pick": int(candidates[int(np.argmin(means))]),
            "mean": means, "cov": covs}


def run_space_lane(tau, tau_resc, events, *, n_workers, b0, width, speeds, n_tasks, n_jobs,
                   max_steps, rnd=float) -> tuple[np.ndarray, np.ndarray]:
    """One lane of the packed space-sharing cluster: (starts, finishes).

    Jobs, all queued at time 0, each ask for ``width`` workers and run
    concurrently on disjoint ones.  The queued job that can start first
    (its ``width``-th free worker's availability, ties by queue order) takes
    the lowest-id free workers available by then; the worker of rank ``k``
    runs a replica of batch ``k mod B`` on the job's ``k``-th draw.  A
    batch is a segment that ends with its first replica; a job ends with
    its last segment and releases its workers, which are free again once
    their own replicas have ended.  A failure kills the worker's replicas
    and takes it out of its job; a segment left with no replica is rescued
    at the later of the failure and the earliest availability of a worker
    of its job or an unallocated one (the job's own first, then the lowest
    id), oldest request first.  Churn stops once every job is recorded.
    """
    n, J = n_workers, n_jobs
    alive = [True] * n
    w_job, w_avail = [J] * n, [0.0] * n
    g_s, rb_w = [n] * n, [0] * n
    rp_live, rp_start, rp_end = [False] * (2 * n), [0.0] * (2 * n), [INF] * (2 * n)
    seg_job, pending, pend_t = [J] * n, [False] * n, [INF] * n
    resc_k, t_epoch, e, n_done = 0, 0.0, 0, 0
    dispatched, recorded = [False] * J, [False] * J
    job_left, job_b, job_fin = [0] * J, [1] * J, [-INF] * J
    starts, fins = np.full(J, INF), np.full(J, INF)
    speeds = [rnd(s) for s in speeds]
    n_tasks = rnd(n_tasks)
    events = [(rnd(t), w, up) for t, w, up in events]

    def slot_seg(s):
        return g_s[s] if s < n else s - n

    def slot_worker(s):
        return s if s < n else rb_w[s - n]

    for _ in range(max_steps):
        if n_done >= J:
            break
        t_next = events[e][0] if e < len(events) else INF
        # commit segment wins and replica ends up to the next churn event
        win = [INF] * n
        for s in range(2 * n):
            if rp_live[s] and slot_seg(s) < n:
                win[slot_seg(s)] = min(win[slot_seg(s)], rp_end[s])
        newly = [seg_job[g] < J and win[g] <= t_next for g in range(n)]
        for s in range(2 * n):
            if rp_live[s] and rp_end[s] <= t_next:
                rp_live[s] = False
        for w in range(n):
            if not rp_live[w] or (g_s[w] < n and newly[g_s[w]]):
                g_s[w] = n
        for g in range(n):
            if newly[g]:
                j = seg_job[g]
                job_left[j] -= 1
                job_fin[j] = max(job_fin[j], win[g])
                seg_job[g], pending[g] = J, False
        for j in range(J):
            if dispatched[j] and job_left[j] == 0 and not recorded[j]:
                fins[j], recorded[j] = job_fin[j], True
                n_done += 1
                for w in range(n):
                    if w_job[w] == j:
                        w_avail[w], w_job[w] = max(w_avail[w], job_fin[j]), J
        # a pending rescue: the segment that can be served first, oldest first
        serve = [INF] * n
        for g in range(n):
            if pending[g]:
                avail = [w_avail[w] for w in range(n)
                         if alive[w] and w_job[w] in (J, seg_job[g])]
                serve[g] = max(pend_t[g], min(avail, default=INF))
        g = min(range(n), key=lambda i: (serve[i], pend_t[i] if pending[i] else INF, i))
        if pending[g] and serve[g] < INF and serve[g] <= t_next:
            t, j = serve[g], seg_job[g]
            cand = [w for w in range(n) if alive[w] and w_avail[w] <= t and w_job[w] in (J, j)]
            w = min(cand, key=lambda i: (w_job[i] != j, i))
            dur = rnd(rnd(rnd(tau_resc[min(resc_k, len(tau_resc) - 1)][g])
                          * rnd(n_tasks / rnd(max(job_b[j], 1)))) / speeds[w])
            rb_w[g] = w
            rp_start[n + g], rp_end[n + g], rp_live[n + g] = t, rnd(t + dur), True
            pending[g], w_job[w], w_avail[w] = False, j, rnd(t + dur)
            resc_k += 1
            continue
        # dispatch: the queued job that can start first, on the lowest-id free workers
        n_alive = sum(alive)
        free = sorted(w_avail[w] for w in range(n) if alive[w] and w_job[w] == J)
        req = min(max(width, 1), max(n_alive, 1))
        kth = free[req - 1] if len(free) >= req else INF
        b = min(max(b0 if b0 > 0 else req, 1), req)
        seg_free = [g for g in range(n) if seg_job[g] == J]
        td, q = INF, J
        if n_alive and b <= len(seg_free):
            for j in range(J):
                if not dispatched[j]:
                    td, q = max(0.0, kth, t_epoch), j
                    break
        if td < t_next:
            r = req // b
            ws = [w for w in range(n) if alive[w] and w_job[w] == J and w_avail[w] <= td]
            for rank, w in enumerate(ws[:req]):
                w_job[w] = q
                w_avail[w] = td
                if rank < b * r:
                    dur = rnd(rnd(rnd(tau[q][rank]) * rnd(n_tasks / rnd(b))) / speeds[w])
                    g_s[w] = seg_free[rank % b]
                    rp_live[w], rp_start[w], rp_end[w] = True, td, rnd(td + dur)
                    w_avail[w] = rnd(td + dur)
            for g in seg_free[:b]:
                seg_job[g] = q
            starts[q], dispatched[q], job_left[q], job_b[q] = td, True, b, b
            continue
        # otherwise apply the next churn event (none once every job is recorded)
        if e < len(events):
            t_ev, w, up = events[e]
            if not up and alive[w]:
                alive[w] = False
                hit = set()
                for s in range(2 * n):
                    if rp_live[s] and slot_worker(s) == w:
                        rp_live[s] = False
                        hit.add(slot_seg(s))
                for g in hit:
                    if g < n and seg_job[g] < J and not any(
                            rp_live[s] and slot_seg(s) == g for s in range(2 * n)):
                        pending[g], pend_t[g] = True, t_ev
                g_s[w], w_job[w], w_avail[w] = n, J, INF
            elif up and not alive[w]:
                alive[w], w_job[w], w_avail[w] = True, J, t_ev
            t_epoch = max(t_epoch, t_ev, 0.0)
        e += 1
    return starts, fins
