"""Cluster-engine benchmark: §VII dynamics the closed forms cannot express.

Six scenarios on the synthetic Google-trace jobs (and parametric tails):

  * ``redundancy``   -- per trace job, engine mean compute time at B = N (no
    redundancy) vs the planned B*: reproduces the §VII observation that
    planned redundancy speeds heavy-tail jobs up by about an order of
    magnitude.
  * ``queueing``     -- Poisson multi-job arrivals: mean response time with
    and without planned redundancy (the queueing cost/benefit).
  * ``cancellation`` -- replica cancellation on/off: worker-seconds burned,
    seconds reclaimed, response-time delta.
  * ``churn``        -- worker fail/join churn on/off: failures, rescues,
    compute-time delta.
  * ``backend``      -- wall-clock of a full-frontier ``plan_cluster`` sweep
    on the Python event engine vs the vectorized jax backend
    (``repro.cluster.vectorized``): the speedup that makes thousand-candidate
    sweeps and per-window replanning affordable.  The CI regression gate
    (``benchmarks/check_bench_regression.py``) consumes this section.
  * ``dynamic``      -- the same full-frontier sweep under fail/join churn and
    heterogeneous worker speeds, scored by the Python event engine vs the jax
    epoch-scan step loop (``repro.cluster.epoch_scan``): the sweep regime that
    used to fall back to Python entirely.  Records warm speed edge (min-of-3),
    per-dist cold compile+run seconds, and the process peak-RSS column; the
    regression gate keys on the warm edge *and* the cold seconds.
  * ``speculation``  -- planned (proactive) vs speculative (reactive) vs
    hybrid redundancy across Exp/SExp/Pareto and the heavy trace job:
    mean/p95 compute time, worker-seconds, and backup counts per variant.
    The regression gate keys on the Pareto row (reactive backups must keep
    beating the no-redundancy baseline).
  * ``trace_scale``  -- a 10k-job synthetic cluster-day streamed through the
    O(slab)-memory jax path (``repro.cluster.stream``): the full
    (family x budget x scheduler) grid, gated on whole-grid warm wall time
    (single-digit seconds) and process peak RSS (the streaming-aggregation
    memory ceiling).
  * ``slo``          -- tail-SLO planning (``RedundancyPlanner.plan_slo``):
    cheapest feasible (B, r, scheduler) meeting a p99 response target under
    Poisson arrivals, per parametric tail family, on the streaming-quantile
    kernel.  Records the cheapest-feasible vs mean-optimal candidates and
    whether they differ; the regression gate keys on the Pareto row keeping
    the mean-optimal != tail-optimal divergence alive and on all families
    staying feasible.
  * ``space_sharing`` -- the space-sharing scheduler: mean response-time
    ratio of ``packed`` (narrow concurrent jobs on disjoint subsets) vs the
    ``fifo_gang`` baseline on one saturated workload, plus the jax-vs-python
    warm edge on a space-shared full-frontier ``plan_cluster`` sweep (the
    space lane of ``repro.cluster.epoch_scan`` vs the per-candidate Python
    engine).  The regression gate keys on both: packed must keep beating
    the gang, and the space lane must keep its speed edge.

``--smoke`` shrinks every sample count so the whole file runs in seconds --
CI executes it on every PR, gates on the JSON against the committed
``BENCH_cluster.json`` baseline, and uploads the artifact.  ``--backend``
selects which engine scores the ``redundancy`` scenario (the nightly job
runs ``--backend both``).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import resource
import sys
import time

import jax
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from repro.cluster import (
    ChurnProcess,
    ClusterEngine,
    Job,
    Scenario,
    jobs_from_traces,
    sample_job_times,
    simulate_fifo,
)
from repro.compile_cache import enable_compile_cache
from repro.core import traces
from repro.core.planner import RedundancyPlanner
from repro.core.service_time import Empirical, Exponential, Pareto, ShiftedExponential

ART = pathlib.Path(__file__).resolve().parent / "artifacts" / "cluster"


def _cfg(smoke: bool) -> dict:
    if smoke:
        return {
            "n_workers": 10,
            "n_reps": 60,
            "n_jobs": 6,
            "trace_jobs": 4,
            "backend_workers": 24,
            "backend_reps": 800,
            "dyn_workers": 12,
            "dyn_reps": 960,
            "space_workers": 12,
            "space_reps": 768,
            # the trace section streams the REAL 10k-job day even in smoke:
            # the whole grid is ~2s warm, and the acceptance gate is about
            # the full-scale stream, not a toy one.  Smoke only shrinks the
            # cluster (fewer pools -> smaller carry, same stream length).
            "trace_stream_jobs": 10_000,
            "trace_stream_reps": 2,
            "trace_slab": 1024,
            "trace_pool": 6,
            "trace_pools": 96,
            "slo_workers": 8,
            "slo_jobs": 600,
            "slo_reps": 2,
        }
    return {
        "n_workers": 20,
        "n_reps": 400,
        "n_jobs": 24,
        "trace_jobs": 10,
        "backend_workers": 36,
        "backend_reps": 1000,
        "dyn_workers": 16,
        "dyn_reps": 2048,
        "space_workers": 16,
        "space_reps": 2048,
        "trace_stream_jobs": 10_000,
        "trace_stream_reps": 2,
        "trace_slab": 1024,
        "trace_pool": 6,
        "trace_pools": 2304,
        "slo_workers": 8,
        "slo_jobs": 2000,
        "slo_reps": 4,
    }


def bench_redundancy(cfg: dict, seed: int = 0, backend: str = "python") -> dict:
    """Engine-measured speedup of planned redundancy vs no redundancy."""
    n = cfg["n_workers"]
    jobs = traces.synthetic_google_jobs()
    # interleave the exponential (1-4) and heavy (5-10) families so that
    # smoke subsets still exercise both tail regimes
    exp = [j for j in jobs if j.family == "exponential"]
    heavy = [j for j in jobs if j.family == "heavy"]
    interleaved = [j for pair in zip(heavy, exp) for j in pair] + heavy[len(exp):]
    jobs = interleaved[: cfg["trace_jobs"]]
    planner = RedundancyPlanner(n)
    out = {}
    for i, tj in enumerate(jobs):
        dist = Empirical(samples=tuple(float(x) for x in tj.task_times))
        plan = planner.plan_empirical(tj.task_times, "mean", n_mc=4 * cfg["n_reps"], seed=seed)
        t_base = sample_job_times(dist, n, n, cfg["n_reps"], seed=seed + i, backend=backend)
        t_plan = sample_job_times(
            dist, n, plan.n_batches, cfg["n_reps"], seed=seed + i, backend=backend
        )
        out[tj.name] = {
            "family": tj.family,
            "B_star": plan.n_batches,
            "mean_T_no_redundancy": float(t_base.mean()),
            "mean_T_planned": float(t_plan.mean()),
            "speedup": float(t_base.mean() / t_plan.mean()),
        }
    heavy = [v["speedup"] for v in out.values() if v["family"] == "heavy"]
    out["_summary"] = {
        "max_heavy_speedup": max(heavy) if heavy else None,
        "min_heavy_speedup": min(heavy) if heavy else None,
    }
    return out


def bench_queueing(cfg: dict, seed: int = 0) -> dict:
    """Multi-job FIFO queueing under Poisson arrivals, planned vs none."""
    n = cfg["n_workers"]
    trace = traces.synthetic_google_jobs()[5]  # heavy-tail job
    plan = RedundancyPlanner(n).plan_empirical(trace.task_times, "mean", n_mc=2000, seed=seed)
    base_mean = float(np.mean(trace.task_times))
    # arrivals fast enough that queueing matters: ~1 job per planned job-time
    rate = 1.0 / (base_mean * 2.0)
    workload = jobs_from_traces([trace] * cfg["n_jobs"], n, rate, seed=seed)
    out = {}
    for label, b in [("no_redundancy", n), ("planned", plan.n_batches)]:
        rep = ClusterEngine(n, seed=seed, n_batches=b, cancel_redundant=True).run(workload)
        resp = rep.response_times
        resp = resp[np.isfinite(resp)]
        out[label] = {
            "B": b,
            "mean_response": float(resp.mean()),
            "p95_response": float(np.percentile(resp, 95)),
            "worker_seconds": rep.worker_seconds,
        }
    base, planned = out["no_redundancy"]["mean_response"], out["planned"]["mean_response"]
    out["response_speedup"] = base / planned
    return out


def bench_cancellation(cfg: dict, seed: int = 0) -> dict:
    """Worker-seconds reclaimed by cancelling redundant replicas."""
    n = cfg["n_workers"]
    dist = Pareto(sigma=1.0, alpha=1.8)
    jobs = [Job(job_id=i, dist=dist, n_tasks=n) for i in range(cfg["n_jobs"])]
    out = {}
    for label, cancel in [("cancel_on", True), ("cancel_off", False)]:
        rep = ClusterEngine(n, seed=seed, n_batches=max(1, n // 4), cancel_redundant=cancel).run(
            jobs
        )
        out[label] = {
            "worker_seconds": rep.worker_seconds,
            "saved_seconds": rep.cancelled_seconds_saved,
            "mean_response": float(rep.response_times.mean()),
        }
    out["worker_seconds_ratio"] = (
        out["cancel_on"]["worker_seconds"] / out["cancel_off"]["worker_seconds"]
    )
    return out


def bench_churn(cfg: dict, seed: int = 0) -> dict:
    """Fail/join churn: completion under failures, rescue accounting."""
    n = cfg["n_workers"]
    dist = Pareto(sigma=1.0, alpha=1.8)
    jobs = [Job(job_id=i, dist=dist, n_tasks=n) for i in range(cfg["n_jobs"])]
    out = {}
    scenarios = [
        ("churn_off", None),
        ("churn_on", ChurnProcess(fail_rate=0.02, mean_downtime=5.0)),
    ]
    for label, churn in scenarios:
        rep = ClusterEngine(n, seed=seed, n_batches=max(1, n // 4), churn=churn).run(jobs)
        t = rep.compute_times
        out[label] = {
            "mean_compute": float(t[np.isfinite(t)].mean()),
            "n_worker_failures": rep.n_worker_failures,
            "n_replicas_rescued": rep.n_replicas_rescued,
            "all_jobs_completed": bool(np.isfinite(t).all()),
        }
    out["churn_slowdown"] = out["churn_on"]["mean_compute"] / out["churn_off"]["mean_compute"]
    return out


def bench_backend(cfg: dict, seed: int = 0) -> dict:
    """Full-frontier ``plan_cluster`` sweep: Python event engine vs jax.

    Wall-clock for scoring every feasible B of ``backend_workers`` workers
    with ``backend_reps`` Monte-Carlo reps each.  The jax backend is timed
    warm (one untimed call first, reported as ``jax_seconds_cold``): the
    compile amortizes across every subsequent sweep of the same shape, which
    is exactly how ``plan_sweep`` / the online replanner use it.
    """
    n, reps = cfg["backend_workers"], cfg["backend_reps"]
    out = {"n_workers": n, "n_reps": reps, "dists": {}}
    for name, dist in [("exponential", Exponential(1.0)), ("pareto_heavy", Pareto(1.0, 1.8))]:
        planner = RedundancyPlanner(n)
        jax.clear_caches()  # same frontier shapes across dists: force a real compile
        t0 = time.time()
        planner.plan_cluster(dist, n_reps=reps, seed=seed, backend="jax")
        cold = time.time() - t0
        t0 = time.time()
        plan_jax = planner.plan_cluster(dist, n_reps=reps, seed=seed, backend="jax")
        t_jax = time.time() - t0
        t0 = time.time()
        plan_py = planner.plan_cluster(dist, n_reps=reps, seed=seed, backend="python")
        t_py = time.time() - t0
        out["dists"][name] = {
            "frontier_size": len(planner.candidates),
            "python_seconds": t_py,
            "jax_seconds_warm": t_jax,
            "jax_seconds_cold": cold,
            "speedup_warm": t_py / max(t_jax, 1e-9),
            "speedup_cold": t_py / max(cold, 1e-9),
            "B_python": plan_py.n_batches,
            "B_jax": plan_jax.n_batches,
        }
    speedups = [d["speedup_warm"] for d in out["dists"].values()]
    out["min_speedup_warm"] = min(speedups)
    out["max_speedup_warm"] = max(speedups)
    return out


def bench_dynamic(cfg: dict, seed: int = 0) -> dict:
    """Churned + heterogeneous full-frontier ``plan_cluster``: python vs jax.

    The scenario PR 2 could not vectorize: every candidate B scored under
    worker fail/join churn (with replica rescue) on a heterogeneous-speed
    cluster.  The Python engine replays one event loop per candidate; the jax
    epoch scan (``repro.cluster.epoch_scan``) batches the whole frontier's
    correlated job streams into one ``lax.scan`` device call.  Warm timing,
    like ``bench_backend``: the compile amortizes across every sweep of the
    same shape (exactly how ``plan_sweep`` and nightly grids use it).
    """
    from repro.cluster.epoch_scan import clear_runner_cache

    n, reps = cfg["dyn_workers"], cfg["dyn_reps"]
    churn = ChurnProcess(fail_rate=0.02, mean_downtime=2.0)
    rng = np.random.default_rng(seed)
    speeds = tuple(float(s) for s in rng.uniform(0.5, 2.0, size=n))
    out = {"n_workers": n, "n_reps": reps, "churn_fail_rate": churn.fail_rate, "dists": {}}
    for name, dist in [("exponential", Exponential(1.0)), ("pareto_heavy", Pareto(1.0, 1.8))]:
        planner = RedundancyPlanner(n)
        # 2 fail/join pairs per worker comfortably cover each stream's horizon
        # (~1 expected failure); 96-job streams keep the step loop dominated
        # by job dispatches rather than churn-boundary bookkeeping
        sc = Scenario(churn=churn, speeds=speeds)
        kw = dict(n_reps=reps, seed=seed, scenario=sc)
        kw_jax = dict(kw, scenario=sc.replace(churn_pairs_per_worker=2, jobs_per_stream=96))
        clear_runner_cache()
        jax.clear_caches()  # same shapes across dists: force a real compile
        t0 = time.time()
        planner.plan_cluster(dist, **kw_jax, backend="jax")
        cold = time.time() - t0
        # min-of-3 warm: the jax call is tens of milliseconds, where shared
        # CI runners add multiplicative noise the long python run averages out
        warms = []
        for _ in range(3):
            t0 = time.time()
            plan_jax = planner.plan_cluster(dist, **kw_jax, backend="jax")
            warms.append(time.time() - t0)
        t_jax = min(warms)
        t0 = time.time()
        plan_py = planner.plan_cluster(dist, **kw, backend="python")
        t_py = time.time() - t0
        out["dists"][name] = {
            "frontier_size": len(planner.candidates),
            "python_seconds": t_py,
            "jax_seconds_warm": t_jax,
            "jax_seconds_cold": cold,
            "speedup_warm": t_py / max(t_jax, 1e-9),
            "speedup_cold": t_py / max(cold, 1e-9),
            "B_python": plan_py.n_batches,
            "B_jax": plan_jax.n_batches,
        }
    speedups = [d["speedup_warm"] for d in out["dists"].values()]
    out["min_speedup_warm"] = min(speedups)
    out["max_speedup_warm"] = max(speedups)
    out["max_cold_seconds"] = max(d["jax_seconds_cold"] for d in out["dists"].values())
    # process high-water RSS right after the dynamic sweeps: the chunked-rep
    # memory story's observable (ru_maxrss is KiB on Linux, bytes on macOS)
    rss_scale = 1024.0**2 if sys.platform == "darwin" else 1024.0
    out["peak_rss_mb"] = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / rss_scale
    return out


def bench_space_sharing(cfg: dict, seed: int = 0) -> dict:
    """Space-sharing scheduler: packed-vs-gang response ratio + jax edge.

    Two measurements: (1) the scheduling effect itself -- a saturated stream
    of narrow jobs (``workers_per_job = n/3``) finishes with a much lower
    mean response under ``packed`` space sharing than under the whole-cluster
    FIFO gang, because disjoint subsets run three jobs at once; (2) the
    backend effect -- scoring a space-shared candidate frontier on the jax
    space lane vs one Python event loop per candidate (warm min-of-3, like
    ``bench_dynamic``; cold = compile+run).  The regression gate keys on the
    response ratio staying below 1 with margin and the warm edge floor.
    """
    from repro.cluster.epoch_scan import clear_runner_cache
    from repro.core import analysis

    n, reps = cfg["space_workers"], cfg["space_reps"]
    wpj = max(2, n // 3)
    n_jobs = 24
    arr = np.zeros(n_jobs)
    d_ratio = Pareto(1.0, 1.8)
    gang = simulate_fifo(d_ratio, n, 2, arr, max(64, reps // 8), seed=seed)
    packed = simulate_fifo(
        d_ratio, n, 2, arr, max(64, reps // 8), seed=seed,
        scheduler="packed", workers_per_job=wpj,
    )
    ratio = float(packed.response_times.mean() / gang.response_times.mean())
    out = {
        "n_workers": n,
        "n_reps": reps,
        "workers_per_job": wpj,
        "response_ratio_packed_vs_gang": ratio,
        "dists": {},
    }
    cands = analysis.feasible_B(wpj)
    for name, dist in [("exponential", Exponential(1.0)), ("pareto_heavy", Pareto(1.0, 1.8))]:
        planner = RedundancyPlanner(n, candidates=cands)
        kw = dict(
            n_reps=reps,
            seed=seed,
            scenario=Scenario(scheduler="packed", workers_per_job=wpj, jobs_per_stream=48),
        )
        clear_runner_cache()
        jax.clear_caches()  # same shapes across dists: force a real compile
        t0 = time.time()
        planner.plan_cluster(dist, **kw, backend="jax")
        cold = time.time() - t0
        warms = []
        for _ in range(3):
            t0 = time.time()
            plan_jax = planner.plan_cluster(dist, **kw, backend="jax")
            warms.append(time.time() - t0)
        t_jax = min(warms)
        t0 = time.time()
        plan_py = planner.plan_cluster(dist, **kw, backend="python")
        t_py = time.time() - t0
        out["dists"][name] = {
            "frontier_size": len(cands),
            "python_seconds": t_py,
            "jax_seconds_warm": t_jax,
            "jax_seconds_cold": cold,
            "speedup_warm": t_py / max(t_jax, 1e-9),
            "B_python": plan_py.n_batches,
            "B_jax": plan_jax.n_batches,
        }
    speedups = [d["speedup_warm"] for d in out["dists"].values()]
    out["min_speedup_warm"] = min(speedups)
    out["max_speedup_warm"] = max(speedups)
    out["max_cold_seconds"] = max(d["jax_seconds_cold"] for d in out["dists"].values())
    return out


def bench_speculation(cfg: dict, seed: int = 0) -> dict:
    """Planned vs speculative vs hybrid redundancy across tail regimes.

    The paper's planned replication spends workers *proactively*; the
    ``Speculation`` policy spends them *reactively*, backing up only the
    replicas whose elapsed time crosses ``theta x`` the running median of
    completed siblings.  Four variants per distribution, all on the Python
    event engine (the reference semantics the jax scan and the live runtime
    are pinned to):

      no_redundancy  B = N, no backups   -- the straggler-exposed baseline
      planned        B = B*, no backups  -- §VI/§VII proactive replication
      speculative    B = N, backups      -- reactive only
      hybrid         B = B*, backups     -- both

    The check interval scales with each distribution's median task time so
    one policy spec covers sub-second exponentials and the ~14 s-median
    trace job alike.  The regression gate keys on the Pareto row: reactive
    backups alone must keep beating the no-redundancy baseline.
    """
    from repro.cluster import Speculation

    n = cfg["n_workers"]
    n_jobs = cfg["n_reps"]
    theta, min_obs = 2.0, 3
    dists = [
        ("exponential", Exponential(1.0)),
        ("shifted_exp", ShiftedExponential(0.3, 1.0)),
        ("pareto_heavy", Pareto(1.0, 1.5)),
    ]
    trace = traces.synthetic_google_jobs()[5]  # heavy-tail trace job
    dists.append(("trace_heavy", Empirical(samples=tuple(float(x) for x in trace.task_times))))
    out = {
        "n_workers": n,
        "n_jobs": n_jobs,
        "theta": theta,
        "min_observations": min_obs,
        "dists": {},
    }
    for name, dist in dists:
        med = float(np.median(dist.sample_np(np.random.default_rng(seed), (512,))))
        spec = Speculation(
            interval=max(0.05, 0.25 * med), theta=theta, min_observations=min_obs
        )
        planner = RedundancyPlanner(n)
        if isinstance(dist, Empirical):
            plan = planner.plan_empirical(
                np.asarray(dist.samples), "mean", n_mc=4 * n_jobs, seed=seed
            )
        else:
            plan = planner.plan(dist, objective="mean")
        variants = {
            "no_redundancy": (n, None),
            "planned": (plan.n_batches, None),
            "speculative": (n, spec),
            "hybrid": (plan.n_batches, spec),
        }
        entry = {"B_star": plan.n_batches, "interval": spec.interval}
        for label, (b, sp) in variants.items():
            rep = ClusterEngine(
                n, seed=seed, n_batches=b, cancel_redundant=True, speculation=sp
            ).run([Job(job_id=i, dist=dist, n_tasks=n) for i in range(n_jobs)])
            t = rep.compute_times
            entry[label] = {
                "B": b,
                "mean_compute": float(t.mean()),
                "p95_compute": float(np.percentile(t, 95)),
                "worker_seconds": rep.worker_seconds,
                "n_speculative": rep.n_speculative,
            }
        base = entry["no_redundancy"]["mean_compute"]
        for label in ("planned", "speculative", "hybrid"):
            entry[f"speedup_{label}"] = base / entry[label]["mean_compute"]
        out["dists"][name] = entry
    out["pareto_speculative_speedup"] = out["dists"]["pareto_heavy"]["speedup_speculative"]
    out["pareto_hybrid_speedup"] = out["dists"]["pareto_heavy"]["speedup_hybrid"]
    return out


def bench_trace_scale(cfg: dict, seed: int = 0) -> dict:
    """Trace-scale throughput: a 10k-job cluster-day through the stream path.

    The full (distribution family x budget x scheduler) grid -- 12 cells --
    over one synthetic cluster-day per family, on a trace-sized cluster
    (``trace_pools`` pools of ``trace_pool`` workers; the 2011 Google trace
    holds ~12.5k machines).  Every cell streams the whole day through
    ``simulate_stream``: draws generated per slab, statistics carried in the
    scan, so peak memory is O(slab) regardless of the stream length.

    Two gates (``check_bench_regression.py``):

      * ``sweep_seconds_warm`` -- min-of-3 full-grid wall time after the cold
        pass compiled the six kernel shapes (families reuse compiles).  The
        whole cluster-day grid must stay single-digit seconds warm.
      * ``peak_rss_mb`` -- process high-water RSS after the sweep; the O(slab)
        memory story's observable.  A materialized (reps x jobs x B x r) path
        would blow straight through the ceiling.

    ``fifo_gang`` cells run one pool-width gang (the exact ``simulate_fifo``
    regime); ``packed``/``balanced`` split the cluster into disjoint pools.
    """
    from repro.cluster import simulate_stream
    from repro.core.traces import synthetic_cluster_day

    pool = cfg["trace_pool"]
    n_jobs = cfg["trace_stream_jobs"]
    reps = cfg["trace_stream_reps"]
    slab = cfg["trace_slab"]
    n_workers = pool * cfg["trace_pools"]
    days = {
        fam: synthetic_cluster_day(n_jobs=n_jobs, seed=seed + 7, families=(fam,))
        for fam in ("exponential", "heavy")
    }
    budgets = {"planned": pool // 2, "no_redundancy": pool}

    def sweep() -> dict:
        cells = {}
        for fam, day in days.items():
            for sched in ("fifo_gang", "packed", "balanced"):
                gang = sched == "fifo_gang"
                for bname, b in budgets.items():
                    sc = Scenario(
                        outputs="stream",
                        scheduler=sched,
                        workers_per_job=None if gang else pool,
                        cancel_redundant=True,
                    )
                    stats = simulate_stream(
                        day, pool if gang else n_workers, b, reps,
                        scenario=sc, slab=slab,
                    )
                    s = stats.summary()
                    cells[f"{fam}/{sched}/{bname}"] = {
                        "B": b,
                        "r": pool // b,
                        "mean_response": s["mean_response"],
                        "p99_response": s["p99_response"],
                        "worker_seconds": s["worker_seconds"],
                        "cancelled_seconds_saved": s["cancelled_seconds_saved"],
                    }
        return cells

    jax.clear_caches()  # force real compiles into the cold pass
    t0 = time.time()
    cells = sweep()
    cold = time.time() - t0
    warms = []
    for _ in range(3):
        t0 = time.time()
        cells = sweep()
        warms.append(time.time() - t0)
    rss_scale = 1024.0**2 if sys.platform == "darwin" else 1024.0
    return {
        "n_jobs": n_jobs,
        "n_reps": reps,
        "slab": slab,
        "pool_width": pool,
        "n_pools": cfg["trace_pools"],
        "n_cells": len(cells),
        "cells": cells,
        "sweep_seconds_cold": cold,
        "sweep_seconds_warm": min(warms),
        "jobs_per_second_warm": len(cells) * n_jobs * reps / max(min(warms), 1e-9),
        "peak_rss_mb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / rss_scale,
    }


def bench_slo(cfg: dict, seed: int = 0) -> dict:
    """Tail-SLO planning: cheapest feasible (B, r, scheduler) per tail family.

    Runs ``RedundancyPlanner.plan_slo`` over the (scheduler x pool-width x B)
    grid for the three parametric tails and records, per family, the cheapest
    feasible candidate, the mean-optimal candidate, and whether they differ --
    the paper's "mean-optimal is not tail-optimal" observation, kept live as
    a gated benchmark fact (the gate keys on the Pareto row).
    """
    from repro.cluster import SLO

    n = cfg["slo_workers"]
    planner = RedundancyPlanner(n)
    rate = 0.05
    # p99 response targets sized so each family is feasible at the committed
    # smoke scale but tight enough that heavy tails need planning to meet it
    dists = {
        "exponential": (Exponential(1.0), 12.0),
        "shifted_exp": (ShiftedExponential(0.3, 1.0), 15.0),
        "pareto_heavy": (Pareto(1.0, 1.5), 60.0),
    }

    def sweep() -> dict:
        return {
            name: planner.plan_slo(
                [dist],
                SLO(quantile=0.99, target_s=target, arrival_rate=rate),
                n_jobs=cfg["slo_jobs"],
                n_reps=cfg["slo_reps"],
                seed=seed,
                schedulers=("fifo_gang", "packed"),
            )
            for name, (dist, target) in dists.items()
        }

    jax.clear_caches()
    t0 = time.time()
    plans = sweep()
    cold = time.time() - t0
    t0 = time.time()
    plans = sweep()
    warm = time.time() - t0

    def _cand(c) -> dict:
        return {
            "scheduler": c.scheduler,
            "workers_per_job": c.workers_per_job,
            "B": c.n_batches,
            "r": c.replication,
            "feasible": c.feasible,
            "cost_worker_seconds": c.cost_worker_seconds,
            "mean_response": c.mean_response,
            "achieved_p99": c.achieved[0],
        }

    def _key(c) -> tuple:
        return (c.scheduler, c.workers_per_job, c.n_batches, c.replication)

    out: dict = {"n_workers": n, "arrival_rate": rate, "quantile": 0.99}
    feas_total = cand_total = 0
    for name, plan in plans.items():
        mean_opt = min(plan.candidates, key=lambda c: c.mean_response)
        n_feas = sum(c.feasible for c in plan.candidates)
        feas_total += n_feas
        cand_total += len(plan.candidates)
        out[name] = {
            "target_p99_s": dists[name][1],
            "feasible": plan.feasible,
            "n_candidates": len(plan.candidates),
            "n_feasible": n_feas,
            "best": None if plan.best is None else _cand(plan.best),
            "mean_optimal": _cand(mean_opt),
            "mean_vs_tail_diverge": plan.best is not None
            and _key(plan.best) != _key(mean_opt),
        }
    out["feasible_frac"] = feas_total / max(cand_total, 1)
    out["all_feasible"] = all(out[name]["feasible"] for name in dists)
    out["pareto_mean_vs_tail_diverge"] = out["pareto_heavy"]["mean_vs_tail_diverge"]
    out["sweep_seconds_cold"] = cold
    out["sweep_seconds_warm"] = warm
    return out


def run_all(smoke: bool = True, seed: int = 0) -> list:
    """CSV rows for the benchmark aggregator (smoke sizes by default)."""
    cfg = _cfg(smoke)
    rows = []
    t0 = time.time()
    red = bench_redundancy(cfg, seed)
    s = red["_summary"]
    rows.append(
        (
            "cluster_redundancy",
            (time.time() - t0) * 1e6 / max(cfg["trace_jobs"], 1),
            f"heavy speedup {s['min_heavy_speedup']:.1f}x..{s['max_heavy_speedup']:.1f}x",
        )
    )
    t0 = time.time()
    q = bench_queueing(cfg, seed)
    rows.append(
        (
            "cluster_queueing",
            (time.time() - t0) * 1e6 / cfg["n_jobs"],
            f"response speedup {q['response_speedup']:.1f}x (B*={q['planned']['B']})",
        )
    )
    t0 = time.time()
    c = bench_cancellation(cfg, seed)
    rows.append(
        (
            "cluster_cancellation",
            (time.time() - t0) * 1e6 / cfg["n_jobs"],
            f"worker-seconds x{c['worker_seconds_ratio']:.2f} with cancellation",
        )
    )
    t0 = time.time()
    ch = bench_churn(cfg, seed)
    rows.append(
        (
            "cluster_churn",
            (time.time() - t0) * 1e6 / cfg["n_jobs"],
            f"slowdown x{ch['churn_slowdown']:.2f} under churn "
            f"({ch['churn_on']['n_worker_failures']} failures)",
        )
    )
    t0 = time.time()
    bk = bench_backend(cfg, seed)
    rows.append(
        (
            "cluster_backend",
            (time.time() - t0) * 1e6 / max(cfg["backend_reps"], 1),
            f"jax frontier sweep {bk['min_speedup_warm']:.0f}x"
            f"..{bk['max_speedup_warm']:.0f}x vs python engine",
        )
    )
    t0 = time.time()
    dy = bench_dynamic(cfg, seed)
    rows.append(
        (
            "cluster_dynamic",
            (time.time() - t0) * 1e6 / max(cfg["dyn_reps"], 1),
            f"churned/hetero sweep {dy['min_speedup_warm']:.0f}x"
            f"..{dy['max_speedup_warm']:.0f}x vs python engine",
        )
    )
    t0 = time.time()
    sk = bench_speculation(cfg, seed)
    rows.append(
        (
            "cluster_speculation",
            (time.time() - t0) * 1e6 / max(cfg["n_reps"], 1),
            f"pareto: speculative x{sk['pareto_speculative_speedup']:.2f}, "
            f"hybrid x{sk['pareto_hybrid_speedup']:.2f} vs no redundancy",
        )
    )
    t0 = time.time()
    sp = bench_space_sharing(cfg, seed)
    rows.append(
        (
            "cluster_space_sharing",
            (time.time() - t0) * 1e6 / max(cfg["space_reps"], 1),
            f"packed/gang response x{sp['response_ratio_packed_vs_gang']:.2f}, "
            f"jax space sweep {sp['min_speedup_warm']:.0f}x"
            f"..{sp['max_speedup_warm']:.0f}x",
        )
    )
    t0 = time.time()
    tr = bench_trace_scale(cfg, seed)
    rows.append(
        (
            "cluster_trace_scale",
            (time.time() - t0) * 1e6 / max(cfg["trace_stream_jobs"], 1),
            f"{tr['n_cells']}-cell day sweep {tr['sweep_seconds_warm']:.1f}s warm "
            f"({tr['jobs_per_second_warm'] / 1e3:.0f}k jobs/s, "
            f"rss {tr['peak_rss_mb']:.0f}MB)",
        )
    )
    t0 = time.time()
    sl = bench_slo(cfg, seed)
    rows.append(
        (
            "cluster_slo",
            (time.time() - t0) * 1e6 / max(cfg["slo_jobs"], 1),
            f"p99 plans feasible {sl['feasible_frac']:.0%} of grid, "
            f"pareto mean!=tail: {sl['pareto_mean_vs_tail_diverge']}",
        )
    )
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny sample counts (CI)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--backend",
        choices=["python", "jax", "both"],
        default="python",
        help="engine scoring the redundancy scenario (the backend section always runs both)",
    )
    ap.add_argument("--out", type=pathlib.Path, default=ART / "cluster_bench.json")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = _cfg(args.smoke)
    t0 = time.time()
    result = {
        "config": {"smoke": args.smoke, "seed": args.seed, "backend": args.backend, **cfg},
        "queueing": bench_queueing(cfg, args.seed),
        "cancellation": bench_cancellation(cfg, args.seed),
        "churn": bench_churn(cfg, args.seed),
        "backend": bench_backend(cfg, args.seed),
        "dynamic": bench_dynamic(cfg, args.seed),
        "space_sharing": bench_space_sharing(cfg, args.seed),
        "speculation": bench_speculation(cfg, args.seed),
        "trace_scale": bench_trace_scale(cfg, args.seed),
        "slo": bench_slo(cfg, args.seed),
    }
    if args.backend in ("python", "both"):
        result["redundancy"] = bench_redundancy(cfg, args.seed, backend="python")
    if args.backend in ("jax", "both"):
        result["redundancy_jax"] = bench_redundancy(cfg, args.seed, backend="jax")
    if "redundancy" not in result:
        # the regression gate keys on "redundancy"; alias the jax run
        result["redundancy"] = result["redundancy_jax"]
    result["wall_seconds"] = time.time() - t0

    args.out.parent.mkdir(parents=True, exist_ok=True)
    args.out.write_text(json.dumps(result, indent=2))
    print(json.dumps(result, indent=2))
    print(f"\nwrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()
