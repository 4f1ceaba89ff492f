#!/usr/bin/env python3
"""Smoke run of the device path on a TPU, through the entry points users call.

    python chip_smoke.py              # phases 1-6 on one chip
    python chip_smoke.py --chips 4    # only the sharded epoch-scan lanes

One process, phases in order.  Each phase prints one line: what ran, the
compile seconds and wall seconds of its first (cold) run on the chip, the
wall seconds of a second (warm) run, and its check.  The references run in
this same process: the CPU backend, pinned with ``jax.default_device``, or
the Python discrete-event engine, which is the system's reference
semantics.  Every phase runs even after one fails; any failure exits 1.
Only when every check passed does the last line print, as one JSON object
naming the device.  There is no CPU fallback: without a TPU the run fails.

Sizes are the ones users run: a 10k-job synthetic cluster-day on 2304 pools
of 6 workers (13,824 workers; the 2011 Google trace cell held ~12.5k
machines), the dynamic planner at 16 workers x 2048 reps under churn, the
tail-SLO planner at 8 workers x 2000 jobs, and the cover kernel on a
(16384, 16, 16) replica grid.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import pathlib
import sys
import time
import traceback

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# the CPU references run beside the chip in this process; keep the CPU
# backend loaded when the platform list is pinned
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Workload sizes of every phase (the defaults are the real ones)."""

    stream_jobs: int = 10_000
    stream_reps: int = 2
    slab: int = 1024
    pool: int = 6
    pools: int = 2304
    plan_workers: int = 16
    plan_reps: int = 2048
    jobs_per_stream: int = 96
    space_width: int = 4
    slo_workers: int = 8
    slo_jobs: int = 2000
    slo_reps: int = 4
    cover_grid: tuple = (16384, 16, 16)
    runtime_workers: int = 4
    runtime_jobs: int = 3


class CheckFailed(Exception):
    """A phase ran but its result disagreed with the reference."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


# --------------------------------------------------------------------------
# compile accounting (jax's own monitoring events)
# --------------------------------------------------------------------------


class _Compiles:
    """Backend-compile seconds and persistent-cache hits since ``reset``."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def reset(self):
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0


_COMPILES = None


def timed_twice(fn):
    """Run ``fn`` cold then warm; return (cold result, timing dict)."""
    global _COMPILES
    if _COMPILES is None:  # jax's listeners cannot be removed: register once
        _COMPILES = _Compiles()
    _COMPILES.reset()
    t0 = time.perf_counter()
    out = fn()
    cold = time.perf_counter() - t0
    compile_s, n_compiles, hits = _COMPILES.seconds, _COMPILES.count, _COMPILES.cache_hits
    t0 = time.perf_counter()
    fn()
    warm = time.perf_counter() - t0
    return out, {
        "compile_s": compile_s,
        "compiles": n_compiles,
        "cache_hits": hits,
        "cold_s": cold,
        "warm_s": warm,
    }


@contextlib.contextmanager
def x64(on: bool):
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", on)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)


def on_cpu():
    return jax.default_device(jax.devices("cpu")[0])


def _fmt(t: dict) -> str:
    return (
        f"compile_s={t['compile_s']:.3f} ({t['compiles']} compiles, "
        f"{t['cache_hits']} cache hits) cold_s={t['cold_s']:.3f} warm_s={t['warm_s']:.3f}"
    )


# --------------------------------------------------------------------------
# phase 2: trace-scale stream
# --------------------------------------------------------------------------

_INT_FIELDS = ("count", "hist", "class_count", "class_hist")
_FLOAT_FIELDS = (
    "resp_sum", "resp_sq", "resp_min", "resp_max", "comp_sum", "busy_sum", "saved_sum",
    "class_resp_sum",
)


def compare_stats(got, ref, tol: float) -> tuple[list, float, list]:
    """(fields not bit-equal, largest relative float difference, failed fields).

    Integer fields must match exactly.  A float field passes when
    ``|got - ref| <= tol * scale``, the scale being the larger magnitude of
    the two -- or, for ``saved_sum`` (planned minus charged seconds, a
    difference of two sums), the charged worker-seconds it is taken from.
    """
    bad, worst, differ = [], 0.0, []
    for f in _INT_FIELDS:
        a, b = getattr(got, f), getattr(ref, f)
        if a is None and b is None:
            continue
        if not np.array_equal(a, b):
            bad.append(f)
            differ.append(f)
    for f in _FLOAT_FIELDS:
        a, b = getattr(got, f), getattr(ref, f)
        if a is None and b is None:
            continue
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if np.array_equal(a, b):
            continue
        differ.append(f)
        scale = np.maximum(np.abs(a), np.abs(b))
        if f == "saved_sum":
            scale = np.maximum(scale, np.abs(np.asarray(ref.busy_sum, np.float64)))
        with np.errstate(invalid="ignore", divide="ignore"):
            rel = np.where(a == b, 0.0, np.abs(a - b) / scale)
        worst = max(worst, float(np.nanmax(rel)))
        if not (rel <= tol).all():
            bad.append(f)
    return differ, worst, bad


def phase_stream(sz: Sizes) -> str:
    from repro.cluster import Scenario, simulate_stream
    from repro.core.traces import synthetic_cluster_day

    days = {
        fam: synthetic_cluster_day(n_jobs=sz.stream_jobs, seed=7, families=(fam,))
        for fam in ("heavy", "exponential")
    }
    budgets = (sz.pool // 2, sz.pool)  # B=3 planned, B=6 no redundancy
    n_cluster = sz.pool * sz.pools

    def cell(day, sched, b, dtype):
        gang = sched == "fifo_gang"
        sc = Scenario(
            outputs="stream",
            scheduler=sched,
            workers_per_job=None if gang else sz.pool,
            cancel_redundant=True,
            dtype=dtype,
        )
        return simulate_stream(
            day, sz.pool if gang else n_cluster, b, sz.stream_reps, scenario=sc, slab=sz.slab
        )

    grid = [
        (fam, sched, b)
        for fam in days
        for sched in ("fifo_gang", "packed", "balanced")
        for b in budgets
    ]

    def sweep(dtype="float32"):
        return {k: cell(days[k[0]], k[1], k[2], dtype) for k in grid}

    chip, t32 = timed_twice(sweep)
    with on_cpu():
        ref = sweep()
    # a 10k-term sequential f32 sum whose terms agree to a few ulps can drift
    # by at most one ulp of the running sum per step: n_jobs * eps
    tol = sz.stream_jobs * float(np.finfo(np.float32).eps)
    n_bit, worst, bad, differ = 0, 0.0, [], set()
    for k in grid:
        d, w, b = compare_stats(chip[k], ref[k], tol)
        n_bit += not d
        differ.update(d)
        worst = max(worst, w)
        bad += [f"{'/'.join(map(str, k))}:{f}" for f in b]
    jobs_s = len(grid) * sz.stream_jobs * sz.stream_reps / t32["warm_s"]

    f64_key = ("heavy", "packed", budgets[0])
    with x64(True):
        chip64, t64 = timed_twice(lambda: cell(days["heavy"], "packed", budgets[0], "float64"))
        with on_cpu():
            ref64 = cell(days["heavy"], "packed", budgets[0], "float64")
    d64, w64, bad64 = compare_stats(chip64, ref64, tol)
    line = (
        f"[phase 2] stream: {len(grid)} f32 cells ({'x'.join(days)} x fifo_gang/packed/balanced"
        f" x B={budgets}) of {sz.stream_jobs} jobs x {sz.stream_reps} reps, {sz.pools} pools"
        f" x {sz.pool} workers, slab {sz.slab} | f32 {_fmt(t32)} jobs_per_s_warm={jobs_s:.0f}"
        f" | vs CPU: ints exact, floats rtol {tol:.3g}: bit_equal_cells={n_bit}/{len(grid)}"
        f" max_rel={worst:.3g} not_bit_equal={sorted(differ)} | f64"
        f" {'/'.join(map(str, f64_key))} {_fmt(t64)} bit_equal={not d64} max_rel={w64:.3g}"
        f" not_bit_equal={d64}"
    )
    _check(not bad and not bad64, f"{line} | mismatched: {bad + bad64}")
    return line + " | PASS"


# --------------------------------------------------------------------------
# phase 3: dynamic planner (epoch-scan lanes)
# --------------------------------------------------------------------------


def churned_scenario(sz: Sizes):
    """The bench's dynamic cell: fail/join churn on heterogeneous speeds."""
    from repro.cluster import ChurnProcess, Scenario

    rng = np.random.default_rng(0)
    speeds = tuple(float(s) for s in rng.uniform(0.5, 2.0, size=sz.plan_workers))
    return Scenario(churn=ChurnProcess(fail_rate=0.02, mean_downtime=2.0), speeds=speeds)


def _z_frontier(a, b, n_a: int, n_b: int) -> float:
    """Largest per-candidate z of the frontier-mean difference of two plans."""
    ma, mb = np.asarray(a.frontier_mean), np.asarray(b.frontier_mean)
    sa, sb = ma * np.asarray(a.frontier_cov), mb * np.asarray(b.frontier_cov)
    se = np.sqrt(sa**2 / n_a + sb**2 / n_b)
    return float(np.max(np.abs(ma - mb) / se))


def epochs_run(sz: Sizes, sc, devices: int = 1):
    """``simulate_epochs`` of ``plan_reps`` lanes in one device call, B=4."""
    from repro.cluster import simulate_epochs
    from repro.core.service_time import Pareto

    return simulate_epochs(
        Pareto(1.0, 1.8), sz.plan_workers, 4, np.zeros(sz.jobs_per_stream), sz.plan_reps,
        seed=0,
        # a horizon long enough that no heavy-tailed stream outruns its churn
        scenario=sc.replace(devices=devices, churn_pairs_per_worker=128),
    )


def _z_means(a, b) -> float:
    """z of the difference of two samples' means."""
    return float(abs(a.mean() - b.mean()) / np.sqrt(a.var() / a.size + b.var() / b.size))


def phase_planner(sz: Sizes) -> str:
    from repro.core import analysis
    from repro.core.planner import RedundancyPlanner
    from repro.core.service_time import Pareto

    dist = Pareto(1.0, 1.8)
    sc = churned_scenario(sz).replace(jobs_per_stream=sz.jobs_per_stream)
    sc_space = sc.replace(scheduler="packed", workers_per_job=sz.space_width)
    kw = dict(n_reps=sz.plan_reps, seed=0)
    planner = RedundancyPlanner(sz.plan_workers)
    space_planner = RedundancyPlanner(
        sz.plan_workers, candidates=analysis.feasible_B(sz.space_width)
    )

    t0 = time.perf_counter()
    ref = planner.plan_cluster(dist, **kw, scenario=sc, backend="python")
    ref_space = space_planner.plan_cluster(dist, **kw, scenario=sc_space, backend="python")
    t_ref = time.perf_counter() - t0

    parts, bad = [], []
    runs = [
        ("f32", planner, sc, ref, False),
        ("f64", planner, sc.replace(dtype="float64"), ref, True),
        ("packed", space_planner, sc_space, ref_space, False),
    ]
    for name, pl, scn, r, wide in runs:
        with x64(wide):
            plan, t = timed_twice(lambda: pl.plan_cluster(dist, **kw, scenario=scn, backend="jax"))
        z = _z_frontier(plan, r, sz.plan_reps, sz.plan_reps)
        parts.append(f"{name} {_fmt(t)} B*={plan.n_batches}/python {r.n_batches} max_z={z:.2f}")
        if plan.n_batches != r.n_batches or not z < 3.0:
            bad.append(name)

    # the same churned cluster replayed as plan_reps lanes in one device
    # call, against the CPU backend: equal unfinished counts, means at 3 sigma
    rep, t = timed_twice(lambda: epochs_run(sz, sc))
    with on_cpu():
        rep_cpu = epochs_run(sz, sc)
    unf, unf_cpu = (int((~np.isfinite(x.finishes)).sum()) for x in (rep, rep_cpu))
    z = _z_means(rep.compute_times.mean(axis=1), rep_cpu.compute_times.mean(axis=1))
    parts.append(
        f"simulate_epochs B=4 {_fmt(t)} unfinished {unf}/CPU {unf_cpu} mean compute z={z:.2f}"
    )
    if unf != unf_cpu or not z < 3.0:
        bad.append("simulate_epochs")
    line = (
        f"[phase 3] plan_cluster: {sz.plan_workers} workers x {sz.plan_reps} reps, churn + "
        f"speeds, Pareto(1, 1.8); python reference {t_ref:.3f}s | " + " | ".join(parts)
    )
    _check(not bad, f"{line} | failed: {bad}")
    return line + " | PASS"


# --------------------------------------------------------------------------
# phase 4: tail-SLO planner
# --------------------------------------------------------------------------


def phase_slo(sz: Sizes) -> str:
    from repro.cluster import SLO
    from repro.core.planner import RedundancyPlanner
    from repro.core.service_time import Exponential, Pareto, ShiftedExponential

    planner = RedundancyPlanner(sz.slo_workers)
    dists = {
        "exponential": (Exponential(1.0), 12.0),
        "shifted_exp": (ShiftedExponential(0.3, 1.0), 15.0),
        "pareto_heavy": (Pareto(1.0, 1.5), 60.0),
    }

    def sweep():
        return {
            name: planner.plan_slo(
                [dist],
                SLO(quantile=0.99, target_s=target, arrival_rate=0.05),
                n_jobs=sz.slo_jobs,
                n_reps=sz.slo_reps,
                seed=0,
                schedulers=("fifo_gang", "packed"),
            )
            for name, (dist, target) in dists.items()
        }

    def verdicts(plan):
        key = lambda c: (c.scheduler, c.workers_per_job, c.n_batches, c.replication)  # noqa: E731
        best = None if plan.best is None else key(plan.best)
        return plan.feasible, best, {key(c): c.feasible for c in plan.candidates}

    chip, t = timed_twice(sweep)
    with on_cpu():
        ref = sweep()
    bad = [n for n in dists if verdicts(chip[n]) != verdicts(ref[n])]
    picks = ", ".join(f"{n}: {verdicts(p)[1]}" for n, p in chip.items())
    n_cand = sum(len(p.candidates) for p in chip.values())
    line = (
        f"[phase 4] plan_slo p99: {sz.slo_workers} workers, {sz.slo_jobs} jobs x "
        f"{sz.slo_reps} reps, {n_cand} candidates | {_fmt(t)} | best {picks} | "
        f"verdicts and picks equal to CPU: {not bad}"
    )
    _check(not bad, f"{line} | differ: {bad}")
    return line + " | PASS"


# --------------------------------------------------------------------------
# phase 5: Pallas cover kernel, compiled
# --------------------------------------------------------------------------


def _best_of(fn, n: int = 5) -> float:
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def phase_cover(sz: Sizes, interpret: bool = False) -> str:
    from repro.core.simulator import gang_cover_times
    from repro.kernels.cover import masked_cover_times

    reps, b_pad, r_pad = sz.cover_grid
    draws = jax.random.exponential(jax.random.key(0), sz.cover_grid)
    pairs = sorted(
        {(b_pad, r_pad), (b_pad, 1), (1, r_pad), (b_pad // 2, 2), (4, 4), (5, 3), (3, r_pad // 2)}
    )
    oracle = jax.jit(gang_cover_times)

    def kernel(b, r):
        return masked_cover_times(draws, jnp.int32(b), jnp.int32(r), interpret=interpret)

    def run_all():
        return [jax.block_until_ready(kernel(b, r)) for b, r in pairs]

    got, t = timed_twice(run_all)
    bad = [
        (b, r) for (b, r), g in zip(pairs, got)
        if not np.array_equal(np.asarray(g), np.asarray(oracle(draws, jnp.int32(b), jnp.int32(r))))
    ]
    b, r = b_pad // 2, 2
    t_kernel = _best_of(lambda: kernel(b, r))
    t_xla = _best_of(lambda: oracle(draws, jnp.int32(b), jnp.int32(r)))
    line = (
        f"[phase 5] masked_cover_times(interpret={interpret}) on {sz.cover_grid}, "
        f"{len(pairs)} (B, r) pairs | {_fmt(t)} | equal to gang_cover_times: {not bad} | "
        f"info at (B, r)=({b}, {r}): pallas_s={t_kernel:.6f} xla_s={t_xla:.6f}"
    )
    _check(not bad, f"{line} | differ at {bad}")
    return line + " | PASS"


# --------------------------------------------------------------------------
# phase 6: live runtime beside the chip
# --------------------------------------------------------------------------


def phase_runtime(sz: Sizes) -> str:
    from repro.cluster import Scenario
    from repro.cluster.runtime import LiveJob, Runtime, replay_trace

    scenario = Scenario(n_batches=2, cancel_redundant=True)
    jobs = [
        LiveJob(job_id=i, costs=(0.05,) * 4, skew=0.5, name=f"smoke-{i}")
        for i in range(sz.runtime_jobs)
    ]

    def run():
        runtime = Runtime(sz.runtime_workers, scenario, spawn="subprocess")
        report = runtime.run(jobs, timeout_s=120.0)
        twin = replay_trace(report.trace, sz.runtime_workers, scenario)
        exact = twin.accounting() == report.accounting() and [
            r.finish for r in twin.records
        ] == [r.finish for r in sorted(report.records, key=lambda rec: rec.job_id)]
        return report, exact

    (report, exact), t = timed_twice(run)
    line = (
        f"[phase 6] Runtime(spawn='subprocess'): {sz.runtime_jobs} jobs on "
        f"{sz.runtime_workers} worker processes, {len(report.trace)} trace events | "
        f"cold_s={t['cold_s']:.3f} warm_s={t['warm_s']:.3f} | replay_trace exact: {exact}"
    )
    _check(exact, line)
    return line + " | PASS"


# --------------------------------------------------------------------------
# --chips 4: the sharded epoch-scan lanes
# --------------------------------------------------------------------------


def _reports_equal(a, b) -> list:
    bad = []
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            x, y = np.asarray(x), np.asarray(y)
            eq = np.array_equal(x, y, equal_nan=x.dtype.kind == "f")
        else:
            eq = x == y
        if not eq:
            bad.append(f.name)
    return bad


def phase_sharded(sz: Sizes, devices: int = 4) -> str:
    from repro.cluster import epoch_scan
    from repro.core.planner import RedundancyPlanner
    from repro.core.service_time import Pareto

    dist = Pareto(1.0, 1.8)
    sc = churned_scenario(sz).replace(jobs_per_stream=sz.jobs_per_stream)
    cands = RedundancyPlanner(sz.plan_workers).candidates
    spans = []
    build = epoch_scan._get_runner

    def spying_runner(cfg):
        runner = build(cfg)

        def call(*args):
            out = runner(*args)
            spans.append((cfg.devices, {len(v.sharding.device_set) for v in out.values()}))
            return out

        return call

    def frontier(d):
        return epoch_scan.frontier_job_times_dynamic(
            dist, sz.plan_workers, cands, sz.plan_reps, seed=0, scenario=sc.replace(devices=d)
        )

    epoch_scan._get_runner = spying_runner
    try:
        (f_n, e_n), t_n = timed_twice(lambda: (frontier(devices), epochs_run(sz, sc, devices)))
        (f_1, e_1), t_1 = timed_twice(lambda: (frontier(1), epochs_run(sz, sc, 1)))
    finally:
        epoch_scan._get_runner = build
    frontier_eq = np.array_equal(f_n, f_1)
    bad = _reports_equal(e_n, e_1)
    spread = sorted({n for d, s in spans if d == devices for n in s})
    line = (
        f"[chips {devices}] frontier_job_times_dynamic ({len(cands)} candidates) + "
        f"simulate_epochs (B=4, {sz.jobs_per_stream} jobs), {sz.plan_workers} workers x "
        f"{sz.plan_reps} reps, churn + speeds | devices={devices} {_fmt(t_n)} | devices=1 "
        f"{_fmt(t_1)} | frontier bit-identical: {frontier_eq} | epochs bit-identical: "
        f"{not bad} | sharded outputs span devices: {spread}"
    )
    _check(frontier_eq and not bad and spread == [devices], f"{line} | differ: {bad}")
    return line + " | PASS"


# --------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (jax.devices()[0] is {devs[0].platform!r}); "
            "this script runs on the chip only"
        )
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but only {len(devs)} TPU devices")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    device = phase_device(args.chips)
    cache = enable_compile_cache()
    print(
        f"[phase 1] device: {device['platform']} {device['kind']} x{device['count']}, "
        f"jax {jax.__version__}, compile cache {cache} | PASS",
        flush=True,
    )
    sz = Sizes()
    if args.chips == 4:
        phases = [lambda: phase_sharded(sz, 4)]
    else:
        phases = [
            lambda: phase_stream(sz),
            lambda: phase_planner(sz),
            lambda: phase_slo(sz),
            lambda: phase_cover(sz),
            lambda: phase_runtime(sz),
        ]
    failed = 0
    for phase in phases:
        try:
            print(phase(), flush=True)
        except Exception as e:  # every phase reports; any failure fails the run
            failed += 1
            traceback.print_exc()
            print(f"FAIL: {type(e).__name__}: {e}", flush=True)
    if failed:
        print(f"chip_smoke: {failed} phase(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
