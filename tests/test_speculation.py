"""Speculative (reactive) replication: engine semantics, invariants, replay.

The hand-computable fixture used throughout: 4 workers, one slow
(speed 1/4), a single job of 4 unit tasks split into B=4 batches (r=1,
so planned redundancy contributes nothing -- every backup is reactive).
With ``Empirical((1.0,))`` every draw is exactly 1.0, so the fast batches
complete at t=1, the straggler would run to t=4, and all arithmetic is
exact in binary floating point (speeds and epochs are powers of two).

Timeline under Speculation(interval=0.25, theta=1.5):
  t=1      three sibling batches complete -> obs median 1.0; the straggler's
           replica started at 0, so it crosses at 0 + 1.5*1.0 = 1.5
  t=1.75   first heartbeat epoch strictly after the crossing with a free
           worker -> ONE backup launched (first lagging batch in order)
  t=2.75   the backup (unit task on a unit-speed worker) finishes first:
           the job covers at 2.75 instead of 4.0
"""
import math
import warnings

import numpy as np
import pytest

from repro.cluster import (
    ClusterEngine,
    Job,
    Scenario,
    Speculation,
    SpeculativePolicy,
    sample_job_times,
)
from repro.cluster.scheduler import JobPlan
from repro.core.service_time import Empirical, Pareto

UNIT = Empirical(samples=(1.0,))
SPEC = Speculation(interval=0.25, theta=1.5)


def run_one(speeds, speculation, *, cancel=True, n_jobs=1, dist=UNIT, seed=0, **kw):
    n = len(speeds)
    jobs = [Job(job_id=i, dist=dist, n_tasks=n) for i in range(n_jobs)]
    engine = ClusterEngine(
        n,
        seed=seed,
        n_batches=kw.pop("n_batches", n),
        cancel_redundant=cancel,
        speeds=speeds,
        speculation=speculation,
        **kw,
    )
    return engine.run(jobs)


# --------------------------------------------------------------------------
# the trigger: median, theta, heartbeat grid
# --------------------------------------------------------------------------


def test_backup_rescues_straggler_at_the_predicted_epoch():
    rep = run_one((1.0, 1.0, 1.0, 0.25), SPEC)
    assert rep.n_speculative == 1
    assert rep.records[0].compute_time == 2.75  # exact: epoch 1.75 + 1.0
    # the original replica's tail is reclaimed by cancellation
    assert rep.cancelled_seconds_saved == 4.0 - 2.75
    base = run_one((1.0, 1.0, 1.0, 0.25), None)
    assert base.n_speculative == 0
    assert base.records[0].compute_time == 4.0


def test_no_backup_when_theta_never_crossed():
    rep = run_one((1.0, 1.0, 1.0, 0.25), Speculation(interval=0.25, theta=10.0))
    assert rep.n_speculative == 0
    assert rep.records[0].compute_time == 4.0


def test_min_observations_gates_the_median():
    # two stragglers leave only 2 completed siblings; demanding 3 means the
    # median never becomes available and no backup launches
    rep = run_one((1.0, 1.0, 0.25, 0.25), Speculation(interval=0.25, theta=1.5, min_observations=3))
    assert rep.n_speculative == 0
    assert rep.records[0].compute_time == 4.0


def test_max_backups_caps_per_job_and_one_launch_per_epoch():
    speeds = (1.0, 1.0, 0.25, 0.25)
    capped = run_one(speeds, Speculation(interval=0.25, theta=1.5, max_backups=1))
    assert capped.n_speculative == 1
    # batch 2 (first lagging in batch order) gets the backup at 1.75 and
    # covers at 2.75; batch 3 stays with its straggler until 4.0
    assert capped.records[0].compute_time == 4.0

    both = run_one(speeds, Speculation(interval=0.25, theta=1.5, max_backups=2))
    assert both.n_speculative == 2
    # one launch per heartbeat: batch 2 at 1.75, batch 3 at the NEXT epoch
    # 2.0 -> covers at 3.0
    assert both.records[0].compute_time == 3.0
    assert both.cancelled_seconds_saved == (4.0 - 2.75) + (4.0 - 3.0)


def test_policy_pure_functions():
    pol = SpeculativePolicy(Speculation(interval=0.25, theta=2.0, min_observations=3))
    assert pol.median([3.0, 1.0]) is None  # below min_observations
    assert pol.median([3.0, 1.0, 2.0]) == 2.0
    assert pol.median([4.0, 1.0, 2.0, 3.0]) == 2.0  # lower median
    assert pol.lagging(4.1, 2.0) and not pol.lagging(4.0, 2.0)  # strict
    assert pol.next_epoch(1.5, 1.0) == 1.75  # first epoch strictly after 1.5
    assert pol.next_epoch(1.75, 1.0) == 2.0  # grid point itself is too early
    assert pol.next_epoch(0.2, 1.0) == 1.25  # past crossing: next after now


# --------------------------------------------------------------------------
# accounting invariants and composition
# --------------------------------------------------------------------------


def test_worker_seconds_invariant_with_speculation():
    """ws(cancel on) + saved == ws(cancel off), exactly, with backups racing."""
    on = run_one((1.0, 1.0, 1.0, 0.25), SPEC, cancel=True)
    off = run_one((1.0, 1.0, 1.0, 0.25), SPEC, cancel=False)
    assert on.n_speculative == off.n_speculative == 1
    assert on.worker_seconds + on.cancelled_seconds_saved == off.worker_seconds
    # without cancellation the covering time is the same (backup still wins)
    assert off.records[0].compute_time == 2.75
    assert off.cancelled_seconds_saved == 0.0


def test_speculation_is_deterministic_and_composes_with_churn():
    dist = Pareto(1.0, 1.5)
    spec = Speculation(interval=0.23, theta=2.0)
    runs = []
    for _ in range(2):
        jobs = [Job(job_id=i, dist=dist, n_tasks=8) for i in range(30)]
        from repro.cluster import ChurnProcess

        eng = ClusterEngine(
            8,
            seed=5,
            n_batches=8,
            cancel_redundant=True,
            speculation=spec,
            churn=ChurnProcess(fail_rate=0.02, mean_downtime=2.0),
        )
        runs.append(eng.run(jobs))
    assert np.array_equal(runs[0].compute_times, runs[1].compute_times)
    assert runs[0].n_speculative == runs[1].n_speculative
    assert runs[0].worker_seconds == runs[1].worker_seconds
    assert np.isfinite(runs[0].compute_times).all()


def test_speculation_reduces_pareto_tail_latency():
    """On a heavy tail with r=1, reactive backups must beat no-redundancy."""
    dist = Pareto(1.0, 1.2)
    times = {}
    for name, spec in [("off", None), ("on", Speculation(interval=0.23, theta=2.0))]:
        jobs = [Job(job_id=i, dist=dist, n_tasks=8) for i in range(120)]
        eng = ClusterEngine(
            8, seed=3, n_batches=8, cancel_redundant=True, speculation=spec
        )
        times[name] = eng.run(jobs)
    assert times["on"].n_speculative > 0
    assert times["on"].compute_times.mean() < times["off"].compute_times.mean()


def test_speculation_under_space_sharing_uses_own_allocation_first():
    # two 2-worker jobs side by side; job 0's second batch straggles on w1
    # and is backed up on its own freed worker w0, not on job 1's subset
    n = 4
    speeds = (1.0, 0.25, 1.0, 1.0)
    jobs = [Job(job_id=i, dist=UNIT, n_tasks=2) for i in range(2)]
    eng = ClusterEngine(
        n,
        seed=0,
        n_batches=2,
        cancel_redundant=True,
        speeds=speeds,
        speculation=SPEC,
        scheduler="packed",
        workers_per_job=2,
    )
    rep = eng.run(jobs)
    assert rep.n_speculative == 1
    recs = {r.job_id: r for r in rep.records}
    assert recs[1].compute_time == 1.0  # untouched by job 0's backup
    assert recs[0].compute_time == 2.75


# --------------------------------------------------------------------------
# scripted replay (the live-trace mode)
# --------------------------------------------------------------------------


def test_scripted_launch_times_replay_the_grid_run_exactly():
    grid = run_one((1.0, 1.0, 1.0, 0.25), SPEC)
    scripted = run_one((1.0, 1.0, 1.0, 0.25), SPEC, speculation_times=(1.75,))
    assert scripted.n_speculative == grid.n_speculative == 1
    assert scripted.records[0].compute_time == grid.records[0].compute_time
    assert scripted.worker_seconds == grid.worker_seconds
    assert scripted.cancelled_seconds_saved == grid.cancelled_seconds_saved


def test_scripted_replay_diverging_stamp_raises():
    with pytest.raises(RuntimeError, match="speculation replay diverged"):
        run_one((1.0, 1.0, 1.0, 0.25), SPEC, speculation_times=(0.5,))


def test_scripted_times_require_the_policy():
    with pytest.raises(ValueError, match="speculation_times"):
        ClusterEngine(4, speculation_times=(1.0,))


# --------------------------------------------------------------------------
# Scenario plumbing and validation
# --------------------------------------------------------------------------


def test_speculation_config_validates():
    for bad in (
        dict(interval=0.0),
        dict(theta=-1.0),
        dict(min_observations=0),
        dict(max_backups=0),
    ):
        with pytest.raises(ValueError):
            Speculation(**bad)


def test_scenario_rejects_speculation_with_replanning():
    from repro.cluster import ReplanConfig

    sc = Scenario(speculation=SPEC, replan=ReplanConfig())
    with pytest.raises(ValueError, match="mutually exclusive"):
        sc.validate(n_workers=4, backend="python")


def test_scenario_speculation_is_dynamic_and_python_only_for_space():
    """Speculation is a dynamic knob; under space sharing both backends take it."""
    assert Scenario(speculation=SPEC).is_dynamic
    sc = Scenario(speculation=SPEC, workers_per_job=2)
    sc.validate(n_workers=4, backend="python")
    sc.validate(n_workers=4, backend="jax")
    for policy in ("packed", "balanced"):
        Scenario(speculation=SPEC, scheduler=policy, workers_per_job=2).validate(
            n_workers=4, backend="jax")
    Scenario(speculation=SPEC, job_plans=[JobPlan()]).validate(n_workers=4, backend="jax")


# --------------------------------------------------------------------------
# the jax lane: simulate_epochs replays the engine's speculation exactly
# --------------------------------------------------------------------------


def _scan_one(speeds, speculation, *, cancel=True, n_jobs=1, n_batches=None,
              dist=UNIT, seed=0, n_reps=2, dtype="float32", **kw):
    """simulate_epochs under the same fixture run_one builds for the engine."""
    from repro.cluster import simulate_epochs

    n = len(speeds)
    sc = Scenario(
        speculation=speculation, speeds=speeds, cancel_redundant=cancel,
        dtype=dtype, **kw,
    )
    return simulate_epochs(
        dist, n, n_batches or n, np.zeros(n_jobs), n_reps, seed=seed, scenario=sc
    )


def _assert_scan_matches_engine(er, sr):
    """Every lane reproduces the engine's times and accounting bit-for-bit
    (the fixture's values are all exactly representable in float32)."""
    e_fin = np.array([r.compute_time for r in er.records])
    for lane in range(sr.finishes.shape[0]):
        s_fin = np.asarray(sr.finishes[lane]) - np.asarray(sr.starts[lane])
        assert np.array_equal(s_fin, e_fin), (lane, s_fin, e_fin)
        assert float(sr.worker_seconds[lane]) == er.worker_seconds
        assert float(sr.cancelled_seconds_saved[lane]) == er.cancelled_seconds_saved
        assert int(sr.n_speculative[lane]) == er.n_speculative
        assert int(sr.n_worker_failures[lane]) == er.n_worker_failures
        assert int(sr.n_replicas_rescued[lane]) == er.n_replicas_rescued


FIXTURES = [
    # (name, speeds, speculation, cancel)
    ("backup-cancel", (1.0, 1.0, 1.0, 0.25), SPEC, True),
    ("backup-nocancel", (1.0, 1.0, 1.0, 0.25), SPEC, False),
    ("theta-never-crossed", (1.0, 1.0, 1.0, 0.25), Speculation(interval=0.25, theta=10.0), True),
    (
        "min-obs-gate",
        (1.0, 1.0, 0.25, 0.25),
        Speculation(interval=0.25, theta=1.5, min_observations=3),
        True,
    ),
    (
        "max-backups-1",
        (1.0, 1.0, 0.25, 0.25),
        Speculation(interval=0.25, theta=1.5, max_backups=1),
        True,
    ),
    (
        "two-backups-staggered",
        (1.0, 1.0, 0.25, 0.25),
        Speculation(interval=0.25, theta=1.5, max_backups=2),
        True,
    ),
]


@pytest.mark.parametrize("name,speeds,spec,cancel", FIXTURES, ids=[f[0] for f in FIXTURES])
def test_jax_scan_matches_engine_exactly(name, speeds, spec, cancel):
    """The trigger (median, theta, heartbeat grid, one launch per firing),
    the winner-duration observations, and the cancellation accounting all
    replay the event engine exactly on the hand-computable fixture."""
    er = run_one(speeds, spec, cancel=cancel)
    sr = _scan_one(speeds, spec, cancel=cancel)
    _assert_scan_matches_engine(er, sr)


def test_jax_scan_speculation_composes_with_churn_exactly():
    """w0 finishes its batch at t=1 and is killed idle at t=1.25: the 1.75
    backup must land on w1 (lowest *alive* free worker) on both substrates."""
    from repro.cluster import ChurnSchedule

    speeds = (1.0, 1.0, 1.0, 0.25)
    sched = ChurnSchedule(times=(1.25, 5.0), wids=(0, 0), ups=(False, True))
    er = run_one(speeds, SPEC, churn_schedule=sched)
    sr = _scan_one(speeds, SPEC, churn_schedule=sched)
    assert er.n_worker_failures == 1 and er.n_speculative == 1
    assert er.records[0].compute_time == 2.75
    _assert_scan_matches_engine(er, sr)


def test_jax_scan_speculation_multi_job_resets_per_dispatch():
    """Three queued jobs each get their own observation window and backup
    budget; per-job spec_used/median reset at dispatch on both substrates."""
    er = run_one((1.0, 1.0, 1.0, 0.25), SPEC, n_jobs=3)
    sr = _scan_one((1.0, 1.0, 1.0, 0.25), SPEC, n_jobs=3)
    assert er.n_speculative == 3
    _assert_scan_matches_engine(er, sr)


def test_jax_scan_speculation_with_planned_redundancy():
    """b=2, r=2: planned replicas already cover the stragglers, so the
    reactive layer stays silent -- identically on both substrates."""
    er = run_one((1.0, 1.0, 0.25, 0.25), SPEC, n_batches=2)
    sr = _scan_one((1.0, 1.0, 0.25, 0.25), SPEC, n_batches=2)
    assert er.n_speculative == 0
    _assert_scan_matches_engine(er, sr)


def test_jax_scan_speculation_f64_lanes_exact():
    import jax

    er = run_one((1.0, 1.0, 1.0, 0.25), SPEC)
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        sr = _scan_one((1.0, 1.0, 1.0, 0.25), SPEC, dtype="float64")
    finally:
        jax.config.update("jax_enable_x64", prev)
    _assert_scan_matches_engine(er, sr)


def test_jax_scan_speculation_stochastic_pareto():
    """On a heavy tail the two substrates draw different task times, so we
    compare mean job latency by a 3-sigma z-test across independent runs."""
    from repro.cluster import simulate_epochs

    dist = Pareto(1.0, 1.5)
    spec = Speculation(interval=0.23, theta=2.0)
    n, n_jobs = 8, 40
    eng = []
    for seed in range(6):
        rep = run_one(
            tuple([1.0] * n), spec, n_jobs=n_jobs, dist=dist, seed=seed, n_batches=n
        )
        eng.append(rep.compute_times.mean())
    eng = np.array(eng)
    sc = Scenario(speculation=spec, cancel_redundant=True)
    sr = simulate_epochs(dist, n, n, np.zeros(n_jobs), 24, seed=100, scenario=sc)
    assert (np.asarray(sr.n_speculative) > 0).all()
    lanes = (np.asarray(sr.finishes) - np.asarray(sr.starts)).mean(axis=1)
    se = math.sqrt(eng.var(ddof=1) / len(eng) + lanes.var(ddof=1) / len(lanes))
    z = (eng.mean() - lanes.mean()) / se
    assert abs(z) < 3.0, z


def test_sample_job_times_speculation_kwarg_warns_scenario_does_not():
    with pytest.warns(DeprecationWarning, match="sample_job_times"):
        loose = sample_job_times(UNIT, 4, 4, 2, seed=0, speculation=SPEC, speeds=(1, 1, 1, 0.25))
    sc = Scenario(speculation=SPEC, speeds=(1, 1, 1, 0.25))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        scoped = sample_job_times(UNIT, 4, 4, 2, seed=0, scenario=sc)
    assert np.array_equal(loose, scoped)
    assert (scoped == 2.75).all()


# --------------------------------------------------------------------------
# the space lane: speculation under space sharing, engine vs jax, float64
# --------------------------------------------------------------------------


def _x64():
    import jax

    class _Ctx:
        def __enter__(self):
            self.prev = jax.config.jax_enable_x64
            jax.config.update("jax_enable_x64", True)

        def __exit__(self, *exc):
            jax.config.update("jax_enable_x64", self.prev)

    return _Ctx()


def _assert_space_exact(er, vr):
    """One float64 space-lane rep replays the engine's trajectory and accounting."""
    e_start = np.array([r.start for r in er.records])
    e_fin = np.array([r.finish for r in er.records])
    assert np.allclose(vr.starts[0], e_start, rtol=1e-9, atol=1e-12)
    assert np.allclose(vr.finishes[0], e_fin, rtol=1e-9, atol=1e-12)
    assert np.isclose(vr.worker_seconds[0], er.worker_seconds, rtol=1e-9)
    assert np.isclose(vr.cancelled_seconds_saved[0], er.cancelled_seconds_saved,
                      rtol=1e-9, atol=1e-9)
    assert int(vr.n_speculative[0]) == er.n_speculative
    assert int(vr.n_worker_failures[0]) == er.n_worker_failures
    assert int(vr.n_replicas_rescued[0]) == er.n_replicas_rescued


def test_jax_space_lane_backs_up_on_its_own_allocation_first():
    """The engine fixture above on the jax space lane: job 0's straggling batch is
    backed up once on its own freed worker at 1.75 and covers at 2.75; job 1's subset
    is untouched."""
    from repro.cluster import simulate_epochs

    speeds = (1.0, 0.25, 1.0, 1.0)
    er = ClusterEngine(
        4, seed=0, n_batches=2, cancel_redundant=True, speeds=speeds, speculation=SPEC,
        scheduler="packed", workers_per_job=2,
    ).run([Job(job_id=i, dist=UNIT, n_tasks=2) for i in range(2)])
    sc = Scenario(speculation=SPEC, speeds=speeds, cancel_redundant=True, n_tasks=2,
                  scheduler="packed", workers_per_job=2, dtype="float64")
    with _x64():
        vr = simulate_epochs(UNIT, 4, 2, np.zeros(2), 2, seed=0, scenario=sc)
    assert (vr.n_speculative == 1).all()
    assert (vr.compute_times == np.array([2.75, 1.0])).all()
    _assert_space_exact(er, vr)


# a shared timeline of three failures and three rejoins against six distinct
# speeds (tests/test_space_sharing.py's), and a policy that fires often: every
# combination below launches backups, and the churned ones rescue too
SPACE_SCHED_TIMES = (0.7, 1.9, 3.35, 5.1, 7.77, 9.4)
SPACE_SPEEDS = (1.0, 1.5, 0.7, 1.2, 0.9, 1.1)
SPACE_SPEC = Speculation(interval=0.25, theta=1.2, min_observations=1, max_backups=2)


@pytest.mark.parametrize("churn", [False, True], ids=["static", "churn"])
@pytest.mark.parametrize("cancel", [False, True], ids=["cancel_off", "cancel_on"])
@pytest.mark.parametrize("policy", ["packed", "balanced"])
def test_jax_space_lane_speculation_matches_engine_exactly(policy, cancel, churn):
    """Two 3-worker jobs side by side (B = 3, r = 1) on six workers: the space lane's
    backups -- trigger, own-allocation-first placement, one launch an epoch, the
    per-job budget, cancellation of the losers, and rescues of killed backups --
    replay the engine exactly in float64."""
    from repro.cluster import ChurnSchedule, simulate_epochs

    d = Empirical(samples=(1.3,))
    n, n_jobs, wpj = 6, 8, 3
    sched = ChurnSchedule(times=SPACE_SCHED_TIMES, wids=(2, 5, 2, 0, 5, 0),
                          ups=(False, False, True, False, True, True)) if churn else None
    er = ClusterEngine(
        n, seed=3, n_batches=3, cancel_redundant=cancel, speeds=SPACE_SPEEDS,
        churn_schedule=sched, scheduler=policy, workers_per_job=wpj, speculation=SPACE_SPEC,
    ).run([Job(job_id=i, dist=d, n_tasks=n) for i in range(n_jobs)])
    sc = Scenario(cancel_redundant=cancel, speeds=SPACE_SPEEDS, churn_schedule=sched,
                  scheduler=policy, workers_per_job=wpj, speculation=SPACE_SPEC,
                  dtype="float64")
    with _x64():
        vr = simulate_epochs(d, n, 3, np.zeros(n_jobs), 1, seed=3, scenario=sc)
    assert er.n_speculative > 0
    assert (er.n_replicas_rescued > 0) == churn
    starts = np.array([r.start for r in er.records])
    fins = np.array([r.finish for r in er.records])
    assert (starts[1:] < fins[:-1]).any()  # the jobs overlap: space sharing is exercised
    _assert_space_exact(er, vr)


def _generated_space_case(seed):
    """A seeded scenario: policy, width, B, cancellation, churn, arrivals and policy
    knobs drawn at random, with irregular speeds and times so that no two events tie."""
    from repro.cluster import JobPlan as Plan

    import strategies as scn

    rng = np.random.default_rng(seed)
    n = int(rng.integers(3, 9))
    speeds = tuple(float(x) for x in rng.uniform(0.3, 2.0, size=n))
    policy = ["packed", "balanced", "fifo_gang"][int(rng.integers(0, 3))]
    wpj = int(rng.integers(1, n + 1))
    b = int(rng.integers(1, wpj + 1))
    cancel = bool(rng.integers(0, 2))
    churn = bool(rng.integers(0, 2))
    n_jobs = int(rng.integers(1, 7))
    d = Empirical(samples=(float(rng.choice([1.0, 1.37, 2.13])),))
    spec = Speculation(interval=float(rng.choice([0.23, 0.37, 0.11])),
                       theta=float(rng.choice([1.1, 1.5, 2.0])),
                       min_observations=int(rng.integers(1, 3)),
                       max_backups=int(rng.integers(1, 3)))
    arr = np.sort(rng.uniform(0, 3, size=n_jobs)) if rng.integers(0, 2) else np.zeros(n_jobs)
    sched = scn.seeded_schedule(n, seed=seed, fail_rate=0.07, mean_downtime=1.2) if churn else None
    space = dict(scheduler=policy, workers_per_job=wpj)
    plan = None
    if policy == "fifo_gang":  # per-job plans route the gang regime onto the space lane
        plan, space = Plan(), dict(scheduler="fifo_gang")
    return n, speeds, b, cancel, sched, n_jobs, d, spec, arr, plan, space


# 125 and 220: grid points off the binary grid (the lane divides as the engine
# does); 171 and 193: a superseded timer at a live check's own instant
@pytest.mark.parametrize("seed", [7, 34, 125, 171, 193, 220])
def test_jax_space_lane_speculation_generated_scenarios(seed):
    from repro.cluster import simulate_epochs

    n, speeds, b, cancel, sched, n_jobs, d, spec, arr, plan, space = _generated_space_case(seed)
    jobs = [Job(job_id=i, dist=d, n_tasks=n, arrival=float(arr[i]), plan=plan)
            for i in range(n_jobs)]
    er = ClusterEngine(n, seed=seed, n_batches=b, cancel_redundant=cancel, speeds=speeds,
                       churn_schedule=sched, speculation=spec, **space).run(jobs)
    sc = Scenario(speculation=spec, speeds=speeds, cancel_redundant=cancel,
                  churn_schedule=sched, dtype="float64",
                  job_plans=[plan] if plan is not None else None, **space)
    with _x64():
        vr = simulate_epochs(d, n, b, arr, 1, seed=seed, scenario=sc)
    _assert_space_exact(er, vr)


def test_superseded_timer_does_not_move_a_live_check():
    """A timer invalidated when its job finished can share its instant with the live
    timer of the next job: the engine still checks at that instant (seed 171's
    scenario launches its second backup at 10.35)."""
    n, speeds, b, cancel, sched, n_jobs, d, spec, arr, plan, space = _generated_space_case(171)
    rep = ClusterEngine(n, seed=171, n_batches=b, cancel_redundant=cancel, speeds=speeds,
                        churn_schedule=sched, speculation=spec, **space).run(
        [Job(job_id=i, dist=d, n_tasks=n, arrival=float(arr[i])) for i in range(n_jobs)])
    assert rep.n_speculative == 2
