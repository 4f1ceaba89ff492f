"""Space-sharing scheduler differential harness: engine vs jax space lane.

The space-sharing subsystem (``cluster/scheduler.py`` + the epoch scan's
space lane) runs concurrent jobs on disjoint worker subsets under per-job
heterogeneous (B, r, cancellation) plans.  The contract mirrors the dynamic
harness in ``tests/test_epoch_scan.py``:

  * ``fifo_gang`` is *bit-compatible* with the pre-scheduler engine on the
    same seeds, and the space lane in gang mode reproduces the legacy lane;
  * on a shared churn schedule with degenerate (constant) service times the
    jax space lane replays the engine **exactly** (float64 lanes: the
    engine's f64 arithmetic is mirrored formula-for-formula, so even
    tie-breaking decisions coincide) for all three policies;
  * with random draws, per-stream mean compute/response times agree at
    3 sigma;
  * accounting identities (cancellation reclaims exactly the redundant
    tails; worker-seconds conservation) hold per rep within the backend.

Scenario configs come from ``tests/strategies.py`` (the space-shared
generators added with this subsystem).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # test extra not installed: seeded fallback engine
    from _hypothesis_compat import given, settings, st

import strategies as scn
from repro.cluster import (
    ChurnProcess,
    ClusterEngine,
    Job,
    JobPlan,
    Scenario,
    make_scheduler,
    sample_job_times,
    simulate_epochs,
    simulate_fifo,
)
from repro.cluster.scheduler import BalancedScheduler, PackedScheduler
from repro.cluster.workers import ChurnSchedule
from repro.core.planner import RedundancyPlanner
from repro.core.service_time import Empirical, Exponential, Pareto

# the crafted shared timeline every exact test replays: three failures, three
# rejoins, distinct event times, against six distinct worker speeds
SCHED = ChurnSchedule(
    times=(0.7, 1.9, 3.35, 5.1, 7.77, 9.4),
    wids=(2, 5, 2, 0, 5, 0),
    ups=(False, False, True, False, True, True),
)
SPEEDS = (1.0, 1.5, 0.7, 1.2, 0.9, 1.1)


def _records(report):
    starts = np.array([r.start for r in report.records])
    fins = np.array([r.finish for r in report.records])
    return starts, fins


def _z_mean(a: np.ndarray, b: np.ndarray) -> float:
    se = np.sqrt(a.var() / a.size + b.var() / b.size)
    if se == 0.0:
        return 0.0 if a.mean() == b.mean() else np.inf
    return float(abs(a.mean() - b.mean()) / se)


def _x64():
    import jax

    class _Ctx:
        def __enter__(self):
            self.prev = jax.config.jax_enable_x64
            jax.config.update("jax_enable_x64", True)

        def __exit__(self, *exc):
            jax.config.update("jax_enable_x64", self.prev)

    return _Ctx()


def _assert_exact(er, vr, rtol=1e-9):
    """Full-trajectory + accounting equality, engine vs one space-lane rep."""
    e_start, e_fin = _records(er)
    assert np.allclose(vr.starts[0], e_start, rtol=rtol, atol=1e-12)
    assert np.allclose(vr.finishes[0], e_fin, rtol=rtol, atol=1e-12)
    ea, va = er.accounting(), vr.accounting()
    assert np.isclose(va["worker_seconds"][0], ea["worker_seconds"], rtol=rtol)
    assert np.isclose(
        va["cancelled_seconds_saved"][0], ea["cancelled_seconds_saved"], rtol=rtol, atol=1e-9
    )
    assert va["n_worker_failures"][0] == ea["n_worker_failures"]
    assert va["n_replicas_rescued"][0] == ea["n_replicas_rescued"]
    vt = vr.epoch_times[0]
    assert np.allclose(vt[np.isfinite(vt)], np.asarray(er.epoch_times), rtol=rtol)


# --------------------------------------------------------------------------
# fifo_gang reduces to the current behavior (the bit-compat identity)
# --------------------------------------------------------------------------


def test_fifo_gang_engine_identity():
    """The scheduler refactor must leave the default engine path untouched:
    an explicit fifo_gang scheduler replays the default-constructed engine
    bit-for-bit on a churned, heterogeneous, cancelling workload."""
    d = Exponential(1.0)
    sched = scn.seeded_schedule(6, seed=9, fail_rate=0.08, mean_downtime=1.0)
    kw = dict(seed=5, n_batches=3, cancel_redundant=True, speeds=SPEEDS, churn_schedule=sched)
    jobs = lambda: [Job(job_id=i, dist=d, n_tasks=6) for i in range(12)]  # noqa: E731
    base = ClusterEngine(6, **kw).run(jobs())
    explicit = ClusterEngine(6, scheduler="fifo_gang", workers_per_job=None, **kw).run(jobs())
    assert _records(base)[0].tolist() == _records(explicit)[0].tolist()
    assert _records(base)[1].tolist() == _records(explicit)[1].tolist()
    assert base.accounting() == explicit.accounting()


def test_packed_full_width_requests_degenerate_to_gang():
    """workers_per_job = n on a static cluster: packed placement admits one
    job at a time on the whole pool -- exactly the gang schedule."""
    d = Exponential(1.0)

    def jobs():
        return [Job(job_id=i, dist=d, n_tasks=6, arrival=0.4 * i) for i in range(10)]

    for cancel in (False, True):
        gang = ClusterEngine(6, seed=2, n_batches=2, cancel_redundant=cancel).run(jobs())
        packed = ClusterEngine(
            6, seed=2, n_batches=2, cancel_redundant=cancel,
            scheduler="packed", workers_per_job=6,
        ).run(jobs())
        assert _records(gang)[0].tolist() == _records(packed)[0].tolist()
        assert _records(gang)[1].tolist() == _records(packed)[1].tolist()
        assert gang.accounting() == packed.accounting()


def test_gang_mode_space_lane_matches_legacy_lane():
    """scheduler='fifo_gang' + an all-None JobPlan forces the space lane in
    gang mode: it must reproduce the legacy single-gang lane and the engine
    exactly (float64) on the shared schedule."""
    d = Empirical(samples=(1.3,))
    with _x64():
        legacy = simulate_epochs(
            d, 6, 3, np.zeros(8), 1, seed=3, speeds=SPEEDS, churn_schedule=SCHED,
            dtype="float64",
        )
        space = simulate_epochs(
            d, 6, 3, np.zeros(8), 1, seed=3, speeds=SPEEDS, churn_schedule=SCHED,
            job_plans=[JobPlan()], dtype="float64",
        )
    assert np.allclose(space.starts, legacy.starts, rtol=1e-12)
    assert np.allclose(space.finishes, legacy.finishes, rtol=1e-12)
    assert np.isclose(space.worker_seconds[0], legacy.worker_seconds[0], rtol=1e-12)
    assert space.n_replicas_rescued[0] == legacy.n_replicas_rescued[0]
    jobs = [Job(job_id=i, dist=d, n_tasks=6) for i in range(8)]
    er = ClusterEngine(6, seed=3, n_batches=3, speeds=SPEEDS, churn_schedule=SCHED).run(jobs)
    _assert_exact(er, space)


# --------------------------------------------------------------------------
# exact differential: shared schedule + degenerate service times, 3 policies
# --------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["fifo_gang", "packed", "balanced"])
@pytest.mark.parametrize("cancel", [False, True], ids=["cancel_off", "cancel_on"])
def test_exact_trajectory_space_shared_schedule(policy, cancel):
    """Constant task times + a shared churn schedule pin every draw: the
    space lane must replay the engine's trajectory, rescues, regrants, and
    accounting exactly for every policy (f64 lanes tie-break like the f64
    engine)."""
    d = Empirical(samples=(1.3,))
    n, n_jobs, wpj = 6, 8, 2
    jobs = [Job(job_id=i, dist=d, n_tasks=n) for i in range(n_jobs)]
    er = ClusterEngine(
        n, seed=3, n_batches=2, cancel_redundant=cancel, speeds=SPEEDS,
        churn_schedule=SCHED, scheduler=policy, workers_per_job=wpj,
    ).run(jobs)
    with _x64():
        vr = simulate_epochs(
            d, n, 2, np.zeros(n_jobs), 1, seed=3, cancel_redundant=cancel,
            speeds=SPEEDS, churn_schedule=SCHED, scheduler=policy,
            workers_per_job=wpj, dtype="float64",
        )
    if policy != "fifo_gang":
        # narrow jobs overlap (space sharing exercised), and the r = 1
        # subsets make every failure a rescue
        e_start, e_fin = _records(er)
        assert (e_start[1:] < e_fin[:-1]).any()
        assert er.n_replicas_rescued > 0
    _assert_exact(er, vr)


def test_exact_heterogeneous_job_plans_shared_schedule():
    """Per-job (workers, B, cancellation) plans -- the regime the gang
    engine cannot express -- replay exactly on both backends, including
    arrivals mid-stream."""
    d = Empirical(samples=(1.7,))
    n, n_jobs = 6, 9
    arr = np.array([0.0, 0.0, 0.8, 1.2, 2.9, 4.0, 5.5, 6.1, 8.0])
    plans = scn.seeded_job_plans(n, seed=4)
    for policy in ("packed", "balanced"):
        jobs = [
            Job(job_id=i, dist=d, n_tasks=n, arrival=float(arr[i]), plan=plans[i % len(plans)])
            for i in range(n_jobs)
        ]
        er = ClusterEngine(
            n, seed=7, n_batches=3, speeds=SPEEDS, churn_schedule=SCHED,
            scheduler=policy, workers_per_job=2,
        ).run(jobs)
        with _x64():
            vr = simulate_epochs(
                d, n, 3, arr, 1, seed=7, speeds=SPEEDS, churn_schedule=SCHED,
                scheduler=policy, workers_per_job=2, job_plans=plans, dtype="float64",
            )
        _assert_exact(er, vr)
        # heterogeneous plans actually ran: at least two distinct B values
        assert len({r.n_batches for r in er.records}) >= 2


@settings(max_examples=4, deadline=None)
@given(
    policy=scn.space_schedulers(),
    wpj=scn.worker_requests(6),
    plans=scn.job_plan_cycles(6),
    seed=st.integers(0, 99),
)
def test_exact_generated_space_scenarios(policy, wpj, plans, seed):
    """Generated scenario grid: any policy x request x plan cycle must stay
    an exact engine replay on a shared schedule with degenerate draws."""
    d = Empirical(samples=(2.1,))
    n, n_jobs = 6, 6
    sched = scn.seeded_schedule(n, seed=seed, fail_rate=0.07, mean_downtime=1.2)
    jobs = [
        Job(job_id=i, dist=d, n_tasks=n, plan=plans[i % len(plans)]) for i in range(n_jobs)
    ]
    er = ClusterEngine(
        n, seed=seed, n_batches=2, speeds=SPEEDS, churn_schedule=sched,
        scheduler=policy, workers_per_job=wpj,
    ).run(jobs)
    with _x64():
        vr = simulate_epochs(
            d, n, 2, np.zeros(n_jobs), 1, seed=seed, speeds=SPEEDS, churn_schedule=sched,
            scheduler=policy, workers_per_job=wpj, job_plans=plans, dtype="float64",
        )
    _assert_exact(er, vr)


# --------------------------------------------------------------------------
# stochastic differential + accounting identities
# --------------------------------------------------------------------------


def test_space_shared_compute_and_response_match_engine():
    """Random draws, shared schedule: per-stream mean compute and response
    agree at 3 sigma between the engine and the space lane."""
    d = Exponential(1.0)
    n, n_jobs, wpj = 6, 18, 3
    sched = scn.seeded_schedule(n, seed=11, fail_rate=0.05, mean_downtime=1.0)
    e_ct, e_rt = [], []
    for s in range(25):
        jobs = [Job(job_id=i, dist=d, n_tasks=n) for i in range(n_jobs)]
        rep = ClusterEngine(
            n, seed=300 + s, n_batches=3, churn_schedule=sched,
            scheduler="packed", workers_per_job=wpj,
        ).run(jobs)
        ct, rt = rep.compute_times, rep.response_times
        e_ct.append(ct[np.isfinite(ct)].mean())
        e_rt.append(rt[np.isfinite(rt)].mean())
    vr = simulate_epochs(
        d, n, 3, np.zeros(n_jobs), 250, seed=1, churn_schedule=sched,
        scheduler="packed", workers_per_job=wpj,
    )
    assert np.isfinite(vr.compute_times).all()
    assert _z_mean(np.array(e_ct), vr.compute_times.mean(axis=1)) < 3.0
    assert _z_mean(np.array(e_rt), vr.response_times.mean(axis=1)) < 3.0


def test_mixed_cancellation_identity_on_scan():
    """Per-job cancellation must not change compute times and must reclaim
    exactly the redundant tails, rep for rep, even when only one job class
    cancels."""
    plans_on = [JobPlan(workers=4, cancel_redundant=True), JobPlan(workers=4)]
    plans_off = [JobPlan(workers=4), JobPlan(workers=4)]
    kw = dict(seed=5, scheduler="packed")
    on = simulate_epochs(Exponential(0.8), 8, 2, np.zeros(10), 50, job_plans=plans_on, **kw)
    off = simulate_epochs(Exponential(0.8), 8, 2, np.zeros(10), 50, job_plans=plans_off, **kw)
    assert np.allclose(on.compute_times, off.compute_times, rtol=1e-4, atol=1e-3)
    assert np.allclose(
        on.worker_seconds + on.cancelled_seconds_saved, off.worker_seconds, rtol=1e-4
    )
    assert (on.cancelled_seconds_saved > 0).all()
    assert (off.cancelled_seconds_saved == 0).all()


def test_space_sharing_cuts_response_time():
    """The headline effect: narrow concurrent jobs beat serial gangs on mean
    response (throughput), on both backends."""
    d = Exponential(1.0)
    arr = np.zeros(12)
    gang = simulate_fifo(d, 8, 2, arr, 200, seed=3)
    packed = simulate_fifo(d, 8, 2, arr, 200, seed=3, scheduler="packed", workers_per_job=4)
    assert packed.response_times.mean() < 0.75 * gang.response_times.mean()
    t_gang = sample_job_times(d, 8, 2, 300, seed=4, backend="python")
    # compute times per job are *worse* per job on fewer workers, but the
    # response win above comes from running 2 jobs at once; check the engine
    # agrees directionally on response via the same fifo surface
    jobs = [Job(job_id=i, dist=d, n_tasks=8) for i in range(12)]
    er_gang = ClusterEngine(8, seed=5, n_batches=2).run(jobs)
    jobs = [Job(job_id=i, dist=d, n_tasks=8) for i in range(12)]
    er_packed = ClusterEngine(8, seed=5, n_batches=2, scheduler="packed", workers_per_job=4).run(
        jobs
    )
    assert er_packed.response_times.mean() < er_gang.response_times.mean()
    assert t_gang.mean() > 0  # sanity: the static sampler still runs


def test_balanced_spreads_load_packed_hammers_low_wids():
    """With sparse 1-wide jobs (every worker idle at each arrival) and
    constant service times, packed keeps re-picking the lowest wid while
    balanced rotates the pool: the per-worker assigned load must come out
    strictly more even under balanced."""
    d = Empirical(samples=(1.0,))
    arr = [Job(job_id=i, dist=d, n_tasks=4, arrival=5.0 * i) for i in range(8)]

    def load(policy):
        eng = ClusterEngine(
            4, seed=0, n_batches=1, scheduler=policy, workers_per_job=1
        )
        eng.run([Job(job_id=j.job_id, dist=d, n_tasks=4, arrival=j.arrival) for j in arr])
        return np.array(eng._load_w)

    lp, lb = load("packed"), load("balanced")
    assert lp.sum() == pytest.approx(lb.sum())  # same total work either way
    assert lb.std() < lp.std()
    assert lb.max() < lp.max()


def test_balanced_is_speed_aware_and_backends_agree():
    """Speed-weighted balanced placement (load = duration / speed), pinned
    differentially under a 2x speed skew.

    The scenario is crafted so the legacy wall-clock metric and the
    speed-weighted one *disagree* on a placement: with speeds (2, 1) and
    sparse 1-wide jobs, the fast worker's wall-clock load catches up to the
    slow worker's after a few jobs (old metric would start alternating),
    while per-speed weighting keeps preferring the fast worker.  The engine
    must steer all but one job to the fast worker, and the f64 jax space
    lane must replay the placements exactly."""
    d = Empirical(samples=(1.0,))
    speeds = (2.0, 1.0)
    n, n_jobs = 2, 6
    arr = np.array([8.0 * i for i in range(n_jobs)])
    jobs = [Job(job_id=i, dist=d, n_tasks=n, arrival=float(arr[i])) for i in range(n_jobs)]
    eng = ClusterEngine(
        n, seed=1, n_batches=1, speeds=speeds, scheduler="balanced", workers_per_job=1
    )
    er = eng.run(jobs)
    # one job takes 2 tasks x 1.0s / speed: 1.0s on the fast worker, 2.0s on
    # the slow one.  Speed-weighted accrual (duration / speed) is 0.5 vs 2.0,
    # so after the slow worker's single job it is never preferred again:
    # 5 jobs on wid 0, 1 on wid 1.  (The legacy wall-clock metric would have
    # sent jobs 4 and 5 back to the slow worker.)
    assert eng._load_w[0] == pytest.approx(5 * (1.0 / 2.0))
    assert eng._load_w[1] == pytest.approx(2.0)
    with _x64():
        vr = simulate_epochs(
            d, n, 1, arr, 1, seed=1, speeds=speeds, scheduler="balanced",
            workers_per_job=1, dtype="float64",
        )
    _assert_exact(er, vr)


def test_balanced_speed_skew_differential_generated():
    """4x speed skew, multi-replica jobs, both backends: placement under the
    speed-weighted metric stays an exact engine replay (f64).  Speeds and
    arrivals are all distinct so no two jobs complete at the same instant
    (tied completions hit a separate, pre-existing lane-granularity limit:
    the engine releases allocations event-by-event within a timestamp while
    the lane batches them per boundary)."""
    d = Empirical(samples=(1.3,))
    speeds = (4.0, 1.0, 3.0, 1.4, 2.2, 0.8)
    n, n_jobs = 6, 10
    arr = np.array([0.0, 0.3, 0.9, 1.4, 2.2, 3.1, 4.4, 5.0, 6.3, 7.1])
    jobs = [Job(job_id=i, dist=d, n_tasks=n, arrival=float(arr[i])) for i in range(n_jobs)]
    er = ClusterEngine(
        6, seed=3, n_batches=2, speeds=speeds, scheduler="balanced", workers_per_job=2
    ).run(jobs)
    with _x64():
        vr = simulate_epochs(
            d, 6, 2, arr, 1, seed=3, speeds=speeds, scheduler="balanced",
            workers_per_job=2, dtype="float64",
        )
    _assert_exact(er, vr)


def test_rep_chunk_bit_identical_space_lane():
    """The chunk/shard reproducibility contract extends to the space lane."""
    d = Exponential(1.0)
    kw = dict(
        seed=7, scheduler="balanced", workers_per_job=3,
        job_plans=scn.seeded_job_plans(6, seed=2), churn_schedule=scn.seeded_schedule(6, seed=3),
    )
    one = simulate_epochs(d, 6, 2, np.zeros(8), 20, **kw)
    for chunk in (7, 20):
        part = simulate_epochs(d, 6, 2, np.zeros(8), 20, rep_chunk=chunk, **kw)
        assert np.array_equal(one.finishes, part.finishes)
        assert np.array_equal(one.starts, part.starts)
        assert np.array_equal(one.worker_seconds, part.worker_seconds)


def test_rep_chunk_bit_identical_space_lane_wide_vmap():
    """Bit-identity holds at 1024 vmapped lanes: a churned, packed, width-4 replay on
    heterogeneous speeds, in one call against 8 reps a call."""
    sc = Scenario(
        scheduler="packed", workers_per_job=4, cancel_redundant=True,
        speeds=tuple(np.random.default_rng(4).uniform(0.5, 2.0, 8)),
        churn=ChurnProcess(fail_rate=0.05, mean_downtime=1.0), churn_pairs_per_worker=16,
    )
    args = (Pareto(1.0, 1.8), 8, 2, np.arange(6) * 0.5, 600)
    one = simulate_epochs(*args, seed=11, scenario=sc)
    part = simulate_epochs(*args, seed=11, scenario=sc.replace(rep_chunk=8))
    assert one.finishes.shape == (600, 6)
    assert np.isfinite(one.finishes).any() and one.n_worker_failures.sum() > 0
    assert np.array_equal(one.finishes, part.finishes)
    assert np.array_equal(one.starts, part.starts)
    assert np.array_equal(one.worker_seconds, part.worker_seconds)
    assert np.array_equal(one.cancelled_seconds_saved, part.cancelled_seconds_saved)
    assert np.array_equal(one.n_replicas_rescued, part.n_replicas_rescued)


# --------------------------------------------------------------------------
# planner integration + validation
# --------------------------------------------------------------------------


def test_plan_cluster_space_backends_agree():
    n = 8
    kw = dict(n_reps=96, seed=0, scheduler="packed", workers_per_job=4)
    pj = RedundancyPlanner(n).plan_cluster(Exponential(1.0), **kw)
    pp = RedundancyPlanner(n).plan_cluster(Exponential(1.0), backend="python", **kw)
    assert pj.source == "cluster_engine:jax"
    assert pp.source == "cluster_engine:python"
    # exponential tails: full diversity *within the granted subset* stays
    # optimal, and both backends agree on the pick
    assert pj.n_batches == pp.n_batches
    # a competing fixed-plan class does not break the sweep surface
    pm = RedundancyPlanner(n).plan_cluster(
        Exponential(1.0), n_reps=64, seed=1, scheduler="balanced", workers_per_job=4,
        job_plans=[None, JobPlan(workers=4, n_batches=4)],
    )
    assert pm.source == "cluster_engine:jax"
    assert np.isfinite(pm.frontier_mean).any()


def test_scheduler_validation_and_construction():
    d = Exponential(1.0)
    with pytest.raises(ValueError, match="scheduler"):
        ClusterEngine(4, scheduler="round_robin")
    with pytest.raises(ValueError, match="scheduler"):
        simulate_epochs(d, 4, 2, np.zeros(2), 2, scheduler="round_robin")
    with pytest.raises(ValueError, match="workers_per_job"):
        ClusterEngine(4, workers_per_job=9)
    with pytest.raises(ValueError, match="workers_per_job"):
        simulate_epochs(d, 4, 2, np.zeros(2), 2, scheduler="packed", workers_per_job=0)
    with pytest.raises(ValueError, match="replan"):
        from repro.cluster import ReplanConfig

        simulate_epochs(
            d, 8, 2, np.zeros(2), 2, scheduler="packed", replan=ReplanConfig(window=16)
        )
    # the python backend rejects the same combinations the jax lane does
    with pytest.raises(ValueError, match="replan"):
        from repro.cluster import ReplanConfig

        sample_job_times(
            d, 8, 2, 4, backend="python", scheduler="packed",
            replan=ReplanConfig(window=16),
        )
    with pytest.raises(ValueError, match="replan/controller"):
        from repro.cluster import OnlineReplanner

        ClusterEngine(8, scheduler="packed", controller=OnlineReplanner(8))
    with pytest.raises(ValueError, match="dtype"):
        simulate_fifo(d, 4, 2, np.zeros(2), 2, dtype="float64")
    with pytest.raises(ValueError, match="JobPlan.workers"):
        JobPlan(workers=0)
    with pytest.raises(ValueError, match="JobPlan.n_batches"):
        JobPlan(n_batches=0)
    with pytest.raises(ValueError, match="job_plans"):
        simulate_epochs(d, 4, 2, np.zeros(2), 2, job_plans=[])
    with pytest.raises(ValueError, match="job_plans"):
        simulate_epochs(d, 4, 2, np.zeros(2), 2, job_plans=["not a plan"])
    # instances pass through make_scheduler untouched
    inst = PackedScheduler()
    assert make_scheduler(inst) is inst
    assert make_scheduler("balanced").__class__ is BalancedScheduler
    assert ClusterEngine(4, scheduler=BalancedScheduler()).scheduler.name == "balanced"
