"""Differential harness: the jax epoch-scan engine vs the Python event engine.

Every dynamic the event engine expresses -- fail/join churn with replica
rescue, heterogeneous speeds, FIFO arrivals, replica cancellation, online
replanning -- must be replayed by ``repro.cluster.epoch_scan`` either

  * **exactly**, when both backends share one churn schedule and a degenerate
    (constant) service-time distribution pins every draw: full trajectory,
    worker-seconds, cancelled-seconds-saved, failure/rescue counts, and epoch
    boundaries match to float32 tolerance; or
  * **in distribution**, at 3 sigma of Monte-Carlo error on compute/response
    times when draws are random, with the accounting *identities* (same-seed
    cancel on/off: identical compute times and ``worker_seconds + saved ==
    worker_seconds(off)``) holding exactly per rep within the backend.

Scenario configs come from ``tests/strategies.py`` -- shared with the engine
and backend suites instead of hand-rolled here.
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
    ReplanConfig,
    sample_job_times,
    simulate_epochs,
    simulate_fifo,
)
from repro.cluster.epoch_scan import frontier_job_times_dynamic
from repro.cluster.workers import ChurnSchedule
from repro.core import analysis
from repro.core.planner import RedundancyPlanner, plan_sweep
from repro.core.service_time import Empirical, Exponential, Pareto, ShiftedExponential


def _z_mean(a: np.ndarray, b: np.ndarray) -> float:
    se = np.sqrt(a.var() / a.size + b.var() / b.size)
    if se == 0.0:  # both degenerate (e.g. deterministic counts): exact compare
        return 0.0 if a.mean() == b.mean() else np.inf
    return float(abs(a.mean() - b.mean()) / se)


def _engine_runs(dist, n, b, n_jobs, n_seeds, seed0=100, **kw):
    """Per-run mean compute/response times from the event engine."""
    ct, rt = [], []
    for s in range(n_seeds):
        jobs = [Job(job_id=i, dist=dist, n_tasks=n) for i in range(n_jobs)]
        rep = ClusterEngine(n, seed=seed0 + s, n_batches=b, **kw).run(jobs)
        t = rep.compute_times
        ct.append(t[np.isfinite(t)].mean())
        r = rep.response_times
        rt.append(r[np.isfinite(r)].mean())
    return np.array(ct), np.array(rt)


# --------------------------------------------------------------------------
# static case: the epoch scan degenerates to the known-good semantics
# --------------------------------------------------------------------------


def test_static_matches_engine_and_fifo_scan():
    d = Exponential(1.0)
    rep = simulate_epochs(d, 8, 4, np.zeros(20), 150, seed=0)
    t_py = sample_job_times(d, 8, 4, 2000, seed=1, backend="python")
    assert _z_mean(rep.compute_times.ravel(), t_py) < 3.0
    # FIFO arrivals, no churn: agrees with the dedicated fifo lax.scan
    arr = np.arange(10) * 1.5
    a = simulate_epochs(Pareto(1.0, 2.0), 8, 2, arr, 400, seed=3)
    f = simulate_fifo(Pareto(1.0, 2.0), 8, 2, arr, 400, seed=9)
    assert _z_mean(a.response_times.mean(axis=1), f.response_times.mean(axis=1)) < 3.0
    assert (a.queue_waits >= -1e-5).all()
    assert (np.diff(a.starts, axis=1) >= -1e-4).all()


def test_deterministic_and_seed_sensitive():
    d = Pareto(1.0, 2.0)
    churn = ChurnProcess(fail_rate=0.05, mean_downtime=1.0)
    a = simulate_epochs(d, 6, 3, np.zeros(8), 5, seed=3, churn=churn, churn_pairs_per_worker=2)
    b = simulate_epochs(d, 6, 3, np.zeros(8), 5, seed=3, churn=churn, churn_pairs_per_worker=2)
    c = simulate_epochs(d, 6, 3, np.zeros(8), 5, seed=4, churn=churn, churn_pairs_per_worker=2)
    assert np.array_equal(a.finishes, b.finishes)
    assert np.array_equal(a.worker_seconds, b.worker_seconds)
    assert not np.array_equal(a.finishes, c.finishes)


def test_churn_horizon_autosizes_and_warns_when_truncation_bites():
    """Sampled churn no longer silently truncates under long streams: the
    default horizon auto-sizes from the stream length (no warning, no flag),
    while an explicit short horizon that the timeline outruns emits a loud
    RuntimeWarning and flags the report."""
    import warnings as _warnings

    d = Empirical(samples=(1.0,))
    churn = ChurnProcess(fail_rate=0.5, mean_downtime=0.5)
    arr = np.arange(40, dtype=np.float64)  # ~40+ s stream, churn period 2.5 s
    with _warnings.catch_warnings():
        _warnings.simplefilter("error", RuntimeWarning)  # auto horizon covers it
        rep = simulate_epochs(d, 4, 2, arr, 3, seed=5, churn=churn)
    assert rep.churn_truncated is not None and not rep.churn_truncated.any()
    with pytest.warns(RuntimeWarning, match="churn horizon"):
        short = simulate_epochs(
            d, 4, 2, arr, 3, seed=5, churn=churn, churn_pairs_per_worker=1
        )
    assert short.churn_truncated.any()
    # churn-free runs carry no flag at all
    assert simulate_epochs(d, 4, 2, arr[:4], 1, seed=5).churn_truncated is None


# --------------------------------------------------------------------------
# exact differential: shared schedule + constant service time pins every draw
# --------------------------------------------------------------------------


@pytest.mark.parametrize("cancel", [False, True], ids=["cancel_off", "cancel_on"])
def test_exact_trajectory_on_shared_schedule(cancel):
    """Constant task times make both backends' draws identical, so churn,
    rescue, cancellation, hetero speeds, and all accounting must replay the
    event engine bit-comparably (float32 tolerance)."""
    d = Empirical(samples=(1.3,))
    n, b, n_jobs = 6, 3, 8
    sched = ChurnSchedule(
        times=(0.7, 1.9, 3.35, 5.1, 7.77, 9.4),
        wids=(2, 5, 2, 0, 5, 0),
        ups=(False, False, True, False, True, True),
    )
    speeds = (1.0, 1.5, 0.7, 1.2, 0.9, 1.1)
    jobs = [Job(job_id=i, dist=d, n_tasks=n) for i in range(n_jobs)]
    er = ClusterEngine(
        n, seed=3, n_batches=b, cancel_redundant=cancel, speeds=speeds, churn_schedule=sched
    ).run(jobs)
    vr = simulate_epochs(
        d,
        n,
        b,
        np.zeros(n_jobs),
        1,
        seed=3,
        cancel_redundant=cancel,
        speeds=speeds,
        churn_schedule=sched,
    )
    e_start = np.array([r.start for r in er.records])
    e_fin = np.array([r.finish for r in er.records])
    assert np.allclose(vr.starts[0], e_start, rtol=1e-4)
    assert np.allclose(vr.finishes[0], e_fin, rtol=1e-4)
    # worker-seconds accounting matches the event engine *exactly* (f32 eps)
    ea, va = er.accounting(), vr.accounting()
    assert set(ea) == set(va)
    assert np.isclose(va["worker_seconds"][0], ea["worker_seconds"], rtol=1e-5)
    assert np.isclose(
        va["cancelled_seconds_saved"][0], ea["cancelled_seconds_saved"], rtol=1e-5, atol=1e-6
    )
    assert va["n_worker_failures"][0] == ea["n_worker_failures"] == 3
    assert va["n_replicas_rescued"][0] == ea["n_replicas_rescued"]
    assert ea["n_replicas_rescued"] > 0
    # same epoch boundaries on both backends
    vt = vr.epoch_times[0]
    assert np.allclose(vt[np.isfinite(vt)], np.asarray(er.epoch_times), rtol=1e-5)


def test_churn_event_unblocking_dispatch_sets_start_time():
    """Regression: when the churn event *itself* frees the gang (a fail
    killing the last straggler), the next job starts at the event time --
    not at the stale last-completion cursor."""
    d = Empirical(samples=(2.0,))
    speeds = (1.0, 0.25)  # worker 1 straggles 4x
    sched = ChurnSchedule(times=(5.0,), wids=(1,), ups=(False,))
    jobs = [Job(job_id=i, dist=d, n_tasks=2) for i in range(2)]
    er = ClusterEngine(2, seed=0, n_batches=1, speeds=speeds, churn_schedule=sched).run(jobs)
    vr = simulate_epochs(d, 2, 1, np.zeros(2), 1, seed=0, speeds=speeds, churn_schedule=sched)
    # job 0's batch wins at t=4 (worker 0), but the straggler holds the gang
    # until its worker fails at t=5; job 1 then runs on the 1 alive worker
    assert er.records[1].start == pytest.approx(5.0)
    assert er.records[1].finish == pytest.approx(9.0)
    assert np.allclose(vr.starts[0], [r.start for r in er.records], rtol=1e-5)
    assert np.allclose(vr.finishes[0], [r.finish for r in er.records], rtol=1e-5)


# --------------------------------------------------------------------------
# accounting identities (exact per rep, within the backend)
# --------------------------------------------------------------------------


def test_cancellation_identity_heterogeneous():
    """Same seed, hetero speeds: cancellation must not change compute times
    and must reclaim exactly the redundant tails: ws(on) + saved == ws(off)."""
    speeds = scn.seeded_speeds(8, seed=2)
    kw = dict(seed=5, speeds=speeds)
    on = simulate_epochs(
        Pareto(1.0, 2.0), 8, 2, np.zeros(10), 60, cancel_redundant=True, **kw
    )
    off = simulate_epochs(
        Pareto(1.0, 2.0), 8, 2, np.zeros(10), 60, cancel_redundant=False, **kw
    )
    # same draws => same compute times; f32 rounding differs because absolute
    # start offsets differ between the runs (see the module's precision note)
    assert np.allclose(on.compute_times, off.compute_times, rtol=1e-4, atol=1e-3)
    assert np.allclose(
        on.worker_seconds + on.cancelled_seconds_saved, off.worker_seconds, rtol=1e-4
    )
    assert (on.cancelled_seconds_saved > 0).all()
    assert (off.cancelled_seconds_saved == 0).all()
    assert (on.response_times <= off.response_times + 1e-3).all()


def test_uniform_speed_rescales_exactly():
    """speeds = c on every worker is a pure time rescale of speeds = 1."""
    slow = simulate_epochs(Exponential(1.0), 6, 3, np.zeros(30), 8, seed=4)
    fast = simulate_epochs(Exponential(1.0), 6, 3, np.zeros(30), 8, seed=4, speeds=[4.0] * 6)
    assert np.allclose(fast.compute_times * 4.0, slow.compute_times, rtol=1e-5)
    assert np.allclose(fast.worker_seconds * 4.0, slow.worker_seconds, rtol=1e-5)


# --------------------------------------------------------------------------
# stochastic differential: 3-sigma equivalence under churn / hetero speeds
# --------------------------------------------------------------------------


def test_churned_compute_and_response_match_engine():
    """Both backends replay one shared churn schedule; per-stream mean
    compute and response times must agree at 3 sigma."""
    d = ShiftedExponential(delta=1.0, mu=0.5)
    n, b, n_jobs = 8, 4, 24
    sched = scn.seeded_schedule(n, seed=7, fail_rate=0.06, mean_downtime=1.0, pairs_per_worker=4)
    assert len(sched) > 0
    e_ct, e_rt = _engine_runs(d, n, b, n_jobs, 30, churn_schedule=sched)
    vr = simulate_epochs(
        d, n, b, np.zeros(n_jobs), 300, seed=1, churn_schedule=sched, cancel_redundant=False
    )
    assert np.isfinite(vr.compute_times).all()
    assert _z_mean(e_ct, vr.compute_times.mean(axis=1)) < 3.0
    assert _z_mean(e_rt, vr.response_times.mean(axis=1)) < 3.0
    assert (vr.n_worker_failures > 0).all()


def test_rescue_counts_match_engine_on_shared_schedule():
    """r = 1 makes every failure kill a batch's only replica: rescues are
    load-bearing, and their counts must match the engine statistically."""
    d = ShiftedExponential(delta=1.0, mu=0.5)
    n = 6
    sched = scn.seeded_schedule(n, seed=3, fail_rate=0.1, mean_downtime=0.5, pairs_per_worker=4)
    n_resc, n_fail = [], []
    for s in range(25):
        jobs = [Job(job_id=i, dist=d, n_tasks=n) for i in range(16)]
        rep = ClusterEngine(n, seed=200 + s, n_batches=n, churn_schedule=sched).run(jobs)
        n_resc.append(rep.n_replicas_rescued)
        n_fail.append(rep.n_worker_failures)
    vr = simulate_epochs(d, n, n, np.zeros(16), 200, seed=2, churn_schedule=sched)
    assert np.isfinite(vr.compute_times).all()
    assert vr.n_replicas_rescued.mean() > 0
    assert _z_mean(np.array(n_resc, float), vr.n_replicas_rescued.astype(float)) < 3.0
    assert _z_mean(np.array(n_fail, float), vr.n_worker_failures.astype(float)) < 3.0


def test_heterogeneous_speeds_match_engine():
    d = Exponential(1.0)
    n, b = 6, 3
    speeds = scn.seeded_speeds(n, seed=11, lo=0.5, hi=2.0)
    e_ct, _ = _engine_runs(d, n, b, 30, 30, speeds=speeds)
    vr = simulate_epochs(d, n, b, np.zeros(30), 300, seed=6, speeds=speeds)
    assert _z_mean(e_ct, vr.compute_times.mean(axis=1)) < 3.0


# --------------------------------------------------------------------------
# online replanning: windowed refit converges on both backends
# --------------------------------------------------------------------------


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 30))
def test_replanning_converges_to_closed_form_optimum_both_backends(seed):
    """Exponential tails: Thm 3 says E[T] = H_B / mu, minimized at full
    diversity B* = 1.  Starting deliberately wrong (full parallelism), the
    windowed replanner must land on B* on *both* backends."""
    n, n_jobs = 8, 80
    dist = Exponential(mu=1.0)
    b_star = analysis.argmin_B(dist, n, metric="mean")
    cfg = ReplanConfig(window=256, refit_every=64, min_observations=64)

    ctl = cfg.to_controller(n)
    jobs = [Job(job_id=i, dist=dist, n_tasks=n) for i in range(n_jobs)]
    er = ClusterEngine(n, seed=seed, n_batches=n, controller=ctl).run(jobs)
    assert er.n_replans >= 1
    assert ctl.current.n_batches == b_star == 1
    assert er.records[-1].n_batches == b_star

    vr = simulate_epochs(dist, n, n, np.zeros(n_jobs), 2, seed=seed, replan=cfg)
    assert (vr.n_replans >= 1).all()
    assert (vr.final_n_batches == b_star).all()
    # same windowing => comparable replan cadence
    assert abs(vr.n_replans.mean() - er.n_replans) <= 3


def test_replanning_under_cancellation_censoring():
    """With cancellation only batch winners are observed; the jax replanner
    must undo the min-of-r censoring like the Python one, or it would fit a
    tail r times lighter and drift away from B*."""
    n, n_jobs = 8, 100
    dist = Exponential(mu=1.0)
    cfg = ReplanConfig(window=256, refit_every=32, min_observations=32)
    vr = simulate_epochs(
        dist, n, n, np.zeros(n_jobs), 4, seed=2, cancel_redundant=True, replan=cfg
    )
    assert (vr.n_replans >= 1).all()
    assert (vr.final_n_batches == 1).all()


# --------------------------------------------------------------------------
# planner integration: no Python fallback left
# --------------------------------------------------------------------------


def test_plan_cluster_dynamic_scenarios_stay_on_jax():
    n = 8
    churn = ChurnProcess(fail_rate=0.03, mean_downtime=1.0)
    speeds = scn.seeded_speeds(n, seed=1)
    plan = RedundancyPlanner(n).plan_cluster(
        Exponential(1.0), n_reps=96, seed=0, churn=churn, speeds=speeds
    )
    assert plan.source == "cluster_engine:jax"
    assert np.isfinite(plan.frontier_mean).all()
    # exponential tails under mild churn keep the full-diversity optimum,
    # and the python engine agrees on the pick
    plan_py = RedundancyPlanner(n).plan_cluster(
        Exponential(1.0), n_reps=96, seed=0, churn=churn, speeds=speeds, backend="python"
    )
    assert plan.n_batches == plan_py.n_batches == 1
    # replanning while scoring also stays on the jax path
    plan_r = RedundancyPlanner(n).plan_cluster(
        Exponential(1.0),
        n_reps=64,
        seed=0,
        churn=churn,
        replan=ReplanConfig(window=64, refit_every=32, min_observations=32),
    )
    assert plan_r.source == "cluster_engine:jax"
    assert plan_r.n_batches in analysis.feasible_B(n)


def test_frontier_dynamic_rows_match_engine_scoring():
    """Frontier rows under a shared schedule agree with per-candidate engine
    sampling at 3 sigma (the plan_cluster differential)."""
    n = 6
    d = Exponential(1.0)
    sched = scn.seeded_schedule(n, seed=5, fail_rate=0.04, mean_downtime=1.0, pairs_per_worker=3)
    cands = scn.frontier(n)
    rows = frontier_job_times_dynamic(
        d, n, cands, 240, seed=0, n_jobs=12, churn_schedule=sched
    )
    assert rows.shape[0] == len(cands)
    for i, b in enumerate(cands):
        e_ct, _ = _engine_runs(d, n, b, 12, 20, seed0=400 + 37 * i, churn_schedule=sched)
        v = rows[i].reshape(-1, 12).mean(axis=1)
        assert _z_mean(e_ct, v) < 3.0, (b, e_ct.mean(), v.mean())


def test_plan_sweep_dynamic_shapes_and_sources():
    plans = plan_sweep(
        [Exponential(1.0)],
        [4, 6],
        n_reps=48,
        seed=1,
        churn=ChurnProcess(fail_rate=0.02, mean_downtime=1.0),
        speeds=lambda n: scn.seeded_speeds(n, seed=n),
    )
    assert len(plans) == 1 and len(plans[0]) == 2
    for p, budget in zip(plans[0], [4, 6]):
        assert p.source == "cluster_engine:jax"
        assert p.n_workers == budget
        assert p.n_batches in analysis.feasible_B(budget)


# --------------------------------------------------------------------------
# generated-scenario invariants (shared strategies)
# --------------------------------------------------------------------------


@settings(max_examples=4, deadline=None)
@given(
    dist=scn.light_tailed_dists(),
    setup=scn.worker_setups(6, 6),
    churn=scn.churn_processes(),
    seed=st.integers(0, 99),
)
def test_epoch_scan_invariants_on_generated_scenarios(dist, setup, churn, seed):
    n, speeds = setup
    rep = simulate_epochs(
        dist,
        n,
        max(1, n // 2),
        np.zeros(8),
        3,
        seed=seed,
        speeds=speeds,
        churn=churn,
        churn_pairs_per_worker=2,
    )
    assert (rep.worker_seconds > 0).all()
    assert (rep.cancelled_seconds_saved == 0).all()  # cancel off
    ct = rep.compute_times
    assert (ct[np.isfinite(ct)] > 0).all()
    fin = np.isfinite(rep.starts)
    assert (rep.n_batches_used[fin] >= 1).all()
    assert (rep.n_batches_used * rep.replication_used <= n).all()
    # FIFO: dispatched jobs start in order
    for row in rep.starts:
        r = row[np.isfinite(row)]
        assert (np.diff(r) >= -1e-4).all()


# --------------------------------------------------------------------------
# validation
# --------------------------------------------------------------------------


def test_validation_errors():
    d = Exponential(1.0)
    with pytest.raises(ValueError, match="sorted"):
        simulate_epochs(d, 4, 2, [3.0, 1.0], 2)
    with pytest.raises(ValueError, match="n_batches"):
        simulate_epochs(d, 4, 9, np.zeros(2), 2)
    with pytest.raises(ValueError, match="speeds"):
        simulate_epochs(d, 4, 2, np.zeros(2), 2, speeds=[1.0, 2.0])
    with pytest.raises(ValueError, match="not both"):
        simulate_epochs(
            d, 4, 2, np.zeros(2), 2,
            churn=ChurnProcess(0.1, 1.0),
            churn_schedule=ChurnSchedule((), (), ()),
        )
    with pytest.raises(ValueError, match="window"):
        simulate_epochs(d, 8, 2, np.zeros(2), 2, replan=ReplanConfig(window=4))
    with pytest.raises(ValueError, match="alternate"):
        ChurnSchedule(times=(1.0,), wids=(0,), ups=(True,))
    with pytest.raises(ValueError, match="candidate"):
        frontier_job_times_dynamic(d, 4, [], 8)
    with pytest.raises(ValueError, match="not both"):
        ClusterEngine(
            4, churn=ChurnProcess(0.1, 1.0), churn_schedule=ChurnSchedule((), (), ())
        )
    # out-of-range schedule worker ids are rejected up front on BOTH backends
    bad_neg = ChurnSchedule(times=(1.0,), wids=(-1,), ups=(False,))
    bad_big = ChurnSchedule(times=(1.0,), wids=(7,), ups=(False,))
    for bad in (bad_neg, bad_big):
        with pytest.raises(ValueError, match="worker ids"):
            ClusterEngine(4, churn_schedule=bad)
        with pytest.raises(ValueError, match="worker ids"):
            simulate_epochs(d, 4, 2, np.zeros(2), 2, churn_schedule=bad)


# --------------------------------------------------------------------------
# scale-out contracts: rep chunking, device sharding, float64 lanes, buckets
# --------------------------------------------------------------------------


def test_rep_chunk_bit_identical():
    """n_reps in one chunk vs k chunks: per-lane seed derivation makes the
    device results bit-identical, not merely statistically equivalent."""
    d = Pareto(1.0, 2.0)
    churn = ChurnProcess(fail_rate=0.05, mean_downtime=1.0)
    kw = dict(seed=7, churn=churn, churn_pairs_per_worker=2, cancel_redundant=True)
    one = simulate_epochs(d, 6, 3, np.zeros(8), 30, **kw)
    for chunk in (7, 13, 30):
        part = simulate_epochs(d, 6, 3, np.zeros(8), 30, rep_chunk=chunk, **kw)
        assert np.array_equal(one.finishes, part.finishes)
        assert np.array_equal(one.starts, part.starts)
        assert np.array_equal(one.worker_seconds, part.worker_seconds)
        assert np.array_equal(one.cancelled_seconds_saved, part.cancelled_seconds_saved)
        assert np.array_equal(one.epoch_times, part.epoch_times)
    rows = frontier_job_times_dynamic(
        d, 6, [1, 2, 3], 60, seed=3, n_jobs=10, churn=churn, churn_pairs_per_worker=2
    )
    for chunk in (2, 4):
        rows_c = frontier_job_times_dynamic(
            d, 6, [1, 2, 3], 60, seed=3, n_jobs=10, churn=churn,
            churn_pairs_per_worker=2, rep_chunk=chunk,
        )
        assert np.array_equal(rows, rows_c)
    with pytest.raises(ValueError, match="rep_chunk"):
        simulate_epochs(d, 6, 3, np.zeros(4), 8, rep_chunk=0)


def test_gated_write_sets_only_when_on_and_in_range():
    """The lanes' gated scalar write: set when on and in range, else a no-op
    (out-of-range indices included), lane by lane under vmap."""
    import jax
    import jax.numpy as jnp

    from repro.cluster.epoch_scan import _put

    x = jnp.arange(4.0)
    assert _put(x, 2, 9.0, True).tolist() == [0.0, 1.0, 9.0, 3.0]
    for i, on in [(2, False), (4, True), (-1, True)]:
        assert _put(x, i, 9.0, on).tolist() == x.tolist()
    lanes = jnp.tile(x, (3, 1))
    got = jax.vmap(_put)(lanes, jnp.array([1, 4, 3]), jnp.array([7.0, 8.0, 9.0]),
                         jnp.array([True, True, False]))
    assert got.tolist() == [[0.0, 7.0, 2.0, 3.0], x.tolist(), x.tolist()]


def test_space_lane_lowers_without_scatters(monkeypatch):
    """The space lane indexes and reduces by segment, job and worker densely in every
    configuration: a TPU serializes the indices of a vmapped scatter, so the
    non-speculative packed runner of a churned plan lowers with no scatter op."""
    import jax

    from repro.cluster import Scenario, epoch_scan

    seen = []
    build = epoch_scan._get_runner

    def shapes_only(cfg):
        runner = build(cfg)

        def call(*a):
            seen.append((cfg, runner, a))
            out = jax.eval_shape(runner, *a)
            return jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), out)

        return call

    monkeypatch.setattr(epoch_scan, "_get_runner", shapes_only)
    sc = Scenario(churn=ChurnProcess(fail_rate=0.02, mean_downtime=2.0),
                  speeds=tuple(np.random.default_rng(0).uniform(0.5, 2.0, 8)),
                  jobs_per_stream=8, churn_pairs_per_worker=4, cancel_redundant=True,
                  scheduler="packed", workers_per_job=4)
    frontier_job_times_dynamic(Pareto(1.0, 1.8), 8, [1, 2, 4], 16, scenario=sc)
    ((cfg, runner, a),) = seen
    assert cfg.scheduler == "packed" and cfg.spec is None and not cfg.stream
    assert cfg.ev_pad > 1  # churned: the boundary's per-segment count runs too
    text = runner.lower(*a).as_text()
    assert "stablehlo.while" in text
    assert "scatter" not in text


@pytest.mark.parametrize(
    "law", ["hedged_durations", "wide_range", "integers", "grid", "near_ties", "specials"]
)
def test_div_rn_is_numpys_float32_division(law):
    """The speculative space lane's float32 divide equals numpy's correctly rounded one
    bit for bit: service draws over speeds, quotients across 18 decades, exact integer
    quotients, the heartbeat grid's index, quotients a hair off a rounding tie, and
    zeros, infinities, NaNs, overflow and underflow."""
    import jax

    from repro.cluster.epoch_scan import _div_rn

    rng = np.random.default_rng(7)
    m = 200_000
    if law == "hedged_durations":
        a = (rng.pareto(1.8, m) + 1) * rng.choice([1, 2, 4, 5, 10, 20, 25, 50, 100], m)
        b = rng.uniform(0.5, 2.0, m)
    elif law == "wide_range":
        a, b = 10.0 ** rng.uniform(-9, 9, m), 10.0 ** rng.uniform(-9, 9, m)
    elif law == "integers":
        b = rng.integers(1, 1 << 12, m).astype(float)
        a = b * rng.integers(1, 1 << 12, m)
    elif law == "grid":
        a, b = rng.integers(0, 1 << 20, m) * 0.25 + rng.choice([0.0, 0.125], m), 0.25
    elif law == "near_ties":  # b x q for q a midpoint between two floats, nudged one ulp
        q = rng.uniform(1.0, 2.0, m).astype(np.float32).astype(float) + 2.0**-24
        b = rng.uniform(0.5, 2.0, m).astype(np.float32)
        a = np.nextafter((q * b).astype(np.float32), rng.choice([-np.inf, np.inf], m))
    else:  # 3e30 / 1e-30 overflows and 1e-30 / 3e30 underflows past the subnormals
        v = [0.0, -0.0, 1.0, -2.5, np.inf, -np.inf, np.nan, 3e30, 1e-30]
        a, b = (x.ravel() for x in np.meshgrid(v, v))
    a = np.asarray(a, np.float32) * (1 if law == "specials" else rng.choice([-1, 1], m))
    a = a.astype(np.float32)
    b = np.broadcast_to(np.asarray(b, np.float32), a.shape)
    with np.errstate(all="ignore"):
        want = a / b
    np.testing.assert_array_equal(np.asarray(jax.jit(_div_rn)(a, b)), want)


def test_sharded_devices_match_single_device():
    """devices > 1 shards the lane grid via shard_map; per-lane seed
    derivation keeps the results exactly equal to single-device runs."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.cluster import ChurnProcess, simulate_epochs
        from repro.cluster.epoch_scan import frontier_job_times_dynamic
        from repro.core.service_time import Exponential
        assert len(jax.devices()) >= 4, jax.devices()
        d, churn = Exponential(1.0), ChurnProcess(0.05, 1.0)
        kw = dict(seed=2, churn=churn, churn_pairs_per_worker=2)
        a = simulate_epochs(d, 6, 3, np.zeros(6), 10, devices=1, **kw)
        b = simulate_epochs(d, 6, 3, np.zeros(6), 10, devices=4, **kw)
        assert np.array_equal(a.finishes, b.finishes)
        assert np.array_equal(a.worker_seconds, b.worker_seconds)
        assert np.array_equal(a.n_replicas_rescued, b.n_replicas_rescued)
        ra = frontier_job_times_dynamic(d, 6, [1, 2, 3, 6], 40, n_jobs=8,
                                        devices=1, **kw)
        rb = frontier_job_times_dynamic(d, 6, [1, 2, 3, 6], 40, n_jobs=8,
                                        devices=4, **kw)
        assert np.array_equal(ra, rb)
        print("PASS")
    """)
    import os

    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout[-2000:]}\nSTDERR:\n{r.stderr[-4000:]}"
    assert "PASS" in r.stdout
    # on the in-process (single device) backend, over-asking must be a
    # clear error, not a silent fallback
    with pytest.raises(ValueError, match="devices"):
        simulate_epochs(
            Exponential(1.0), 4, 2, np.zeros(2), 2, devices=len(__import__("jax").devices()) + 1
        )


def test_float64_lanes_fix_large_arrival_offsets():
    """Absolute times ~1e7 would quantize float32 queue waits (a ulp there is
    ~1 s); the float32 lane now refuses such arrivals loudly, naming
    dtype='float64', while float64 lanes track the (float64) engine to
    ~1e-6."""
    import jax

    d = Empirical(samples=(1.3,))
    n, b, n_jobs = 6, 3, 6
    off = 1.0e7
    arr = off + np.arange(n_jobs) * 1.5
    speeds = (1.0, 1.5, 0.7, 1.2, 0.9, 1.1)
    jobs = [
        Job(job_id=i, dist=d, n_tasks=n, arrival=float(t)) for i, t in enumerate(arr)
    ]
    er = ClusterEngine(n, seed=3, n_batches=b, speeds=speeds).run(jobs)
    e_start = np.array([r.start for r in er.records])
    e_fin = np.array([r.finish for r in er.records])
    # the float32 lane refuses rather than returning quantized statistics,
    # and the message names the fix
    with pytest.raises(ValueError, match=r'dtype="float64"'):
        simulate_epochs(d, n, b, arr, 1, seed=3, speeds=speeds)
    # ... the space-delegated lane of simulate_fifo inherits the same guard
    with pytest.raises(ValueError, match=r'dtype="float64"'):
        simulate_fifo(d, n, b, arr, 1, seed=3, scheduler="packed", workers_per_job=2)
    # arrivals within the f32-safe range stay accepted on the float32 lane
    simulate_epochs(d, n, b, arr - off, 1, seed=3, speeds=speeds)
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        f64 = simulate_epochs(d, n, b, arr, 1, seed=3, speeds=speeds, dtype="float64")
    finally:
        jax.config.update("jax_enable_x64", prev)
    err64 = np.max(np.abs(f64.finishes[0] - e_fin))
    assert err64 < 1e-6, err64
    assert np.max(np.abs(f64.starts[0] - e_start)) < 1e-6
    # float64 without x64 enabled is a loud error, not silent downcast
    with pytest.raises(ValueError, match="x64"):
        simulate_epochs(d, n, b, arr, 1, seed=3, dtype="float64")
    with pytest.raises(ValueError, match="dtype"):
        simulate_epochs(d, n, b, arr - off, 1, seed=3, dtype="float16")


def test_plan_sweep_one_compile_per_shape_bucket():
    """A dynamic (distribution x budget) sweep whose budgets share one shape
    bucket compiles the step runner exactly once (the bucketed jit cache);
    host-side draw prep keeps distributions out of the compile key."""
    from repro.cluster.epoch_scan import clear_runner_cache, runner_cache_stats

    clear_runner_cache()
    churn = ChurnProcess(fail_rate=0.03, mean_downtime=1.0)
    plans = plan_sweep(
        [Exponential(1.0), Exponential(2.0), ShiftedExponential(delta=0.5, mu=1.0)],
        [6, 5],
        n_reps=32,
        seed=0,
        churn=churn,
        candidates=[1, 2],
        jobs_per_stream=8,
        churn_pairs_per_worker=2,
    )
    assert len(plans) == 3 and len(plans[0]) == 2
    stats = runner_cache_stats()
    assert sum(stats.values()) == 1, stats
