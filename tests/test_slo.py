"""Tail-SLO planning: per-class stream state, quantile accuracy, plan_slo.

Three contracts pinned here:

  * the per-class response state (`class_count` / `class_resp_sum` /
    `class_hist`) carried by the streaming kernel equals the sequential
    host fold of the materialized outputs **bit for bit** on f64 lanes,
    under any slab partition;
  * the histogram quantile estimator is conservative within its committed
    bound: for the k-th pooled order statistic r_k (k = ceil(q * total)),
    ``r_k <= quantile(q) <= r_k * (1 + STREAM_QUANTILE_RTOL)`` -- on
    adversarial workloads (heavy Pareto tails, near-degenerate service
    times, multi-slab boundaries);
  * `plan_slo` returns the cheapest feasible (B, r, scheduler) -- a
    feasible verdict survives a fresh independent simulation, an
    impossible target yields an explicit infeasible verdict (never a
    silent fallback), and the grid exhibits the paper's second core
    result: the mean-optimal candidate is not the SLO-optimal one.
"""
import jax
import numpy as np
import pytest

from repro.cluster import (
    SLO,
    STREAM_QUANTILE_RTOL,
    Scenario,
    fold_stream_stats,
    simulate_stream,
)
from repro.cluster.stream import _CLASS_FIELDS
from repro.core import RedundancyPlanner
from repro.core.service_time import Exponential, Pareto
from repro.core.traces import TraceJob, TraceStream, poisson_stream


@pytest.fixture
def x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)


def _mixed_stream(n_jobs=90, seed=5) -> TraceStream:
    """Two far-apart classes so per-class quantiles differ visibly.

    Arrivals are spread thin (mean gap 400 s against ~1-100 s services), so
    responses track each class's own service law instead of a shared queue
    backlog -- the regime where per-class quantiles must separate.
    """
    rng = np.random.default_rng(77)
    fast = TraceJob("fast", "exponential", 1.0 + rng.exponential(0.5, size=300))
    slow = TraceJob("slow", "heavy", 30.0 * rng.pareto(1.6, size=300) + 30.0)
    arr_rng = np.random.default_rng(seed)
    arrivals = np.sort(arr_rng.uniform(0.0, 400.0 * n_jobs, size=n_jobs))
    job_ids = arr_rng.integers(0, 2, size=n_jobs)
    return TraceStream(arrivals=arrivals, job_ids=job_ids, sources=(fast, slow), seed=seed)


def _order_stat(resp: np.ndarray, q: float) -> float:
    """The k-th pooled order statistic the histogram estimator brackets."""
    x = np.sort(resp.ravel())
    k = int(np.ceil(q * x.size))
    return float(x[max(k, 1) - 1])


# --------------------------------------------------------------------------
# per-class stream state: bit-for-bit vs the host fold, slab-invariant
# --------------------------------------------------------------------------


def test_class_state_matches_fold_bitwise_f64(x64):
    st = _mixed_stream(90)
    sc = Scenario(outputs="full", dtype="float64", cancel_redundant=True)
    rep = simulate_stream(st, 6, 3, 4, scenario=sc, slab=32)
    folded = fold_stream_stats(
        rep.waits, rep.t_job, rep.busy_j, rep.planned_j, rep.saved_j,
        class_ids=st.job_ids, classes=("fast", "slow"),
    )
    assert rep.stats.classes == ("fast", "slow")
    for f in _CLASS_FIELDS:
        x, y = getattr(rep.stats, f), getattr(folded, f)
        assert x.dtype == y.dtype, f
        np.testing.assert_array_equal(x, y, err_msg=f)
    # class marginals are consistent with the pooled accumulators
    np.testing.assert_array_equal(rep.stats.class_count.sum(axis=1), rep.stats.count)
    np.testing.assert_array_equal(rep.stats.class_hist.sum(axis=1), rep.stats.hist)


@pytest.mark.parametrize("slab", [1, 7, None])
def test_class_state_slab_invariant(x64, slab):
    st = _mixed_stream(40)
    sc = Scenario(outputs="stream", dtype="float64")
    got = simulate_stream(st, 4, 2, 3, scenario=sc, slab=slab)
    ref = simulate_stream(st, 4, 2, 3, scenario=sc, slab=16)
    for f in _CLASS_FIELDS:
        np.testing.assert_array_equal(getattr(got, f), getattr(ref, f), err_msg=f)


def test_class_summary_and_quantile_lookup(x64):
    st = _mixed_stream(80)
    stats = simulate_stream(
        st, 4, 2, 3,
        scenario=Scenario(outputs="stream", dtype="float64", size_dependent=False),
    )
    summ = stats.class_summary()
    assert set(summ) == {"fast", "slow"}
    # medians separate by class (tails can mix: a fast job behind a giant
    # slow job inherits its wait, so only the bulk is class-ordered)
    assert summ["slow"]["p50_response"] > summ["fast"]["p50_response"]
    assert summ["slow"]["mean_response"] > summ["fast"]["mean_response"]
    assert stats.quantile(0.9, job_class="slow") == stats.quantile(0.9, job_class=1)
    with pytest.raises(KeyError):
        stats.quantile(0.9, job_class="nope")
    # the epoch-scan stream lane carries no class state: explicit error
    bare = stats.__class__(**{
        f: getattr(stats, f)
        for f in ("count", "resp_sum", "resp_sq", "resp_min", "resp_max",
                  "comp_sum", "busy_sum", "saved_sum", "hist")
    })
    with pytest.raises(ValueError, match="per-class"):
        bare.quantile(0.9, job_class=0)
    with pytest.raises(ValueError, match="per-class"):
        bare.class_summary()


# --------------------------------------------------------------------------
# committed quantile accuracy on adversarial workloads
# --------------------------------------------------------------------------


def _adversarial_sources(kind: str):
    rng = np.random.default_rng(13)
    if kind == "pareto_tail":
        # alpha ~ 1.1: extreme right tail spanning many histogram decades
        x = 2.0 * (rng.pareto(1.1, size=500) + 1.0)
        return (TraceJob("heavy", "heavy", x),)
    if kind == "degenerate":
        # near-constant service: every response lands in one or two bins
        x = 5.0 + rng.uniform(-1e-9, 1e-9, size=400)
        return (TraceJob("flat", "exponential", x),)
    raise AssertionError(kind)


@pytest.mark.parametrize("kind", ["pareto_tail", "degenerate"])
@pytest.mark.parametrize("slab", [7, None])
def test_stream_quantile_within_committed_bound(x64, kind, slab):
    sources = _adversarial_sources(kind)
    rng = np.random.default_rng(3)
    n = 120
    arrivals = np.sort(rng.uniform(0.0, 50.0 * n, size=n))
    st = TraceStream(
        arrivals=arrivals,
        job_ids=np.zeros(n, dtype=np.int64),
        sources=sources,
        seed=3,
    )
    rep = simulate_stream(
        st, 4, 2, 3,
        scenario=Scenario(outputs="full", dtype="float64", size_dependent=False),
        slab=slab,
    )
    resp = np.asarray(rep.response_times, np.float64)
    for q in (0.5, 0.9, 0.99, 0.999):
        r_k = _order_stat(resp, q)
        est = rep.stats.quantile(q)
        assert r_k <= est <= r_k * (1.0 + STREAM_QUANTILE_RTOL) * (1 + 1e-12), (
            kind, q, r_k, est,
        )
        est_c = rep.stats.quantile(q, job_class=0)
        assert r_k <= est_c <= r_k * (1.0 + STREAM_QUANTILE_RTOL) * (1 + 1e-12)


def test_stream_quantile_per_class_bound_mixed(x64):
    st = _mixed_stream(100)
    rep = simulate_stream(
        st, 4, 2, 4,
        scenario=Scenario(outputs="full", dtype="float64", size_dependent=False),
        slab=33,
    )
    resp = np.asarray(rep.response_times, np.float64)
    for c, name in enumerate(("fast", "slow")):
        rc = resp[:, st.job_ids == c]
        for q in (0.5, 0.95, 0.99):
            r_k = _order_stat(rc, q)
            est = rep.stats.quantile(q, job_class=name)
            assert r_k <= est <= r_k * (1.0 + STREAM_QUANTILE_RTOL) * (1 + 1e-12), (
                name, q, r_k, est,
            )


# --------------------------------------------------------------------------
# plan_slo: cheapest feasible candidate, explicit infeasibility
# --------------------------------------------------------------------------


def test_plan_slo_feasible_survives_fresh_simulation():
    planner = RedundancyPlanner(4)
    slo = SLO(quantile=0.99, target_s=40.0, arrival_rate=0.05)
    plan = planner.plan_slo(
        Pareto(sigma=2.0, alpha=1.5), slo,
        n_jobs=400, n_reps=3, seed=1, schedulers=("fifo_gang", "packed"),
    )
    best = plan.require_feasible()
    assert plan.feasible and best.feasible
    assert best.achieved[0] <= slo.target_s
    # cheapest: no other feasible candidate is cheaper
    for c in plan.candidates:
        if c.feasible:
            assert best.cost_worker_seconds <= c.cost_worker_seconds + 1e-9
    # the verdict holds on a fresh, independently-seeded arrival stream:
    # re-simulate the winning candidate alone and re-check the quantile
    # (conservative estimator + sampling slack of one histogram bin)
    rng = np.random.default_rng(np.random.SeedSequence((1, 0x51_0, 0)))
    src = TraceJob(
        "pareto", "fitted", Pareto(sigma=2.0, alpha=1.5).sample_np(rng, (4000,))
    )
    fresh = poisson_stream((src,), slo.arrival_rate, 400, seed=99)
    stats = simulate_stream(
        fresh, 4, best.n_batches, 3,
        scenario=Scenario(
            scheduler=best.scheduler,
            workers_per_job=best.workers_per_job,
            size_dependent=False,
            outputs="stream",
        ),
    )
    got = stats.quantile(slo.quantile)
    assert got <= slo.target_s * (1.0 + STREAM_QUANTILE_RTOL), (best, got)


def test_plan_slo_static_grid_equals_each_candidate_alone():
    """Dispatching the whole grid before one batched readback changes no value:
    each candidate equals its own ``simulate_stream`` call, read back alone."""
    workload = [Pareto(sigma=2.0, alpha=1.5), Exponential(mu=0.5)]
    slos = (
        SLO(quantile=0.99, target_s=40.0, arrival_rate=0.05),
        SLO(quantile=0.9, target_s=12.0, arrival_rate=0.05, job_class="exponential"),
    )
    n_jobs, n_reps, seed, slab = 150, 2, 4, 64
    plan = RedundancyPlanner(4).plan_slo(
        workload, slos, n_jobs=n_jobs, n_reps=n_reps, seed=seed,
        schedulers=("fifo_gang", "packed"), slab=slab,
    )
    sources = []
    for i, w in enumerate(workload):  # the trace jobs plan_slo samples
        rng = np.random.default_rng(np.random.SeedSequence((seed, 0x51_0, i)))
        sources.append(TraceJob(type(w).__name__.lower(), "fitted", w.sample_np(rng, (4000,))))
    stream = poisson_stream(sources, 0.05, n_jobs, seed=seed)
    # B in 1, 2, 4 on the whole cluster, 1 on pools of 1, and 1, 2 on pools of 2
    assert len(plan.candidates) == 6
    for c in plan.candidates:
        stats = simulate_stream(
            stream, 4, c.n_batches, n_reps, slab=slab,
            scenario=Scenario(size_dependent=False, scheduler=c.scheduler,
                              workers_per_job=c.workers_per_job, outputs="stream"),
        )
        achieved = tuple(stats.quantile(s.quantile, job_class=s.job_class) for s in slos)
        total = int(stats.count.sum())
        assert c.achieved == achieved, c
        assert c.cost_worker_seconds == float(stats.busy_sum.mean()), c
        assert c.mean_response == float(stats.resp_sum.sum() / max(total, 1)), c
        assert c.feasible == all(a <= s.target_s for a, s in zip(achieved, slos)), c


def test_plan_slo_impossible_target_is_explicit():
    planner = RedundancyPlanner(4)
    plan = planner.plan_slo(
        Exponential(mu=1.0),
        SLO(quantile=0.99, target_s=1e-4, arrival_rate=0.05),
        n_jobs=150, n_reps=2, seed=0, schedulers=("fifo_gang",),
    )
    assert not plan.feasible
    assert plan.best is None
    assert all(not c.feasible for c in plan.candidates)
    with pytest.raises(ValueError, match="no \\(B, r, scheduler\\)"):
        plan.require_feasible()


def test_plan_slo_mean_optimal_differs_from_tail_optimal():
    """The paper's second core result, as a planning assertion.

    On this grid the candidate with the best *mean* response buys extra
    replication (r=2 pools), while the cheapest candidate meeting the p99
    target is the unreplicated one -- mean-optimal and SLO-optimal provably
    differ, and cost (worker-seconds) is what separates them.
    """
    planner = RedundancyPlanner(4)
    plan = planner.plan_slo(
        Pareto(sigma=2.0, alpha=1.5),
        SLO(quantile=0.99, target_s=40.0, arrival_rate=0.05),
        n_jobs=400, n_reps=3, seed=1, schedulers=("fifo_gang", "packed"),
    )
    best = plan.require_feasible()
    mean_opt = min(plan.candidates, key=lambda c: c.mean_response)
    key = lambda c: (c.scheduler, c.workers_per_job, c.n_batches)
    assert key(mean_opt) != key(best)
    assert mean_opt.cost_worker_seconds > best.cost_worker_seconds
    # and the mean-optimal point is itself feasible here: the planner chose
    # the *cheaper* feasible candidate, not the best-mean one
    assert mean_opt.feasible


def test_plan_slo_per_class_space_sharing():
    rng = np.random.default_rng(21)
    fast = TraceJob("fast", "exponential", 1.0 + rng.exponential(0.3, size=500))
    slow = TraceJob("slow", "heavy", 4.0 * (rng.pareto(1.8, size=500) + 1.0))
    slos = (
        SLO(quantile=0.9, target_s=12.0, arrival_rate=0.08, job_class="fast"),
        SLO(quantile=0.9, target_s=80.0, arrival_rate=0.08, job_class="slow"),
    )
    planner = RedundancyPlanner(4)
    plan = planner.plan_slo(
        (fast, slow), slos,
        n_jobs=300, n_reps=2, seed=4, schedulers=("packed", "balanced"),
    )
    assert plan.classes == ("fast", "slow")
    assert all(len(c.achieved) == 2 for c in plan.candidates)
    # per-class re-ranking uses only that class's SLOs
    for name in ("fast", "slow"):
        b = plan.best_for(name)
        if b is not None:
            i = plan.classes.index(name)
            assert b.achieved[i] <= slos[i].target_s
    with pytest.raises(KeyError):
        plan.best_for("nope")
    # a joint-feasible plan must satisfy both classes at once
    if plan.feasible:
        assert all(
            a <= s.target_s for a, s in zip(plan.best.achieved, slos)
        )


def test_plan_slo_validation_errors():
    planner = RedundancyPlanner(4)
    with pytest.raises(ValueError, match="needs an SLO"):
        planner.plan_slo(Exponential(mu=1.0))
    with pytest.raises(ValueError, match="arrival_rate"):
        planner.plan_slo(
            Exponential(mu=1.0),
            (SLO(arrival_rate=1.0), SLO(arrival_rate=2.0)),
            n_jobs=10,
        )
    with pytest.raises(ValueError, match="job_class"):
        planner.plan_slo(
            Exponential(mu=1.0), SLO(job_class="missing"), n_jobs=10
        )
    with pytest.raises(ValueError, match="unknown scheduler"):
        planner.plan_slo(
            Exponential(mu=1.0), SLO(target_s=5.0), n_jobs=10,
            schedulers=("warp",),
        )
    with pytest.raises(ValueError, match="must divide"):
        planner.plan_slo(
            Exponential(mu=1.0), SLO(target_s=5.0), n_jobs=10,
            schedulers=("packed",), pool_widths=(3,),
        )


def test_plan_slo_via_scenario_slo_field():
    sc = Scenario(
        slo=SLO(quantile=0.9, target_s=50.0, arrival_rate=0.05),
        size_dependent=False,
    )
    planner = RedundancyPlanner(2)
    plan = planner.plan_slo(
        Exponential(mu=0.5), scenario=sc,
        n_jobs=120, n_reps=2, seed=2, schedulers=("fifo_gang",),
    )
    assert plan.slos == (sc.slo,)
    assert plan.source == "stream"


def test_plan_slo_dynamic_lane_epoch_scan():
    sc = Scenario(speeds=(1.0, 0.5), size_dependent=False)
    planner = RedundancyPlanner(2)
    plan = planner.plan_slo(
        Exponential(mu=0.5),
        SLO(quantile=0.9, target_s=60.0, arrival_rate=0.05),
        scenario=sc, n_jobs=40, n_reps=2, seed=3,
        schedulers=("fifo_gang",),
    )
    assert plan.source == "epoch_scan"
    assert all(c.scheduler == "fifo_gang" for c in plan.candidates)
    # dynamic + multiple classes / per-class SLOs: explicit rejection
    with pytest.raises(ValueError, match="single job class"):
        planner.plan_slo(
            (Exponential(mu=0.5), Exponential(mu=1.0)),
            SLO(quantile=0.9, target_s=60.0, arrival_rate=0.05),
            scenario=sc, n_jobs=20, n_reps=2, schedulers=("fifo_gang",),
        )
    with pytest.raises(ValueError, match="fifo_gang"):
        planner.plan_slo(
            Exponential(mu=0.5),
            SLO(quantile=0.9, target_s=60.0, arrival_rate=0.05),
            scenario=sc, n_jobs=20, n_reps=2, schedulers=("packed",),
        )
