"""Trace-scale streaming: the bit-for-bit contract, the resampler, the golden day.

The tentpole property: on float64 lanes, the streaming accumulators carried by
the scan equal the sequential host fold of the materialized per-job outputs
**bit for bit** -- same seeds, same job order, same ops, same dtype.  That is
asserted three ways:

  * ``simulate_stream(outputs="full")`` returns both the arrays and the
    accumulators the same kernel run carried; ``fold_stream_stats`` of the
    arrays must equal those accumulators exactly;
  * ``outputs="stream"`` (a separate compile without the collected outputs)
    must produce the very same accumulators;
  * any slab partition (1 / prime / all) must too -- draw streams are a
    prefix-stable function of the per-rep generator.

``simulate_epochs(outputs="stream")`` gets the same treatment against
``epoch_stream_stats`` of the full report, including speeds and the space
lane.  The golden test pins the 10k-job synthetic cluster-day summary:

    PYTHONPATH=src:tests python tests/test_stream.py --regen
"""
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.cluster import (
    EpochStreamReport,
    Scenario,
    StreamFullReport,
    StreamStats,
    epoch_stream_stats,
    fold_stream_stats,
    simulate_epochs,
    simulate_stream,
)
from repro import spans
from repro.cluster.stream import _ACC_FIELDS, _CLASS_FIELDS, _dispatch_stream
from repro.core.service_time import ShiftedExponential
from repro.core.traces import (
    STREAM_VERSION,
    TraceStream,
    synthetic_cluster_day,
    synthetic_google_jobs,
)

GOLDEN = pathlib.Path(__file__).parent / "golden" / "trace_day_summary.json"


@pytest.fixture
def x64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)


def _small_stream(n_jobs=96, seed=11) -> TraceStream:
    jobs = tuple(synthetic_google_jobs(2020)[:4])
    rng = np.random.default_rng(seed)
    arrivals = np.sort(rng.uniform(0.0, 40.0 * n_jobs, size=n_jobs))
    job_ids = rng.integers(0, len(jobs), size=n_jobs)
    return TraceStream(arrivals=arrivals, job_ids=job_ids, sources=jobs, seed=seed)


def _assert_stats_equal(a: StreamStats, b: StreamStats, ctx=""):
    for f in _ACC_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype, (f, x.dtype, y.dtype, ctx)
        # bitwise: exact array equality, inf-safe (== would be True for inf
        # too, but assert_array_equal reports indices on mismatch)
        np.testing.assert_array_equal(x, y, err_msg=f"{f} {ctx}")


# --------------------------------------------------------------------------
# TraceStream: construction, resampling, slab invariance of the draws
# --------------------------------------------------------------------------


def test_trace_stream_validates():
    jobs = tuple(synthetic_google_jobs(2020)[:2])
    with pytest.raises(ValueError, match="sorted"):
        TraceStream(np.array([2.0, 1.0]), np.array([0, 0]), jobs, seed=0)
    with pytest.raises(ValueError, match="non-empty"):
        TraceStream(np.array([]), np.array([], dtype=int), jobs, seed=0)
    with pytest.raises(ValueError):
        TraceStream(np.array([0.0, 1.0]), np.array([0]), jobs, seed=0)
    with pytest.raises(ValueError):
        TraceStream(np.array([0.0, 1.0]), np.array([0, 7]), jobs, seed=0)


def test_sample_slab_draws_from_source_ecdf():
    st = _small_stream(40)
    rng = st.make_rng(0)
    draws = st.sample_slab(rng, 0, 40, 6)
    assert draws.shape == (40, 6) and draws.dtype == np.float64
    # every draw is an actual sample of that arrival's source job
    for j in range(40):
        src = set(np.asarray(st.sources[int(st.job_ids[j])].task_times).tolist())
        assert all(float(x) in src for x in draws[j])


def test_sample_slab_partition_invariant():
    """Any slab partition of the same rep's generator yields the same draws."""
    st = _small_stream(50)
    whole = st.sample_slab(st.make_rng(3), 0, 50, 8)
    rng = st.make_rng(3)
    parts = [st.sample_slab(rng, lo, hi, 8) for lo, hi in st.slabs(7)]
    np.testing.assert_array_equal(whole, np.concatenate(parts, axis=0))
    # distinct reps and distinct stream seeds decorrelate
    other = st.sample_slab(st.make_rng(4), 0, 50, 8)
    assert not np.array_equal(whole, other)


def test_stream_seed_versioned():
    st = _small_stream(20)
    bumped = TraceStream(
        st.arrivals, st.job_ids, st.sources, seed=st.seed, version=STREAM_VERSION + 1
    )
    a = st.sample_slab(st.make_rng(0), 0, 20, 4)
    b = bumped.sample_slab(bumped.make_rng(0), 0, 20, 4)
    assert not np.array_equal(a, b)


def test_synthetic_cluster_day_shape():
    day = synthetic_cluster_day(n_jobs=500, duration=3600.0, seed=9)
    assert day.n_jobs == 500
    assert day.arrivals[0] >= 0.0 and day.arrivals[-1] <= 3600.0
    assert np.all(np.diff(day.arrivals) >= 0.0)
    again = synthetic_cluster_day(n_jobs=500, duration=3600.0, seed=9)
    np.testing.assert_array_equal(day.arrivals, again.arrivals)
    np.testing.assert_array_equal(day.job_ids, again.job_ids)


# --------------------------------------------------------------------------
# the tentpole property: streaming == materialized, bit for bit (f64)
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "scheduler,wpj,cancel",
    [
        ("fifo_gang", None, True),
        ("fifo_gang", None, False),
        ("packed", 6, True),
        ("balanced", 6, False),
    ],
)
def test_stream_equals_materialized_bitwise_f64(x64, scheduler, wpj, cancel):
    st = _small_stream(96)
    kw = dict(
        scheduler=scheduler,
        workers_per_job=wpj,
        cancel_redundant=cancel,
        dtype="float64",
    )
    full = simulate_stream(
        st, 12, 6, 3, scenario=Scenario(outputs="full", **kw), slab=37
    )
    assert isinstance(full, StreamFullReport)
    # (1) host fold of the materialized arrays == the carried accumulators
    _assert_stats_equal(
        fold_stream_stats(full.waits, full.t_job, full.busy_j, full.planned_j, full.saved_j),
        full.stats,
        f"fold vs full {scheduler}",
    )
    # (2) the streaming-only compile (no collected outputs) == same accumulators
    lean = simulate_stream(
        st, 12, 6, 3, scenario=Scenario(outputs="stream", **kw), slab=37
    )
    assert isinstance(lean, StreamStats)
    _assert_stats_equal(lean, full.stats, f"stream vs full {scheduler}")
    # sanity on the materialized side: starts respect arrivals, counts complete
    assert np.all(full.waits >= 0.0)
    assert int(lean.count.sum()) == 3 * 96


def test_stream_slab_partition_bitwise_f64(x64):
    """slab in {1, prime, all}: one accumulator, to the last bit."""
    st = _small_stream(60, seed=5)
    sc = Scenario(outputs="stream", dtype="float64")
    ref = simulate_stream(st, 10, 5, 2, scenario=sc, slab=None)
    for slab in (1, 7, 60):
        got = simulate_stream(st, 10, 5, 2, scenario=sc, slab=slab)
        _assert_stats_equal(got, ref, f"slab={slab}")


def test_stream_f32_slab_invariant_and_sane():
    """The f32 lane is slab-invariant too (same compiled fold per width is
    not required -- the draws and fold order are), and summaries are finite."""
    st = _small_stream(50, seed=8)
    sc = Scenario(outputs="stream", scheduler="packed", workers_per_job=5)
    ref = simulate_stream(st, 10, 5, 2, scenario=sc, slab=None)
    got = simulate_stream(st, 10, 5, 2, scenario=sc, slab=13)
    _assert_stats_equal(got, ref, "f32 slab")
    s = ref.summary()
    assert s["n_jobs_done"] == 2 * 50
    assert np.isfinite(s["mean_response"]) and s["mean_response"] > 0.0
    assert s["p50_response"] <= s["p95_response"] <= s["p99_response"]
    assert s["worker_seconds"] > 0.0


@pytest.mark.parametrize("with_classes", [True, False], ids=["classes", "no_classes"])
def test_from_device_many_equals_per_field_copies(with_classes):
    """One batched readback of several accumulators gives each field as a copy of
    its own would, dtype for dtype; ``from_device`` is its one-element case."""
    st = _small_stream(40, seed=3)
    sc = Scenario(outputs="stream", scheduler="packed", workers_per_job=4)
    fields = _ACC_FIELDS + (_CLASS_FIELDS if with_classes else ())
    accs = []
    for b in (1, 2, 4):
        acc, classes, _ = _dispatch_stream(st, 8, b, 2, sc, 16)
        accs.append({k: acc[k] for k in fields})
    want = [{k: np.asarray(acc[k]) for k in fields} for acc in accs]
    got = StreamStats.from_device_many(accs, classes)
    assert spans.last_call()["counts"] == {"d2h.arrays": len(fields) * len(accs)}
    assert len(got) == len(accs)
    for stats, host in zip(got, want):
        for f in fields:
            x = getattr(stats, f)
            assert x.dtype == host[f].dtype, f
            np.testing.assert_array_equal(x, host[f], err_msg=f)
        if with_classes:
            assert stats.classes == classes
        else:
            assert stats.class_hist is None and stats.classes is None
    one = StreamStats.from_device(accs[0], classes)
    for f in fields:
        assert getattr(one, f).dtype == want[0][f].dtype, f
        np.testing.assert_array_equal(getattr(one, f), want[0][f], err_msg=f)
    assert one.classes == (classes if with_classes else None)


def test_stream_rejects_dynamic_knobs_and_bad_pools():
    st = _small_stream(10)
    with pytest.raises(ValueError, match="churn"):
        from repro.cluster import ChurnProcess

        simulate_stream(
            st, 8, 4, 1, scenario=Scenario(outputs="stream", churn=ChurnProcess(0.1, 1.0))
        )
    with pytest.raises(ValueError, match="speeds"):
        simulate_stream(
            st, 8, 4, 1, scenario=Scenario(outputs="stream", speeds=(1.0,) * 8)
        )
    with pytest.raises(ValueError, match="workers_per_job"):
        simulate_stream(st, 8, 4, 1, scenario=Scenario(outputs="stream", scheduler="packed"))
    with pytest.raises(ValueError, match=r"workers_per_job.*\[1, 8\]"):
        simulate_stream(
            st,
            8,
            4,
            1,
            scenario=Scenario(outputs="stream", scheduler="packed", workers_per_job=16),
        )
    with pytest.raises(ValueError, match=r"\[1, 8\]"):
        simulate_stream(st, 8, 9, 1, scenario=Scenario(outputs="stream"))
    with pytest.raises(TypeError, match="TraceStream"):
        simulate_stream(np.zeros(3), 8, 4, 1)


def test_scenario_outputs_knob():
    with pytest.raises(ValueError, match="outputs"):
        Scenario(outputs="compact").validate(8)
    with pytest.raises(ValueError, match="Python engine"):
        Scenario(outputs="stream").validate(8, backend="python")
    Scenario(outputs="stream").validate(8, backend="jax")


# --------------------------------------------------------------------------
# simulate_epochs(outputs="stream"): same contract on the engine-exact lanes
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "kw",
    [
        {},
        {"cancel_redundant": False},
        {"speeds": (1.0, 1.4, 0.8, 1.2, 1.0, 0.9, 1.1, 1.3)},
        {"scheduler": "packed", "workers_per_job": 4},
        {"scheduler": "balanced", "workers_per_job": 4},
    ],
    ids=["gang", "no-cancel", "speeds", "space-packed", "space-balanced"],
)
def test_epoch_stream_equals_full_bitwise_f64(x64, kw):
    d = ShiftedExponential(delta=1.0, mu=0.5)
    arr = np.sort(np.random.default_rng(2).uniform(0.0, 30.0, size=24))
    base = dict(seed=6, dtype="float64", **kw)
    full = simulate_epochs(d, 8, 4, arr, 3, **base)
    got = simulate_epochs(d, 8, 4, arr, 3, outputs="stream", **base)
    assert isinstance(got, EpochStreamReport)
    _assert_stats_equal(got.stats, epoch_stream_stats(full), str(kw))
    np.testing.assert_array_equal(got.worker_seconds, full.worker_seconds)
    np.testing.assert_array_equal(
        got.cancelled_seconds_saved, full.cancelled_seconds_saved
    )
    assert np.array_equal(got.n_unfinished, np.zeros(3, dtype=got.n_unfinished.dtype))
    # the accounting dict keeps the EpochReport keying
    np.testing.assert_array_equal(
        got.accounting()["worker_seconds"], full.accounting()["worker_seconds"]
    )


def test_epoch_stream_churn_bitwise_and_truncation_flag(x64):
    """Churned lanes aggregate bitwise too, and a horizon-truncated rep is
    flagged on the stream report (the full report warns the same way)."""
    from repro.cluster import ChurnProcess

    d = ShiftedExponential(delta=1.0, mu=0.5)
    arr = np.sort(np.random.default_rng(0).uniform(0.0, 30.0, size=20))
    kw = dict(
        seed=2,
        dtype="float64",
        churn=ChurnProcess(fail_rate=0.05, mean_downtime=2.0),
        churn_pairs_per_worker=2,
    )
    with pytest.warns((RuntimeWarning, DeprecationWarning)):
        full = simulate_epochs(d, 8, 4, arr, 3, **kw)
    with pytest.warns((RuntimeWarning, DeprecationWarning)):
        got = simulate_epochs(d, 8, 4, arr, 3, outputs="stream", **kw)
    _assert_stats_equal(got.stats, epoch_stream_stats(full), "churn")
    assert got.churn_truncated is not None and got.churn_truncated.dtype == bool
    np.testing.assert_array_equal(got.n_worker_failures, full.n_worker_failures)


def test_epoch_stream_summary_tracks_full_f32():
    """f32 lanes: not bitwise by contract, but the summaries must agree to
    float32 accumulation error."""
    d = ShiftedExponential(delta=1.0, mu=0.5)
    arr = np.sort(np.random.default_rng(4).uniform(0.0, 20.0, size=16))
    full = simulate_epochs(d, 6, 3, arr, 4, seed=1)
    got = simulate_epochs(d, 6, 3, arr, 4, seed=1, outputs="stream")
    resp = full.finishes - arr[None, :]
    np.testing.assert_allclose(
        got.stats.mean_response, resp.mean(axis=1), rtol=1e-5
    )
    np.testing.assert_allclose(got.stats.resp_max, resp.max(axis=1), rtol=1e-6)


# --------------------------------------------------------------------------
# golden: the 10k-job synthetic cluster-day summary, pinned
# --------------------------------------------------------------------------

# f32 kernel + pooled summary; exact integer fields pinned exactly, float
# fields to 1e-5 (cross-platform reassociation headroom, far below any
# semantic drift).  The cluster is trace-sized (the 2011 Google trace holds
# ~12.5k machines): 2304 pools of 6 give mild queueing, so the pinned
# quantiles actually spread instead of saturating at the histogram tail.
DAY_CFG = dict(n_jobs=10_000, duration=86_400.0, seed=7)
DAY_RUN = dict(n_workers=13_824, n_batches=3, n_reps=2, slab=1024)


def _day_summary() -> dict:
    day = synthetic_cluster_day(**DAY_CFG)
    sc = Scenario(
        outputs="stream", scheduler="packed", workers_per_job=6, cancel_redundant=True
    )
    stats = simulate_stream(
        day, DAY_RUN["n_workers"], DAY_RUN["n_batches"], DAY_RUN["n_reps"],
        scenario=sc, slab=DAY_RUN["slab"],
    )
    return stats.summary()


def test_cluster_day_summary_matches_golden():
    assert GOLDEN.exists(), (
        f"golden file missing: {GOLDEN} -- generate it with "
        "`PYTHONPATH=src:tests python tests/test_stream.py --regen` and commit it"
    )
    golden = json.loads(GOLDEN.read_text())
    current = _day_summary()
    assert set(current) == set(golden)
    assert current["n_jobs_done"] == golden["n_jobs_done"] == (
        DAY_CFG["n_jobs"] * DAY_RUN["n_reps"]
    )
    for k in golden:
        if k == "n_jobs_done":
            continue
        np.testing.assert_allclose(current[k], golden[k], rtol=1e-5, err_msg=k)


if __name__ == "__main__":
    import sys

    if "--regen" in sys.argv:
        GOLDEN.parent.mkdir(parents=True, exist_ok=True)
        GOLDEN.write_text(json.dumps(_day_summary(), indent=2) + "\n")
        print(f"wrote {GOLDEN}")
    else:
        print(__doc__)
