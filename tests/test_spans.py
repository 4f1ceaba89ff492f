"""Host spans and counters (``repro.spans``) and the names the chip benchmark reads.

The recorder's arithmetic runs on a fake clock; the entry points' records
come from tiny CPU calls, and the byte counter is checked against the lane
shapes derived independently of it.  The last tests lower the two device
programs and pin the module names the benchmark's device-time metrics match
in a profiler trace.
"""
import importlib.util
import math
import pathlib
import threading
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.cluster import SLO, ChurnProcess, Scenario, epoch_scan, simulate_stream
from repro.core.analysis import divisor_table
from repro.core.planner import RedundancyPlanner
from repro.core.service_time import Pareto
from repro.core.traces import TraceStream, synthetic_google_jobs

LAYERS = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "layers"


@pytest.fixture
def clock(monkeypatch):
    """A clock that reads the listed nanoseconds, one per reading."""

    def set_readings(*ns):
        it = iter(ns)
        monkeypatch.setattr(spans, "time", SimpleNamespace(perf_counter_ns=lambda: next(it)))

    return set_readings


def test_nested_self_time(clock):
    clock(0, 10, 30, 40, 45, 50, 58, 100)
    with spans.span("outer"):
        with spans.span("inner"):  # 10..30
            pass
        with spans.span("inner"):  # 40..45
            pass
        with spans.span("other"):  # 50..58
            spans.count("bytes", 3)
            spans.count("bytes", 4)
    rec = spans.last_call()
    assert rec["spans"] == {
        "outer": (pytest.approx(100e-9), pytest.approx(67e-9), 1),
        "inner": (pytest.approx(25e-9), pytest.approx(25e-9), 2),
        "other": (pytest.approx(8e-9), pytest.approx(8e-9), 1),
    }
    assert rec["counts"] == {"bytes": 7}


def test_each_outermost_call_starts_a_fresh_record():
    with spans.span("first"):
        spans.count("n", 1)
    with spans.span("second"):
        with spans.span("second.child"):
            pass
    rec = spans.last_call()
    assert set(rec["spans"]) == {"second", "second.child"}
    assert rec["counts"] == {}
    spans.count("n", 5)  # outside any span: dropped
    assert spans.last_call()["counts"] == {}


def test_a_call_that_raises_publishes_its_record():
    with pytest.raises(RuntimeError):
        with spans.span("failing"):
            spans.count("n", 2)
            with spans.span("failing.inner"):
                raise RuntimeError("boom")
    rec = spans.last_call()
    assert {k: v[2] for k, v in rec["spans"].items()} == {"failing": 1, "failing.inner": 1}
    assert rec["counts"] == {"n": 2}


def test_threads_keep_their_own_records():
    with spans.span("main"):
        seen = {}

        def work():
            seen["before"] = spans.last_call()
            with spans.span("worker"):
                spans.count("n", 1)
            seen["after"] = spans.last_call()

        t = threading.Thread(target=work)
        t.start()
        t.join()
        spans.count("m", 1)
    assert seen["before"] is None
    assert set(seen["after"]["spans"]) == {"worker"} and seen["after"]["counts"] == {"n": 1}
    rec = spans.last_call()
    assert set(rec["spans"]) == {"main"} and rec["counts"] == {"m": 1}


def _entries(rec):
    return {k: v[2] for k, v in rec["spans"].items()}


def _tiny_stream(n_jobs, seed=11):
    jobs = tuple(synthetic_google_jobs(2020)[:4])
    rng = np.random.default_rng(seed)
    arrivals = np.sort(rng.uniform(0.0, 40.0 * n_jobs, size=n_jobs))
    job_ids = rng.integers(0, len(jobs), size=n_jobs)
    return TraceStream(arrivals=arrivals, job_ids=job_ids, sources=jobs, seed=seed)


def test_simulate_stream_record():
    n_jobs, slab, reps, b, r = 300, 32, 3, 2, 2  # 10 slabs, the last one partial
    sc = Scenario(outputs="stream", scheduler="packed", workers_per_job=b * r)
    simulate_stream(_tiny_stream(n_jobs), 8, b, reps, scenario=sc, slab=slab)
    rec = spans.last_call()
    n_slabs = math.ceil(n_jobs / slab)
    assert _entries(rec) == {"entry.simulate_stream": 1, "draws.slab": n_slabs,
                             "xfer.put": n_slabs, "xfer.get": 1, "wait.device": 1}
    assert sum(_entries(rec).values()) <= 40  # the span budget of a replayed day
    assert rec["counts"]["stream.job_reps"] == reps * n_jobs
    # per slab: draws (f32) + scales + gaps (f32) + mask (bool) + job ids (int32)
    assert rec["counts"]["h2d.bytes"] == n_slabs * (reps * slab * b * r * 4 + slab * 13)
    total, own, _ = rec["spans"]["xfer.get"]
    assert own <= total and rec["spans"]["wait.device"][0] <= total
    assert rec["counts"]["d2h.arrays"] == 12  # nine accumulator and three class fields


def test_plan_slo_record_stays_within_its_span_budget():
    plan = RedundancyPlanner(16).plan_slo(
        [Pareto(1.0, 1.8)], SLO(quantile=0.99, target_s=10.0, arrival_rate=1.0),
        scenario=Scenario(size_dependent=False), n_jobs=120, n_reps=2, seed=3,
        schedulers=("fifo_gang", "packed"), slab=64,
    )
    rec = spans.last_call()
    entries = _entries(rec)
    n_cand = len(plan.candidates)
    assert n_cand == 15
    assert entries["entry.plan_slo"] == 1 and entries["entry.simulate_stream"] == n_cand
    assert entries["draws.slab"] == 2 * n_cand
    assert sum(entries.values()) <= 150
    # every candidate is dispatched first, then all are read back at once
    assert entries["xfer.get"] == 1 and entries["wait.device"] == 1
    assert rec["counts"]["d2h.arrays"] == 12 * n_cand


@pytest.mark.filterwarnings("ignore:sampled churn horizon")
@pytest.mark.parametrize("width", [None, 4], ids=["gang", "space4"])
def test_plan_cluster_h2d_bytes_match_the_lane_shapes(width):
    n_workers, jobs, pairs, reps = 16, 32, 16, 64
    churn = ChurnProcess(fail_rate=0.02, mean_downtime=2.0)
    speeds = tuple(np.random.default_rng(0).uniform(0.5, 2.0, n_workers))
    sc = Scenario(churn=churn, speeds=speeds, jobs_per_stream=jobs,
                  churn_pairs_per_worker=pairs, scheduler="packed" if width else "fifo_gang",
                  workers_per_job=width)
    cands = [1, 2, 4] if width else [1, 2, 4, 8, 16]
    RedundancyPlanner(n_workers, candidates=cands).plan_cluster(
        Pareto(1.0, 1.8), n_reps=reps, seed=5, scenario=sc, backend="jax")
    rec = spans.last_call()
    assert _entries(rec) == {"entry.plan_cluster": 1, "entry.frontier_dynamic": 1,
                             "draws.lanes": 1, "xfer.put": 2, "xfer.get": 1, "wait.device": 1}
    # the runner's outputs: starts, finishes, worker and cancelled seconds,
    # failures, rescues, replans and the lanes' step chunks
    assert rec["counts"]["d2h.arrays"] == 8
    # the serial steps: whole chunks, within the runner's budget
    steps = rec["counts"]["scan.steps"]
    assert steps > 0 and steps % epoch_scan._STEP_CHUNK == 0
    assert steps <= epoch_scan._shapes(n_workers, jobs, churn, None, pairs)[4] * epoch_scan._STEP_CHUNK
    assert "spec.backups" not in rec["counts"]  # no speculation, no count
    n_pad, jobs_pad, ev_pad, resc_cap, _ = epoch_scan._shapes(n_workers, jobs, churn, None, pairs)
    lanes_pad = epoch_scan._pow2(len(cands) * math.ceil(reps / jobs))
    f32 = i32 = 4
    lanes = lanes_pad * (
        (jobs_pad + resc_cap + 1) * n_pad * f32  # service, rescue, one speculation row
        + ev_pad * (f32 + i32 + 1)  # churn event times, workers, up flags
        + i32  # the lane's B
    )
    shared = jobs_pad * f32 + n_pad * f32 + 3 * 4  # arrivals, speeds, three scalars
    if width:  # per-job worker demand, B and cancel flag, and the default demand
        tail = jobs_pad * (i32 + i32 + 1) + i32
    else:  # blend, divisor table, two harmonic tables
        n_div = epoch_scan._pow2(divisor_table(n_workers).shape[1])
        tail = f32 + (n_pad + 1) * n_div * i32 + 2 * (n_pad + 1) * f32
    assert rec["counts"]["h2d.bytes"] == lanes + shared + tail


@pytest.mark.parametrize("policy", ["fifo_gang", "packed"])
def test_plan_cluster_counts_backups_and_scan_steps(policy, monkeypatch):
    """``spec.backups`` sums the lanes' launches and ``scan.steps`` is the largest lane's
    chunk count times the chunk, both from the one readback of a lane batch."""
    from repro.cluster import Speculation

    seen = []
    run = epoch_scan._run_lanes

    def keep(*args, **kw):
        out = run(*args, **kw)
        seen.append(out)
        return out

    monkeypatch.setattr(epoch_scan, "_run_lanes", keep)
    got = []
    build = epoch_scan._get_runner

    def chunks(cfg):
        runner = build(cfg)

        def call(*a):
            out = runner(*a)
            got.append(int(np.max(np.asarray(out["n_chunks"]))))
            return out

        return call

    monkeypatch.setattr(epoch_scan, "_get_runner", chunks)
    speeds = tuple(np.random.default_rng(0).uniform(0.5, 2.0, 8))
    sc = Scenario(speeds=speeds, jobs_per_stream=8, cancel_redundant=True,
                  speculation=Speculation(interval=0.25, theta=1.5, min_observations=2,
                                          max_backups=2),
                  scheduler=policy, workers_per_job=4 if policy == "packed" else None,
                  rep_chunk=2)
    RedundancyPlanner(8, candidates=[2, 4]).plan_cluster(
        Pareto(1.0, 1.8), n_reps=32, seed=2, scenario=sc, backend="jax")
    rec = spans.last_call()
    assert len(seen) == len(got) == 2  # two lane batches of two streams a candidate
    backups = sum(int(out["n_speculative"].sum()) for out in seen)
    assert backups > 0 and rec["counts"]["spec.backups"] == backups
    assert rec["counts"]["scan.steps"] == sum(got) * epoch_scan._STEP_CHUNK
    # per batch: the eight outputs and the lanes' launches, in one readback
    assert _entries(rec)["xfer.get"] == 2 and rec["counts"]["d2h.arrays"] == 2 * 9


def _layer_module(name):
    spec = importlib.util.spec_from_file_location(f"layer_{name}", LAYERS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _module_name(lower) -> str:
    """The module name of ``lower()``'s program; lowering must record no span."""
    with spans.span("sentinel"):
        lowered = lower()
    assert set(spans.last_call()["spans"]) == {"sentinel"}
    return str(lowered.compiler_ir().operation.attributes["sym_name"]).strip('"')


def test_device_programs_carry_the_module_names_the_benchmark_reads(monkeypatch):
    """A rename of ``_stream_slab`` or of the runner's ``lane`` fails here, not as a
    silently missing device-time metric."""
    from repro.cluster.vectorized import STREAM_HIST_EDGES, _stream_slab, stream_acc_init

    dt = jnp.float32
    args = (jnp.zeros((2, 8, 2, 2), dt), jnp.zeros(8, dt), jnp.zeros(8, dt),
            jnp.zeros(8, bool), jnp.zeros(8, jnp.int32), jnp.zeros((2, 3), dt),
            jnp.zeros((2, 3), dt), stream_acc_init(2, dt, 2), jnp.asarray(STREAM_HIST_EDGES, dt))
    stream_name = _module_name(lambda: _stream_slab.lower(
        *args, b=2, r=2, n_gangs=3, cancel_redundant=True, balanced=False, collect=False,
        n_classes=2))

    seen = []
    build = epoch_scan._get_runner

    def shapes_only(cfg):
        runner = build(cfg)

        def call(*a):
            seen.append((runner, a))
            out = jax.eval_shape(runner, *a)
            return jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), out)

        return call

    monkeypatch.setattr(epoch_scan, "_get_runner", shapes_only)
    sc = Scenario(churn=ChurnProcess(fail_rate=0.02, mean_downtime=2.0), jobs_per_stream=4,
                  churn_pairs_per_worker=4)
    names = set()
    for sched in (None, "packed"):
        seen.clear()
        epoch_scan.frontier_job_times_dynamic(
            Pareto(1.0, 1.8), 4, [1, 2], 8, scenario=sc.replace(
                scheduler=sched or "fifo_gang", workers_per_job=2 if sched else None))
        ((runner, a),) = seen
        names.add(_module_name(lambda: runner.lower(*a)))

    assert stream_name == "jit__stream_slab"
    assert names == {"jit_lane"}  # the gang and the space lane alike
    for layer in ("stream_scan_us", "stream_scan_ms"):
        assert _layer_module(layer).MODULE in stream_name
    assert _layer_module("epoch_scan_ms").MODULE in names
