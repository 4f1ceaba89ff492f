"""The span and counter metrics read the program's record of its last call.

Each reader returns nothing on a program that keeps no record, or when the
record lacks what it reads (without a traced device window, as every reader:
``test_catalog.py``); with busy time it returns the record's number in its
unit.  The recorded v5e trace shows
the program's spans on the harness's host line, on the device trace's clock.
"""
import gzip
import pathlib
import sys

import pytest
from repro import spans
from yardstick import harness
from yardstick.trace import SPAN, Reduced, reduce_trace

# one traced tiny plan_cluster plan on one v5e chip, with the program's spans
FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_spans_trace.xplane.pb.gz"
READERS = ["host_draws_ms", "host_draws_us", "xfer_ms", "xfer_us", "host_entry_ms", "h2d_mb"]
SEED = 3000000023


def reader(name):
    return harness.load_module(harness.BENCH_DIR / "layers" / f"{name}.py")


def reduced(busy_s):
    return Reduced(window_s=1.0, busy_s=busy_s, module_s={}, idle_by_host={}, n_spans=1)


@pytest.fixture(scope="module")
def records():
    """The record of one tiny plan_cluster plan and of one tiny replayed day, on the CPU."""
    from test_entries_cpu import tiny

    out = {}
    for name in ("plan_cluster.churn", "cell_day.heavy_packed"):
        cell = tiny(name)
        work = cell["entry"].Work(cell["config"], cell["traffic"], SEED)
        work.step(0)
        out[name] = spans.last_call()
    return out


def own(rec, prefix):
    return sum(s for n, (_, s, _) in rec["spans"].items() if n.startswith(prefix + "."))


@pytest.mark.parametrize("name", READERS)
def test_nothing_from_a_program_without_spans(name, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.spans", None)  # the import fails, as on a parent
    assert reader(name).read(reduced(0.5), 10.0) is None


def test_plan_readers_read_the_last_plan(records, monkeypatch):
    rec = records["plan_cluster.churn"]
    monkeypatch.setattr(spans, "last_call", lambda: rec)
    r = reduced(0.5)
    assert reader("host_draws_ms").read(r, 3.0) == pytest.approx(1e3 * own(rec, "draws"))
    assert reader("xfer_ms").read(r, 3.0) == pytest.approx(1e3 * own(rec, "xfer"))
    assert reader("host_entry_ms").read(r, 3.0) == pytest.approx(1e3 * own(rec, "entry"))
    assert reader("h2d_mb").read(r, 3.0) == pytest.approx(rec["counts"]["h2d.bytes"] / 1e6)
    assert all(reader(n).read(r, 3.0) > 0 for n in ("host_draws_ms", "xfer_ms", "host_entry_ms"))
    # a plan replays no stream: the per-job readers find no job-reps
    assert reader("host_draws_us").read(r, 3.0) is None
    assert reader("xfer_us").read(r, 3.0) is None


def test_day_readers_read_the_last_day(records, monkeypatch):
    rec = records["cell_day.heavy_packed"]
    monkeypatch.setattr(spans, "last_call", lambda: rec)
    jobs = rec["counts"]["stream.job_reps"]
    assert jobs == 2 * 300  # the tiny day: 2 reps of 300 jobs
    r = reduced(0.5)
    assert reader("host_draws_us").read(r, 1.0) == pytest.approx(1e6 * own(rec, "draws") / jobs)
    assert reader("xfer_us").read(r, 1.0) == pytest.approx(1e6 * own(rec, "xfer") / jobs)
    assert reader("h2d_mb").read(r, 1.0) == pytest.approx(rec["counts"]["h2d.bytes"] / 1e6)


def test_nothing_when_the_record_lacks_the_spans(monkeypatch):
    monkeypatch.setattr(spans, "last_call", lambda: {"spans": {"entry.x": (1.0, 1.0, 1)},
                                                     "counts": {}})
    r = reduced(0.5)
    for name in ("host_draws_ms", "host_draws_us", "xfer_ms", "xfer_us", "h2d_mb"):
        assert reader(name).read(r, 1.0) is None
    assert reader("host_entry_ms").read(r, 1.0) == pytest.approx(1e3)


def test_recorded_chip_trace_shares_the_harness_clock(tmp_path):
    from jax.profiler import ProfileData

    (tmp_path / "t.xplane.pb").write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    planes = ProfileData.from_file(str(tmp_path / "t.xplane.pb")).planes
    host = [ln for p in planes if p.name.startswith("/host:") for ln in p.lines
            if any(e.name == SPAN for e in ln.events)]
    assert len(host) == 1
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in host[0].events]
    (_, lo, hi), = [e for e in events if e[0] == SPAN]
    program = {n: (s, e) for n, s, e in events if n.split(".")[0] in ("entry", "draws", "xfer",
                                                                        "wait")}
    assert set(program) == {"entry.plan_cluster", "entry.frontier_dynamic", "draws.lanes",
                            "xfer.put", "xfer.get", "wait.device"}
    assert all(lo <= s <= e <= hi for s, e in program.values())
    r = reduce_trace(tmp_path)
    assert r.n_spans == 1 and 0 < r.busy_s < r.window_s
    assert r.module_time("jit_lane") > 0
    # the device idles while the host draws the lanes, and the gap is named for it
    assert max(r.idle_by_host, key=r.idle_by_host.get) == "draws.lanes"
    # the numbers of this recording: 200 lanes (256 padded) of 16 jobs, 2 churn pairs a worker
    assert r.window_s == pytest.approx(0.078044609)
    assert r.busy_s == pytest.approx(0.024800842)
    assert r.module_s["jit_lane"] == pytest.approx(0.024796047)
    assert r.idle_by_host["draws.lanes"] == pytest.approx(0.042940648)
