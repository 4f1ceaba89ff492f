"""The reduction from a profiler trace to busy time, module time and idle gaps."""
import gzip
import pathlib
from types import SimpleNamespace as NS

import numpy as np
import pytest
from yardstick.trace import SPAN, reduce_planes, reduce_trace

# a traced call of the stream kernel on one v5e chip ("TPU v5 lite"), at a tiny size
FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "v5e_stream_trace.xplane.pb.gz"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def line(name, events):
    return NS(name=name, events=events)


def synthetic():
    host = line("python", [
        ev(SPAN, 1000, 8000), ev(SPAN, 12000, 8000),
        ev("PjitFunction(_stream_slab)", 1500, 500), ev("DevicePutWithSharding", 8000, 1000),
    ])
    dev = line("XLA Modules", [
        ev("jit__stream_slab(12)", 2000, 3000), ev("jit__stream_slab(12)", 4000, 2000),
        ev("jit_convert(3)", 14000, 1000), ev("jit__stream_slab(12)", 19000, 5000),
    ])
    return [NS(name="/host:CPU", lines=[host, line("other", [ev("x", 0, 99999)])]),
            NS(name="/device:TPU:0", lines=[dev, line("XLA Ops", [ev("fusion", 0, 1)])])]


def test_synthetic_trace_reduces_by_hand():
    r = reduce_planes(synthetic())
    # window: first span start 1000 to last span end 20000
    assert r.window_s == pytest.approx(19000e-9)
    # busy: [2000, 6000) + [14000, 15000) + [19000, 20000) clipped to the window
    assert r.busy_s == pytest.approx(6000e-9)
    assert r.module_s == pytest.approx({"jit__stream_slab": 5000e-9 + 1000e-9,
                                        "jit_convert": 1000e-9})
    assert r.module_time("_stream_slab") == pytest.approx(6000e-9)
    # gaps [1000,2000) mid 1500 in the pjit call; [6000,14000) mid 10000 between spans;
    # [15000,19000) mid 17000 inside the second span only
    assert r.idle_by_host == pytest.approx({"PjitFunction(_stream_slab)": 1000e-9,
                                            "outside host spans": 8000e-9, SPAN: 4000e-9})
    assert r.n_spans == 2
    bd = r.breakdown()
    assert bd["device_ops"][0][0] == "jit__stream_slab"
    assert bd["idle_gaps"][0] == ["outside host spans", pytest.approx(8000e-9)]


def test_trace_without_spans_is_refused():
    planes = synthetic()
    planes[0].lines[0].events = planes[0].lines[0].events[2:]
    with pytest.raises(ValueError):
        reduce_planes(planes)


def _raster(trace_dir):
    """Busy time of a recorded trace by a second method: a 100 ns raster."""
    from jax.profiler import ProfileData
    from yardstick.trace import find_xplane

    planes = list(ProfileData.from_file(str(find_xplane(trace_dir))).planes)
    host = [p for p in planes if p.name.startswith("/host:")]
    spans = [(e.start_ns, e.start_ns + e.duration_ns) for p in host for ln in p.lines
             for e in ln.events if e.name == SPAN]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    grid = np.zeros(int((hi - lo) // 100) + 1, bool)
    dev = [p for p in planes if p.name.startswith("/device:")][0]
    mods = [ln for ln in dev.lines if ln.name == "XLA Modules"][0]
    for e in mods.events:
        s, t = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
        if t > s:
            grid[int((s - lo) // 100):int((t - lo) // 100)] = True
    return (hi - lo) * 1e-9, grid.sum() * 100e-9, len(spans)


def test_recorded_chip_trace(tmp_path):
    (tmp_path / "t.xplane.pb").write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    r = reduce_trace(tmp_path)
    window, busy, spans = _raster(tmp_path)
    assert r.window_s == pytest.approx(window)
    assert r.busy_s == pytest.approx(busy, rel=0.02)
    assert r.n_spans == spans
    assert 0 < r.busy_s < r.window_s
    assert r.module_time("_stream_slab") > 0
    idle = sum(r.idle_by_host.values())
    assert idle == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    # the numbers of this recording: one traced call of 96 jobs on 16 pools, 3 slabs
    assert r.n_spans == 1
    assert r.window_s == pytest.approx(0.02708932)
    assert r.busy_s == pytest.approx(0.003790025)
    assert r.module_s["jit__stream_slab"] == pytest.approx(0.003770555)
    assert r.idle_by_host["np.asarray(jax.Array)"] == pytest.approx(0.007137806)
