"""Each entry's set-up, step and check on the CPU at tiny sizes; the control and the
planted faults come out as not correct.

The harness's look for a chip is skipped (``require_tpu=False``); the rest of
a run is driven as on the chip.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest
import repro.cluster.epoch_scan as epoch_mod
import repro.cluster.stream as stream_mod
from yardstick import harness

SEED = 3000000023  # larger than 32 signed bits hold


BENCH = json.loads((harness.CHECKOUT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def tiny(name):
    cell = harness.load_cell(name)
    entry = cell["traffic"]["entry"]
    if entry == "simulate_stream":
        cell["config"].update(pools=16, jobs_per_day=300)
        cell["traffic"].update(reps=2, slab=128)
    elif entry == "plan_slo":
        cell["traffic"].update(n_jobs=120, reps=2, slab=64)
    else:
        cell["config"]["churn"].update(pairs_per_worker=16)
        cell["traffic"].update(reps=64, jobs_per_stream=32)
    return cell


def run_tiny(name, trace=0):
    return harness.run(["--workload", name, "--seed", str(SEED), "--seconds", "0.3",
                        "--trace", str(trace)], require_tpu=False, cell=tiny(name))


@pytest.mark.parametrize("name", CELLS)
def test_run_is_correct_and_prints_its_checks(name, capsys):
    res = run_tiny(name)
    out, err = capsys.readouterr()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert json.loads(out.strip().splitlines()[-1]) == json.loads(json.dumps(res))
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", harness.load_cell(name)["entry"].METRIC}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] >= 1
    last = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") and "limit" in line for line in last)


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_device_window(name):
    res = run_tiny(name, trace=1)
    assert res["correct"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny(name)
    work = cell["entry"].Work(cell["config"], cell["traffic"], SEED)
    checks = harness.check(work, cell["traffic"], [0, 1, 2, 3], SEED, control=True)
    assert any(v > lim for _, v, lim in checks), checks


def _break_stream(fault, monkeypatch):
    real = stream_mod._stream_slab

    def broken(*args, **kw):
        args = list(args)
        if fault == "half_left_out":  # half of the jobs masked out of the statistics
            args[3] = args[3] & (jnp.arange(args[3].shape[0]) % 2 == 0)
        elif fault == "answer_altered":  # service 1% slower than drawn
            args[0] = args[0] * 1.01
        out = real(*args, **kw)
        if fault == "state_unchanged":  # the step hands its input state back
            return args[5], args[6], args[7], out[3]
        return out

    monkeypatch.setattr(stream_mod, "_stream_slab", broken)


def _break_lanes(fault, monkeypatch):
    real = epoch_mod._run_lanes

    def broken(*args, **kw):
        out = real(*args, **kw)
        fin, start = out["finishes"], out["starts"]
        if fault == "state_unchanged":  # every lane returns its initial state
            out["finishes"] = np.full_like(fin, np.inf)
        elif fault == "half_left_out":  # half of the lanes dropped, the mean over the rest
            out["finishes"] = np.where((np.arange(len(fin)) % 2 == 0)[:, None], fin, np.inf)
        elif fault == "answer_altered":  # job times 1% longer than run
            out["finishes"] = start + (fin - start) * 1.01
        return out

    monkeypatch.setattr(epoch_mod, "_run_lanes", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out", "answer_altered"])
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    if harness.load_cell(name)["traffic"]["entry"] == "plan_cluster":
        _break_lanes(fault, monkeypatch)
    else:
        _break_stream(fault, monkeypatch)
    res = run_tiny(name)
    assert not res["correct"]
    json.loads(json.dumps(res, allow_nan=False))  # the result line stays valid JSON


@pytest.mark.parametrize("n_workers,n_jobs,pairs", [(16, 96, 128), (16, 32, 16), (6, 20, 4), (256, 96, 128)])
def test_draw_layout_is_the_programs(n_workers, n_jobs, pairs):
    """The epoch reference draws at the program's shapes; a drift shows here first."""
    from repro.cluster.workers import ChurnProcess
    from yardstick.epoch_reference import draw_layout

    churn = ChurnProcess(fail_rate=0.02, mean_downtime=2.0)
    n_pad, jobs_pad, _, resc_cap, n_chunks = epoch_mod._shapes(n_workers, n_jobs, churn, None, pairs)
    assert draw_layout(n_workers, n_jobs, pairs) == (jobs_pad, n_pad, resc_cap,
                                                     n_chunks * epoch_mod._STEP_CHUNK)
