"""The hedged fan-out cell on the CPU at a tiny size: the entry equals its plain reference,
and the bfloat16 control and planted faults do not; the reference draws at the program's
shapes, replays the engine's hand-computed fixture, and the step-time reader reads the
program's step counter.

The tiny cell keeps the configuration's policy and cuts the cluster to 16 workers and the
fan-out to 8 leaves, so that B = 8 has the five finished leaves a hedge needs.
"""
import dataclasses

import numpy as np
import pytest
import repro.cluster.epoch_scan as epoch_mod
from repro import spans
from repro.cluster import Speculation
from yardstick import harness, hedge_reference
from yardstick.trace import Reduced

SEED = 3000000023  # larger than 32 signed bits hold
CELL = "plan_cluster.hedge100"


def tiny():
    cell = harness.load_cell(CELL)
    cell["config"].update(workers=16)
    cell["traffic"].update(width=8, reps=64, jobs_per_stream=16)
    return cell


def run_tiny(seed=SEED):
    return harness.run(["--workload", CELL, "--seed", str(seed), "--seconds", "0.2",
                        "--trace", "0"], require_tpu=False, cell=tiny())


@pytest.mark.parametrize("seed", [SEED, 17])
def test_entry_equals_its_reference_and_hedges(seed):
    cell = tiny()
    work = cell["entry"].Work(cell["config"], cell["traffic"], seed)
    work.step(0)
    assert spans.last_call()["counts"]["spec.backups"] > 0
    got = work.numbers(work.answers[0], work.reference(0))
    assert got == {"frontier_rel": 0.0}  # float32 lanes and the float32-rounded reference
    res = run_tiny(seed)
    assert res["correct"], res["checks"]


def test_control_is_not_correct():
    cell = tiny()
    work = cell["entry"].Work(cell["config"], cell["traffic"], SEED)
    checks = harness.check(work, cell["traffic"], [0, 1], SEED, control=True)
    assert any(v > lim for _, v, lim in checks), checks


def _plant(fault, monkeypatch):
    real = epoch_mod._run_lanes

    def broken(dist, cfg, *args, **kw):
        if fault == "never_launched":  # the lanes run without their backups
            cfg = dataclasses.replace(cfg, spec=None)
        elif fault == "budget_ignored":  # fifty times the backups a request may take
            cfg = dataclasses.replace(
                cfg, spec=dataclasses.replace(cfg.spec, max_backups=50 * cfg.spec.max_backups))
        out = real(dist, cfg, *args, **kw)
        fin, start = out["finishes"], out["starts"]
        if fault == "state_unchanged":  # every lane returns its initial state
            out["finishes"] = np.full_like(fin, np.inf)
        elif fault == "half_dropped":  # half of the lanes dropped, the mean over the rest
            out["finishes"] = np.where((np.arange(len(fin)) % 2 == 0)[:, None], fin, np.inf)
        elif fault == "answer_altered":  # job times 1% longer than run
            out["finishes"] = start + (fin - start) * 1.01
        return out

    monkeypatch.setattr(epoch_mod, "_run_lanes", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "never_launched", "budget_ignored",
                                   "half_dropped", "answer_altered"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    _plant(fault, monkeypatch)
    assert not run_tiny()["correct"]


@pytest.mark.parametrize("n_workers,n_jobs,max_backups", [(200, 96, 2), (16, 16, 2),
                                                          (6, 20, 1), (201, 33, 3)])
def test_draw_layout_is_the_programs(n_workers, n_jobs, max_backups):
    """The hedge reference draws at the program's shapes; a drift shows here first."""
    spec = Speculation(max_backups=max_backups)
    n_pad, jobs_pad, _, _, _ = epoch_mod._shapes(n_workers, n_jobs, None, None, 8,
                                                 speculation=spec)
    assert hedge_reference.draw_layout(n_workers, n_jobs, max_backups) == (
        jobs_pad, n_pad, epoch_mod._spec_cap(jobs_pad, spec))


def test_reference_replays_the_engines_fixture():
    """Two 2-leaf requests on 4 workers, one leaf 4x slow: its batch is hedged on the
    request's own freed worker at 1.75 and answers at 2.75; the other request takes 1.0
    (the engine's own-allocation-first fixture, tests/test_speculation.py)."""
    ones = np.ones((2, 4))
    st, fin, k = hedge_reference.run_lane(
        ones, ones, n_workers=4, width=2, b0=2, speeds=(1.0, 0.25, 1.0, 1.0), n_tasks=2,
        n_jobs=2, theta=1.5, interval=0.25, min_observations=1, max_backups=1)
    assert k == 1
    assert (fin - st).tolist() == [2.75, 1.0]


def test_epoch_step_us_reads_the_step_counter(monkeypatch):
    reader = harness.layer_reader("epoch_step_us.hedge")
    r = Reduced(window_s=1.0, busy_s=0.5, module_s={"jit_lane": 0.2}, idle_by_host={},
                n_spans=1)
    monkeypatch.setattr(spans, "last_call", lambda: {"spans": {}, "counts": {"scan.steps": 1600}})
    assert reader.read(r, 2.0) == pytest.approx(1e6 * 0.2 / 2.0 / 1600)
    monkeypatch.setattr(spans, "last_call", lambda: {"spans": {}, "counts": {}})
    assert reader.read(r, 2.0) is None  # a program without the counter, as the parent
