"""One run of one cell: set up, warm up, measure a closed loop, check, print.

Everything that belongs to one configuration, traffic mix, entry or
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``:

* ``configs/<config>.json``: the deployment (sizes, precision, source);
* ``traffic/<traffic>.json``: the entry that serves the mix, its
  parameters, the size of the checked sample and the limits of the check;
* ``entries/<entry>.py``: set-up, one call, the work it counts, the answer
  it keeps and the reference answer it is compared with;
* ``layers/<metric>.py`` (or ``layers/<prefix>.py`` for ``<prefix>.<cell
  group>``): one per-layer metric, read from the reduced trace.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]
CACHE_DIR = CHECKOUT / ".jax_cache"
TRACE_DIR = CHECKOUT / ".bench_trace"
# the traced window: at least one call.  A v5e trace records every XLA op of
# every scan step, some 180 MB and a minute of host time per traced second
TRACE_SECONDS = 0.5


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str):
    exact = BENCH_DIR / "layers" / f"{metric}.py"
    return load_module(exact if exact.exists() else BENCH_DIR / "layers" / f"{metric.split('.')[0]}.py")


def load_cell(name: str, bench: dict | None = None) -> dict:
    """The workload entry with its configuration, traffic, entry module and metrics."""
    bench = bench if bench is not None else load_json(CHECKOUT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(CHECKOUT / configs[cell["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json")
    entry = load_module(BENCH_DIR / "entries" / f"{traffic['entry']}.py")

    def applies(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"]
           if applies(m) and m["name"] in ("setup_s", entry.METRIC)]
    layers = [m for m in bench["per_layer"] if applies(m)]
    return {"cell": cell, "config": config, "traffic": traffic, "entry": entry,
            "end_to_end": e2e, "per_layer": layers}


def enable_cache(jax) -> None:
    """The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR``, else a fixed checkout path."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # the stream and planner programs compile in well under a second each;
    # jax's default threshold (1 s) would compile them again in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def closed_loop(work, seconds: float, start: int, span=None):
    """Call the entry back to back until ``seconds`` have passed; every call counts.

    Returns the work units, the elapsed seconds, the call indices and each
    call's seconds (for the stderr line: a stall shows as one long call).
    """
    units, i, took = 0.0, start, []
    t0 = t_prev = time.perf_counter()
    while True:
        if span is not None:
            with span("bench.step"):
                units += work.step(i)
        else:
            units += work.step(i)
        i += 1
        now = time.perf_counter()
        took.append(now - t_prev)
        t_prev = now
        if now - t0 >= seconds:
            return units, now - t0, list(range(start, i)), took


def check(work, traffic: dict, calls: list, seed: int, control: bool = False) -> list:
    """Compare a sample of the window's answers, drawn from the seed, with the reference.

    Returns ``[(name, reading, limit), ...]``.  With ``control`` the
    reference in the precision below the configuration's is put in the
    program's place.
    """
    rng = np.random.default_rng(np.random.SeedSequence((int(seed), 0xC4EC)))
    k = min(int(traffic["check_sample"]), len(calls))
    sample = [calls[-1], *rng.choice(calls[:-1], size=k - 1, replace=False).tolist()]
    readings = []
    for i in sample:
        got = work.reference(i, control=True) if control else work.answers[i]
        readings.append(work.numbers(got, work.reference(i)))
    worst = {n: max(r[n] for r in readings) for n in readings[0]}
    return [(n, worst[n], traffic["limits"][n]) for n in sorted(worst)]


def run(argv=None, require_tpu: bool = True, cell: dict | None = None) -> dict:
    """One run; returns the result line's object (and prints it).

    Tests pass ``require_tpu=False`` and a ``cell`` from :func:`load_cell`
    with its sizes cut, to drive a run on the CPU.
    """
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="On-chip benchmark: one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cell if cell is not None else load_cell(args.workload)
    sys.path.insert(0, str(CHECKOUT / "src"))

    import jax

    from .compiles import Compiles, device_record, tpu_devices

    if require_tpu:
        devs = tpu_devices(int(cell["cell"]["chips"]))
        enable_cache(jax)
    else:
        devs = jax.devices()[:1]
    compiles = Compiles()
    entry = cell["entry"]
    work = entry.Work(cell["config"], cell["traffic"], args.seed)
    work.warm()
    setup_s = time.perf_counter() - t_start
    setup_line = compiles.line()
    compiles.reset()

    trace_dir = TRACE_DIR / args.workload
    reduced = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python tracing would slow the host it measures
        with jax.profiler.trace(str(trace_dir), profiler_options=opts):
            units, elapsed, calls, took = closed_loop(
                work, min(args.seconds, TRACE_SECONDS), 0, jax.profiler.TraceAnnotation)
    else:
        units, elapsed, calls, took = closed_loop(work, args.seconds, 0)
    window_line = compiles.line()
    device = device_record(devs, len(jax.devices()))

    checks = check(work, cell["traffic"], calls, args.seed)
    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)

    metrics = {}
    result = {"correct": correct, "attempted": len(calls), "failed": 0}
    if args.trace:
        from .trace import reduce_trace

        reduced = reduce_trace(trace_dir)
        for m in cell["per_layer"]:
            v = layer_reader(m["name"]).read(reduced, units)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in cell["end_to_end"]:
            v = setup_s if m["name"] == "setup_s" else entry.value(units, elapsed)
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if reduced is not None:
        result["breakdown"] = reduced.breakdown()
    # a reading that is not a number (a missing or unfinished answer) prints as a string,
    # which keeps the line valid JSON
    result["checks"] = {n: {"value": v if math.isfinite(v) else str(v), "limit": lim}
                        for n, v, lim in checks}

    print(f"set-up: {setup_s:.3f} s, {setup_line}", file=sys.stderr)
    print(f"window: {len(calls)} calls, {units:g} {entry.UNIT_OF_WORK} in {elapsed:.3f} s "
          f"(call seconds: median {np.median(took):.4f}, longest {max(took):.4f}), "
          f"{window_line} inside it; peak device memory {device['memory_peak_bytes']} bytes",
          file=sys.stderr)
    for n, v, lim in checks:
        print(f"check {n} = {v!r} (limit {lim!r}): {'ok' if v <= lim else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


def main(argv=None) -> int:
    run(argv)
    return 0
