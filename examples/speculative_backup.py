"""Speculative execution from partial progress, on all three substrates.

The paper's replication is *proactive*: pick (B, r) up front and pay the
redundancy on every batch.  The :class:`Speculation` policy is *reactive*:
run with no (or less) redundancy, watch each batch's elapsed time against
``theta x`` the running median of completed siblings, and launch a backup
replica on a free worker only for the laggards.  One policy object drives
all three substrates identically:

  1. the Python event engine (the reference semantics);
  2. the vectorized jax epoch scan (pinned to the engine bit-for-bit by
     ``tests/test_speculation.py``);
  3. the live asyncio runtime, where worker heartbeats double as partial
     progress reports and the recorded trace replays through the engine
     as its digital twin.

The walkthrough ends with the Scenario v2 serialization story: the frozen
spec round-trips through JSON exactly, and ``replace()`` derives variants.

Run:  PYTHONPATH=src python examples/speculative_backup.py
"""
import numpy as np

from repro.cluster import ClusterEngine, Job, Scenario, Speculation, simulate_epochs
from repro.cluster.runtime import LiveJob, Runtime, replay_trace
from repro.compile_cache import enable_compile_cache
from repro.core.planner import RedundancyPlanner
from repro.core.service_time import Pareto


def main():
    n_workers = 10
    n_jobs = 40
    dist = Pareto(sigma=1.0, alpha=1.5)  # heavy tail: the straggler regime
    spec = Speculation(interval=0.4, theta=2.0, min_observations=3)

    # --- 1. engine: planned vs speculative vs hybrid -------------------------
    plan = RedundancyPlanner(n_workers).plan(dist, objective="mean")
    variants = {
        "no redundancy": (n_workers, None),
        "planned      ": (plan.n_batches, None),
        "speculative  ": (n_workers, spec),
        "hybrid       ": (plan.n_batches, spec),
    }
    base = None
    for label, (b, sp) in variants.items():
        rep = ClusterEngine(
            n_workers, seed=0, n_batches=b, cancel_redundant=True, speculation=sp
        ).run([Job(job_id=i, dist=dist, n_tasks=n_workers) for i in range(n_jobs)])
        mean_t = float(rep.compute_times.mean())
        base = base or mean_t
        print(
            f"[eng ] {label} B={b:2d}: mean job time {mean_t:6.2f} "
            f"(x{base / mean_t:.2f} vs baseline), "
            f"{rep.n_speculative or 0} backups, "
            f"{rep.worker_seconds:.0f} worker-seconds"
        )

    # --- 2. the same policy on the jax epoch scan ----------------------------
    sc = Scenario(speculation=spec, cancel_redundant=True)
    rep = simulate_epochs(
        dist, n_workers, n_workers, np.zeros(n_jobs), n_reps=200, seed=0, scenario=sc
    )
    t = rep.compute_times
    print(
        f"[scan] 200 Monte-Carlo reps in one device call: mean job time "
        f"{t[np.isfinite(t)].mean():.2f}, "
        f"{rep.n_speculative.mean():.1f} backups per rep "
        f"(engine-exact semantics; see tests/test_speculation.py)"
    )

    # --- 3. live runtime: backups from real partial progress ----------------
    live_sc = Scenario(
        n_batches=3, cancel_redundant=True, speculation=Speculation(interval=0.12, theta=2.0)
    )
    # worker 2's skew makes batch 2 a genuine straggler; its heartbeats carry
    # the partial-progress evidence the master requires before backing it up
    report = Runtime(3, live_sc).run([LiveJob(job_id=0, costs=(0.15, 0.15, 1.0), skew=0.8)])
    acct = report.accounting()
    print(
        f"[live] 1 job on 3 workers: {acct['n_speculative']} speculative "
        f"launch(es), {acct['cancelled_seconds_saved']:.2f}s reclaimed by "
        f"cancelling the overtaken original"
    )
    # the trace alone is replayable: its first event embeds the Scenario, and
    # each speculative launch stamp replays as a scripted speculation epoch
    twin = replay_trace(report.trace)
    assert twin.accounting() == acct  # bit-for-bit digital twin
    print("[live] replay_trace(trace) == live accounting, bit for bit")

    # --- 4. Scenario v2: exact JSON round-trip + replace() -------------------
    blob = live_sc.to_json()
    assert Scenario.from_json(blob) == live_sc  # every field, floats bit-exact
    hotter = live_sc.replace(speculation=Speculation(interval=0.06, theta=1.5))
    print(
        f"[spec] Scenario round-trips through {len(blob)} bytes of JSON; "
        f"replace() derives variants (theta {live_sc.speculation.theta} -> "
        f"{hotter.speculation.theta}) without mutating the frozen original"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
