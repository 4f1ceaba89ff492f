"""Elastic failover on the event-driven cluster engine.

The walkthrough closes the planner -> engine -> replanner loop:

  1. plan (B, r) for a heavy-tail workload from the closed forms;
  2. execute a stream of jobs on :class:`ClusterEngine` with worker
     fail/join churn -- dead replicas are rescued, coverage never breaks;
  3. the :class:`OnlineReplanner` refits the service-time model from the
     engine's observed task times and re-picks (B, r) mid-stream;
  4. the mesh-level view (``repro.distributed.rdp``) shows how the final
     plan maps onto a ("replica", "shard") device-mesh factorization;
  5. the same churned + heterogeneous + replanning scenario replayed on the
     vectorized jax epoch scan -- hundreds of Monte-Carlo reps in one device
     call, and a whole-frontier churned ``plan_cluster`` sweep that used to
     require one Python event loop per candidate.

Run:  PYTHONPATH=src python examples/elastic_failover.py
"""
import numpy as np

from repro.cluster import (
    ChurnProcess,
    ClusterEngine,
    Job,
    OnlineReplanner,
    ReplanConfig,
    Scenario,
    simulate_epochs,
)
from repro.compile_cache import enable_compile_cache
from repro.core.planner import RedundancyPlanner
from repro.core.service_time import Pareto
from repro.distributed import rdp


def main():
    n_workers = 16
    dist = Pareto(sigma=1.0, alpha=1.8)  # heavy-tail step times

    # --- 1. plan from the closed forms --------------------------------------
    plan = RedundancyPlanner(n_workers).plan(dist, objective="mean")
    print(
        f"[plan] N={n_workers}: B={plan.n_batches} shards x r={plan.replication} "
        f"replicas (predicted E[T]={plan.predicted_mean:.2f})"
    )

    # --- 2. execute under churn ---------------------------------------------
    controller = OnlineReplanner(
        n_workers, window=512, refit_every=128, min_observations=96, initial_plan=plan
    )
    engine = ClusterEngine(
        n_workers,
        seed=42,
        cancel_redundant=True,
        churn=ChurnProcess(fail_rate=0.02, mean_downtime=3.0),
        controller=controller,
    )
    jobs = [Job(job_id=i, dist=dist, n_tasks=n_workers) for i in range(40)]
    report = engine.run(jobs)

    t = report.compute_times
    print(
        f"[run ] {len(report.records)} jobs, {report.n_worker_failures} worker failures, "
        f"{report.n_replicas_rescued} replicas rescued, all completed: "
        f"{bool(np.isfinite(t).all())}"
    )
    print(
        f"[run ] mean job time {t[np.isfinite(t)].mean():.2f}, "
        f"{report.cancelled_seconds_saved:.0f} worker-seconds reclaimed by cancellation"
    )

    # --- 3. the replanner refit from observed task times ---------------------
    final = controller.current
    print(
        f"[ctl ] {report.n_replans} replan(s) from "
        f"{len(controller.observations)} observed task times: "
        f"B {plan.n_batches} -> {final.n_batches} ({final.source})"
    )

    # --- 4. mesh view: plan -> ("replica", "shard") factorization ------------
    cov = rdp.surviving_coverage(final, [True] * final.n_workers)
    print(
        f"[mesh] final plan factorizes the data axis as "
        f"(replica={final.replication}, shard={final.n_batches}); "
        f"replicas per shard: {cov['replicas_per_shard']}"
    )
    # --- 5. the same dynamics, vectorized: the jax epoch scan -----------------
    rep = simulate_epochs(
        dist,
        n_workers,
        plan.n_batches,
        np.zeros(40),
        n_reps=200,
        seed=42,
        scenario=Scenario(
            cancel_redundant=True,
            churn=ChurnProcess(fail_rate=0.02, mean_downtime=3.0),
            replan=ReplanConfig(window=512, refit_every=128, min_observations=96),
        ),
    )
    t = rep.compute_times
    print(
        f"[scan] 200 Monte-Carlo reps of the same churned scenario in one "
        f"device call: mean job time {t[np.isfinite(t)].mean():.2f}, "
        f"{rep.n_worker_failures.mean():.1f} failures and "
        f"{rep.n_replicas_rescued.mean():.1f} rescues per rep, "
        f"{rep.n_replans.mean():.1f} replans"
    )
    hetero = RedundancyPlanner(n_workers).plan_cluster(
        dist,
        n_reps=400,
        seed=7,
        scenario=Scenario(
            churn=ChurnProcess(fail_rate=0.02, mean_downtime=3.0),
            speeds=tuple(1.0 + 0.5 * (i % 3) for i in range(n_workers)),
        ),
    )
    print(
        f"[scan] churned + heterogeneous frontier sweep on jax "
        f"({hetero.source}): B={hetero.n_batches} x r={hetero.replication}"
    )
    print(
        "\nCheckpoint restore across mesh shapes is exercised in "
        "tests/test_distributed_multidev.py::test_checkpoint_cross_mesh_restore."
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
