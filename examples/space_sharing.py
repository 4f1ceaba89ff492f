"""Space sharing: two job classes with different redundancy compete for workers.

The paper's (B, r) results are *per job* -- but a real cluster runs many jobs
at once, and the whole-cluster FIFO gang (the engine's default) forces every
concurrent job onto one schedule and one plan.  The space-sharing scheduler
lifts that: jobs request disjoint worker subsets (``workers_per_job``) and
each carries its own ``JobPlan`` (B, r, cancellation), so the §V
mean-vs-predictability trade-off becomes a *policy choice per job class*:

  * class A ("interactive"): 4 workers at full diversity B=1 (r=4) -- every
    task replicated everywhere in the subset; slowest mean, tightest tail;
  * class B ("batch"): 4 workers at full parallelism B=4 (r=1) -- fastest
    mean under light tails, widest spread under heavy ones.

Run me::

    PYTHONPATH=src python examples/space_sharing.py
"""

import numpy as np

from repro.cluster import ClusterEngine, Job, JobPlan, Scenario, simulate_epochs
from repro.compile_cache import enable_compile_cache
from repro.core.planner import RedundancyPlanner
from repro.core.service_time import Pareto

N, WPJ = 12, 4
DIST = Pareto(sigma=1.0, alpha=1.8)  # heavy-tailed stragglers: §V's regime
PLAN_A = JobPlan(workers=WPJ, n_batches=1)  # full diversity within the subset
PLAN_B = JobPlan(workers=WPJ, n_batches=WPJ)  # full parallelism within it


def one_timeline() -> None:
    """A single seeded run, printed: three jobs run concurrently."""
    jobs = [
        Job(job_id=i, dist=DIST, n_tasks=WPJ, plan=(PLAN_A, PLAN_B)[i % 2])
        for i in range(8)
    ]
    rep = ClusterEngine(N, seed=7, scheduler="packed").run(jobs)
    print(f"one packed timeline on {N} workers ({WPJ} per job):")
    for r in rep.records:
        klass = "A (B=1,r=4)" if r.job_id % 2 == 0 else "B (B=4,r=1)"
        print(
            f"  job {r.job_id} [{klass}]  start {r.start:7.2f}  "
            f"finish {r.finish:7.2f}  response {r.response_time:7.2f}"
        )


def class_stats() -> None:
    """Monte-Carlo per-class response stats, packed vs the gang baseline."""
    n_jobs, reps = 16, 400
    plans = [PLAN_A, PLAN_B]
    arr = np.zeros(n_jobs)
    packed = simulate_epochs(
        DIST, N, None, arr, reps, seed=1, scenario=Scenario(scheduler="packed", job_plans=plans)
    )
    gang = simulate_epochs(DIST, N, None, arr, reps, seed=1)
    print("\nper-class response times (packed space sharing, mean over "
          f"{reps} reps x {n_jobs} jobs):")
    resp = packed.response_times
    for k, name in ((0, "A full diversity"), (1, "B full parallelism")):
        cls = resp[:, k::2].ravel()
        print(
            f"  class {name:<20s} mean {cls.mean():7.2f}  "
            f"p95 {np.percentile(cls, 95):7.2f}  CoV {cls.std() / cls.mean():.2f}"
        )
    print(
        f"  gang baseline (serial)   mean {gang.response_times.mean():7.2f}  "
        f"p95 {np.percentile(gang.response_times, 95):7.2f}"
    )
    print("  -> under heavy tails diversity wins both mean and tail (the")
    print("     paper's §V point), and *both* classes beat the serial gang:")
    print(f"     the cluster runs {N // WPJ} jobs at once instead of one.")
    print("     The mean-vs-predictability tension shows up in the frontier")
    print("     sweep below: B* flips between the mean and cov objectives.")


def plan_against_competition() -> None:
    """Sweep class A's frontier while class B holds its plan fixed."""
    planner = RedundancyPlanner(N, candidates=[1, 2, 4])
    for objective in ("mean", "cov"):
        plan = planner.plan_cluster(
            DIST,
            objective,
            n_reps=256,
            seed=3,
            scenario=Scenario(
                scheduler="packed",
                workers_per_job=WPJ,
                job_plans=[None, PLAN_B],  # even jobs sweep B, odd jobs stay batch
            ),
        )
        print(
            f"\nclass-A plan against fixed class-B competition "
            f"(objective={objective}): B*={plan.n_batches} "
            f"(r={WPJ // min(plan.n_batches, WPJ)} within its {WPJ}-worker subset)"
        )
        frontier = ", ".join(
            f"B={b}: {m:.2f}/{c:.2f}"
            for b, m, c in zip(plan.frontier_B, plan.frontier_mean, plan.frontier_cov)
        )
        print(f"  frontier (mean/CoV): {frontier}")


if __name__ == "__main__":
    enable_compile_cache()
    one_timeline()
    class_stats()
    plan_against_competition()
