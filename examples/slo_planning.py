"""Tail-SLO planning: the mean-optimal plan is not the tail-optimal plan.

The paper's §V observation in planner form: pick the (B, r, scheduler)
configuration that *minimizes mean* job time and you will often buy a lot of
replication -- great for the average, expensive in worker-seconds, and not
what a p99 response-time SLO actually asks for.  ``RedundancyPlanner.plan_slo``
sweeps the (scheduler x pool-width x B) grid on the streaming simulator,
reads the p99 off the on-device response-time histogram, and returns the
*cheapest feasible* candidate instead: the least worker-seconds that still
meets ``SLO(quantile=0.99, target_s=..., arrival_rate=...)``.

This example runs that sweep for the three parametric tails (Exp / SExp /
Pareto) and prints, side by side:

  * the cheapest p99-feasible candidate (what ``plan_slo`` picks), and
  * the mean-optimal candidate on the same grid (what a mean planner picks),

showing that they differ -- mean-optimal buys full replication (r = width)
while the SLO is already met by a leaner plan at a fraction of the cost --
and what happens when the target is impossible (an explicit infeasible
verdict, never a silent fallback).

Run me::

    PYTHONPATH=src python examples/slo_planning.py
"""

from repro.compile_cache import enable_compile_cache
from repro.core import SLO, Exponential, Pareto, ShiftedExponential
from repro.core.planner import RedundancyPlanner

N_WORKERS = 8
RATE = 0.05  # Poisson arrivals, jobs per second: light load, tails dominate

# p99 response targets per tail family, sized to be feasible but not trivial
CASES = [
    ("Exp(1)", Exponential(1.0), 12.0),
    ("SExp(0.3, 1)", ShiftedExponential(0.3, 1.0), 15.0),
    ("Pareto(1, 1.5)", Pareto(1.0, 1.5), 60.0),
]


def describe(c) -> str:
    """One line for a candidate: schedule shape, cost, and achieved tail."""
    width = "whole cluster" if c.workers_per_job is None else f"w={c.workers_per_job}"
    return (
        f"{c.scheduler:9s} {width:13s} B={c.n_batches} r={c.replication}  "
        f"p99={c.achieved[0]:8.2f}s  mean={c.mean_response:6.2f}s  "
        f"cost={c.cost_worker_seconds:8.0f} worker-s"
    )


def main() -> None:
    planner = RedundancyPlanner(N_WORKERS)
    print(f"{N_WORKERS} workers, Poisson arrivals at {RATE}/s, p99 SLO per family\n")
    for name, dist, target in CASES:
        slo = SLO(quantile=0.99, target_s=target, arrival_rate=RATE)
        plan = planner.plan_slo([dist], slo, schedulers=("fifo_gang", "packed"))
        mean_opt = min(plan.candidates, key=lambda c: c.mean_response)
        best = plan.best
        print(f"{name}: p99 target {target:.0f}s")
        print(f"  cheapest feasible   {describe(best)}")
        print(f"  mean-optimal        {describe(mean_opt)}")
        same = (best.scheduler, best.workers_per_job, best.n_batches) == (
            mean_opt.scheduler,
            mean_opt.workers_per_job,
            mean_opt.n_batches,
        )
        if not same:
            ratio = mean_opt.cost_worker_seconds / best.cost_worker_seconds
            print(
                f"  -> mean-optimal != tail-optimal: the mean planner pays "
                f"{ratio:.1f}x the worker-seconds for capacity the SLO never asked for\n"
            )
        else:
            print("  -> the two coincide on this grid\n")

    # an impossible target: p99 below the service floor -- plan_slo must say
    # so explicitly rather than quietly returning the least-bad candidate
    slo = SLO(quantile=0.99, target_s=0.05, arrival_rate=RATE)
    plan = planner.plan_slo([Pareto(1.0, 1.5)], slo, schedulers=("fifo_gang", "packed"))
    print(f"Pareto(1, 1.5): p99 target 0.05s -> feasible={plan.feasible}, best={plan.best}")
    try:
        plan.require_feasible()
    except ValueError as ex:
        print(f"  require_feasible() raises: {ex}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
