"""Quickstart: plan replication for a workload and check it against MC.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import analysis, simulator
from repro.core.planner import RedundancyPlanner, fit_service_time
from repro.core.service_time import Exponential, Pareto, ShiftedExponential


def main():
    n = 24  # worker budget

    print("=== 1. closed-form planning (paper §VI) ===")
    for dist, label in [
        (Exponential(mu=2.0), "exponential tasks (memoryless)"),
        (ShiftedExponential(delta=0.5, mu=2.0), "shifted-exp tasks (deterministic floor)"),
        (Pareto(sigma=1.0, alpha=1.5), "pareto tasks (heavy tail)"),
    ]:
        planner = RedundancyPlanner(n)
        pm = planner.plan(dist, "mean")
        pc = planner.plan(dist, "cov")
        print(
            f"{label:42s} B*(mean)={pm.n_batches:3d} (r={pm.replication}) "
            f"B*(CoV)={pc.n_batches:3d} -- the paper's avg-vs-predictability tradeoff"
        )

    print("\n=== 2. Monte-Carlo check of the chosen plan ===")
    dist = Pareto(sigma=1.0, alpha=1.5)
    plan = RedundancyPlanner(n).plan(dist, "mean")
    for b in (1, plan.n_batches, n):
        t = simulator.simulate_balanced(jax.random.key(0), dist, n, b, 100_000)
        st = simulator.stats_from_samples(t)
        closed = analysis.mean_T(dist, n, b)
        mark = " <- planned" if b == plan.n_batches else ""
        print(
            f"B={b:3d}: E[T] closed={closed:8.3f} MC={st.mean:8.3f} "
            f"CoV={st.cov:.3f} p99={st.p99:8.3f}{mark}"
        )

    print("\n=== 3. fitting from observed service times (paper §VII) ===")
    rng = np.random.default_rng(0)
    observed = 2.0 * rng.uniform(size=5000) ** (-1 / 1.3)  # unknown heavy tail
    fitted = fit_service_time(observed)
    plan = RedundancyPlanner(100).plan_auto(observed, "mean")
    print(f"fitted family: {type(fitted).__name__}: {fitted}")
    print(
        f"plan for N=100: B={plan.n_batches}, r={plan.replication}; "
        f"predicted E[T]={plan.predicted_mean:.2f} vs "
        f"no-redundancy={plan.frontier_mean[plan.frontier_B.index(100)]:.2f}"
    )


if __name__ == "__main__":
    enable_compile_cache()
    main()
