"""Entry: one hedged fan-out plan from ``RedundancyPlanner.plan_cluster`` on the packed space lane.

One call plans once: the host prepares every lane's service and backup
draws, the device runs every (candidate B, stream) lane of 100-leaf
requests packed two side by side, with slow leaves hedged by speculative
backups and the losers cancelled, and the host reads back the starts and
finishes and picks the mean-optimal B.  Each call of a run plans with a
different seed, drawn from the run's seed.  The answer kept is the
frontier (mean and CoV per candidate) and the pick.
"""
from __future__ import annotations

import numpy as np
from yardstick import compare, generate, hedge_reference, reference

METRIC = "plan_s"
UNIT_OF_WORK = "plans"
WARM_CALL = 1 << 40  # a call index no window reaches


def value(units: float, seconds: float) -> float:
    """The window's seconds over the plans completed in it."""
    return seconds / units


def speeds(config: dict) -> np.ndarray:
    """The cluster's worker speeds: uniform on [lo, hi] from the configuration's seed."""
    s = config["churn"]["speeds"]
    return np.random.default_rng(s["seed"]).uniform(s["low"], s["high"], size=config["workers"])


class Work:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.cluster import Scenario, Speculation
        from repro.core.planner import RedundancyPlanner
        from repro.core.service_time import Pareto

        if (config["service"]["law"] != "pareto" or traffic["scheduler"] != "packed"
                or config["churn"]["fail_rate"] != 0.0 or not config["cancel_redundant"]):
            raise ValueError("plan_cluster_hedged entry: packed, cancelling, churn-free, Pareto")
        self.seed = seed
        self.config, self.traffic = config, traffic
        self.speeds = speeds(config)
        self.width = traffic["width"]
        # every B that divides the request's fan-out: B = width is pure fan-out plus hedging
        self.candidates = reference.divisors(self.width)
        self.planner = RedundancyPlanner(config["workers"], candidates=self.candidates)
        self.dist = Pareto(config["service"]["sigma"], config["service"]["alpha"])
        self.scenario = Scenario(
            speeds=tuple(float(s) for s in self.speeds),
            jobs_per_stream=traffic["jobs_per_stream"],
            n_tasks=config["n_tasks"],
            cancel_redundant=True,
            speculation=Speculation(**config["speculation"]),
            dtype=config["dtype"],
            scheduler="packed", workers_per_job=self.width,
        )
        self.answers: dict = {}

    def step(self, call: int) -> float:
        plan = self.planner.plan_cluster(
            self.dist, n_reps=self.traffic["reps"], seed=generate.call_seed(self.seed, call),
            scenario=self.scenario, backend="jax",
        )
        self.answers[call] = {"B": list(plan.frontier_B), "pick": plan.n_batches,
                              "mean": list(plan.frontier_mean), "cov": list(plan.frontier_cov)}
        return 1.0

    def warm(self):
        self.step(WARM_CALL)
        del self.answers[WARM_CALL]

    def reference(self, call: int, control: bool = False) -> dict:
        """The plan from the plain reference, every operation rounded to the configuration's
        precision, or for the control to the precision below it."""
        svc, stated = self.config["service"], self.config["dtype"]
        dt = reference.control_dtype(stated) if control else np.dtype(stated).type

        def rnd(x):
            return float(dt(x))
        return hedge_reference.plan_cluster(
            generate.call_seed(self.seed, call), n_workers=self.config["workers"],
            candidates=self.candidates, n_reps=self.traffic["reps"],
            jobs_per_stream=self.traffic["jobs_per_stream"], sigma=svc["sigma"],
            alpha=svc["alpha"], speeds=self.speeds, width=self.width,
            n_tasks=self.config["n_tasks"], rnd=rnd, **self.config["speculation"],
        )

    numbers = staticmethod(compare.frontier_numbers)
