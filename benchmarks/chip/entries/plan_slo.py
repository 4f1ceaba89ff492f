"""Entry: one tail-SLO plan from ``RedundancyPlanner.plan_slo``.

One call plans once: it draws the Poisson evaluation stream, runs every
(scheduler, pool width, B) candidate through the streaming kernel, reads
back each candidate's histogram quantile and cost, and picks the cheapest
feasible one.  Each call of a run plans with a different seed, drawn from
the run's seed.  The answer kept is every candidate's achieved quantile,
cost, mean response and verdict, and the pick.
"""
from __future__ import annotations

import numpy as np
from yardstick import compare, generate, reference

METRIC = "plan_s"
UNIT_OF_WORK = "plans"
WARM_CALL = 1 << 40  # a call index no window reaches


def value(units: float, seconds: float) -> float:
    """The window's seconds over the plans completed in it."""
    return seconds / units


class Work:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.cluster import SLO, Scenario
        from repro.core.planner import RedundancyPlanner
        from repro.core.service_time import Pareto

        if config["service"]["law"] != "pareto" or traffic["churn"]:
            raise ValueError("plan_slo entry: a static cluster with Pareto service only")
        self.seed = seed
        self.config, self.traffic = config, traffic
        self.planner = RedundancyPlanner(config["workers"])
        self.dist = Pareto(config["service"]["sigma"], config["service"]["alpha"])
        self.slo = SLO(quantile=traffic["quantile"], target_s=traffic["target_s"],
                       arrival_rate=traffic["arrival_rate"])
        self.scenario = Scenario(size_dependent=traffic["size_dependent"],
                                 cancel_redundant=traffic["cancel_redundant"],
                                 dtype=config["dtype"])
        self.answers: dict = {}

    def step(self, call: int) -> float:
        plan = self.planner.plan_slo(
            [self.dist], self.slo, scenario=self.scenario, n_jobs=self.traffic["n_jobs"],
            n_reps=self.traffic["reps"], seed=generate.call_seed(self.seed, call),
            schedulers=tuple(self.traffic["schedulers"]), slab=self.traffic["slab"],
        )

        def key(c):
            return (c.scheduler, c.workers_per_job, c.n_batches)

        self.answers[call] = {
            "candidates": {
                key(c): {"achieved": c.achieved[0], "cost": c.cost_worker_seconds,
                         "mean": c.mean_response, "feasible": c.feasible}
                for c in plan.candidates
            },
            "best": None if plan.best is None else key(plan.best),
        }
        return 1.0

    def warm(self):
        self.step(WARM_CALL)
        del self.answers[WARM_CALL]

    def reference(self, call: int, control: bool = False) -> dict:
        """The plan from the plain reference: float64, or the control's lower precision."""
        t, svc = self.traffic, self.config["service"]
        return reference.plan_slo(
            generate.call_seed(self.seed, call), n_workers=self.config["workers"],
            sigma=svc["sigma"], alpha=svc["alpha"], rate=t["arrival_rate"],
            quantile_q=t["quantile"], target_s=t["target_s"], n_jobs=t["n_jobs"],
            n_reps=t["reps"], schedulers=t["schedulers"], size_dependent=t["size_dependent"],
            cancel=t["cancel_redundant"],
            dtype=reference.control_dtype(self.config["dtype"]) if control else np.float64,
        )

    numbers = staticmethod(compare.slo_numbers)
