"""Entry: one dynamic plan from ``RedundancyPlanner.plan_cluster`` on the epoch-scan lanes.

One call plans once: the host prepares every lane's service, rescue and
churn draws, the device runs every (candidate B, stream) lane of jobs --
serial gangs, or packed side by side at the traffic's ``width`` -- under
fail/join churn on heterogeneous speeds, and the host reads
back the starts and finishes and picks the mean-optimal B.  Each call of a
run plans with a different seed, drawn from the run's seed.  The answer
kept is the frontier (mean and CoV per candidate) and the pick.
"""
from __future__ import annotations

import numpy as np
from yardstick import compare, epoch_reference, generate, reference

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
        from repro.cluster import ChurnProcess, Scenario
        from repro.core.planner import RedundancyPlanner
        from repro.core.service_time import Pareto

        if config["service"]["law"] != "pareto" or traffic["scheduler"] not in ("fifo_gang",
                                                                                "packed"):
            raise ValueError("plan_cluster entry: gang or packed lanes with Pareto service only")
        self.seed = seed
        self.config, self.traffic = config, traffic
        churn = config["churn"]
        self.speeds = speeds(config)
        self.width = traffic.get("width")  # None: whole-cluster gangs
        # every B that divides the gang: the planner's default frontier
        self.candidates = reference.divisors(self.width or config["workers"])
        self.planner = RedundancyPlanner(config["workers"], candidates=self.candidates)
        self.dist = Pareto(config["service"]["sigma"], config["service"]["alpha"])
        self.scenario = Scenario(
            churn=ChurnProcess(fail_rate=churn["fail_rate"], mean_downtime=churn["mean_downtime"]),
            speeds=tuple(float(s) for s in self.speeds),
            jobs_per_stream=traffic["jobs_per_stream"],
            churn_pairs_per_worker=churn["pairs_per_worker"],
            dtype=config["dtype"],
            scheduler=traffic["scheduler"], workers_per_job=self.width,
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
        precision, or for the control to the precision below it.

        Not float64: the lanes keep absolute times, and float32's rounding of them
        reorders a failure against a replica's end in a lane or two of a plan, which
        float64 then follows differently (seen alike on the chip and the CPU).
        """
        churn, svc = self.config["churn"], self.config["service"]
        stated = self.config["dtype"]
        dt = reference.control_dtype(stated) if control else np.dtype(stated).type

        def rnd(x):
            return float(dt(x))
        return epoch_reference.plan_cluster(
            generate.call_seed(self.seed, call), n_workers=self.config["workers"],
            candidates=self.candidates, n_reps=self.traffic["reps"],
            jobs_per_stream=self.traffic["jobs_per_stream"], sigma=svc["sigma"],
            alpha=svc["alpha"], speeds=self.speeds, fail_rate=churn["fail_rate"],
            mean_downtime=churn["mean_downtime"], pairs=churn["pairs_per_worker"],
            width=self.width, rnd=rnd,
        )

    numbers = staticmethod(compare.frontier_numbers)
