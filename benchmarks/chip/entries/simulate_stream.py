"""Entry: replay a cluster-day through ``repro.cluster.simulate_stream``.

One call replays one day: the host draws, the transfer, the slab scans on
the device and the readback of the accumulators.  Each call of a run
replays a different day of the same size, drawn from the run's seed.
The answer kept is every field of the returned ``StreamStats``.
"""
from __future__ import annotations

import numpy as np
from yardstick import compare, generate, reference

METRIC = "stream_jobs_per_s"
UNIT_OF_WORK = "job-reps"
WARM_CALL = 1 << 40  # a call index no window reaches
FIELDS = ("count", "resp_sum", "resp_sq", "resp_min", "resp_max", "comp_sum", "busy_sum",
          "saved_sum", "hist", "class_count", "class_resp_sum", "class_hist")


def value(units: float, seconds: float) -> float:
    """Job-reps simulated in the window, over the window's seconds."""
    return units / seconds


class Work:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.cluster import Scenario, simulate_stream
        from repro.core.traces import TraceJob, TraceStream

        self._simulate, self._stream_cls = simulate_stream, TraceStream
        self.seed = seed
        self.config, self.traffic = config, traffic
        self.sources = [t for t in generate.source_jobs(config["jobs_seed"])
                        if t[1] == traffic["family"]]
        self._jobs = tuple(TraceJob(name=n, family=f, task_times=x) for n, f, x in self.sources)
        self.n_workers = config["pools"] * config["pool_width"]
        self.b = traffic["n_batches"]
        self.r = config["pool_width"] // self.b
        self.scenario = Scenario(
            outputs="stream", scheduler=traffic["scheduler"],
            workers_per_job=config["pool_width"], cancel_redundant=traffic["cancel_redundant"],
            size_dependent=traffic["size_dependent"], dtype=config["dtype"],
        )
        self.answers: dict = {}

    def _day(self, call: int):
        s = generate.call_seed(self.seed, call)
        arrivals, job_ids = generate.cluster_day(
            self.config["jobs_per_day"], self.config["day_s"], s, len(self.sources))
        return s, arrivals, job_ids

    def step(self, call: int) -> float:
        s, arrivals, job_ids = self._day(call)
        stream = self._stream_cls(arrivals=arrivals, job_ids=job_ids, sources=self._jobs, seed=s)
        stats = self._simulate(stream, self.n_workers, self.b, self.traffic["reps"],
                               scenario=self.scenario, slab=self.traffic["slab"])
        self.answers[call] = {f: getattr(stats, f) for f in FIELDS}
        return float(self.traffic["reps"] * self.config["jobs_per_day"])

    def warm(self):
        self.step(WARM_CALL)
        del self.answers[WARM_CALL]

    def reference(self, call: int, control: bool = False) -> dict:
        """The day's accumulators from the plain reference: float64, or the control's bfloat16."""
        s, arrivals, job_ids = self._day(call)
        draws = reference.ecdf_draws(s, self.traffic["reps"], [x for _, _, x in self.sources],
                                     job_ids, self.b * self.r)
        n_tasks = np.array([x.size for _, _, x in self.sources], np.float64)[job_ids]
        scales = n_tasks / self.b if self.traffic["size_dependent"] else np.ones(job_ids.size)
        return reference.gang_pools(
            arrivals, draws, scales, job_ids, len(self.sources), b=self.b, r=self.r,
            gangs=self.config["pools"], cancel=self.traffic["cancel_redundant"],
            balanced=self.traffic["scheduler"] == "balanced",
            dtype=reference.control_dtype(self.config["dtype"]) if control else np.float64,
        )

    numbers = staticmethod(compare.stream_numbers)
