"""Traffic generation: the benchmark's own copy of the program's generators.

Copied from ``repro.core.traces`` (``synthetic_google_jobs``,
``synthetic_cluster_day``) with the same constants, so a day made here is
the day the program's generator makes for the same seed.  The copy lives
with the benchmark so that a change to the program cannot change the
traffic it is measured on.
"""
from __future__ import annotations

import numpy as np

# the program's STREAM_VERSION at the time of the copy; folded into seeds
STREAM_VERSION = 1


def source_jobs(jobs_seed: int = 2020) -> list[tuple[str, str, np.ndarray]]:
    """Ten seeded synthetic jobs in the two families of arXiv:2006.02318, Fig. 11.

    They are the program's stand-ins, not task times of the Google trace:
    jobs 1-4 shifted exponential, job 5 the borderline Pareto, jobs 6-10
    Pareto with a 3% straggler mixture of 10-30x slowdowns.  Returns
    ``(name, family, task times)``.
    """
    rng = np.random.default_rng(jobs_seed)
    jobs = []
    for i, (delta, mu) in enumerate(
        [(10.0, 1 / 3.0), (10.0, 1 / 8.0), (10.0, 1 / 20.0), (1000.0, 1 / 150.0)], start=1
    ):
        n = int(rng.integers(400, 1200))
        jobs.append((f"job{i}", "exponential", delta + rng.exponential(scale=1.0 / mu, size=n)))
    n = int(rng.integers(400, 1200))
    u = rng.uniform(size=n)
    jobs.append(("job5", "heavy", 12.0 * u ** (-1.0 / 3.0)))
    for i, (sigma, alpha) in enumerate(
        [(8.0, 1.4), (15.0, 1.8), (6.0, 1.3), (20.0, 2.2), (10.0, 1.6)], start=6
    ):
        n = int(rng.integers(400, 1200))
        x = sigma * rng.uniform(size=n) ** (-1.0 / alpha)
        mask = rng.uniform(size=n) < 0.03
        jobs.append((f"job{i}", "heavy", np.where(mask, x * rng.uniform(10.0, 30.0, size=n), x)))
    return jobs


def cluster_day(n_jobs: int, duration: float, seed: int, n_sources: int):
    """Sorted uniform arrivals over the day and a uniform source id per arrival."""
    rng = np.random.default_rng(np.random.SeedSequence((int(seed), STREAM_VERSION, 0xDA7)))
    arrivals = np.sort(rng.uniform(0.0, float(duration), size=int(n_jobs)))
    job_ids = rng.integers(0, int(n_sources), size=int(n_jobs))
    return arrivals, job_ids


def call_seed(seed: int, call: int) -> int:
    """The seed of the ``call``-th request of a run: distinct per call, fixed by the run seed."""
    return int(np.random.SeedSequence((int(seed), int(call), 0xB3)).generate_state(1, np.uint64)[0])
