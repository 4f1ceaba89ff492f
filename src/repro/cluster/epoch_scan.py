"""The jax epoch-scan engine: churn, heterogeneous speeds, rescue, and replanning.

This module closes the vectorization gap left by :mod:`repro.cluster.vectorized`
(which covers the static case): it replays the *dynamic* semantics of the
event-driven :class:`~repro.cluster.master.ClusterEngine` -- worker fail/join
churn, replica rescue, per-worker speed factors, FIFO multi-job dispatch, and
windowed online replanning -- as a bounded device loop, batched over
Monte-Carlo reps (and, for planning, over a whole candidate frontier).

The structural insight making this vectorizable: between two churn events the
alive set is constant, so no replica can die and no rescue can be requested --
every job that starts and ends inside an epoch is a pure masked
``max_b min_r`` cover computation (the shared
:func:`~repro.core.simulator.gang_cover_times` semantics), and the only
sequential state is the one job straddling the boundary.  Earlier revisions
expressed this as a ``lax.scan`` over churn epochs whose steps ran
progress-gated ``while_loop``s for rescue dispatch and commit/dispatch; under
``vmap`` those loops serialize -- every lane waits for the slowest lane's trip
count at every scan step.  The current formulation removes the inner loops
entirely: one flat, trip-count-static step loop in which **each step performs
exactly one action** --

  * *rescue*: dispatch the oldest pending rescue onto the earliest-freeing
    alive worker (engine: first free worker, FIFO rescue queue), or
  * *commit + dispatch*: commit batch wins up to the next churn boundary
    (batch wins, sibling cancellation accounting, job finishes, replanner
    observations) and gang-dispatch the next queued job, or
  * *commit + boundary*: apply one fail/join event (replica kill, rescue
    queueing, the engine's sim-over churn truncation).

The step budget is static (``#events + #jobs + rescue allowance``), chunked
under an early-exit ``while_loop`` so finished lanes stop paying for churn
noise past their last job.  State is O(workers) -- per-worker gang assignment
vectors plus one rescue slot per batch -- instead of the previous
O(workers^2) slot grid, which shrinks both the compiled graph and the
per-step work.  Shapes are padded to buckets (workers to multiples of 4,
jobs to multiples of 32, events and lanes to powers of two), so frontier/grid
sweeps of nearby sizes share one compile (see :func:`runner_cache_stats`).

Replanning mirrors :class:`~repro.cluster.control.OnlineReplanner` in jax: a
ring buffer of censoring-tagged task-time observations, maximum-likelihood
refits of the Exp/SExp/Pareto families picked by log-likelihood, the
min-of-r censoring inversion, and a closed-form frontier argmin over the
divisors of the alive-worker count (harmonic/``gammaln`` tables).

Accounting matches the engine's identities: with a shared seed,
``worker_seconds(cancel on) + cancelled_seconds_saved == worker_seconds(cancel
off)`` holds per rep in churn-free runs, and the report exposes the same
counter fields (:meth:`EpochReport.accounting`) as
:class:`~repro.cluster.master.EngineReport` for the differential tests.

Space sharing (the scheduler subsystem of :mod:`repro.cluster.scheduler`)
runs on a second lane builder, :func:`_build_space_lane`: per-worker
job-assignment and availability-timestamp vectors plus per-job plan tables
replay concurrent jobs on disjoint worker subsets under heterogeneous
(B, r, cancellation) plans -- ``packed`` / ``balanced`` / gang-mode
``fifo_gang`` placement, first-fit dispatch by earliest feasible time, and
churn-aware rescue regrants, and speculative backups as the engine launches
them.  ``scheduler`` / ``workers_per_job`` / ``job_plans`` on the public
entry points select it; the default configuration keeps the legacy
single-gang lane untouched.

Reproducibility contract: every lane (one Monte-Carlo rep of one candidate)
derives its draws host-side from
``numpy.random.default_rng(SeedSequence((seed, global_lane_index)))`` -- a
pure function of the global lane index -- so results are bit-identical
whether reps run in one call or chunked (``rep_chunk``) and whether lanes run
on one device or sharded across several (``devices``).

Precision: lanes default to float32 absolute simulation time; pass
``dtype="float64"`` (with jax x64 enabled) for long-horizon workloads where
float32 quantizes large arrival offsets -- the engine always runs float64.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import gammaln

from ..core.analysis import divisor_table, harmonic_tables
from ..core.service_time import ServiceTime
from ..spans import count, readback, span
from .scenario import UNSET, Scenario, Speculation, resolve_scenario
from .scheduler import SCHEDULERS, JobPlan, is_space
from .workers import ChurnProcess, ChurnSchedule

__all__ = [
    "ReplanConfig",
    "EpochReport",
    "EpochStreamReport",
    "simulate_epochs",
    "frontier_job_times_dynamic",
    "runner_cache_stats",
    "clear_runner_cache",
]


@dataclasses.dataclass(frozen=True)
class ReplanConfig:
    """Static mirror of :class:`~repro.cluster.control.OnlineReplanner` knobs.

    Hashable (it keys the jit cache); ``to_controller`` builds the equivalent
    Python-engine controller so differential tests drive both backends from
    one config.
    """

    window: int = 512
    refit_every: int = 128
    min_observations: int = 64
    objective: str = "mean"
    blend: float = 0.5

    def to_controller(self, n_workers: int):
        """Materialize this config as an :class:`~repro.cluster.control.OnlineReplanner`."""
        from .control import OnlineReplanner

        return OnlineReplanner(
            n_workers,
            objective=self.objective,
            window=self.window,
            refit_every=self.refit_every,
            min_observations=self.min_observations,
            blend=self.blend,
        )


@dataclasses.dataclass(frozen=True)
class EpochReport:
    """Batched outcome of :func:`simulate_epochs` (axis 0 = Monte-Carlo rep).

    Mirrors :class:`~repro.cluster.master.EngineReport` field-for-field where
    the semantics overlap; ``inf`` marks jobs never dispatched / completed
    (dead cluster), exactly like the engine's unfinished records.
    ``epoch_times`` are the applied churn-event times per rep (inf-padded),
    the same epoch boundaries ``EngineReport.epoch_times`` records.
    """

    arrivals: np.ndarray  # (n_jobs,)
    starts: np.ndarray  # (n_reps, n_jobs)
    finishes: np.ndarray  # (n_reps, n_jobs)
    n_batches_used: np.ndarray  # (n_reps, n_jobs)
    replication_used: np.ndarray  # (n_reps, n_jobs)
    worker_seconds: np.ndarray  # (n_reps,)
    cancelled_seconds_saved: np.ndarray  # (n_reps,)
    n_worker_failures: np.ndarray  # (n_reps,)
    n_replicas_rescued: np.ndarray  # (n_reps,)
    n_replans: np.ndarray  # (n_reps,)
    epoch_times: np.ndarray  # (n_reps, n_events) applied boundaries, inf pad
    n_speculative: np.ndarray = None  # (n_reps,) reactive backups launched
    # (n_reps,) bool: the rep's timeline outran its sampled churn horizon
    # (workers stayed up past it while the engine's law keeps churning);
    # None when churn is scheduled or absent -- see simulate_epochs
    churn_truncated: np.ndarray = None

    @property
    def compute_times(self) -> np.ndarray:
        """Per-(rep, job) compute time: finish minus start."""
        return self.finishes - self.starts

    @property
    def response_times(self) -> np.ndarray:
        """Per-(rep, job) response time: finish minus arrival."""
        return self.finishes - self.arrivals[None, :]

    @property
    def queue_waits(self) -> np.ndarray:
        """Per-(rep, job) queueing delay: start minus arrival."""
        return self.starts - self.arrivals[None, :]

    @property
    def final_n_batches(self) -> np.ndarray:
        """The B each rep's replanner ended the run on."""
        return self.n_batches_used[:, -1]

    def accounting(self) -> dict:
        """Per-rep counters, keyed identically to ``EngineReport.accounting``."""
        return {
            "worker_seconds": self.worker_seconds,
            "cancelled_seconds_saved": self.cancelled_seconds_saved,
            "n_worker_failures": self.n_worker_failures,
            "n_replicas_rescued": self.n_replicas_rescued,
            "n_replans": self.n_replans,
            "n_speculative": (
                self.n_speculative
                if self.n_speculative is not None
                else np.zeros_like(self.n_replans)
            ),
            # task-level payload failures exist on the Python engine and the
            # live runtime only; the jax lanes report structural zeros so the
            # accounting key set stays identical across backends
            "n_task_failures": np.zeros_like(self.n_replans),
            "n_retries": np.zeros_like(self.n_replans),
        }


@dataclasses.dataclass(frozen=True)
class EpochStreamReport:
    """``Scenario.outputs="stream"`` outcome of :func:`simulate_epochs`.

    Carries O(n_reps) streaming aggregates instead of ``(n_reps, n_jobs)``
    per-job records: ``stats`` is a
    :class:`~repro.cluster.stream.StreamStats` whose response/compute fields
    come from the on-device fold (its ``busy_sum`` / ``saved_sum`` are the
    lane's per-rep worker-seconds totals), plus the usual per-rep counters.
    ``n_unfinished`` counts jobs never completed (dead cluster) -- those are
    excluded from the statistics rather than surfacing as ``inf`` records.
    On float64 lanes the stats equal the host fold of the equivalent
    ``outputs="full"`` report bit for bit (shared seeds; the draw pipeline
    is identical in both modes).
    """

    arrivals: np.ndarray  # (n_jobs,)
    stats: "object"  # StreamStats (declared loose: stream.py imports us not)
    n_unfinished: np.ndarray  # (n_reps,)
    worker_seconds: np.ndarray  # (n_reps,)
    cancelled_seconds_saved: np.ndarray  # (n_reps,)
    n_worker_failures: np.ndarray  # (n_reps,)
    n_replicas_rescued: np.ndarray  # (n_reps,)
    n_replans: np.ndarray  # (n_reps,)
    n_speculative: np.ndarray = None  # (n_reps,)
    churn_truncated: np.ndarray = None  # see EpochReport

    def accounting(self) -> dict:
        """Per-rep counters, keyed identically to ``EpochReport.accounting``."""
        return {
            "worker_seconds": self.worker_seconds,
            "cancelled_seconds_saved": self.cancelled_seconds_saved,
            "n_worker_failures": self.n_worker_failures,
            "n_replicas_rescued": self.n_replicas_rescued,
            "n_replans": self.n_replans,
            "n_speculative": (
                self.n_speculative
                if self.n_speculative is not None
                else np.zeros_like(self.n_replans)
            ),
            # task-level payload failures exist on the Python engine and the
            # live runtime only; the jax lanes report structural zeros so the
            # accounting key set stays identical across backends
            "n_task_failures": np.zeros_like(self.n_replans),
            "n_retries": np.zeros_like(self.n_replans),
        }


# --------------------------------------------------------------------------
# shape buckets and the bucketed jit cache
# --------------------------------------------------------------------------

_RUNNERS: dict = {}
_STEP_CHUNK = 16  # steps per early-exit check


def _pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1): the shape-bucket rounding."""
    return 1 << (max(int(x), 1) - 1).bit_length()


def _bucket_workers(n: int) -> int:
    """Worker counts bucket to multiples of 4: most per-step work is O(n),
    so a finer granularity than power-of-two buys back real element count
    (16 -> 12 for the common mid-size clusters) at a few extra compiles."""
    return max(4, -(-int(n) // 4) * 4)


def runner_cache_stats() -> dict:
    """Compiled-runner cache: ``{bucket_key: number_of_jit_cache_entries}``.

    One entry per *shape bucket* (padded worker/job/event/lane sizes plus the
    static cancel/size-dep/replan/dtype/devices knobs).  The jit cache size of
    each runner counts actual compiles (one per distinct lane-batch shape);
    the regression test asserts a dynamic ``plan_sweep`` grid stays at one.
    """
    return {key: fn._cache_size() for key, fn in _RUNNERS.items()}


def clear_runner_cache() -> None:
    """Drop all cached compiled runners (test/bench isolation helper)."""
    _RUNNERS.clear()


@dataclasses.dataclass(frozen=True)
class _RunnerCfg:
    """Static configuration of one compiled runner (the bucket key)."""

    n: int  # padded worker count
    jobs_pad: int
    ev_pad: int
    resc_cap: int
    n_chunks: int
    cancel: bool
    size_dep: bool
    replan: Optional[ReplanConfig]
    dtype: str
    devices: int
    # False drops the per-event epoch-times buffer and the per-job B/r
    # records plus their per-step scatters; the cheap scalar counters stay.
    # The plan_cluster/plan_sweep hot path only reads starts/finishes.
    full_outputs: bool = True
    # True folds the per-job starts/finishes into streaming accumulators
    # (count, response moment sums, min/max, log histogram) on device before
    # anything leaves the lane -- Scenario.outputs="stream".  Implies
    # full_outputs=False; the lane internals are untouched, so "full" paths
    # stay bit-identical.
    stream: bool = False
    # None selects the legacy single-gang lane; a policy name selects the
    # space-sharing lane (per-worker job assignment, per-job plan tables).
    scheduler: Optional[str] = None
    # Reactive replication, on either lane.  Enabling it switches the commit
    # pass to completion groups no launch can fall between, so the trigger's
    # median and candidate set evolve exactly as the engine's event loop
    # interleaves them.
    spec: Optional[Speculation] = None


# --------------------------------------------------------------------------
# the per-lane step loop (one Monte-Carlo rep of one candidate)
# --------------------------------------------------------------------------


def _put(x, i, v, on):
    """``x.at[i].set(v)`` where ``on`` and ``0 <= i < len(x)``, else ``x``.

    A select over the whole row, not a scatter: a TPU v5e compile of the
    vmapped lanes at 1024+ lanes per device flattens the per-lane scalar
    scatters over the lane axis, and those runs left most lanes' jobs
    unfinished while 512 lanes and the CPU were right.
    """
    return jnp.where((jnp.arange(x.shape[0]) == i) & on, v, x)


def _div_rn(a, b):
    """``a / b`` in float32, rounded to nearest even as IEEE division is.

    A TPU's float32 divide is not correctly rounded, so a quotient can land
    one ulp off numpy's.  Here the quotient of the two 24-bit significands
    comes from exact integer long division, 25 bits and a sticky remainder,
    and is rounded once.  Zeros, infinities and NaNs take the plain divide;
    a quotient in the subnormal range is not rounded as IEEE's (a TPU
    flushes those to zero), and the lanes' times stay far from it.
    """
    ma, ea = jnp.frexp(a)
    mb, eb = jnp.frexp(b)
    ia = (jnp.abs(ma) * (1 << 24)).astype(jnp.int32)  # in [2**23, 2**24)
    ib = (jnp.abs(mb) * (1 << 24)).astype(jnp.int32)
    below = ia < ib  # a quotient under 1 takes one more bit
    r = jnp.where(below, 2 * ia, ia)
    q = r // ib  # the leading bit
    r = r - q * ib
    for _ in range(4):  # 24 more bits, 6 at a time (r * 64 < 2**30)
        r = r * 64
        d = r // ib
        r = r - d * ib
        q = q * 64 + d
    keep, half = q >> 1, q & 1
    up = (half == 1) & ((r != 0) | ((keep & 1) == 1))
    mag = jnp.ldexp((keep + up.astype(jnp.int32)).astype(jnp.float32),
                    ea - eb - below.astype(jnp.int32) - 23)
    out = jnp.where(jnp.signbit(a) != jnp.signbit(b), -mag, mag)
    plain = ~(jnp.isfinite(a) & jnp.isfinite(b) & (a != 0) & (b != 0))
    return jnp.where(plain, a / b, out)


def _build_lane(cfg: _RunnerCfg):
    n, jobs_pad, ev_pad = cfg.n, cfg.jobs_pad, cfg.ev_pad
    replan = cfg.replan
    spec = cfg.spec
    assert not (spec is not None and replan is not None)  # Scenario.validate
    dt = jnp.dtype(cfg.dtype)
    bidx = jnp.arange(n)
    wid = jnp.arange(n)
    # replica slots: [0, n) gang replica of worker i, [n, 2n) rescue replica
    # of batch i - n, and -- with speculation on -- [2n, 3n) the reactive
    # backup of batch i - 2n.  One flat axis keeps every per-replica
    # reduction a single vector op (the de-serialized sibling of
    # gang_cover_times).  One backup slot per batch means a batch whose
    # backup is still running is not re-eligible; the engine's
    # youngest-replica rule re-arms on the backup instead, so the two differ
    # only when a backup itself lags past theta x median (not exercised by
    # the differential suite).
    rp_batch_rescue = bidx  # rescue slot i hosts batch i
    n_slots = 3 * n if spec is not None else 2 * n
    W = replan.window if replan is not None else 0

    def _seg_min(seg, vals, mask):
        """Per-batch min of ``vals`` over entries with ``mask`` (inf empty).

        ``seg`` is always in-bounds; masked-out entries contribute the
        neutral inf, so only the values need masking."""
        return (
            jnp.full(n + 1, jnp.inf, dt).at[seg].min(jnp.where(mask, vals, jnp.inf))[:n]
        )

    def _obs_push(st, vals, comps, times, valid):
        # ring-buffer push in completion-time order: valid entries take ranks
        # 0..nv-1 under a stable sort of their times, landing at head+rank
        valid = valid & (vals > 0.0) & jnp.isfinite(vals)
        nv = valid.sum()
        rank = jnp.argsort(jnp.argsort(jnp.where(valid, times, jnp.inf)))
        pos = jnp.where(valid, (st["obs_head"] + rank) % W, W)
        st2 = {**st}
        st2["obs_val"] = jnp.append(st["obs_val"], 0.0).at[pos].set(vals)[:W]
        st2["obs_comp"] = jnp.append(st["obs_comp"], 0.0).at[pos].set(comps)[:W]
        st2["obs_head"] = (st["obs_head"] + nv) % W
        st2["obs_count"] = jnp.minimum(st["obs_count"] + nv, W)
        st2["since_refit"] = st["since_refit"] + nv
        return st2

    def _replan_pick(st, div_tab, h1, h2, blend):
        # MLE refit of Exp/SExp/Pareto on the window (mirrors
        # core.planner.fit_service_time), min-of-c censoring inversion
        # (control._inverse_min), closed-form frontier argmin over the
        # divisors of the current alive count (core.analysis forms).
        m = jnp.arange(W) < st["obs_count"]
        nobs = jnp.maximum(st["obs_count"], 1).astype(dt)
        x = st["obs_val"]
        sx = jnp.where(m, x, 0.0).sum()
        mean = sx / nobs
        xmin = jnp.min(jnp.where(m, x, jnp.inf))
        slogx = jnp.where(m, jnp.log(jnp.maximum(x, 1e-30)), 0.0).sum()
        tiny = 1e-30
        mu_e = 1.0 / jnp.maximum(mean, tiny)
        ll_e = nobs * jnp.log(mu_e) - mu_e * sx
        gap = mean - xmin
        mu_s = 1.0 / jnp.maximum(gap, tiny)
        ll_s = jnp.where(gap > 0, nobs * jnp.log(mu_s) - mu_s * (sx - nobs * xmin), -jnp.inf)
        slogs = slogx - nobs * jnp.log(jnp.maximum(xmin, tiny))
        alpha = nobs / jnp.maximum(slogs, tiny)
        ll_p = jnp.where(
            slogs > 0,
            nobs * jnp.log(alpha) + nobs * alpha * jnp.log(jnp.maximum(xmin, tiny))
            - (alpha + 1.0) * slogx,
            -jnp.inf,
        )
        fam = jnp.argmax(jnp.stack([ll_e, ll_s, ll_p]))
        c = jnp.where(m, st["obs_comp"], 0.0).sum() / nobs
        c = jnp.maximum(c, 1.0)
        mu_e, mu_s, alpha_c = mu_e / c, mu_s / c, alpha / c

        n_alive = st["alive"].sum()
        cands = div_tab[n_alive]  # (D,) zero-padded
        vb = cands > 0
        b = jnp.maximum(cands, 1).astype(dt)
        H1, H2 = h1[jnp.maximum(cands, 1)], h2[jnp.maximum(cands, 1)]
        na = n_alive.astype(dt)
        mean_e = H1 / mu_e
        cov_e = jnp.sqrt(H2) / H1
        mean_s = na * xmin / b + H1 / mu_s
        cov_s = jnp.sqrt(H2) / (na * xmin * mu_s / b + H1)
        xp = b / jnp.maximum(na * alpha_c, tiny)
        lgm = jnp.log(jnp.maximum(na * xmin / b, tiny)) + gammaln(b + 1.0)
        lgm = lgm - gammaln(b + 1.0 - xp) + gammaln(1.0 - xp)
        mean_p = jnp.where(xp < 1.0, jnp.exp(lgm), jnp.inf)
        lgq = (
            gammaln(1.0 - 2.0 * xp)
            + 2.0 * gammaln(b + 1.0 - xp)
            - gammaln(b + 1.0)
            - gammaln(b + 1.0 - 2.0 * xp)
            - 2.0 * gammaln(1.0 - xp)
        )
        cov_p = jnp.where(
            2.0 * xp < 1.0, jnp.sqrt(jnp.maximum(jnp.exp(lgq) - 1.0, 0.0)), jnp.inf
        )
        means = jnp.select([fam == 0, fam == 1], [mean_e, mean_s], mean_p)
        covs = jnp.select([fam == 0, fam == 1], [cov_e, cov_s], cov_p)
        means = jnp.where(vb, means, jnp.inf)
        covs = jnp.where(vb, covs, jnp.inf)
        if replan.objective == "mean":
            score = means
        elif replan.objective == "cov":
            score = covs
        elif replan.objective == "blend":
            finite = jnp.isfinite(means) & jnp.isfinite(covs)

            def norm01(v):
                vf = jnp.where(finite, v, jnp.inf)
                lo = jnp.min(vf)
                hi = jnp.max(jnp.where(finite, v, -jnp.inf))
                return jnp.where(finite, (v - lo) / jnp.maximum(hi - lo, 1e-12), 0.0)

            score = jnp.where(
                finite, blend * norm01(means) + (1.0 - blend) * norm01(covs), jnp.inf
            )
        else:  # pragma: no cover - validated at the wrapper
            raise ValueError(f"unknown objective {replan.objective!r}")
        new_b = cands[jnp.argmin(score)]
        return jnp.where(n_alive > 0, jnp.maximum(new_b, 1), st["plan_b"])

    def lane(tau, tau_resc, tau_spec, ev_t, ev_w, ev_up, b0, arrivals, speeds, n_real,
             jobs_real, n_tasks, blend, div_tab, h1, h2):
        inf = jnp.asarray(jnp.inf, dt)

        def batch_scale(job_b):
            return n_tasks / job_b.astype(dt) if cfg.size_dep else jnp.asarray(1.0, dt)

        def step(st):
            """One action per step -- rescue, else commit + (dispatch |
            boundary) -- applied as a single gated pass: every update is
            masked by its action predicate, so no state branching/merging
            is materialized (the predicates are mutually exclusive)."""
            st = {**st}
            e = st["e"]
            t_next = ev_t[e]
            # replica slot -> (batch, worker): gang, rescue, then backup bank
            if spec is not None:
                rp_b = jnp.concatenate([st["g_b"], rp_batch_rescue, bidx])
                rp_w = jnp.concatenate([wid, st["rb_w"], st["sb_w"]])
            else:
                rp_b = jnp.concatenate([st["g_b"], rp_batch_rescue])
                rp_w = jnp.concatenate([wid, st["rb_w"]])
            win = _seg_min(rp_b, st["rp_end"], st["rp_live"])

            # -- rescue: oldest pending rescue onto the earliest-freeing
            # alive worker (engine: first free worker, FIFO rescue queue).
            # Computed on the pre-commit state so projected worker free
            # times still see replicas that commit later this epoch.
            if cfg.cancel:
                # with cancellation a worker frees at its batch's win
                proj_vals = jnp.where(st["rp_live"], win[rp_b], -inf)
            else:
                proj_vals = jnp.where(st["rp_live"], st["rp_end"], -inf)
            # rp_w of a dead rescue slot may be stale but is always in
            # bounds, and its -inf value is the scatter-max neutral
            proj = jnp.full(n + 1, -jnp.inf, dt).at[rp_w].max(proj_vals)[:n]
            # pending rescues block commits/dispatches, so t_cursor has been
            # floored to the request boundary: it is the epoch start time
            wfree = jnp.where(st["alive"], jnp.maximum(proj, st["t_cursor"]), inf)
            wfree = jnp.where(wfree <= t_next, wfree, inf)
            tgt = jnp.argmin(jnp.where(st["resc_pending"], st["resc_t"], inf))
            wstar = jnp.argmin(wfree)
            can_r = st["resc_pending"].any() & jnp.isfinite(wfree[wstar]) & st["job_active"]
            td_r = wfree[wstar]
            rk = jnp.clip(st["resc_k"], 0, cfg.resc_cap - 1)
            dur_r = tau_resc[rk, tgt] * batch_scale(st["job_b"]) / speeds[wstar]
            st["rb_w"] = _put(st["rb_w"], tgt, wstar.astype(jnp.int32), can_r)
            st["rp_start"] = _put(st["rp_start"], n + tgt, td_r, can_r)
            st["rp_end"] = _put(st["rp_end"], n + tgt, td_r + dur_r, can_r)
            st["rp_live"] = _put(st["rp_live"], n + tgt, True, can_r)
            st["resc_pending"] = _put(st["resc_pending"], tgt, False, can_r)
            st["n_resc"] = st["n_resc"] + can_r
            st["resc_k"] = st["resc_k"] + can_r

            # -- speculative backup trigger (reactive replication).  All of
            # it is a pure function of the committed state, evaluated with
            # the exact float expressions of SpeculativePolicy /
            # ClusterEngine._next_spec_time so the differential tests can
            # demand bit-equality: the running lower median of completed
            # sibling durations, each unfinished batch's youngest live
            # replica crossing at start + theta x median, and the launch on
            # the first heartbeat epoch strictly after both the crossing and
            # the last processed event.
            if spec is not None:
                iv, theta = spec.interval, spec.theta
                ofin = jnp.isfinite(st["spec_obs"])
                cnt = ofin.sum()
                med = jnp.sort(jnp.where(ofin, st["spec_obs"], jnp.inf))[
                    jnp.maximum((cnt - 1) // 2, 0)
                ]
                live = st["rp_live"]
                y_b = (
                    jnp.full(n + 1, -jnp.inf, dt)
                    .at[rp_b].max(jnp.where(live, st["rp_start"], -inf))[:n]
                )
                occ = jnp.zeros(n + 1, bool).at[jnp.where(live, rp_w, n)].set(True)[:n]
                free_ok = (st["alive"] & ~occ).any()
                elig = (
                    st["job_active"]
                    & (cnt >= spec.min_observations)
                    & free_ok
                    & (st["spec_used"] < spec.max_backups)
                    & ~st["batch_done"]
                    & jnp.isfinite(y_b)  # the batch holds a live replica
                    & ~live[2 * n :]  # one live backup per batch (see above)
                )
                now_s = jnp.maximum(st["t_cursor"], st["spec_now"])
                k = (
                    jnp.maximum(
                        jnp.floor((y_b + theta * med) / iv), jnp.floor(now_s / iv)
                    )
                    + 1.0
                )
                t_spec = jnp.min(jnp.where(elig, k * iv, jnp.inf))
                # the next replica-completion event: a batch win under
                # cancellation (the win retires the whole batch), any
                # replica end otherwise.  A launch happens only strictly
                # before it -- a completion at the same instant is an
                # earlier-queued event on the engine's heap, and its re-arm
                # supersedes the stale check.
                if cfg.cancel:
                    t_evm = jnp.min(jnp.where(~st["batch_done"], win, jnp.inf))
                else:
                    t_evm = jnp.min(jnp.where(live, st["rp_end"], jnp.inf))
                can_s = (
                    (~can_r) & jnp.isfinite(t_spec) & (t_spec < t_evm) & (t_spec < t_next)
                )
                # fire re-check at the epoch itself, the engine's
                # lagging(now - y, med); a check that launches nothing (the
                # two forms can disagree by 1 ulp) still consumes the epoch,
                # and the next arming lands one grid point later -- the same
                # self-healing re-arm the engine performs
                lag = elig & ((t_spec - y_b) > theta * med)
                b_s = jnp.argmin(jnp.where(lag, bidx, n))
                do_l = can_s & lag.any()
                w_s = jnp.argmin(jnp.where(st["alive"] & ~occ, wid, n))
                sk = jnp.clip(st["spec_k"], 0, tau_spec.shape[0] - 1)
                dur_s = (
                    tau_spec[sk, jnp.clip(b_s, 0, n - 1)]
                    * batch_scale(st["job_b"])
                    / speeds[w_s]
                )
                st["sb_w"] = _put(st["sb_w"], b_s, w_s.astype(jnp.int32), do_l)
                st["rp_start"] = _put(st["rp_start"], 2 * n + b_s, t_spec, do_l)
                st["rp_end"] = _put(st["rp_end"], 2 * n + b_s, t_spec + dur_s, do_l)
                st["rp_live"] = _put(st["rp_live"], 2 * n + b_s, True, do_l)
                st["spec_used"] = st["spec_used"] + do_l
                st["n_spec"] = st["n_spec"] + do_l
                st["spec_k"] = st["spec_k"] + do_l
                st["spec_now"] = jnp.where(can_s, t_spec, st["spec_now"])
            else:
                can_s = jnp.array(False)
                t_evm = inf

            # -- commit completions up to the next boundary (masked out
            # entirely on rescue steps: pending rescues must dispatch before
            # any commit clears the replicas their free times project from).
            # With speculation on, commit only the earliest completion-time
            # group: every completion changes the trigger's median and
            # candidate set, so later completions must see the launches (and
            # re-armed epochs) that precede them, one event at a time.
            newly = (~st["batch_done"]) & (win <= t_next) & jnp.isfinite(win) & ~can_r
            if spec is not None:
                newly = newly & (win == t_evm) & ~can_s
            if cfg.cancel:
                win_r = win[rp_b]
                done_r = st["rp_live"] & newly[rp_b]
                busy_add = jnp.where(done_r, win_r - st["rp_start"], 0.0).sum()
                saved_add = jnp.where(done_r, st["rp_end"] - win_r, 0.0).sum()
                t_new = jnp.max(jnp.where(newly, win, -inf))
            else:
                done_r = st["rp_live"] & (st["rp_end"] <= t_next) & ~can_r
                if spec is not None:
                    done_r = done_r & (st["rp_end"] == t_evm) & ~can_s
                busy_add = jnp.where(done_r, st["rp_end"] - st["rp_start"], 0.0).sum()
                saved_add = 0.0
                t_new = jnp.max(jnp.where(done_r, st["rp_end"], -inf))
            if spec is not None:
                # the winning replica's wall-clock duration is the sibling
                # observation the policy's median runs over (engine:
                # jexec.obs.append(now - worker.busy_since)); ties keep the
                # earliest-queued gang replica, i.e. the smallest start
                is_w = st["rp_live"] & newly[rp_b] & (st["rp_end"] <= win[rp_b])
                w_st = (
                    jnp.full(n + 1, jnp.inf, dt)
                    .at[jnp.where(is_w, rp_b, n)].min(st["rp_start"])[:n]
                )
                st["spec_obs"] = jnp.where(newly, win - w_st, st["spec_obs"])
            live2 = st["rp_live"] & ~done_r
            done2 = st["batch_done"] | newly
            done_t2 = jnp.where(newly, win, st["batch_done_t"])
            all_done = jnp.all(done2)
            fin = jnp.max(jnp.where(bidx < st["job_b"], done_t2, -inf))
            completes = st["job_active"] & all_done & ~can_r
            qa = st["q_active"]
            st["rp_live"] = live2
            st["busy"] = st["busy"] + busy_add
            st["saved"] = st["saved"] + saved_add
            st["batch_done"] = done2
            st["batch_done_t"] = done_t2
            st["t_cursor"] = jnp.maximum(
                st["t_cursor"], jnp.maximum(t_new, jnp.where(completes, fin, -inf))
            )
            st["fins"] = _put(st["fins"], qa, fin, completes)
            st["job_active"] = st["job_active"] & ~(all_done & ~can_r)
            st["resc_pending"] = st["resc_pending"] & ~completes

            if replan is not None:
                sc = batch_scale(st["job_b"])
                spd = speeds[rp_w]
                if cfg.cancel:
                    # one observation per newly-won batch: the winner's task
                    # time, censored by however many rivals it raced
                    cand = (st["rp_live"] | done_r) & (st["rp_end"] <= win[rp_b])
                    win_slot = (
                        jnp.full(n + 1, 2 * n, jnp.int32)
                        .at[jnp.where(cand, rp_b, n)]
                        .min(jnp.arange(2 * n, dtype=jnp.int32))[:n]
                    )
                    ws = jnp.clip(win_slot, 0, 2 * n - 1)
                    vals = (win - st["rp_start"][ws]) * spd[ws] / sc
                    comps = (
                        jnp.zeros(n + 1, jnp.int32)
                        .at[jnp.where(st["rp_live"] | done_r, rp_b, n)]
                        .add(1)[:n]
                    ).astype(dt)
                    st = _obs_push(st, vals, comps, win, newly)
                else:
                    # every replica that completes while its job is active is
                    # an uncensored observation (the engine drops stragglers
                    # that outlive their job)
                    fin_limit = jnp.where(completes, fin, inf)
                    ovalid = done_r & (st["job_active"] | completes) & (
                        st["rp_end"] <= fin_limit
                    )
                    vals = (st["rp_end"] - st["rp_start"]) * spd / sc
                    st = _obs_push(st, vals, jnp.ones_like(vals), st["rp_end"], ovalid)
                do_replan = (
                    completes
                    & (st["obs_count"] >= replan.min_observations)
                    & (st["since_refit"] >= replan.refit_every)
                )
                # _replan_pick runs unconditionally: under vmap a lax.cond on
                # the (batched) do_replan lowers to a select that evaluates
                # both branches anyway, so gating would add bookkeeping
                # without skipping the work
                new_b = _replan_pick(st, div_tab, h1, h2, blend)
                st["plan_b"] = jnp.where(do_replan, new_b, st["plan_b"])
                st["n_replans"] = st["n_replans"] + do_replan
                st["since_refit"] = jnp.where(do_replan, 0, st["since_refit"])

            # -- gang-dispatch the next queued job (engine: whole-cluster
            # FIFO gangs); mutually exclusive with rescue via job_active
            n_alive = st["alive"].sum(dtype=jnp.int32)
            q = st["q"]
            can_d = (
                (~st["job_active"])
                & (q < jobs_real)
                & (n_alive > 0)
                & ~st["rp_live"].any()
                & ~can_r
            )
            # out-of-range job gathers clamp (jax default), and can_d is
            # already false there -- no explicit clip needed
            td = jnp.maximum(st["t_cursor"], arrivals[q])
            can_d = can_d & (td < t_next)
            b = jnp.where(st["plan_b"] > 0, st["plan_b"], n_alive)
            b = jnp.clip(b, 1, jnp.maximum(n_alive, 1))
            r = n_alive // jnp.maximum(b, 1)
            rank = jnp.cumsum(st["alive"]) - 1
            sel = st["alive"] & (rank < b * r)
            # draw index = alive-rank (the engine assigns free workers in wid
            # order, drawing sequentially); batch = rank mod b
            dur = tau[q][rank] * batch_scale(b) / speeds
            sel2 = jnp.concatenate([sel, jnp.zeros(n_slots - n, bool)])
            end2 = jnp.concatenate([td + dur, jnp.full(n_slots - n, jnp.inf, dt)])
            st["g_b"] = jnp.where(can_d & sel, (rank % b).astype(jnp.int32), st["g_b"])
            st["rp_live"] = jnp.where(can_d, sel2, st["rp_live"])
            st["rp_start"] = jnp.where(can_d & sel2, td, st["rp_start"])
            st["rp_end"] = jnp.where(can_d & sel2, end2, st["rp_end"])
            st["batch_done"] = jnp.where(can_d, bidx >= b, st["batch_done"])
            st["batch_done_t"] = jnp.where(
                can_d, jnp.where(bidx >= b, -inf, inf), st["batch_done_t"]
            )
            st["job_active"] = st["job_active"] | can_d
            st["job_b"] = jnp.where(can_d, b, st["job_b"])
            st["q_active"] = jnp.where(can_d, st["q"], st["q_active"])
            st["starts"] = _put(st["starts"], q, td, can_d)
            if cfg.full_outputs:
                st["br"] = _put(st["br"], q, (b << 16 | r).astype(jnp.int32), can_d)
            st["q"] = st["q"] + can_d
            if spec is not None:
                # per-job policy state resets at dispatch (a fresh _JobExec)
                st["spec_obs"] = jnp.where(can_d, inf, st["spec_obs"])
                st["spec_used"] = jnp.where(can_d, 0, st["spec_used"])

            # -- otherwise apply one fail/join event (the engine stops
            # replaying churn once every job is recorded: the sim_over gate)
            t_ev, w_raw, up = ev_t[e], ev_w[e], ev_up[e]
            if spec is not None:
                # a launch or a committed completion group consumed this
                # step; the boundary waits for a step with neither
                do_b = ~can_r & ~can_d & ~can_s & ~newly.any() & ~done_r.any()
            else:
                do_b = ~can_r & ~can_d
            sim_over = (st["q"] >= jobs_real) & ~st["job_active"]
            act = do_b & (w_raw >= 0) & jnp.isfinite(t_ev) & ~sim_over
            w = jnp.clip(w_raw, 0, n - 1)
            was = st["alive"][w]
            do_fail = act & ~up & was
            do_join = act & up & ~was
            # a fail flips alive to False (= up), a join to True (= up)
            st["alive"] = _put(st["alive"], w, up, do_fail | do_join)
            kill = st["rp_live"] & (rp_w == w) & do_fail
            st["busy"] = st["busy"] + jnp.where(kill, t_ev - st["rp_start"], 0.0).sum()
            live3 = st["rp_live"] & ~kill
            st["rp_live"] = live3
            # a batch that just lost its last live replica needs a rescue:
            # one segment count carries both indicators (kills in the low
            # bits, survivors shifted past any possible kill count)
            seg = jnp.zeros(n + 1, jnp.int32).at[rp_b].add(kill + 4096 * live3)[:n]
            lost = (seg & 4095) > 0
            lost = lost & (seg < 4096) & ~st["batch_done"]
            st["resc_pending"] = st["resc_pending"] | lost
            st["resc_t"] = jnp.where(lost, t_ev, st["resc_t"])
            st["n_fail"] = st["n_fail"] + do_fail
            # No dispatch in this epoch can precede its boundary: when the
            # *churn event itself* is what frees the gang (a fail killing the
            # last straggler, or a join reviving a dead cluster), the engine
            # dispatches at the event time -- not at the stale last-completion
            # cursor.  Floor the cursor at the (finite) boundary.
            st["t_cursor"] = jnp.maximum(
                st["t_cursor"],
                jnp.where(do_b & jnp.isfinite(t_ev), jnp.maximum(t_ev, 0.0), -inf),
            )
            if cfg.full_outputs:
                st["ep_times"] = _put(st["ep_times"], e, t_ev, do_fail | do_join)
            st["e"] = jnp.minimum(e + do_b, ev_pad - 1)
            return st

        def done(st):
            return (st["q"] >= jobs_real) & ~st["job_active"]

        st = {
            "t_cursor": jnp.asarray(0.0, dt),
            "e": jnp.int32(0),
            "alive": wid < n_real,
            "q": jnp.int32(0),
            "job_active": jnp.array(False),
            "job_b": jnp.int32(1),
            "q_active": jnp.int32(0),
            "g_b": jnp.zeros(n, jnp.int32),
            "rb_w": jnp.zeros(n, jnp.int32),
            "rp_live": jnp.zeros(n_slots, bool),
            "rp_start": jnp.zeros(n_slots, dt),
            "rp_end": jnp.full(n_slots, jnp.inf, dt),
            "batch_done": jnp.ones(n, bool),
            "batch_done_t": jnp.full(n, -jnp.inf, dt),
            "resc_pending": jnp.zeros(n, bool),
            "resc_t": jnp.full(n, jnp.inf, dt),
            "resc_k": jnp.int32(0),
            "busy": jnp.asarray(0.0, dt),
            "saved": jnp.asarray(0.0, dt),
            "n_fail": jnp.int32(0),
            "n_resc": jnp.int32(0),
            "n_replans": jnp.int32(0),
            "plan_b": b0.astype(jnp.int32),
            "starts": jnp.full(jobs_pad, jnp.inf, dt),
            "fins": jnp.full(jobs_pad, jnp.inf, dt),
        }
        if cfg.full_outputs:
            st["br"] = jnp.zeros(jobs_pad, jnp.int32)
            st["ep_times"] = jnp.full(ev_pad, jnp.inf, dt)
        if replan is not None:
            st.update(
                obs_val=jnp.zeros(W, dt),
                obs_comp=jnp.ones(W, dt),
                obs_head=jnp.int32(0),
                obs_count=jnp.int32(0),
                since_refit=jnp.int32(0),
            )
        if spec is not None:
            st.update(
                sb_w=jnp.zeros(n, jnp.int32),
                spec_obs=jnp.full(n, jnp.inf, dt),
                spec_used=jnp.int32(0),
                spec_k=jnp.int32(0),
                spec_now=jnp.asarray(0.0, dt),
                n_spec=jnp.int32(0),
            )

        def chunk_body(carry):
            st, it = carry
            st = jax.lax.fori_loop(0, _STEP_CHUNK, lambda _, s: step(s), st)
            return st, it + 1

        def chunk_cond(carry):
            st, it = carry
            return (it < cfg.n_chunks) & ~done(st)

        st, n_chunks = jax.lax.while_loop(chunk_cond, chunk_body, (st, jnp.int32(0)))
        # flush replicas still in flight: their full duration is committed
        # worker time (it will burn whether or not we simulate it), which
        # keeps the invariant  ws(cancel on) + saved == ws(cancel off)
        flush = jnp.where(st["rp_live"], st["rp_end"] - st["rp_start"], 0.0).sum()
        out = {
            "starts": st["starts"],
            "finishes": st["fins"],
            "worker_seconds": st["busy"] + flush,
            "cancelled_seconds_saved": st["saved"],
            "n_worker_failures": st["n_fail"],
            "n_replicas_rescued": st["n_resc"],
            "n_replans": st["n_replans"],
            # step chunks the lane ran: vmapped lanes step together, so the
            # batch's largest is the runner's serial step count / _STEP_CHUNK
            "n_chunks": n_chunks,
        }
        if spec is not None:
            out["n_speculative"] = st["n_spec"]
        if cfg.full_outputs:
            out["br"] = st["br"]
            out["epoch_times"] = st["ep_times"]
        return out

    return lane


# --------------------------------------------------------------------------
# the space-sharing lane: concurrent jobs on disjoint worker subsets
# --------------------------------------------------------------------------


def _build_space_lane(cfg: _RunnerCfg):
    """One lane of the space-sharing replay (packed / balanced / fifo_gang).

    Extends the event-step formulation with per-worker vectors -- ``w_job``
    (queue index of the owning job, ``jobs_pad`` = unallocated), ``w_avail``
    (the *time* the worker is next available: set to the replica's scheduled
    end at placement, corrected down to the batch win under cancellation,
    to the job finish at release, to inf on fail and the join time on join)
    and ``w_load`` (cumulative assigned wall-clock, the 'balanced' metric) --
    plus per-job plan tables (worker request, B, cancellation mode) indexed
    by queue position, so concurrent jobs run heterogeneous plans.

    Batches of in-flight jobs live in *segment slots*: a (n,)-sized id space
    mapping each unfinished batch to its rescue bookkeeping and win
    reduction.  n slots always suffice -- rescues are served before any
    dispatch, so at dispatch time every unfinished batch of every active job
    holds a live replica on a distinct worker, and slots are freed the
    moment a batch wins.

    Each step still performs exactly one action, chosen by earliest time
    (rescues outrank dispatches at equal times, matching the engine's
    rescues-first event handlers):

      * *rescue*: the earliest-serveable pending rescue onto the earliest
        available worker -- free workers of the job's own allocation first,
        else a free unallocated worker is regranted (churn-aware
        reassignment);
      * *dispatch*: the first-fit queued job (earliest feasible time, ties
        by queue order) onto the policy's choice of free unallocated
        workers (packed: lowest wids; balanced: least ``w_load``;
        fifo_gang: the whole alive set);
      * *boundary*: one fail/join event.

    Batch wins and replica retirements up to the next churn boundary are
    committed at the top of every step -- timestamps in ``w_avail`` make
    commit order irrelevant to placement decisions, unlike the legacy
    lane's projection from live replica state.

    Speculation (``cfg.spec``) adds the engine's reactive backups: a bank of
    completed-batch durations per active job for the running lower median,
    a per-job backup budget, and a *launch* action on the heartbeat grid
    that backs up the first lagging (job, batch) on the job's own free
    worker, else a regranted unallocated one.  A backup runs in its
    worker's own replica slot (a free worker holds no live replica), so
    cancellation and churn treat it like any replica of its segment.  The
    launch reads the state at its epoch, so with speculation on the step
    takes its one action in time order -- a commit group (every completion
    before the first epoch after the next one: no launch falls between
    them), a rescue, a dispatch, a launch or a boundary -- each planned on
    the step's input state.  Without speculation the lane traces as before.
    """
    n, jobs_pad, ev_pad = cfg.n, cfg.jobs_pad, cfg.ev_pad
    dt = jnp.dtype(cfg.dtype)
    widx = jnp.arange(n)
    J = jobs_pad  # sentinel: unallocated worker / free segment slot
    balanced = cfg.scheduler == "balanced"
    spec = cfg.spec
    # the observation bank: one entry per completed batch of an active job,
    # tagged with its job (J = free).  Without churn the active jobs'
    # batches fit on their disjoint allocations, so n entries suffice; a
    # failed worker that rejoins can carry a second job's batches, so
    # churned lanes get twice that
    K = n if ev_pad == 1 else 2 * n
    # Speculation's lanes index and reduce by segment, job and worker as
    # dense masked reductions, not scatters and gathers: a TPU serializes the
    # indices of a vmapped scatter (on a v5e, a scatter-min of 400 updates
    # into 200 segments over 256 lanes takes ~680 us, the masked min ~26 us),
    # and the speculative step runs thousands of times a plan.  Without
    # speculation the lane keeps its scatter program.
    dense = spec is not None
    # A launch turns on exact comparisons (a lag against theta x the median, a
    # grid point's floor), where one ulp decides whether a backup runs.  So
    # speculation's float32 lanes divide correctly rounded, as numpy and the
    # engine do, which a TPU's divide is not.
    div = _div_rn if spec is not None and dt == jnp.float32 else jnp.divide

    def onehot(idx, size):
        return idx[None, :] == jnp.arange(size)[:, None]

    def at_min(base, idx, vals):
        if not dense:
            return base.at[idx].min(vals)
        return jnp.minimum(base, jnp.where(onehot(idx, base.shape[0]), vals, jnp.inf).min(axis=1))

    def at_max(base, idx, vals):
        if not dense:
            return base.at[idx].max(vals)
        return jnp.maximum(base, jnp.where(onehot(idx, base.shape[0]), vals, -jnp.inf).max(axis=1))

    def at_add(base, idx, vals):
        if not dense:
            return base.at[idx].add(vals)
        return base + jnp.where(onehot(idx, base.shape[0]), vals, 0).sum(axis=1).astype(base.dtype)

    def at_set(base, idx, vals):
        """``base.at[idx].set(vals)`` for indices that are distinct within ``base``
        (repeats only at a trash slot the caller drops)."""
        if not dense:
            return base.at[idx].set(vals)
        hit = onehot(idx, base.shape[0])
        got = jnp.where(hit, vals, 0).sum(axis=1).astype(base.dtype)
        return jnp.where(hit.any(axis=1), got, base)

    def take(x, idx):
        """``x[idx]`` for in-range indices."""
        if not dense:
            return x[idx]
        hit = idx[:, None] == jnp.arange(x.shape[0])[None, :]
        return jnp.where(hit, x[None, :], 0).sum(axis=1).astype(x.dtype)

    def lane(tau, tau_resc, tau_spec, ev_t, ev_w, ev_up, b0, arrivals, speeds, n_real,
             jobs_real, n_tasks, req_tab, b_tab, cancel_tab, default_req):
        inf = jnp.asarray(jnp.inf, dt)
        jidx = jnp.arange(jobs_pad)

        def bscale(b):
            return div(n_tasks, b.astype(dt)) if cfg.size_dep else jnp.asarray(1.0, dt)

        def commit(st, t_next, rp_seg, rp_w, gate=None, upto=None):
            """Commit batch wins and replica retirements up to ``t_next`` (with
            speculation: only under ``gate``, and only those before ``upto``)."""
            seg_of = jnp.clip(rp_seg, 0, n - 1)
            occupied = st["seg_job"] < J
            win = at_min(
                jnp.full(n + 1, jnp.inf, dt), rp_seg,
                jnp.where(st["rp_live"], st["rp_end"], jnp.inf),
            )[:n]
            newly = occupied & jnp.isfinite(win) & (win <= t_next)
            if spec is not None:
                newly = newly & gate & (win < upto)
            on_win = st["rp_live"] & take(newly, seg_of) & (rp_seg < n)
            win_r = take(win, seg_of)
            if spec is not None:
                # the batch's first completion is an observation for its job's
                # median: the winner's duration, ties to the earliest-queued
                # (smallest start) replica, as the engine's heap pops them
                first = on_win & (st["rp_end"] == win_r)
                w_st = at_min(jnp.full(n + 1, jnp.inf, dt), jnp.where(first, rp_seg, n),
                              st["rp_start"])[:n]
                free_b = st["obs_job"] == J
                slot = at_set(jnp.full(K + 1, K, jnp.int32),
                              jnp.where(free_b, jnp.cumsum(free_b) - 1, K),
                              jnp.arange(K, dtype=jnp.int32))[:K]
                pos = jnp.where(newly, take(slot, jnp.clip(jnp.cumsum(newly) - 1, 0, K - 1)), K)
                st["obs_val"] = at_set(jnp.append(st["obs_val"], 0.0), pos, win - w_st)[:K]
                st["obs_job"] = at_set(jnp.append(st["obs_job"], J), pos, st["seg_job"])[:K]
            # cancellation: every replica of a winning segment stops at the
            # win (the winner by construction, the losers reclaimed)
            kill_c = on_win & st["rp_cancel"]
            st["busy"] = st["busy"] + jnp.where(kill_c, win_r - st["rp_start"], 0.0).sum()
            st["saved"] = st["saved"] + jnp.where(kill_c, st["rp_end"] - win_r, 0.0).sum()
            # trash slot n takes the writes of replicas not killed
            st["w_avail"] = at_set(
                jnp.append(st["w_avail"], 0.0), jnp.where(kill_c, rp_w, n), win_r
            )[:n]
            # non-cancel replicas retire individually at their own end
            retire = st["rp_live"] & ~st["rp_cancel"] & (st["rp_end"] <= t_next)
            if spec is not None:
                retire = retire & gate & (st["rp_end"] < upto)
                last = jnp.maximum(jnp.max(jnp.where(newly, win, -inf)),
                                   jnp.max(jnp.where(retire, st["rp_end"], -inf)))
                st["t_now"] = jnp.maximum(st["t_now"], last)
            st["busy"] = st["busy"] + jnp.where(
                retire, st["rp_end"] - st["rp_start"], 0.0
            ).sum()
            live2 = st["rp_live"] & ~(kill_c | retire)
            st["rp_live"] = live2
            # non-cancel survivors of a winning segment detach: the batch is
            # done but the straggler replica keeps burning to its end
            gone = ~live2[:n] | (take(newly, jnp.clip(st["g_s"], 0, n - 1)) & (st["g_s"] < n))
            st["g_s"] = jnp.where(gone, n, st["g_s"])

            # -- job bookkeeping: wins decrement the owner's open count
            segj = st["seg_job"]
            i_new = jnp.where(newly, jnp.clip(segj, 0, J - 1), J)
            st["job_left"] = at_add(jnp.append(st["job_left"], 0), i_new, -1)[:J]
            st["job_fin"] = at_max(jnp.append(st["job_fin"], -jnp.inf), i_new, win)[:J]
            st["seg_job"] = jnp.where(newly, J, segj)  # freed at the win
            st["resc_pending"] = st["resc_pending"] & ~newly
            comp = st["dispatched"] & (st["job_left"] == 0) & ~st["recorded"]
            st["fins"] = jnp.where(comp, st["job_fin"], st["fins"])
            st["recorded"] = st["recorded"] | comp
            st["n_done"] = st["n_done"] + comp.sum(dtype=jnp.int32)
            wj = jnp.clip(st["w_job"], 0, J - 1)
            rel = (st["w_job"] < J) & take(comp, wj)
            st["w_avail"] = jnp.where(
                rel, jnp.maximum(st["w_avail"], take(st["job_fin"], wj)), st["w_avail"]
            )
            st["w_job"] = jnp.where(rel, J, st["w_job"])
            if spec is not None:  # a finished job's observations leave the bank
                oj = st["obs_job"]
                st["obs_job"] = jnp.where((oj < J) & comp[jnp.clip(oj, 0, J - 1)], J, oj)
            return st

        def tier_of(st, j):
            # space policies take the job's own free workers before regranting
            # an unallocated one; the gang engine has no allocations and just
            # takes the policy-first free worker
            if cfg.scheduler == "fifo_gang":
                return jnp.zeros(n, jnp.int32)
            return jnp.where(st["w_job"] == j, 0, 1)

        def pick(st, cand, tier):
            """The policy's first worker of ``cand`` in the best tier: lowest wid
            (packed, fifo_gang) or least load, ties by wid (balanced)."""
            key2 = st["w_load"] if balanced else widx.astype(dt)
            mt = cand & (tier == jnp.min(jnp.where(cand, tier, 2)))
            mk = mt & (key2 == jnp.min(jnp.where(mt, key2, jnp.inf)))
            return jnp.argmin(jnp.where(mk, widx, n))

        def rescue(st, t_next):
            """The earliest-serveable pending rescue, oldest first on ties;
            eligible workers are the job's own free allocation plus free
            unallocated workers (regrant)."""
            pend = st["resc_pending"]
            segjob = jnp.clip(st["seg_job"], 0, J - 1)
            free_w = st["alive"] & (st["w_job"] == J)
            elig = (free_w[None, :] | (st["w_job"][None, :] == segjob[:, None])) & (
                st["alive"][None, :] & pend[:, None]
            )
            serve0 = jnp.min(jnp.where(elig, st["w_avail"][None, :], jnp.inf), axis=1)
            serve_t = jnp.where(pend, jnp.maximum(st["resc_t"], serve0), jnp.inf)
            serve_min = jnp.min(serve_t)
            m1 = serve_t == serve_min
            r_min = jnp.min(jnp.where(m1, st["resc_t"], jnp.inf))
            s_star = jnp.argmin(jnp.where(m1 & (st["resc_t"] == r_min), widx, n))
            ok = pend.any() & jnp.isfinite(serve_min) & (serve_min <= t_next)
            j_star = segjob[s_star]
            cand = st["alive"] & (st["w_avail"] <= serve_min) & (
                (st["w_job"] == j_star) | (st["w_job"] == J)
            )
            w_star = pick(st, cand, tier_of(st, j_star))
            rk = jnp.clip(st["resc_k"], 0, cfg.resc_cap - 1)
            dur_r = div(
                tau_resc[rk, s_star] * bscale(jnp.maximum(st["job_b"][j_star], 1)),
                speeds[w_star],
            )
            return {"ok": ok, "t": serve_min, "s": s_star, "j": j_star, "w": w_star,
                    "dur": dur_r}

        def place_rescue(st, r, on):
            s_star, j_star, w_star, t, dur_r = r["s"], r["j"], r["w"], r["t"], r["dur"]
            st["rb_w"] = _put(st["rb_w"], s_star, w_star.astype(jnp.int32), on)
            st["rp_start"] = _put(st["rp_start"], n + s_star, t, on)
            st["rp_end"] = _put(st["rp_end"], n + s_star, t + dur_r, on)
            st["rp_live"] = _put(st["rp_live"], n + s_star, True, on)
            st["rp_cancel"] = _put(st["rp_cancel"], n + s_star, cancel_tab[j_star], on)
            st["resc_pending"] = _put(st["resc_pending"], s_star, False, on)
            st["w_job"] = _put(st["w_job"], w_star, j_star.astype(jnp.int32), on)
            st["w_avail"] = _put(st["w_avail"], w_star, t + dur_r, on)
            # speed-weighted load (duration / speed), same op order as the
            # engine's _assign so f64 lanes replay placement bit-for-bit
            w_load_new = st["w_load"][w_star] + div(dur_r, speeds[w_star])
            st["w_load"] = _put(st["w_load"], w_star, w_load_new, on)
            st["n_resc"] = st["n_resc"] + on
            st["resc_k"] = st["resc_k"] + on
            return st

        def dispatch(st, t_next, gate):
            """First-fit over undispatched jobs -- earliest feasible time
            (req-th smallest availability among free unallocated workers,
            floored at the job's arrival and the epoch start), ties broken by
            queue order.  ``gate(td)`` decides whether it may run."""
            n_alive = st["alive"].sum(dtype=jnp.int32)
            free_w2 = st["alive"] & (st["w_job"] == J)
            sa = jnp.sort(jnp.where(free_w2, st["w_avail"], jnp.inf))
            req = jnp.where(
                req_tab > 0, req_tab, jnp.where(default_req > 0, default_req, n_alive)
            )
            req_eff = jnp.clip(req, 1, jnp.maximum(n_alive, 1))
            kth = take(sa, jnp.clip(req_eff - 1, 0, n - 1))
            segfree = st["seg_job"] == J
            seg_rank = jnp.cumsum(segfree) - 1
            n_segfree = segfree.sum(dtype=jnp.int32)
            bq = jnp.clip(
                jnp.where(b_tab > 0, b_tab, jnp.where(b0 > 0, b0, req_eff)), 1, req_eff
            )
            t_q = jnp.maximum(arrivals, jnp.maximum(kth, st["t_epoch"]))
            t_q = jnp.where(
                (~st["dispatched"]) & (jidx < jobs_real) & (n_alive > 0)
                & (bq <= n_segfree),
                t_q,
                jnp.inf,
            )
            q_star = jnp.argmin(t_q)  # first min: lowest queue index
            td = t_q[q_star]
            ok = gate(td)
            b_d = bq[q_star]
            r_d = req_eff[q_star] // b_d
            elig_d = free_w2 & (st["w_avail"] <= td)
            keyd = jnp.where(elig_d, st["w_load"] if balanced else widx.astype(dt), jnp.inf)
            rank = jnp.argsort(jnp.argsort(keyd, stable=True), stable=True)
            sel_rep = elig_d & (rank < b_d * r_d)
            sel_alloc = elig_d & (rank < req_eff[q_star])
            # the beta-th dispatched batch takes the beta-th free segment
            seg_by_beta = at_set(
                jnp.full(n + 1, n, jnp.int32), jnp.where(segfree, seg_rank, n),
                widx.astype(jnp.int32),
            )[:n]
            w_seg = take(seg_by_beta, jnp.clip(rank % jnp.maximum(b_d, 1), 0, n - 1))
            # draw index = policy rank: the engine draws in placement order
            dur = div(take(tau[q_star], jnp.clip(rank, 0, n - 1)) * bscale(b_d), speeds)
            return {"ok": ok, "t": td, "q": q_star, "b": b_d, "r": r_d, "rep": sel_rep,
                    "alloc": sel_alloc, "seg": w_seg, "dur": dur, "segfree": segfree,
                    "seg_rank": seg_rank}

        def place_dispatch(st, d, on):
            q_star, td, b_d, dur = d["q"], d["t"], d["b"], d["dur"]
            sel_rep, sel_alloc = d["rep"], d["alloc"]
            sel2 = jnp.concatenate([on & sel_rep, jnp.zeros(n, bool)])
            st["g_s"] = jnp.where(on & sel_rep, d["seg"], st["g_s"])
            st["rp_live"] = st["rp_live"] | sel2
            st["rp_start"] = jnp.where(sel2, td, st["rp_start"])
            st["rp_end"] = jnp.where(
                sel2, jnp.concatenate([td + dur, jnp.zeros(n, dt)]), st["rp_end"]
            )
            st["rp_cancel"] = jnp.where(sel2, cancel_tab[q_star], st["rp_cancel"])
            st["w_job"] = jnp.where(on & sel_alloc, q_star.astype(jnp.int32), st["w_job"])
            st["w_avail"] = jnp.where(
                on & sel_rep,
                td + dur,
                jnp.where(on & sel_alloc, td, st["w_avail"]),
            )
            st["w_load"] = st["w_load"] + jnp.where(on & sel_rep, div(dur, speeds), 0.0)
            st["seg_job"] = jnp.where(
                on & d["segfree"] & (d["seg_rank"] < b_d), q_star.astype(jnp.int32),
                st["seg_job"],
            )
            st["starts"] = _put(st["starts"], q_star, td, on)
            st["dispatched"] = _put(st["dispatched"], q_star, True, on)
            st["job_left"] = _put(st["job_left"], q_star, b_d, on)
            st["job_b"] = _put(st["job_b"], q_star, b_d, on)
            if cfg.full_outputs:
                br = (b_d << 16 | d["r"]).astype(jnp.int32)
                st["br"] = _put(st["br"], q_star, br, on)
            return st

        def hedge(st, rp_seg):
            """The next speculative launch on the committed state, with the
            engine's float expressions (``_next_spec_time``,
            ``_on_spec_check``, ``_spec_pick_worker``): each job's lower median
            of completed-batch durations, each unfinished batch's youngest live
            replica crossing at start + theta x median, the launch on the first
            heartbeat epoch strictly after both the crossing and now, and the
            first lagging (job, batch) in order backed up there -- batches of
            a job take segments in batch order.  Also the end of the next
            commit group: no launch can fall before the first epoch after the
            next completion, so every completion before it commits at once."""
            # the grid spacing as an opaque value: a division by a constant
            # compiles to a multiply by its reciprocal, which floors a grid
            # point's own index one lower than the engine's true division
            iv = jax.lax.optimization_barrier(jnp.asarray(spec.interval, dt))
            theta = spec.theta
            live = st["rp_live"]
            t_evm = jnp.min(jnp.where(live, st["rp_end"], jnp.inf))
            _, bval = jax.lax.sort((st["obs_job"], st["obs_val"]), num_keys=2)
            cnt = at_add(jnp.zeros(J + 1, jnp.int32), st["obs_job"], 1)[:J]
            med = take(bval, jnp.clip(jnp.cumsum(cnt) - cnt + (cnt - 1) // 2, 0, K - 1))
            now = jnp.maximum(st["t_now"], st["t_epoch"])
            free = st["alive"] & (st["w_avail"] <= now)
            if cfg.scheduler == "fifo_gang":
                room = free.any()
            else:
                own = at_add(jnp.zeros(J + 1, jnp.int32), jnp.where(free, st["w_job"], J), 1)
                room = (own[:J] > 0) | (free & (st["w_job"] == J)).any()
            job_ok = (
                (cnt >= spec.min_observations) & (st["spec_used"] < spec.max_backups) & room
            )
            sj = jnp.clip(st["seg_job"], 0, J - 1)
            m_s = take(med, sj)
            y = at_max(jnp.full(n + 1, -jnp.inf, dt), rp_seg,
                       jnp.where(live, st["rp_start"], -inf))[:n]
            elig = (st["seg_job"] < J) & take(job_ok, sj) & jnp.isfinite(y)
            k = jnp.maximum(jnp.floor(div(y + theta * m_s, iv)), jnp.floor(div(now, iv))) + 1.0
            t_s = jnp.min(jnp.where(elig, k * iv, jnp.inf))
            # fire re-check at the epoch itself, the engine's lagging(now - y,
            # med); a check that launches nothing (the two forms can disagree
            # by 1 ulp) still consumes the epoch, as the engine's does
            lag = elig & ((t_s - y) > theta * m_s)
            j_first = jnp.min(jnp.where(lag, st["seg_job"], J))
            s_star = jnp.argmin(jnp.where(lag & (st["seg_job"] == j_first), widx, n))
            j_star = jnp.clip(j_first, 0, J - 1)
            cand = free & ((st["w_job"] == j_star) | (st["w_job"] == J))
            w_s = pick(st, cand, tier_of(st, j_star))
            sk = jnp.clip(st["spec_k"], 0, tau_spec.shape[0] - 1)
            dur = div(tau_spec[sk, w_s] * bscale(jnp.maximum(st["job_b"][j_star], 1)),
                      speeds[w_s])
            return {"t": t_s, "launch": lag.any(), "s": s_star, "j": j_star, "w": w_s,
                    "dur": dur, "t_evm": t_evm,
                    "upto": (jnp.floor(div(t_evm, iv)) + 1.0) * iv}

        def place_backup(st, h, on):
            """A backup replica takes the free worker's own slot: a free worker
            runs no live replica."""
            s_star, j_star, w_s, t, dur = h["s"], h["j"], h["w"], h["t"], h["dur"]
            go = on & h["launch"]
            st["g_s"] = _put(st["g_s"], w_s, s_star.astype(jnp.int32), go)
            st["rp_start"] = _put(st["rp_start"], w_s, t, go)
            st["rp_end"] = _put(st["rp_end"], w_s, t + dur, go)
            st["rp_live"] = _put(st["rp_live"], w_s, True, go)
            st["rp_cancel"] = _put(st["rp_cancel"], w_s, cancel_tab[j_star], go)
            st["w_job"] = _put(st["w_job"], w_s, j_star.astype(jnp.int32), go)
            st["w_avail"] = _put(st["w_avail"], w_s, t + dur, go)
            w_load_new = st["w_load"][w_s] + div(dur, speeds[w_s])
            st["w_load"] = _put(st["w_load"], w_s, w_load_new, go)
            st["spec_used"] = _put(st["spec_used"], j_star, st["spec_used"][j_star] + 1, go)
            st["n_spec"] = st["n_spec"] + go
            st["spec_k"] = st["spec_k"] + go
            st["t_now"] = jnp.where(on, jnp.maximum(st["t_now"], t), st["t_now"])
            return st

        def boundary(st, e, rp_w, do_b):
            """Apply one fail/join event (sim-over gated)."""
            sim_over = st["n_done"] >= jobs_real
            t_ev, w_raw, up = ev_t[e], ev_w[e], ev_up[e]
            act = do_b & (w_raw >= 0) & jnp.isfinite(t_ev) & ~sim_over
            w = jnp.clip(w_raw, 0, n - 1)
            was = st["alive"][w]
            do_fail = act & ~up & was
            do_join = act & up & ~was
            st["alive"] = _put(st["alive"], w, up, do_fail | do_join)
            kill = st["rp_live"] & (rp_w == w) & do_fail
            st["busy"] = st["busy"] + jnp.where(kill, t_ev - st["rp_start"], 0.0).sum()
            live3 = st["rp_live"] & ~kill
            st["rp_live"] = live3
            rp_seg3 = jnp.concatenate([st["g_s"], widx])
            seg_cnt = at_add(jnp.zeros(n + 1, jnp.int32), rp_seg3, kill + 4096 * live3)[:n]
            lost = ((seg_cnt & 4095) > 0) & (seg_cnt < 4096) & (st["seg_job"] < J)
            st["resc_pending"] = st["resc_pending"] | lost
            st["resc_t"] = jnp.where(lost, t_ev, st["resc_t"])
            st["g_s"] = jnp.where(do_fail & (widx == w), n, st["g_s"])
            st["w_job"] = _put(st["w_job"], w, J, do_fail | do_join)
            st["w_avail"] = _put(st["w_avail"], w, jnp.inf, do_fail)
            st["w_avail"] = _put(st["w_avail"], w, t_ev, do_join)
            st["n_fail"] = st["n_fail"] + do_fail
            st["t_epoch"] = jnp.maximum(
                st["t_epoch"],
                jnp.where(do_b & jnp.isfinite(t_ev), jnp.maximum(t_ev, 0.0), -inf),
            )
            if cfg.full_outputs:
                st["ep_times"] = _put(st["ep_times"], e, t_ev, do_fail | do_join)
            st["e"] = jnp.minimum(e + do_b, ev_pad - 1)
            return st

        def step(st):
            st = {**st}
            e = st["e"]
            t_next = ev_t[e]
            rp_seg = jnp.concatenate([st["g_s"], widx])
            rp_w = jnp.concatenate([widx, st["rb_w"]])
            if spec is None:
                # commit everything up to the boundary, then one action
                st = commit(st, t_next, rp_seg, rp_w)
                r = rescue(st, t_next)
                can_r = r["ok"]
                st = place_rescue(st, r, can_r)
                d = dispatch(st, t_next, lambda td: ~can_r & jnp.isfinite(td) & (td < t_next))
                can_d = d["ok"]
                st = place_dispatch(st, d, can_d)
                return boundary(st, e, rp_w, ~can_r & ~can_d)
            # speculation reads the state at its epoch, so actions run in time
            # order, one a step, from plans made on the step's input state:
            # commit group, then rescue, dispatch, launch, boundary on ties
            # (completions and the handlers they fire precede a check at the
            # same instant; churn events precede a check)
            r = rescue(st, t_next)
            d = dispatch(st, t_next, lambda td: jnp.isfinite(td) & (td < t_next))
            h = hedge(st, rp_seg)
            t_c = jnp.where(h["t_evm"] <= t_next, h["t_evm"], jnp.inf)
            t_r = jnp.where(r["ok"], r["t"], jnp.inf)
            t_d = jnp.where(d["ok"], d["t"], jnp.inf)
            t_s = jnp.where(h["t"] < t_next, h["t"], jnp.inf)
            do_c = jnp.isfinite(t_c) & (t_c <= t_r) & (t_c <= t_d) & (t_c <= t_s)
            do_r = ~do_c & r["ok"] & (t_r <= t_d) & (t_r <= t_s)
            do_d = ~do_c & ~do_r & d["ok"] & (t_d <= t_s)
            do_s = ~do_c & ~do_r & ~do_d & jnp.isfinite(t_s)
            st = commit(st, t_next, rp_seg, rp_w, gate=do_c, upto=h["upto"])
            st = place_rescue(st, r, do_r)
            st = place_dispatch(st, d, do_d)
            st = place_backup(st, h, do_s)
            st["t_now"] = jnp.maximum(
                st["t_now"], jnp.where(do_r, t_r, jnp.where(do_d, t_d, -inf))
            )
            return boundary(st, e, rp_w, ~(do_c | do_r | do_d | do_s))

        st = {
            "t_epoch": jnp.asarray(0.0, dt),
            "e": jnp.int32(0),
            "alive": widx < n_real,
            "w_job": jnp.full(n, J, jnp.int32),
            "w_avail": jnp.where(widx < n_real, 0.0, jnp.inf).astype(dt),
            "w_load": jnp.zeros(n, dt),
            "g_s": jnp.full(n, n, jnp.int32),
            "rb_w": jnp.zeros(n, jnp.int32),
            "rp_live": jnp.zeros(2 * n, bool),
            "rp_start": jnp.zeros(2 * n, dt),
            "rp_end": jnp.full(2 * n, jnp.inf, dt),
            "rp_cancel": jnp.zeros(2 * n, bool),
            "seg_job": jnp.full(n, J, jnp.int32),
            "resc_pending": jnp.zeros(n, bool),
            "resc_t": jnp.full(n, jnp.inf, dt),
            "resc_k": jnp.int32(0),
            "busy": jnp.asarray(0.0, dt),
            "saved": jnp.asarray(0.0, dt),
            "n_fail": jnp.int32(0),
            "n_resc": jnp.int32(0),
            "n_done": jnp.int32(0),
            "dispatched": jnp.zeros(jobs_pad, bool),
            "recorded": jnp.zeros(jobs_pad, bool),
            "job_left": jnp.zeros(jobs_pad, jnp.int32),
            "job_b": jnp.ones(jobs_pad, jnp.int32),
            "job_fin": jnp.full(jobs_pad, -jnp.inf, dt),
            "starts": jnp.full(jobs_pad, jnp.inf, dt),
            "fins": jnp.full(jobs_pad, jnp.inf, dt),
        }
        if cfg.full_outputs:
            st["br"] = jnp.zeros(jobs_pad, jnp.int32)
            st["ep_times"] = jnp.full(ev_pad, jnp.inf, dt)
        if spec is not None:
            st.update(
                t_now=jnp.asarray(0.0, dt),
                obs_val=jnp.zeros(K, dt),
                obs_job=jnp.full(K, J, jnp.int32),
                spec_used=jnp.zeros(jobs_pad, jnp.int32),
                spec_k=jnp.int32(0),
                n_spec=jnp.int32(0),
            )

        def chunk_body(carry):
            s, it = carry
            s = jax.lax.fori_loop(0, _STEP_CHUNK, lambda _, x: step(x), s)
            return s, it + 1

        def chunk_cond(carry):
            s, it = carry
            return (it < cfg.n_chunks) & (s["n_done"] < jobs_real)

        st, n_chunks = jax.lax.while_loop(chunk_cond, chunk_body, (st, jnp.int32(0)))
        flush = jnp.where(st["rp_live"], st["rp_end"] - st["rp_start"], 0.0).sum()
        out = {
            "starts": st["starts"],
            "finishes": st["fins"],
            "worker_seconds": st["busy"] + flush,
            "cancelled_seconds_saved": st["saved"],
            "n_worker_failures": st["n_fail"],
            "n_replicas_rescued": st["n_resc"],
            "n_replans": jnp.int32(0),
            "n_chunks": n_chunks,
        }
        if spec is not None:
            out["n_speculative"] = st["n_spec"]
        if cfg.full_outputs:
            out["br"] = st["br"]
            out["epoch_times"] = st["ep_times"]
        return out

    return lane


def _wrap_stream_lane(lane, cfg: _RunnerCfg):
    """Fold a lane's per-job outputs into streaming accumulators on device.

    Runs *after* the untouched lane body, as a sequential ``lax.scan`` over
    the job axis in arrival order -- the exact fold order the host reference
    (:func:`repro.cluster.stream.epoch_stream_stats`) replays over a full
    report, which is what makes streaming equal materialized bit for bit
    (float64 lanes).  Jobs past the real count and jobs never finished
    (dead cluster) are masked out of the statistics; the latter are counted
    in ``n_unfinished`` and force ``fin_max`` to the sampled-churn check's
    conservative side via the unfinished flag.
    """
    from .vectorized import STREAM_HIST_BINS, STREAM_HIST_EDGES

    dt = jnp.dtype(cfg.dtype)
    edges = jnp.asarray(STREAM_HIST_EDGES, dt)

    def wrapped(*args):
        out = lane(*args)
        arrivals, jobs_real = args[7], args[10]
        starts = out.pop("starts")
        fins = out.pop("finishes")

        def fold(acc, inp):
            a, s, f, j = inp
            real = j < jobs_real
            m = real & jnp.isfinite(f)
            resp = f - a
            comp = f - s
            one = m.astype(jnp.int32)
            bins = jnp.searchsorted(edges, resp, side="right")
            # max(sq, 0) pins the square as a standalone IEEE multiply --
            # see the matching comment in vectorized._stream_slab
            resp2 = jnp.maximum(resp * resp, 0.0)
            return {
                "count": acc["count"] + one,
                "resp_sum": acc["resp_sum"] + jnp.where(m, resp, 0.0),
                "resp_sq": acc["resp_sq"] + jnp.where(m, resp2, 0.0),
                "resp_min": jnp.minimum(acc["resp_min"], jnp.where(m, resp, jnp.inf)),
                "resp_max": jnp.maximum(acc["resp_max"], jnp.where(m, resp, -jnp.inf)),
                "comp_sum": acc["comp_sum"] + jnp.where(m, comp, 0.0),
                "hist": acc["hist"].at[bins].add(one),
                "n_unfinished": acc["n_unfinished"] + (real & ~jnp.isfinite(f)).astype(jnp.int32),
                "fin_max": jnp.maximum(acc["fin_max"], jnp.where(m, f, -jnp.inf)),
            }, None

        zero = jnp.asarray(0.0, dt)
        acc0 = {
            "count": jnp.int32(0),
            "resp_sum": zero,
            "resp_sq": zero,
            "resp_min": jnp.asarray(jnp.inf, dt),
            "resp_max": jnp.asarray(-jnp.inf, dt),
            "comp_sum": zero,
            "hist": jnp.zeros(STREAM_HIST_BINS, jnp.int32),
            "n_unfinished": jnp.int32(0),
            "fin_max": jnp.asarray(-jnp.inf, dt),
        }
        acc, _ = jax.lax.scan(
            fold,
            acc0,
            (arrivals, starts, fins, jnp.arange(cfg.jobs_pad, dtype=jnp.int32)),
        )
        out.update(acc)
        return out

    return wrapped


def _get_runner(cfg: _RunnerCfg):
    if cfg in _RUNNERS:
        return _RUNNERS[cfg]
    lane = _build_space_lane(cfg) if cfg.scheduler is not None else _build_lane(cfg)
    if cfg.stream:
        lane = _wrap_stream_lane(lane, cfg)
    fn = jax.vmap(lane, in_axes=(0,) * 7 + (None,) * 9)
    if cfg.devices > 1:
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[: cfg.devices]), ("lanes",))
        # check_vma=False: the early-exit while_loop has no replication rule,
        # and every lane is independent anyway (out_specs split the lane axis)
        fn = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P("lanes"),) * 7 + (P(),) * 9,
            out_specs=P("lanes"),
            check_vma=False,
        )
    # no buffer donation: XLA can only alias a donated input to an output of
    # the same shape, and no per-lane draw or churn buffer has one (a TPU
    # compile warns that the donated buffers were not usable)
    runner = jax.jit(fn)
    _RUNNERS[cfg] = runner
    return runner


# --------------------------------------------------------------------------
# per-lane draw preparation (chunk- and shard-invariant seed derivation)
# --------------------------------------------------------------------------


def _sample_churn_np(rng, churn: ChurnProcess, n_workers: int, pairs: int):
    """One lane's alternating-renewal fail/join timeline, the engine's law.

    Also returns the lane's *horizon*: the earliest time any worker's
    sampled stream runs dry (its last of ``2 * pairs`` events).  Past the
    horizon the lane's workers stay up while the engine keeps churning, so
    a simulation that outruns it has silently left the engine's law --
    callers compare finish times against it and warn.  With
    ``mean_downtime == 0`` downtimes are infinite (failures are permanent),
    every stream ends at +inf, and the horizon is never reached.
    """
    ups = rng.exponential(1.0 / churn.fail_rate, (n_workers, pairs))
    if churn.mean_downtime > 0.0:
        downs = rng.exponential(churn.mean_downtime, (n_workers, pairs))
    else:
        downs = np.full((n_workers, pairs), np.inf)
    iv = np.stack([ups, downs], axis=-1).reshape(n_workers, 2 * pairs)
    t = np.cumsum(iv, axis=-1)  # fail at even positions, join at odd
    horizon = float(np.min(t[:, -1]))
    u = np.broadcast_to((np.arange(2 * pairs) % 2).astype(bool), t.shape).ravel()
    w = np.broadcast_to(np.arange(n_workers, dtype=np.int32)[:, None], t.shape).ravel()
    t = t.ravel()
    order = np.argsort(t, kind="stable")
    t, w, u = t[order], w[order], u[order]
    return t, np.where(np.isfinite(t), w, -1), u, horizon


def _pack_schedule(schedule: Optional[ChurnSchedule], n_lanes: int, ev_pad: int, dtype):
    """Shared explicit timeline (or the no-churn stream), inf-padded."""
    t = np.full(ev_pad, np.inf, np.float64)
    w = np.full(ev_pad, -1, np.int32)
    u = np.zeros(ev_pad, bool)
    if schedule is not None and len(schedule):
        t[: len(schedule)] = np.asarray(schedule.times, np.float64)
        w[: len(schedule)] = np.asarray(schedule.wids, np.int32)
        u[: len(schedule)] = np.asarray(schedule.ups, bool)
    put = (jnp.asarray(t.astype(dtype)), jnp.asarray(w), jnp.asarray(u))
    count("h2d.bytes", sum(a.nbytes for a in put))
    return tuple(jnp.broadcast_to(a, (n_lanes,) + a.shape) for a in put)


def _prepare_lanes(dist, n_workers, n_pad, lane_idx, n_real, jobs_pad, ev_pad, resc_cap,
                   seed, churn, churn_schedule, pairs, dtype, spec_cap=0):
    """Per-lane inputs shared by both entry points: service draws, rescue
    draws, and the churn event stream.

    Host-side numpy on purpose: lane ``i`` draws from
    ``default_rng(SeedSequence((seed, i)))``, a pure function of the global
    lane index, so results are bit-identical under ``rep_chunk`` chunking,
    ``devices`` sharding, and shape-bucket padding -- and the cold path pays
    zero sampling compiles (the fastest jax program is the one never traced).

    Only the first ``n_real`` lanes carry results; bucket-padding lanes get
    constant durations (their outputs are sliced off, no need to sample).
    Rescue draws are sampled only when churn events can actually create
    rescues -- tau is drawn first per lane, so skipping them changes nothing.
    """
    n_lanes = len(lane_idx)
    seed = int(seed)
    sample_churn = churn is not None and churn.fail_rate > 0.0 and pairs > 0
    need_resc = sample_churn or (churn_schedule is not None and len(churn_schedule))
    with span("draws.lanes"):
        tau = np.ones((n_lanes, jobs_pad, n_pad), dtype)
        tau_resc = np.ones((n_lanes, resc_cap, n_pad), dtype)
        tau_spec = np.ones((n_lanes, max(spec_cap, 1), n_pad), dtype)
        horizon = np.full(n_lanes, np.inf)
        if sample_churn:
            ev_t = np.full((n_lanes, ev_pad), np.inf, dtype)
            ev_w = np.full((n_lanes, ev_pad), -1, np.int32)
            ev_up = np.zeros((n_lanes, ev_pad), bool)
        for i, lane in enumerate(lane_idx[:n_real]):
            rng = np.random.default_rng(np.random.SeedSequence((seed, int(lane))))
            tau[i] = dist.sample_np(rng, (jobs_pad, n_pad))
            if need_resc:
                tau_resc[i] = dist.sample_np(rng, (resc_cap, n_pad))
            if spec_cap:
                tau_spec[i] = dist.sample_np(rng, (spec_cap, n_pad))
            if sample_churn:
                t, w, u, horizon[i] = _sample_churn_np(rng, churn, n_workers, pairs)
                k = min(len(t), ev_pad)
                ev_t[i, :k], ev_w[i, :k], ev_up[i, :k] = t[:k], w[:k], u[:k]
    with span("xfer.put"):
        if not sample_churn:
            ev = _pack_schedule(churn_schedule, n_lanes, ev_pad, dtype)
        else:
            ev = (jnp.asarray(ev_t), jnp.asarray(ev_w), jnp.asarray(ev_up))
            count("h2d.bytes", sum(a.nbytes for a in ev))
        taus = (jnp.asarray(tau), jnp.asarray(tau_resc), jnp.asarray(tau_spec))
        count("h2d.bytes", sum(a.nbytes for a in taus))
    return (*taus, *ev, horizon)


def _shapes(n_workers, n_jobs, churn, churn_schedule, pairs, speculation=None):
    n_pad = _bucket_workers(n_workers)
    # per-job output arrays are scattered into every step: bucket them at a
    # finer granularity than power-of-two (32) to keep the carried elements
    # close to the real job count
    jobs_pad = _pow2(n_jobs) if n_jobs < 32 else -(-n_jobs // 32) * 32
    if churn is not None and churn.fail_rate > 0.0 and pairs > 0:
        ev_real = 2 * pairs * n_workers
    elif churn_schedule is not None:
        ev_real = len(churn_schedule)
    else:
        ev_real = 0
    ev_pad = _pow2(ev_real + 1)
    # rescue dispatches are bounded by worker failures, at most half the
    # event stream under the alternating fail/join law
    resc_cap = max(8, ev_pad // 2)
    # step budget: one step per job dispatch + one per churn event + a rescue
    # allowance, plus one trailing commit; overruns leave jobs at inf exactly
    # like the engine's max_events cap
    if speculation is not None:
        # commit groups each retire at least one replica (at most n_pad gang
        # replicas a job, its backups and the rescues), and each job takes a
        # dispatch and up to one launch and one (rare, 1-ulp) empty check per
        # backup; rescues take a step each
        mb = speculation.max_backups
        budget = jobs_pad * (n_pad + 1 + 3 * mb) + ev_pad + 2 * resc_cap + 2
    else:
        budget = jobs_pad + ev_pad + resc_cap + 2
    n_chunks = -(-budget // _STEP_CHUNK)
    return n_pad, jobs_pad, ev_pad, resc_cap, n_chunks


def _spec_cap(jobs_pad: int, spec: Optional[Speculation]) -> int:
    """Rows of a lane's speculation draws: one row per possible launch
    (``max_backups`` a job), drawn after the service and rescue draws."""
    return jobs_pad * spec.max_backups if spec is not None else 0


def _run_lanes(dist, cfg, n_workers, lane_idx, b0, arrivals_pad, n_jobs_real, seed,
               speeds, churn, churn_schedule, pairs, n_tasks, replan, space_tabs=None):
    """Pad the lane batch to its bucket, run the compiled runner, unpad.

    ``space_tabs`` carries the space-sharing lane's per-job plan tables
    ``(req_tab, b_tab, cancel_tab, default_req)``; the legacy lane instead
    receives the replanner's blend/divisor/harmonic tables.  Both variants
    take 15 arguments with the same batched/broadcast split, so one vmap /
    shard_map wrapper serves either.
    """
    lanes = len(lane_idx)
    lanes_pad = _pow2(lanes)
    if cfg.devices > 1 and lanes_pad % cfg.devices:
        lanes_pad = -(-lanes_pad // cfg.devices) * cfg.devices
    idx = np.concatenate([lane_idx, np.arange(lanes_pad - lanes) + (1 << 30)])
    b0 = np.concatenate([b0, np.zeros(lanes_pad - lanes, np.int32)])
    dtype = jnp.dtype(cfg.dtype)
    tau, tau_resc, tau_spec, ev_t, ev_w, ev_up, horizon = _prepare_lanes(
        dist, n_workers, cfg.n, idx, lanes, cfg.jobs_pad, cfg.ev_pad, cfg.resc_cap,
        seed, churn, churn_schedule, pairs, dtype, spec_cap=_spec_cap(cfg.jobs_pad, cfg.spec),
    )
    # (host value, device dtype) of the runner's remaining inputs
    if cfg.scheduler is not None:
        req_tab, b_tab, cancel_tab, default_req = space_tabs
        tail = ((req_tab, jnp.int32), (b_tab, jnp.int32), (cancel_tab, bool),
                (default_req, jnp.int32))
    else:
        div_tab, (h1, h2) = divisor_table(n_workers), harmonic_tables(n_workers)
        div_pad = np.zeros((cfg.n + 1, _pow2(div_tab.shape[1])), div_tab.dtype)
        div_pad[: div_tab.shape[0], : div_tab.shape[1]] = div_tab
        h_pad = np.zeros(cfg.n + 1)
        hp1, hp2 = h_pad.copy(), h_pad.copy()
        hp1[: len(h1)], hp2[: len(h2)] = h1, h2
        tail = ((replan.blend if replan is not None else 0.5, dtype), (div_pad, None),
                (hp1, dtype), (hp2, dtype))
    head = ((b0, jnp.int32), (arrivals_pad, dtype), (speeds, dtype), (n_workers, jnp.int32),
            (n_jobs_real, jnp.int32), (n_tasks, dtype))
    with span("xfer.put"):
        rest = tuple(jnp.asarray(x, t) for x, t in head + tail)
        count("h2d.bytes", sum(a.nbytes for a in rest))
    out = readback(_get_runner(cfg)(tau, tau_resc, tau_spec, ev_t, ev_w, ev_up, *rest))
    count("scan.steps", int(out.pop("n_chunks").max()) * _STEP_CHUNK)
    res = {k: v[:lanes] for k, v in out.items()}
    if "n_speculative" in res:
        count("spec.backups", int(res["n_speculative"].sum()))
    res["churn_horizon"] = horizon[:lanes]  # host-side, inf unless churn sampled
    return res


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------


# float32 resolves consecutive integers only up to 2^24; past half that, a
# single ulp of an absolute timestamp already approaches one second, and
# sub-second queue waits / service times start quantizing away.
_F32_SAFE_TIME = float(2**23)


def _check_arrival_span(arrivals, dtype):
    """Refuse f32 lanes whose absolute arrivals exceed the f32-safe range.

    Unlike the gang kernel in :mod:`repro.cluster.vectorized` (whose scan
    carries only backlog-sized slack and rebuilds absolute times in
    float64), the epoch-scan lanes -- the space-delegated lane in
    particular -- carry *absolute* event times in the lane dtype.  Under
    float32 an arrival near 1e7 s has a ulp around 1 s, so statistics come
    back subtly wrong with no error.  Fail loudly and name the fix instead.
    """
    if dtype != "float32":
        return  # float64 is safe; invalid dtypes get the validation error
    finite = arrivals[np.isfinite(arrivals)]
    span = float(np.abs(finite).max()) if finite.size else 0.0
    if span > _F32_SAFE_TIME:
        raise ValueError(
            f"arrival magnitude {span:.6g} s exceeds the float32-safe range "
            f"(~{_F32_SAFE_TIME:.3g} s): the scan lanes carry absolute times "
            "in the lane dtype, and float32 ulps this large silently quantize "
            'queue waits and service times.  Pass dtype="float64" (requires '
            "jax x64) or rebase arrivals near zero."
        )


def _validate_common(n_workers, sc):
    """Scenario validation + the jax-environment checks, returning the
    bucket-padded speed vector.

    The cross-field rules live in :meth:`repro.cluster.scenario.Scenario.validate`
    (the single validation path shared with the engine and the planner); only
    the process-environment checks -- x64 enabled, visible device count --
    stay here, because they are properties of the jax runtime, not of the
    scenario.
    """
    sc.validate(n_workers=n_workers, backend="jax")
    if sc.dtype == "float64" and not jax.config.jax_enable_x64:
        raise ValueError(
            "dtype='float64' needs jax x64 enabled (jax.config.update('jax_enable_x64', True))"
        )
    if sc.devices > len(jax.devices()):
        raise ValueError(f"devices={sc.devices} but only {len(jax.devices())} jax devices visible")
    speeds = np.ones(n_workers) if sc.speeds is None else np.asarray(sc.speeds, np.float64)
    pad = _bucket_workers(n_workers) - n_workers
    return np.concatenate([speeds, np.ones(pad)])


def _space_tabs(scheduler, workers_per_job, job_plans, n_jobs, jobs_pad, n_workers,
                cancel_default, replan):
    """Resolve space-sharing routing and build the per-job plan tables.

    Returns ``(scheduler_name_or_None, tabs)``: ``None`` means the legacy
    single-gang lane (scheduler ``fifo_gang`` with no per-job plans -- the
    bit-compatible fast path); otherwise the space lane runs with
    ``tabs = (req_tab, b_tab, cancel_tab, default_req)``, zero meaning
    "inherit the engine-wide default" exactly like
    :class:`~repro.cluster.scheduler.JobPlan`'s None fields.
    """
    if scheduler is None:
        scheduler = "fifo_gang"
    if not is_space(scheduler, workers_per_job, job_plans):
        return None, None
    # scheduler / workers_per_job / job_plans / replan-exclusion constraints
    # were already checked by Scenario.validate() (the single validation
    # path) in the public entry points above
    req_tab = np.zeros(jobs_pad, np.int32)
    b_tab = np.zeros(jobs_pad, np.int32)
    cancel_tab = np.full(jobs_pad, bool(cancel_default))
    if job_plans is not None:
        plans = list(job_plans)
        for q in range(n_jobs):
            p = plans[q % len(plans)]
            if p is None:
                continue
            if p.workers is not None:
                req_tab[q] = min(int(p.workers), n_workers)
            if p.n_batches is not None:
                b_tab[q] = int(p.n_batches)
            if p.cancel_redundant is not None:
                cancel_tab[q] = bool(p.cancel_redundant)
    if scheduler == "fifo_gang":
        req_tab[:] = 0  # the gang regime ignores worker requests, like the engine
        default_req = 0
    else:
        default_req = int(workers_per_job) if workers_per_job is not None else 0
    return scheduler, (req_tab, b_tab, cancel_tab, default_req)


def _resolve_churn_pairs(pairs, dist, churn, n_workers, n_batches, n_tasks,
                         size_dependent, speeds, arrivals, n_jobs):
    """Resolve ``churn_pairs_per_worker`` (None = auto-size from the stream).

    The engine's alternating-renewal churn runs forever; the scan lanes
    sample a finite stream of fail/join pairs per worker, after which that
    worker stays up -- so a horizon shorter than the simulated timeline
    silently leaves the engine's law.  Auto-sizing estimates the timeline
    (arrival span plus a serial-gang bound on total service: jobs x mean
    batch duration at the slowest speed) and draws enough pairs to cover
    twice that, floored at the historical default of 8 and capped at 1024
    to bound the event-step budget -- the post-run truncation check warns
    loudly if even the cap fell short.  An explicit integer is honoured
    bit-for-bit (pair count determines the lanes' draw shapes).
    """
    if pairs is not None:
        return int(pairs)
    if churn is None or churn.fail_rate <= 0.0:
        return 8  # no sampled churn: the horizon is never consulted
    # mean service estimate from a fixed-seed host draw: it only sizes an
    # integer, so it must not perturb (or depend on) the caller's seed
    rng = np.random.default_rng(np.random.SeedSequence((0x5A11, 0)))
    mean_tau = float(np.mean(dist.sample_np(rng, (256,))))
    b = int(n_batches) if n_batches else n_workers
    scale = (float(n_tasks) / b) if size_dependent else 1.0
    slow = float(np.min(speeds)) if len(speeds) else 1.0
    span = float(arrivals[-1] - arrivals[0]) if arrivals is not None and len(arrivals) else 0.0
    t_est = span + n_jobs * mean_tau * scale / max(slow, 1e-12)
    period = 1.0 / churn.fail_rate + churn.mean_downtime
    pairs = math.ceil(2.0 * t_est / max(period, 1e-12)) + 4
    return max(8, min(int(pairs), 1024))


def _warn_churn_truncated(truncated, pairs):
    n_hit, n_reps = int(np.sum(truncated)), len(truncated)
    warnings.warn(
        f"sampled churn horizon ended before the simulated timeline in "
        f"{n_hit}/{n_reps} rep(s): past the horizon the lanes' workers stay "
        "up while the Python engine keeps churning, so results diverge from "
        f"the engine's law.  Raise churn_pairs_per_worker (resolved to "
        f"{pairs}; None auto-sizes from the stream) or pass an explicit "
        "churn_schedule, which both backends replay identically.",
        RuntimeWarning,
        stacklevel=3,
    )


def _rep_slices(total: int, rep_chunk: Optional[int]):
    if rep_chunk is None or rep_chunk >= total:
        return [(0, total)]
    if rep_chunk < 1:
        raise ValueError("rep_chunk must be >= 1")
    return [(lo, min(lo + rep_chunk, total)) for lo in range(0, total, rep_chunk)]


def simulate_epochs(
    dist: Optional[ServiceTime] = None,
    n_workers: Optional[int] = None,
    n_batches: Optional[int] = None,
    arrivals=None,
    n_reps: Optional[int] = None,
    *,
    seed: int = 0,
    cancel_redundant=UNSET,
    size_dependent=UNSET,
    n_tasks=UNSET,
    speeds=UNSET,
    churn=UNSET,
    churn_schedule=UNSET,
    churn_pairs_per_worker=UNSET,
    replan=UNSET,
    speculation=UNSET,
    scheduler=UNSET,
    workers_per_job=UNSET,
    job_plans=UNSET,
    dtype=UNSET,
    rep_chunk=UNSET,
    devices=UNSET,
    outputs=UNSET,
    scenario: Optional["Scenario"] = None,
) -> EpochReport:
    """Replay the full engine semantics on the jax epoch scan.

    Statistically identical to ``ClusterEngine(n_workers, n_batches=...,
    cancel_redundant=..., speeds=..., churn=..., controller=...)`` run on the
    same arrival vector (the differential suite in ``tests/test_epoch_scan.py``
    enforces this at 3 sigma, and bit-comparably on shared
    ``churn_schedule`` + degenerate service times).  ``n_batches=None`` means
    full parallelism (B = alive workers at dispatch), like the engine.

    ``scheduler`` / ``workers_per_job`` / ``job_plans`` mirror the engine's
    space-sharing knobs: under ``"packed"`` or ``"balanced"`` jobs run
    concurrently on disjoint worker subsets, each under its own
    :class:`~repro.cluster.scheduler.JobPlan` (``job_plans`` cycles over the
    arrival vector; unset fields inherit ``n_batches`` /
    ``cancel_redundant`` / ``workers_per_job``).  The default ``fifo_gang``
    with no per-job plans keeps the legacy single-gang lane bit-compatibly;
    ``fifo_gang`` *with* per-job plans runs the space lane in gang mode
    (whole-cluster dispatch, per-job B and cancellation).  ``replan`` is
    mutually exclusive with space sharing.

    ``speculation=Speculation(...)`` enables reactive backup replicas on
    either lane: completed sibling-batch durations feed a running lower
    median, and a batch whose youngest live replica lags past ``theta x``
    that median earns one backup at the next heartbeat epoch (one launch per
    epoch, capped at ``max_backups`` per job) -- the exact trigger
    :class:`~repro.cluster.master.ClusterEngine` fires, computed with the
    same float expressions so the differential tests demand bit-equality on
    shared schedules.  Under space sharing the backup takes one of the
    job's own free workers first, else regrants a free unallocated one.  On
    the gang lane a batch whose backup is still running is not re-eligible
    until it resolves (the engine's youngest-replica rule differs only when
    the backup itself lags past the trigger); the space lane follows the
    youngest-replica rule.  Mutually exclusive with ``replan``.

    Each Monte-Carlo rep derives every draw (replica durations, rescue draws,
    and -- when ``churn`` is given -- its own fail/join timeline of
    ``churn_pairs_per_worker`` up/down pairs per worker, after which that
    worker stays up) from ``default_rng(SeedSequence((seed, rep)))``, so results are
    bit-identical under ``rep_chunk`` chunking (bounding device memory for
    rep budgets in the hundreds-to-thousands) and under multi-device
    ``devices`` sharding.  ``churn_pairs_per_worker=None`` (the default)
    auto-sizes the sampled-churn horizon from the stream length; a rep whose
    timeline still outruns its horizon triggers a loud ``RuntimeWarning``
    and is flagged in ``EpochReport.churn_truncated``.  ``dtype="float64"``
    runs the scan lanes in double precision for long-horizon workloads
    (requires jax x64).

    ``outputs="stream"`` (``Scenario.outputs``) folds the per-job records
    into streaming accumulators on device and returns an
    :class:`EpochStreamReport` instead -- O(n_reps) memory for trace-scale
    job counts.  The lane internals and the draw pipeline are identical in
    both modes, so on float64 lanes the streamed statistics equal the host
    fold of the ``outputs="full"`` report bit for bit (the property
    ``tests/test_stream.py`` enforces); the default ``"full"`` path is
    untouched.

    The scenario knobs (dynamics, space sharing, scale) are best passed as
    one validated ``scenario=Scenario(...)``; the loose keyword forms keep
    working behind a :class:`DeprecationWarning` shim.
    """
    with span("entry.simulate_epochs"):
        sc = resolve_scenario(
            scenario,
            {
                "cancel_redundant": cancel_redundant,
                "size_dependent": size_dependent,
                "n_tasks": n_tasks,
                "speeds": speeds,
                "churn": churn,
                "churn_schedule": churn_schedule,
                "churn_pairs_per_worker": churn_pairs_per_worker,
                "replan": replan,
                "speculation": speculation,
                "scheduler": scheduler,
                "workers_per_job": workers_per_job,
                "job_plans": job_plans,
                "dtype": dtype,
                "rep_chunk": rep_chunk,
                "devices": devices,
                "outputs": outputs,
            },
            where="simulate_epochs",
        )
        dist = dist if dist is not None else sc.dist
        n_workers = int(n_workers if n_workers is not None else sc.n_workers)
        n_batches = n_batches if n_batches is not None else sc.n_batches
        if dist is None or arrivals is None or n_reps is None:
            raise ValueError("simulate_epochs needs dist (or scenario.dist), arrivals, and n_reps")
        arrivals = np.asarray(arrivals, dtype=np.float64)
        if arrivals.ndim != 1 or arrivals.size == 0:
            raise ValueError("arrivals must be a non-empty 1-D array")
        if (np.diff(arrivals) < 0).any():
            raise ValueError("arrivals must be sorted (FIFO order)")
        _check_arrival_span(arrivals, sc.dtype)
        if n_batches is not None and not (1 <= int(n_batches) <= n_workers):
            raise ValueError(f"n_batches must lie in [1, {n_workers}] or be None")
        speeds = _validate_common(n_workers, sc)
        cancel_redundant = sc.cancel_redundant
        size_dependent = sc.size_dependent
        churn = sc.churn
        churn_schedule = sc.churn_schedule
        churn_pairs_per_worker = sc.churn_pairs_per_worker
        replan = sc.replan
        speculation = sc.speculation
        scheduler = sc.scheduler_name
        workers_per_job = sc.workers_per_job
        job_plans = sc.job_plans
        dtype = sc.dtype
        rep_chunk = sc.rep_chunk
        devices = sc.devices
        n_tasks = sc.n_tasks if sc.n_tasks is not None else n_workers
        n_jobs = arrivals.size
        churn_pairs_per_worker = _resolve_churn_pairs(
            churn_pairs_per_worker, dist, churn, n_workers, n_batches, n_tasks,
            size_dependent, speeds, arrivals, n_jobs,
        )
        n_pad, jobs_pad, ev_pad, resc_cap, n_chunks = _shapes(
            n_workers, n_jobs, churn, churn_schedule, churn_pairs_per_worker,
            speculation=speculation,
        )
        sched_name, tabs = _space_tabs(
            scheduler, workers_per_job, job_plans, n_jobs, jobs_pad, n_workers,
            cancel_redundant, replan,
        )
        stream_mode = sc.outputs == "stream"
        cfg = _RunnerCfg(
            n_pad, jobs_pad, ev_pad, resc_cap, n_chunks,
            bool(cancel_redundant), bool(size_dependent), replan, dtype, int(devices),
            full_outputs=not stream_mode,
            stream=stream_mode,
            scheduler=sched_name,
            spec=speculation,
        )
        arrivals_pad = np.concatenate([arrivals, np.full(jobs_pad - n_jobs, np.inf)])
        b0_val = 0 if n_batches is None else int(n_batches)
        chunks = []
        for lo, hi in _rep_slices(int(n_reps), rep_chunk):
            chunks.append(
                _run_lanes(
                    dist, cfg, n_workers, np.arange(lo, hi), np.full(hi - lo, b0_val, np.int32),
                    arrivals_pad, n_jobs, seed, speeds, churn, churn_schedule,
                    churn_pairs_per_worker, n_tasks, replan, space_tabs=tabs,
                )
            )
        out = {k: np.concatenate([c[k] for c in chunks], axis=0) for k in chunks[0]}
        sampled = churn is not None and churn.fail_rate > 0.0
        if stream_mode:
            from .stream import StreamStats

            n_unfinished = np.asarray(out["n_unfinished"])
            truncated = None
            if sampled:
                # unfinished jobs have no finish stamp: count them as outrunning
                # the horizon, exactly like the full path's inf finishes do
                truncated = (np.asarray(out["fin_max"], np.float64) > out["churn_horizon"]) | (
                    n_unfinished > 0
                )
                if truncated.any():
                    _warn_churn_truncated(truncated, churn_pairs_per_worker)
            stats = StreamStats(
                count=np.asarray(out["count"]),
                resp_sum=np.asarray(out["resp_sum"]),
                resp_sq=np.asarray(out["resp_sq"]),
                resp_min=np.asarray(out["resp_min"]),
                resp_max=np.asarray(out["resp_max"]),
                comp_sum=np.asarray(out["comp_sum"]),
                busy_sum=np.asarray(out["worker_seconds"]),
                saved_sum=np.asarray(out["cancelled_seconds_saved"]),
                hist=np.asarray(out["hist"]),
            )
            return EpochStreamReport(
                arrivals=arrivals,
                stats=stats,
                n_unfinished=n_unfinished,
                worker_seconds=np.asarray(out["worker_seconds"], np.float64),
                cancelled_seconds_saved=np.asarray(out["cancelled_seconds_saved"], np.float64),
                n_worker_failures=np.asarray(out["n_worker_failures"]),
                n_replicas_rescued=np.asarray(out["n_replicas_rescued"]),
                n_replans=np.asarray(out["n_replans"]),
                n_speculative=(
                    np.asarray(out["n_speculative"]) if "n_speculative" in out else None
                ),
                churn_truncated=truncated,
            )
        br = np.asarray(out["br"])[:, :n_jobs]
        finishes = np.asarray(out["finishes"], np.float64)[:, :n_jobs]
        truncated = None
        if sampled:
            # a rep whose timeline outran its sampled horizon ran its tail
            # churn-free (unfinished jobs at inf count as outrunning it)
            truncated = finishes.max(axis=1) > out["churn_horizon"]
            if truncated.any():
                _warn_churn_truncated(truncated, churn_pairs_per_worker)
        return EpochReport(
            arrivals=arrivals,
            starts=np.asarray(out["starts"], np.float64)[:, :n_jobs],
            finishes=finishes,
            n_batches_used=br >> 16,
            replication_used=br & 0xFFFF,
            worker_seconds=np.asarray(out["worker_seconds"], np.float64),
            cancelled_seconds_saved=np.asarray(out["cancelled_seconds_saved"], np.float64),
            n_worker_failures=np.asarray(out["n_worker_failures"]),
            n_replicas_rescued=np.asarray(out["n_replicas_rescued"]),
            n_replans=np.asarray(out["n_replans"]),
            epoch_times=np.asarray(out["epoch_times"], np.float64),
            n_speculative=(
                np.asarray(out["n_speculative"]) if "n_speculative" in out else None
            ),
            churn_truncated=truncated,
        )


def frontier_job_times_dynamic(
    dist: Optional[ServiceTime] = None,
    n_workers: Optional[int] = None,
    candidates=None,
    n_reps: Optional[int] = None,
    *,
    seed: int = 0,
    n_jobs: Optional[int] = None,
    cancel_redundant=UNSET,
    size_dependent=UNSET,
    n_tasks=UNSET,
    speeds=UNSET,
    churn=UNSET,
    churn_schedule=UNSET,
    churn_pairs_per_worker=UNSET,
    replan=UNSET,
    speculation=UNSET,
    scheduler=UNSET,
    workers_per_job=UNSET,
    job_plans=UNSET,
    dtype=UNSET,
    rep_chunk=UNSET,
    devices=UNSET,
    scenario: Optional["Scenario"] = None,
) -> np.ndarray:
    """Per-candidate job compute times under churn/hetero/replan dynamics.

    ``scheduler`` / ``workers_per_job`` / ``job_plans`` score the candidates
    under space sharing: each stream's jobs run concurrently on disjoint
    worker subsets, the candidate B filling the plan of every job whose
    :class:`~repro.cluster.scheduler.JobPlan` leaves ``n_batches`` unset --
    so a frontier can be swept for one job class while competing classes
    hold fixed heterogeneous plans.

    The dynamic sibling of :func:`repro.cluster.vectorized.frontier_job_times`
    and the workhorse behind ``plan_cluster(backend="jax")`` on dynamic
    scenarios: every candidate B runs serial job streams of ``n_jobs`` jobs
    (matching the Python engine's ``sample_job_times`` structure -- under
    churn, consecutive jobs share a timeline, so samples come in correlated
    streams) across ``ceil(n_reps / n_jobs)`` independent reps.  Returns
    ``(len(candidates), >= n_reps)`` compute times; unfinished jobs are inf
    (callers filter, like ``planner._frontier_stats``).

    ``rep_chunk`` bounds device memory by scoring at most that many streams
    per candidate per device call; ``devices`` shards the (candidate x
    stream) lane grid via ``shard_map``.  Both are bit-identical to the
    single-call single-device result (per-lane ``SeedSequence`` derivation).

    ``Scenario.outputs`` is accepted and ignored: this path *is* the
    planner's per-job-times source, so it always runs the reduced-output
    lanes (no per-event/per-plan buffers) and never the streaming fold.
    """
    with span("entry.frontier_dynamic"):
        sc = resolve_scenario(
            scenario,
            {
                "cancel_redundant": cancel_redundant,
                "size_dependent": size_dependent,
                "n_tasks": n_tasks,
                "speeds": speeds,
                "churn": churn,
                "churn_schedule": churn_schedule,
                "churn_pairs_per_worker": churn_pairs_per_worker,
                "replan": replan,
                "speculation": speculation,
                "scheduler": scheduler,
                "workers_per_job": workers_per_job,
                "job_plans": job_plans,
                "dtype": dtype,
                "rep_chunk": rep_chunk,
                "devices": devices,
            },
            where="frontier_job_times_dynamic",
        )
        dist = dist if dist is not None else sc.dist
        n_workers = int(n_workers if n_workers is not None else sc.n_workers)
        if dist is None or candidates is None or n_reps is None:
            raise ValueError(
                "frontier_job_times_dynamic needs dist (or scenario.dist), candidates, and n_reps"
            )
        bs = np.asarray(list(candidates), dtype=np.int32)
        if bs.size == 0:
            raise ValueError("need at least one candidate B")
        if (bs < 1).any() or (bs > n_workers).any():
            raise ValueError(f"candidates must lie in [1, {n_workers}], got {bs.tolist()}")
        speeds = _validate_common(n_workers, sc)
        cancel_redundant = sc.cancel_redundant
        size_dependent = sc.size_dependent
        churn = sc.churn
        churn_schedule = sc.churn_schedule
        churn_pairs_per_worker = sc.churn_pairs_per_worker
        replan = sc.replan
        speculation = sc.speculation
        scheduler = sc.scheduler_name
        workers_per_job = sc.workers_per_job
        job_plans = sc.job_plans
        dtype = sc.dtype
        rep_chunk = sc.rep_chunk
        devices = sc.devices
        n_tasks = sc.n_tasks if sc.n_tasks is not None else n_workers
        n_jobs = sc.jobs_per_stream if n_jobs is None else n_jobs
        n_jobs = max(1, min(int(n_jobs), int(n_reps)))
        s = math.ceil(n_reps / n_jobs)
        c = len(bs)
        # auto-size against the widest-scale candidate (smallest B): its jobs
        # run longest, so its streams are the ones that outlive short horizons
        churn_pairs_per_worker = _resolve_churn_pairs(
            churn_pairs_per_worker, dist, churn, n_workers, int(bs.min()), n_tasks,
            size_dependent, speeds, None, n_jobs,
        )
        n_pad, jobs_pad, ev_pad, resc_cap, n_chunks = _shapes(
            n_workers, n_jobs, churn, churn_schedule, churn_pairs_per_worker,
            speculation=speculation,
        )
        sched_name, tabs = _space_tabs(
            scheduler, workers_per_job, job_plans, n_jobs, jobs_pad, n_workers,
            cancel_redundant, replan,
        )
        cfg = _RunnerCfg(
            n_pad, jobs_pad, ev_pad, resc_cap, n_chunks,
            bool(cancel_redundant), bool(size_dependent), replan, dtype, int(devices),
            full_outputs=False,  # planning reads starts/finishes only
            scheduler=sched_name,
            spec=speculation,
        )
        arrivals_pad = np.concatenate([np.zeros(n_jobs), np.full(jobs_pad - n_jobs, np.inf)])
        chunks = []
        trunc = np.zeros(0, bool)
        for lo, hi in _rep_slices(s, rep_chunk):
            # lane (ci, rep) has global index ci * s + rep: chunking over reps
            # keeps every lane's SeedSequence identity, hence its draws, unchanged
            lane_idx = (np.arange(c)[:, None] * s + np.arange(lo, hi)[None, :]).ravel()
            b0 = np.repeat(bs, hi - lo)
            out = _run_lanes(
                dist, cfg, n_workers, lane_idx, b0, arrivals_pad, n_jobs, seed,
                speeds, churn, churn_schedule, churn_pairs_per_worker, n_tasks, replan,
                space_tabs=tabs,
            )
            fin = np.asarray(out["finishes"], np.float64)
            start = np.asarray(out["starts"], np.float64)
            if churn is not None and churn.fail_rate > 0.0:
                trunc = np.append(trunc, fin[:, :n_jobs].max(axis=1) > out["churn_horizon"])
            # unfinished jobs (inf start and finish) score inf, not inf - inf
            with np.errstate(invalid="ignore"):
                t = np.where(np.isfinite(fin), fin - start, np.inf)
            chunks.append(t[:, :n_jobs].reshape(c, (hi - lo) * n_jobs))
        if trunc.any():
            _warn_churn_truncated(trunc, churn_pairs_per_worker)
        return np.concatenate(chunks, axis=1)
