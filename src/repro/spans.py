"""Host spans and counters: where a plan's or a replay's host time went.

Not :mod:`repro.cluster.runtime.trace`, which is the live runtime's
replayable event log.  This module names the host work of the jax entry
points (draws, transfers, readbacks, the entry points' own bookkeeping) so
that a profiler trace shows it beside the device's work, on one clock, and
so that an operator can read it back without a profiler.

* :func:`span` opens a ``jax.profiler.TraceAnnotation`` (a no-op unless a
  profiler session is active) and adds its wall time to the calling
  thread's record of the current outermost call: per name, the total, the
  self time (the total less the time its child spans cover) and the number
  of entries.
* :func:`count` adds to a counter of the same record (``h2d.bytes``: the
  device bytes put by ``xfer.put`` spans; ``d2h.arrays``: the device arrays
  :func:`readback` fetched; ``stream.job_reps``).
* :func:`readback` is the package's one device-to-host path: one wait, then
  one batched get of every array of a pytree.
* :func:`last_call` returns the record of the last outermost call that
  ended on this thread.  An operator reads it after a plan or a replay to
  see where its host time went and how many bytes it put on the device.

Span names are fixed strings: ``entry.*`` (the public entry points),
``draws.*`` (host numpy draws), ``xfer.put`` / ``xfer.get`` (host-device
transfers; ``xfer.get`` holds ``wait.device``, so its self time is the copy
alone).  A span never sits inside a function jax traces, nor in a per-rep
or per-lane loop.
"""
from __future__ import annotations

import threading
import time

import jax
from jax.profiler import TraceAnnotation

__all__ = ["span", "count", "readback", "last_call"]

_local = threading.local()


class span:
    """Time the ``with`` block under ``name``: the profiler's host line and this thread's record."""

    __slots__ = ("name", "_note", "_t0", "_inner")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _local.__dict__.setdefault("stack", [])
        if not stack:  # an outermost call starts a fresh record
            _local.rec = {"spans": {}, "counts": {}}
        self._inner = [0]  # nanoseconds covered by child spans
        stack.append(self._inner)
        self._note = TraceAnnotation(self.name)
        self._note.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self._t0
        self._note.__exit__(*exc)
        stack, rec = _local.stack, _local.rec
        stack.pop()
        if stack:
            stack[-1][0] += dt
        total, own, n = rec["spans"].get(self.name, (0, 0, 0))
        rec["spans"][self.name] = (total + dt, own + dt - self._inner[0], n + 1)
        if not stack:  # also when the block raised: the record is published
            _local.last = rec
        return False


def count(name: str, n) -> None:
    """Add ``n`` to counter ``name`` of the current call; outside any span, nothing."""
    if getattr(_local, "stack", None):
        counts = _local.rec["counts"]
        counts[name] = counts.get(name, 0) + n


def readback(tree):
    """``tree`` with every device array copied to a host numpy array, in one batched get.

    One ``xfer.get`` span holds one ``wait.device`` span (the wait for the
    device) and the copies, which ``jax.device_get`` starts together before it
    converts any of them; ``d2h.arrays`` counts the arrays fetched.  A caller
    with several independent device calls dispatches them all and reads them
    back here in one pytree.
    """
    with span("xfer.get"):
        with span("wait.device"):
            jax.block_until_ready(tree)
        count("d2h.arrays", len(jax.tree.leaves(tree)))
        return jax.device_get(tree)


def last_call() -> dict | None:
    """``{"spans": {name: (total_s, self_s, entries)}, "counts": {name: n}}`` of the
    last outermost call that ended on this thread, or ``None`` before the first."""
    rec = getattr(_local, "last", None)
    if rec is None:
        return None
    return {
        "spans": {k: (t * 1e-9, s * 1e-9, n) for k, (t, s, n) in rec["spans"].items()},
        "counts": dict(rec["counts"]),
    }
