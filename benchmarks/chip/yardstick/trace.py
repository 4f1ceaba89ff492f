"""Reduce a JAX profiler trace to device busy time, per-module device time and idle gaps.

Busy time is the union of the intervals in which an XLA module runs on a
device, inside the traced window; the window runs from the start of the
first harness span to the end of the last.  Each idle gap inside it is
named by what the host's harness thread was doing at the gap's middle: the
innermost host event there, or the harness span itself when nothing finer
was recorded (host Python, such as drawing inputs with numpy).
"""
from __future__ import annotations

import dataclasses
import pathlib
import re

SPAN = "bench.step"
_ID_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # averaged over the devices that ran anything
    module_s: dict  # module name -> device seconds inside the window
    idle_by_host: dict  # host activity -> idle device seconds
    n_spans: int

    def module_time(self, fragment: str) -> float:
        """Device seconds of every module whose name contains ``fragment``."""
        return sum(s for name, s in self.module_s.items() if fragment in name)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.module_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(trace_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def reduce_planes(planes) -> Reduced:
    """``planes``: objects with ``name`` and ``lines``; lines with ``name`` and ``events``;
    events with ``name``, ``start_ns`` and ``duration_ns`` (``jax.profiler.ProfileData``)."""
    host_line = None
    device_lines = []
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                if any(e.name == SPAN for e in ln.events):
                    host_line = _events(ln)
        elif plane.name.startswith("/device:"):
            ln = lines.get("XLA Modules") or lines.get("XLA Ops")
            if ln is not None:
                device_lines.append(_events(ln))
    if host_line is None:
        raise ValueError(f"no host span {SPAN!r} in the trace")
    spans = [(s, e) for n, s, e in host_line if n == SPAN]
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    module_ns: dict = {}
    busy_ns = []
    first_busy = None
    for events in device_lines:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]
        if not inside:
            continue
        for n, s, e in inside:
            key = _ID_SUFFIX.sub("", n)
            module_ns[key] = module_ns.get(key, 0.0) + (e - s)
        merged = _union([(s, e) for _, s, e in inside])
        busy_ns.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
    idle: dict = {}
    if first_busy is not None:
        edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
        host = [(n, s, e) for n, s, e in host_line if e > lo and s < hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = 0.5 * (gs + ge)
            covering = [(e - s, n) for n, s, e in host if s <= mid <= e]
            label = min(covering)[1] if covering else "outside host spans"
            idle[label] = idle.get(label, 0.0) + (ge - gs) * 1e-9
    return Reduced(
        window_s=(hi - lo) * 1e-9,
        busy_s=(sum(busy_ns) / len(busy_ns) * 1e-9) if busy_ns else 0.0,
        module_s={k: v * 1e-9 for k, v in module_ns.items()},
        idle_by_host=idle,
        n_spans=len(spans),
    )


def reduce_trace(trace_dir) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(find_xplane(trace_dir))).planes)
