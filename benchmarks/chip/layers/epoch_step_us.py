"""Device time of one serial step of the epoch-scan lanes: the traced plan's ``jit_lane`` time
over its ``scan.steps`` counter (us/step).

The lanes are a serial, latency-bound scan: every step is one action of
every vmapped lane, a few kilobytes of state a lane, so the step's
latency, not a roofline, bounds the runner; there is no roofline share.
A program without the ``scan.steps`` counter reads nothing.
"""
from yardstick.program_record import record

MODULE = "jit_lane"  # the runner's module name in the trace, as epoch_scan_ms reads it


def read(reduced, units):
    rec = record(reduced)
    steps = rec["counts"].get("scan.steps", 0) if rec is not None else 0
    t = reduced.module_time(MODULE)
    if steps <= 0 or t <= 0 or units <= 0:
        return None
    return 1e6 * t / units / steps
