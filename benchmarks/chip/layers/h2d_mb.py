"""Bytes put on the device per plan: the ``h2d.bytes`` counter of the last call (MB/plan)."""
from yardstick.program_record import record


def read(reduced, units):
    rec = record(reduced)
    n = rec["counts"].get("h2d.bytes", 0) if rec is not None else 0
    return n / 1e6 if n > 0 else None
