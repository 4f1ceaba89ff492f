"""Transfer and readback per plan: self time of the ``xfer.*`` spans in the last call (ms/plan).

The readback's wait for the device is its own span (``wait.device``), so
this is host time spent putting inputs and copying outputs.
"""
from yardstick.program_record import self_s


def read(reduced, units):
    s = self_s(reduced, "xfer")
    return None if s is None else 1e3 * s
