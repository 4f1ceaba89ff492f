"""Entry-point host work per plan: self time of the ``entry.*`` spans in the last call (ms/plan).

What the entry points do outside their draws, transfers and device waits:
scenario resolution, shapes, tables, dispatch, quantiles and the pick.
"""
from yardstick.program_record import self_s


def read(reduced, units):
    s = self_s(reduced, "entry")
    return None if s is None else 1e3 * s
