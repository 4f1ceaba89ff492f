"""Host draw prep per plan: self time of the ``draws.*`` spans in the last call (ms/plan)."""
from yardstick.program_record import self_s


def read(reduced, units):
    s = self_s(reduced, "draws")
    return None if s is None else 1e3 * s
