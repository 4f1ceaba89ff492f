"""Transfer and readback per job-rep: self time of the ``xfer.*`` spans of the last day (us/job)."""
from yardstick.program_record import per_job_rep


def read(reduced, units):
    s = per_job_rep(reduced, "xfer")
    return None if s is None else 1e6 * s
