"""Device time of the epoch-scan lanes (the ``epoch_scan._get_runner`` program) per plan (ms/plan)."""

MODULE = "jit_lane"  # the runner's module name in the trace: jit of the vmapped lane


def read(reduced, units):
    t = reduced.module_time(MODULE)
    return 1e3 * t / units if t > 0 and units > 0 else None
