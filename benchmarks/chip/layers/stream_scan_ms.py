"""Device time of the stream kernel (``vectorized._stream_slab``) per plan (ms/plan)."""

MODULE = "_stream_slab"  # matched against the XLA module names the trace prints


def read(reduced, units):
    t = reduced.module_time(MODULE)
    return 1e3 * t / units if t > 0 and units > 0 else None
