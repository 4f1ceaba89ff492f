"""The program's own record of its last call (``repro.spans.last_call()``).

The span and counter metrics read it after a traced run: the last call of
the traced window is one plan, or one replayed day.  A program without the
record (one older than ``repro.spans``) or a run without a traced device
window reads nothing.
"""


def record(reduced):
    """The record, or ``None`` without a traced device window or a record."""
    if reduced.busy_s <= 0:
        return None
    try:
        from repro.spans import last_call
    except ImportError:
        return None
    return last_call()


def self_s(reduced, prefix: str):
    """Self seconds of the spans named ``<prefix>.*`` in the last call, or ``None``."""
    rec = record(reduced)
    if rec is None:
        return None
    got = [own for name, (_, own, _) in rec["spans"].items() if name.startswith(prefix + ".")]
    return sum(got) if got else None


def per_job_rep(reduced, prefix: str):
    """As :func:`self_s`, over the job-reps the call simulated (``stream.job_reps``)."""
    s = self_s(reduced, prefix)
    if s is None:
        return None
    jobs = record(reduced)["counts"].get("stream.job_reps", 0)
    return s / jobs if jobs > 0 else None
