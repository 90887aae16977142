"""95th percentile of the time the window's requests spent queued: the
engine's ``QUEUED`` spans, submission to admission, in wall time."""

from lib.measure import p


def read(run):
    return p(run.queue_waits_ms, 95)
