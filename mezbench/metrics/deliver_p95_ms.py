"""95th percentile of due-publish-time to poll-return over every frame shipped in the window (ms)."""

from mezbench import readers


def read(run):
    return readers.p95_ms(getattr(run, 'latencies_s', None))
