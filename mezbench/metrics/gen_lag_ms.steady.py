"""95th percentile of publish time minus due time: how late the load generator ran (ms)."""

from mezbench import readers


def read(run):
    return readers.p95_ms(getattr(run, 'lags_s', None))
