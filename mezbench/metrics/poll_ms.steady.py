"""Mean host time of one poll, measured by the benchmark around each poll (ms)."""

from mezbench import readers


def read(run):
    return readers.mean_poll_ms(run)
