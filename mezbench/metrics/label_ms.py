"""Device time of the labeler per characterization sweep, from the trace (ms)."""

from mezbench import readers


def read(run):
    return readers.per_sweep_ms(run, '_label_group')
