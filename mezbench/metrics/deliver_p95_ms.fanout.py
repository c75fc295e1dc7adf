"""deliver_p95_ms in the fan-out cell, where it is recorded but not judged (ms)."""

from mezbench import readers


def read(run):
    return readers.p95_ms(getattr(run, 'latencies_s', None))
