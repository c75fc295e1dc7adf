"""Least time of a sweep's grid calls (memory or compute bound) over their device time (%)."""

from mezbench import readers


def read(run):
    return readers.grid_roofline(run)
