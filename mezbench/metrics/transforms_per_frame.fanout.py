"""Knob transforms computed (shared frame cache misses) per frame shipped."""

from mezbench import readers


def read(run):
    return readers.transforms_per_frame(run)
