"""Device time of the frame_knob_grid kernel per characterization sweep, from the trace (ms)."""

from mezbench import readers


def read(run):
    return readers.per_sweep_ms(run, readers.GRID_MODULE)
