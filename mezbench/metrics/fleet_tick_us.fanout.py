"""Device time of the fused fleet tick per dispatch, from the trace (us).
``FleetController`` jits a lambda around ``fused_fleet_tick``, so its
module is ``jit__lambda``: the only jitted lambda on the serving path."""

from mezbench import readers


def read(run):
    return readers.per_dispatch_us(run, "jit__lambda")
