"""Seconds per full camera characterization: whole sweeps back to back."""


def read(run):
    return sum(run.sweeps_s) / len(run.sweeps_s) if getattr(run, 'sweeps_s', None) else None
