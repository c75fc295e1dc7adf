"""Frames shipped with a payload, over all subscriptions, per second of window."""


def read(run):
    return run.delivered / run.window_s if getattr(run, 'delivered', 0) else None
