"""Set-up seconds: process start to the window, compilation and the characterization included."""


def read(run):
    return run.setup_s
