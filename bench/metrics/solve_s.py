"""Seconds per load case: the window's whole time over the load cases
solved in it, against one preprocessed cluster."""


def read(run):
    if run.mix.cluster != "once" or not run.cases:
        return None
    return run.window_s / run.cases
