"""Seconds per cluster solved: the window's whole time over the requests
in it, where each request builds, preprocesses and solves a new cluster
and returns its global solution."""


def read(run):
    if run.mix.cluster != "per_request" or not run.requests:
        return None
    return run.window_s / len(run.requests)
