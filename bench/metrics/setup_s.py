"""Seconds from the process's start to the window's: start-up, the
decomposition and one warm-up request (which compiles, or loads from the
compilation cache, every program the window runs)."""


def read(run):
    return run.setup_s
