"""Device milliseconds per load case of the operations under the named
scope ``feti:project``: the projector P with its coarse solves, as the
PCPG loops apply it."""


def read(run):
    if run.trace is None or run.mix.cluster != "once":
        return None
    t = run.trace.scope_time(("feti:project",))
    return 1e3 * t / run.trace.requests if t > 0 else None
