"""Device milliseconds per load case of the operations under the named
scope ``feti:dual_apply``: the dual operator F, as the PCPG loops apply it
and as the solver applies it for the refinement's residuals and the
recovery."""


def read(run):
    if run.trace is None or run.mix.cluster != "once":
        return None
    t = run.trace.scope_time(("feti:dual_apply",))
    return 1e3 * t / run.trace.requests if t > 0 else None
