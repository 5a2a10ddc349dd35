"""Device seconds per cluster of the explicit assembly: the operations
under the named scopes ``stage:dual`` or ``stage:dirichlet`` of the prep
program and not under ``factorize``."""


def read(run):
    if run.trace is None or run.mix.cluster != "per_request":
        return None
    t = run.trace.scope_time(include=("stage:dual", "stage:dirichlet"),
                             exclude=("factorize",))
    return t / run.trace.requests if t > 0 else None
