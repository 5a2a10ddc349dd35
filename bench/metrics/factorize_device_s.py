"""Device seconds per cluster of the operations under the named scope
``factorize`` of the prep program (the batched Cholesky factorization)."""


def read(run):
    if run.trace is None or run.mix.cluster != "per_request":
        return None
    t = run.trace.scope_time(include=("factorize",))
    return t / run.trace.requests if t > 0 else None
