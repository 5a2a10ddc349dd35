"""Programs traced per cluster: the ``jit:trace`` spans (top-level traces
only) of the window's requests, over the clusters."""


def read(run):
    if run.mix.cluster != "per_request" or not run.requests:
        return None
    n = sum(s[0] == "jit:trace" for q in run.requests for s in q.spans)
    return n / len(run.requests) if n else None
