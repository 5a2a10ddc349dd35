"""Seconds per cluster the program spent building its programs' jaxprs
and MLIR modules: every ``jit:trace`` and ``jit:lower`` span of the
window's requests (each top-level trace once; a trace nested in another is
part of it), summed at whatever depth it was recorded, over the clusters."""

NAMES = ("jit:trace", "jit:lower")


def read(run):
    if run.mix.cluster != "per_request" or not run.requests:
        return None
    spans = [s for q in run.requests for s in q.spans if s[0] in NAMES]
    if not spans:
        return None
    return sum(e - s for _, s, e, _ in spans) / len(run.requests)
