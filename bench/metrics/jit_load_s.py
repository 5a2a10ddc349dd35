"""Seconds per cluster the program spent in backend compiles: every
``jit:load`` (served by the persistent compilation cache) and
``jit:compile`` (the compiler ran: a recompile inside the window) span of
the window's requests, summed at whatever depth it was recorded, over the
clusters."""

NAMES = ("jit:load", "jit:compile")


def read(run):
    if run.mix.cluster != "per_request" or not run.requests:
        return None
    spans = [s for q in run.requests for s in q.spans if s[0] in NAMES]
    if not spans:
        return None
    return sum(e - s for _, s, e, _ in spans) / len(run.requests)
