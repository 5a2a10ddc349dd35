"""Milliseconds per PCPG iteration: the program's spans ``pcpg`` and
``refine_outer`` over the window, divided by the iterations they ran."""


def read(run):
    its = sum(q.iterations for q in run.requests)
    if not its:
        return None
    return 1e3 * run.span_total("pcpg", "refine_outer") / its
