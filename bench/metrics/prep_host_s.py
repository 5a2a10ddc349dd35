"""Host seconds of preprocessing per cluster: the program's span
``preprocess`` minus its span ``prep`` (the compiled prep program's call
and sync), so the symbolic ``init``, the unspanned host packing of the
stacks and ``pack``."""


def read(run):
    if run.mix.cluster != "per_request" or not run.requests:
        return None
    host = run.span_total("preprocess") - run.span_total("prep")
    return host / len(run.requests)
