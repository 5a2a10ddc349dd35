"""PCPG iterations per load case (``FetiSolution.iterations``: the inner
solve's and every refinement outer's), mean over the window's cases."""


def read(run):
    its = [q.iterations for q in run.requests]
    return sum(its) / len(its) if its else None
