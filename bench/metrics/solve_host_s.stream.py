"""Seconds per load case of the solve outside the PCPG loops: the
program's span ``solve`` minus its spans ``pcpg`` and ``refine_outer``
(the right-hand side's set-up, the refinement's outer residuals and
syncs, and the recovery of the global solution)."""


def read(run):
    if not run.cases:
        return None
    rest = run.span_total("solve") - run.span_total("pcpg", "refine_outer")
    return rest / run.cases
