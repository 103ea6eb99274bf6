"""Median self time of the ``lp.prepare`` span over the window's solves, in
ms: presolve, the interior form, the warm-cache lookup and Ruiz scaling."""

from benchmark import solve_spans


def read(run):
    return solve_spans.stage_ms(run, "lp.prepare")
