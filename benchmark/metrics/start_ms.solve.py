"""Median self time of the ``lp.start`` span over the window's solves, in
ms: the starting point (or warm start, or checkpoint resume)."""

from benchmark import solve_spans


def read(run):
    return solve_spans.stage_ms(run, "lp.start")
