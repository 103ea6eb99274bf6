"""Median self time of the ``lp.finalize`` span over the window's solves, in
ms: unscale, recover, postsolve and certificates after the loop."""

from benchmark import solve_spans


def read(run):
    return solve_spans.stage_ms(run, "lp.finalize")
