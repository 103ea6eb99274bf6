"""Median self time of the ``lp.build`` span over the window's solves, in
ms: the chosen backend's set-up less detection (block: build_tensors,
problem data, transfers to the device)."""

from benchmark import solve_spans


def read(run):
    return solve_spans.stage_ms(run, "lp.build")
