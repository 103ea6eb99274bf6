"""Median self time of the ``lp.detect`` span over the window's solves, in
ms: auto's two-stage and block detection and its tensor-size veto."""

from benchmark import solve_spans


def read(run):
    return solve_spans.stage_ms(run, "lp.detect")
