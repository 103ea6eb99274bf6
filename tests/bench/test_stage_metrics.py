"""The readers of the stage metrics (``benchmark/solve_spans.py`` and the
metric files that use it), on hand-made solve records."""

import os

import pytest

from benchmark import spec
from distributedlpsolver_tpu.obs import trace as obs_trace


def _metric(name):
    return spec.load_module(
        os.path.join(spec.ROOT, "benchmark", "metrics", name + ".py")).read


def _answer(t0, t1):
    return {"k": 0, "t_submit": t0, "t_done": t1, "status": "optimal"}


def _solve(t0, t1, spans):
    return {"t0": t0, "t1": t1, "spans": spans, "phase_report": None}


# The warm-up solve (before the window) must not count; the window's two
# solves run inside its two answers.
SOLVES = [
    _solve(0.5, 9.0, {"lp.prepare": 9.0, "lp.detect": 9.0, "lp.build": 9.0,
                      "lp.start": 9.0, "lp.finalize": 9.0}),
    _solve(10.1, 19.9, {"lp.prepare": 0.2, "lp.detect": 0.5, "lp.build": 3.0,
                        "lp.start": 0.8, "lp.finalize": 0.1}),
    _solve(20.1, 29.9, {"lp.prepare": 0.4, "lp.detect": 0.7, "lp.build": 3.4,
                        "lp.start": 1.0, "lp.finalize": 0.3}),
]
RUN = {"answers": [_answer(10.0, 20.0), _answer(20.0, 30.0)]}


@pytest.mark.parametrize("name,want", [
    ("prepare_ms.solve", 300.0),
    ("detect_ms.solve", 600.0),
    ("build_ms.solve", 3200.0),
    ("start_ms.solve", 900.0),
    ("finalize_ms.solve", 200.0),
])
def test_stage_metric_arithmetic(monkeypatch, name, want):
    monkeypatch.setattr(obs_trace, "recent_solves", lambda: SOLVES)
    assert _metric(name)(RUN) == pytest.approx(want)


@pytest.mark.parametrize("name", ["prepare_ms.solve", "detect_ms.solve"])
def test_stage_metrics_read_nothing_where_nothing_was_recorded(
        monkeypatch, name):
    # A solve with a backend named outright: no detection span.
    monkeypatch.setattr(obs_trace, "recent_solves", lambda: [
        _solve(10.1, 19.9, {"lp.prepare": 0.2, "ipm.loop": 5.0})])
    assert (_metric(name)(RUN) is None) == (name == "detect_ms.solve")
    # A program with no such record (an older checkout): no value, no error.
    monkeypatch.delattr(obs_trace, "recent_solves")
    assert _metric(name)(RUN) is None

