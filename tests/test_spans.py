"""Stage spans (``obs.trace.span``): self-time accounting on each thread,
the Chrome-trace sink, and what a solve reports from them
(``IPMResult.spans``, ``IPMResult.phase_report``)."""

import json
import threading

import pytest

from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.ipm.state import Status
from distributedlpsolver_tpu.models.generators import block_angular_lp
from distributedlpsolver_tpu.obs import trace as obs_trace

STAGES = {"lp.solve", "lp.prepare", "lp.build", "lp.start", "ipm.loop",
          "lp.finalize"}


class _Clock:
    """A perf_counter that moves only when the test says so."""

    def __init__(self):
        self.t = 100.0

    def perf_counter(self):
        return self.t


def test_span_self_time_leaves_out_nested_spans(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(obs_trace, "time", clock)
    with obs_trace.collect_spans() as spans:
        with obs_trace.span("outer"):
            clock.t += 2.0
            with obs_trace.span("inner"):
                clock.t += 3.0
                with obs_trace.span("leaf"):
                    clock.t += 0.5
            with obs_trace.span("inner"):
                clock.t += 1.0
            clock.t += 0.25
    assert spans == {"leaf": 0.5, "inner": 4.0, "outer": 2.25}
    assert sum(spans.values()) == 6.75  # outer's inclusive time
    # Outside the block no table is installed: spans record nothing.
    with obs_trace.span("outer"):
        clock.t += 1.0
    assert spans["outer"] == 2.25


def test_span_tables_of_two_threads_keep_apart():
    both_open = threading.Barrier(2, timeout=30)
    tables = {}

    def worker(name):
        with obs_trace.collect_spans() as spans:
            with obs_trace.span(name):
                both_open.wait()  # each span is open while the other is
                with obs_trace.span(name + ".child"):
                    both_open.wait()
        tables[name] = spans

    threads = [threading.Thread(target=worker, args=(n,)) for n in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert set(tables["a"]) == {"a", "a.child"}
    assert set(tables["b"]) == {"b", "b.child"}
    assert all(v >= 0 for t in tables.values() for v in t.values())


def test_span_emits_chrome_events_only_under_an_installed_tracer(tmp_path):
    tracer = obs_trace.Tracer(str(tmp_path / "trace.json"))
    with obs_trace.span("lp.before"):
        pass
    prev = obs_trace.set_tracer(tracer)
    try:
        with obs_trace.span("lp.traced", phase=1):
            pass
    finally:
        obs_trace.set_tracer(prev)
    with obs_trace.span("lp.after"):
        pass
    events = json.loads(open(tracer.close()).read())["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["lp.traced"]
    assert xs[0]["args"] == {"phase": 1} and xs[0]["dur"] >= 0


def test_block_solve_reports_every_stage_and_each_phase():
    """segment_iters=2 sends the block backend through drive_phase_plan
    on the CPU too: several segments, one phase."""
    p = block_angular_lp(4, 16, 32, 8, seed=6, sparse=False)
    r = solve(p, backend="block", segment_iters=2, verbose=False)
    assert r.status == Status.OPTIMAL
    assert STAGES | {"ipm.phase", "ipm.segment"} <= set(r.spans)
    assert all(v >= 0 for v in r.spans.values())
    rec = obs_trace.recent_solves()[-1]
    assert rec["spans"] == r.spans and rec["phase_report"] is r.phase_report
    assert sum(r.spans.values()) == pytest.approx(rec["t1"] - rec["t0"],
                                                  rel=0.01)
    assert sum(ph["iters"] for ph in r.phase_report) == r.iterations
    assert [ph["mode"] for ph in r.phase_report] == ["f64"]
    assert r.phase_report[0]["segments"] >= 2
    assert r.spans["lp.build"] + r.spans["lp.start"] == pytest.approx(
        r.setup_time, rel=0.02)


def test_auto_detects_inside_build_and_forwards_the_phase_report(
        monkeypatch):
    from distributedlpsolver_tpu.backends import auto

    monkeypatch.setattr(
        auto, "choose_backend_name",
        lambda inf, platform, detect=False: ("block", inf.block_structure))
    p = block_angular_lp(4, 16, 32, 8, seed=6, sparse=False)
    r = solve(p, backend="auto", segment_iters=2, verbose=False)
    assert r.status == Status.OPTIMAL and r.backend == "auto(block)"
    assert STAGES | {"lp.detect"} <= set(r.spans)
    assert sum(ph["iters"] for ph in r.phase_report) == r.iterations


def test_host_loop_solve_has_no_phase_report():
    p = block_angular_lp(2, 8, 16, 4, seed=1, sparse=False)
    r = solve(p, backend="cpu", fused_loop=False, verbose=False)
    assert r.status == Status.OPTIMAL and r.phase_report is None
    assert STAGES <= set(r.spans)


def test_a_reused_backend_reports_no_phases_of_an_earlier_solve():
    from distributedlpsolver_tpu.backends.block_angular import (
        BlockAngularBackend,
    )

    be = BlockAngularBackend()
    p = block_angular_lp(4, 16, 32, 8, seed=6, sparse=False)
    first = solve(p, backend=be, segment_iters=2, verbose=False)
    assert first.phase_report
    # The CPU's default segment setting runs one fused program: no phases.
    again = solve(p, backend=be, verbose=False)
    assert again.status == Status.OPTIMAL and again.phase_report is None


def test_annotate_adds_args_to_the_innermost_open_span_of_that_name(
        tmp_path):
    tracer = obs_trace.Tracer(str(tmp_path / "trace.json"))
    prev = obs_trace.set_tracer(tracer)
    try:
        with obs_trace.span("lp.build"):
            with obs_trace.span("lp.build", depth=2):
                with obs_trace.span("lp.detect"):
                    obs_trace.annotate("lp.build", assembly="pairs")
            obs_trace.annotate("lp.start", ignored=True)  # none open
    finally:
        obs_trace.set_tracer(prev)
    obs_trace.annotate("lp.build", after=True)  # nothing open: no-op
    events = json.loads(open(tracer.close()).read())["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    assert [(e["name"], e.get("args")) for e in xs] == [
        ("lp.detect", None),
        ("lp.build", {"depth": 2, "assembly": "pairs"}),
        ("lp.build", None),
    ]
