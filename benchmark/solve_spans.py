"""The stage spans of the window's solves, read from the program: every
``ipm.solve`` leaves its ``IPMResult.spans`` (self seconds by stage
span) in ``obs.trace.recent_solves()``, on the perf_counter clock of the
answers' ``t_submit`` and ``t_done``. A solve belongs to the window
when it ran inside one of its answers. A program without that record
gives nothing, and the metrics that read it then give None."""

from __future__ import annotations

import statistics


def window_solves(run) -> list:
    try:
        from distributedlpsolver_tpu.obs.trace import recent_solves
    except ImportError:
        return []
    recs = recent_solves()
    return [r for a in run["answers"] for r in recs
            if a["t_submit"] <= r["t0"] and r["t1"] <= a["t_done"]]


def stage_ms(run, name: str) -> float | None:
    """Median over the window's solves of one span's self time, in ms."""
    v = [r["spans"][name] for r in window_solves(run) if name in r["spans"]]
    return statistics.median(v) * 1e3 if v else None

