"""The window's batched calls, read from the program: every
``backends.batched.solve_batched`` call leaves one record in
``obs.trace.recent_solves()``, on the perf_counter clock of the answers'
``t_submit`` and ``t_done``: the self seconds of its spans (``batch.*``,
and the ``ipm.segment`` spans of its segmented drive), each summed over
the call's chunks, and its ``counters``. A call belongs to the window
when it ran inside one of its answers. The solo clean-up's own solves
leave records with no ``batch.loop`` and are not calls. A program
without such records gives nothing, and the metrics that read them then
give None."""

from __future__ import annotations

import statistics

from benchmark import solve_spans


def window_calls(run) -> list:
    """The window's batched-call records, once each (every LP of a call
    is an answer of its own)."""
    seen, out = set(), []
    for r in solve_spans.window_solves(run):
        if "batch.loop" in r["spans"] and id(r) not in seen:
            seen.add(id(r))
            out.append(r)
    return out


def median(run, read) -> float | None:
    """Median over the window's calls of ``read(record)``, leaving out
    the calls where it gives None."""
    v = [x for x in map(read, window_calls(run)) if x is not None]
    return float(statistics.median(v)) if v else None


def self_ms(run, *names) -> float | None:
    """Median over the window's calls of the spans' summed self time, in
    ms."""
    return median(run, lambda r: 1e3 * sum(r["spans"].get(n, 0.0)
                                           for n in names))


def counter(record, name):
    """A counter of a call's record; None where the program counts none."""
    return (record.get("counters") or {}).get(name)
