"""Span tracer emitting Chrome-trace-format JSON (Perfetto-loadable).

One :class:`Tracer` accumulates events in memory (bounded) and writes a
``{"traceEvents": [...]}`` JSON object at :meth:`close`. Event phases
used (the Trace Event Format's stable subset):

- ``X`` complete spans — one lane per pipeline thread (the thread id is
  the OS thread ident; an ``M`` metadata event names each lane the
  first time it emits).
- ``b``/``e`` async spans keyed by ``(cat, id)`` — the cross-thread
  request track: ``request <id>`` begins on the submit thread, its
  nested ``queue``/``pack``/``solve`` phases begin and end on whichever
  pipeline thread handles them, and the track ends where the result is
  finished. Perfetto renders each (cat, id) pair as one connected track
  regardless of which threads emitted the events.
- ``i`` instant events — supervisor faults, reshards, ladder swaps,
  admission rejections.

Timestamps are microseconds on the ``time.perf_counter`` clock (the
same monotonic clock every JSONL record's ``t_mono`` stamp uses, so a
trace and a JSONL stream from one process line up exactly).

Like the metrics registry, the module default is :data:`NULL_TRACER`,
whose methods are no-ops — instrumentation sites call unconditionally
and the disabled path allocates nothing. The real tracer takes one lock
per event append; it is never on the device path.

:func:`span` is the one primitive for the stages of a solve. It writes
each stage onto three sinks at once: the host plane of an active
``jax.profiler`` trace (the device's clock), the installed tracer's
``X`` events, and the calling thread's :func:`collect_spans` table of
self seconds, which ``ipm.solve`` turns into ``IPMResult.spans``.
"""

from __future__ import annotations

import collections
import contextlib
import json
import threading
import time
from typing import Iterator, Optional

# Bound on buffered events: a runaway loop must not grow host memory
# without bound. 1M events ≈ a few hundred MB of JSON — far above any
# probe run; on overflow the tracer drops new events and records that it
# did in the file's metadata.
MAX_EVENTS = 1_000_000


def _now_us() -> float:
    return time.perf_counter() * 1e6


class Tracer:
    """Collects Chrome-trace events; ``close()`` writes the JSON file."""

    enabled = True

    def __init__(self, path: str, process_name: str = "distributedlpsolver"):
        self.path = path
        self._lock = threading.Lock()
        self._events: list = []  # guarded-by: _lock
        self._named_threads: set = set()  # guarded-by: _lock
        self._dropped = 0  # guarded-by: _lock
        self._closed = False  # guarded-by: _lock
        self._events.append(
            {
                "ph": "M", "name": "process_name", "pid": 1, "tid": 0,
                "args": {"name": process_name},
            }
        )

    # -- internals -------------------------------------------------------

    def _emit(self, ev: dict) -> None:
        tid = ev.setdefault("tid", threading.get_ident())
        ev.setdefault("pid", 1)
        with self._lock:
            if self._closed:
                return
            if len(self._events) >= MAX_EVENTS:
                self._dropped += 1
                return
            if tid not in self._named_threads:
                self._named_threads.add(tid)
                self._events.append(
                    {
                        "ph": "M", "name": "thread_name", "pid": 1,
                        "tid": tid,
                        "args": {"name": threading.current_thread().name},
                    }
                )
            self._events.append(ev)

    # -- synchronous spans (thread lanes) --------------------------------

    @contextlib.contextmanager
    def span(
        self, name: str, cat: str = "", args: Optional[dict] = None
    ) -> Iterator[None]:
        """``X`` complete span on the calling thread's lane."""
        t0 = _now_us()
        try:
            yield
        finally:
            self._emit(
                {
                    "ph": "X", "name": name, "cat": cat or "span",
                    "ts": t0, "dur": _now_us() - t0,
                    **({"args": args} if args else {}),
                }
            )

    def complete(
        self,
        name: str,
        dur_s: float,
        cat: str = "",
        args: Optional[dict] = None,
        end_us: Optional[float] = None,
    ) -> None:
        """``X`` span for an interval that already happened (the caller
        measured ``dur_s`` itself and is reporting after the fact)."""
        end = _now_us() if end_us is None else end_us
        self._emit(
            {
                "ph": "X", "name": name, "cat": cat or "span",
                "ts": end - dur_s * 1e6, "dur": dur_s * 1e6,
                **({"args": args} if args else {}),
            }
        )

    # -- async request tracks (cross-thread) -----------------------------

    def async_begin(
        self, name: str, track: int, cat: str = "request",
        args: Optional[dict] = None,
    ) -> None:
        self._emit(
            {
                "ph": "b", "name": name, "cat": cat, "id": track,
                "ts": _now_us(), **({"args": args} if args else {}),
            }
        )

    def async_end(
        self, name: str, track: int, cat: str = "request",
        args: Optional[dict] = None,
    ) -> None:
        self._emit(
            {
                "ph": "e", "name": name, "cat": cat, "id": track,
                "ts": _now_us(), **({"args": args} if args else {}),
            }
        )

    # -- instants --------------------------------------------------------

    def instant(self, name: str, args: Optional[dict] = None,
                cat: str = "event") -> None:
        self._emit(
            {
                "ph": "i", "name": name, "cat": cat, "ts": _now_us(),
                "s": "p",  # process-scoped marker line
                **({"args": args} if args else {}),
            }
        )

    # -- lifecycle -------------------------------------------------------

    def event_count(self) -> int:
        with self._lock:
            return len(self._events)

    def close(self) -> Optional[str]:
        """Write the trace JSON; returns the path (idempotent — later
        calls rewrite with whatever accumulated since, so a service can
        flush at shutdown while the CLI flushes again at exit)."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        payload = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "clock": "perf_counter_us",
                **({"dropped_events": dropped} if dropped else {}),
            },
        }
        with open(self.path, "w") as fh:
            json.dump(payload, fh)
            fh.write("\n")
        return self.path


class _NullTracer:
    """Disabled tracer: same surface, every method a no-op (the span
    context manager is a shared reusable null context)."""

    enabled = False
    path = None

    __slots__ = ()

    def span(self, name, cat="", args=None):
        return _NULL_CONTEXT

    def complete(self, name, dur_s, cat="", args=None, end_us=None):
        pass

    def async_begin(self, name, track, cat="request", args=None):
        pass

    def async_end(self, name, track, cat="request", args=None):
        pass

    def instant(self, name, args=None, cat="event"):
        pass

    def event_count(self) -> int:
        return 0

    def close(self):
        return None


_NULL_CONTEXT = contextlib.nullcontext()
NULL_TRACER = _NullTracer()

from distributedlpsolver_tpu.obs import DefaultSlot  # noqa: E402

_DEFAULT = DefaultSlot(NULL_TRACER)


def get_tracer():
    return _DEFAULT.get()


def set_tracer(tracer) -> object:
    """Install ``tracer`` as the module default (None restores the no-op
    tracer); returns the previous default for scoped restore."""
    return _DEFAULT.set(tracer)


# --------------------------------------------------------------------------
# Stage spans: profiler annotation + tracer event + per-solve self time
# --------------------------------------------------------------------------

_tls = threading.local()


class _SpanTable:
    """Self seconds by span name for one thread's spans. ``_open`` holds,
    for each span still open, the seconds its closed children took."""

    __slots__ = ("seconds", "_open")

    def __init__(self):
        self.seconds: dict = {}
        self._open: list = []

    def open(self) -> None:
        self._open.append(0.0)

    def close(self, name: str, dur: float) -> None:
        child = self._open.pop()
        self.seconds[name] = self.seconds.get(name, 0.0) + dur - child
        if self._open:
            self._open[-1] += dur


@contextlib.contextmanager
def collect_spans() -> Iterator[dict]:
    """Install a span table on the calling thread for the block; yields
    its ``{name: self seconds}`` dict. Spans on other threads never land
    in it, so concurrent solves keep apart. An inner ``collect_spans``
    shadows the outer one until it exits."""
    prev = getattr(_tls, "table", None)
    table = _tls.table = _SpanTable()
    try:
        yield table.seconds
    finally:
        _tls.table = prev


@contextlib.contextmanager
def span(name: str, **args) -> Iterator[None]:
    """One stage of work on the calling thread: a
    ``jax.profiler.TraceAnnotation`` (``args`` become its stats), an
    ``X`` event on the installed tracer when it is enabled, and the
    span's self time (its duration less its nested spans') in the
    thread's :func:`collect_spans` table, if one is installed."""
    import jax  # lazily: ipm.core keeps jax out of its import path

    table = getattr(_tls, "table", None)
    stack = _tls.__dict__.setdefault("open_spans", [])
    with jax.profiler.TraceAnnotation(name, **args) as ann, get_tracer().span(
        name, cat="stage", args=args
    ):
        stack.append((name, ann, args))
        try:
            if table is None:
                yield
                return
            table.open()
            t0 = time.perf_counter()
            try:
                yield
            finally:
                table.close(name, time.perf_counter() - t0)
        finally:
            stack.pop()


def annotate(name: str, **args) -> None:
    """Add ``args`` to the innermost open :func:`span` called ``name`` on
    the calling thread — for what is known only once the span's work has
    run (the profiler event's stats and the tracer event's args both get
    them). No such span open: nothing happens."""
    for open_name, ann, open_args in reversed(
        getattr(_tls, "open_spans", ())
    ):
        if open_name == name:
            ann.set_metadata(**args)
            open_args.update(args)
            return


# The span tables and phase reports of this process's last solves, for a
# caller that did not keep the IPMResult (a benchmark reading stage and
# phase times after its window): one dict per solve, ``{"t0", "t1",
# "spans", "phase_report", "counters"}``, t0/t1 on the perf_counter
# clock, ``counters`` None where the solve counts nothing (a batched
# solve counts its chunks, compactions, iterations and clean-ups).
# Bounded; appends are atomic.
_RECENT_SOLVES: collections.deque = collections.deque(maxlen=64)


def record_solve(t0: float, t1: float, spans: dict, phase_report,
                 counters: Optional[dict] = None) -> None:
    _RECENT_SOLVES.append({"t0": t0, "t1": t1, "spans": dict(spans),
                           "phase_report": phase_report,
                           "counters": None if counters is None
                           else dict(counters)})


def recent_solves() -> list:
    """The last solves' span records, oldest first (see ``record_solve``)."""
    return list(_RECENT_SOLVES)
