"""What the traffic entries share. A mix is a data file
(``traffic/<mix>.json``) of parameters; its ``entry`` names the driver,
``entries/<entry>.py``, which reads the rest (see each entry's
docstring). An entry module defines ``Entry(cell, seed)`` with:

- ``setup()``: builds the window's LPs and solves warm-up LPs at the
  window's exact solve options, so every program the window runs
  compiles or loads from the cache here; returns notes;
- ``window(seconds, profiler)``: drives the system from window start for
  ``seconds`` (what is in flight at the close runs to its end); returns
  (answers, window start, notes), one answer per LP with its ``k``,
  ``status``, ``x``, ``y``, ``t_submit`` and ``t_done``;
- ``answers(n=None)``: the answers to LPs k = 0 … n−1 (default: the
  window's pool) through the entry's own calls at ``opts``, the solve
  options, on which the control lays its own (``control.py``);
- ``close()``: frees what set-up built.

Every LP is ``generator.build(params, seed, k)``: the window's LPs have
k = 0, 1, 2, ... and the set-up's warm-up LPs k ≥ ``WARM_BASE``, so a
seed fixes every input and no warm-up LP is one of the window's.
``trace_seconds`` in a mix bounds the part of the window that a
``--trace 1`` run profiles, its first seconds (the profiler is stopped
by a timer, in the middle of a solve if need be).
"""

from __future__ import annotations

import threading
import time

WARM_BASE = 1 << 40


def _span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """Start and stop ``jax.profiler`` around the traced part of a
    window, inside a ``bench.window`` host span. ``start(seconds)`` also
    stops it from a timer thread that many seconds later, in the middle
    of a solve if need be."""

    def __init__(self, trace_dir):
        self.dir = trace_dir
        self.on = False
        self.seconds = 0.0
        self._lock = threading.Lock()
        self._timer = None

    def start(self, seconds=None):
        import jax

        if self.dir and not self.on:
            # No Python tracer: its events made a 20 s trace of a pds10
            # solve 318 MB and its reduction minutes long.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.span = _span("bench.window")
            self.span.__enter__()
            self.t0 = time.perf_counter()
            self.on = True
            if seconds:
                self._timer = threading.Timer(seconds, self.stop)
                self._timer.start()

    def stop(self):
        import jax

        with self._lock:
            if self.on:
                self.seconds = time.perf_counter() - self.t0
                self.span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                self.on = False

    def join(self):
        if self._timer is not None:
            self._timer.cancel()
            self._timer.join()
