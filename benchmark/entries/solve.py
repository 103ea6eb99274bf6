"""``"entry": "solve"``: back-to-back ``ipm.solve`` calls on the calling
thread, one LP at a time. Mix keys:

- ``pool``: LPs built in set-up, as many as the window solves; no
  instance repeats in a window while the pool lasts;
- ``warm``: LPs solved in set-up to compile every program the window
  runs (same sizes and solve options as the window's, other instances);
- ``trace_seconds``: see ``load.py``.

The configuration's ``solve`` options are ``ipm.solve``'s keywords.
"""

from __future__ import annotations

import time

from benchmark.load import WARM_BASE, _span


class Entry:
    """``ipm.solve`` on this thread; the window's LPs are built in set-up."""

    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed
        self.params = cell.config["params"]
        self.opts = dict(cell.config.get("solve", {}))

    def build(self, k):
        return self.cell.generator.build(self.params, self.seed, k)

    def setup(self) -> dict:
        t = self.cell.traffic
        routes = []
        for i in range(int(t.get("warm", 1))):
            routes.append(self._solve(self.build(WARM_BASE + i), -1)["backend"])
        self.pool = [self.build(k) for k in range(int(t.get("pool", 1)))]
        return {"warm_routes": routes, "pool": len(self.pool)}

    def _solve(self, problem, k):
        from distributedlpsolver_tpu.ipm import solve

        t0 = time.perf_counter()
        with _span("bench.solve"):
            r = solve(problem, **self.opts)
        t1 = time.perf_counter()
        return {
            "k": k, "t_submit": t0, "t_done": t1, "status": r.status.value,
            "iterations": int(r.iterations), "objective": float(r.objective),
            "x": r.x, "y": r.y, "backend": r.backend,
            "solve_time": float(r.solve_time),
            "setup_time": float(r.setup_time),
        }

    def window(self, seconds, profiler) -> tuple[list, float, dict]:
        """Back-to-back solves from window start; the one in flight at
        the close runs to its end. Returns (answers, window start,
        notes)."""
        answers = []
        t0 = time.perf_counter()
        profiler.start(self.cell.traffic.get("trace_seconds"))
        k = 0
        while time.perf_counter() < t0 + seconds:
            i = k % len(self.pool)
            answers.append(self._solve(self.pool[i], i))
            k += 1
        profiler.stop()
        profiler.join()
        solves = [{n: a[n] for n in ("k", "status", "iterations",
                                     "solve_time", "setup_time")}
                  | {"wall": a["t_done"] - a["t_submit"]} for a in answers]
        return answers, t0, {"repeats": max(0, k - len(self.pool)),
                             "routes": sorted({a["backend"] for a in answers}),
                             "solves": solves}

    def answers(self, n=None) -> list:
        """LPs k = 0 … n−1 (default: the pool), one solve each."""
        n = int(self.cell.traffic.get("pool", 1)) if n is None else n
        return [self._solve(self.build(k), k) for k in range(n)]

    def close(self):
        self.pool = []
