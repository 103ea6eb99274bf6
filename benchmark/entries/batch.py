"""``"entry": "batch"``: back-to-back calls of the program's batched path,
``backends.batched.solve_batched``, each on one batch of independent
standard-form LPs of one shape. Mix keys:

- ``batch``: LPs per call;
- ``pool``: batches built in set-up; no batch repeats in a window while
  the pool lasts;
- ``warm``: batches solved in set-up, of LPs k ≥ ``WARM_BASE``, so that
  every program the window's calls run compiles or loads there;
- ``trace_seconds``: see ``load.py``.

Batch i of the pool holds the generator's LPs k = i·batch … i·batch +
batch − 1. Each LP of a call is one answer, with the call's ``t_submit``
and ``t_done``: ``solve_s`` then reads seconds per LP. The
configuration's ``solve`` options go to ``solve_batched`` as config
overrides (no backend: the call is the path).
"""

from __future__ import annotations

import time

import numpy as np
import scipy.sparse as sp

from benchmark.load import WARM_BASE, _span


def stack(problems):
    """The ``BatchedLP`` of LPs in standard form (min cᵀx s.t. Ax = b,
    x ≥ 0) of one shape; a ``ValueError`` names an LP that is not."""
    from distributedlpsolver_tpu.models.generators import BatchedLP

    shape = problems[0].A.shape
    A = np.empty((len(problems),) + shape)
    for i, p in enumerate(problems):
        what = [
            (p.A.shape != shape,
             f"shape {p.A.shape}, not the batch's {shape}"),
            (not np.array_equal(p.rlb, p.rub),
             "a row that is not an equality"),
            (np.any(p.lb != 0), "a lower bound that is not 0"),
            (np.any(p.ub != np.inf), "a finite upper bound"),
            (p.c0 != 0, f"an objective constant {p.c0}"),
        ]
        broke = [msg for bad, msg in what if bad]
        if broke:
            raise ValueError(f"LP {p.name} (member {i}) is not in standard "
                             "form: " + "; ".join(broke))
        A[i] = p.A.toarray() if sp.issparse(p.A) else p.A
    return BatchedLP(c=np.stack([p.c for p in problems]), A=A,
                     b=np.stack([p.rlb for p in problems]))


class Entry:
    """``solve_batched`` on this thread; the window's batches are built in
    set-up."""

    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed
        self.params = cell.config["params"]
        self.opts = dict(cell.config.get("solve", {}))
        self.size = int(cell.traffic["batch"])

    def build(self, k0):
        return stack([self.cell.generator.build(self.params, self.seed, k)
                      for k in range(k0, k0 + self.size)])

    def setup(self) -> dict:
        t = self.cell.traffic
        warm = []
        for i in range(int(t.get("warm", 1))):
            ans = self._solve(self.build(WARM_BASE + i * self.size))
            warm.append(_call(ans))
        self.pool = [self.build(i * self.size)
                     for i in range(int(t.get("pool", 1)))]
        return {"warm_calls": warm, "pool": len(self.pool),
                "batch": self.size}

    def _solve(self, batch, k0=None):
        """One call; an answer per LP (k from ``k0``, or -1 for warm-up)."""
        from distributedlpsolver_tpu.backends import batched

        t0 = time.perf_counter()
        with _span("bench.solve"):
            r = batched.solve_batched(batch, **self.opts)
        t1 = time.perf_counter()
        call = {"t_submit": t0, "t_done": t1, "backend": "batched",
                "solve_time": float(r.solve_time),
                "setup_time": float(r.setup_time)}
        return [{"k": -1 if k0 is None else k0 + j,
                 "status": r.status[j].value,
                 "iterations": int(r.iterations[j]),
                 "objective": float(r.objective[j]),
                 "x": r.x[j], "y": None if r.y is None else r.y[j], **call}
                for j in range(batch.batch)]

    def window(self, seconds, profiler) -> tuple[list, float, dict]:
        """Back-to-back calls from window start; the one in flight at the
        close runs to its end. Returns (answers, window start, notes)."""
        answers, calls = [], []
        t0 = time.perf_counter()
        profiler.start(self.cell.traffic.get("trace_seconds"))
        n = 0
        while time.perf_counter() < t0 + seconds:
            i = n % len(self.pool)
            ans = self._solve(self.pool[i], i * self.size)
            answers += ans
            calls.append(_call(ans))
            n += 1
        profiler.stop()
        profiler.join()
        return answers, t0, {"repeats": max(0, n - len(self.pool)),
                             "routes": ["batched"] if calls else [],
                             "calls": calls}

    def answers(self, n=None) -> list:
        """LPs k = 0 … n−1 (default: the pool's), in whole batches."""
        if n is None:
            n = int(self.cell.traffic.get("pool", 1)) * self.size
        out = []
        for k0 in range(0, n, self.size):
            out += self._solve(self.build(k0), k0)
        return [a for a in out if a["k"] < n]

    def close(self):
        self.pool = []


def _call(answers) -> dict:
    """One call's notes, from its answers."""
    a = answers[0]
    return {"lps": len(answers), "wall": a["t_done"] - a["t_submit"],
            "solve_time": a["solve_time"], "setup_time": a["setup_time"],
            "not_optimal": sum(b["status"] != "optimal" for b in answers),
            "iterations_max": max(b["iterations"] for b in answers)}
