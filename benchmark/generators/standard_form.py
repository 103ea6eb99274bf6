"""Independent standard-form LPs, min cᵀx s.t. Ax = b, x ≥ 0, with a
dense Gaussian A of ``m`` rows and ``n`` columns, feasible and bounded by
construction: b = A x0 for an interior x0 > 0, and c = Aᵀy0 + s0 with
s0 > 0, so (y0, s0) is dual feasible. LP k of a seed comes from
(seed, k). With ``base_seed`` the data come from (``base_seed``, k) and
the seed picks only the signs of the rows (each with its b): negation
is exact, so every seed gets the same work.

The generator of the ``lp128x512`` configuration (the batch entry's
LPs); nothing of the program is used but its ``LPProblem`` container.
"""

from __future__ import annotations

import numpy as np


def build(params: dict, seed: int, k: int):
    from distributedlpsolver_tpu.models.problem import LPProblem

    m, n = params["m"], params["n"]
    base = params.get("base_seed")
    rng = np.random.default_rng([seed if base is None else base, k])
    A = rng.standard_normal((m, n))
    b = A @ rng.uniform(0.5, 2.0, size=n)
    c = A.T @ rng.standard_normal(m) + rng.uniform(0.5, 2.0, size=n)
    if base is not None:
        s = np.random.default_rng([seed, k]).choice([-1.0, 1.0], size=m)
        A, b = A * s[:, None], b * s
    return LPProblem(c=c, A=A, rlb=b, rub=b, lb=np.zeros(n),
                     ub=np.full(n, np.inf), name=f"standard_form_{seed}_{k}")
