"""Optimality certificate of an answer, checked in float64 against the
problem's own data (numpy/scipy only; nothing of the program).

An answer of ``min cᵀx s.t. rlb ≤ Ax ≤ rub, lb ≤ x ≤ ub`` is a primal
point x and row multipliers y. With reduced costs r = c − Aᵀy, every
feasible x' has cᵀx' = yᵀAx' + rᵀx'. Each multiplier on a finite bound
bounds its term from below:

    D(y) = Σ_i [y_i⁺ rlb_i + y_i⁻ rub_i] + Σ_j [r_j⁺ lb_j + r_j⁻ ub_j]

(v⁺ = max(v, 0), v⁻ = min(v, 0)). A multiplier whose sign points at an
infinite bound (y_i > 0 on a row with no lower bound, r_j < 0 on a
column with no upper bound, ...) bounds nothing: its weight w is dual
infeasibility, and over points no larger than the answer's own it can
cost up to |w| · (1 + max|Ax|) for a row, |w| · (1 + max|x|) for a
column. So a primal feasible x with

    gap_rel = (|cᵀx − D(y)| + rows' and columns' such costs) / (1 + |cᵀx|)

small is optimal to that gap; a suboptimal x cannot hide behind a y that
closes the gap with weight on an infinite bound. Compared numbers:

- ``primal_rel``: the largest violation of rlb ≤ Ax ≤ rub and
  lb ≤ x ≤ ub, over 1 + the largest finite bound;
- ``gap_rel``: as above; NaN, which fails any limit, where the answer
  carries no y or a y of another length than the rows: a path that
  returns no dual has given no certificate.

The objective the program reports is not compared: it is cᵀx of its own
x, so no run, the float32 control's included, ever reads apart from it.
"""

from __future__ import annotations

import numpy as np


def _bound_terms(mult, lo, hi):
    """(D's terms, weight on infinite bounds) of multipliers ``mult`` on
    quantities bounded by [lo, hi]."""
    pos, neg = np.maximum(mult, 0.0), np.minimum(mult, 0.0)
    lo_ok, hi_ok = np.isfinite(lo), np.isfinite(hi)
    d = float(np.dot(pos[lo_ok], lo[lo_ok]) + np.dot(neg[hi_ok], hi[hi_ok]))
    w = float(pos[~lo_ok].sum() - neg[~hi_ok].sum())
    return d, w


def primal_rel(p, x) -> float:
    ax = np.asarray(p.A @ x, dtype=np.float64).ravel()
    viol = max(
        float(np.max(np.maximum(p.rlb - ax, ax - p.rub), initial=0.0)),
        float(np.max(np.maximum(p.lb - x, x - p.ub), initial=0.0)),
        0.0,
    )
    bounds = np.concatenate([p.rlb, p.rub, p.lb, p.ub])
    scale = 1.0 + float(np.max(np.abs(bounds[np.isfinite(bounds)]),
                               initial=0.0))
    return viol / scale


def check(p, answer: dict) -> dict:
    x = np.asarray(answer["x"], dtype=np.float64)
    if answer.get("y") is None or np.shape(answer["y"]) != (p.m,):
        return {"primal_rel": primal_rel(p, x), "gap_rel": float("nan")}
    y = np.asarray(answer["y"], dtype=np.float64)
    ax = np.asarray(p.A @ x, dtype=np.float64).ravel()
    r = p.c - np.asarray(p.A.T @ y, dtype=np.float64).ravel()
    pobj = float(np.dot(p.c, x)) + float(p.c0)
    d_row, w_row = _bound_terms(y, p.rlb, p.rub)
    d_col, w_col = _bound_terms(r, p.lb, p.ub)
    dobj = d_row + d_col + float(p.c0)
    cost = (w_row * (1.0 + float(np.max(np.abs(ax), initial=0.0)))
            + w_col * (1.0 + float(np.max(np.abs(x), initial=0.0))))
    return {
        "primal_rel": primal_rel(p, x),
        "gap_rel": (abs(pobj - dobj) + cost) / (1.0 + abs(pobj)),
    }
