"""Mehrotra predictor-corrector step — the algorithm core.

This module is the single implementation of the IPM math, written
array-library-generically: every function takes a :class:`LinOps` bundle
whose ``xp`` is either ``numpy`` (eager CPU backends) or ``jax.numpy``
(jitted TPU/device backends).  Backends differ only in how they implement
the four linear-algebra callables — ``matvec``/``rmatvec`` with the
constraint matrix and ``factorize``/``solve`` for the normal equations
``M = A·diag(d)·Aᵀ`` (BASELINE.json:5 names exactly this path: normal
equations, dense Cholesky, triangular solves).  The distributed backends
swap in sharded arrays so XLA turns the same expressions into
psum-combined per-shard Schur contributions (SURVEY.md §3.4).

Problem form handled (ipm/state.py): ``min cᵀx  s.t. Ax=b, 0≤x, x+w=u`` on
the columns with finite upper bound.  Columns without a finite upper bound
carry ``w=1, z=0`` and every ``w``/``z`` term is masked by ``hub`` so the
arithmetic stays finite under jit (no data-dependent shapes — SURVEY.md §7
"keep shapes static").

Newton system and its elimination to normal equations::

    A dx               = r_p  := b - Ax
    dx + dw            = r_u  := u - x - w          (masked)
    Aᵀdy + ds - dz     = r_d  := c - Aᵀy - s + z
    S dx + X ds        = r_xs := target - x∘s
    Z dw + W dz        = r_wz := target - w∘z       (masked)

    ⇒  dinv = s/x + z/w,  h = r_d - r_xs/x + (r_wz - z∘r_u)/w
       (A·diag(1/dinv)·Aᵀ) dy = r_p + A(h/dinv)
       dx = (Aᵀdy - h)/dinv ;  ds = (r_xs - s∘dx)/x
       dw = r_u - dx ;  dz = (r_wz - z∘dw)/w
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

from distributedlpsolver_tpu.ipm.config import StepParams
from distributedlpsolver_tpu.ipm.state import IPMState, StepStats
from distributedlpsolver_tpu.obs import trace as obs_trace


class LinOps(NamedTuple):
    """Backend linear-algebra seam (SURVEY.md §1 L3 — the `SolverBackend`
    interface's execution half)."""

    xp: Any  # numpy or jax.numpy
    matvec: Callable[[Any], Any]  # v ↦ A @ v           (n,) → (m,)
    rmatvec: Callable[[Any], Any]  # v ↦ Aᵀ @ v          (m,) → (n,)
    factorize: Callable[[Any], Any]  # d ↦ factors of A·diag(d)·Aᵀ (+ reg)
    solve: Callable[[Any, Any], Any]  # (factors, rhs) ↦ M⁻¹ rhs
    # Optional exact primal-row closure: rv ↦ Aᵀ(A·Aᵀ)⁻¹·rv. When set,
    # each KKT solve corrects its final dx so A·dx equals its target —
    # the regularized normal-equations solve Tikhonov-filters precisely
    # the near-null-space component of the feasibility RHS (the
    # diagnosed 10k×50k terminal-pinf wall), and iterate-space repair
    # was measured to break centrality/step lengths instead. Two valid
    # implementations exist: a pure-jax closure over a precomputed f32
    # factor of A·Aᵀ (dense._make_ops — traces into fused/jitted
    # programs), and an eager host-LAPACK closure (the dense host
    # endgame). The default None leaves every other path unchanged.
    primal_project: Any = None


class ProblemData(NamedTuple):
    """Problem vectors as backend arrays. ``u_f`` is the upper-bound vector
    with +inf replaced by 1.0; ``hub`` the finite-ub mask as 0/1 floats."""

    c: Any  # (n,)
    b: Any  # (m,)
    u_f: Any  # (n,)
    hub: Any  # (n,)
    ncomp: Any  # scalar: n + #finite-ub (complementarity pair count)
    norm_b: Any  # scalar: 1 + ||b||₂
    norm_c: Any  # scalar: 1 + ||c||₂


def make_problem_data(xp, c, b, u, dtype) -> ProblemData:
    c = xp.asarray(c, dtype=dtype)
    b = xp.asarray(b, dtype=dtype)
    u = xp.asarray(u, dtype=dtype)
    hub = xp.isfinite(u).astype(dtype)
    u_f = xp.where(hub > 0, u, xp.asarray(1.0, dtype=dtype))
    return ProblemData(
        c=c,
        b=b,
        u_f=u_f,
        hub=hub,
        ncomp=c.shape[0] + xp.sum(hub),
        norm_b=1.0 + xp.linalg.norm(b),
        norm_c=1.0 + xp.linalg.norm(c),
    )


def _solve_kkt_once(ops: LinOps, state: IPMState, hub, d, factors, r_p, r_u,
                    r_d, r_xs, r_wz, elementwise: str = "native"):
    """Back-substitute one Newton solve through the normal equations.

    ``elementwise="df32"`` (StepParams.elementwise) runs the elementwise
    chains — the division-heavy h/dx/ds/dw/dz blocks that dominate the
    batched step on emulated-f64 hardware — through the two-float layer
    (ops/df32.py, ~1e-13 relative); the matvecs and the normal-equations
    solve keep their native route either way. jax-only (resolved at
    trace time: ``elementwise`` rides the static StepParams key).
    """
    x, y, s, w, z = state
    if elementwise == "df32":
        # Lazy import keeps jax out of this module's import path (the
        # eager numpy backends pass elementwise="native" and never reach
        # here).
        from distributedlpsolver_tpu.ops import df32 as _df32

        h = _df32.kkt_h(r_d, r_xs, x, r_wz, z, r_u, w)
        dy = ops.solve(factors, r_p + ops.matvec(_df32.mul64(d, h)))
        dx = _df32.kkt_dx(d, ops.rmatvec(dy), h)
        ds = _df32.kkt_ds(r_xs, s, dx, x)
        dw = _df32.sub64(r_u, dx)
        dz = _df32.kkt_dz(hub, r_wz, z, dw, w)
        return dx, dy, ds, dw, dz
    h = r_d - r_xs / x + (r_wz - z * r_u) / w
    dy = ops.solve(factors, r_p + ops.matvec(d * h))
    dx = d * (ops.rmatvec(dy) - h)
    ds = (r_xs - s * dx) / x
    dw = r_u - dx
    dz = hub * (r_wz - z * dw) / w
    return dx, dy, ds, dw, dz


def _solve_kkt(
    ops: LinOps, state: IPMState, hub, d, factors, r_p, r_u, r_d, r_xs, r_wz,
    refine: int, elementwise: str = "native",
):
    """Newton solve + ``refine`` rounds of KKT-level iterative refinement.

    Near convergence the scaling ``d`` spans ~1/μ orders of magnitude and
    the back-substitution ``dx = d·(Aᵀdy - h)`` loses ~μ⁻¹·ε of absolute
    accuracy to cancellation, which stalls primal feasibility around 1e-6
    (observed; refinement of the *normal-equations* solve alone cannot fix
    it). Re-evaluating the full 5-block KKT residual and solving for a
    correction restores the lost digits at the cost of one extra
    factorization-reuse solve per round.
    """
    x, y, s, w, z = state
    dx, dy, ds, dw, dz = _solve_kkt_once(
        ops, state, hub, d, factors, r_p, r_u, r_d, r_xs, r_wz, elementwise
    )
    for _ in range(refine):
        # KKT residuals stay native: they are the accuracy arbiter each
        # refinement round corrects toward, so they must not inherit the
        # df32 chains' (tiny but nonzero) rounding.
        e_p = r_p - ops.matvec(dx)
        e_u = hub * (r_u - (dx + dw))
        e_d = r_d - (ops.rmatvec(dy) + ds - dz)
        e_xs = r_xs - (s * dx + x * ds)
        e_wz = hub * (r_wz - (z * dw + w * dz))
        cx, cy, cs, cw, cz = _solve_kkt_once(
            ops, state, hub, d, factors, e_p, e_u, e_d, e_xs, e_wz, elementwise
        )
        dx, dy, ds, dw, dz = dx + cx, dy + cy, ds + cs, dw + cw, dz + cz
    if ops.primal_project is not None:
        # Exact primal-row closure (LinOps.primal_project), applied ONCE
        # on the final direction and deliberately NOT fed back into
        # ds/dz: those back-substitutions divide by x (resp. w), so a
        # tiny-column correction δ would come back as ds_i ~ δ_i·s_i/x_i
        # — measured at 10k×50k to explode dinf to O(1) and zero every
        # step length. dw IS kept consistent (dw = r_u − dx involves no
        # division), so the closure never leaks into the upper-bound
        # row. The residual it induces in the complementarity rows is
        # ~s·δ with δ the CURRENT solve's filtered junk (reg·D̃·dy-scale,
        # not the accumulated pinf) — absorbed by the corrector at any
        # μ above that scale, which is why the closure must be active
        # from the FIRST phase (junk must never accumulate past μ).
        delta = ops.primal_project(r_p - ops.matvec(dx))
        dx = dx + delta
        dw = dw - hub * delta
    return dx, dy, ds, dw, dz


def _max_step(xp, v, dv, v2, dv2, mask):
    """Largest α ≤ 1 with v+αdv ≥ 0 and (masked) v2+αdv2 ≥ 0 (ratio test,
    kept on device — SURVEY.md §7 'step-length reductions ... return only
    scalars')."""
    inf = xp.asarray(xp.inf, dtype=v.dtype)
    r1 = xp.where(dv < 0, -v / xp.where(dv < 0, dv, -1.0), inf)
    neg2 = (dv2 < 0) & (mask > 0)
    r2 = xp.where(neg2, -v2 / xp.where(neg2, dv2, -1.0), inf)
    return xp.minimum(1.0, xp.minimum(xp.min(r1), xp.min(r2)))


def _centrality_backoff(xp, state, hub, dirs, ap_max, ad_max, ncomp, gamma):
    """N₋∞(γ) neighborhood guard: damp the steps until no complementarity
    product falls below γ·μ(α).

    Iterates that stray orders of magnitude *below* the average
    complementarity create the extreme scaling spreads (d_max/d_min ≳ 1e18)
    that make the f64 normal equations unable to repair primal
    infeasibility — once injured, pinf freezes around 1e-6 (observed).
    Keeping products within γ of μ bounds the spread and prevents the
    injury. Implemented jit-style: evaluate a geometric grid of 24 damped
    (α_p, α_d) candidates at once and pick the least-damped admissible one
    — no data-dependent control flow (SURVEY.md §7).
    """
    if gamma <= 0:
        return ap_max, ad_max
    x, y, s, w, z = state
    dx, ds, dw, dz = dirs
    # If the CURRENT iterate already sits outside N₋∞(γ), demanding γ from
    # every candidate rejects them all (α→0 approaches the current point,
    # which violates γ) and the fallback pins every step at the most-damped
    # candidate — the solve crawls at α≈0.8²³ forever (observed). Relax the
    # demand to 0.9× the current centrality ratio in that case: the guard
    # then only blocks steps that make centrality *worse*, while iterates
    # inside the neighborhood still get the full γ.
    xs0 = x * s
    wz0 = w * z
    mu0 = (xs0.sum() + (wz0 * hub).sum()) / ncomp
    inf0 = xp.asarray(xp.inf, dtype=x.dtype)
    minprod0 = xp.minimum(xs0.min(), xp.where(hub > 0, wz0, inf0).min())
    ratio0 = minprod0 / xp.maximum(mu0, xp.finfo(x.dtype).tiny)
    # Only relax when actually outside — an unconditional min() would let
    # the floor erode geometrically (each accepted step lands near the
    # floor, then 0.9× it again next iteration).
    gamma = xp.where(ratio0 < gamma, 0.9 * ratio0, gamma)
    fac = 0.8 ** xp.arange(24, dtype=x.dtype)
    aps = ap_max * fac
    ads = ad_max * fac
    xs = (x[None, :] + aps[:, None] * dx[None, :]) * (
        s[None, :] + ads[:, None] * ds[None, :]
    )
    wz = (w[None, :] + aps[:, None] * dw[None, :]) * (
        z[None, :] + ads[:, None] * dz[None, :]
    )
    comp = xs.sum(axis=1) + (wz * hub[None, :]).sum(axis=1)
    mu_a = comp / ncomp
    inf_ = xp.asarray(xp.inf, dtype=x.dtype)
    minprod = xp.minimum(
        xs.min(axis=1), xp.where(hub[None, :] > 0, wz, inf_).min(axis=1)
    )
    ok = minprod >= gamma * mu_a
    # Least-damped admissible candidate; fall back to the most damped one.
    idx = xp.argmax(ok)
    idx = xp.where(xp.any(ok), idx, len(fac) - 1)
    return aps[idx], ads[idx]


def pcg_solve(op, prec, rhs, tol, max_iter):
    """Preconditioned conjugate gradient, fully on-device (jax only).

    Shared by the PCG solve modes of the dense and block backends: ``op``
    is the full-precision matrix-free normal-equations operator, ``prec``
    the (typically f32-factorization-based) preconditioner. Terminates at
    relative residual ``tol`` or ``max_iter`` iterations.

    A broken preconditioner (f32 Cholesky breakdown → NaN factor) makes
    the loop exit on its non-finite guard with x still at the FINITE zero
    initial guess; returning that silently would feed a zero direction to
    the step and bypass the driver's bad-step → regularization-escalation
    recovery (observed at 2048×10240). The failure is propagated as NaN
    exactly like a direct Cholesky solve would.
    """
    import jax
    import jax.numpy as jnp

    norm0 = jnp.linalg.norm(rhs)
    thresh = tol * norm0

    x0 = jnp.zeros_like(rhs)
    z0 = prec(rhs)
    carry0 = (x0, rhs, z0, rhs @ z0, jnp.asarray(0, jnp.int32))

    def cond(carry):
        x, r, p, rz, it = carry
        return (it < max_iter) & (jnp.linalg.norm(r) > thresh) & jnp.isfinite(rz)

    def body(carry):
        x, r, p, rz, it = carry
        Ap = op(p)
        denom = p @ Ap
        alpha = rz / jnp.where(denom != 0, denom, 1.0)
        x = x + alpha * p
        r = r - alpha * Ap
        z = prec(r)
        rz_new = r @ z
        beta = rz_new / jnp.where(rz != 0, rz, 1.0)
        p = z + beta * p
        return (x, r, p, rz_new, it + 1)

    x, r, p, rz, it = jax.lax.while_loop(cond, body, carry0)
    bad = ~(jnp.isfinite(rz) & jnp.all(jnp.isfinite(x)))
    # A cap-limited CG that did NOT meaningfully reduce the residual is a
    # failed solve, not an approximate one: the resulting direction is
    # noise with finite entries, and silently returning it poisons the
    # iterate while μ keeps shrinking (observed: pinf freezes at 1e-2 and
    # the divergence heuristic misfires). The failure line is 1e-3
    # relative OR 10× the requested tol, whichever is looser — so a
    # caller running with a deliberately loose cg_tol still gets its
    # approximate directions, and only order-of-magnitude misses NaN.
    bad = bad | (jnp.linalg.norm(r) > jnp.maximum(1e-3 * norm0, 10.0 * thresh))
    return jnp.where(bad, jnp.asarray(jnp.nan, x.dtype), x)


def residual_norms(ops: LinOps, data: ProblemData, state: IPMState):
    """Relative primal/dual infeasibility, gap, and objectives of a state."""
    xp = ops.xp
    x, y, s, w, z = state
    r_p = data.b - ops.matvec(x)
    r_u = data.hub * (data.u_f - x - w)
    r_d = data.c - ops.rmatvec(y) - s + z
    pinf = xp.sqrt(xp.sum(r_p * r_p) + xp.sum(r_u * r_u)) / data.norm_b
    dinf = xp.linalg.norm(r_d) / data.norm_c
    pobj = data.c @ x
    dobj = data.b @ y - (data.hub * data.u_f) @ z
    gap = xp.abs(pobj - dobj)
    rel_gap = gap / (1.0 + xp.abs(pobj))
    mu = (x @ s + (data.hub * w) @ z) / data.ncomp
    return pinf, dinf, gap, rel_gap, pobj, dobj, mu


def scaling_d(state: IPMState, data: ProblemData, cfg: StepParams):
    """The normal-equations diagonal ``d = 1/(s/x + z/w + reg_primal)``.

    One definition shared by :func:`mehrotra_step` and backends that
    precompute factorizations outside the step program (the dense
    endgame phase splits one iteration across dispatches and must form
    the SAME d the step will use). With ``cfg.elementwise == "df32"``
    the division chain runs through the two-float layer (jax paths
    only; see :func:`_solve_kkt_once`)."""
    x, y, s, w, z = state
    if cfg.elementwise == "df32":
        from distributedlpsolver_tpu.ops import df32 as _df32

        return _df32.scaling_d(x, s, w, z, data.hub, cfg.reg_primal)
    dinv = s / x + data.hub * z / w + cfg.reg_primal
    return 1.0 / dinv


def mehrotra_step(
    ops: LinOps, data: ProblemData, cfg: StepParams, state: IPMState
):
    """One full predictor-corrector iteration: state ↦ (state', stats).

    Everything here runs on the backend's device(s) in one traced call; only
    the :class:`StepStats` scalars cross back to the host loop
    (BASELINE.json:5: driver on host, linear algebra on device).
    """
    xp = ops.xp
    x, y, s, w, z = state
    hub, u_f, c, b = data.hub, data.u_f, data.c, data.b

    # Residuals of the current iterate.
    r_p = b - ops.matvec(x)
    r_u = hub * (u_f - x - w)
    r_d = c - ops.rmatvec(y) - s + z
    mu = (x @ s + (hub * w) @ z) / data.ncomp

    # Diagonal scaling and one factorization, shared by both solves.
    d = scaling_d(state, data, cfg)
    factors = ops.factorize(d)

    # Aim the centering target at the convergence tolerance, not at zero:
    # letting μ overshoot orders of magnitude below what a 1e-8 relative
    # gap needs pushes the scaling spread d_max/d_min past what f64 can
    # factor, and the *feasibility* components of subsequent directions
    # collapse (observed: pinf jumps 1e-9 → 5e-6 and freezes). 0.03·tol
    # keeps a safe 30× margin below the gap test.
    pobj_now = c @ x
    mu_floor = 0.03 * cfg.tol * (1.0 + xp.abs(pobj_now)) / data.ncomp
    if cfg.mu_pinf_floor:
        # Balance floor for limited-precision phases (StepParams
        # docstring): μ may trail the remaining primal infeasibility by
        # at most 1/mu_pinf_floor — same unit construction as the tol
        # floor, with pinf_rel in tol's place.
        pinf_now = xp.sqrt(xp.sum(r_p * r_p) + xp.sum(r_u * r_u)) / data.norm_b
        mu_floor = xp.maximum(
            mu_floor,
            cfg.mu_pinf_floor * pinf_now * (1.0 + xp.abs(pobj_now)) / data.ncomp,
        )

    if cfg.center:
        # Pure centering step (StepParams.center): one KKT solve aiming
        # every product at the current μ — no predictor, no cross term.
        sigma = xp.asarray(1.0, dtype=x.dtype)
        target = xp.maximum(mu, mu_floor)
        rxs = target - x * s
        rwz = hub * (target - w * z)
    else:
        # Predictor (affine-scaling) direction.
        rxs_aff = -x * s
        rwz_aff = -(w * z) * hub
        dxa, dya, dsa, dwa, dza = _solve_kkt(
            ops, state, hub, d, factors, r_p, r_u, r_d, rxs_aff, rwz_aff,
            cfg.kkt_refine, cfg.elementwise
        )
        ap_aff = _max_step(xp, x, dxa, w, dwa, hub)
        ad_aff = _max_step(xp, s, dsa, z, dza, hub)
        mu_aff = (
            (x + ap_aff * dxa) @ (s + ad_aff * dsa)
            + ((w + ap_aff * dwa) * (z + ad_aff * dza)) @ hub
        ) / data.ncomp
        sigma = xp.clip(
            (xp.maximum(mu_aff, 0.0) / mu) ** cfg.sigma_power,
            cfg.sigma_min, cfg.sigma_max,
        )
        target = xp.maximum(sigma * mu, mu_floor)

        # Corrector: recenter to the target and cancel the second-order
        # term, reusing the factorization (the defining Mehrotra move,
        # BASELINE.json:5).
        rxs = target - x * s - dxa * dsa
        rwz = hub * (target - w * z - dwa * dza)
    dx, dy, ds, dw, dz = _solve_kkt(
        ops, state, hub, d, factors, r_p, r_u, r_d, rxs, rwz, cfg.kkt_refine,
        cfg.elementwise
    )

    ap_raw = _max_step(xp, x, dx, w, dw, hub)
    ad_raw = _max_step(xp, s, ds, z, dz, hub)
    if cfg.mcc and not cfg.center:
        # Gondzio multiple centrality correctors (StepParams.mcc): each
        # round solves ONCE more on the held factorization with a
        # complementarity-only RHS that pulls the TRIAL point's outlier
        # products back into a [0.1, 10]·target band, and keeps the
        # corrected direction only if it lengthens the combined step.
        # Feasibility RHS is zero, so an accepted correction never
        # perturbs r_p/r_u/r_d reduction — pure recentering.
        zm = xp.zeros_like(b)
        zn = xp.zeros_like(x)
        for mc in range(cfg.mcc):
            # Progressively enlarged trial step per round (Gondzio's own
            # escalation): without it a REJECTED round makes every later
            # round bit-identical — the same trial point, band, RHS, and
            # solve, deterministically rejected again (round-5 review
            # finding: a guaranteed-useless KKT solve per extra round).
            grow = 1.3 + 0.25 * mc
            ap_t = xp.minimum(1.0, grow * ap_raw + 0.1 * (mc + 1))
            ad_t = xp.minimum(1.0, grow * ad_raw + 0.1 * (mc + 1))
            v_xs = (x + ap_t * dx) * (s + ad_t * ds)
            v_wz = hub * ((w + ap_t * dw) * (z + ad_t * dz))
            cxs = xp.clip(v_xs, 0.1 * target, 10.0 * target) - v_xs
            cwz = hub * (xp.clip(v_wz, 0.1 * target, 10.0 * target) - v_wz)
            gx, gy, gs, gw, gz = _solve_kkt_once(
                ops, state, hub, d, factors, zm, zn, zn, cxs, cwz,
                cfg.elementwise
            )
            dx2, dy2, ds2, dw2, dz2 = dx + gx, dy + gy, ds + gs, dw + gw, dz + gz
            ap2 = _max_step(xp, x, dx2, w, dw2, hub)
            ad2 = _max_step(xp, s, ds2, z, dz2, hub)
            better = (ap2 + ad2) > (ap_raw + ad_raw) + 0.01
            keep = lambda new, old: xp.where(better, new, old)
            dx, dy, ds = keep(dx2, dx), keep(dy2, dy), keep(ds2, ds)
            dw, dz = keep(dw2, dw), keep(dz2, dz)
            ap_raw = keep(ap2, ap_raw)
            ad_raw = keep(ad2, ad_raw)

    alpha_p = xp.minimum(1.0, cfg.eta * ap_raw)
    alpha_d = xp.minimum(1.0, cfg.eta * ad_raw)
    alpha_p, alpha_d = _centrality_backoff(
        xp, state, hub, (dx, ds, dw, dz), alpha_p, alpha_d, data.ncomp, cfg.gamma_cent
    )

    finite = (
        xp.all(xp.isfinite(dx))
        & xp.all(xp.isfinite(dy))
        & xp.all(xp.isfinite(ds))
        & xp.all(xp.isfinite(dw))
        & xp.all(xp.isfinite(dz))
    )
    ok = finite & (alpha_p > 0) & (alpha_d > 0)

    def upd(v, dv, a):
        return xp.where(ok, v + a * dv, v)

    x1 = upd(x, dx, alpha_p)
    w1 = xp.where(hub > 0, upd(w, dw, alpha_p), 1.0)
    y1 = upd(y, dy, alpha_d)
    s1 = upd(s, ds, alpha_d)
    z1 = xp.where(hub > 0, upd(z, dz, alpha_d), 0.0)
    new_state = IPMState(x=x1, y=y1, s=s1, w=w1, z=z1)

    pinf, dinf, gap, rel_gap, pobj, dobj, mu1 = residual_norms(ops, data, new_state)
    stats = StepStats(
        mu=mu1,
        gap=gap,
        rel_gap=rel_gap,
        pinf=pinf,
        dinf=dinf,
        pobj=pobj,
        dobj=dobj,
        alpha_p=xp.where(ok, alpha_p, 0.0),
        alpha_d=xp.where(ok, alpha_d, 0.0),
        sigma=sigma,
        bad=~ok,
    )
    return new_state, stats


STATUS_RUNNING, STATUS_OPTIMAL, STATUS_MAXITER, STATUS_NUMERR = 0, 1, 2, 3
STATUS_PINFEAS, STATUS_DINFEAS = 4, 5
STATUS_STALL = 6  # no max(gap,pinf,dinf) improvement over the stall window
N_STAT = 10  # mu, gap, rel_gap, pinf, dinf, pobj, dobj, alpha_p, alpha_d, sigma

DIVERGE_MU = 1e30


def classify_divergence(mu, pinf, dinf, rel_gap, pobj, dobj):
    """Heuristic infeasibility/unboundedness signals (works on host floats
    and on traced scalars).

    * Primal infeasible: complementarity has converged (μ ≈ 0) while primal
      infeasibility is stuck far above tolerance — the iteration found a
      Farkas-like stationary point (observed signature: μ→1e-10, pinf
      frozen ~1e-1) — or the dual objective runs away upward.
    * Primal unbounded (dual infeasible): dual infeasibility is stuck while
      the primal objective dives along a recession ray; rel_gap→1 is the
      scale-free confirmation (gap ≈ |pobj|).
    These are heuristics, not certificates — a homogeneous self-dual
    embedding would give certified rays (future work, SURVEY.md §5.3 notes
    the reference has no such machinery either).

    Every test is scale-relative (dimensionless): μ and the objectives
    carry the problem's c·x units, so absolute cutoffs misfire under bad
    data scaling — scaling c by 1e6 would leave an absolute μ test
    unreachable (muting detection) or let a legitimately large objective
    trip an absolute divergence cutoff on a feasible problem. pinf /
    dinf / rel_gap arrive already normalized (residual_norms divides by
    ‖b‖ / ‖c‖ / 1+|pobj|), and the objective comparisons below normalize
    each objective by the OTHER side's magnitude — at a divergence point
    the runaway side explodes while the other stays finite, so the ratio
    is scale-free.
    """
    # Constants preserve the old absolute behavior at unit scale (1e12
    # for the unguarded runaway legs) — the rewrite makes them relative,
    # it must not also make them 2-4 orders looser: a feasible problem
    # whose legitimate optimum is ~ -1e10 would otherwise trip the
    # primal-dive leg mid-solve while the dual still lags near zero.
    scale_p = 1.0 + abs(pobj)
    scale_d = 1.0 + abs(dobj)
    # μ-converged threshold 1e-11·scale, NOT 1e-8: μ is per-pair, so on
    # a large problem 1e-8·scale still describes a mid-solve iterate —
    # observed at the pds-20 class (ncomp≈1.2e5): μ=2.4e-4 < 1e-8·scale
    # with rel_gap still 6e-4 fired a false PRIMAL_INFEASIBLE one
    # iteration into the f64 finisher. A rel_gap conjunct cannot fix it
    # (a genuine Farkas point has HUGE rel_gap — the dual runs away);
    # the real Farkas signature sits orders lower (μ/scale ~1e-13).
    pinfeas = ((mu < 1e-11 * scale_p) & (pinf > 1e-3)) | (
        dobj > 1e12 * scale_p
    )
    dinfeas = ((dinf > 1e-3) & (pobj < -1e8 * scale_d) & (rel_gap > 0.99)) | (
        pobj < -1e12 * scale_d
    )
    return pinfeas, dinfeas


def buffer_cap(max_iter: int, quantum: int = 512) -> int:
    """Static stats-buffer size for :func:`fused_solve`, bucketed so that
    different ``max_iter`` values share one compiled executable (max_iter
    itself is a *traced* loop bound; only this cap is a jit key). The
    quantum covers two phase budgets of the default max_iter (2×200), so a
    tiny-max_iter warm-up lands in the same bucket as production runs —
    the buffer is (cap, N_STAT) scalars, so a generous cap costs ~40 KB."""
    return ((max(int(max_iter), 1) + quantum - 1) // quantum) * quantum


def fused_solve(
    step_fn,
    state0,
    reg0,
    params,
    max_iter,
    max_refactor,
    reg_grow,
    buf_cap=None,
    *,
    stall_window=0,
    stall_patience_floor=0.0,
    carry_in=None,
    finalize=True,
    it_stop=None,
    resume=None,
    return_carry=False,
):
    """Entire IPM solve as one traced program (``lax.while_loop`` over
    iterations) — jax-only, called from inside a backend's jit.

    Removes the per-iteration host↔device round trip (which dominated
    wall-clock on the remote chip used before PR 21). Mirrors the host driver's
    loop semantics: deterministic regularization escalation on bad steps
    (state frozen, reg ×= grow, give up after max_refactor), convergence
    at params.tol on rel_gap/pinf/dinf. Per-iteration stats stream into a
    fixed (buf_cap, N_STAT) buffer so the host can reconstruct the full
    iteration log afterwards. Returns (state, iterations, status, buffer).

    ``max_iter``, ``max_refactor``, and ``reg_grow`` may be traced scalars —
    changing them never recompiles; only ``buf_cap`` (static, bucketed via
    :func:`buffer_cap`) is part of the compile key. ``buf_cap`` is REQUIRED
    whenever ``max_iter`` is traced (the default derives it via
    ``int(max_iter)``, which only works on concrete values).

    ``stall_window`` (static) > 0 adds a stall exit: if the error measure
    ``max(rel_gap, pinf, dinf)`` fails to improve by ≥10% over that many
    accepted steps, the loop stops (status ``STATUS_STALL`` if this is the
    ``finalize`` phase, else left ``STATUS_RUNNING`` for a continuation).
    ``stall_patience_floor`` suppresses the stall exit while the best error
    is at or below it — IPM tails can plateau for dozens of iterations
    within ~100× of tolerance and still converge (observed), so final
    phases pass ~1e3·tol here; 0 means stall always exits.

    Phase composition (mixed-precision two-phase solves): pass
    ``finalize=False`` to leave a non-terminal exit as ``STATUS_RUNNING``
    and feed ``(it, status, buf)`` of one call as ``carry_in`` of the next —
    the continuation resumes the global iteration count and appends to the
    same stats buffer.

    Segmentation (bounding single device-program runtime; see
    :func:`use_segments`): pass ``it_stop`` (a
    traced iteration bound for THIS call) and ``return_carry=True`` to get
    the raw loop carry back; feed it to the next call via ``resume`` to
    continue exactly where the segment stopped (regularization, stall
    counters and stats buffer included). ``return_carry`` skips the
    ``finalize`` status mapping — the segment driver owns it.
    """
    import jax
    import jax.numpy as jnp

    if buf_cap is None:
        buf_cap = buffer_cap(int(max_iter))

    def cond(carry):
        _, it, _, _, status, _, best_err, since = carry
        go = (status == STATUS_RUNNING) & (it < max_iter) & (it < buf_cap)
        if it_stop is not None:
            go = go & (it < it_stop)
        if stall_window:
            stall = since > stall_window
            if stall_patience_floor:
                stall = stall & (best_err > stall_patience_floor)
            go = go & ~stall
        return go

    def body(carry):
        state, it, reg, badcount, status, buf, best_err, since = carry
        new_state, stats = step_fn(state, reg)
        bad = stats.bad
        conv = (
            (stats.rel_gap <= params.tol)
            & (stats.pinf <= params.tol)
            & (stats.dinf <= params.tol)
        )
        state = jax.tree_util.tree_map(
            lambda n, o: jnp.where(bad, o, n), new_state, state
        )
        row = jnp.stack(
            [stats.mu, stats.gap, stats.rel_gap, stats.pinf, stats.dinf,
             stats.pobj, stats.dobj, stats.alpha_p, stats.alpha_d, stats.sigma]
        )
        buf = jnp.where(bad, buf, buf.at[it].set(row))
        it = jnp.where(bad, it, it + 1)
        badcount = jnp.where(bad, badcount + 1, badcount)
        status = jnp.where(
            bad & ((badcount > max_refactor) | (reg * reg_grow > 1e-2)),
            STATUS_NUMERR,
            jnp.where(conv & ~bad, STATUS_OPTIMAL, status),
        )
        ok = ~bad & (status == STATUS_RUNNING)
        pinfeas, dinfeas = classify_divergence(
            stats.mu, stats.pinf, stats.dinf, stats.rel_gap, stats.pobj, stats.dobj
        )
        status = jnp.where(ok & pinfeas, STATUS_PINFEAS, status)
        status = jnp.where(ok & dinfeas, STATUS_DINFEAS, status)
        status = jnp.where(
            ok & (~jnp.isfinite(stats.mu) | (stats.mu > DIVERGE_MU)),
            STATUS_NUMERR,
            status,
        )
        err = jnp.maximum(stats.rel_gap, jnp.maximum(stats.pinf, stats.dinf))
        improved = ~bad & (err < 0.9 * best_err)
        best_err = jnp.where(improved, err, best_err)
        since = jnp.where(bad, since, jnp.where(improved, 0, since + 1))
        reg = jnp.where(bad, jnp.maximum(reg, 1e-12) * reg_grow, reg)
        return state, it, reg, badcount, status, buf, best_err, since

    if resume is not None:
        carry0 = resume
    else:
        if carry_in is not None:
            it0, status0, buf0 = carry_in
            it0 = jnp.asarray(it0, jnp.int32)
            status0 = jnp.asarray(status0, jnp.int32)
        else:
            it0 = jnp.asarray(0, jnp.int32)
            status0 = jnp.asarray(STATUS_RUNNING, jnp.int32)
            buf0 = jnp.zeros((buf_cap, N_STAT), dtype=state0.x.dtype)
        carry0 = (
            state0,
            it0,
            reg0,
            jnp.asarray(0, jnp.int32),
            status0,
            buf0,
            jnp.asarray(jnp.inf, state0.x.dtype),
            jnp.asarray(0, jnp.int32),
        )
    carry = jax.lax.while_loop(cond, body, carry0)
    if return_carry:
        return carry
    state, it, reg, _, status, buf, _, since = carry
    if finalize:
        stalled = (
            (since > stall_window) if stall_window else jnp.asarray(False, bool)
        )
        status = jnp.where(
            status == STATUS_RUNNING,
            jnp.where(stalled & (it < max_iter), STATUS_STALL, STATUS_MAXITER),
            status,
        )
    return state, it, status, buf


def seg_trace_enabled() -> bool:
    """Whether TPULP_SEG_VERBOSE asks for live progress lines
    (conventional 0/1 contract: "", "0", "false", "no" disable)."""
    import os

    return os.environ.get("TPULP_SEG_VERBOSE", "").lower() not in (
        "", "0", "false", "no",
    )


def drive_segments(
    run_seg, carry0, max_iter, stall_window, seg_init=16, target_s=15.0,
    stall_patience_floor=0.0, it0_status0=(0, STATUS_RUNNING),
    early_stop=None, seg_cap=256,
):
    """Host loop over bounded fused-solve segments.

    ``run_seg(carry, it_stop) -> (carry, meta)`` executes one device
    program continuing from ``carry`` until the iteration count reaches
    ``it_stop`` or the loop exits on its own; ``meta`` is the packed
    ``[it, status, best_err, since]`` scalar array (ONE device→host
    transfer per segment — individually fetching loop scalars costs a
    device round trip each). Repeats — adapting the segment length toward
    ``target_s`` seconds of device time, the bound on single-program
    runtime (see :func:`use_segments`) — until the status
    leaves RUNNING, the stall window fires, or ``max_iter`` is reached.
    Returns ``(carry, (it, status, best_err, since))`` — the final carry
    plus host copies of the loop scalars, so callers never re-fetch them.

    ``early_stop(it, status, best_err, since) -> bool`` (optional) is
    consulted after each segment; True ends the drive. Callers with extra
    loop state pack it into the meta slots (e.g. the batched driver packs
    its active-problem count into the ``best_err`` slot and stops when the
    tail is small enough to hand to per-problem cleanup).
    """
    import os as _os
    import time as _time

    import numpy as _np

    trace = seg_trace_enabled()
    carry = carry0
    seg = max(int(seg_init), 1)
    # Entry it/status are read from the packed meta the CALLER already has
    # (or known statically at a fresh start) — fetching them from carry
    # here would cost two extra device round trips per phase.
    it, status = it0_status0
    best_err, since = float("inf"), 0
    first = True
    while status == STATUS_RUNNING and it < max_iter:
        prev_it = it
        stop = min(it + seg, max_iter)
        t0 = _time.perf_counter()
        with obs_trace.span("ipm.segment", it=it, stop=stop):
            carry, meta = run_seg(carry, stop)
            meta = _np.asarray(meta)  # blocks; the segment's one host read
        dt = _time.perf_counter() - t0
        it, status = int(meta[0]), int(meta[1])
        best_err, since = float(meta[2]), int(meta[3])
        if trace:
            import sys as _sys

            print(
                f"[seg] it={it} status={status} best_err={best_err:.3e} "
                f"since={since} dt={dt:.1f}s seg={seg}",
                file=_sys.stderr, flush=True,
            )
        if (
            stall_window
            and since > stall_window
            and (not stall_patience_floor or best_err > stall_patience_floor)
        ):
            break
        if early_stop is not None and early_stop(it, status, best_err, since):
            break
        if it == prev_it:  # no progress possible (defensive: avoid spinning)
            break
        if not first:  # first call's wall time includes compile — don't adapt
            # Jump straight to the measured rate (dt is clean post-compile);
            # the cap keeps one segment well under the watchdog either way.
            # ``seg_cap`` lets callers that act at segment boundaries
            # (the batched compaction drive) keep boundaries frequent.
            seg = max(1, min(seg_cap, int(seg * target_s / max(dt, 1e-3))))
        first = False
    return carry, (it, status, best_err, since)


def pack_segment_meta(carry):
    """[it, status, best_err, since] as one array — see drive_segments."""
    import jax.numpy as jnp

    _, it, _, _, status, _, best_err, since = carry
    f = best_err.dtype
    return jnp.stack(
        [it.astype(f), status.astype(f), best_err, since.astype(f)]
    )


def fresh_segment_carry(state, reg0, buf_cap, dtype):
    """Initial drive_segments carry for a fused solve starting at ``state``
    (mirrors fused_solve's internal carry layout)."""
    import jax.numpy as jnp

    return (
        state,
        jnp.asarray(0, jnp.int32),
        reg0,
        jnp.asarray(0, jnp.int32),
        jnp.asarray(STATUS_RUNNING, jnp.int32),
        jnp.zeros((buf_cap, N_STAT), dtype),
        jnp.asarray(jnp.inf, dtype),
        jnp.asarray(0, jnp.int32),
    )


_PHASE_RESET_JIT = None


def segment_phase_reset(carry, reg0):
    """Device-side phase-boundary reset (one dispatch): keep state,
    iteration count, and stats buffer; reset everything provisional
    (regularization, bad-count, status, stall tracking) — every phase-1
    verdict is provisional and phase 2 re-derives it at full precision."""
    global _PHASE_RESET_JIT
    if _PHASE_RESET_JIT is None:
        import jax
        import jax.numpy as jnp

        # Cached in the module-level _PHASE_RESET_JIT slot: the wrapper
        # is built ONCE (core.py keeps jax out of its import path), so
        # this is a hoist in disguise, not a per-call jit.
        @jax.jit  # graftcheck: disable=jit-nonhoisted (cached lazy init)
        def _reset(carry, reg0):
            st, it, _, _, _, buf, _, _ = carry
            z = jnp.asarray(0, jnp.int32)
            return (
                st, it, reg0, z,
                jnp.asarray(STATUS_RUNNING, jnp.int32), buf,
                jnp.asarray(jnp.inf, buf.dtype), z,
            )

        _PHASE_RESET_JIT = _reset
    return _PHASE_RESET_JIT(carry, reg0)


def drive_phase_plan(phases, state, reg0, max_iter, buf_cap, dtype,
                     report=None):
    """Host driver for a multi-phase segmented fused solve.

    ``phases`` is a list of ``(make_run_seg, stall_window,
    stall_patience_floor, seg_init, mode)`` where ``make_run_seg(bound) ->
    run_seg(carry, it_stop)`` builds the phase's device program around its
    global iteration bound and ``mode`` names its arithmetic ("f32",
    "mixed", "f64", "f64c", "pcg"). Each phase runs in an ``ipm.phase``
    span. Each phase gets its own ``max_iter`` budget;
    between phases the carry is reset via :func:`segment_phase_reset`.
    Returns ``(state, iterations, status, stats_buffer, reg)`` — ``reg``
    is the final phase's escalated regularization (still on device), so
    a follow-on finisher (the dense endgame) can seed from it instead of
    replaying known-bad factorizations — with the final RUNNING status
    mapped to STALL/MAXITER exactly as the fused loop would. ONE
    implementation shared by the dense and block backends so their
    termination semantics can never diverge.

    ``report`` (optional mutable list) receives one ``{"phase", "mode",
    "iters", "wall_s", "segments"}`` row per phase — the per-phase split
    the utilization artifacts and ``IPMResult.phase_report`` record,
    measured here because only the driver knows the phase boundaries.
    """
    import time as _time

    import jax.numpy as jnp

    carry = fresh_segment_carry(state, reg0, buf_cap, dtype)
    it, status = 0, STATUS_RUNNING
    window, patience, bound = 0, 0.0, max_iter
    best, since = float("inf"), 0
    for pi, (make_run_seg, window, patience, seg_init, mode) in enumerate(
        phases
    ):
        bound = it + max_iter
        it_before, t_ph = it, _time.perf_counter()
        run_seg, segments = make_run_seg(bound), 0

        def counted_seg(carry, stop, run_seg=run_seg):
            nonlocal segments
            segments += 1
            return run_seg(carry, stop)

        with obs_trace.span("ipm.phase", phase=pi, mode=mode):
            carry, (it, status, best, since) = drive_segments(
                counted_seg, carry, bound, window, seg_init,
                stall_patience_floor=patience, it0_status0=(it, status),
            )
        if report is not None:
            report.append({
                "phase": pi, "mode": mode, "iters": int(it - it_before),
                "wall_s": round(_time.perf_counter() - t_ph, 3),
                "segments": segments,
            })
        if pi < len(phases) - 1:
            carry = segment_phase_reset(carry, reg0)
            status = STATUS_RUNNING
    st, buf = carry[0], carry[5]
    if status == STATUS_RUNNING:
        stalled = (
            window
            and since > window
            and it < bound
            and (not patience or best > patience)
        )
        status = STATUS_STALL if stalled else STATUS_MAXITER
    return st, it, jnp.asarray(status, jnp.int32), buf, carry[2]


# Conservative opening-segment cap in auto mode: big enough that a small
# fast solve finishes in one or two segments, small enough that a ~4x
# error in the FLOP-rate model cannot push the (unmeasured) first device
# program past the execution watchdog before adaptation gets a data point.
SEG_OPEN_CAP = 32

# Conservative effective rates for watchdog seeding (ONE definition for
# every backend): f32 paths ride the MXU; f64 is software-emulated on TPU.
SEG_RATE_F32 = 2e12
SEG_RATE_F64 = 2.5e11


def use_segments(seg_cfg, platform: str) -> bool:
    """Whether a backend should host-segment its fused loop: explicit
    ``segment_iters=0`` disables, any positive value enables, and auto
    (None) enables exactly on TPU. The rule was made for the remote chip
    used before PR 21, which killed device programs that ran past ~60 s;
    whether it still pays on a local TPU is an open measurement."""
    if seg_cfg is None:
        return platform == "tpu"
    return seg_cfg > 0


def seg_open(seg_cfg, est_iter_seconds, target_s: float = 15.0) -> int:
    """Opening segment length: the FLOP-estimated iteration count toward
    ``target_s``, capped by SEG_OPEN_CAP in auto mode or by the user's
    explicit ``segment_iters``."""
    cap = seg_cfg if seg_cfg is not None else SEG_OPEN_CAP
    return max(1, min(cap, int(target_s / max(est_iter_seconds, 1e-3))))


def starting_point(ops: LinOps, data: ProblemData, cfg: StepParams) -> IPMState:
    """Mehrotra's least-squares starting point, extended to upper bounds.

    ``x̂ = Aᵀ(AAᵀ)⁻¹b`` (min-norm primal), ``ŷ = (AAᵀ)⁻¹Ac``, ``ŝ = c-Aᵀŷ``,
    then positive shifts sized so initial complementarity is balanced
    (Mehrotra 1992 §7 heuristic — standard, SURVEY.md §2 [INFERRED]).
    Bounded columns are clamped into (5%, 95%) of [0, u] and their dual is
    split ``s-z = ŝ`` with both parts positive, so r_d starts at 0 there.
    """
    xp = ops.xp
    c, b, u_f, hub = data.c, data.b, data.u_f, data.hub
    ones = xp.ones_like(c)
    factors = ops.factorize(ones)
    x_hat = ops.rmatvec(ops.solve(factors, b))
    y_hat = ops.solve(factors, ops.matvec(c))
    s_hat = c - ops.rmatvec(y_hat)

    dx = xp.maximum(-1.5 * xp.min(x_hat), 0.0)
    ds = xp.maximum(-1.5 * xp.min(s_hat), 0.0)
    x1 = x_hat + dx
    s1 = s_hat + ds
    xs = x1 @ s1
    dx_hat = dx + 0.5 * xs / xp.maximum(xp.sum(s1), 1e-30)
    ds_hat = ds + 0.5 * xs / xp.maximum(xp.sum(x1), 1e-30)
    floor = xp.asarray(1.0, dtype=c.dtype)
    x0 = xp.maximum(x_hat + dx_hat, floor * 1e-2)
    s0_free = xp.maximum(s_hat + ds_hat, floor * 1e-2)

    # Bounded columns: interior of [0, u] and positive dual split.
    x0 = xp.where(hub > 0, xp.clip(x0, 0.05 * u_f, 0.95 * u_f), x0)
    w0 = xp.where(hub > 0, u_f - x0, 1.0)
    pad = 1.0 + xp.abs(s_hat)
    s0 = xp.where(hub > 0, xp.maximum(s_hat, 0.0) + 0.1 * pad, s0_free)
    z0 = xp.where(hub > 0, s0 - s_hat, 0.0)
    return IPMState(x=x0, y=y_hat, s=s0, w=w0, z=z0)
