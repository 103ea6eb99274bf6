"""Dense single-device JAX backend — the default TPU execution path.

Implements the north-star architecture (BASELINE.json:5): the constraint
matrix lives in device HBM; normal-equations assembly ``A·diag(d)·Aᵀ``,
Cholesky, and the triangular solves run under one jitted step per IPM
iteration; the Mehrotra driver stays on the host. The whole iteration is a
single compiled XLA program so elementwise work fuses into the GEMMs and
only :class:`StepStats` scalars cross the host↔device boundary
(SURVEY.md §3.4).

Mixed precision: with ``config.factor_dtype="float32"`` the Cholesky runs
on the MXU in f32 and each triangular solve is polished by
``config.refine_steps`` rounds of iterative refinement against the f64
normal matrix — the SURVEY.md §7 mitigation for TPUs' emulated f64.

Regularization is a *traced* scalar argument of the jitted step, so the
driver's NaN-recovery escalation (reg ×= reg_grow) never recompiles.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from distributedlpsolver_tpu.backends.base import SolverBackend, register_backend
from distributedlpsolver_tpu.ipm import core
from distributedlpsolver_tpu.ipm.config import SolverConfig
from distributedlpsolver_tpu.ipm.state import IPMState, StepStats
from distributedlpsolver_tpu.models.problem import InteriorForm


# Matrix-entry count above which f64 ops on A run tiled: XLA's TPU f64
# emulation materializes ~8 full-size f32 component copies of each GEMM
# operand (observed: a 15 GB f32[8,50000,10000] temp at the 10k×50k
# reference shape — 3× HBM for ONE operand). Tiling every f64 contraction
# with A keeps each emulated operand at tile scale. 2²⁵ entries ⇒ ~1 GB
# of split temps per operand; 2²⁶ left the reference shape 665 MB over
# budget with overlapped double-buffered tiles.
_CHUNK_ENTRIES = 1 << 25


def _tile_rows(m: int, n: int) -> int:
    # ~_CHUNK_ENTRIES entries per tile, 8-row aligned (TPU sublane); never
    # larger than m itself (a slice size > operand size fails at trace).
    return min(m, max(8, (_CHUNK_ENTRIES // max(n, 1)) // 8 * 8))


def _normal_eq_chunked(A, d):
    """``A·diag(d)·Aᵀ`` with BOTH GEMM operands tiled (lax.fori_loop over
    row-block pairs; one compiled body, clamped dynamic slices — the last
    partial block is recomputed at a clamped offset, writing identical
    values, so no padding is needed)."""
    m, n = A.shape
    if m * n <= _CHUNK_ENTRIES:
        return (A * d[None, :]) @ A.T
    tile = _tile_rows(m, n)
    nblk = -(-m // tile)

    def ibody(ib, M):
        i0 = ib * tile
        Ci = jax.lax.dynamic_slice_in_dim(A, i0, tile, 0) * d[None, :]

        def jbody(jb, M):
            j0 = jb * tile
            Aj = jax.lax.dynamic_slice_in_dim(A, j0, tile, 0)
            return jax.lax.dynamic_update_slice(M, Ci @ Aj.T, (i0, j0))

        return jax.lax.fori_loop(0, nblk, jbody, M)

    return jax.lax.fori_loop(0, nblk, ibody, jnp.zeros((m, m), A.dtype))


# Above this many entries, f64 GEMVs on TPU run as elementwise
# multiply + reduction instead of a dot: XLA's emulated-f64 DOT lowering
# has pathological compile times at large operands (observed: 271 s for
# ONE 2048×10240 f64 GEMV; >90 min for the tiled 10000×50000 pair),
# while elementwise double-double ops compile in seconds and fuse with
# the reduce — the arithmetic is identical (exact f64 products, exact
# f64 accumulation), only the lowering differs.
_EW_F64_GEMV_ENTRIES = 1 << 24


def _use_ew_f64(A) -> bool:
    return (
        A.dtype == jnp.float64
        and A.shape[0] * A.shape[1] > _EW_F64_GEMV_ENTRIES
        and jax.default_backend() == "tpu"
    )


def _matvec_chunked(A, v):
    """``A @ v`` via row tiles (bounds emulated-f64 operand temps); the
    per-tile contraction is a dot, or multiply+sum on the ew-f64 path."""
    m, n = A.shape
    ew = _use_ew_f64(A)
    if not ew and m * n <= _CHUNK_ENTRIES:
        return A @ v
    if ew:
        contract = lambda Ai: jnp.sum(Ai * v[None, :], axis=1)
    else:
        contract = lambda Ai: Ai @ v
    tile = _tile_rows(m, n)
    nblk = -(-m // tile)

    def body(ib, out):
        i0 = ib * tile
        blk = contract(jax.lax.dynamic_slice_in_dim(A, i0, tile, 0))
        return jax.lax.dynamic_update_slice(out, blk, (i0,))

    return jax.lax.fori_loop(0, nblk, body, jnp.zeros((m,), A.dtype))


def _rmatvec_chunked(A, y):
    """``Aᵀ @ y`` as a sum of row-tile contributions.

    The clamped-slice trick is NOT safe for an accumulating loop (the last
    partial tile would double-count), so the ragged tail is handled as a
    separate term. The per-tile contraction is a dot, or multiply+sum on
    the ew-f64 path.
    """
    m, n = A.shape
    ew = _use_ew_f64(A)
    if not ew and m * n <= _CHUNK_ENTRIES:
        return A.T @ y
    if ew:
        contract = lambda Ai, yi: jnp.sum(Ai * yi[:, None], axis=0)
    else:
        contract = lambda Ai, yi: Ai.T @ yi
    tile = _tile_rows(m, n)
    nfull = m // tile

    def body(ib, acc):
        i0 = ib * tile
        Ai = jax.lax.dynamic_slice_in_dim(A, i0, tile, 0)
        yi = jax.lax.dynamic_slice_in_dim(y, i0, tile, 0)
        return acc + contract(Ai, yi)

    acc = jax.lax.fori_loop(0, nfull, body, jnp.zeros((n,), A.dtype))
    rem = m - nfull * tile
    if rem:
        acc = acc + contract(A[nfull * tile :], y[nfull * tile :])
    return acc


def _trsm_slabs(L, base, width, panel, out):
    """Columns ``[base, base+width)`` of ``L⁻¹`` by ``panel``-column TRSM
    slabs, accumulated into ``out`` (shape ``(m, width)``).

    ONE slab-solve body shared by the replicated build
    (:func:`_tri_inv_paneled`: base 0, width m) and the mesh-sharded
    build (:func:`_tri_inv_mesh`: each device its own slab range, traced
    ``base``). Full panels run in a fori_loop; a ragged final panel gets
    its own (differently-shaped) TRSM, so no padding of ``L`` is needed
    for panel alignment.
    """
    m = L.shape[0]
    nfull = width // panel
    if nfull:
        eye_t = jnp.eye(m, panel, dtype=L.dtype)  # column slab template

        def body(jb, acc):
            # slab = columns [base + jb·panel, … + panel) of the identity
            slab = jnp.roll(eye_t, base + jb * panel, axis=0)
            X = jax.scipy.linalg.solve_triangular(L, slab, lower=True)
            return jax.lax.dynamic_update_slice(acc, X, (0, jb * panel))

        out = jax.lax.fori_loop(0, nfull, body, out)
    rem = width - nfull * panel
    if rem:
        slab = jnp.roll(
            jnp.eye(m, rem, dtype=L.dtype), base + nfull * panel, axis=0
        )
        X = jax.scipy.linalg.solve_triangular(L, slab, lower=True)
        out = jax.lax.dynamic_update_slice(out, X, (0, nfull * panel))
    return out


def _tri_inv_paneled(L, panel: int = 512):
    """Explicit inverse of a lower-triangular ``L`` via paneled TRSM.

    ``solve_triangular(L, eye(m))`` asks XLA for one TRSM with m
    right-hand sides, whose blocked lowering materializes O(m/bs) full
    (k, m) temps at once — 15.4 GB at m=10000 (observed OOM). Solving the
    identity one ``panel``-column slab at a time inside a fori_loop keeps
    the temps at slab scale while doing the same m³/2 flops on the MXU.
    """
    m = L.shape[0]
    if m <= panel:
        return jax.scipy.linalg.solve_triangular(
            L, jnp.eye(m, dtype=L.dtype), lower=True
        )
    return _trsm_slabs(L, 0, m, panel, jnp.zeros((m, m), L.dtype))


def _tri_inv_mesh(L, prec_shard, panel: int = 512):
    """Column-sharded explicit triangular inverse over a device mesh.

    The replicated build (:func:`_tri_inv_paneled`) makes every device
    compute AND store all m² entries of ``L⁻¹``. The column slabs of the
    identity are independent TRSMs, so each device solves only its own
    slab range (``shard_map`` over the preconditioner axis): compute and
    storage both drop to 1/K per device, and the factor lands already
    laid out for the two sharded GEMVs of the preconditioner apply —
    the first cut of a distributed factorization (SURVEY.md §2.2;
    review round 2 item 5: "distribute panels over the mesh").
    """
    from jax.sharding import PartitionSpec

    from distributedlpsolver_tpu.parallel.mesh import (
        pvary_compat,
        shard_map_compat,
    )

    mesh = prec_shard.mesh
    axis = next(a for a in prec_shard.spec if a is not None)
    K = int(mesh.shape[axis])
    m = L.shape[0]
    # Pad ONLY to the mesh multiple (equal per-device slab widths) — the
    # ragged last panel is handled inside _trsm_slabs, so no rounding to
    # a panel multiple: at m=10000, K=8 the padded size stays 10000, not
    # the 12288 a K·panel rounding would cost in TRSM flops and storage.
    w = -(-m // K)  # per-device slab width
    mp = w * K
    Lp = L
    if mp != m:
        # Identity tail keeps the padded L triangular and invertible;
        # the pad region is sliced off after the shard_map.
        Lp = jnp.zeros((mp, mp), L.dtype)
        Lp = Lp.at[:m, :m].set(L)
        Lp = Lp.at[jnp.arange(m, mp), jnp.arange(m, mp)].set(1.0)

    def device_fn(Lfull):
        base = jax.lax.axis_index(axis) * w
        # The output is device-varying (each device fills different
        # slabs, via axis_index) — mark the zero init as varying over
        # the mesh axis or the slab loop's carry types mismatch under
        # shard_map.
        init = pvary_compat(jnp.zeros((mp, w), Lfull.dtype), (axis,))
        return _trsm_slabs(Lfull, base, w, panel, init)

    Linv = shard_map_compat(
        device_fn,
        mesh=mesh,
        in_specs=(PartitionSpec(None, None),),
        out_specs=PartitionSpec(None, axis),
    )(Lp)
    return Linv[:m, :m] if mp != m else Linv


def _pcg_ops(A, factor_dtype, use_pallas, Af, cg_tol, cg_iters,
             prec_shard=None):
    """factorize/solve closures for the mixed-precision PCG mode.

    The factorization builds only a PRECONDITIONER: f32 assembly (Pallas
    kernel or plain MXU GEMM on the precast copy) + f32 Cholesky + an
    explicit triangular inverse, so each preconditioner application is two
    f32 GEMVs instead of two sequential triangular solves (TPUs pipeline
    GEMVs; single-rhs TRSV serializes). Accuracy comes from the CG loop,
    whose operator applies the TRUE f64 ``A·diag(d)·Aᵀ (+reg·diag)``
    matrix-free via the chunked GEMVs — no f64 O(m²n) assembly and no f64
    O(m³) Cholesky ever runs, which is what makes the reference-scale
    10k×50k config (BASELINE.json:9) tractable on emulated-f64 hardware.
    """
    m = A.shape[0]

    def factorize(d, reg):
        # Everything in this preconditioner build must run at true-f32
        # matmul precision: the TPU DEFAULT lowers f32 matmuls (including
        # the ones inside cholesky and the paneled TRSM) to bf16
        # multiplies with ~1e-3 relative error — the Pallas kernel guards
        # itself with Precision.HIGHEST, but the factorization wouldn't.
        with jax.default_matmul_precision("highest"):
            return _factorize_impl(d, reg)

    def _factorize_impl(d, reg):
        df = d.astype(factor_dtype)
        if use_pallas:
            from distributedlpsolver_tpu.ops import normal_eq_pallas

            M = normal_eq_pallas(Af, df, out_m=m)
        else:
            M = (Af * df[None, :]) @ Af.T
        if prec_shard is not None:
            # Pin the assembly output to the factor's column sharding:
            # with A variable-sharded the GSPMD partials then combine by
            # REDUCE-SCATTER (each device keeps only its slab) instead of
            # the all-reduce that would materialize a replicated m² M on
            # every device — the first stage of the fully distributed
            # factorization (ops/dist_chol.py).
            M = jax.lax.with_sharding_constraint(M, prec_shard)
        diagM = jnp.diagonal(M)
        # Jacobi (unit-diagonal) symmetric scaling before the f32
        # factorization: late-IPM diagonals span ~10 orders, and an f32
        # Cholesky at that spread loses its small pivots' relative
        # accuracy — which is the preconditioner floor CG then has to
        # grind through. In the scaled space the relative diagonal
        # regularization becomes + reg·I exactly.
        s = jax.lax.rsqrt(jnp.maximum(diagM, jnp.finfo(factor_dtype).tiny))
        Ms = M * s[:, None] * s[None, :]
        Ms = Ms + jnp.asarray(reg, M.dtype) * jnp.eye(m, dtype=M.dtype)
        # The preconditioner APPLY must run in the iterate dtype: an f32
        # apply injects ~1e-7 nonlinear rounding noise per call, which
        # breaks plain CG's recurrences at late-IPM conditioning — the
        # true residual stagnates around 1e-7 while the recurrence
        # residual keeps "converging" (observed at 2048×10240: pinf
        # frozen at 2.7e-7; raising the CG budget made it WORSE, classic
        # stagnation drift). The FACTOR may be f32-accurate — cast it up
        # once per factorization so the apply is an exact fixed linear
        # operator and CG behaves like textbook PCG.
        if prec_shard is not None:
            # Fully distributed factorization (SURVEY.md §2.2 second cut):
            # panel Cholesky + blocked inversion inside shard_map — the
            # round-3 path (replicated cholesky + _tri_inv_mesh slabs)
            # still held full m² M and L on every device; this one never
            # materializes a replicated m×m anywhere, so per-device peak
            # is ~3·m²/K + the (m, panel) psum buffers.
            from distributedlpsolver_tpu.ops.dist_chol import (
                chol_tri_inv_mesh,
            )

            Linv = chol_tri_inv_mesh(Ms, prec_shard).astype(A.dtype)
            Linv = jax.lax.with_sharding_constraint(Linv, prec_shard)
        else:
            L = jnp.linalg.cholesky(Ms)
            Linv = _tri_inv_paneled(L).astype(A.dtype)
        return (
            Linv, s.astype(A.dtype), diagM.astype(A.dtype), d,
            jnp.asarray(reg, A.dtype),
        )

    def solve(factors, rhs):
        Linv, s, diagM, d, reg = factors
        regd = reg * diagM

        def op(v):
            return _matvec_chunked(A, d * _rmatvec_chunked(A, v)) + regd * v

        if prec_shard is not None:
            # Column-sharded L⁻¹: plain matmuls, partitioned by GSPMD —
            # the first contracts over the sharded axis (per-device GEMV
            # + psum), the second produces the sharded axis (per-device
            # GEMV + all-gather); both collectives ride ICI.
            def prec(r):
                z = Linv @ (s * r)
                return s * (Linv.T @ z)
        else:
            def prec(r):
                z = _matvec_chunked(Linv, s * r)
                return s * _rmatvec_chunked(Linv, z)

        return core.pcg_solve(op, prec, rhs, cg_tol, cg_iters)

    return factorize, solve


# ----------------------------------------------------------------------
# Endgame phase (huge-m full-precision finish, host-driven).
#
# At reference scale (10k×50k) one full-precision iteration exceeded the
# ~60 s program limit of the remote chip used before PR 21 if run as a
# single device program (see core.use_segments), and the
# f32-preconditioned PCG phase cannot finish the last ~1.5 orders of
# magnitude (the f32 assembly carries no information about M's smallest
# eigen-subspace once κ(M) > 1/ε_f32 — observed as a hard pinf floor at
# ~3e-7). The endgame splits ONE Mehrotra iteration into bounded
# dispatches — the tiled full-precision assembly, the factorization,
# then the step with the factor injected — so no single device program
# holds the whole iteration (review round 1 item 1: "segment at the
# factorization level"). The assembly dispatch is the longest at ~40 s
# estimated for 10k×50k; if a future shape pushes it past the watchdog,
# split it into row-range pieces next.
# ----------------------------------------------------------------------

# Endgame-local regularization ladder step. The fused phases escalate by
# cfg.reg_grow (default 100) — too coarse here: the emulated-f64
# Cholesky NaNs below a state-dependent threshold, the direction bias
# (and so the attainable pinf) scales LINEARLY with the reg actually
# used, and a ×100 ladder overshoots the minimal factorable reg by up
# to 100×. Factor+step retries cost ~2 s (assembly held), so the finer
# ladder is nearly free.
_EG_REG_GROW = 10.0


@functools.partial(jax.jit, static_argnames=("params",))
def _endgame_assemble(A, data, state, params):
    """Full-precision M = A·diag(d)·Aᵀ with d derived from the state
    exactly as mehrotra_step will. MUST go through the double-tiled
    contraction: a plain emulated-f64 GEMM at reference scale asks XLA
    for an 8×full-size f32 operand-split temp (observed: 15.07 GB for
    one half-assembly — the round-1 OOM, reproduced)."""
    d = core.scaling_d(state, data, params)
    return _normal_eq_chunked(A, d)


@functools.partial(jax.jit, static_argnames=("params",))
def _endgame_recenter(data, state, params):
    """Lift collapsed complementarity pairs to a centered band before the
    full-precision finish. A phase that ground at its f32 floor can leave
    pairs with x_i·s_i ≪ μ; the resulting d spans far enough that
    A·diag(d)·Aᵀ becomes numerically singular beyond ANY tolerable
    regularization (observed at 10k×50k: factorization unusable below
    reg 1e-6, which pins pinf at ~1e-5). Raising the smaller member of a
    collapsed pair to (0.01·μ)/partner perturbs the residuals by at most
    ‖A‖·Δ — negligible against the entry infeasibility — and restores a
    factorable Newton system. No-op on a well-centered state."""
    x, y, s, w, z = state
    hub = data.hub
    mu = (x @ s + (hub * w) @ z) / data.ncomp
    floor = 0.01 * mu

    def lift(a, b):
        need = a * b < floor
        a2 = jnp.where(need & (a <= b), floor / jnp.maximum(b, 1e-300), a)
        b2 = jnp.where(need & (b < a), floor / jnp.maximum(a, 1e-300), b)
        return a2, b2

    x2, s2 = lift(x, s)
    w2, z2 = lift(w, z)
    return IPMState(
        x=x2, y=y, s=s2,
        w=jnp.where(hub > 0, w2, w),
        z=jnp.where(hub > 0, z2, z),
    )


@jax.jit
def _cent_diag(data, state, gamma):
    """Centrality diagnostics of an iterate: (minprod/μ, #products below
    γ·μ, μ). Scalars only — the endgame loop records them per iteration
    so a blocked-step stall is attributable from the artifact alone (is
    the iterate outside N₋∞(γ), and how far?)."""
    x, _, s, w, z = state
    xs = x * s
    wz_on = jnp.where(data.hub > 0, w * z, jnp.inf)
    mu = (jnp.sum(xs) + jnp.sum(jnp.where(data.hub > 0, w * z, 0.0))) / data.ncomp
    minprod = jnp.minimum(jnp.min(xs), jnp.min(wz_on))
    below = jnp.sum(xs < gamma * mu) + jnp.sum(wz_on < gamma * mu)
    return minprod / jnp.maximum(mu, jnp.finfo(x.dtype).tiny), below, mu


@jax.jit
def _endgame_factor(M, reg):
    """Jacobi-scaled f64 Cholesky: factoring s·M·s (unit diagonal) cuts
    the FACTORED matrix's condition number by the diagonal's spread —
    late-IPM diagonals span many orders, and every order removed
    sharpens the refinement sweep's contraction (observed without it:
    ~1e-2 contraction at 10k, leaving ~1e-4 direction error after one
    sweep and a glacial 3%/iteration tail). The relative diagonal
    perturbation becomes + reg·I exactly in the scaled space."""
    diagM = jnp.diagonal(M)
    s = jax.lax.rsqrt(jnp.maximum(diagM, jnp.finfo(M.dtype).tiny))
    Ms = M * s[:, None] * s[None, :]
    Ms = Ms + jnp.asarray(reg, M.dtype) * jnp.eye(M.shape[0], dtype=M.dtype)
    return jnp.linalg.cholesky(Ms), s


@functools.partial(jax.jit, donate_argnums=(0,))
def _eg_scale_reg(M, reg):
    """Jacobi scale + diagonal reg shift (M donated — its buffer feeds
    the scaled copy; the shift is a diagonal scatter, not ``+ reg·eye``,
    which would materialize another m² buffer)."""
    diagM = jnp.diagonal(M)
    s = jax.lax.rsqrt(jnp.maximum(diagM, jnp.finfo(M.dtype).tiny))
    Ms = M * s[:, None] * s[None, :]
    rng_ = jnp.arange(M.shape[0])
    return Ms.at[rng_, rng_].add(jnp.asarray(reg, M.dtype)), s


def _endgame_factor_mxu(M, reg):
    """On-device Jacobi-scaled factor + EXPLICIT inverse through the
    GEMM-dominated panel kernels (ops/chol_mxu.py) — the round-5 endgame
    mode. Same scaling/reg convention as :func:`_endgame_factor`;
    returns ``(Linv, s)`` with ``(s·M·s + reg·I)⁻¹ = Linvᵀ·Linv``.
    Measured at m=10240: ~10 s warm on the chip — against the host
    path's ~20–33 s symmetric d2h transfer PLUS ~20–38 s LAPACK factor
    per iteration it replaces. Solve quality: effective ε ≈ 1.5e-13 at
    the degenerate-spectrum probe (double-double class; LAPACK is
    2.2e-16) — the step's true-operator refinement sweeps carry the
    difference (each sweep contracts by the solve's relres).

    Returns ``(L, Winv, s)`` — the padded in-place panel factor, its
    per-panel diagonal-block inverses, and the Jacobi scale; solves run
    as panel substitutions (ops/chol_mxu.py: panel_cho_solve). NO m×m
    inverse is ever formed: the fused factor+inverse's (T, X) while
    carry (~4 m² live under XLA double-buffering) and then even the
    stand-alone inversion's X/eye buffers OOM'd at 10k next to the
    resident 4 GB constraint matrix (observed three times, 2026-08-01).
    Peak here is ~2 m² (scale copy + factor carry), and M is donated
    into the scale stage; callers re-assemble on the rare bad-step
    retry instead of holding M across the factor."""
    from distributedlpsolver_tpu.ops.chol_mxu import chol_mxu_factor

    Ms, s = _eg_scale_reg(M, reg)
    L, Winv = chol_mxu_factor(Ms)
    return L, Winv, s


@functools.partial(jax.jit, static_argnames=("params", "refine", "closure_sweeps"))
def _endgame_step_mxu(A, data, state, Linv_s, reg, diagM, params, refine=2,
                      closure=None, closure_sweeps=1):
    """One Mehrotra step with the on-device panel factor injected:
    every solve is a pair of panel triangular substitutions plus
    ``refine`` true-operator sweeps (matrix-free exact f64 residual —
    never forms M). ``closure``
    (the f32 AAᵀ factor pair from _closure_factors) feeds the
    direction-level primal closure exactly as in the PCG phases —
    pure-jax, so unlike the host endgame the whole step stays ONE device
    program (no eager per-op dispatches, no host round trips at all).
    KKT-level refinement runs params.kkt_refine rounds (auto 1 via
    SolverConfig.endgame_kkt_refine — the panel solves made the rounds
    cheap; ROUND5_NOTES lever 1); the solve-level sweeps own the
    factor-rounding recovery either way."""
    from distributedlpsolver_tpu.ops.chol_mxu import panel_cho_solve

    d_scale = core.scaling_d(state, data, params)

    def solve(Lf, rhs):
        L, Winv, s = Lf  # panel factor of s·M·s + reg·I (scaled space)
        x = s * panel_cho_solve(L, Winv, s * rhs)
        for _ in range(refine):
            Mx = _matvec_chunked(A, d_scale * _rmatvec_chunked(A, x))
            r = rhs - Mx - reg * diagM * x
            x = x + s * panel_cho_solve(L, Winv, s * r)
        return x

    pp = None
    if closure is not None:
        LinvG, sG = closure

        def prec(r):
            z = LinvG @ (sG * r).astype(LinvG.dtype)
            return sG * (LinvG.T @ z).astype(sG.dtype)

        def pp(rv):
            t = prec(rv)
            for _ in range(closure_sweeps):
                rr = rv - _matvec_chunked(A, _rmatvec_chunked(A, t))
                t = t + prec(rr)
            return _rmatvec_chunked(A, t)

    ops = core.LinOps(
        xp=jnp,
        matvec=lambda v: _matvec_chunked(A, v),
        rmatvec=lambda v: _rmatvec_chunked(A, v),
        factorize=lambda d: Linv_s,
        solve=solve,
        primal_project=pp,
    )
    return core.mehrotra_step(ops, data, params, state)


def _endgame_step_params(cfg, host_mode: bool = False):
    """StepParams of the endgame's split-dispatch Mehrotra step — ONE
    definition of the endgame's KKT-refinement policy (ROUND5_NOTES
    lever 1, test-pinned).

    Device/mxu modes run ``cfg.endgame_kkt_refine`` KKT-level rounds
    (auto: 1 — the old hardwired 0 was a host-era program-size
    constraint; the round-5 panel factorization made each refinement's
    solves cheap panel substitutions, and one round recovers the
    cancellation digits the regularized back-substitution loses right
    where the terminal μ-stall cycle burns iterations). Host mode caps
    at ``min(cfg.kkt_refine, 1)`` regardless: each eager round is a
    full host solve + device residual pair against a direction the
    host solve already operator-refined internally."""
    if host_mode:
        refine = min(cfg.kkt_refine, 1)
    else:
        refine = (
            1 if cfg.endgame_kkt_refine is None else cfg.endgame_kkt_refine
        )
    return cfg.replace(kkt_refine=refine).step_params(mcc=cfg.endgame_mcc)


@functools.partial(jax.jit, static_argnames=("params", "refine"))
def _endgame_step(A, data, state, Ls, reg, diagM, params, refine=1):
    """One Mehrotra step with the factorization INJECTED (computed by the
    preceding dispatches); solves run through the regularized
    Jacobi-scaled f64 factor with ``refine`` exact-residual sweeps.

    The REGULARIZED solve is the right object at this conditioning:
    CG on the exact operator was tried and cannot converge — the
    preconditioned spectrum λ/(λ+reg·d) still spans ~1e11 at the real
    late-IPM eigenvalue cluster (measured: 80 preconditioned sweeps
    bought <1e-3 residual reduction), while the Tikhonov-filtered
    direct solve yields usable directions whose bias scales with reg.
    Accuracy therefore hinges on running at the SMALLEST factorable reg
    (the emulated-f64 Cholesky NaNs below a state-dependent threshold —
    see the ×10 retry ladder in _endgame_loop), with the refinement
    sweep (matrix-free exact f64 residual of the regularized system)
    recovering full solve quality against factor rounding. KKT-level
    refinement runs params.kkt_refine rounds (SolverConfig.
    endgame_kkt_refine, auto 1 — restored by ROUND5_NOTES lever 1; set
    it to 0 where program size binds, e.g. a compiler whose response
    drops mid-compile)."""
    d_scale = core.scaling_d(state, data, params)

    def factorize(d):
        return Ls

    def solve(Lf, rhs):
        L, s = Lf  # Jacobi-scaled factor: (M+regD)⁻¹ = s·(LLᵀ)⁻¹·s
        x = s * jax.scipy.linalg.cho_solve((L, True), s * rhs)
        for _ in range(refine):
            Mx = _matvec_chunked(A, d_scale * _rmatvec_chunked(A, x))
            r = rhs - Mx - reg * diagM * x
            x = x + s * jax.scipy.linalg.cho_solve((L, True), s * r)
        return x

    ops = core.LinOps(
        xp=jnp,
        matvec=lambda v: _matvec_chunked(A, v),
        rmatvec=lambda v: _rmatvec_chunked(A, v),
        factorize=factorize,
        solve=solve,
    )
    return core.mehrotra_step(ops, data, params, state)


# ----------------------------------------------------------------------
# Host-factor endgame (the true-f64 finish on emulated-f64 hardware).
#
# Measured on the 10k×50k reference config (BENCH_10K.json, round 3
# pre-host): the emulated-f64 (double-double) Cholesky NaNs below
# reg ≈ 1e-7 on the real late-IPM spectrum, and the reg actually used
# floors both μ (≈3e-10 — steps at α≈1 stop reducing complementarity
# because the solve error dominates the corrector RHS) and pinf
# (sublinear in reg: 1.24e-5 at 1e-6, 8.0e-6 at 1e-7). Host LAPACK f64
# (ε = 2.2e-16 vs the double-double's effective ≈4e-15, plus LAPACK's
# guarded pivots instead of NaN propagation) factors the same matrices
# at reg ≈ 1e-11 — four orders less Tikhonov bias. Only the m×m factor
# and the m-vector triangular solves cross to the host; the O(m²·n)
# assembly and every refinement matvec stay on device. The step runs
# core.mehrotra_step EAGERLY. It was written so because the remote
# chip's runtime used before PR 21 rejected pure_callback, so the host
# solve could not be injected into a jitted program (eager op latency
# ~28 ms and 80 KB host↔device hops ~100 ms were measured there). PR 21
# found that the installed stack compiles a pure_callback for a v5e
# ahead of time, so that reason no longer holds; the eager step is kept
# until a chip measurement says whether a jitted step pays.
# ----------------------------------------------------------------------


def _fetch_symmetric(M, pieces: int = 32):
    """Device→host transfer of a symmetric matrix by its LOWER TRIANGLE
    only, in ``pieces`` equal-area row blocks (block k = rows
    ``[m·√(k/p), m·√((k+1)/p))``, columns ``[:row_end)``), then mirrored
    on host. Each block over-fetches its upper wedge, so the transferred
    fraction is ~(0.5 + 0.4/p)·m²: 0.60·m² at p=8, 0.53·m² at the
    default 32 (measured at m=10000) — block-count host overhead is
    negligible against the transfer itself.

    The d2h copy was the host endgame's single largest cost at 10k scale
    (~45–73 s per iteration for the 800 MB M from the remote chip used
    before PR 21; not re-measured on a local chip — vs ~11 s
    assembly and ~15 s factorization — BENCH_10K.json timings), and M is
    always symmetric here; halving the bytes takes ~40% off the whole
    endgame iteration. Host mirror + block copies are ~0.5 s of numpy.
    """
    import math

    m = M.shape[0]
    out = np.empty((m, m), np.float64)
    bounds = [round(m * math.sqrt(k / pieces)) for k in range(pieces + 1)]
    bounds[-1] = m
    for k in range(pieces):
        i0, i1 = bounds[k], bounds[k + 1]
        if i1 > i0:
            out[i0:i1, :i1] = np.asarray(M[i0:i1, :i1])
    # Mirror blockwise from the transferred lower part (each block already
    # carries its own upper wedge since its columns run to the row end) —
    # a triu_indices mirror would allocate ~1.2 GB of index/gather temps
    # at m=10k, defeating the transfer saving.
    for k in range(pieces):
        i0, i1 = bounds[k], bounds[k + 1]
        if i1 > i0 and i0 > 0:
            out[:i0, i0:i1] = out[i0:i1, :i0].T
    return out


def _endgame_factor_host(Mh, reg):
    """True-f64 host (LAPACK) Cholesky of the Jacobi-scaled, regularized
    system: factors ``s·Mh·s + reg·I`` (unit diagonal — same scaling
    rationale as :func:`_endgame_factor`). Returns ``(L, s)`` or None if
    the factorization fails at this reg (caller escalates the ladder;
    retries re-use the SAME host copy — no device re-assembly or
    re-transfer)."""
    import scipy.linalg as sla

    dg = np.diagonal(Mh)
    if not np.all(np.isfinite(dg)) or np.any(dg < 0.0):
        # These diagonals are sums of nonnegative terms (Σ d_j·A_ij², plus
        # reg·diagM) — a negative or non-finite entry means upstream
        # corruption no reg in the ladder can repair; bail before the
        # Jacobi scaling overflows on 1/sqrt of it. An EXACTLY-zero entry
        # is legitimate (zero row ⇒ its off-diagonals are zero too): clamp
        # it, so the scaled row is zero and the +reg shift makes it PD.
        return None
    s = 1.0 / np.sqrt(np.maximum(dg, np.finfo(np.float64).tiny))
    Ms = Mh * s[:, None]
    Ms *= s[None, :]
    Ms[np.diag_indices_from(Ms)] += reg
    try:
        L = sla.cholesky(Ms, lower=True, overwrite_a=True, check_finite=False)
    except np.linalg.LinAlgError:
        return None
    # potrf breakdown propagates NaN down-column, so the full diagonal of
    # L (O(m)) witnesses any column breakdown anywhere in the factor.
    if not np.all(np.isfinite(np.diagonal(L))):
        return None
    return L, s


@jax.jit
def _eg_op_residual(A, d, diagM, reg, xv, rhs):
    """``rhs − (A·diag(d)·Aᵀ + reg·diag(diagM))·x`` — the true-operator
    refinement residual, one device dispatch per sweep (exact emulated-f64
    matvec pair; never forms M)."""
    return rhs - _matvec_chunked(A, d * _rmatvec_chunked(A, xv)) - reg * diagM * xv


def _endgame_step_host(A, data, state, hostf, reg, diagM, params, refine=1,
                       restore=None):
    """One Mehrotra step with the factorization resident on the HOST in
    true f64. ``core.mehrotra_step`` runs eagerly (one implementation of
    the step shared with every other path) over ops whose solve ships the
    m-vector RHS to host LAPACK and refines against the true operator on
    device. KKT-level refinement is affordable again here (no device
    program to size-limit), restoring the cancellation digits the
    device endgame had to give up (see core._solve_kkt's rationale).
    ``restore`` (the AAᵀ primal closure from _build_host_projector)
    makes every back-substituted dx exactly primal-feasible — see
    core.LinOps.primal_project."""
    import scipy.linalg as sla

    L, sh = hostf
    d_scale = core.scaling_d(state, data, params)
    regj = jnp.asarray(reg, diagM.dtype)

    def host_tri(rh):
        return sh * sla.cho_solve((L, True), sh * rh, check_finite=False)

    def solve(_, rhs):
        rhs_h = np.asarray(rhs)
        xh = host_tri(rhs_h)
        for _ in range(refine):
            r = np.asarray(
                _eg_op_residual(A, d_scale, diagM, regj, jnp.asarray(xh), rhs)
            )
            xh = xh + host_tri(r)
        return jnp.asarray(xh)

    ops = core.LinOps(
        xp=jnp,
        matvec=lambda v: _matvec_chunked(A, v),
        rmatvec=lambda v: _rmatvec_chunked(A, v),
        factorize=lambda d: None,
        solve=solve,
        primal_project=restore,
    )
    return core.mehrotra_step(ops, data, params, state)


@jax.jit
def _eg_pinf(A, data, x, w):
    """Relative primal infeasibility of (x, w) — the projector's accept
    test, same normalization as core.residual_norms."""
    r_p = data.b - _matvec_chunked(A, x)
    r_u = data.hub * (data.u_f - x - w)
    return jnp.sqrt(jnp.sum(r_p * r_p) + jnp.sum(r_u * r_u)) / data.norm_b


@jax.jit
def _eg_w_op_residual(A, wdiag, t, r):
    """``r − (A·diag(w)·Aᵀ)·t`` — projector refinement residual."""
    return r - _matvec_chunked(A, wdiag * _rmatvec_chunked(A, t))


def _build_host_projector(A, data, trace=False):
    """Primal feasibility restoration by alternating projections.

    The diagnosed terminal-pinf wall (BENCH_10K.json round-3 analysis) is
    the near-null-space component of the feasibility RHS: the IPM's
    *weighted* normal matrix A·D²·Aᵀ collapses exactly the directions
    that component needs (D → 0 on nonbasic columns), so no regularized
    solve of it can restore Ax = b — and for the same reason ANY
    x-derived reweighting fails structurally (a capped-weight variant
    min ‖W^{-1/2}Δx‖, W = min(x, τ)², was tried first: the residual
    component lives in directions reachable only through the tiny-x
    columns W zeroes out, so its Δx explodes on the capped set, every
    clamp fires, and the accept test rejects — observed at 10k×50k,
    entry pinf 1.54e-5 unimproved). This projector instead alternates
    between the two constraint sets directly (POCS):

        repeat: x ← x + Aᵀ·(A·Aᵀ)⁻¹·(b − A·x)   (affine projection)
                x ← clamp to the box (x > 0, x < u) (box projection)

    The affine step goes through the UNWEIGHTED A·Aᵀ — well-conditioned
    for any full-row-rank A, no IPM scaling involved — so each round is
    numerically clean; the clamp re-pollutes Ax = b only through the
    (tiny, nonbasic) columns the affine step pushed negative, and the
    alternation contracts toward the intersection (both sets convex,
    intersection = the feasible region, nonempty). Rounds stop when pinf
    stops improving; the best iterate is accepted only if it beat the
    entry. A·Aᵀ is assembled on device, factored ONCE on host (true
    f64); each round is two device matvecs + one refined host solve.
    Returns ``project(state, rounds=...) -> (state', pinf_before,
    pinf_after)`` or None if no factorization succeeded.
    """
    import time as _time

    ones = jnp.ones((A.shape[1],), A.dtype)
    t0 = _time.perf_counter()
    G = _normal_eq_chunked(A, ones)
    jax.block_until_ready(G)
    Gh = _fetch_symmetric(G)
    del G
    hostf = None
    reg = 1e-12
    while reg <= 1e-4:
        hostf = _endgame_factor_host(Gh, reg)
        if hostf is not None:
            break
        reg *= 100.0
    del Gh
    if hostf is None:
        return None
    if trace:
        import sys as _sys

        print(
            f"[endgame] projector (AAᵀ) built in "
            f"{_time.perf_counter() - t0:.1f}s (reg={reg:.1e})",
            file=_sys.stderr, flush=True,
        )
    L, sh = hostf

    def host_tri(rh):
        import scipy.linalg as sla

        return sh * sla.cho_solve((L, True), sh * rh, check_finite=False)

    def restore(rv):
        """``rv (m,) ↦ Aᵀ·(A·Aᵀ)⁻¹·rv (n,)`` — one refined host solve.
        The exact primal-row closure injected into the endgame step's
        KKT back-substitution (core.LinOps.primal_project): correcting
        the DIRECTION keeps feasibility decaying as (1−α) per iteration
        without touching the iterate (iterate-space repair was measured
        to inflate μ 4 orders and crush step lengths at 10k×50k)."""
        th = host_tri(np.asarray(rv))
        res = np.asarray(_eg_w_op_residual(A, ones, jnp.asarray(th), rv))
        th = th + host_tri(res)
        return _rmatvec_chunked(A, jnp.asarray(th))

    def project(st, rounds=6):
        pinf0 = float(_eg_pinf(A, data, st.x, st.w))
        x, w = st.x, st.w
        best_x, best_w, best = x, w, pinf0
        prev = pinf0
        for _ in range(rounds):
            r = data.b - _matvec_chunked(A, x)
            th = host_tri(np.asarray(r))
            res = np.asarray(_eg_w_op_residual(A, ones, jnp.asarray(th), r))
            th = th + host_tri(res)
            x2 = x + _rmatvec_chunked(A, jnp.asarray(th))
            # Box projection, kept strictly interior: a column pushed
            # nonpositive keeps 10% of its current value (the IPM needs
            # x > 0; exact-0 clamping would also collapse the next d).
            x2 = jnp.where(x2 > 0, x2, 0.1 * x)
            x2 = jnp.where(
                (data.hub > 0) & (x2 >= data.u_f),
                x + 0.5 * (data.u_f - x),
                x2,
            )
            w2 = jnp.where(data.hub > 0, data.u_f - x2, w)
            p = float(_eg_pinf(A, data, x2, w2))
            if p < best:
                best, best_x, best_w = p, x2, w2
            if not (p < 0.9 * prev):
                break  # alternation has stopped paying
            prev = p
            x, w = x2, w2
        if best < pinf0:
            return st._replace(x=best_x, w=best_w), pinf0, best
        return st, pinf0, pinf0

    project.restore = restore
    return project


def _use_chol_mxu(factor_dtype) -> bool:
    """Route f64 factorizations to the GEMM-dominated panel
    factor+inverse (ops/chol_mxu.py). Auto: exactly on TPU, where the
    builtin emulated-f64 cholesky is ~10× slower (measured) — CPU/LAPACK
    paths are left alone. TPULP_CHOL_MXU=1/0 overrides (tests exercise
    the kernel on the CPU mesh with it). The flag is read at TRACE time
    and is not part of any jit cache key: set it at process start (or
    jax.clear_caches() after changing it) — flipping it mid-process
    leaves already-compiled shapes on their old route."""
    import os

    if jnp.dtype(factor_dtype) != jnp.dtype(jnp.float64):
        return False
    env = os.environ.get("TPULP_CHOL_MXU", "")
    if env in ("0", "1"):
        return env == "1"
    return jax.default_backend() == "tpu"


def _cholesky_ops(A, factor_dtype, refine_steps, use_pallas=False, Af=None):
    """Build factorize/solve closures over a (traced) matrix ``A``.

    ``factorize(d, reg)`` returns ``(L, M)`` with ``M = A·diag(d)·Aᵀ``
    plus a per-row relative diagonal perturbation, ``M`` kept at full
    precision for refinement and ``L`` its (possibly lower-precision)
    Cholesky factor.

    With ``use_pallas`` the assembly runs through the fused Pallas kernel
    (ops/normal_eq.py) in ``factor_dtype`` — no scaled-matrix HBM
    round trip. Only auto-selected when ``factor_dtype`` is single
    precision on a TPU with no normal-equations-level refinement
    (refinement wants the full-precision M this path never forms).
    """

    def factorize(d, reg):
        if use_pallas:
            from distributedlpsolver_tpu.ops import normal_eq_pallas

            # Af is the loop-invariant precast, pre-padded copy from setup —
            # casting or tile-padding A here would re-materialize an m×n
            # array every iteration. M stays in factor_dtype: the pallas
            # path requires refine_steps == 0, so the full-precision M the
            # refinement loop would read is never consumed — casting up to
            # A.dtype would be an m×m f64 HBM round trip of pure waste.
            M = normal_eq_pallas(Af, d.astype(factor_dtype), out_m=A.shape[0])
        elif Af is not None:
            # Plain-XLA low-precision assembly on the precast copy: the
            # O(m²n) GEMM runs in factor_dtype on the MXU instead of
            # emulated f64 (two-phase phase 1 off-TPU-pallas / sharded).
            M = (Af * d.astype(Af.dtype)[None, :]) @ Af.T
        else:
            M = _normal_eq_chunked(A, d)
        # Per-row *relative* diagonal perturbation: with heterogeneous d the
        # diagonal spans many orders of magnitude, and a uniform (trace- or
        # norm-scaled) shift would swamp the small rows and wreck the
        # Newton direction's primal-residual reduction.
        M = M + jnp.diag(jnp.asarray(reg, M.dtype) * jnp.diagonal(M))
        if inv_mxu:
            # f64 on TPU: XLA's emulated-f64 cholesky/cho_solve lower to
            # scalarized recurrences (~345 ms + ~20 ms/solve measured at
            # the (128,128,128) batched shape) while emulated-f64 GEMM is
            # fast and 2e-15-accurate — use the GEMM-dominated panel
            # factor+inverse instead (ops/chol_mxu.py, ~10× measured).
            from distributedlpsolver_tpu.ops.chol_mxu import chol_inv_mxu

            return chol_inv_mxu(M.astype(factor_dtype)), M
        L = jnp.linalg.cholesky(M if M.dtype == factor_dtype else M.astype(factor_dtype))
        if explicit_inv:
            # Large-m f32 path on TPU: one paneled inverse per
            # factorization turns every subsequent triangular solve into
            # two GEMVs — XLA's single-rhs TRSV serializes badly at this
            # scale, and each factorization serves ≥6 solves.
            return _tri_inv_paneled(L), M
        return L, M

    m_ = A.shape[0]
    explicit_inv = (
        jnp.dtype(factor_dtype) == jnp.dtype(jnp.float32)
        and m_ >= 2048
        and jax.default_backend() == "tpu"
    )
    inv_mxu = _use_chol_mxu(factor_dtype)

    def _apply_inv(factors, rhs32):
        if explicit_inv or inv_mxu:
            Linv, _ = factors
            return Linv.T @ (Linv @ rhs32)
        L, _ = factors
        return jax.scipy.linalg.cho_solve((L, True), rhs32)

    def solve(factors, rhs):
        y = _apply_inv(factors, rhs.astype(factor_dtype)).astype(rhs.dtype)
        M = factors[1]
        for _ in range(refine_steps):
            r = rhs - _matvec_chunked(M, y)
            y = y + _apply_inv(factors, r.astype(factor_dtype)).astype(
                rhs.dtype
            )
        return y

    return factorize, solve


@jax.jit
def _closure_from_G(G):
    """Shared factor body of the primal-row closure: Jacobi-scale the
    Gram matrix ``G = A·Aᵀ``, shift, f32 Cholesky, paneled explicit
    inverse. One definition for both assembly routes (plain f32 GEMM
    and the Pallas-padded kernel) so the shift/scaling can never
    silently diverge between them. Unlike the per-iteration A·D²·Aᵀ,
    G carries no IPM scaling, so its conditioning never degrades as
    μ → 0; the small relative shift keeps the f32 Cholesky robust and
    washes out under the closure's true-operator refinement sweeps."""
    with jax.default_matmul_precision("highest"):
        dG = jnp.diagonal(G)
        s = jax.lax.rsqrt(jnp.maximum(dG, jnp.finfo(jnp.float32).tiny))
        Gs = G * s[:, None] * s[None, :]
        Gs = Gs + jnp.asarray(1e-6, jnp.float32) * jnp.eye(
            G.shape[0], dtype=jnp.float32
        )
        L = jnp.linalg.cholesky(Gs)
        Linv = _tri_inv_paneled(L)
    return Linv, s.astype(jnp.float64)


@jax.jit
def _closure_factors(A32v):
    """f32 factor of the LOOP-INVARIANT ``G = A·Aᵀ`` from an unpadded
    f32 copy — built once per problem, it powers the primal-row closure
    (core.LinOps.primal_project) of every PCG-plan phase."""
    with jax.default_matmul_precision("highest"):
        G = A32v @ A32v.T
    return _closure_from_G(G)


def _make_ops(
    A, reg, factor_dtype, refine_steps, use_pallas=False, Af=None,
    cg_iters=0, cg_tol=0.0, prec_shard=None, closure=None, closure_sweeps=0,
):
    if cg_iters > 0:
        factorize, solve = _pcg_ops(
            A, factor_dtype, use_pallas, Af, cg_tol, cg_iters, prec_shard
        )
    else:
        factorize, solve = _cholesky_ops(
            A, factor_dtype, refine_steps, use_pallas, Af
        )
    pp = None
    if closure is not None:
        # Direction-level primal closure δ = Aᵀ·(A·Aᵀ)⁻¹·rv (see
        # core.LinOps.primal_project and core._solve_kkt): the f32
        # factor is applied through the Jacobi scaling; each refinement
        # sweep re-evaluates the TRUE operator A·(Aᵀt) at iterate
        # precision. Pure jax — runs inside fused/jitted programs.
        LinvG, sG = closure

        def prec(r):
            z = LinvG @ (sG * r).astype(LinvG.dtype)
            return sG * (LinvG.T @ z).astype(sG.dtype)

        def pp(rv):
            t = prec(rv)
            for _ in range(closure_sweeps):
                rr = rv - _matvec_chunked(A, _rmatvec_chunked(A, t))
                t = t + prec(rr)
            return _rmatvec_chunked(A, t)

    return core.LinOps(
        xp=jnp,
        matvec=lambda v: _matvec_chunked(A, v),
        rmatvec=lambda v: _rmatvec_chunked(A, v),
        factorize=functools.partial(factorize, reg=reg),
        solve=solve,
        primal_project=pp,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "params", "factor_dtype", "refine_steps", "use_pallas", "cg_iters",
        "cg_tol", "prec_shard",
    ),
)
def _dense_step(
    A, data, state, reg, params, factor_dtype, refine_steps, use_pallas=False,
    Af=None, cg_iters=0, cg_tol=0.0, prec_shard=None,
):
    ops = _make_ops(
        A, reg, jnp.dtype(factor_dtype), refine_steps, use_pallas, Af,
        cg_iters, cg_tol, prec_shard,
    )
    return core.mehrotra_step(ops, data, params, state)


@functools.partial(
    jax.jit,
    static_argnames=(
        "params", "factor_dtype", "refine_steps", "use_pallas", "cg_iters",
        "cg_tol", "prec_shard",
    ),
)
def _dense_start(
    A, data, reg, params, factor_dtype, refine_steps, use_pallas=False,
    Af=None, cg_iters=0, cg_tol=0.0, prec_shard=None,
):
    ops = _make_ops(
        A, reg, jnp.dtype(factor_dtype), refine_steps, use_pallas, Af,
        cg_iters, cg_tol, prec_shard,
    )
    return core.starting_point(ops, data, params)


@functools.partial(
    jax.jit,
    static_argnames=(
        "params", "factor_dtype", "refine_steps", "buf_cap", "use_pallas",
        "stall_window", "cg_iters", "cg_tol", "prec_shard",
    ),
)
def _dense_solve_full(
    A, data, state0, reg0, params, factor_dtype, refine_steps, max_iter, max_refactor, reg_grow,
    buf_cap, use_pallas=False, Af=None, stall_window=0, cg_iters=0, cg_tol=0.0,
    prec_shard=None,
):
    # max_iter / max_refactor / reg_grow are traced scalars: one compiled
    # executable serves every iteration-limit config (only the bucketed
    # buf_cap is a jit key), so warm-up runs share the timed run's compile.
    def step(state, reg):
        ops = _make_ops(
            A, reg, jnp.dtype(factor_dtype), refine_steps, use_pallas, Af,
            cg_iters, cg_tol, prec_shard,
        )
        return core.mehrotra_step(ops, data, params, state)

    return core.fused_solve(
        step, state0, reg0, params, max_iter, max_refactor, reg_grow, buf_cap,
        stall_window=stall_window, stall_patience_floor=1e3 * params.tol,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "params", "factor_dtype", "refine_steps", "buf_cap", "use_pallas",
        "stall_window", "patience", "cg_iters", "cg_tol", "prec_shard",
        "closure_sweeps",
    ),
)
def _dense_segment(
    A, data, carry, it_stop, max_iter, max_refactor, reg_grow,
    params, factor_dtype, refine_steps, buf_cap, use_pallas=False, Af=None,
    stall_window=0, patience=0.0, cg_iters=0, cg_tol=0.0, prec_shard=None,
    closure=None, closure_sweeps=0,
):
    """One bounded continuation of the fused loop (host segmentation —
    see core.drive_segments). ``carry`` is the raw fused_solve carry;
    ``max_iter`` here is the phase's global iteration bound (phase start +
    per-phase budget)."""

    def step(state, reg):
        ops = _make_ops(
            A, reg, jnp.dtype(factor_dtype), refine_steps, use_pallas, Af,
            cg_iters, cg_tol, prec_shard, closure, closure_sweeps,
        )
        return core.mehrotra_step(ops, data, params, state)

    out = core.fused_solve(
        step, None, None, params, max_iter, max_refactor, reg_grow, buf_cap,
        stall_window=stall_window, stall_patience_floor=patience,
        resume=carry, it_stop=it_stop, return_carry=True,
    )
    return out, core.pack_segment_meta(out)


@functools.partial(
    jax.jit,
    static_argnames=(
        "params", "params_p1", "refine_steps", "buf_cap", "pallas_p1",
        "stall_window",
    ),
)
def _dense_solve_two_phase(
    A, A32, data, state0, reg0, params, params_p1, max_iter, max_refactor,
    reg_grow, buf_cap, refine_steps, pallas_p1, stall_window,
):
    """Mixed-precision fused solve: f32 factorizations (MXU-native) down to
    the handoff tolerance, then f64 warm-started from the same iterate —
    one compiled program, one stats buffer, global iteration count.

    Phase 1 is pure speed (every factorization + assembly in f32, KKT
    residuals/refinement still f64) and runs under ``params_p1``, whose
    loosened tol both ends the phase at the handoff point and keys the
    μ-floor so the iterate stays centered — grinding f32 at its ~1e-6
    noise floor instead injures the iterate beyond f64 repair (observed:
    handing over a stalled iterate leaves even f64 stuck). Phase 2 always
    re-enters at full precision/tolerance: a phase-1 "optimal" is only
    optimal at the handoff tol, and a phase-1 numerical failure deserves an
    f64 retry, so both reset to RUNNING. This is the SURVEY.md §7
    mixed-precision design, scheduled rather than per-solve-chosen.
    """
    f32 = jnp.dtype(jnp.float32)

    def step32(state, reg):
        ops = _make_ops(A, reg, f32, 0, pallas_p1, A32)
        return core.mehrotra_step(ops, data, params_p1, state)

    def step64(state, reg):
        # Full-accuracy phase: a true-f64 direct factorization. (PCG
        # solves never reach this program — solve_full routes every
        # pcg+two_phase config through the segmented plan, which owns
        # the f32-preconditioned phase and its full-precision finish.)
        ops = _make_ops(A, reg, A.dtype, refine_steps, False, None)
        return core.mehrotra_step(ops, data, params, state)

    st1, it1, status1, buf = core.fused_solve(
        step32, state0, reg0, params_p1, max_iter, max_refactor, reg_grow,
        buf_cap, stall_window=stall_window, finalize=False,
    )
    # Every phase-1 verdict is provisional: "optimal" is only optimal at
    # the handoff tol, a numerical failure deserves an f64 retry, and the
    # infeasibility heuristics can misfire on f32 factorization error —
    # phase 2 re-derives all of them at full precision.
    status1 = jnp.full_like(status1, core.STATUS_RUNNING)
    # Phase 2 gets its own max_iter budget beyond the phase-1 iterations
    # (it1 + max_iter), matching the batched/segmented paths.
    return core.fused_solve(
        step64, st1, reg0, params, it1 + max_iter, max_refactor, reg_grow,
        buf_cap, stall_window=2 * stall_window if stall_window else 0,
        stall_patience_floor=1e3 * params.tol,
        carry_in=(it1, status1, buf), finalize=True,
    )


@register_backend("tpu", "dense", "jax")
class DenseJaxBackend(SolverBackend):
    """Single-device dense path (afiro / random-dense configs,
    BASELINE.json:7,9). Subclasses override :meth:`shardings` to distribute
    the same compiled step over a mesh."""

    def __init__(self):
        self._reg: float = 0.0
        self._cfg: Optional[SolverConfig] = None
        self._step = None
        self._start = None

    # -- placement hooks (overridden by the sharded backend) ---------------
    def shardings(self, m: int, n: int):
        """Returns (matrix_sharding, col_vec_sharding, row_vec_sharding) or
        Nones for default single-device placement."""
        return None, None, None

    def prec_sharding(self):
        """Sharding for the PCG preconditioner factor L⁻¹ (m×m), or None
        for replicated/single-device placement. Hashable — it is a jit
        static argument keying the sharded-vs-replicated build."""
        return None

    def pad_multiple(self) -> int:
        """Column count is padded to a multiple of this (sharded backends
        need the variable axis divisible by the mesh)."""
        return 1

    def _put(self, arr, sharding):
        return jax.device_put(arr, sharding) if sharding is not None else jnp.asarray(arr)

    # -- SolverBackend ------------------------------------------------------
    def setup(self, inf: InteriorForm, config: SolverConfig) -> None:
        self._cfg = config
        self._reg = config.reg_dual
        dtype = jnp.dtype(config.dtype)
        factor_dtype = jnp.dtype(config.factor_dtype_resolved())
        refine = config.refine_steps

        A_host = inf.A.toarray() if sp.issparse(inf.A) else np.asarray(inf.A)
        m, n = A_host.shape
        c_host = np.asarray(inf.c, dtype=np.float64)
        u_host = np.asarray(inf.u, dtype=np.float64)
        self._n_orig = n
        # Pad the variable axis to the mesh multiple with zero columns
        # (cost 1, unbounded): they stay centered at x≈target, never bind,
        # and are sliced off in to_host.
        n_extra = (-n) % self.pad_multiple()
        if n_extra:
            A_host = np.hstack([A_host, np.zeros((m, n_extra))])
            c_host = np.concatenate([c_host, np.ones(n_extra)])
            u_host = np.concatenate([u_host, np.full(n_extra, np.inf)])
            n += n_extra
        mat_s, col_s, row_s = self.shardings(m, n)
        A = self._put(A_host.astype(dtype), mat_s)
        c = self._put(c_host.astype(dtype), col_s)
        b = self._put(np.asarray(inf.b, dtype=dtype), row_s)
        u = self._put(u_host.astype(dtype), col_s)
        self._col_sharding = col_s
        self._row_sharding = row_s

        self._A = A
        self._data = core.make_problem_data(jnp, c, b, u, dtype)
        self._params = config.step_params()
        self._factor_dtype_name = jnp.dtype(factor_dtype).name
        self._refine = refine
        self._dtype = dtype
        # Fused Pallas normal-equations assembly: auto on single-device TPU
        # placement with a single-precision factor dtype and no
        # M-level refinement (which needs the full-precision M the fused
        # path never materializes). Sharded placement would need the kernel
        # wrapped in shard_map — not done yet, so it stays on plain XLA,
        # which GSPMD-partitions into the psum-combined Schur form.
        from distributedlpsolver_tpu.ops import supports_pallas

        two_phase = config.two_phase_enabled(jax.default_backend())
        pallas_ok = mat_s is None and refine == 0 and supports_pallas(factor_dtype)
        if config.use_pallas is None:
            self._use_pallas = pallas_ok
        elif config.use_pallas and not (pallas_ok or two_phase):
            raise ValueError(
                "use_pallas=True requires single-device placement, "
                "refine_steps=0, and a single-precision (or auto two-phase) "
                f"factor_dtype on a TPU (got factor_dtype="
                f"{jnp.dtype(factor_dtype).name}, "
                f"refine_steps={refine}, sharded={mat_s is not None}, "
                f"platform={jax.default_backend()})"
            )
        else:
            self._use_pallas = bool(config.use_pallas) and pallas_ok
        # Loop-invariant precast + tile-pad for the Pallas path: once here,
        # not per factorize call (A never changes across iterations).
        if self._use_pallas:
            from distributedlpsolver_tpu.ops import pad_for_pallas

            self._Af = pad_for_pallas(A.astype(factor_dtype))
        else:
            self._Af = None

        # Two-phase (f32→f64) fused schedule: "auto" factor dtype on a TPU.
        # Sharded placement runs phase 1 on the plain-XLA f32 GEMM (astype
        # preserves the mesh layout, so GSPMD partitions the f32 assembly
        # into per-device Schur blocks + psum exactly like the f64 path);
        # single-device placement additionally gets the Pallas kernel. The
        # f32 copy is materialized lazily in solve_full: the host-driver
        # path (e.g. per-iteration checkpointing disables the fused loop)
        # never reads it, and at large m×n it is real HBM. An explicit
        # use_pallas=False opts phase 1 out of the Pallas kernel too.
        self._two_phase = two_phase
        self._pallas_p1 = (
            two_phase
            and mat_s is None
            and supports_pallas(jnp.float32)
            and config.use_pallas is not False
        )
        self._A32 = None
        self._closure = None
        # PCG full-accuracy mode (config.solve_mode): replaces the f64
        # phase 2 / f64 host-driver steps with f32-preconditioned
        # matrix-free CG, auto-on for large two-phase TPU problems where
        # emulated-f64 assembly/Cholesky is the bottleneck.
        # PCG is mesh-compatible: the chunked matrix-free operator
        # compiles under GSPMD, dropping the f64 M/L halves the
        # replicated per-device footprint, and on mesh placement the
        # preconditioner factor L⁻¹ is column-sharded (_tri_inv_mesh +
        # prec_sharding) so its build and storage are 1/K per device; the
        # f32 m×m Cholesky itself still runs replicated (a fully
        # distributed panel Cholesky remains future work).
        if config.solve_mode == "pcg":
            self._pcg = True
        elif config.solve_mode is None:
            # Auto: engage PCG only where the fused f64 finish gets heavy
            # (the measured two-phase direct path wins below this).
            self._pcg = two_phase and m * n >= (1 << 26)
        else:
            self._pcg = False
        self._cg_iters = config.cg_iters if self._pcg else 0
        self._cg_tol = config.cg_tol if self._pcg else 0.0
        self._prec_shard = self.prec_sharding() if self._pcg else None

    def _ensure_A32(self):
        """The f32 (optionally Pallas-padded) copy of A, materialized
        lazily — the pure-f64 host-driver path never reads it."""
        if self._A32 is None:
            if self._pallas_p1:
                from distributedlpsolver_tpu.ops import pad_for_pallas

                self._A32 = pad_for_pallas(self._A.astype(jnp.float32))
            else:
                self._A32 = self._A.astype(jnp.float32)
        return self._A32

    def _ensure_closure(self):
        """(LinvG, sG) — the f32 factor of the loop-invariant G = A·Aᵀ
        powering the primal-row closure of the PCG phase plans (see
        core.LinOps.primal_project; built once, ~m²·4 bytes of HBM).
        The closure keeps pinf pinned from the FIRST iteration: the
        feasibility junk each regularized/filtered solve leaks is
        removed while μ is still large enough to absorb the induced
        complementarity perturbation — removing it later was measured
        to be impossible without wrecking μ or the dual (10k×50k,
        round 3)."""
        if self._closure is None:
            m, n = self._A.shape
            A32 = self._ensure_A32()
            if A32.shape != (m, n):
                # Pallas-padded copy: assemble G through the kernel
                # (d = 1 on real columns, 0 on padding) instead of
                # slicing out an unpadded ~m·n·4-byte duplicate.
                from distributedlpsolver_tpu.ops import normal_eq_pallas

                G = normal_eq_pallas(
                    A32, jnp.ones((n,), jnp.float32), out_m=m
                )
                self._closure = _closure_from_G(G)
            else:
                self._closure = _closure_factors(A32)
            jax.block_until_ready(self._closure)
        return self._closure

    def _point_spec(self):
        """(factor_dtype_name, refine, use_pallas, Af, cg_iters, cg_tol,
        prec_shard) for the per-call entry points (starting_point /
        iterate).

        PCG mode uses the f32-preconditioner + f64-CG ops everywhere. A
        two-phase schedule computes the STARTING POINT with the f32 direct
        factorization too — it is a heuristic, and the f64 assembly +
        Cholesky it would otherwise pay is exactly the emulated-f64 cost
        the schedule exists to avoid (at 10k×50k it alone blows the
        warm-up budget); iterate() keeps full f64 in that mode because the
        host-driven loop has no second phase to repair f32 error.
        """
        if self._pcg:
            return ("float32", 0, self._pallas_p1, self._ensure_A32(),
                    self._cg_iters, self._cg_tol, self._prec_shard)
        return (self._factor_dtype_name, self._refine, self._use_pallas,
                self._Af, 0, 0.0, None)

    def _start_spec(self):
        if self._two_phase and not self._pcg:
            return ("float32", 0, self._pallas_p1, self._ensure_A32(), 0,
                    0.0, None)
        return self._point_spec()

    def starting_point(self) -> IPMState:
        fdt, refine, pallas, Af, cgi, cgt, psh = self._start_spec()
        state = _dense_start(
            self._A,
            self._data,
            jnp.asarray(self._reg, self._dtype),
            self._params,
            fdt,
            refine,
            pallas,
            Af,
            cgi,
            cgt,
            psh,
        )
        jax.block_until_ready(state)
        return state

    def iterate(self, state: IPMState) -> Tuple[IPMState, StepStats]:
        fdt, refine, pallas, Af, cgi, cgt, psh = self._point_spec()
        return _dense_step(
            self._A,
            self._data,
            state,
            jnp.asarray(self._reg, self._dtype),
            self._params,
            fdt,
            refine,
            pallas,
            Af,
            cgi,
            cgt,
            psh,
        )

    def bump_regularization(self) -> bool:
        if self._reg * self._cfg.reg_grow > 1e-2:
            return False
        self._reg = max(self._reg, 1e-12) * self._cfg.reg_grow
        return True

    def _phase_plan(self):
        """Per-phase execution specs for the fused solve: (params,
        factor_dtype_name, refine_steps, use_pallas, Af, stall_window,
        stall_patience_floor, cg_iters, cg_tol, prec_shard, closure,
        closure_sweeps)."""
        cfg = self._cfg
        patience = 1e3 * cfg.tol  # near-tol plateaus deserve patience
        w = cfg.stall_window
        if self._pcg and not self._two_phase:
            # Forced PCG without a phase schedule: one full-tol PCG phase.
            fdt, refine, pallas, Af, cgi, cgt, psh = self._point_spec()
            return [
                (self._params, fdt, refine, pallas, Af, 2 * w if w else 0,
                 patience, cgi, cgt, psh, self._ensure_closure(), 2)
            ]
        if not self._two_phase:
            # Final (only) phase gets the same stall semantics as the
            # two-phase finish and the batched backend: window 2·w with
            # the near-tol patience floor.
            return [
                (self._params, self._factor_dtype_name, self._refine,
                 self._use_pallas, self._Af, 2 * w if w else 0, patience,
                 0, 0.0, None, None, 0)
            ]
        A32 = self._ensure_A32()
        params_p1 = cfg.phase1_params()
        m, n = self._A.shape
        if self._pcg:
            # Phase 2 = f32-preconditioned matrix-free PCG at the PCG
            # HANDOFF tol (μ-floor keyed there, the phase1_tol mechanism
            # one level down — see config.pcg_handoff_tol) with NO stall
            # patience: the f32-assembled preconditioner carries no
            # information about M's smallest eigen-subspace once
            # kappa(M) > 1/eps_f32, so PCG floors around 1e-6 — it hands
            # over at its handoff tol or its stall, still well-centered,
            # and a full-precision phase finishes: a fused f64 phase
            # below the endgame threshold, the host-driven endgame above.
            params_pcg = cfg.replace(
                tol=max(cfg.tol, cfg.pcg_handoff_tol)
            ).step_params()
            # SHORT stall window for the PCG phase: every iteration it
            # grinds at its f32-preconditioner floor degrades the iterate
            # (observed at 10k×50k: 9 floor iterations collapsed
            # complementarity pairs badly enough that the endgame's f64
            # factorization failed below reg 1e-6, pinning pinf ~1e-5);
            # hand over within ~3 of the floor instead.
            w_pcg = min(3, w) if w else 0
            # The primal-row closure runs in EVERY pcg-plan phase: pinf
            # junk must never accumulate past the μ that can absorb its
            # removal (core._solve_kkt rationale). Phase 1 gets 0
            # true-operator sweeps (f32-factor accuracy ~1e-6 matches
            # the phase's own floor and skips the ew-f64 matvec cost);
            # the full-precision phases sweep twice.
            closure = self._ensure_closure()
            phases = [
                (params_p1, "float32", 0, self._pallas_p1, A32, w, 0.0,
                 0, 0.0, None, closure, 0),
                (params_pcg, "float32", 0, self._pallas_p1, A32, w_pcg, 0.0,
                 self._cg_iters, self._cg_tol, self._prec_shard, closure, 2),
            ]
            if m * n < self._ENDGAME_ENTRIES:
                phases.append(
                    (self._params, self._dtype.name, self._refine, False,
                     None, 2 * w if w else 0, patience, 0, 0.0, None,
                     closure, 2)
                )
            return phases
        phase2 = (self._params, self._dtype.name, self._refine, False,
                  None, 2 * w if w else 0, patience, 0, 0.0, None, None, 0)
        return [
            (params_p1, "float32", 0, self._pallas_p1, A32, w, 0.0, 0, 0.0,
             None, None, 0),
            phase2,
        ]

    # m·n above which the full-precision finish runs as the host-driven
    # endgame (one iteration split across dispatches) instead of a fused
    # f64 phase: a single fused iteration's assembly alone would exceed
    # the execution watchdog.
    _ENDGAME_ENTRIES = 1 << 28

    def _endgame_loop(self, state: IPMState, it0: int, buf, reg0=None):
        """Host-driven full-precision finish for huge m (see the endgame
        program docstrings above). Returns (state, it, status, buf).

        Regularization seeds at the configured base (1e-12), NOT from
        the phases' escalated value (``reg0`` is informational): phase
        escalations answer *f32* breakdowns the f64 factorization does
        not share, and a 1e-6-seeded endgame was observed (10k×50k) to
        pin pinf at ~1e-5 — while re-finding the right level costs only
        cheap factor+step retries (the assembly is held across them).
        Per-dispatch wall times land in ``self.endgame_timings`` (one
        dict per factor+step attempt); scripts/run_dense10k.py folds
        them into the timing artifact.
        """
        import time as _time

        cfg = self._cfg
        # Endgame KKT-refinement policy: cfg.endgame_kkt_refine rounds
        # (auto 1 — ROUND5_NOTES lever 1; the solves are cheap panel
        # substitutions now, the old hardwired 0 was a host-era
        # program-size constraint). See _endgame_step_params.
        params = _endgame_step_params(cfg)
        trace = core.seg_trace_enabled()
        buf = np.asarray(buf)[:it0] if it0 else np.zeros((0, core.N_STAT))
        rows = []
        it = it0
        status = core.STATUS_MAXITER
        best = np.inf
        since = 0
        reg_base = max(self._reg, 1e-12)  # user-configured floor
        reg = reg_base
        state = _endgame_recenter(self._data, state, params)
        reg_fail_floor = 0.0  # smallest reg observed to fail a factor
        good_streak = 0  # consecutive good steps since the last bad one
        # Endgame factor mode. Auto (endgame_host=None) on TPU is now
        # the on-device "mxu" mode (round 5): the GEMM-dominated panel
        # factor+inverse (ops/chol_mxu.py) factors the Jacobi-scaled
        # system in 10.0 s at m=10240 — against the host path's
        # ~20–33 s symmetric transfer PLUS ~20–38 s LAPACK factor per
        # iteration — and the whole step stays one jitted device
        # program (the host mode ran mehrotra_step eagerly). The host
        # mode remains behind endgame_host=True: LAPACK's true-f64
        # ε = 2.2e-16 and guarded pivots are the escape hatch if a
        # problem's late spectrum defeats the emulated-f64 kernel
        # (probe: mxu factors the degenerate cond-1e19 spectrum to
        # reg 1e-12 with effective ε ≈ 1.5e-13, double-double class).
        # endgame_host=False keeps the legacy builtin device mode.
        import os as _os

        eg_env = _os.environ.get("TPULP_ENDGAME", "")
        if eg_env in ("mxu", "host", "device"):
            eg_mode = eg_env  # test hook / A-B escape hatch
        elif cfg.endgame_host is None:
            eg_mode = "mxu" if jax.default_backend() == "tpu" else "device"
        else:
            eg_mode = "host" if cfg.endgame_host else "device"
        host_mode = eg_mode == "host"
        mxu_mode = eg_mode == "mxu"
        closure = None
        if mxu_mode:
            # The mxu step reuses the phases' pure-jax AAᵀ closure for
            # the direction-level primal restoration — build it BEFORE
            # A32 is dropped (it factors from the f32 copy).
            closure = self._ensure_closure()
        # The endgame never touches the f32 copy the PCG phases
        # preconditioned with — drop it before the first f64 assembly:
        # at 10k×50k the (Pallas-padded) A32 is ~2 GB of HBM, and with it
        # resident the SECOND endgame iteration's assembly hit
        # RESOURCE_EXHAUSTED (observed 2026-07-30; iteration 1 fit only
        # because no previous factor L was alive yet). The device-side
        # closure factor goes with it (~m²·4 bytes; KEPT in mxu mode,
        # which feeds it to the step's primal_project) — the host
        # endgame uses the exact host AAᵀ closure instead.
        self._A32 = None
        # The Pallas-padded phase-1 assembly copy (~2 GB at 10k) is dead
        # too — the endgame's assembly is the chunked f64 contraction.
        # Host mode never needed the headroom (no factor lives in HBM);
        # the mxu factor's T/X panel buffers do (observed runtime
        # RESOURCE_EXHAUSTED with Af resident, 2026-08-01).
        self._Af = None
        if not mxu_mode:
            self._closure = None
        budget = cfg.max_iter
        refactor = 0
        self.endgame_timings = timings = []
        # Host-factor mode (cfg.endgame_host=True): LAPACK factorization
        # + triangular solves on host, assembly and refinement matvecs on
        # device. The same mode builds the AAᵀ host factor whose
        # restore() closure makes every Newton dx exactly
        # primal-feasible — with the phases' device closure, the two
        # mechanisms that broke the round-3 terminal wall
        # (BENCH_10K.json analysis): a four-orders-smaller factorable
        # reg, and feasibility that never leaks into the iterate.
        project = None
        restore = None
        if host_mode:
            # Eager steps carry no program-size limit but each KKT round
            # is a full host solve + device residual pair — capped at 1
            # regardless of the endgame knob (see _endgame_step_params
            # and the endgame_host note in ipm/config.py).
            params = _endgame_step_params(cfg, host_mode=True)
            # The AAᵀ factor powers the DIRECTION-level primal closure
            # (restore → ops.primal_project): every Newton dx is made
            # exactly primal-feasible, so pinf decays as (1−α) per
            # iteration. The iterate-space project() is deliberately NOT
            # applied here: projecting the ITERATE was measured (10k×50k)
            # to inflate μ by 4 orders (Δx ~ ‖r_p‖/σ_min lands on
            # complementarity products) and its box clamps crushed the
            # next step's α to ~0.01 — the direction-level closure has
            # neither failure mode.
            project = _build_host_projector(self._A, self._data, trace=trace)
            if project is not None:
                restore = project.restore
        # Holding M across the step amortizes bad-step retries (only the
        # factorization sees the escalated reg), but costs an extra m²·8
        # bytes of HBM concurrent with L and the step's working set —
        # affordable at the 10k target (M+L ≈ 1.6 GB of 16 GB), not at
        # m ≳ 24k where two f64 m×m buffers alone approach the chip.
        # Above the cutoff, fall back to re-assembling on (rare) retries.
        m = self._A.shape[0]
        # mxu mode DONATES M into the factor program (HBM headroom — see
        # _endgame_factor_mxu), so holding it for retries is impossible
        # there; retries re-assemble (~11 s at 10k, rare).
        hold_m = m <= 16384 and not mxu_mode
        # Anti-stagnation ladder for the BLOCKED-STEP mode (first observed
        # 2026-07-31 at 10k×50k: pinf/dinf at ~9e-15 but μ frozen at
        # 3.7e-8 with α pinned to the backoff grid's floor — the Mehrotra
        # direction anti-centers the minimum pair, every N₋∞ candidate is
        # inadmissible, and σ stays tiny because the AFFINE step keeps
        # predicting progress the guard can't accept). Remedy ladder:
        # after ONE sub-10%-μ step, run ONE pure centering
        # step (StepParams.center: one KKT solve aiming every product at
        # the current μ — admissible by construction, restores the step
        # room the next Mehrotra iteration needs); if stagnation persists,
        # lift collapsed pairs (_endgame_recenter) once; the stall window
        # remains the final exit.
        import dataclasses as _dc

        params_center = _dc.replace(params, center=True)
        stag = 0
        center_next = False
        recenters = 0
        prev_mu = None
        k = 0
        while k < budget:
            t0 = _time.perf_counter()
            # σ=1 on a centering iteration; the ASSEMBLY always runs with
            # the base params (d depends only on reg_primal, identical in
            # both — and a params-keyed recompile of the assembly would
            # cost minutes at 10k scale for a bitwise-equal program).
            step_par = params_center if center_next else params
            cr, nb, _ = _cent_diag(
                self._data, state, jnp.asarray(params.gamma_cent)
            )
            cent_ratio, n_below = float(np.asarray(cr)), int(np.asarray(nb))
            # M depends only on the iterate, NOT on reg — assemble once
            # per state; re-running the assembly dispatch (the longest,
            # ~40 s at 10k×50k) per bad-step retry would be pure waste.
            M = _endgame_assemble(self._A, self._data, state, params)
            jax.block_until_ready(M)  # bound each dispatch's device time
            t_asm = _time.perf_counter() - t0
            Mh = None
            if host_mode:
                # One d2h transfer per iterate — lower triangle only,
                # mirrored on host (M is symmetric; see _fetch_symmetric:
                # the full 800 MB copy measured ~45–73 s per iteration
                # from the remote chip used before PR 21). Retries
                # refactor from this SAME host copy, and the device M is
                # freed immediately — the host path never holds M and L
                # in HBM together.
                t1 = _time.perf_counter()
                Mh = _fetch_symmetric(M)
                t_xfer = _time.perf_counter() - t1
                diagM_h = np.ascontiguousarray(np.diagonal(Mh))
                diagM = jnp.asarray(diagM_h)
                del M
                M = None
            else:
                t_xfer = 0.0
                diagM = jnp.diagonal(M)  # O(m); survives M's deletion,
            failed = False  # feeds the matrix-free refinement residual
            while True:
                t1 = _time.perf_counter()
                if host_mode:
                    hostf = _endgame_factor_host(Mh, reg)
                    t_fac = _time.perf_counter() - t1
                    if hostf is None:
                        # Failed host factorization: escalate without
                        # paying for a step dispatch (LAPACK reports
                        # breakdown instead of propagating NaN).
                        timings.append({
                            "it": it, "t_assemble": round(t_asm, 3),
                            "t_transfer": round(t_xfer, 3),
                            "t_factor": round(t_fac, 3), "t_step": 0.0,
                            "bad": True, "reg": float(reg),
                            "alpha_p": 0.0, "alpha_d": 0.0,
                            "mu": float("nan"), "sigma": float("nan"),
                            "L_finite": False, "host": True,
                        })
                        t_asm = 0.0
                        t_xfer = 0.0
                        refactor += 1
                        good_streak = 0
                        reg_fail_floor = max(reg_fail_floor, reg * _EG_REG_GROW)
                        reg *= _EG_REG_GROW
                        if trace:
                            import sys as _sys

                            print(
                                f"[endgame] it={it} host factor failed, "
                                f"reg->{reg:.1e}",
                                file=_sys.stderr, flush=True,
                            )
                        if refactor > cfg.max_refactor or reg > 1e-2:
                            failed = True
                            break
                        continue
                    t1 = _time.perf_counter()
                    new_state, stats = _endgame_step_host(
                        self._A, self._data, state, hostf, float(reg),
                        diagM, step_par, restore=restore,
                    )
                    bad = bool(np.asarray(stats.bad))
                    t_step = _time.perf_counter() - t1
                    L_finite = True
                else:
                    fac_fn = _endgame_factor_mxu if mxu_mode else _endgame_factor
                    L = fac_fn(M, jnp.asarray(reg, self._dtype))
                    jax.block_until_ready(L)
                    t_fac = _time.perf_counter() - t1
                    if not hold_m:
                        del M
                        M = None
                    t1 = _time.perf_counter()
                    if mxu_mode:
                        new_state, stats = _endgame_step_mxu(
                            self._A, self._data, state, L,
                            jnp.asarray(reg, self._dtype), diagM, step_par,
                            closure=closure,
                        )
                    else:
                        new_state, stats = _endgame_step(
                            self._A, self._data, state, L,
                            jnp.asarray(reg, self._dtype), diagM, step_par,
                        )
                    bad = bool(stats.bad)  # blocks on the step dispatch
                    t_step = _time.perf_counter() - t1
                    L_finite = bool(
                        np.isfinite(float(np.asarray(jnp.sum(L[0]))))
                    )
                timings.append({
                    "it": it, "t_assemble": round(t_asm, 3),
                    "t_transfer": round(t_xfer, 3),
                    "t_factor": round(t_fac, 3),
                    "t_step": round(t_step, 3),
                    "bad": bad, "reg": float(reg),
                    # failure-mechanism diagnostics: bad == non-finite
                    # direction OR a zero step length. alpha_* are masked
                    # to 0 on bad; sigma goes NaN iff the PREDICTOR
                    # direction was non-finite (mu_aff propagates);
                    # L_finite isolates a failed factorization.
                    "alpha_p": float(np.asarray(stats.alpha_p)),
                    "alpha_d": float(np.asarray(stats.alpha_d)),
                    "mu": float(np.asarray(stats.mu)),
                    "sigma": float(np.asarray(stats.sigma)),
                    "L_finite": L_finite,
                    "host": host_mode,
                    "mode": eg_mode,
                    # blocked-step-mode diagnostics (entry state): a stall
                    # with cent_ratio ≪ γ is a guard-limited deadlock, one
                    # with ratio ≈ γ and tiny α a ratio-test block.
                    "center": bool(center_next),
                    "cent_ratio": cent_ratio,
                    "n_below": n_below,
                })
                t_asm = 0.0  # amortized: no re-assembly on retries
                t_xfer = 0.0
                if not bad:
                    break
                refactor += 1
                good_streak = 0
                # Decay (below) must never re-enter a reg that already
                # failed: without this floor an up/down cycle repeats
                # the failing factorization EVERY iteration (observed at
                # 10k×50k: one guaranteed bad step per iterate).
                reg_fail_floor = max(reg_fail_floor, reg * _EG_REG_GROW)
                reg *= _EG_REG_GROW
                if trace:
                    import sys as _sys

                    print(
                        f"[endgame] it={it} bad step, reg->{reg:.1e} "
                        f"(factor {t_fac:.1f}s + step {t_step:.1f}s)",
                        file=_sys.stderr, flush=True,
                    )
                if refactor > cfg.max_refactor or reg > 1e-2:
                    failed = True
                    break
                if M is None and not host_mode:
                    # Big-m device path dropped M before the step (host
                    # mode refactors from the held host copy instead).
                    # The failed factor is dead — free it BEFORE the
                    # re-assembly, the same assembly+L concurrency the
                    # iteration-boundary del below exists to avoid.
                    del L
                    t1 = _time.perf_counter()
                    M = _endgame_assemble(self._A, self._data, state,
                                          params)
                    jax.block_until_ready(M)
                    t_asm = _time.perf_counter() - t1
            if M is not None:
                del M
            # The factor is dead once the step consumed it — freeing its
            # m²·8 bytes BEFORE the next assembly dispatch is what keeps
            # the 10k-scale endgame inside HBM across iterations (host
            # mode: the host copies go instead, ~2.4 GB of RAM each).
            if host_mode:
                Mh = None
                hostf = None
            else:
                del L
            dt = _time.perf_counter() - t0
            if failed:
                status = core.STATUS_NUMERR
                break
            refactor = 0
            # One-notch decay per good step: a retry-escalated reg is
            # evidence about THAT iterate's system, not the remaining
            # trajectory's; without decay the perturbation compounds into
            # a permanent tol floor (reg only ever grows above). Floored
            # at the user-configured base and at the smallest reg that
            # recently failed a factorization — but that fail-floor AGES
            # OUT after 4 clean steps (one probing decay per 4 iterates
            # at worst), so a single early bad step cannot pin the whole
            # remaining trajectory above reg_base.
            good_streak += 1
            if good_streak >= 4:
                reg_fail_floor = 0.0
                good_streak = 0
            reg = max(reg / _EG_REG_GROW, reg_base, reg_fail_floor)
            state = new_state
            it += 1
            k += 1
            row = [
                float(np.asarray(getattr(stats, f)))
                for f in (
                    "mu", "gap", "rel_gap", "pinf", "dinf", "pobj", "dobj",
                    "alpha_p", "alpha_d", "sigma",
                )
            ]
            rows.append(row)
            err = max(row[2], row[3], row[4])  # rel_gap, pinf, dinf
            if trace:
                import sys as _sys

                print(
                    f"[endgame] it={it} gap={row[2]:.3e} pinf={row[3]:.3e} "
                    f"dinf={row[4]:.3e} mu={row[0]:.2e} "
                    f"a={row[7]:.2f}/{row[8]:.2f}"
                    f"{' CENTER' if center_next else ''} ({dt:.1f}s)",
                    file=_sys.stderr, flush=True,
                )
            if row[2] <= cfg.tol and row[3] <= cfg.tol and row[4] <= cfg.tol:
                status = core.STATUS_OPTIMAL
                break
            if err < 0.9 * best:
                best, since = err, 0
            else:
                since += 1
                if cfg.stall_window and since > 2 * cfg.stall_window:
                    status = core.STATUS_STALL
                    break
            # Blocked-step ladder (see init above): μ-stagnation drives
            # one centering step, then one collapsed-pair lift. Gated on
            # BOTH counters: in the healthy endgame tail μ deliberately
            # pins at core.mehrotra_step's mu_floor while pinf still
            # improves 10×/iteration — μ-stagnation alone would fire
            # centering (and the decidedly non-free recenter) mid-polish,
            # so the ladder additionally requires err to have stopped
            # improving (since > 0).
            mu_new = row[0]
            was_center = center_next
            center_next = False
            # A step that cuts μ by less than 10% is stagnant. The old
            # scheme (0.98 threshold + TWO-strike trigger) needed a ~−3%
            # step miscounted as progress AND two further strike-counting
            # near-zero-α steps before centering — the recorded terminal
            # cycle (BENCH_10K.json rows, its 31–77) fires CENTER only
            # every ~5 iterations, wasting 2–3 ~15 s steps per cycle.
            # The −10% line with a ONE-strike trigger centers on the
            # first weak step; partial telemetry from the tightened
            # re-run (cut short 2 iterations from optimal by a hung
            # dispatch) showed the expected 3-step cycle with
            # post-center α 0.37–0.52. Healthy steps cut μ 3–5× and
            # never count; the ``since > 0`` gate keeps the μ-floor
            # polish regime (pinf still improving) exempt.
            if prev_mu is not None and mu_new > 0.90 * prev_mu:
                stag += 1
            else:
                stag = 0
            prev_mu = mu_new
            if stag >= 1 and since > 0 and not was_center:
                if stag >= 3 and recenters == 0:
                    state = _endgame_recenter(self._data, state, params)
                    recenters += 1
                    if trace:
                        import sys as _sys

                        print(
                            "[endgame] stagnant after centering — lifting "
                            "collapsed pairs",
                            file=_sys.stderr, flush=True,
                        )
                center_next = True
        buf = np.concatenate([buf, np.asarray(rows)]) if rows else buf
        return state, it, jnp.asarray(status, jnp.int32), buf

    def _solve_segmented(self, state: IPMState):
        """Host-driven segmented fused solve: per-phase specs feed the
        shared driver (core.drive_phase_plan), which bounds single
        device-program runtime under execution watchdogs."""
        cfg = self._cfg
        dtype = self._dtype
        # An explicit segment_iters=0 can still reach here (the PCG
        # two-phase route overrides it — solve_full); 0 would degenerate
        # seg_open to 1-iteration opening programs, so treat it as auto.
        seg_cfg = cfg.segment_iters if cfg.segment_iters else None
        # Each phase gets its own max_iter budget (matching the batched
        # path), so a tiny-max_iter warm-up still reaches and compiles
        # every phase; the buffer covers the 2-phase worst case.
        n_phases = 1 + (1 if self._two_phase else 0) + (1 if self._pcg else 0)
        buf_cap = core.buffer_cap(n_phases * cfg.max_iter)
        mr = jnp.asarray(cfg.max_refactor, jnp.int32)
        rg = jnp.asarray(cfg.reg_grow, dtype)
        m, n = self._A.shape
        flops = 2.0 * m * m * n + m**3 / 3.0  # per-iteration FLOP estimate

        def make_phase(spec):
            (params, fdt, refine, pallas, Af, window, patience, cgi,
             cgt, psh, closure, csweeps) = spec
            rate = core.SEG_RATE_F32 if fdt == "float32" else core.SEG_RATE_F64
            est = flops / rate

            def make_run_seg(bound):
                mi = jnp.asarray(bound, jnp.int32)

                def run_seg(c, stop):
                    return _dense_segment(
                        self._A, self._data, c, jnp.asarray(stop, jnp.int32),
                        mi, mr, rg, params, fdt, refine, buf_cap, pallas, Af,
                        window, patience, cgi, cgt, psh, closure, csweeps,
                    )

                return run_seg

            # A PCG phase's true per-iteration cost is dominated by the
            # worst-case CG sweeps (up to 6 solves × cg_iters matrix-free
            # operator applications), which the FLOP model above cannot
            # see — and on the remote chip used before PR 21 a program
            # past its ~60 s limit was fatal, not slow (observed: a
            # 32-iteration opening PCG segment crashed the worker). Open with ONE iteration and let the
            # measured-rate adaptation in drive_segments size the rest.
            seg0 = 1 if cgi else core.seg_open(seg_cfg, est)
            return (make_run_seg, window, patience, seg0)

        plan = self._phase_plan()
        # Phase MODE from the plan spec itself (cg_iters > 0 = pcg, else
        # the factor dtype) — utilization folding keys seed rates off
        # this, never off positional index guesses. Extracted BEFORE the
        # solve so `plan` (whose specs hold the ~2 GB Pallas-padded A32
        # and the closure factor) can be dropped: holding it across the
        # endgame kept those buffers alive through _endgame_loop's
        # entry-time release and OOMed the projector's AAᵀ assembly at
        # 10k×50k (observed 2026-07-31 — the same +2.4 GB failure the
        # release exists to prevent).
        modes = [
            "pcg" if spec[7] else ("f32" if spec[1] == "float32" else "f64")
            for spec in plan
        ]
        phases_built = [make_phase(s) for s in plan]
        del plan
        self.phase_report = []  # per-phase iters/wall split (utilization)
        st, it, status, buf, reg_out = core.drive_phase_plan(
            phases_built,
            state, jnp.asarray(self._reg, dtype), cfg.max_iter, buf_cap, dtype,
            report=self.phase_report,
        )
        del phases_built  # make_phase closures also reference A32
        for ph, mode in zip(self.phase_report, modes):
            ph["mode"] = mode
        m, n = self._A.shape
        # OPTIMAL re-enters the endgame ONLY when the two-phase plan
        # actually clamped the PCG phase to the looser handoff tol — then
        # "optimal" means optimal-at-handoff and the endgame owns the
        # finish. Forced single-phase PCG and tol ≥ handoff configs run
        # at the requested tol, so their OPTIMAL is final.
        clamped = self._two_phase and self._cfg.tol < self._cfg.pcg_handoff_tol
        trigger = (core.STATUS_STALL, core.STATUS_MAXITER) + (
            (core.STATUS_OPTIMAL,) if clamped else ()
        )
        if (
            self._pcg
            and m * n >= self._ENDGAME_ENTRIES
            and int(np.asarray(status)) in trigger
        ):
            import time as _time

            it_before, t_eg = int(np.asarray(it)), _time.perf_counter()
            st, it, status, buf = self._endgame_loop(
                st, it_before, buf,
                reg0=float(np.asarray(reg_out)),
            )
            # The endgame is a phase too: without this row the report
            # under-attributes exactly the iterations the utilization
            # artifacts care most about.
            self.phase_report.append({
                "phase": len(self.phase_report), "mode": "endgame",
                "iters": int(it) - it_before,
                "wall_s": round(_time.perf_counter() - t_eg, 3),
            })
        return st, it, status, buf

    def solve_full(self, state: IPMState):
        # Two-phase PCG always takes the segmented route, even when
        # segmentation was explicitly disabled: the fused two-phase
        # program's PCG phase 2 floors at the f32 preconditioner's ~3e-7
        # accuracy wall with no f64 finish, while the segmented plan
        # appends one (fused f64 phase below the endgame threshold,
        # host-driven endgame above). Segment sizing treats the explicit
        # 0 as auto — see _solve_segmented.
        if core.use_segments(
            self._cfg.segment_iters, jax.default_backend()
        ) or (self._pcg and self._two_phase):
            return self._solve_segmented(state)
        if self._two_phase:
            cfg = self._cfg
            self._ensure_A32()
            params_p1 = cfg.replace(
                tol=max(cfg.tol, cfg.phase1_tol)
            ).step_params()
            return _dense_solve_two_phase(
                self._A,
                self._A32,
                self._data,
                state,
                jnp.asarray(self._reg, self._dtype),
                self._params,
                params_p1,
                jnp.asarray(self._cfg.max_iter, jnp.int32),
                jnp.asarray(self._cfg.max_refactor, jnp.int32),
                jnp.asarray(self._cfg.reg_grow, self._dtype),
                core.buffer_cap(2 * self._cfg.max_iter),
                self._refine,
                self._pallas_p1,
                self._cfg.stall_window,
            )
        if self._pcg:
            # Forced PCG without a two-phase schedule (e.g. CPU tests):
            # one full-tol PCG phase through the single-phase fused loop.
            fdt, refine, pallas, Af, cgi, cgt, psh = self._point_spec()
            return _dense_solve_full(
                self._A,
                self._data,
                state,
                jnp.asarray(self._reg, self._dtype),
                self._params,
                fdt,
                refine,
                jnp.asarray(self._cfg.max_iter, jnp.int32),
                jnp.asarray(self._cfg.max_refactor, jnp.int32),
                jnp.asarray(self._cfg.reg_grow, self._dtype),
                core.buffer_cap(self._cfg.max_iter),
                pallas,
                Af,
                2 * self._cfg.stall_window if self._cfg.stall_window else 0,
                cgi,
                cgt,
                psh,
            )
        return _dense_solve_full(
            self._A,
            self._data,
            state,
            jnp.asarray(self._reg, self._dtype),
            self._params,
            self._factor_dtype_name,
            self._refine,
            jnp.asarray(self._cfg.max_iter, jnp.int32),
            jnp.asarray(self._cfg.max_refactor, jnp.int32),
            jnp.asarray(self._cfg.reg_grow, self._dtype),
            core.buffer_cap(self._cfg.max_iter),
            self._use_pallas,
            self._Af,
            2 * self._cfg.stall_window if self._cfg.stall_window else 0,
        )

    def to_host(self, state: IPMState) -> IPMState:
        # host_values = np.asarray on single-process placements; on a
        # multi-process mesh the column-sharded fields ride one
        # replicating gather program (a collective — every rank runs
        # the same driver, so every rank reaches each to_host together,
        # and the host-canonical checkpoint contract holds world-wide).
        from distributedlpsolver_tpu.parallel.mesh import host_values

        n = self._n_orig
        x, y, s, w, z = host_values(
            (state.x, state.y, state.s, state.w, state.z)
        )
        return IPMState(x=x[:n], y=y, s=s[:n], w=w[:n], z=z[:n])

    def from_host(self, state: IPMState) -> IPMState:
        n_extra = self._data.c.shape[0] - self._n_orig
        x, y, s, w, z = (np.asarray(v, dtype=self._dtype) for v in state)
        if n_extra:
            # Padded columns (cost 1, zero A column): re-enter centered.
            x = np.concatenate([x, np.full(n_extra, 1e-8)])
            s = np.concatenate([s, np.ones(n_extra)])
            w = np.concatenate([w, np.ones(n_extra)])
            z = np.concatenate([z, np.zeros(n_extra)])
        col_s = self._col_sharding
        row_s = getattr(self, "_row_sharding", None)
        put = lambda v: jax.device_put(v, col_s) if col_s is not None else jnp.asarray(v)
        # y rides the row (replicated-on-mesh) sharding: under a
        # multi-process mesh an uncommitted single-device array cannot
        # feed a global SPMD program — every input needs a concrete
        # global placement.
        put_y = (
            (lambda v: jax.device_put(v, row_s))
            if row_s is not None
            else jnp.asarray
        )
        return IPMState(x=put(x), y=put_y(y), s=put(s), w=put(w), z=put(z))

    def block_until_ready(self, obj) -> None:
        jax.block_until_ready(obj)
