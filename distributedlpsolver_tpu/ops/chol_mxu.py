"""GEMM-dominated Cholesky factorization + explicit triangular inverse.

Why this exists (measured on the v5e chip, scripts/probe_batched_parts.py
and scripts/probe_chol_mxu.py, 2026-08-01): XLA's emulated-f64
``jnp.linalg.cholesky`` on a (128, 128, 128) batch costs ~345 ms and a
single emulated-f64 ``cho_solve`` ~130 ms — they lower to scalarized
recurrences whose every scalar op pays the f64-emulation tax. Meanwhile
emulated-f64 *GEMM* runs at ~150 GFLOP/s with 2.2e-15 max relative error
(the MXU split path), and fused f64 elementwise streams at ~2 ns/element.
Round 4 misattributed the batched backend's wall to "emulated-f64
elementwise"; the component probe shows the
factorization and triangular solves own ~75% of the 622 ms step.

So: restructure the factorization so ALL O(m³) work is GEMM and the only
sequential arithmetic is a p-column recursion inside each diagonal block.
This panel scheme is the single-device sibling of ops/dist_chol.py's
mesh panel factorization (SURVEY.md §2 "LA kernels"; BASELINE.json:5
names the dense-Cholesky path) with two differences: the diagonal block
is factored by an unrolled static-slice recursion instead of
``jnp.linalg.cholesky`` (the builtin is the very thing being avoided),
and the triangular inverse is fused into the same panel loop, so a
factorization's 6+ downstream solves (kkt_refine=2 ⇒ 6 per IPM step)
become two batched GEMVs each.

Measured win (same probe): (128, 128, 128) factor+full-inverse ~35 ms vs
~350 ms builtin factor alone — ~10× — and each solve drops from ~20 ms
to GEMV noise. Accuracy: ||M⁻¹M − I||_max = 1.7e-10 at cond 7.5e5 and
3.2e-13 at m = 2048 — the backward-stable class expected of an IEEE-f64
right-looking Cholesky (identical operation set, blocked order).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _tri_inv_block(C):
    """(p, p) lower-triangular → C⁻¹ masked fori forward substitution on
    the identity — ONE definition, also used for `_factor_diag_block`'s W."""
    p = C.shape[0]
    eye = jnp.eye(p, dtype=C.dtype)

    def sub_body(i, W):
        row = (eye[i] - C[i] @ W) / C[i, i]
        return W.at[i].set(row)

    return jax.lax.fori_loop(0, p, sub_body, jnp.zeros_like(C))


def _pad_spd(M, p):
    """Pad an (m, m) SPD matrix to a panel multiple with an inert
    identity tail; returns (padded, mp)."""
    m = M.shape[0]
    mp = -(-m // p) * p
    if mp != m:
        M = jnp.pad(M, ((0, mp - m), (0, mp - m)))
        M = M.at[jnp.arange(m, mp), jnp.arange(m, mp)].set(1.0)
    return M, mp


def _factor_diag_block(D):
    """(p, p) SPD block → (C, W) with ``C = chol(D)`` and ``W = C⁻¹``.

    Masked ``fori_loop`` column recursion: per column, one sqrt + one
    scaled masked column + one masked rank-1 trailing update; then W by
    a masked forward-substitution loop on the identity. The loop bodies
    are ~10 fused ops regardless of p, so the graph (and compile time)
    stays tiny at the wide panels large m wants — an earlier unrolled
    version put 2p static steps in the panel body and XLA compile
    diverged at p = 256. Runtime is 2p sequential steps of (p,)/(p,p)
    fused VPU work — microseconds against the panel GEMMs. Breakdown
    (non-SPD D) yields NaN from the sqrt and propagates, matching the
    builtin's contract.
    """
    p = D.shape[0]
    rows = jnp.arange(p)

    def fac_body(i, carry):
        D, Ct = carry
        r = jnp.sqrt(D[i, i])
        col = jnp.where(rows >= i, D[:, i] / r, 0.0)
        Ct = Ct.at[i].set(col)  # Ct row i = column i of C
        t = jnp.where(rows > i, col, 0.0)
        D = D - t[:, None] * t[None, :]
        return D, Ct

    _, Ct = jax.lax.fori_loop(0, p, fac_body, (D, jnp.zeros_like(D)))
    C = Ct.T
    return C, _tri_inv_block(C)


def _panel_for(m: int) -> int:
    """Default panel width: small blocks keep the unrolled recursion
    short where the batch axis supplies parallelism (measured best at
    p=16 for the (128, 128) members); large m amortizes panel GEMMs
    better at wider panels (p=256 beat 128 at m=2048)."""
    if m <= 512:
        return 16
    if m < 2048:
        return 128
    return 256


@functools.partial(jax.jit, static_argnames=("panel",))
def chol_inv_mxu(M, panel: int | None = None):
    """``L⁻¹`` for ``M = L·Lᵀ`` (SPD), all O(m³) on the MXU.

    Unbatched (m, m) → (m, m) lower-triangular ``Linv`` with
    ``M⁻¹ = Linvᵀ·Linv``; ``vmap`` supplies the batch axis (the batched
    backend's usage). Ragged m is padded to a panel multiple with an
    identity tail (factors to I, inert, sliced off — same device-side
    trick as ops/dist_chol.py) entirely under jit.

    Per panel j (rows/cols [g0, g0+p)) on the running trailing matrix T
    and inverse accumulator X (init I):

        C, W  = factor+invert T[diag block]         (2p fused steps)
        L_below = T[:, panel] · Wᵀ  masked rows ≥ g0+p   (GEMM)
        T    −= L_below · L_belowᵀ                  (GEMM)
        X[panel rows]  = W · X[panel rows]          (GEMM)
        X[below rows] −= L_below · X[panel rows]    (GEMM)

    The processed region of T is never read again (its garbage is
    masked out of every later panel), and L itself is never stored —
    the inverse substitution consumes each panel in the iteration that
    produces it.
    """
    m = M.shape[0]
    p = panel if panel is not None else _panel_for(m)
    p = min(p, m)
    M, mp = _pad_spd(M, p)
    P = mp // p
    rows = jnp.arange(mp)
    X0 = jnp.eye(mp, dtype=M.dtype)

    def body(j, carry):
        T, X = carry
        g0 = j * p
        D = jax.lax.dynamic_slice(T, (g0, g0), (p, p))
        C, W = _factor_diag_block(D)
        Tpan = jax.lax.dynamic_slice(T, (0, g0), (mp, p))
        # Only the below-panel rows of the L panel are ever consumed
        # (the panel rows' C is already folded into W; rows above hold
        # stale M values the mask discards).
        Lbelow = (Tpan @ W.T) * (rows[:, None] >= g0 + p).astype(M.dtype)
        T = T - Lbelow @ Lbelow.T
        Xp = jax.lax.dynamic_slice(X, (g0, 0), (p, mp))
        Xp = W @ Xp
        X = jax.lax.dynamic_update_slice(X, Xp, (g0, 0))
        X = X - Lbelow @ Xp
        return T, X

    _, X = jax.lax.fori_loop(0, P, body, (M, X0))
    return X[:m, :m] if mp != m else X


@functools.partial(jax.jit, static_argnames=("panel",))
def chol_mxu_factor(M, panel: int | None = None):
    """IN-PLACE panel Cholesky: (m, m) SPD → ``(L, Winv)`` with L padded
    to a panel multiple and ``Winv`` the (P, p, p) inverses of its
    diagonal blocks (collected as the loop factors each — they power
    :func:`panel_cho_solve`'s substitution sweeps). Carries a SINGLE
    (mp, mp) buffer: each panel's columns are overwritten with the
    finished factor while the trailing region keeps the running Schur
    complement.

    The memory-lean large-m path: the fused `chol_inv_mxu` carries
    (T, X) — with XLA's while-loop double-buffering that is ~4 m²
    buffers live, which at m = 10⁴ f64 (800 MB each) OOM'd next to the
    resident 4 GB constraint matrix; even a separate diag-inverse
    dispatch after this one hit RESOURCE_EXHAUSTED in the full-resident
    context (observed repeatedly, 2026-08-01) — hence everything a
    solve needs comes out of this ONE program. (No donation here: the
    identity-tail pad changes the shape, so a donated input could never
    alias the output — the caller's scale/reg stage owns the donation
    instead.)
    """
    m = M.shape[0]
    p = min(panel if panel is not None else _panel_for(m), m)
    M, mp = _pad_spd(M, p)
    P = mp // p
    rows = jnp.arange(mp)

    def body(j, carry):
        T, Wbuf = carry
        g0 = j * p
        D = jax.lax.dynamic_slice(T, (g0, g0), (p, p))
        C, W = _factor_diag_block(D)
        Tpan = jax.lax.dynamic_slice(T, (0, g0), (mp, p))
        # full finished column block: zeros above the panel, C at the
        # panel rows (Tpan @ Wᵀ equals C there), L below.
        colblk = (Tpan @ W.T) * (rows[:, None] >= g0).astype(T.dtype)
        Lbelow = colblk * (rows[:, None] >= g0 + p).astype(T.dtype)

        # Trailing update in COLUMN CHUNKS: a one-shot
        # ``T - Lbelow @ Lbelowᵀ`` materializes the full (mp, mp)
        # emulated-f64 product, whose 8×-f32 operand/accumulator split
        # temps measured 16.83 GB at m=10⁴ via compiled memory_analysis
        # — more than the chip. Chunk width p keeps each product
        # (mp, p): split temps drop to ~8·mp·p·4 B (~80 MB).
        def upd(jc, T):
            c0 = jc * p
            Lc = jax.lax.dynamic_slice(Lbelow, (c0, 0), (p, p))
            Tc = jax.lax.dynamic_slice(T, (0, c0), (mp, p))
            return jax.lax.dynamic_update_slice(
                T, Tc - Lbelow @ Lc.T, (0, c0)
            )

        # chunks at or left of the panel see only Lbelow's zero rows —
        # start at j + 1 (traced lower bound; fori_loop allows it)
        T = jax.lax.fori_loop(j + 1, P, upd, T)
        T = jax.lax.dynamic_update_slice(T, colblk, (0, g0))
        Wbuf = jax.lax.dynamic_update_slice(Wbuf, W[None], (j, 0, 0))
        return T, Wbuf

    return jax.lax.fori_loop(
        0, P, body, (M, jnp.zeros((P, p, p), M.dtype))
    )


@functools.partial(jax.jit, static_argnames=("panel",))
def panel_diag_inv(L, panel: int | None = None):
    """(P, p, p) inverses of L's diagonal blocks. TEST ORACLE: the
    production path gets these from :func:`chol_mxu_factor`'s collected
    ``Winv`` (they fall out of the panel loop for free, and a separate
    dispatch in the full-resident 10k context hit RESOURCE_EXHAUSTED);
    tests cross-check that collection against this standalone
    derivation."""
    mp = L.shape[0]
    p = min(panel if panel is not None else _panel_for(mp), mp)
    P = mp // p
    idx = jnp.arange(P)
    D = L.reshape(P, p, P, p)[idx, :, idx, :]  # (P, p, p) diagonal blocks
    return jax.vmap(_tri_inv_block)(D)


def panel_cho_solve(L, Winv, b):
    """``(L·Lᵀ)⁻¹ b`` via two panel-substitution fori loops — the
    memory-lean solve of the two-stage large-m path: no explicit m×m
    inverse is ever formed (the fused inverse's X/eye buffers were the
    10k endgame's OOM margin), and each solve reads L once per sweep
    (bandwidth-equivalent to the inverse-GEMV it replaces). ``b`` may be
    shorter than L's padded size; the identity pad tail is inert.
    Traceable — the endgame step jits it into its program."""
    mp = L.shape[0]
    P, p, _ = Winv.shape
    m = b.shape[0]
    bp = jnp.zeros(mp, L.dtype).at[:m].set(b) if m != mp else b

    def fwd(j, y):
        g0 = j * p
        Lrows = jax.lax.dynamic_slice(L, (g0, 0), (p, mp))
        r = jax.lax.dynamic_slice(bp, (g0,), (p,)) - Lrows @ y
        return jax.lax.dynamic_update_slice(y, Winv[j] @ r, (g0,))

    y = jax.lax.fori_loop(0, P, fwd, jnp.zeros(mp, L.dtype))

    def bwd(i, x):
        j = P - 1 - i
        g0 = j * p
        Lcols = jax.lax.dynamic_slice(L, (0, g0), (mp, p))
        r = jax.lax.dynamic_slice(y, (g0,), (p,)) - Lcols.T @ x
        return jax.lax.dynamic_update_slice(x, Winv[j].T @ r, (g0,))

    x = jax.lax.fori_loop(0, P, bwd, jnp.zeros(mp, L.dtype))
    return x[:m] if m != mp else x


@functools.partial(jax.jit, static_argnames=("panel", "out_m"))
def tri_inv_mxu(L, panel: int | None = None, out_m: int | None = None):
    """Explicit L⁻¹ of a (possibly identity-tail-padded) lower-
    triangular L. TEST ORACLE for the panel pipeline (production solves
    never form an m×m inverse — :func:`panel_cho_solve` substitutes
    panel-by-panel precisely because this inverse's X/eye buffers were
    the 10k endgame's OOM margin). ``out_m`` slices the pad back off."""
    mp = L.shape[0]
    p = min(panel if panel is not None else _panel_for(mp), mp)
    rows = jnp.arange(mp)
    X0 = jnp.eye(mp, dtype=L.dtype)

    def body(j, X):
        g0 = j * p
        C = jax.lax.dynamic_slice(L, (g0, g0), (p, p))
        W = _tri_inv_block(C)
        Xp = W @ jax.lax.dynamic_slice(X, (g0, 0), (p, mp))
        X = jax.lax.dynamic_update_slice(X, Xp, (g0, 0))
        Lbelow = jax.lax.dynamic_slice(L, (0, g0), (mp, p)) * (
            rows[:, None] >= g0 + p
        ).astype(L.dtype)
        return X - Lbelow @ Xp

    X = jax.lax.fori_loop(0, mp // p, body, X0)
    return X[:out_m, :out_m] if out_m is not None and out_m != mp else X
