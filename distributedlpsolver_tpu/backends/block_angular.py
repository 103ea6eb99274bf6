"""Block-angular Schur-complement backend — the pds-* distributed path.

The reference's core distributed feature (BASELINE.json:5,8): block-
angular problems (multicommodity flow pds-*, stochastic stormG2) are
row-partitioned so each rank owns a diagonal block, forms its local
normal-equation/Schur contribution, and an ``MPI_Allreduce`` sums the
dense linking-block Schur complement which is then factorized replicated.

TPU-native restatement:

* The K diagonal blocks live on a *leading batch axis*: ``B_all (K, mb,
  nb)``, ``L_all (K, link, nb)``. Per-block factorizations and solves are
  ``vmap``-batched — K small Choleskys become one batched MXU-friendly
  kernel instead of K sequential ones.
* The Schur complement ``S = M_LL - Σ_k G_k M_kk⁻¹ G_kᵀ`` is a sum over
  the K axis; sharding that axis over the mesh turns the sum into an XLA
  all-reduce over ICI — *the* reference Allreduce (SURVEY.md §3.2),
  compiler-inserted.
* Everything runs inside the same shared Mehrotra step (ipm/core.py);
  only the LinOps seam differs from the dense backend.

Structure handling: the backend consumes the ``block_structure`` hint
carried by the problem (generator-produced, or user-annotated for real
pds/stormG2 files) describing the *original* row/column grouping, and
maps interior-form columns (slacks appended by to_interior_form, free
splits) to their block by sparsity: a column belongs to block k if its
nonzeros touch only block-k rows (± linking rows); columns touching only
linking rows (e.g. linking-row slacks) form the dense border. Columns
spanning two blocks would break the arrow structure and raise (route
those problems to the dense/sharded backends).
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from distributedlpsolver_tpu.backends.base import SolverBackend, register_backend
from distributedlpsolver_tpu.ipm import core
from distributedlpsolver_tpu.ipm.config import SolverConfig
from distributedlpsolver_tpu.ipm.state import IPMState, StepStats
from distributedlpsolver_tpu.models.problem import InteriorForm
from distributedlpsolver_tpu.parallel import mesh as mesh_lib


class BlockTensors(NamedTuple):
    """Stacked device arrays describing the arrow-structured A."""

    B_all: jnp.ndarray  # (K, mb, nb)  diagonal blocks (zero-padded rows/cols)
    L_all: jnp.ndarray  # (K, link, nb) linking-row entries of block cols
    A0: jnp.ndarray  # (link, n0)   border columns (linking rows only)
    col_idx: jnp.ndarray  # (K, nb) int32 → index into x_pad (n is the sentinel)
    border_idx: jnp.ndarray  # (n0,) int32
    row_idx: jnp.ndarray  # (K, mb) int32 → interior row (m is the sentinel)
    link_idx: jnp.ndarray  # (link,) int32 interior rows of the linking system


class BlockLayout(NamedTuple):
    K: int
    mb: int
    nb: int
    link: int
    n0: int
    n: int
    m: int


def analyze_structure(inf: InteriorForm) -> Tuple[BlockLayout, dict]:
    """Derive the interior-form block layout from the problem's hint.

    Two hint formats are accepted:

    * legacy uniform: ``{num_blocks, block_m, link_m}`` — rows ordered
      [K·block_m block rows, link_m linking rows];
    * general: ``{num_blocks, row_block}`` with ``row_block[i] ∈
      {-1 (linking), 0..K-1}`` in ANY order with ragged block sizes
      (the format models/structure.py's detector emits). Blocks are
      padded to the largest block's row count via index maps — no
      physical permutation of the problem.

    Returns the layout plus host-side index arrays. Raises ValueError when
    the hint is missing or a column spans multiple blocks.
    """
    hint = inf.block_structure
    if not hint:
        raise ValueError(
            "block backend needs problem.block_structure "
            "{num_blocks, block_m, link_m} or {num_blocks, row_block}"
        )
    m, n = inf.m, inf.n
    K = int(hint["num_blocks"])
    if "row_block" in hint:
        row_block = np.asarray(hint["row_block"], dtype=np.int64)
        if row_block.shape != (m,):
            raise ValueError(
                f"row_block has shape {row_block.shape}, expected ({m},)"
            )
        if row_block.min() < -1 or row_block.max() >= K:
            # An out-of-range id would silently drop that row's equation
            # from every operator — reject instead of solving a different LP.
            raise ValueError(
                f"row_block ids must lie in [-1, {K - 1}], got range "
                f"[{row_block.min()}, {row_block.max()}]"
            )
    else:
        mb_u, link_u = int(hint["block_m"]), int(hint["link_m"])
        if K * mb_u + link_u != m:
            raise ValueError(f"structure hint rows {K}*{mb_u}+{link_u} != m={m}")
        row_block = np.concatenate(
            [np.repeat(np.arange(K, dtype=np.int64), mb_u), np.full(link_u, -1)]
        )
    sizes = np.bincount(row_block[row_block >= 0], minlength=K)
    mb = int(sizes.max()) if K else 0
    link = int((row_block == -1).sum())

    from distributedlpsolver_tpu.models.structure import column_block_ids

    A = sp.csc_matrix(inf.A) if sp.issparse(inf.A) else sp.csc_matrix(np.asarray(inf.A))
    # Column → block via shared segment reductions (models/structure.py);
    # validation rejects columns whose non-linking rows disagree.
    block_of_col = column_block_ids(A, row_block, validate=True)

    counts = np.bincount(block_of_col[block_of_col >= 0], minlength=K)
    nb = int(counts.max()) if K else 0
    border = np.flatnonzero(block_of_col == -1)
    layout = BlockLayout(K=K, mb=mb, nb=nb, link=link, n0=len(border), n=n, m=m)
    return layout, {
        "block_of_col": block_of_col,
        "border": border,
        "A": A,
        "row_block": row_block,
    }


def build_tensors(
    inf: InteriorForm, dtype, shard_put=None, pad_blocks: int = 0
) -> Tuple[BlockTensors, BlockLayout]:
    """``pad_blocks`` appends DEAD blocks to the K axis: all-sentinel
    index maps (every row/column reads the padded zero slot) and zero
    B/L tiles. A dead block's normal matrix gets the unit diagonal the
    sentinel-row machinery already installs (``pad_diag`` at each
    factorization site), so it factors cleanly, contributes nothing to
    the linking Schur sum (G_k = 0), and scatters nothing back. This is
    the ragged-tail layout that lets an ARBITRARY mesh width divide the
    block axis: K blocks shard over ``axis_size`` devices as
    ``ceil(K / axis_size)`` per device with the tail masked — survivor
    counts after an elastic shrink no longer need to divide K."""
    layout, info = analyze_structure(inf)
    K, mb, nb, link, n0, n, m = layout
    K = K + max(0, int(pad_blocks))
    layout = layout._replace(K=K)
    # Slice per block straight out of the sparse matrix — densifying only
    # the (mb, nb_k) / (link, nb_k) tiles that exist. Never materialize the
    # full m×n dense A: for a Mittelmann-scale sparse problem that is the
    # multi-terabyte allocation the sparse routing exists to avoid.
    Ar = info["A"].tocsr()
    block_of_col, border = info["block_of_col"], info["border"]
    row_block = info["row_block"]
    link_rows = np.flatnonzero(row_block == -1)
    A_link = Ar[link_rows].tocsc() if link else sp.csc_matrix((0, n))

    B_all = np.zeros((K, mb, nb))
    L_all = np.zeros((K, link, nb))
    col_idx = np.full((K, nb), n, dtype=np.int32)  # sentinel → padded zero
    row_idx = np.full((K, mb), m, dtype=np.int32)  # sentinel → padded zero row
    for k in range(K):
        cols = np.flatnonzero(block_of_col == k)
        rows = np.flatnonzero(row_block == k)
        col_idx[k, : len(cols)] = cols
        row_idx[k, : len(rows)] = rows
        B_all[k, : len(rows), : len(cols)] = Ar[rows][:, cols].toarray()
        L_all[k, :, : len(cols)] = A_link[:, cols].toarray()
    A0 = A_link[:, border].toarray() if n0 else np.zeros((link, 0))

    put = shard_put or (lambda x, kind: jnp.asarray(x))
    tensors = BlockTensors(
        B_all=put(B_all.astype(dtype), "blocked"),
        L_all=put(L_all.astype(dtype), "blocked"),
        A0=put(A0.astype(dtype), "rep"),
        col_idx=put(col_idx, "blocked"),
        border_idx=put(border.astype(np.int32), "rep"),
        row_idx=put(row_idx, "blocked"),
        link_idx=put(link_rows.astype(np.int32), "rep"),
    )
    return tensors, layout


# Above this many stored f64 tensor entries, block matvec/rmatvec/diag
# contractions on TPU run as elementwise multiply + reduction instead of
# dot_generals: XLA's emulated-f64 DOT lowering materializes 8×-f32
# operand-split temps of the FULL operand (observed at the pds-20 class,
# K=64 link=1600 nb≈1300: a 3.91 GB + 1.95 GB pair of L_all-sized HLO
# temps → compile-time HBM OOM), while elementwise double-double ops
# fuse with the reduce. Mirrors dense._use_ew_f64; arithmetic identical.
_EW_F64_BLOCK_ENTRIES = 1 << 24

# HBM budget for the 8×-f32 operand-split temps of a ONE-SHOT f64 Schur
# assembly; above it the full-precision phase runs n-chunked ("f64c").
# 2e9, not the round-4 4e9: the storm100k-class instance (K=256 merged,
# mb=384, nb=768 — split_bytes 2.8e9) reliably CRASHED the TPU worker in
# its one-shot f64 phase while its f32 phase and the chunked programs
# run clean (2026-08-01; same workload-correlated crash class as the
# round-4 batched chunk≥256 PCG programs). pds-10-class (1.6e9) stays
# direct and is measured healthy.
_F64_SPLIT_BUDGET = 2e9


# ROUND5_NOTES lever 4: the storm ≥100k-row class dies on an f64
# program-class kernel fault (the worker crashes outright on the big-K
# batched f64 phases), not on HBM — while chunk ≤128 program shapes
# stay in the healthy class. The f64 factorize/solve kernels therefore
# run the K axis in SEQUENTIAL groups of ≤ _K_GROUP blocks: every
# batched cholesky/einsum instance the compiler sees is a ≤128-block
# program, and the group results concatenate/accumulate outside the
# kernels. Read ONCE at import — a per-call toggle would be invisible
# to the jit cache (traces key on shapes, not module globals), which is
# why the run_storm100k.py A/B harness isolates each arm in its own
# subprocess. f32 phases keep their one-shot shapes (measured healthy).
_K_GROUP = int(os.environ.get("DLPS_BLOCK_K_GROUP", "128"))


def _k_groups(K: int, group: Optional[int] = None) -> list:
    """Static [(start, size), …] covering the K axis in ≤group-size
    runs; the degenerate [(0, K)] (one-shot) when grouping is off
    (group ≤ 0) or K already fits one group."""
    g = _K_GROUP if group is None else group
    if g <= 0 or K <= g:
        return [(0, K)]
    return [(s, min(g, K - s)) for s in range(0, K, g)]


def phase_program_class(K: int, dtype) -> str:
    """Program-class stamp of one phase's batched-K kernels — the
    per-phase label the run_storm100k.py A/B harness records. f64
    phases with K past the group cap run K-grouped (lever 4);
    everything else is one-shot."""
    name = jnp.dtype(dtype).name
    if name == "float64" and len(_k_groups(K)) > 1:
        return f"{name}-kgroup{_K_GROUP}"
    return f"{name}-oneshot"


def _ew_block(t: "BlockTensors") -> bool:
    return (
        t.B_all.dtype == jnp.float64
        and t.B_all.size + t.L_all.size > _EW_F64_BLOCK_ENTRIES
        and jax.default_backend() == "tpu"
    )


def _chol_mxu_here(dtype) -> bool:
    """Shared routing predicate (defined in dense.py so the env override
    and platform rule cannot diverge between backends)."""
    from distributedlpsolver_tpu.backends.dense import _use_chol_mxu

    return _use_chol_mxu(dtype)


def _block_ops(t: BlockTensors, lay: BlockLayout, reg, dtype, gram_s=False,
               link_shard=None):
    """LinOps over the arrow structure (shared-core seam).

    ``gram_s`` switches the linking Schur complement's assembly to the
    cancellation-free GRAM form — the fix for the diagnosed f32 floor
    (round-4 utilization analysis: direct f32
    ``S = MLL − Σ Gₖ Mₖₖ⁻¹ Gₖᵀ`` subtracts two near-equal PSD matrices,
    so S's relative error grows as ε₃₂·‖MLL‖/‖S‖ and the f32 phases die
    at err ≈ 2e-2, handing 19 of 31 iterations to the 3.3 s/iter f64c
    finisher). Algebra: with weighted tensors ``Bw = B·D^½``,
    ``Lw = L·D^½`` and ``Cₖ = Lₖ⁻¹Bw`` (so ``CₖCₖᵀ = I`` exactly),

        S = Σₖ Zₖ Zₖᵀ,   Zₖ = Lw − (Cₖᵀ·(Cₖ·Lwᵀ))ᵀ

    Z is formed EXPLICITLY — the cancellation lands in Z's entries,
    which sit at the square root of S's scale, so only half the digits
    are lost — and Z·Zᵀ is a clean positive Gram product. Error drops
    from ε₃₂·(‖MLL‖/‖S‖) to ~ε₃₂·√(‖MLL‖/‖S‖): at a 1e10 scale ratio
    that is 6e-3 instead of garbage. Intended for the f32 phase-1 /
    preconditioner instances (the f64 direct path keeps the one-GEMM
    difference form — ε₆₄ absorbs the cancellation there)."""
    K, mb, nb, link, n0, n, m = lay
    ew = _ew_block(t)

    def pad(v):
        return jnp.concatenate([v, jnp.zeros(1, dtype=v.dtype)])

    def matvec(x):
        xb = pad(x)[t.col_idx]  # (K, nb)
        if ew:
            y_blocks = jnp.sum(t.B_all * xb[:, None, :], axis=-1)
            y_link = jnp.sum(t.L_all * xb[:, None, :], axis=(0, -1))
        else:
            y_blocks = jnp.einsum("kmn,kn->km", t.B_all, xb)
            y_link = jnp.einsum("kln,kn->l", t.L_all, xb)
        if n0:
            y_link = y_link + t.A0 @ x[t.border_idx]
        # Scatter through the row maps (sentinel row m falls off the end);
        # with the legacy contiguous layout this is a pure permutation.
        out = jnp.zeros(m + 1, dtype=x.dtype).at[t.row_idx].add(y_blocks)
        return out.at[t.link_idx].add(y_link)[:m]

    def rmatvec(y):
        yb = pad(y)[t.row_idx]  # (K, mb); padded rows read 0
        yL = y[t.link_idx]
        if ew:
            g = jnp.sum(t.B_all * yb[:, :, None], axis=1) + jnp.sum(
                t.L_all * yL[None, :, None], axis=1
            )
        else:
            g = jnp.einsum("kmn,km->kn", t.B_all, yb) + jnp.einsum(
                "kln,l->kn", t.L_all, yL
            )
        out = jnp.zeros(n + 1, dtype=y.dtype).at[t.col_idx].add(g)[:n]
        if n0:
            out = out.at[t.border_idx].add(t.A0.T @ yL)
        return out

    # f64 factorizations on TPU route through the GEMM-dominated panel
    # factor+inverse (ops/chol_mxu.py): the builtin emulated-f64
    # cholesky/cho_solve lower to scalarized recurrences ~10× slower
    # (measured, scripts/probe_chol_mxu.py). Inverse-based factors turn
    # every solve into batched GEMVs; the f32 instances (phase-1 /
    # preconditioner ops) keep the fast native builtins, and the gram
    # factorize returns plain cholesky factors, never inverses.
    use_mxu = _chol_mxu_here(t.B_all.dtype) and not gram_s
    # ``link_shard`` (a NamedSharding, mesh runs only) distributes the
    # link×link Schur factorization: chol_tri_inv_mesh never
    # materializes a replicated factor, and its input constraint turns
    # the K-contraction all-reduce into a reduce-scatter (review
    # round-4 item 5/7 — the replicated linking factor was the
    # per-device HBM floor at link=1600). Solves then apply the
    # column-sharded L⁻¹ as two sharded GEMVs.
    ls_inv = use_mxu or link_shard is not None

    def _link_factor(S):
        if link_shard is not None:
            from distributedlpsolver_tpu.ops.dist_chol import (
                chol_tri_inv_mesh,
            )

            return chol_tri_inv_mesh(_rel_diag_reg(S, reg), link_shard)
        if use_mxu:
            from distributedlpsolver_tpu.ops.chol_mxu import chol_inv_mxu

            return chol_inv_mxu(_rel_diag_reg(S, reg))
        return jnp.linalg.cholesky(_rel_diag_reg(S, reg))

    def factorize_gram(d):
        dB = pad(d)[t.col_idx]  # (K, nb); padded cols get d=0, sq=0
        sq = jnp.sqrt(dB)
        Bw = t.B_all * sq[:, None, :]  # (K, mb, nb)
        Lw = t.L_all * sq[:, None, :]  # (K, link, nb)
        Mkk = jnp.einsum("kmn,kpn->kmp", Bw, Bw)
        pad_diag = (t.row_idx == m).astype(Mkk.dtype)
        Mkk = Mkk + jnp.zeros_like(Mkk).at[
            :, jnp.arange(mb), jnp.arange(mb)
        ].set(pad_diag)
        Lk = jnp.linalg.cholesky(_rel_diag_reg(Mkk, reg))
        Ck = jax.scipy.linalg.solve_triangular(Lk, Bw, lower=True)
        Uk = jnp.einsum("kmn,kln->kml", Ck, Lw)  # (K, mb, link)
        Zk = Lw - jnp.einsum("kml,kmn->kln", Uk, Ck)
        S = jnp.einsum("kln,kpn->lp", Zk, Zk)
        if n0:
            # Border columns touch only linking rows — a pure Gram
            # addition, no block coupling to cancel against.
            A0w = t.A0 * jnp.sqrt(d[t.border_idx])[None, :]
            S = S + A0w @ A0w.T
        Gk = jnp.einsum("kln,kmn->klm", Lw, Bw)  # = L·D·Bᵀ (sq·sq = dB)
        return Lk, _link_factor(S), Gk

    # K-grouped f64 phases (ROUND5_NOTES lever 4): the full-precision
    # direct kernels are the program class that faults at storm-100k K;
    # groups of ≤ _K_GROUP keep every batched instance healthy. The
    # single-group case traces EXACTLY the pre-grouping program (the
    # one-shot identity), so small-K solves are byte-identical.
    kgroup = (not gram_s) and t.B_all.dtype == jnp.dtype("float64")

    def factorize(d):
        if gram_s:
            return factorize_gram(d)
        dB = pad(d)[t.col_idx]  # (K, nb); padded cols get d=0
        # Padded (sentinel) rows are all-zero in B_all → zero rows/cols in
        # M_kk, which would sink the batched Cholesky. A unit diagonal
        # decouples them: their rhs entries are zero, so their solution
        # components stay exactly zero.
        pad_diag = (t.row_idx == m).astype(t.B_all.dtype)  # (K, mb)
        groups = _k_groups(K) if kgroup else [(0, K)]
        fac_parts, Gk_parts = [], []
        S = jnp.zeros((link, link), dtype=t.B_all.dtype)
        for s, g in groups:
            Bg = t.B_all[s : s + g]
            Lg = t.L_all[s : s + g]
            dg = dB[s : s + g]
            Bd = Bg * dg[:, None, :]
            Mkk = jnp.einsum("kmn,kpn->kmp", Bd, Bg)
            Mkk = Mkk + jnp.zeros_like(Mkk).at[
                :, jnp.arange(mb), jnp.arange(mb)
            ].set(pad_diag[s : s + g])
            Gk = jnp.einsum("kln,kmn->klm", Lg * dg[:, None, :], Bg)
            if use_mxu:
                from distributedlpsolver_tpu.ops.chol_mxu import chol_inv_mxu

                Lki = jax.vmap(chol_inv_mxu)(_rel_diag_reg(Mkk, reg))
                # H_k = M_kk⁻¹ G_kᵀ via two batched GEMMs with Lk⁻¹
                Hk = jnp.einsum(
                    "kpm,kpl->kml", Lki, jnp.einsum("kmp,klp->kml", Lki, Gk)
                )
                fac_parts.append(Lki)
            else:
                Lk = jnp.linalg.cholesky(_rel_diag_reg(Mkk, reg))
                # H_k = M_kk⁻¹ G_kᵀ (batched two-triangular-solve)
                Hk = jax.scipy.linalg.cho_solve(
                    (Lk, True), jnp.swapaxes(Gk, 1, 2)
                )
                fac_parts.append(Lk)
            # Contract K INSIDE the einsum: the two-step form
            # einsum("kln,kpn->klp").sum(0) materializes a (K, link, link)
            # intermediate — 10.5 GB in f64 at the pds-20 class (K=64,
            # link=1600), the exact compile-time HBM OOM observed on one
            # chip. Contracting k,n together lowers to a single
            # (link, K·nb)×(K·nb, link) GEMM with tile-sized temps. Under a
            # K-sharded mesh GSPMD still emits per-device partial sums + one
            # all-reduce, same as the .sum(0) form. The Σ_k is the
            # reference's MPI_Allreduce of Schur blocks (BASELINE.json:5).
            S = S + jnp.einsum("kln,kpn->lp", Lg * dg[:, None, :], Lg)
            S = S - jnp.einsum("klm,kmp->lp", Gk, Hk)
            Gk_parts.append(Gk)
        if n0:
            d0 = d[t.border_idx]
            S = S + (t.A0 * d0[None, :]) @ t.A0.T
        fac = fac_parts[0] if len(fac_parts) == 1 else jnp.concatenate(fac_parts)
        Gk = Gk_parts[0] if len(Gk_parts) == 1 else jnp.concatenate(Gk_parts)
        return fac, _link_factor(S), Gk

    def solve(factors, r):
        Lk, Ls, Gk = factors
        rb = pad(r)[t.row_idx]  # (K, mb); padded rows read 0
        rL = r[t.link_idx]
        if use_mxu:
            # factors hold EXPLICIT inverses: every solve is GEMVs.
            blk = lambda L, v: jnp.einsum(
                "kpm,kp->km", L, jnp.einsum("kmp,kp->km", L, v)
            )
        else:
            blk = lambda L, v: jax.scipy.linalg.cho_solve(
                (L, True), v[..., None]
            )[..., 0]
        if ls_inv:
            lnk = lambda v: Ls.T @ (Ls @ v)
        else:
            lnk = lambda v: jax.scipy.linalg.cho_solve((Ls, True), v)
        groups = _k_groups(K) if kgroup else [(0, K)]
        tmps = [blk(Lk[s : s + g], rb[s : s + g]) for s, g in groups]
        rS = rL - sum(
            jnp.einsum("klm,km->l", Gk[s : s + g], tmp)
            for (s, g), tmp in zip(groups, tmps)
        )
        yL = lnk(rS)
        yb_parts = [
            blk(
                Lk[s : s + g],
                rb[s : s + g] - jnp.einsum("klm,l->km", Gk[s : s + g], yL),
            )
            for s, g in groups
        ]
        yb = yb_parts[0] if len(yb_parts) == 1 else jnp.concatenate(yb_parts)
        out = jnp.zeros(m + 1, dtype=r.dtype).at[t.row_idx].add(yb)
        return out.at[t.link_idx].add(yL)[:m]

    return core.LinOps(
        xp=jnp, matvec=matvec, rmatvec=rmatvec, factorize=factorize, solve=solve
    )


def _block_ops_mixed(t64: BlockTensors, t32: BlockTensors, lay: BlockLayout,
                     reg, link_shard=None):
    """Phase-1 LinOps: residual matvecs in full precision against the f64
    tensors, factorizations/solves through the f32 tensor stack on the MXU
    (the dense backend's two-phase split, restated for the arrow
    structure). Solutions cast back up so the Mehrotra step's state stays
    f64."""
    base = _block_ops(t64, lay, reg, None)
    f32 = jnp.float32
    # Gram-form S (see _block_ops): keeps the f32 phase's factor quality
    # from collapsing to the ε₃₂·‖MLL‖/‖S‖ cancellation floor, so phase 1
    # carries iterations the f64 finisher otherwise owns.
    ops32 = _block_ops(t32, lay, jnp.asarray(reg, f32), None, gram_s=True,
                       link_shard=link_shard)

    def factorize(d):
        return ops32.factorize(d.astype(f32))

    def solve(factors, r):
        return ops32.solve(factors, r.astype(f32)).astype(r.dtype)

    return core.LinOps(
        xp=jnp,
        matvec=base.matvec,
        rmatvec=base.rmatvec,
        factorize=factorize,
        solve=solve,
    )


def _rel_diag_reg(M, reg):
    """Per-row relative diagonal perturbation (shared by every block
    factorize — one definition so the reg semantics cannot diverge)."""
    di = jnp.diagonal(M, axis1=-2, axis2=-1)
    return M + jnp.zeros_like(M).at[
        ..., jnp.arange(M.shape[-1]), jnp.arange(M.shape[-1])
    ].set(reg * di)


# HBM budget for one n-chunk's emulated-f64 operand-split temps in the
# f64c assembly (~32 bytes per (K·(link+mb))·chunk entry). 2 GB leaves
# room for M, the factors, and the step's working set on a 16 GB chip.
_F64C_TEMP_BUDGET = 2e9


def _block_ops_f64c(t: BlockTensors, lay: BlockLayout, reg,
                    chunk: Optional[int] = None, link_shard=None):
    """Full-precision direct Schur LinOps for HUGE shapes (the block
    analogue of the dense endgame): the f64 assembly einsums run
    n-CHUNKED inside a fori_loop, so XLA's emulated-f64 dot_generals see
    only (…, chunk)-sized operands — their 8×-f32 operand-split temps
    drop from the full-tensor gigabytes (the observed pds-20 OOM) to
    ~chunk/nb of that. Triangular factors are explicitly inverted
    (batched small TRSMs against the identity), so every solve is a
    batched GEMV — no large-rhs TRSM lowering ever runs.

    ``chunk=None`` sizes the chunk to the temp budget: the LARGEST chunk
    whose split temps fit _F64C_TEMP_BUDGET, floored at 128. Bigger
    chunks mean fewer, larger emulated-f64 dots — measured at the pds-20
    class: 70.7 s vs 81.6 s full solve (1.15×) going from the old fixed
    128 to budget-sized (480), identical iterations and result (round 4,
    builder chip run).

    Per-iteration cost at the pds-20 class (K=64, mb=432, nb≈1300,
    link=1600): ~5e11 emulated-f64 flops ≈ 2–3 s of MXU time — the
    price of true f64 factor quality, paid only for the final orders of
    magnitude after the f32 phases hand over.
    """
    K, mb, nb, link, n0, n, m = lay
    if chunk is None:
        chunk = max(128, int(_F64C_TEMP_BUDGET / (32.0 * K * (link + mb))))
    chunk = min(chunk, nb)  # small shapes: fori body must trace in-bounds
    base = _block_ops(t, lay, reg, None)  # ew-f64 mat/rmatvec shared
    use_mxu = _chol_mxu_here(t.B_all.dtype)

    def factorize(d):
        dB = jnp.concatenate([d, jnp.zeros(1, d.dtype)])[t.col_idx]
        nfull = nb // chunk
        dt = t.B_all.dtype
        pad_diag = (t.row_idx == m).astype(dt)
        # K-grouped outer loop (ROUND5_NOTES lever 4, same rationale as
        # _block_ops): every n-chunked emulated-f64 dot and every
        # batched factor kernel sees ≤ _K_GROUP blocks — the f64c
        # finisher is exactly the phase the storm-100k class faults in.
        # One group (K ≤ _K_GROUP) traces the pre-grouping program.
        groups = _k_groups(K)
        Lki_parts, Gk_parts = [], []
        MLL = jnp.zeros((link, link), dt)
        S = jnp.zeros((link, link), dt)
        for s, g in groups:
            Bfull = t.B_all[s : s + g]
            Lfull = t.L_all[s : s + g]
            dfull = dB[s : s + g]

            def contrib(Bc, Lc, dc):
                Bd = Bc * dc[:, None, :]
                Ld = Lc * dc[:, None, :]
                return (
                    jnp.einsum("kmc,kpc->kmp", Bd, Bc),
                    jnp.einsum("klc,kmc->klm", Ld, Bc),
                    jnp.einsum("klc,kpc->lp", Ld, Lc),
                )

            def body(jb, acc):
                Mkk, Gk, MLLg = acc
                j0 = jb * chunk
                dMkk, dGk, dMLL = contrib(
                    jax.lax.dynamic_slice_in_dim(Bfull, j0, chunk, 2),
                    jax.lax.dynamic_slice_in_dim(Lfull, j0, chunk, 2),
                    jax.lax.dynamic_slice_in_dim(dfull, j0, chunk, 1),
                )
                return Mkk + dMkk, Gk + dGk, MLLg + dMLL

            Mkk, Gk, MLLg = jax.lax.fori_loop(
                0, nfull, body,
                (
                    jnp.zeros((g, mb, mb), dt),
                    jnp.zeros((g, link, mb), dt),
                    jnp.zeros((link, link), dt),
                ),
            )
            # Ragged tail as one static slice (accumulation forbids the
            # clamped-slice trick — a re-read tail would double-count —
            # and padding copies of the full tensors would cost ~1.5 GB
            # inside the very path built to bound HBM).
            if nb - nfull * chunk:
                j0 = nfull * chunk
                dMkk, dGk, dMLL = contrib(
                    Bfull[:, :, j0:], Lfull[:, :, j0:], dfull[:, j0:]
                )
                Mkk, Gk, MLLg = Mkk + dMkk, Gk + dGk, MLLg + dMLL
            MLL = MLL + MLLg
            Mkk = Mkk + jnp.zeros_like(Mkk).at[
                :, jnp.arange(mb), jnp.arange(mb)
            ].set(pad_diag[s : s + g])
            # Explicit inverse factors: the link-many-rhs TRSM these
            # replace is exactly the lowering that blows temps; GEMVs
            # against Lk⁻¹ are clean batched dots. On TPU the
            # factor+inverse itself runs through the GEMM-dominated
            # panel kernel (ops/chol_mxu.py) — XLA's emulated-f64
            # cholesky/solve_triangular lower to scalarized recurrences
            # ~10× slower (measured, probe_chol_mxu).
            if use_mxu:
                from distributedlpsolver_tpu.ops.chol_mxu import chol_inv_mxu

                Lki = jax.vmap(chol_inv_mxu)(_rel_diag_reg(Mkk, reg))
            else:
                eye_b = jnp.broadcast_to(jnp.eye(mb, dtype=dt), (g, mb, mb))
                Lki = jax.scipy.linalg.solve_triangular(
                    jnp.linalg.cholesky(_rel_diag_reg(Mkk, reg)), eye_b,
                    lower=True,
                )
            # H_k = M_kk⁻¹ G_kᵀ via two batched GEMMs with Lk⁻¹
            tmp = jnp.einsum("kmp,klp->kml", Lki, Gk)  # Lk⁻¹ Gkᵀ
            Hk = jnp.einsum("kpm,kpl->kml", Lki, tmp)  # Lk⁻ᵀ (…)
            S = S - jnp.einsum("klm,kmp->lp", Gk, Hk)
            Lki_parts.append(Lki)
            Gk_parts.append(Gk)
        if n0:
            d0 = d[t.border_idx]
            MLL = MLL + (t.A0 * d0[None, :]) @ t.A0.T
        S = MLL + S
        Lki = (
            Lki_parts[0] if len(Lki_parts) == 1
            else jnp.concatenate(Lki_parts)
        )
        Gk = Gk_parts[0] if len(Gk_parts) == 1 else jnp.concatenate(Gk_parts)
        if link_shard is not None:
            from distributedlpsolver_tpu.ops.dist_chol import (
                chol_tri_inv_mesh,
            )

            Lsi = chol_tri_inv_mesh(_rel_diag_reg(S, reg), link_shard)
        elif use_mxu:
            from distributedlpsolver_tpu.ops.chol_mxu import chol_inv_mxu

            Lsi = chol_inv_mxu(_rel_diag_reg(S, reg))
        else:
            Lsi = jax.scipy.linalg.solve_triangular(
                jnp.linalg.cholesky(_rel_diag_reg(S, reg)),
                jnp.eye(link, dtype=dt), lower=True,
            )
        return Lki, Lsi, Gk

    def solve(factors, r):
        Lki, Lsi, Gk = factors
        rb = jnp.concatenate([r, jnp.zeros(1, r.dtype)])[t.row_idx]
        rL = r[t.link_idx]
        groups = _k_groups(K)

        def blk(L, v):
            # M_kk⁻¹ v via two batched GEMVs with Lk⁻¹
            return jnp.einsum("kpm,kp->km", L, jnp.einsum("kmp,kp->km", L, v))

        tmps = [blk(Lki[s : s + g], rb[s : s + g]) for s, g in groups]
        rS = rL - sum(
            jnp.einsum("klm,km->l", Gk[s : s + g], tmp)
            for (s, g), tmp in zip(groups, tmps)
        )
        yL = Lsi.T @ (Lsi @ rS)
        yb_parts = [
            blk(
                Lki[s : s + g],
                rb[s : s + g] - jnp.einsum("klm,l->km", Gk[s : s + g], yL),
            )
            for s, g in groups
        ]
        yb = yb_parts[0] if len(yb_parts) == 1 else jnp.concatenate(yb_parts)
        out = jnp.zeros(m + 1, dtype=r.dtype).at[t.row_idx].add(yb)
        return out.at[t.link_idx].add(yL)[:m]

    return core.LinOps(
        xp=jnp, matvec=base.matvec, rmatvec=base.rmatvec,
        factorize=factorize, solve=solve,
    )


def _block_diag_m(t: BlockTensors, lay: BlockLayout, d):
    """diag(A·diag(d)·Aᵀ) in full precision — the consistent diagonal for
    the PCG operator's regularization term (mirrors the dense backend's
    ``reg·diag(M)``)."""
    K, mb, nb, link, n0, n, m = lay
    dB = jnp.concatenate([d, jnp.zeros(1, d.dtype)])[t.col_idx]  # (K, nb)
    if _ew_block(t):
        diag_blocks = jnp.sum(t.B_all * t.B_all * dB[:, None, :], axis=-1)
        diag_link = jnp.sum(
            t.L_all * t.L_all * dB[:, None, :], axis=(0, -1)
        )
    else:
        diag_blocks = jnp.einsum("kmn,kn->km", t.B_all * t.B_all, dB)
        diag_link = jnp.einsum("kln,kn->l", t.L_all * t.L_all, dB)
    if n0:
        diag_link = diag_link + (t.A0 * t.A0) @ d[t.border_idx]
    out = jnp.zeros(m + 1, dtype=d.dtype).at[t.row_idx].add(diag_blocks)
    return out.at[t.link_idx].add(diag_link)[:m]


def _block_pcg_ops(t64, t32, lay, reg, cg_tol, cg_iters, link_shard=None):
    """PCG LinOps for the arrow structure: the f32 Schur factorization
    (per-block Choleskys + linking-system Cholesky, all MXU work) is only
    a PRECONDITIONER; accuracy comes from CG whose operator applies
    ``A·diag(d)·Aᵀ (+reg·diag)`` matrix-free through the full-precision
    tensors — einsums linear in the stored entries, so no emulated-f64
    O(K·mb²·nb) assembly or O(link²·K·nb) linking-system work ever runs.
    Same design as dense._pcg_ops; shares core.pcg_solve."""
    base = _block_ops(t64, lay, reg, None)
    f32 = jnp.float32
    # Gram-form S for the preconditioner too (same rationale as
    # _block_ops_mixed): the round-4 run's PCG phase executed ZERO
    # iterations because its f32-assembled S was cancellation garbage
    # by handoff time.
    ops32 = _block_ops(t32, lay, jnp.asarray(reg, f32), None, gram_s=True,
                       link_shard=link_shard)

    def factorize(d):
        factors32 = ops32.factorize(d.astype(f32))
        regd = jnp.asarray(reg, d.dtype) * _block_diag_m(t64, lay, d)
        return factors32, d, regd

    def solve(factors, rhs):
        factors32, d, regd = factors

        def op(y):
            return base.matvec(d * base.rmatvec(y)) + regd * y

        def prec(r):
            return ops32.solve(factors32, r.astype(f32)).astype(rhs.dtype)

        return core.pcg_solve(op, prec, rhs, cg_tol, cg_iters)

    return core.LinOps(
        xp=jnp,
        matvec=base.matvec,
        rmatvec=base.rmatvec,
        factorize=factorize,
        solve=solve,
    )


def _ops_for(mode, tensors, tensors32, lay, reg, cg_iters=0, cg_tol=0.0,
             link_shard=None):
    """One mode→LinOps map shared by the per-call entry points and the
    segment driver ("direct" | "f64c" | "mixed" | "pcg")."""
    if mode == "pcg":
        return _block_pcg_ops(tensors, tensors32, lay, reg, cg_tol, cg_iters,
                              link_shard)
    if mode == "f64c":
        return _block_ops_f64c(tensors, lay, reg, link_shard=link_shard)
    if mode == "mixed":
        return _block_ops_mixed(tensors, tensors32, lay, reg, link_shard)
    return _block_ops(tensors, lay, reg, None, link_shard=link_shard)


@functools.partial(
    jax.jit,
    static_argnames=("lay", "params", "cg_iters", "cg_tol", "mode",
                     "link_shard"),
)
def _block_step(tensors, lay, data, state, reg, params, tensors32=None,
                cg_iters=0, cg_tol=0.0, mode="direct", link_shard=None):
    if mode == "direct" and cg_iters > 0:
        mode = "pcg"
    ops = _ops_for(mode, tensors, tensors32, lay, reg, cg_iters, cg_tol,
                   link_shard)
    return core.mehrotra_step(ops, data, params, state)


@functools.partial(
    jax.jit,
    static_argnames=(
        "lay", "params", "buf_cap", "stall_window", "patience", "mode",
        "cg_iters", "cg_tol", "link_shard",
    ),
)
def _block_segment(
    tensors, tensors32, lay, data, carry, it_stop, max_iter, max_refactor,
    reg_grow, params, buf_cap, stall_window=0, patience=0.0, mode="f64",
    cg_iters=0, cg_tol=0.0, link_shard=None,
):
    """One bounded continuation of the fused Schur loop (host segmentation
    against the device execution watchdog — see core.drive_segments and
    dense._dense_segment). ``mode`` selects the per-step ops: "f64"
    (direct full precision), "f64c" (n-chunked f64 direct, the
    huge-shape finisher), "mixed" (f32 factorizations, phase 1), or
    "pcg" (f32 preconditioner + full-precision matrix-free CG);
    ``tensors32`` may be None for the full-precision modes."""

    def step(state, reg):
        if mode == "mixed":
            ops = _block_ops_mixed(tensors, tensors32, lay, reg, link_shard)
        elif mode == "pcg":
            ops = _block_pcg_ops(tensors, tensors32, lay, reg, cg_tol,
                                 cg_iters, link_shard)
        elif mode == "f64c":
            ops = _block_ops_f64c(tensors, lay, reg, link_shard=link_shard)
        else:
            ops = _block_ops(tensors, lay, reg, None, link_shard=link_shard)
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
        "lay", "params", "params_p1", "buf_cap", "stall_window", "cg_iters",
        "cg_tol", "link_shard",
    ),
)
def _block_solve_two_phase(
    tensors, tensors32, lay, data, state0, reg0, params, params_p1,
    max_iter, max_refactor, reg_grow, buf_cap, stall_window,
    cg_iters=0, cg_tol=0.0, link_shard=None,
):
    """Mixed-precision fused Schur solve: f32 per-block factorizations and
    linking-system Cholesky down to the handoff tolerance, then the
    full-accuracy phase warm-started from the same iterate — f64 direct,
    or (cg_iters > 0) the f32-preconditioned matrix-free PCG mode — one
    compiled program, shared stats buffer and global iteration count
    (mirrors dense._dense_solve_two_phase, including the
    provisional-verdict reset at the phase boundary)."""

    def step32(state, reg):
        ops = _block_ops_mixed(tensors, tensors32, lay, reg, link_shard)
        return core.mehrotra_step(ops, data, params_p1, state)

    def step64(state, reg):
        if cg_iters > 0:
            ops = _block_pcg_ops(tensors, tensors32, lay, reg, cg_tol,
                                 cg_iters, link_shard)
        else:
            ops = _block_ops(tensors, lay, reg, None, link_shard=link_shard)
        return core.mehrotra_step(ops, data, params, state)

    st1, it1, status1, buf = core.fused_solve(
        step32, state0, reg0, params_p1, max_iter, max_refactor, reg_grow,
        buf_cap, stall_window=stall_window, finalize=False,
    )
    status1 = jnp.full_like(status1, core.STATUS_RUNNING)
    return core.fused_solve(
        step64, st1, reg0, params, it1 + max_iter, max_refactor, reg_grow,
        buf_cap, stall_window=2 * stall_window if stall_window else 0,
        stall_patience_floor=1e3 * params.tol,
        carry_in=(it1, status1, buf), finalize=True,
    )


@functools.partial(
    jax.jit,
    static_argnames=("lay", "params", "cg_iters", "cg_tol", "mode",
                     "link_shard"),
)
def _block_start(tensors, lay, data, reg, params, tensors32=None,
                 cg_iters=0, cg_tol=0.0, mode="direct", link_shard=None):
    if mode == "direct" and cg_iters > 0:
        mode = "pcg"
    ops = _ops_for(mode, tensors, tensors32, lay, reg, cg_iters, cg_tol,
                   link_shard)
    return core.starting_point(ops, data, params)


@functools.partial(
    jax.jit,
    static_argnames=("lay", "params", "buf_cap", "stall_window", "cg_iters",
                     "cg_tol", "link_shard"),
)
def _block_solve_full(
    tensors, lay, data, state0, reg0, params, max_iter, max_refactor, reg_grow,
    buf_cap, stall_window=0, tensors32=None, cg_iters=0, cg_tol=0.0,
    link_shard=None,
):
    # max_iter / max_refactor / reg_grow are traced — no recompile across
    # iteration-limit configs (see dense._dense_solve_full). Stall
    # semantics match the segmented path (window 2·w, near-tol patience),
    # so termination status cannot depend on whether segmentation is on.
    def step(state, reg):
        if cg_iters > 0:
            ops = _block_pcg_ops(tensors, tensors32, lay, reg, cg_tol,
                                 cg_iters, link_shard)
        else:
            ops = _block_ops(tensors, lay, reg, None, link_shard=link_shard)
        return core.mehrotra_step(ops, data, params, state)

    return core.fused_solve(
        step, state0, reg0, params, max_iter, max_refactor, reg_grow, buf_cap,
        stall_window=stall_window, stall_patience_floor=1e3 * params.tol,
    )


@register_backend("block", "schur", "block-angular")
class BlockAngularBackend(SolverBackend):
    """Schur-complement execution over the arrow structure; optionally
    shards the block axis over a mesh (pass ``mesh=`` or set
    ``config.mesh_shape``)."""

    def __init__(self, mesh: Optional[jax.sharding.Mesh] = None):
        self._mesh = mesh
        self._reg = 0.0

    def setup(self, inf: InteriorForm, config: SolverConfig) -> None:
        self._cfg = config
        self._reg = config.reg_dual
        self._params = config.step_params()
        dtype = jnp.dtype(config.dtype)
        self._dtype = dtype

        shard_put = None
        pad_blocks = 0
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            # Blocks shard over the OUTER (first) mesh axis — on a hybrid
            # ICI×DCN mesh that's the DCN axis, which fits: diagonal blocks
            # exchange only the small linking system. Divisibility is
            # against that axis's size, not the whole device count.
            # Arbitrary widths are accepted: a K not divisible by the
            # axis is padded with DEAD blocks (ragged-tail layout, see
            # build_tensors) — the elastic-shrink path re-shards onto
            # ANY survivor count instead of degrading down the chain.
            axis = self._mesh.axis_names[0]
            axis_size = self._mesh.shape[axis]
            K_hint = int((inf.block_structure or {}).get("num_blocks", 0))
            pad_blocks = (-K_hint) % axis_size

            def shard_put(arr, kind):
                spec = (
                    P(axis, *([None] * (arr.ndim - 1))) if kind == "blocked" else P()
                )
                return jax.device_put(arr, NamedSharding(self._mesh, spec))

        self._tensors, self._lay = build_tensors(
            inf, dtype, shard_put, pad_blocks=pad_blocks
        )
        # Distributed linking-system factorization (review round-4 item
        # 7): with a mesh, the link×link Schur complement factors through
        # ops/dist_chol.py column-sharded over the LAST mesh axis (ICI on
        # a hybrid mesh) instead of replicated on every device — the
        # replicated factor was the per-device HBM floor at link=1600.
        # chol_tri_inv_mesh pads ragged link sizes itself.
        if self._mesh is not None and self._lay.link > 0:
            from jax.sharding import NamedSharding as _NS, PartitionSpec as _P

            self._link_shard = _NS(
                self._mesh, _P(None, self._mesh.axis_names[-1])
            )
        else:
            self._link_shard = None
        self._data = core.make_problem_data(jnp, inf.c, inf.b, inf.u, dtype)
        # Two-phase (f32→f64) schedule: "auto" factor dtype on TPU, exactly
        # as the dense backend — phase 1 runs every per-block factorization
        # and the linking Cholesky in f32 on the MXU. The f32 tensor stack
        # (shares the integer index maps) is materialized lazily on first
        # solve_full use: the per-iteration iterate() path is pure f64 and
        # must not pay the +50% HBM for a copy it never reads.
        self._two_phase = config.two_phase_enabled(jax.default_backend())
        self._tensors32 = None
        # PCG full-accuracy mode (config.solve_mode, mirrors the dense
        # backend): replaces the emulated-f64 Schur assembly/factorization
        # phase with f32 preconditioning + full-precision matrix-free CG.
        # Auto-on where the f64 einsums are the bottleneck: the FLOP
        # estimate crossing ~0.8 s/iteration of emulated-f64 work.
        K, mb, nb, link, n0, n, m = self._lay
        self._f64_flops = K * (2.0 * mb * mb * nb + mb**3 / 3.0) + (
            2.0 * link * link * (K * nb + n0) + link**3 / 3.0
        )
        # PCG only on explicit request — it is OFF in the auto plan
        # (round-5 measurement at the pds-20 class): the CG operator
        # here is the elementwise-f64 matvec pair over the padded block
        # tensors (~0.35 s per application at K=64·nb=1300·link=1600),
        # so ONE PCG iteration cost 37.5 s against the chunked-f64
        # direct finisher's 3.4 s — and with the gram-form f32 phase
        # carrying the early orders, the preconditioner's edge never
        # pays for its matvecs.
        self._pcg = config.solve_mode == "pcg"
        self._cg_iters = config.cg_iters if self._pcg else 0
        self._cg_tol = config.cg_tol if self._pcg else 0.0
        # Above this operand-split budget the one-shot f64 assembly is
        # un-lowerable on TPU (8×-f32 split temps of the full tensors —
        # observed 3.91 G for one Gk einsum at the pds-20 class); every
        # full-precision entry point must then take the n-chunked f64c
        # route, INCLUDING the starting point and per-iteration path.
        self._huge_f64 = (
            dtype == jnp.float64
            and jax.default_backend() == "tpu"
            and 32.0 * (K * link * nb + K * mb * nb) > _F64_SPLIT_BUDGET
        )

    def _point_args(self):
        """(tensors32, cg_iters, cg_tol, mode) for per-call entry points."""
        if self._pcg:
            return self._get_tensors32(), self._cg_iters, self._cg_tol, "pcg"
        if self._huge_f64:
            return None, 0, 0.0, "f64c"
        return None, 0, 0.0, "direct"

    def starting_point(self) -> IPMState:
        t32, cgi, cgt, mode = self._point_args()
        st = _block_start(
            self._tensors, self._lay, self._data,
            jnp.asarray(self._reg, self._dtype), self._params, t32, cgi, cgt,
            mode, self._link_shard,
        )
        jax.block_until_ready(st)
        return st

    def iterate(self, state: IPMState) -> Tuple[IPMState, StepStats]:
        t32, cgi, cgt, mode = self._point_args()
        return _block_step(
            self._tensors, self._lay, self._data, state,
            jnp.asarray(self._reg, self._dtype), self._params, t32, cgi, cgt,
            mode, self._link_shard,
        )

    def bump_regularization(self) -> bool:
        if self._reg * self._cfg.reg_grow > 1e-2:
            return False
        self._reg = max(self._reg, 1e-12) * self._cfg.reg_grow
        return True

    @property
    def mesh(self) -> Optional[jax.sharding.Mesh]:
        return self._mesh

    def reshard(self, mesh: jax.sharding.Mesh) -> "BlockAngularBackend":
        """Elastic-recovery seam (supervisor SHRINK rung): a fresh
        instance on the survivor mesh. With the ragged-tail layout any
        survivor count re-shards — K pads up to the next multiple of
        the new mesh's block axis with dead blocks instead of pushing
        the solve down the degradation chain (ROADMAP carried item)."""
        return type(self)(mesh=mesh)

    def _get_tensors32(self) -> BlockTensors:
        if self._tensors32 is None:
            f32 = jnp.float32
            self._tensors32 = self._tensors._replace(
                B_all=self._tensors.B_all.astype(f32),
                L_all=self._tensors.L_all.astype(f32),
                A0=self._tensors.A0.astype(f32),
            )
        return self._tensors32

    def _solve_segmented(self, state: IPMState):
        """Host-driven segmented fused Schur solve: per-phase specs feed
        the shared driver (core.drive_phase_plan) — same termination
        semantics as the dense backend by construction."""
        cfg = self._cfg
        dtype = self._dtype
        n_phases = 1 + (1 if self._two_phase else 0) + (
            1 if (self._pcg and self._two_phase) else 0
        )
        buf_cap = core.buffer_cap(n_phases * cfg.max_iter)
        mr = jnp.asarray(cfg.max_refactor, jnp.int32)
        rg = jnp.asarray(cfg.reg_grow, dtype)
        # Per-iteration FLOP estimate: per-block normal equations and
        # Cholesky plus the linking-system dense work (setup-computed).
        flops = self._f64_flops
        w = cfg.stall_window
        patience = 1e3 * cfg.tol
        K, mb, nb, link, n0, n, m = self._lay
        # The one-shot f64 direct Schur assembly is un-lowerable at huge
        # shapes on TPU: XLA's emulated-f64 dot_generals materialize
        # 8×-f32 operand-split temps of the full (K, link, nb) /
        # (K, mb, nb) tensors (observed OOM at pds-20 scale: 19.4 G
        # needed of 15.75 G). Above that budget (setup-computed
        # self._huge_f64) the full-precision phase runs n-CHUNKED
        # ("f64c", the block analogue of the dense endgame) — same f64
        # arithmetic, bounded per-chunk temps.
        finish_mode = "f64c" if self._huge_f64 else "f64"
        full_mode = "pcg" if self._pcg else finish_mode
        full_t32 = self._get_tensors32() if full_mode == "pcg" else None
        # The chunked-f64 finisher gets Gondzio correctors (same knob as
        # the dense endgame): each f64c factorization costs ~3 s at the
        # pds-20 class while an extra solve against its INVERSE factors
        # is GEMV noise — exactly the economics StepParams.mcc exists
        # for. The one-shot "f64" mode at small shapes keeps mcc off
        # (its factorizations are cheap; extra solves only add latency).
        params_finish = (
            cfg.step_params(mcc=cfg.endgame_mcc)
            if finish_mode == "f64c" else self._params
        )
        if self._two_phase:
            plan = [
                (cfg.phase1_params(), "mixed", self._get_tensors32(), w, 0.0),
            ]
            if self._pcg:
                # PCG runs to its HANDOFF tol (μ-floor keyed there — see
                # config.pcg_handoff_tol), then the true-f64 finisher
                # owns the last orders at full tolerance.
                params_pcg = cfg.replace(
                    tol=max(cfg.tol, cfg.pcg_handoff_tol)
                ).step_params()
                plan.append(
                    (params_pcg, "pcg", self._get_tensors32(), w, 0.0)
                )
                plan.append(
                    (params_finish, finish_mode, None,
                     2 * w if w else 0, patience)
                )
            else:
                plan.append(
                    (params_finish if full_mode == finish_mode
                     else self._params,
                     full_mode, full_t32, 2 * w if w else 0, patience)
                )
        else:
            plan = [
                (params_finish if full_mode == finish_mode else self._params,
                 full_mode, full_t32, 2 * w if w else 0, patience)
            ]

        def make_phase(spec):
            params, mode, t32, window, patience_now = spec
            rate = (
                core.SEG_RATE_F64 if mode in ("f64", "f64c")
                else core.SEG_RATE_F32
            )
            cgi = self._cg_iters if mode == "pcg" else 0
            cgt = self._cg_tol if mode == "pcg" else 0.0

            def make_run_seg(bound):
                mi = jnp.asarray(bound, jnp.int32)

                def run_seg(c, stop):
                    return _block_segment(
                        self._tensors, t32, self._lay, self._data, c,
                        jnp.asarray(stop, jnp.int32), mi, mr, rg, params,
                        buf_cap, window, patience_now, mode, cgi, cgt,
                        self._link_shard,
                    )

                return run_seg

            # PCG phases: the worst-case CG sweeps dwarf the FLOP model
            # and a watchdog overrun is fatal — open with ONE iteration
            # and let measured-rate adaptation size the rest (same rule
            # as the dense backend).
            seg0 = (
                1 if mode == "pcg"
                else core.seg_open(cfg.segment_iters, flops / rate)
            )
            return (make_run_seg, window, patience_now, seg0)

        self.phase_report = []  # per-phase iters/wall split (utilization)
        st, it, status, buf, _ = core.drive_phase_plan(
            [make_phase(s) for s in plan],
            state, jnp.asarray(self._reg, dtype), cfg.max_iter, buf_cap, dtype,
            report=self.phase_report,
        )
        # Phase MODE from the plan spec (utilization folding keys seed
        # rates off this; index guessing breaks on 1/2/3-phase plans).
        for ph, spec in zip(self.phase_report, plan):
            ph["mode"] = spec[1]
        return st, it, status, buf

    def solve_full(self, state: IPMState):
        # Two-phase PCG always routes through the segmented plan (same
        # rule as the dense backend): only that plan carries the chunked
        # f64 finisher behind the PCG phase's handoff tolerance. Huge
        # f64 shapes route there too regardless of segment settings —
        # the fused one-shot programs would hit the operand-split OOM
        # the segmented plan's "f64c" mode exists to avoid.
        if (
            core.use_segments(self._cfg.segment_iters, jax.default_backend())
            or (self._pcg and self._two_phase)
            or self._huge_f64
        ):
            return self._solve_segmented(state)
        if self._pcg and not self._two_phase:
            # Forced PCG without a phase schedule: ONE full-tol PCG phase
            # (same plan the segmented path builds for this config, and
            # the same shape as dense's single-phase PCG branch).
            return _block_solve_full(
                self._tensors,
                self._lay,
                self._data,
                state,
                jnp.asarray(self._reg, self._dtype),
                self._params,
                jnp.asarray(self._cfg.max_iter, jnp.int32),
                jnp.asarray(self._cfg.max_refactor, jnp.int32),
                jnp.asarray(self._cfg.reg_grow, self._dtype),
                core.buffer_cap(self._cfg.max_iter),
                2 * self._cfg.stall_window if self._cfg.stall_window else 0,
                self._get_tensors32(),
                self._cg_iters,
                self._cg_tol,
                self._link_shard,
            )
        if self._two_phase:
            return _block_solve_two_phase(
                self._tensors,
                self._get_tensors32(),
                self._lay,
                self._data,
                state,
                jnp.asarray(self._reg, self._dtype),
                self._params,
                self._cfg.phase1_params(),
                jnp.asarray(self._cfg.max_iter, jnp.int32),
                jnp.asarray(self._cfg.max_refactor, jnp.int32),
                jnp.asarray(self._cfg.reg_grow, self._dtype),
                core.buffer_cap(2 * self._cfg.max_iter),
                self._cfg.stall_window,
                self._cg_iters,
                self._cg_tol,
                self._link_shard,
            )
        return _block_solve_full(
            self._tensors,
            self._lay,
            self._data,
            state,
            jnp.asarray(self._reg, self._dtype),
            self._params,
            jnp.asarray(self._cfg.max_iter, jnp.int32),
            jnp.asarray(self._cfg.max_refactor, jnp.int32),
            jnp.asarray(self._cfg.reg_grow, self._dtype),
            core.buffer_cap(self._cfg.max_iter),
            2 * self._cfg.stall_window if self._cfg.stall_window else 0,
            link_shard=self._link_shard,
        )

    def block_until_ready(self, obj) -> None:
        jax.block_until_ready(obj)
