"""Mesh-distributed Cholesky factorization + triangular inversion.

The second distributed-factorization cut (SURVEY.md §2.2; review round 3
item 6). Round 3 sharded the TRSM slabs of L⁻¹ (`dense._tri_inv_mesh`),
but the m×m Cholesky itself — and the full M and L it reads and writes —
stayed REPLICATED on every device: per-device HBM held 3 full m×m buffers
(M, L, and the TRSM's read copy of L), which is the memory ceiling for
dense m ≳ 10k on a real multi-chip mesh. This module distributes the
whole pipeline: M arrives column-block-sharded (one reduce-scatter out of
the GSPMD assembly instead of an all-reduce), the factorization runs as a
left-looking panel Cholesky inside ``shard_map``, and the inversion is a
right-looking blocked forward substitution on each device's identity
slab — no stage materializes a replicated m×m array on any device.

Dataflow per panel (pb columns, P = m/pb panels):

  factor:  U = psum( ownerʼs M panel − L_loc · L_loc[panel rows]ᵀ )
           C = chol(U[diag block])          (pb×pb, replicated compute)
           L panel = U · C⁻ᵀ                (TRSM, pb rhs, replicated)
           owner stores its panel slab
  invert:  Lp = psum( ownerʼs L panel )     (the only broadcast of L)
           X[panel rows] = C⁻¹ · X[panel rows]
           X[below]     −= Lp[below] · X[panel rows]

Left-looking contraction trick: each device contracts ALL of its local
columns every panel (``L_loc @ L_loc[panel_rows].T``) — columns not yet
factored are still zero and contribute nothing, so no dynamic column
masking is needed and the total per-device flop count telescopes to
m³/K + O(m²·pb) (the ideal 1/K share plus the replicated pb-wide panel
math). Communication: one (m, pb) psum per panel per stage — 2m² words
total, the same volume as one replicated all-reduce of M, riding ICI.

Numerics match the replicated factorization: identical IEEE operations
per panel, only the summation ORDER of the psum differs (deterministic
on a fixed mesh — XLA collectives are reduction-order-stable, the
property tests/test_determinism.py pins).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec


def _axis_of(shard: NamedSharding) -> str:
    """The (single) mesh axis a ``P(None, axis)`` column sharding names."""
    return next(a for a in shard.spec if a is not None)


def chol_tri_inv_mesh(Ms, shard: NamedSharding, panel: int = 256):
    """``L⁻¹`` of ``chol(Ms)``, column-sharded end-to-end over the mesh.

    ``Ms`` is the (already scaled + regularized) SPD matrix, accepted with
    ANY placement — a ``with_sharding_constraint`` immediately pins it to
    ``shard`` (``P(None, axis)``), so when the caller's assembly is GSPMD
    column-partials the compiler emits a reduce-scatter instead of an
    all-reduce and the replicated m² buffer never exists. Returns L⁻¹
    (unit layout as `dense._tri_inv_mesh`: column-sharded, ready for the
    preconditioner's two sharded GEMVs).

    ``panel`` is a target: the actual panel width is ``min(panel, w)``
    with the per-device slab ``w`` rounded UP to a panel multiple (the
    pad carries an identity tail, sliced off at the end) so every panel
    lies inside one device's slab.
    """
    from distributedlpsolver_tpu.parallel.mesh import (
        pvary_compat,
        shard_map_compat,
    )

    mesh = shard.mesh
    axis = _axis_of(shard)
    K = int(mesh.shape[axis])
    m = Ms.shape[0]
    w0 = -(-m // K)  # per-device slab before panel alignment
    pb = min(panel, w0)
    w = -(-w0 // pb) * pb  # slab width: multiple of pb
    mp = w * K
    P = mp // pb  # global panel count

    sharding = NamedSharding(mesh, PartitionSpec(None, axis))
    if mp != m:
        pad = mp - m
        # Constrain the PADDED buffer before the identity-tail scatter:
        # building it via zeros+set and constraining only at the end let
        # GSPMD materialize an unconstrained replicated (mp, mp)
        # intermediate — exactly the buffer this module promises never
        # exists (ADVICE round 4). The raw m is not divisible by the
        # mesh axis (that is what the pad is for), so the constraint can
        # only attach from the padded shape onward; the diagonal scatter
        # preserves it.
        Ms = jax.lax.with_sharding_constraint(
            jnp.pad(Ms, ((0, pad), (0, pad))), sharding
        )
        # Identity tail: pad rows factor to L=I there and stay inert.
        Ms = Ms.at[jnp.arange(m, mp), jnp.arange(m, mp)].set(1.0)
    Ms = jax.lax.with_sharding_constraint(Ms, sharding)

    rows = jnp.arange(mp)

    def device_fn(Msloc):
        # Msloc: (mp, w) — this device's column slab of Ms.
        k = jax.lax.axis_index(axis)
        base = k * w

        def factor_panel(p, Lloc):
            g0 = p * pb  # global first column of this panel
            owner = g0 // w
            lc = g0 - owner * w  # same scalar on every device, always valid
            mine = (k == owner).astype(Msloc.dtype)
            # Owner contributes its M panel; everyone subtracts the
            # left-looking update from its already-factored local columns
            # (unfactored columns are still zero — no masking needed).
            Mpan = jax.lax.dynamic_slice(Msloc, (0, lc), (mp, pb))
            Lrows = jax.lax.dynamic_slice(Lloc, (g0, 0), (pb, w))
            U = jax.lax.psum(mine * Mpan - Lloc @ Lrows.T, axis)
            D = jax.lax.dynamic_slice(U, (g0, 0), (pb, pb))
            C = jnp.linalg.cholesky(D)
            # Panel of L: rows ≥ g0+pb get U·C⁻ᵀ; rows in the panel get C
            # itself (algebraically U·C⁻ᵀ there too); rows above are not
            # part of the lower factor — mask to zero.
            Lpan = jax.scipy.linalg.solve_triangular(
                C, U.T, lower=True
            ).T
            Lpan = jnp.where((rows >= g0)[:, None], Lpan, 0.0)
            cur = jax.lax.dynamic_slice(Lloc, (0, lc), (mp, pb))
            Lpan = jnp.where(mine > 0, Lpan, cur)  # non-owners keep slab
            return jax.lax.dynamic_update_slice(Lloc, Lpan, (0, lc))

        init = pvary_compat(jnp.zeros((mp, w), Msloc.dtype), (axis,))
        Lloc = jax.lax.fori_loop(0, P, factor_panel, init)

        # ---- distributed inversion: solve L·X = I_slab for this
        # device's identity slab (columns [base, base+w)).
        X0 = (rows[:, None] == (base + jnp.arange(w))[None, :]).astype(
            Msloc.dtype
        )

        def subst_panel(p, X):
            g0 = p * pb
            owner = g0 // w
            lc = g0 - owner * w
            mine = (k == owner).astype(Msloc.dtype)
            # The only broadcast of L: the owner's (mp, pb) panel.
            Lpan = jax.lax.psum(
                mine * jax.lax.dynamic_slice(Lloc, (0, lc), (mp, pb)), axis
            )
            C = jax.lax.dynamic_slice(Lpan, (g0, 0), (pb, pb))
            Xp = jax.lax.dynamic_slice(X, (g0, 0), (pb, w))
            Xp = jax.scipy.linalg.solve_triangular(C, Xp, lower=True)
            X = jax.lax.dynamic_update_slice(X, Xp, (g0, 0))
            # Right-looking update of the rows below the panel; rows in
            # and above the panel are masked out of Lpan (L's rows above
            # g0 are zero already, but the C block is not).
            Lbelow = jnp.where((rows >= g0 + pb)[:, None], Lpan, 0.0)
            return X - Lbelow @ Xp

        return jax.lax.fori_loop(0, P, subst_panel, X0)

    Linv = shard_map_compat(
        device_fn,
        mesh=mesh,
        in_specs=(PartitionSpec(None, axis),),
        out_specs=PartitionSpec(None, axis),
    )(Ms)
    return Linv[:m, :m] if mp != m else Linv
