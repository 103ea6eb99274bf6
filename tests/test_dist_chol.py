"""Mesh-distributed Cholesky + triangular inversion (ops/dist_chol.py) —
the second distributed-factorization cut (SURVEY.md §2.2, review round 3
item 6): unlike round 3's sharded-TRSM-only build, no stage may
materialize a replicated m×m buffer on any device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributedlpsolver_tpu.ops.dist_chol import chol_tri_inv_mesh
from distributedlpsolver_tpu.parallel import mesh as mesh_lib


@pytest.fixture(scope="module")
def mesh8():
    return mesh_lib.make_mesh((8,), axis_names=("cols",))


def _spd(m, seed=0):
    rng = np.random.default_rng(seed)
    G = rng.standard_normal((m, m))
    return G @ G.T + m * np.eye(m)


@pytest.mark.parametrize(
    "m,panel,dtype,tol",
    [
        (96, 8, jnp.float64, 1e-12),   # divisible: w=12, pad-free
        (130, 16, jnp.float64, 1e-12), # ragged: slab padded to panel mult
        (200, 32, jnp.float32, 5e-6),  # f32 (the production factor dtype)
        (8, 4, jnp.float64, 1e-12),    # one column per device
    ],
)
def test_matches_replicated_factorization(mesh8, m, panel, dtype, tol):
    sh = NamedSharding(mesh8, P(None, "cols"))
    Ms = _spd(m)
    ref = np.linalg.inv(np.linalg.cholesky(Ms))
    got = np.asarray(
        jax.jit(lambda M: chol_tri_inv_mesh(M, sh, panel=panel))(
            jnp.asarray(Ms, dtype)
        )
    )
    err = np.abs(got - ref).max() / np.abs(ref).max()
    assert err < tol, err


def test_output_is_column_sharded(mesh8):
    sh = NamedSharding(mesh8, P(None, "cols"))
    out = jax.jit(lambda M: chol_tri_inv_mesh(M, sh, panel=8))(
        jnp.asarray(_spd(64), jnp.float32)
    )
    spec = out.sharding.spec
    assert tuple(spec) == (None, "cols"), spec


def test_memory_beats_round3_replicated_cholesky(mesh8):
    """Per-device compiled peak of the full distributed pipeline must be
    measurably below the round-3 path (replicated jnp Cholesky feeding
    the sharded TRSM slabs), whose replicated Ms and L buffers are the
    multi-chip HBM ceiling this cut removes."""
    from distributedlpsolver_tpu.backends import dense as D

    sh = NamedSharding(mesh8, P(None, "cols"))
    m = 1024
    Ms = jnp.asarray(_spd(m), jnp.float32)

    def peak(fn):
        comp = jax.jit(fn).lower(Ms).compile()
        return comp.memory_analysis().temp_size_in_bytes

    new = peak(lambda M: chol_tri_inv_mesh(M, sh, panel=128))
    old = peak(lambda M: D._tri_inv_mesh(jnp.linalg.cholesky(M), sh))
    # The old path's replicated L alone is m²·4 bytes on every device;
    # demand at least half of that as the margin (buffer reuse hides
    # part of the win from temp accounting).
    assert new < old - 2 * m * m, (new, old)


def test_preconditioner_path_end_to_end(mesh8):
    """The sharded PCG backend must route through the distributed
    factorization and still converge to the same optimum as the
    replicated dense solve."""
    from distributedlpsolver_tpu.backends.sharded import ShardedJaxBackend
    from distributedlpsolver_tpu.ipm import solve
    from distributedlpsolver_tpu.models.generators import random_dense_lp

    p = random_dense_lp(48, 120, seed=11)
    r_ref = solve(p, backend="cpu")
    be = ShardedJaxBackend(mesh=mesh8)
    r = solve(p, backend=be, solve_mode="pcg")
    assert r.status.value == "optimal"
    assert r.objective == pytest.approx(r_ref.objective, rel=1e-6)


def test_memory_ragged_m_stays_sharded(mesh8):
    """Ragged m (padding path): the identity-tail construction must not
    materialize an unconstrained replicated (mp, mp) buffer (ADVICE
    round 4). Envelope: the ragged case's compiled peak stays within 40%
    of the divisible case at comparable size (the pad itself adds rows,
    so exact equality is not expected — a replicated intermediate would
    roughly DOUBLE it)."""
    sh = NamedSharding(mesh8, P(None, "cols"))

    def peak(m, panel):
        Ms = jnp.asarray(_spd(m), jnp.float32)
        comp = jax.jit(
            lambda M: chol_tri_inv_mesh(M, sh, panel=panel)
        ).lower(Ms).compile()
        return comp.memory_analysis().temp_size_in_bytes

    ragged = peak(1000, 128)   # 1000 -> slab 125 -> pad to 128*8 = 1024
    exact = peak(1024, 128)
    assert ragged < 1.4 * exact, (ragged, exact)
    # and the math survives the pad (oracle check at the ragged size)
    m = 1000
    Ms = jnp.asarray(_spd(m), jnp.float64)
    Linv = np.asarray(chol_tri_inv_mesh(Ms, sh, panel=128))
    err = np.abs(Linv.T @ Linv @ np.asarray(Ms) - np.eye(m)).max()
    assert err < 1e-6, err


def test_block_linking_factor_distributes_over_mesh(mesh8):
    """review round-4 item 7: with a mesh, the block backend's
    link x link Schur factorization must route through chol_tri_inv_mesh
    (column-sharded factor) instead of replicating it on every device.
    Compile-time per-device temp peak of one f64c segment program at
    link=1600 must drop measurably vs the replicated route."""
    import jax.numpy as jnp
    from distributedlpsolver_tpu.backends import block_angular as B
    from distributedlpsolver_tpu.ipm.config import SolverConfig
    from distributedlpsolver_tpu.ipm import core as C
    from distributedlpsolver_tpu.models.generators import block_angular_lp
    from distributedlpsolver_tpu.models.problem import to_interior_form

    link = 1600
    p = block_angular_lp(8, 24, 48, link, seed=0, sparse=True, density=0.02)
    inf = to_interior_form(p)

    def peak(link_shard):
        be = B.BlockAngularBackend(mesh=mesh8 if link_shard else None)
        be.setup(inf, SolverConfig())
        lay, t = be._lay, be._tensors
        data = be._data
        params = SolverConfig().step_params()
        buf_cap = C.buffer_cap(200)
        state = be.starting_point()
        carry = C.fresh_segment_carry(
            state, jnp.asarray(1e-10, jnp.float64), buf_cap, jnp.float64
        )
        lowered = B._block_segment.lower(
            t, None, lay, data, carry, jnp.asarray(4, jnp.int32),
            jnp.asarray(8, jnp.int32), jnp.asarray(3, jnp.int32),
            jnp.asarray(100.0, jnp.float64), params, buf_cap,
            mode="f64c", link_shard=be._link_shard,
        )
        return lowered.compile().memory_analysis().temp_size_in_bytes

    sharded = peak(True)
    replicated = peak(False)
    # the replicated link x link f64 factor alone is link^2*8 bytes on
    # every device; demand at least half of that as the margin
    assert sharded < replicated - 4 * link * link, (sharded, replicated)
