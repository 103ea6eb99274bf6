"""Determinism tier (SURVEY.md §4; review round 1 item 6).

Three guarantees, strongest first:

1. Re-run determinism: the same solve on the same 8-device mesh twice
   produces a BITWISE-identical per-iteration trajectory (psum/GSPMD
   reductions are deterministic — the rebuild's analogue of the
   reference's fixed MPI reduction order).
2. Cross-mesh agreement: the same seed solved on 1 device vs the
   8-device mesh follows the same trajectory to f64-roundoff levels,
   iteration by iteration — not just a loose final-objective match.
   (Bitwise equality across DIFFERENT mesh shapes is not a meaningful
   target: the reduction order genuinely differs; what must hold is
   per-iteration agreement at roundoff scale, amplified only by the
   problem's conditioning.)
3. A ``jax_debug_nans`` smoke job: the production solve path stays
   NaN-free under JAX's NaN checker on a well-posed problem.
"""

import jax
import numpy as np
import pytest

from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.ipm.state import Status
from distributedlpsolver_tpu.models.generators import random_dense_lp
from distributedlpsolver_tpu.parallel import make_mesh

_TRAJ_FIELDS = ("mu", "pobj", "dobj", "pinf", "dinf", "alpha_p", "alpha_d")


def _mesh_backend():
    from distributedlpsolver_tpu.backends.sharded import ShardedJaxBackend

    return ShardedJaxBackend(mesh=make_mesh(devices=jax.devices()[:8]))


def _trajectory(result):
    return {
        f: np.array([getattr(rec, f) for rec in result.history])
        for f in _TRAJ_FIELDS
    }


def test_same_mesh_rerun_is_bitwise_identical():
    p = random_dense_lp(24, 64, seed=0)
    r1 = solve(p, backend=_mesh_backend())
    r2 = solve(p, backend=_mesh_backend())
    assert r1.status == r2.status == Status.OPTIMAL
    assert r1.iterations == r2.iterations
    t1, t2 = _trajectory(r1), _trajectory(r2)
    for f in _TRAJ_FIELDS:
        np.testing.assert_array_equal(t1[f], t2[f], err_msg=f)
    np.testing.assert_array_equal(r1.x, r2.x)


def test_one_vs_eight_device_trajectory_roundoff():
    p = random_dense_lp(24, 64, seed=1)
    r1 = solve(p, backend="tpu")
    r8 = solve(p, backend=_mesh_backend())
    assert r1.status == r8.status == Status.OPTIMAL
    assert r1.iterations == r8.iterations, (
        f"iteration counts diverge: {r1.iterations} vs {r8.iterations}"
    )
    t1, t8 = _trajectory(r1), _trajectory(r8)
    # Roundoff-scale agreement per iteration: reduction-order noise is
    # ~1e-16 per contraction; through the factorization it is amplified
    # by the iteration's conditioning, so the bound grows with μ⁻¹ but
    # stays ~6 orders below the 1e-7 objective-only check this replaces.
    for f in ("mu", "pobj", "dobj"):
        np.testing.assert_allclose(
            t1[f], t8[f], rtol=1e-12, atol=1e-13, err_msg=f
        )
    # Step lengths are ratio-test minima — exquisitely sensitive near
    # degeneracy, but still must agree far beyond f32 levels.
    for f in ("alpha_p", "alpha_d"):
        np.testing.assert_allclose(
            t1[f], t8[f], rtol=1e-9, atol=1e-12, err_msg=f
        )
    np.testing.assert_allclose(r1.x, r8.x, rtol=1e-10, atol=1e-12)


def test_debug_nans_smoke():
    # The production step must not rely on transient NaNs on the healthy
    # path: under jax_debug_nans a well-posed solve still reaches OPTIMAL.
    jax.config.update("jax_debug_nans", True)
    try:
        p = random_dense_lp(20, 48, seed=2)
        r = solve(p, backend="tpu")
        assert r.status == Status.OPTIMAL
    finally:
        jax.config.update("jax_debug_nans", False)
