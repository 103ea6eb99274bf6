"""First-order (restarted PDHG) backend: oracle agreement + sparse path.

First-order methods trade per-iteration cost for iteration count, so the
tests run at 1e-5/1e-6 tolerances (the regime the backend exists for —
huge sparse problems where a Cholesky is not an option) and check
objective agreement against HiGHS at matching accuracy.
"""

import numpy as np
import pytest
import scipy.sparse as sp

from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.ipm.state import Status
from distributedlpsolver_tpu.models.generators import (
    block_angular_lp,
    random_general_lp,
)

from tests.oracle import highs_on_general


def test_dense_matches_highs():
    p = random_general_lp(30, 60, seed=0)
    ref = highs_on_general(p)
    r = solve(p, backend="pdlp", tol=1e-6, max_iter=100)
    assert r.status == Status.OPTIMAL
    assert r.objective == pytest.approx(ref.fun, abs=1e-4 * (1 + abs(ref.fun)))
    assert p.max_violation(r.x) < 1e-4


def test_sparse_bcoo_path_matches_dense():
    p = block_angular_lp(3, 12, 20, 6, seed=2, sparse=True)
    assert sp.issparse(p.A)
    ref = highs_on_general(p)
    r = solve(p, backend="pdlp", tol=1e-6, max_iter=200, presolve=False)
    assert r.status == Status.OPTIMAL
    assert r.objective == pytest.approx(ref.fun, abs=1e-3 * (1 + abs(ref.fun)))


def test_iteration_limit_reported_not_nan():
    # A tolerance PDHG cannot reach in a tiny budget must surface as
    # ITERATION_LIMIT with finite diagnostics, never NaNs.
    p = random_general_lp(40, 80, seed=3)
    r = solve(p, backend="pdlp", tol=1e-12, max_iter=2)
    assert r.status in (Status.ITERATION_LIMIT, Status.OPTIMAL)
    assert np.isfinite(r.rel_gap)


def test_registered_names():
    from distributedlpsolver_tpu.backends import available_backends

    names = available_backends()
    for name in ("pdlp", "first-order", "pdhg"):
        assert name in names


def test_mesh_sharded_matches_single_device():
    # PDHG under GSPMD: A's columns sharded over the 8 virtual devices;
    # the matvec's partial products all-reduce over the mesh. Objective
    # must match the single-device solve.
    import jax

    from distributedlpsolver_tpu.backends.first_order import FirstOrderBackend
    from distributedlpsolver_tpu.parallel import make_mesh

    p = random_general_lp(24, 50, seed=7)  # 50 cols → padded to 56
    mesh = make_mesh(devices=jax.devices()[:8])
    r_mesh = solve(
        p, backend=FirstOrderBackend(mesh=mesh), tol=1e-6, max_iter=100
    )
    r_one = solve(p, backend="pdlp", tol=1e-6, max_iter=100)
    assert r_mesh.status == Status.OPTIMAL
    assert r_mesh.objective == pytest.approx(
        r_one.objective, abs=1e-4 * (1 + abs(r_one.objective))
    )
    assert r_mesh.x.shape == (p.n,)


def test_segmented_bursts_match_fused():
    # Host-segmented solve_full (the core.use_segments rule): bursts
    # of segment_iters*400 inner steps carrying (x, y, omega, err_restart)
    # must converge to the same objective as the single fused loop.
    p = random_general_lp(30, 60, seed=11)
    r_seg = solve(p, backend="pdlp", tol=1e-6, max_iter=100, segment_iters=1)
    r_fused = solve(p, backend="pdlp", tol=1e-6, max_iter=100, segment_iters=0)
    assert r_seg.status == Status.OPTIMAL
    assert r_seg.objective == pytest.approx(
        r_fused.objective, abs=1e-4 * (1 + abs(r_fused.objective))
    )
