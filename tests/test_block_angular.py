"""Block-angular Schur-complement backend tests (BASELINE.json:8 path,
SURVEY.md §3.2): per-block factorization + Allreduce-combined linking
Schur complement, batched over K and optionally sharded over a mesh."""

import jax
import numpy as np
import pytest

from distributedlpsolver_tpu.backends.block_angular import (
    BlockAngularBackend,
    analyze_structure,
)
from distributedlpsolver_tpu.ipm import SolverConfig, Status, solve
from distributedlpsolver_tpu.models.generators import block_angular_lp, random_dense_lp
from distributedlpsolver_tpu.models.problem import to_interior_form
from distributedlpsolver_tpu.parallel import make_mesh
from tests.oracle import highs_on_general


@pytest.mark.parametrize("K,mb,nb,lk", [(4, 12, 30, 8), (6, 10, 25, 5)])
def test_block_matches_highs_and_dense(K, mb, nb, lk):
    p = block_angular_lp(K, mb, nb, lk, seed=1, sparse=False)
    r = solve(p, backend="block", max_iter=60)
    rd = solve(p, backend="tpu", max_iter=60)
    hi = highs_on_general(p)
    assert r.status == Status.OPTIMAL
    assert abs(r.objective - hi.fun) <= 2e-6 * (1 + abs(hi.fun))
    # identical algorithm through a different factorization path
    assert r.objective == pytest.approx(rd.objective, rel=1e-9, abs=1e-9)


def test_sparse_input_accepted():
    p = block_angular_lp(4, 10, 24, 6, seed=2, sparse=True)
    r = solve(p, backend="block", max_iter=60)
    hi = highs_on_general(p)
    assert r.status == Status.OPTIMAL
    assert abs(r.objective - hi.fun) <= 2e-6 * (1 + abs(hi.fun))


def test_structure_detection():
    p = block_angular_lp(4, 10, 24, 6, seed=0, sparse=False)
    inf = to_interior_form(p)
    lay, info = analyze_structure(inf)
    assert lay.K == 4 and lay.mb == 10 and lay.link == 6
    # border = linking-row slacks plus any sparse column whose only
    # nonzeros happen to sit in linking rows
    assert lay.n0 >= 6
    assert lay.nb <= 24
    assert lay.K * lay.nb + lay.n0 >= inf.n - 6


def test_missing_hint_raises():
    p = random_dense_lp(10, 20, seed=0)
    inf = to_interior_form(p)
    with pytest.raises(ValueError, match="block_structure"):
        analyze_structure(inf)


def test_cross_block_column_rejected():
    p = block_angular_lp(3, 8, 16, 4, seed=0, sparse=False)
    A = np.asarray(p.A).copy()
    A[0, 17] = 1.0  # block-0 row entry for a block-1 column
    p.A = A
    inf = to_interior_form(p)
    with pytest.raises(ValueError, match="spans blocks"):
        analyze_structure(inf)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_block_sharded_over_mesh():
    """K blocks sharded over the mesh: the Σ_k Schur sum must become an
    all-reduce (the reference's MPI_Allreduce, BASELINE.json:5) and the
    result must match the unsharded run."""
    p = block_angular_lp(8, 10, 24, 6, seed=3, sparse=False)
    mesh = make_mesh(axis_names=("blocks",))
    be = BlockAngularBackend(mesh=mesh)
    r = solve(p, backend=be, max_iter=60)
    r_ref = solve(p, backend="block", max_iter=60)
    assert r.status == Status.OPTIMAL
    assert r.objective == pytest.approx(r_ref.objective, rel=1e-9, abs=1e-9)

    from distributedlpsolver_tpu.backends.block_angular import _block_step
    import jax.numpy as jnp

    be2 = BlockAngularBackend(mesh=mesh)
    cfg = SolverConfig()
    be2.setup(to_interior_form(p), cfg)
    st = be2.starting_point()
    hlo = (
        _block_step.lower(
            be2._tensors, be2._lay, be2._data, st,
            jnp.asarray(cfg.reg_dual, be2._dtype), be2._params,
        )
        .compile()
        .as_text()
    )
    assert "all-reduce" in hlo


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_block_mesh_ragged_tail_accepts_indivisible_K():
    # The K-divisible-by-mesh constraint is GONE (ISSUE 13 satellite):
    # K=6 blocks over an 8-device axis pad up with dead blocks
    # (all-sentinel index maps, unit pad diagonal) and solve to the
    # unsharded optimum — the layout arbitrary survivor counts re-shard
    # onto after an elastic shrink.
    p = block_angular_lp(6, 8, 16, 4, seed=0, sparse=False)  # 6 % 8 != 0
    mesh = make_mesh(axis_names=("blocks",))
    be = BlockAngularBackend(mesh=mesh)
    be.setup(to_interior_form(p), SolverConfig())
    assert be._lay.K == 8  # padded to the mesh axis
    from distributedlpsolver_tpu.ipm.driver import solve as drv_solve

    cfg = SolverConfig(tol=1e-8, verbose=False)
    ref = drv_solve(p, backend="block", config=cfg)
    res = drv_solve(p, backend=BlockAngularBackend(mesh=mesh), config=cfg)
    assert res.status.value == "optimal"
    rel = abs(res.objective - ref.objective) / max(1.0, abs(ref.objective))
    assert rel <= 1e-8


def test_two_phase_matches_single_phase():
    # The mixed-precision fused Schur solve (f32 per-block factorizations,
    # f64 finish) must reach the same optimum as the single-phase f64 path.
    # Exercised directly — config auto-enables it only on TPU platforms.
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import block_angular as ba
    from distributedlpsolver_tpu.ipm import core
    from distributedlpsolver_tpu.ipm.config import SolverConfig
    from distributedlpsolver_tpu.models.generators import block_angular_lp
    from distributedlpsolver_tpu.models.problem import to_interior_form

    p = block_angular_lp(4, 16, 28, 8, seed=9, sparse=True)
    inf = to_interior_form(p)
    cfg = SolverConfig()
    tensors, lay = ba.build_tensors(inf, jnp.float64)
    t32 = tensors._replace(
        B_all=tensors.B_all.astype(jnp.float32),
        L_all=tensors.L_all.astype(jnp.float32),
        A0=tensors.A0.astype(jnp.float32),
    )
    data = core.make_problem_data(jnp, inf.c, inf.b, inf.u, jnp.float64)
    reg0 = jnp.asarray(cfg.reg_dual, jnp.float64)
    params = cfg.step_params()
    mi = jnp.asarray(cfg.max_iter, jnp.int32)
    mr = jnp.asarray(cfg.max_refactor, jnp.int32)
    rg = jnp.asarray(cfg.reg_grow, jnp.float64)
    state0 = ba._block_start(tensors, lay, data, reg0, params)

    st1, it1, status1, _ = ba._block_solve_full(
        tensors, lay, data, state0, reg0, params, mi, mr, rg,
        core.buffer_cap(cfg.max_iter),
    )
    st2, it2, status2, _ = ba._block_solve_two_phase(
        tensors, t32, lay, data, state0, reg0, params, cfg.phase1_params(),
        mi, mr, rg, core.buffer_cap(2 * cfg.max_iter), cfg.stall_window,
    )
    assert int(status1) == core.STATUS_OPTIMAL
    assert int(status2) == core.STATUS_OPTIMAL
    obj1 = float(data.c @ st1.x)
    obj2 = float(data.c @ st2.x)
    assert abs(obj1 - obj2) < 1e-6 * (1 + abs(obj1))


def test_f64c_chunked_ops_match_direct():
    """The n-chunked f64 factorize/solve (_block_ops_f64c, the huge-shape
    finisher) must agree with the one-shot direct ops to round-off —
    including a chunk width that does not divide nb (pad-with-zeros)."""
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import block_angular as B
    from distributedlpsolver_tpu.models.problem import to_interior_form

    p = block_angular_lp(5, 12, 25, 9, seed=2, sparse=False)
    inf = to_interior_form(p)
    t, lay = B.build_tensors(inf, jnp.float64)
    reg = jnp.asarray(1e-10, jnp.float64)
    ops_ref = B._block_ops(t, lay, reg, None)
    ops_c = B._block_ops_f64c(t, lay, reg, chunk=7)  # ragged on purpose
    rng = np.random.default_rng(0)
    d = jnp.asarray(rng.uniform(0.5, 2.0, lay.n))
    r = jnp.asarray(rng.standard_normal(lay.m))
    x_ref = np.asarray(ops_ref.solve(ops_ref.factorize(d), r))
    x_c = np.asarray(ops_c.solve(ops_c.factorize(d), r))
    np.testing.assert_allclose(x_c, x_ref, rtol=1e-9, atol=1e-9)


def test_f64c_finisher_solves_to_full_tol(monkeypatch):
    """Force the huge-shape plan (split-bytes threshold dropped to 0) on a
    small block problem: phase 1 f32 -> PCG at handoff -> f64c chunked
    finisher must reach 1e-8 through the public API."""
    import jax

    from distributedlpsolver_tpu.backends import block_angular as B

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(B, "_F64_SPLIT_BUDGET", 0.0)
    p = block_angular_lp(4, 16, 32, 8, seed=6, sparse=False)
    be = B.BlockAngularBackend()
    r = solve(p, backend=be, solve_mode="pcg", scale=False, segment_iters=4)
    assert r.status == Status.OPTIMAL
    assert r.rel_gap <= 1e-8 and r.pinf <= 1e-8 and r.dinf <= 1e-8
    ref = highs_on_general(p)
    np.testing.assert_allclose(r.objective, ref.fun, rtol=1e-6, atol=1e-7)


def test_k_groups_partition_and_program_class():
    """Lever-4 plumbing: the K-group partitioner covers [0, K) exactly
    (ragged tail included), and the per-phase program-class stamp names
    the grouped f64 programs — and ONLY those — as a distinct class."""
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import block_angular as B

    assert B._k_groups(12, 5) == [(0, 5), (5, 5), (10, 2)]
    assert B._k_groups(12, 0) == [(0, 12)]  # grouping disabled
    assert B._k_groups(12, 12) == [(0, 12)]  # single group degenerates
    for K, g in ((1563, 128), (7, 3)):
        spans = B._k_groups(K, g)
        assert sum(s for _, s in spans) == K
        assert spans[0][0] == 0
        assert all(
            spans[i][0] + spans[i][1] == spans[i + 1][0]
            for i in range(len(spans) - 1)
        )
    assert B.phase_program_class(1563, jnp.float64) == "float64-kgroup128"
    assert B.phase_program_class(64, jnp.float64) == "float64-oneshot"
    # f32 phases NEVER group — the fault class is the big-K f64 kernels.
    assert B.phase_program_class(1563, jnp.float32) == "float32-oneshot"


def test_kgroup_factorize_solve_match_oneshot(monkeypatch):
    """K-grouped sequential chunking (lever 4) must match the one-shot
    f64 programs to round-off on BOTH phase paths — the direct ops and
    the n-chunked f64c finisher — including a group width that does not
    divide K. Eager comparison: ``_K_GROUP`` is a module global read at
    trace time, so the two settings must not share a jit cache."""
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import block_angular as B
    from distributedlpsolver_tpu.models.problem import to_interior_form

    p = block_angular_lp(12, 10, 18, 7, seed=4, sparse=False)
    inf = to_interior_form(p)
    t, lay = B.build_tensors(inf, jnp.float64)
    reg = jnp.asarray(1e-10, jnp.float64)
    rng = np.random.default_rng(1)
    d = jnp.asarray(rng.uniform(0.5, 2.0, lay.n))
    r = jnp.asarray(rng.standard_normal(lay.m))

    monkeypatch.setattr(B, "_K_GROUP", 0)
    ops_ref = B._block_ops(t, lay, reg, None)
    x_ref = np.asarray(ops_ref.solve(ops_ref.factorize(d), r))
    ops_cref = B._block_ops_f64c(t, lay, reg, chunk=7)
    xc_ref = np.asarray(ops_cref.solve(ops_cref.factorize(d), r))

    monkeypatch.setattr(B, "_K_GROUP", 5)  # ragged: 5 + 5 + 2
    ops_g = B._block_ops(t, lay, reg, None)
    x_g = np.asarray(ops_g.solve(ops_g.factorize(d), r))
    ops_cg = B._block_ops_f64c(t, lay, reg, chunk=7)
    xc_g = np.asarray(ops_cg.solve(ops_cg.factorize(d), r))

    np.testing.assert_allclose(x_g, x_ref, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(xc_g, xc_ref, rtol=1e-12, atol=1e-12)


def _network_lp(K, nodes, extra, joint, seed):
    """pds-shaped multicommodity flow: K copies of one node-arc incidence
    block (a cycle through every node, ``extra`` random arcs, a supply
    arc into every fourth node) and ``joint`` capacity rows, each one +1
    per commodity on one arc. Feasible and bounded by construction."""
    import scipy.sparse as sp

    from distributedlpsolver_tpu.models.problem import LPProblem

    rng = np.random.default_rng(seed)
    cyc = rng.permutation(nodes)
    tail = np.r_[cyc, rng.integers(0, nodes, extra)]
    head = np.r_[np.roll(cyc, -1),
                 (tail[nodes:] + rng.integers(1, nodes, extra)) % nodes]
    sup = np.arange(0, nodes, 4)
    arcs = len(tail)
    E = arcs + len(sup)
    r1 = np.r_[tail, head, sup]
    c1 = np.r_[np.arange(arcs), np.arange(arcs), arcs + np.arange(len(sup))]
    v1 = np.r_[-np.ones(arcs), np.ones(arcs + len(sup))]
    cap = rng.choice(arcs, joint, replace=False)
    rows = [r1 + k * nodes for k in range(K)]
    rows.append(np.repeat(K * nodes + np.arange(joint), K))
    cols = [c1 + k * E for k in range(K)]
    cols.append((cap[:, None] + E * np.arange(K)[None, :]).ravel())
    vals = [v1] * K + [np.ones(joint * K)]
    m, n = K * nodes + joint, K * E
    A = sp.csr_matrix(
        (np.concatenate(vals), (np.concatenate(rows), np.concatenate(cols))),
        shape=(m, n))
    x0 = rng.uniform(0.5, 2.0, n)
    Ax0 = A @ x0
    y0 = rng.standard_normal(m)
    y0[K * nodes:] = -np.abs(y0[K * nodes:])
    p = LPProblem(
        c=A.T @ y0 + rng.uniform(0.5, 2.0, n), A=A,
        rlb=np.r_[Ax0[:K * nodes], np.full(joint, -np.inf)],
        rub=np.r_[Ax0[:K * nodes],
                  Ax0[K * nodes:] + rng.uniform(0.1, 1.0, joint)],
        lb=np.zeros(n), ub=np.full(n, np.inf), name=f"network_{seed}",
    )
    p.block_structure = {"num_blocks": K, "block_m": nodes, "link_m": joint}
    return p


def test_f64c_pair_assembly_matches_chunked(monkeypatch):
    """The f64c Schur matrices built from the blocks' nonzero pairs must
    give the chunked einsums' factors and solves to round-off, with a
    ragged K grouping (5 + 5 + 2) and one dead block."""
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import block_angular as B

    inf = to_interior_form(_network_lp(11, 32, 24, 8, seed=3))
    rep = {}
    t, lay = B.build_tensors(inf, jnp.float64, pad_blocks=1, assembly=rep)
    assert rep["assembly"] == "pairs" and lay.K == 12
    monkeypatch.setattr(B, "_K_GROUP", 5)
    reg = jnp.asarray(1e-10, jnp.float64)
    rng = np.random.default_rng(5)
    d = jnp.asarray(rng.uniform(0.5, 2.0, lay.n))
    r = jnp.asarray(rng.standard_normal(lay.m))
    ops_p = B._block_ops_f64c(t, lay, reg, chunk=7)
    ops_d = B._block_ops_f64c(t._replace(pairs=None), lay, reg, chunk=7)
    f_p, f_d = ops_p.factorize(d), ops_d.factorize(d)
    for a, b in zip(f_p, f_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(ops_p.solve(f_p, r)),
                               np.asarray(ops_d.solve(f_d, r)),
                               rtol=1e-12, atol=1e-12)


def _hk_form_link_inverse(t, lay, d, reg):
    """Lsi by the three-GEMM downdate, written out in NumPy:
    ``S = MLL − Σₖ Gk·Hk``, ``Hk = Mkk⁻¹Gkᵀ``, each matrix with the
    factorization's relative diagonal regularization."""
    from scipy.linalg import solve_triangular

    B_, L_ = np.asarray(t.B_all), np.asarray(t.L_all)
    dB = np.r_[np.asarray(d), 0.0][np.asarray(t.col_idx)]
    idx = np.arange(lay.mb)
    Mkk = np.einsum("kmn,kn,kpn->kmp", B_, dB, B_)
    Mkk[:, idx, idx] += (np.asarray(t.row_idx) == lay.m)
    Mkk[:, idx, idx] *= 1 + reg
    Gk = np.einsum("kln,kn,kmn->klm", L_, dB, B_)
    Hk = np.linalg.solve(Mkk, np.swapaxes(Gk, 1, 2))
    A0 = np.asarray(t.A0)
    S = (np.einsum("kln,kn,kpn->lp", L_, dB, L_)
         + (A0 * np.asarray(d)[np.asarray(t.border_idx)]) @ A0.T
         - np.einsum("klm,kmp->lp", Gk, Hk))
    S[np.arange(lay.link), np.arange(lay.link)] *= 1 + reg
    return solve_triangular(np.linalg.cholesky(S), np.eye(lay.link),
                            lower=True)


def _downdate_rule(monkeypatch, B, downdate):
    """Set the rule's gather cost so these blocks take ``downdate``: at
    the chip's cost (``_GATHER_MAD_COST``) blocks of under 186 rows keep
    the GEMM, and these have 24–32."""
    monkeypatch.setattr(B, "_GATHER_MAD_COST",
                        1 if downdate == "gather" else 10**30)


@pytest.mark.parametrize("downdate", ["gather", "gemm"])
def test_f64c_downdate_forms_match_the_hk_form(monkeypatch, downdate):
    """The linking downdate as WᵀW, W = Lk⁻¹Gkᵀ gathered from Gk's
    nonzeros or formed by one GEMM, gives the three-GEMM ``Gk·Hk`` form's
    linking factor and solves to round-off, and the two W forms agree,
    with a ragged K grouping (5 + 5 + 2) and one dead block."""
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import block_angular as B

    _downdate_rule(monkeypatch, B, downdate)
    inf = to_interior_form(_network_lp(11, 32, 24, 8, seed=3))
    rep = {}
    t, lay = B.build_tensors(inf, jnp.float64, pad_blocks=1, assembly=rep)
    assert rep["assembly"] == "pairs" and rep["downdate"] == downdate
    assert (t.pairs.w_row is None) == (downdate == "gemm")
    monkeypatch.setattr(B, "_K_GROUP", 5)
    reg = 1e-10
    rng = np.random.default_rng(5)
    d = jnp.asarray(rng.uniform(0.5, 2.0, lay.n))
    r = jnp.asarray(rng.standard_normal(lay.m))
    ops = B._block_ops_f64c(t, lay, jnp.asarray(reg, jnp.float64), chunk=7)
    Lki, Lsi, Gk = ops.factorize(d)
    x = np.asarray(ops.solve((Lki, Lsi, Gk), r))
    Lsi_hk = _hk_form_link_inverse(t, lay, d, reg)
    np.testing.assert_allclose(np.asarray(Lsi), Lsi_hk, rtol=1e-12,
                               atol=1e-12)
    np.testing.assert_allclose(
        x, np.asarray(ops.solve((Lki, jnp.asarray(Lsi_hk), Gk), r)),
        rtol=1e-12, atol=1e-12)
    if downdate == "gather":
        t_gemm = t._replace(pairs=t.pairs._replace(w_row=None))
        ops_m = B._block_ops_f64c(t_gemm, lay, jnp.asarray(reg), chunk=7)
        f_m = ops_m.factorize(d)
        np.testing.assert_allclose(np.asarray(Lsi), np.asarray(f_m[1]),
                                   rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(x, np.asarray(ops_m.solve(f_m, r)),
                                   rtol=1e-12, atol=1e-12)


def test_assembly_rule_follows_block_sparsity():
    """Node-arc incidence blocks take the pairs, dense random tiles the
    einsums; the downdate gathers W on network blocks long enough for
    q·``_GATHER_MAD_COST`` ≤ 2·mb and takes the GEMM on shorter ones and
    on dense tiles. The pair count is every pair of nonzeros sharing a
    column, and ``downdate_q`` the most nonzeros in a row of Gk, both
    counted here from the padded tensors."""
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import block_angular as B

    for p, want, downdate in (
        (_network_lp(3, 200, 40, 8, seed=1), "pairs", "gather"),
        (_network_lp(11, 32, 24, 8, seed=3), "pairs", "gemm"),
        (block_angular_lp(4, 12, 30, 8, seed=1, sparse=False), "dense",
         "gemm"),
    ):
        rep = {}
        t, lay = B.build_tensors(to_interior_form(p), jnp.float64,
                                 assembly=rep)
        assert rep["assembly"] == want
        assert (t.pairs is None) == (want == "dense")
        B_, L_ = np.asarray(t.B_all), np.asarray(t.L_all)
        nB = np.count_nonzero(B_, axis=1)
        nL = np.count_nonzero(L_, axis=1)
        assert rep["pairs"] == int(np.sum(nB * nB + nL * nB + nL * nL))
        dense = 2.0 * lay.K * lay.nb * (
            lay.mb**2 + lay.link * lay.mb + lay.link**2)
        assert rep["dense_flops_avoided"] == (dense if want == "pairs" else 0)
        assert rep["padded_pairs"] * B._PAIR_ASSEMBLY_ADVANTAGE <= (
            dense if want == "pairs" else 0)
        assert rep["downdate"] == downdate
        gemm_w = 2.0 * lay.K * lay.mb**2 * lay.link
        if want == "dense":
            # Nothing enumerated: q is not counted, the Hk GEMM still goes.
            assert rep["downdate_q"] == 0
            assert rep["downdate_flops_avoided"] == gemm_w
            continue
        G_nz = np.einsum("kln,kmn->klm", L_ != 0, B_ != 0)
        q = int(np.count_nonzero(G_nz, axis=2).max())
        assert rep["downdate_q"] == q
        if downdate == "gemm":
            assert t.pairs.w_row is None
            assert B._bucket(q) * B._GATHER_MAD_COST > 2 * lay.mb
            assert rep["downdate_flops_avoided"] == gemm_w
            continue
        rows = np.asarray(t.pairs.w_row)
        assert rows.shape == (lay.K, lay.link, B._bucket(q))
        assert rows.shape[2] * B._GATHER_MAD_COST <= 2 * lay.mb
        got = np.zeros_like(G_nz, dtype=bool)
        k, l, _ = np.nonzero(rows < lay.mb)
        got[k, l, rows[rows < lay.mb]] = True
        np.testing.assert_array_equal(got, G_nz > 0)
        assert rep["downdate_flops_avoided"] == (
            2 * gemm_w - 2.0 * lay.K * lay.link * rows.shape[2] * lay.mb)


def _forced_f64c(monkeypatch):
    """The huge-shape plan (f32 gram phase, then the f64c finisher) on a
    small LP: the platform reads as TPU and the split budget is 0."""
    from distributedlpsolver_tpu.backends import block_angular as B

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(B, "_F64_SPLIT_BUDGET", 0.0)
    return B


@pytest.mark.parametrize("downdate", ["gather", "gemm"])
def test_f64c_finisher_same_iterations_under_both_assemblies(monkeypatch,
                                                            downdate):
    """Every iteration and the starting point in f64c (factor_dtype
    float64 drops the f32 phase): the pair assembly and the chunked
    einsums take the same path to 1e-8, with the pairs' downdate W
    gathered or formed by one GEMM, as the rule's cost is set (the
    chunked einsums always take the GEMM)."""
    B = _forced_f64c(monkeypatch)
    _downdate_rule(monkeypatch, B, downdate)
    p = _network_lp(6, 24, 24, 8, seed=7)
    runs = {}
    for advantage, want in ((B._PAIR_ASSEMBLY_ADVANTAGE, "pairs"),
                            (10**30, "dense")):
        monkeypatch.setattr(B, "_PAIR_ASSEMBLY_ADVANTAGE", advantage)
        be = B.BlockAngularBackend()
        r = solve(p, backend=be, segment_iters=4, factor_dtype="float64",
                  verbose=False)
        assert be.assembly["assembly"] == want
        assert be.assembly["downdate"] == (
            downdate if want == "pairs" else "gemm")
        assert r.status == Status.OPTIMAL
        assert r.rel_gap <= 1e-8 and r.pinf <= 1e-8 and r.dinf <= 1e-8
        assert [(ph["mode"], ph["iters"]) for ph in r.phase_report] == [
            ("f64c", r.iterations)]
        runs[want] = r
    assert runs["pairs"].iterations == runs["dense"].iterations
    np.testing.assert_allclose(runs["pairs"].objective,
                               runs["dense"].objective, rtol=1e-9)
    ref = highs_on_general(p)
    np.testing.assert_allclose(runs["pairs"].objective, ref.fun, rtol=1e-6,
                               atol=1e-7)


def test_f64c_phase_report_and_build_span_carry_the_assembly(
        monkeypatch, tmp_path):
    import json

    from distributedlpsolver_tpu.obs import trace as obs_trace

    B = _forced_f64c(monkeypatch)
    _downdate_rule(monkeypatch, B, "gather")
    be = B.BlockAngularBackend()
    tracer = obs_trace.Tracer(str(tmp_path / "trace.json"))
    prev = obs_trace.set_tracer(tracer)
    try:
        r = solve(_network_lp(6, 24, 24, 8, seed=7), backend=be,
                  segment_iters=4, verbose=False)
    finally:
        obs_trace.set_tracer(prev)
    assert r.status == Status.OPTIMAL
    rows = {ph["mode"]: ph for ph in r.phase_report}
    assert rows["f64c"]["assembly"] == "pairs"
    assert rows["f64c"]["pairs"] == be.assembly["pairs"] > 0
    assert rows["f64c"]["dense_flops_avoided"] > 0
    assert rows["f64c"]["downdate"] == "gather"
    assert rows["f64c"]["downdate_q"] == be.assembly["downdate_q"] == 2
    assert rows["f64c"]["downdate_flops_avoided"] > 0
    assert "assembly" not in rows["mixed"]
    # The f64c row's rate is folded with the pair assembly's flops and
    # the downdate's: the Gram GEMM and the gather of W.
    K, mb, link = be._lay.K, be._lay.mb, be._lay.link
    q = be._tensors.pairs.w_row.shape[2]
    assert rows["f64c"]["flops_per_iter"] == be._f64_flops == (
        K * mb**3 / 3.0 + 2.0 * link * link * be._lay.n0 + link**3 / 3.0
        + 2.0 * be.assembly["padded_pairs"]
        + 2.0 * K * link * link * mb + 2.0 * K * link * q * mb)
    assert rows["mixed"]["flops_per_iter"] == be._dense_flops > be._f64_flops
    events = json.loads(open(tracer.close()).read())["traceEvents"]
    build = [e for e in events if e["name"] == "lp.build"]
    assert build and build[0]["args"] == be.assembly
    assert build[0]["args"]["downdate"] == "gather"


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_f64c_pair_assembly_on_a_mesh_matches_unsharded(monkeypatch):
    """Pair lists and the downdate's gather rows shard over the block
    axis like B_all (6 blocks over 8 devices: two dead ones) and the
    linking factor over the mesh; the f64c solve matches the unsharded
    chunked one."""
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import block_angular as B

    _downdate_rule(monkeypatch, B, "gather")
    inf = to_interior_form(_network_lp(6, 24, 24, 8, seed=7))
    be = B.BlockAngularBackend(mesh=make_mesh(axis_names=("blocks",)))
    be.setup(inf, SolverConfig())
    assert be._lay.K == 8 and be.assembly["assembly"] == "pairs"
    # The gathered downdate runs sharded: its rows lie with their blocks.
    assert be.assembly["downdate"] == "gather"
    assert be._tensors.pairs.w_row.sharding.spec[0] == "blocks"
    t, lay = B.build_tensors(inf, jnp.float64)
    reg = jnp.asarray(1e-10, jnp.float64)
    rng = np.random.default_rng(2)
    d = jnp.asarray(rng.uniform(0.5, 2.0, lay.n))
    r = jnp.asarray(rng.standard_normal(lay.m))

    @jax.jit
    def sharded(t, d, r):
        ops = B._block_ops_f64c(t, be._lay, reg, link_shard=be._link_shard)
        return ops.solve(ops.factorize(d), r)

    ops = B._block_ops_f64c(t._replace(pairs=None), lay, reg, chunk=7)
    np.testing.assert_allclose(
        np.asarray(sharded(be._tensors, d, r)),
        np.asarray(ops.solve(ops.factorize(d), r)), rtol=1e-10, atol=1e-10)


def test_pair_lists_of_one_layout_share_a_padded_shape(monkeypatch):
    """Two networks of one layout with other random arcs get pair lists
    (the downdate's gather rows among them) of one padded shape, so a
    second LP reuses the first's programs: T, r and q are rounded up to
    a quarter of their power of two."""
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import block_angular as B

    _downdate_rule(monkeypatch, B, "gather")

    assert [B._bucket(x) for x in (0, 1, 7, 8, 9, 11, 9959, 9969)] == [
        1, 1, 7, 8, 10, 12, 10240, 10240]
    shapes = []
    for seed in (3, 4):
        inf = to_interior_form(_network_lp(11, 32, 24, 8, seed=seed))
        t, lay = B.build_tensors(inf, jnp.float64)
        shapes.append((tuple(lay), [a.shape for a in t.pairs]))
    assert shapes[0] == shapes[1]
