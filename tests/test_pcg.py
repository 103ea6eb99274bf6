"""Mixed-precision PCG solve mode of the dense backend.

The mode replaces the f64 direct factorization with an f32-Cholesky
preconditioner + matrix-free CG whose operator applies A·diag(d)·Aᵀ in
the iterate dtype (backends/dense.py:_pcg_ops). It exists for
reference-scale dense problems (BASELINE.json:9) where emulated-f64
assembly/Cholesky is intractable; these tests pin its algebra on CPU
(where f64 is native) — full-tolerance agreement with HiGHS through the
single-phase, two-phase, and segmented execution paths.
"""

import jax
import numpy as np
import pytest

from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.ipm.config import SolverConfig
from distributedlpsolver_tpu.ipm.state import Status
from distributedlpsolver_tpu.models.generators import random_dense_lp
from distributedlpsolver_tpu.models.problem import to_interior_form

from tests.oracle import highs_on_general


def _check_optimal(r, p):
    assert r.status == Status.OPTIMAL
    assert r.rel_gap <= 1e-8 and r.pinf <= 1e-8 and r.dinf <= 1e-8
    ref = highs_on_general(p)
    np.testing.assert_allclose(r.objective, ref.fun, rtol=1e-6, atol=1e-7)


def test_pcg_single_phase_full_tol():
    p = random_dense_lp(60, 180, seed=0)
    from distributedlpsolver_tpu.backends.dense import DenseJaxBackend

    be = DenseJaxBackend()
    r = solve(p, backend=be, solve_mode="pcg")
    assert be._pcg and not be._two_phase  # CPU platform: no phase schedule
    _check_optimal(r, p)


def test_pcg_as_phase2_of_two_phase(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from distributedlpsolver_tpu.backends.dense import DenseJaxBackend

    p = random_dense_lp(40, 100, seed=1)
    be = DenseJaxBackend()
    r = solve(p, backend=be, solve_mode="pcg", use_pallas=False)
    assert be._pcg and be._two_phase
    _check_optimal(r, p)


def test_pcg_segmented(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    p = random_dense_lp(40, 100, seed=2)
    r = solve(p, backend="tpu", solve_mode="pcg", use_pallas=False,
              segment_iters=2)
    _check_optimal(r, p)


def test_pcg_auto_resolution():
    from distributedlpsolver_tpu.backends.dense import DenseJaxBackend

    inf = to_interior_form(random_dense_lp(20, 50, seed=3))
    be = DenseJaxBackend()
    be.setup(inf, SolverConfig())
    assert not be._pcg  # auto: small problem / CPU platform


def test_pcg_sharded_on_mesh():
    # PCG under GSPMD: the chunked matrix-free operator and the
    # replicated f32 preconditioner compile over the mesh; dropping the
    # f64 factorization halves the replicated per-device footprint
    # (review round 1 item 8, first cut).
    from distributedlpsolver_tpu.backends.sharded import ShardedJaxBackend
    from distributedlpsolver_tpu.parallel import make_mesh

    p = random_dense_lp(48, 128, seed=4)
    be = ShardedJaxBackend(mesh=make_mesh(devices=jax.devices()[:8]))
    r = solve(p, backend=be, solve_mode="pcg")
    assert be._pcg
    _check_optimal(r, p)


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="memory-crossover claim is TPU-specific: CPU XLA's buffer "
    "assignment fuses the direct-f64 Cholesky differently (and f64 is "
    "native there), so temp_size_in_bytes does not reproduce the "
    "documented ordering off-TPU",
)
def test_pcg_memory_analysis_beats_direct_f64():
    # Compile-time per-device memory of one full-accuracy step at a
    # mid-size shape: the PCG step (f32 preconditioner + matrix-free CG)
    # must allocate less temp memory than the direct-f64 step it replaces
    # (which materializes M and its Cholesky factor in f64). This is the
    # documented memory crossover for the replicated-factorization relief.
    import jax.numpy as jnp
    from distributedlpsolver_tpu.backends import dense as D
    from distributedlpsolver_tpu.ipm.config import SolverConfig as SC

    m, n = 512, 1536
    inf = to_interior_form(random_dense_lp(m, n, seed=5))
    A = jnp.asarray(np.asarray(inf.A), dtype=jnp.float64)
    from distributedlpsolver_tpu.ipm import core as C

    data = C.make_problem_data(
        jnp, jnp.asarray(inf.c), jnp.asarray(inf.b), jnp.asarray(inf.u),
        jnp.float64,
    )
    params = SC().step_params()

    from distributedlpsolver_tpu.ipm.state import IPMState

    key_state = IPMState(
        x=jnp.ones(inf.n), y=jnp.zeros(inf.m), s=jnp.ones(inf.n),
        w=jnp.ones(inf.n), z=jnp.zeros(inf.n),
    )
    reg = jnp.asarray(1e-10, jnp.float64)

    def mem(fn, *args, **kw):
        lowered = jax.jit(
            fn, static_argnames=tuple(kw.keys())
        ).lower(*args, **kw)
        return lowered.compile().memory_analysis().temp_size_in_bytes

    def direct_step(A, data, state, reg):
        ops = D._make_ops(A, reg, jnp.dtype(jnp.float64), 0, False, None)
        return C.mehrotra_step(ops, data, params, state)

    A32 = A.astype(jnp.float32)

    def pcg_step(A, A32, data, state, reg):
        ops = D._make_ops(
            A, reg, jnp.dtype(jnp.float32), 0, False, A32, 100, 1e-11
        )
        return C.mehrotra_step(ops, data, params, state)

    m_direct = mem(direct_step, A, data, key_state, reg)
    m_pcg = mem(pcg_step, A, A32, data, key_state, reg)
    assert m_pcg < m_direct, (m_pcg, m_direct)


def test_pcg_host_driver_path():
    # fused_loop=False exercises starting_point + per-iteration iterate()
    # through the PCG ops.
    p = random_dense_lp(30, 90, seed=5)
    r = solve(p, backend="tpu", solve_mode="pcg", fused_loop=False)
    _check_optimal(r, p)


class TestBlockPCG:
    """PCG mode of the block-angular Schur backend (same design, arrow
    structure: f32 block/linking factorization preconditioner +
    full-precision matrix-free CG through the block tensors)."""

    def test_block_pcg_matches_highs(self):
        from distributedlpsolver_tpu.models.generators import block_angular_lp
        from distributedlpsolver_tpu.backends.block_angular import (
            BlockAngularBackend,
        )

        p = block_angular_lp(6, 24, 48, 12, seed=3, sparse=False)
        be = BlockAngularBackend()
        r = solve(p, backend=be, solve_mode="pcg", scale=False)
        assert be._pcg
        assert r.status == Status.OPTIMAL
        assert r.rel_gap <= 1e-8
        ref = highs_on_general(p)
        np.testing.assert_allclose(r.objective, ref.fun, rtol=1e-6, atol=1e-7)

    def test_block_pcg_segmented(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        from distributedlpsolver_tpu.models.generators import block_angular_lp

        p = block_angular_lp(4, 16, 32, 8, seed=4, sparse=False)
        r = solve(p, backend="block", solve_mode="pcg", scale=False,
                  segment_iters=2)
        assert r.status == Status.OPTIMAL
        ref = highs_on_general(p)
        np.testing.assert_allclose(r.objective, ref.fun, rtol=1e-6, atol=1e-7)

    def test_block_pcg_on_mesh(self):
        # The arrow-structure PCG is pure einsum + vector work, so it
        # shards over the K axis like the direct path.
        from distributedlpsolver_tpu.models.generators import block_angular_lp
        from distributedlpsolver_tpu.backends.block_angular import (
            BlockAngularBackend,
        )
        from distributedlpsolver_tpu.parallel import make_mesh

        p = block_angular_lp(8, 12, 24, 8, seed=5, sparse=False)
        mesh = make_mesh(devices=jax.devices()[:8])
        r = solve(p, backend=BlockAngularBackend(mesh=mesh),
                  solve_mode="pcg", scale=False)
        assert r.status == Status.OPTIMAL
        ref = highs_on_general(p)
        np.testing.assert_allclose(r.objective, ref.fun, rtol=1e-6, atol=1e-7)

    def test_block_pcg_host_driver(self):
        from distributedlpsolver_tpu.models.generators import block_angular_lp

        p = block_angular_lp(4, 16, 32, 8, seed=6, sparse=False)
        r = solve(p, backend="block", solve_mode="pcg", scale=False,
                  fused_loop=False)
        assert r.status == Status.OPTIMAL
        ref = highs_on_general(p)
        np.testing.assert_allclose(r.objective, ref.fun, rtol=1e-6, atol=1e-7)


def _force_endgame(monkeypatch, **extra):
    """Run a small PCG solve that GENUINELY enters the endgame loop.

    On a well-conditioned toy the f32 preconditioner is essentially
    exact, so the PCG phase cannot be made to floor the way it does at
    reference scale (observed there: hard pinf floor ~3e-7). Instead the
    fused phases' iteration budget is truncated at the host driver so
    they exit MAXITER with a genuinely unconverged iterate — the endgame
    must then do real full-precision work to reach 1e-8. Returns
    (backend, result, problem)."""
    import distributedlpsolver_tpu.backends.dense as d

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(d.DenseJaxBackend, "_ENDGAME_ENTRIES", 1)
    real_dpp = d.core.drive_phase_plan

    def truncated(phases, state, reg0, max_iter, buf_cap, dtype, **kw):
        return real_dpp(phases, state, reg0, 4, buf_cap, dtype, **kw)

    monkeypatch.setattr(d.core, "drive_phase_plan", truncated)
    p = random_dense_lp(48, 128, seed=6)
    be = d.DenseJaxBackend()
    r = solve(p, backend=be, solve_mode="pcg", use_pallas=False, **extra)
    return be, r, p


def test_endgame_finishes_after_pcg_floor(monkeypatch):
    # Phase 1 f32 -> phase 2 PCG (crippled: stalls below tol) ->
    # host-driven endgame iterations with the factorization computed in
    # separate dispatches. Must reach full 1e-8 optimality, and must
    # actually have run the endgame (per-dispatch timings recorded).
    be, r, p = _force_endgame(monkeypatch)
    assert be._pcg
    _check_optimal(r, p)
    # the history must be contiguous through the endgame append
    assert len(r.history) == r.iterations
    tm = [row for row in be.endgame_timings if "t_step" in row]
    assert tm, "endgame loop was never entered"
    assert {"it", "t_assemble", "t_factor", "t_step", "bad", "reg"} <= set(
        tm[0]
    )
    # the endgame is a phase_report row too — without it the utilization
    # artifacts under-attribute exactly the endgame iterations
    rep = be.phase_report
    assert rep[-1]["mode"] == "endgame"
    assert sum(ph["iters"] for ph in rep) == r.iterations
    # seeded reg is capped: f32-phase escalations must not pin the f64
    # finish above tol (code-review finding, round 3)
    assert all(row["reg"] <= 1e-6 + 1e-18 for row in tm if not row["bad"])


def test_endgame_bad_step_escalates_without_reassembly(monkeypatch):
    # A bad step must re-run ONLY factor+step with escalated reg — the
    # assembly (longest dispatch at scale) is reused for the same iterate.
    # Pinned to the DEVICE factor path (endgame_host=False): the forced
    # badness is injected into the device step function.
    import distributedlpsolver_tpu.backends.dense as d

    real_step = d._endgame_step
    real_asm = d._endgame_assemble
    forced = {"n": 0}
    asm_calls = {"n": 0}

    def bad_once_step(A, data, state, L, reg, diagM, params, refine=1):
        new_state, stats = real_step(A, data, state, L, reg, diagM, params,
                                     refine=refine)
        if forced["n"] == 0:
            forced["n"] += 1
            stats = stats._replace(bad=True)
        return new_state, stats

    def counting_asm(A, data, state, params):
        asm_calls["n"] += 1
        return real_asm(A, data, state, params)

    monkeypatch.setattr(d, "_endgame_step", bad_once_step)
    monkeypatch.setattr(d, "_endgame_assemble", counting_asm)
    be, r, p = _force_endgame(monkeypatch, endgame_host=False)
    _check_optimal(r, p)
    tm = be.endgame_timings
    bad_rows = [row for row in tm if row["bad"]]
    assert len(bad_rows) == 1  # the forced one
    # retry escalated reg relative to the failed attempt...
    i = tm.index(bad_rows[0])
    assert tm[i + 1]["reg"] > bad_rows[0]["reg"]
    # ...WITHOUT a fresh assembly: one assemble per endgame ITERATE, not
    # per attempt (attempts == len(tm) > iterates when a retry happened)
    assert asm_calls["n"] == len(tm) - len(bad_rows)
    # and the retry row records no assembly time of its own
    assert tm[i + 1]["t_assemble"] == 0.0


def test_endgame_numerical_error_exit(monkeypatch):
    # Persistent bad steps must escalate reg to the cap and exit
    # NUMERICAL_ERROR instead of looping forever.
    import distributedlpsolver_tpu.backends.dense as d

    real_step = d._endgame_step

    def always_bad(A, data, state, L, reg, diagM, params, refine=1):
        new_state, stats = real_step(A, data, state, L, reg, diagM, params,
                                     refine=refine)
        return new_state, stats._replace(bad=True)

    monkeypatch.setattr(d, "_endgame_step", always_bad)
    be, r, p = _force_endgame(monkeypatch, endgame_host=False)
    assert r.status == Status.NUMERICAL_ERROR
    tm = be.endgame_timings
    assert all(row["bad"] for row in tm)
    regs = [row["reg"] for row in tm]
    assert regs == sorted(regs) and regs[-1] > regs[0]  # monotone escalation


def test_endgame_stall_exit(monkeypatch):
    # Steps that stop improving must trip the endgame's stall window and
    # exit STALLED rather than burning the whole iteration budget.
    import distributedlpsolver_tpu.backends.dense as d

    real_step = d._endgame_step

    def frozen_step(A, data, state, L, reg, diagM, params, refine=1):
        _, stats = real_step(A, data, state, L, reg, diagM, params,
                             refine=refine)
        return state, stats  # no progress: same iterate every time

    monkeypatch.setattr(d, "_endgame_step", frozen_step)
    be, r, p = _force_endgame(monkeypatch, endgame_host=False,
                              stall_window=3, max_iter=60)
    assert r.status == Status.STALLED
    # it gave up well before the iteration budget
    assert len(be.endgame_timings) < 40


def test_pcg_sharded_preconditioner_memory_and_agreement():
    """The column-sharded L⁻¹ build (dense._tri_inv_mesh) must (a) agree
    with the replicated build and (b) cut per-device compiled memory of a
    PCG step on the mesh — the distributed-factorization first cut
    (review round 2 item 5: 'per-device peak memory measurably below
    the replicated-PCG baseline')."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributedlpsolver_tpu.backends import dense as D
    from distributedlpsolver_tpu.ipm import core as C
    from distributedlpsolver_tpu.ipm.config import SolverConfig as SC
    from distributedlpsolver_tpu.ipm.state import IPMState
    from distributedlpsolver_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh((8,), axis_names=("cols",))
    psh = NamedSharding(mesh, P(None, "cols"))

    # (a) numerical agreement of the sharded triangular inverse
    rng = np.random.default_rng(3)
    m = 96
    Lt = np.tril(rng.standard_normal((m, m))) + 4.0 * np.eye(m)
    L = jnp.asarray(Lt, dtype=jnp.float32)
    ref = np.asarray(D._tri_inv_paneled(L, panel=32))
    got = np.asarray(jax.jit(lambda L: D._tri_inv_mesh(L, psh, panel=8))(L))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    # (b) per-device compiled memory: sharded factor beats replicated
    mm, nn = 512, 1024
    inf = to_interior_form(random_dense_lp(mm, nn, seed=5))
    A = jax.device_put(
        jnp.asarray(np.asarray(inf.A), dtype=jnp.float64),
        NamedSharding(mesh, P(None, "cols")),
    )
    A32 = A.astype(jnp.float32)
    data = C.make_problem_data(
        jnp, jnp.asarray(inf.c), jnp.asarray(inf.b), jnp.asarray(inf.u),
        jnp.float64,
    )
    params = SC().step_params()
    key_state = IPMState(
        x=jnp.ones(inf.n), y=jnp.zeros(inf.m), s=jnp.ones(inf.n),
        w=jnp.ones(inf.n), z=jnp.zeros(inf.n),
    )
    reg = jnp.asarray(1e-10, jnp.float64)

    def mem(prec_shard):
        def step(A, A32, data, state, reg):
            ops = D._make_ops(
                A, reg, jnp.dtype(jnp.float32), 0, False, A32, 100, 1e-11,
                prec_shard,
            )
            return C.mehrotra_step(ops, data, params, state)

        lowered = jax.jit(step).lower(A, A32, data, key_state, reg)
        return lowered.compile().memory_analysis().temp_size_in_bytes

    m_repl = mem(None)
    m_shard = mem(psh)
    # The replicated step holds the full m×m f64 L⁻¹ per device; the
    # sharded step holds m×(m/8). Demand a real margin (≥ 2·m² bytes —
    # a quarter of the f64 factor), not noise: buffer reuse means the
    # full 7/8·8m² savings is not visible in temp accounting.
    assert m_shard < m_repl - 2 * mm * mm, (m_shard, m_repl)

    # (c) end-to-end on the mesh through the public API
    from distributedlpsolver_tpu.backends.sharded import ShardedJaxBackend

    p = random_dense_lp(64, 160, seed=9)
    be = ShardedJaxBackend(mesh=mesh)
    r = solve(p, backend=be, solve_mode="pcg")
    assert be._prec_shard is not None
    _check_optimal(r, p)


class TestHostEndgame:
    """Host-LAPACK endgame factorization + feasibility projection
    (cfg.endgame_host; auto = on under emulated f64). These are the two
    mechanisms that broke the round-3 10k×50k terminal wall — the
    emulated-f64 Cholesky NaN floor and the reg-filtered pinf floor
    (BENCH_10K.json analysis) — pinned here at toy scale on CPU."""

    def test_auto_endgame_is_mxu_and_finishes(self, monkeypatch):
        # auto-resolution: endgame_host=None on (monkeypatched) TPU ->
        # the on-device mxu mode (round 5). Must reach 1e-8 with mxu
        # step rows in the timing record; the pure-jax AAᵀ closure keeps
        # the final iterate essentially on Ax=b.
        be, r, p = _force_endgame(monkeypatch)
        _check_optimal(r, p)
        tm = be.endgame_timings
        assert all(row.get("mode") == "mxu" for row in tm)
        assert not any(row.get("host") for row in tm)
        assert r.pinf < 1e-10

    def test_host_endgame_finishes(self, monkeypatch):
        # Explicit endgame_host=True keeps the LAPACK escape hatch: host
        # step rows (with a transfer phase) in the timing record, same
        # 1e-8 finish, pinf pinned by the host AAᵀ closure.
        be, r, p = _force_endgame(monkeypatch, endgame_host=True)
        _check_optimal(r, p)
        tm = be.endgame_timings
        assert any(row.get("host") for row in tm)
        steps = [row for row in tm if "t_step" in row and not row["bad"]]
        assert steps and all("t_transfer" in row for row in steps)
        assert r.pinf < 1e-10

    def test_host_factor_failure_escalates_without_retransfer(
        self, monkeypatch
    ):
        # A host factorization failure must walk the reg ladder on the
        # HELD host copy: no step dispatch, no re-assembly, no re-transfer
        # for the retry; the eventual good step runs at the escalated reg.
        import distributedlpsolver_tpu.backends.dense as d

        real_fac = d._endgame_factor_host
        # call 0 is the projector build (same helper) — let it succeed,
        # then fail the endgame loop's first two factorizations
        calls = {"n": 0}

        def flaky(Mh, reg):
            calls["n"] += 1
            if calls["n"] in (2, 3):
                return None
            return real_fac(Mh, reg)

        monkeypatch.setattr(d, "_endgame_factor_host", flaky)
        be, r, p = _force_endgame(monkeypatch, endgame_host=True)
        _check_optimal(r, p)
        tm = [row for row in be.endgame_timings if "t_step" in row]
        assert [row["bad"] for row in tm[:3]] == [True, True, False]
        assert tm[0]["L_finite"] is False and tm[1]["L_finite"] is False
        # ladder retries paid neither assembly nor transfer again
        assert tm[1]["t_assemble"] == 0.0 and tm[1]["t_transfer"] == 0.0
        assert tm[2]["t_assemble"] == 0.0 and tm[2]["t_transfer"] == 0.0
        assert tm[2]["reg"] > tm[0]["reg"]

    def test_host_bad_step_retries_from_held_copy(self, monkeypatch):
        # A bad STEP (finite factor, zero step) in host mode must retry
        # with escalated reg from the held host M — no re-assembly.
        import distributedlpsolver_tpu.backends.dense as d

        real_step = d._endgame_step_host
        real_asm = d._endgame_assemble
        forced = {"n": 0}
        asm_calls = {"n": 0}

        def bad_once(A, data, state, hostf, reg, diagM, params, refine=1,
                     restore=None):
            new_state, stats = real_step(
                A, data, state, hostf, reg, diagM, params, refine=refine,
                restore=restore,
            )
            if forced["n"] == 0:
                forced["n"] += 1
                stats = stats._replace(bad=True)
            return new_state, stats

        def counting_asm(A, data, state, params):
            asm_calls["n"] += 1
            return real_asm(A, data, state, params)

        monkeypatch.setattr(d, "_endgame_step_host", bad_once)
        monkeypatch.setattr(d, "_endgame_assemble", counting_asm)
        be, r, p = _force_endgame(monkeypatch, endgame_host=True)
        _check_optimal(r, p)
        tm = [row for row in be.endgame_timings if "t_step" in row]
        bad_rows = [row for row in tm if row["bad"]]
        assert len(bad_rows) == 1
        i = tm.index(bad_rows[0])
        assert tm[i + 1]["reg"] > bad_rows[0]["reg"]
        assert tm[i + 1]["t_assemble"] == 0.0
        assert tm[i + 1]["t_transfer"] == 0.0
        assert asm_calls["n"] == len(tm) - len(bad_rows)


@pytest.mark.parametrize("m", [1, 5, 97, 256, 1000, 1023])
def test_fetch_symmetric_exact(m):
    """The lower-triangle d2h fetch (dense._fetch_symmetric) must
    reconstruct a symmetric matrix EXACTLY (bitwise) — the host endgame
    factors what it returns, so any mirroring defect becomes a silent
    factorization of the wrong matrix."""
    import jax.numpy as jnp

    import distributedlpsolver_tpu.backends.dense as d

    rng = np.random.default_rng(m)
    G = rng.standard_normal((m, m))
    S = G + G.T
    got = d._fetch_symmetric(jnp.asarray(S))
    assert got.dtype == np.float64
    np.testing.assert_array_equal(got, S)


def test_pure_centering_step_improves_centrality():
    """StepParams.center: a pure centering step on a badly off-center
    iterate must raise the worst product/μ ratio while staying feasible —
    the blocked-step remedy the endgame's anti-stagnation ladder fires."""
    import dataclasses

    import jax.numpy as jnp

    import distributedlpsolver_tpu.backends.dense as d
    from distributedlpsolver_tpu.ipm import core as C
    from distributedlpsolver_tpu.ipm.config import SolverConfig
    from distributedlpsolver_tpu.ipm.state import IPMState

    rng = np.random.default_rng(4)
    m, n = 12, 32
    A = jnp.asarray(rng.standard_normal((m, n)))
    x0 = jnp.asarray(rng.uniform(0.5, 2.0, n))
    b = A @ x0
    data = C.make_problem_data(
        jnp, jnp.asarray(rng.standard_normal(n)), b,
        jnp.full(n, jnp.inf), jnp.float64,
    )
    # off-center: a handful of products orders below the average
    s0 = jnp.asarray(rng.uniform(0.5, 2.0, n)).at[:4].set(1e-6)
    st = IPMState(x=x0, y=jnp.zeros(m), s=s0, w=jnp.ones(n),
                  z=jnp.zeros(n))
    params = dataclasses.replace(SolverConfig().step_params(), center=True)
    ops = d._make_ops(A, jnp.asarray(1e-10), jnp.dtype(jnp.float64), 0,
                      False, None, 0, 0.0, None)
    r0, _, _ = d._cent_diag(data, st, jnp.asarray(params.gamma_cent))
    st1, stats = C.mehrotra_step(ops, data, params, st)
    r1, _, _ = d._cent_diag(data, st1, jnp.asarray(params.gamma_cent))
    assert not bool(np.asarray(stats.bad))
    assert float(np.asarray(stats.sigma)) == 1.0
    # centrality must improve by a real factor, not noise
    assert float(np.asarray(r1)) > 10 * float(np.asarray(r0))
    assert np.all(np.asarray(st1.x) > 0) and np.all(np.asarray(st1.s) > 0)


def test_endgame_stagnation_fires_centering_ladder(monkeypatch):
    """μ-stagnant accepted steps must trigger the anti-stagnation ladder:
    a pure centering step after ONE sub-10%-μ step (round-5 one-strike
    trigger; center=True param reaching the step, row flagged), the
    collapsed-pair lift after three consecutive strikes, and the run
    still finishing OPTIMAL once the (simulated) blockage lifts."""
    import distributedlpsolver_tpu.backends.dense as d

    real_step = d._endgame_step_host
    real_recenter = d._endgame_recenter
    sim = {"blocked": 0, "centers": 0, "recenters": 0}

    def blocked_then_real(A, data, state, hostf, reg, diagM, params,
                          refine=1, restore=None):
        import jax.numpy as jnp

        new_state, stats = real_step(
            A, data, state, hostf, reg, diagM, params, refine=refine,
            restore=restore,
        )
        if params.center:
            sim["centers"] += 1
        if sim["centers"] >= 2:
            return new_state, stats  # blockage lifted — run real
        # Simulate the blocked-step mode: the iterate does not move and
        # μ reports a CONSTANT, so the loop's stagnation counter climbs
        # deterministically through the whole ladder (1 strike → center,
        # 3 strikes → recenter + center) before the real solve resumes.
        sim["blocked"] += 1
        return state, stats._replace(
            alpha_p=jnp.asarray(0.005), alpha_d=jnp.asarray(0.01),
            mu=stats.mu * 0 + 1e-6, bad=stats.bad & False,
        )

    def counting_recenter(data, state, params):
        sim["recenters"] += 1
        return real_recenter(data, state, params)

    monkeypatch.setattr(d, "_endgame_step_host", blocked_then_real)
    monkeypatch.setattr(d, "_endgame_recenter", counting_recenter)
    be, r, p = _force_endgame(monkeypatch, endgame_host=True)
    _check_optimal(r, p)
    tm = [row for row in be.endgame_timings if "t_step" in row]
    # the ladder fired at least one centering step, flagged in the rows
    assert sim["centers"] >= 1
    assert any(row["center"] for row in tm)
    # ONE-strike trigger pinned: with constant-μ blocked steps the first
    # CENTER row must land by the third step (blocked, strike → center).
    # The old two-strike scheme centers one step later and fails this.
    assert any(row["center"] for row in tm[:3]), [r["center"] for r in tm[:5]]
    # entry recenter always runs once; the ladder's mid-loop lift adds one
    assert sim["recenters"] >= 2
    # every row carries the blocked-step diagnostics
    assert all("cent_ratio" in row and "n_below" in row for row in tm)


def test_host_projector_restores_feasibility_and_respects_bounds():
    """Unit test of the alternating-projections (POCS) projector: an
    iterate pushed off Ax=b must come back to ~machine feasibility
    WITHOUT violating positivity or finite upper bounds."""
    import jax.numpy as jnp
    import distributedlpsolver_tpu.backends.dense as d
    from distributedlpsolver_tpu.ipm import core as C
    from distributedlpsolver_tpu.ipm.state import IPMState

    rng = np.random.default_rng(11)
    m, n = 24, 64
    A = jnp.asarray(rng.standard_normal((m, n)))
    # late-IPM-like ground truth: m "basic" O(1) columns, the rest
    # collapsed tiny; b is consistent with THIS point, and the iterate
    # is knocked a small distance off it (the endgame regime: small,
    # reg-filtered feasibility drift on an otherwise converged iterate)
    x = np.full(n, 1e-9)
    basic = rng.choice(n, size=m, replace=False)
    x[basic] = np.abs(rng.standard_normal(m)) + 0.5
    b = A @ jnp.asarray(x)
    u = np.full(n, np.inf)
    u[:8] = x[:8] + 1.5  # a few finite upper bounds
    data = C.make_problem_data(
        jnp, jnp.asarray(rng.standard_normal(n)), b, jnp.asarray(u),
        jnp.float64,
    )
    x = jnp.asarray(x)
    x_off = x + 1e-5 * jnp.asarray(rng.standard_normal(n))
    x_off = jnp.maximum(x_off, 1e-12)
    st = IPMState(
        x=x_off, y=jnp.zeros(m), s=jnp.ones(n),
        w=jnp.where(data.hub > 0, jnp.maximum(data.u_f - x_off, 1e-12), 1.0),
        z=jnp.where(data.hub > 0, 1.0, 0.0),
    )
    pinf0 = float(d._eg_pinf(A, data, st.x, st.w))
    project = d._build_host_projector(A, data)
    assert project is not None
    st2, p0, p1 = project(st, rounds=40)
    assert p0 == pytest.approx(pinf0)
    # alternating projections contract geometrically (measured ~1.9x per
    # round on this construction); 40 rounds must buy several orders
    assert p1 < 1e-3 * p0
    x2 = np.asarray(st2.x)
    assert (x2 > 0).all()
    hub = np.asarray(data.hub) > 0
    assert (x2[hub] < np.asarray(data.u_f)[hub]).all()
    # the box projection keeps columns STRICTLY interior at every round
    # (asserted by the (x2 > 0).all() above); columns the affine set
    # persistently wants at zero decay geometrically (0.1x per round) —
    # approaching their true nonbasic value — rather than oscillating
    nonbasic = np.setdiff1d(np.arange(n), basic)
    assert x2[nonbasic].max() < 1e-3  # none blew up to basic scale


def test_host_factor_reports_breakdown_as_none():
    """_endgame_factor_host must report breakdown (indefinite /
    non-factorable input) by returning None — the ladder's retry signal —
    through the REAL scipy path, not a monkeypatch."""
    import distributedlpsolver_tpu.backends.dense as d

    rng = np.random.default_rng(3)
    B = rng.standard_normal((16, 16))
    indefinite = B + B.T  # symmetric, eigenvalues of both signs
    assert d._endgame_factor_host(indefinite, 1e-12) is None
    spd = B @ B.T + 16 * np.eye(16)
    out = d._endgame_factor_host(spd, 1e-12)
    assert out is not None
    L, s = out
    assert np.isfinite(L).all() and np.isfinite(s).all()
    # round-trip: the factor solves the Jacobi-scaled regularized system
    rhs = rng.standard_normal(16)
    import scipy.linalg as sla

    x = s * sla.cho_solve((L, True), s * rhs)
    sc = 1.0 / np.sqrt(np.diagonal(spd))
    Ms = spd + 1e-12 * np.diag(1.0 / sc**2)
    np.testing.assert_allclose(Ms @ x, rhs, rtol=1e-9, atol=1e-9)


def test_endgame_host_config_rejects_strings():
    with pytest.raises(ValueError):
        SolverConfig(endgame_host="host")
