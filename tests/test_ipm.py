"""IPM correctness tests against the scipy-HiGHS oracle (SURVEY.md §4).

The reference validates against Netlib problems with known optima
(BASELINE.json:7,8); without network access, the oracle role is played by
scipy's HiGHS on generated problems (feasible+bounded by construction) and
hand-written MPS fixtures.
"""

import numpy as np
import pytest

from distributedlpsolver_tpu.io.mps import read_mps_string
from distributedlpsolver_tpu.ipm import SolverConfig, Status, solve
from distributedlpsolver_tpu.models.generators import (
    block_angular_lp,
    random_dense_lp,
    random_general_lp,
)
from tests.oracle import highs_on_general

BACKEND = "tpu"


def _check_against_highs(p, r, tol=2e-6):
    hi = highs_on_general(p)
    assert hi.status == 0
    assert r.status == Status.OPTIMAL, r.summary()
    assert abs(r.objective - hi.fun) <= tol * (1.0 + abs(hi.fun))
    assert p.max_violation(r.x) <= 1e-5 * (1.0 + float(np.abs(r.x).max()))


@pytest.mark.parametrize("seed", range(6))
def test_random_dense_matches_highs(seed):
    p = random_dense_lp(30, 60, seed=seed)
    r = solve(p, backend=BACKEND, max_iter=60)
    _check_against_highs(p, r)


@pytest.mark.parametrize("seed", range(4))
def test_random_general_matches_highs(seed):
    """Exercises slacks, ranges, shifts, negated and free columns."""
    p = random_general_lp(30, 50, seed=seed)
    r = solve(p, backend=BACKEND, max_iter=60)
    _check_against_highs(p, r)


def test_medium_dense():
    p = random_dense_lp(150, 400, seed=7)
    r = solve(p, backend=BACKEND, max_iter=60)
    _check_against_highs(p, r)


def test_block_angular_dense_path():
    p = block_angular_lp(4, 20, 40, 10, seed=0, sparse=False)
    r = solve(p, backend=BACKEND, max_iter=60)
    _check_against_highs(p, r)


def test_converges_to_1e8_gap():
    """The reference's convergence criterion: 1e-8 duality gap
    (BASELINE.json:2)."""
    p = random_dense_lp(50, 120, seed=11)
    r = solve(p, backend=BACKEND)
    assert r.status == Status.OPTIMAL
    assert r.rel_gap <= 1e-8
    assert r.pinf <= 1e-8
    assert r.dinf <= 1e-8


def test_iteration_history_recorded():
    p = random_dense_lp(20, 45, seed=1)
    r = solve(p, backend=BACKEND)
    assert len(r.history) == r.iterations
    assert r.history[-1].rel_gap <= 1e-8
    # gap trajectory is broadly decreasing (allow transient bumps)
    gaps = [h.mu for h in r.history]
    assert gaps[-1] < gaps[0]


def test_maximize_sense():
    """LPProblem stores the minimized form; the maximize flag flips the
    *reported* objective (originally 'maximize -c' ≡ 'minimize c')."""
    p = random_dense_lp(15, 30, seed=2)
    pm = random_dense_lp(15, 30, seed=2)
    pm.maximize = True
    r_min = solve(p, backend=BACKEND)
    r_max = solve(pm, backend=BACKEND)
    assert r_max.objective == pytest.approx(-r_min.objective, rel=1e-6)


def test_mps_roundtrip_solve():
    mps = """NAME          TINY
ROWS
 N  COST
 L  LIM1
 G  LIM2
 E  EQ1
COLUMNS
    X1  COST  1.0  LIM1  1.0
    X1  EQ1   1.0
    X2  COST  2.0  LIM1  1.0
    X2  LIM2  1.0
    X3  COST  -1.0  LIM2  1.0
    X3  EQ1   1.0
RHS
    RHS  LIM1  4.0  LIM2  1.0
    RHS  EQ1   3.0
BOUNDS
 UP BND  X3  2.0
ENDATA
"""
    p = read_mps_string(mps)
    r = solve(p, backend=BACKEND)
    _check_against_highs(p, r)


def test_warm_start_resume(tmp_path):
    """Checkpoint mid-solve, resume, reach the same optimum
    (SURVEY.md §5.4)."""
    p = random_dense_lp(40, 90, seed=5)
    ck = str(tmp_path / "state.npz")
    cfg = SolverConfig(max_iter=4, checkpoint_path=ck, checkpoint_every=1)
    r1 = solve(p, backend=BACKEND, config=cfg)
    assert r1.status == Status.ITERATION_LIMIT
    cfg2 = SolverConfig(checkpoint_path=ck)
    r2 = solve(p, backend=BACKEND, config=cfg2)
    assert r2.status == Status.OPTIMAL
    hi = highs_on_general(p)
    assert abs(r2.objective - hi.fun) <= 2e-6 * (1.0 + abs(hi.fun))


def test_jsonl_logging(tmp_path):
    import json

    path = str(tmp_path / "iters.jsonl")
    p = random_dense_lp(20, 40, seed=3)
    r = solve(p, backend=BACKEND, log_jsonl=path)
    records = [json.loads(line) for line in open(path)]
    assert len(records) == r.iterations
    assert {"iter", "mu", "rel_gap", "pinf", "dinf", "t_iter"} <= set(records[0])


def test_fused_loop_matches_host_loop():
    """The on-device lax.while_loop solve must replay the host loop
    exactly (same semantics, zero per-iteration round trips)."""
    p = random_dense_lp(30, 70, seed=13)
    rf = solve(p, backend=BACKEND, fused_loop=True)
    rl = solve(p, backend=BACKEND, fused_loop=False)
    assert rf.status == rl.status == Status.OPTIMAL
    assert rf.iterations == rl.iterations
    assert rf.objective == rl.objective
    assert len(rf.history) == rf.iterations
    assert rf.history[-1].rel_gap <= 1e-8


def test_drive_phase_plan_status_mapping():
    """The shared multi-phase segment driver must terminate with the same
    status semantics as the fused loop: OPTIMAL passes through, RUNNING at
    the budget maps to ITERATION_LIMIT."""
    import jax.numpy as jnp

    from distributedlpsolver_tpu.ipm import core

    calls = []

    def make_run_seg(bound):
        def run_seg(carry, stop):
            st, it, reg, bad, status, buf, best, since = carry
            calls.append((int(it), stop))
            new_it = jnp.minimum(jnp.asarray(stop, jnp.int32), bound)
            # pretend we converge at iteration >= 5
            new_status = jnp.where(
                new_it >= 5, core.STATUS_OPTIMAL, core.STATUS_RUNNING
            )
            carry = (st, new_it, reg, bad, new_status, buf, best, since)
            return carry, core.pack_segment_meta(carry)

        return run_seg

    state = jnp.zeros(3)
    reg0 = jnp.asarray(1e-10, jnp.float64)
    buf_cap = 8
    phases = [(make_run_seg, 0, 0.0, 2, "f64")]
    st, it, status, buf, reg_out = core.drive_phase_plan(
        phases, state, reg0, 20, buf_cap, jnp.float64
    )
    assert float(reg_out) == float(reg0)  # reg threaded out of the carry
    assert int(status) == core.STATUS_OPTIMAL
    assert it >= 5
    # never-converging phase hits the budget -> MAXITER
    def make_run_seg2(bound):
        def run_seg(carry, stop):
            st, it, reg, bad, status, buf, best, since = carry
            carry = (
                st, jnp.asarray(stop, jnp.int32), reg, bad,
                jnp.asarray(core.STATUS_RUNNING, jnp.int32), buf, best, since,
            )
            return carry, core.pack_segment_meta(carry)

        return run_seg

    st, it, status, buf, _ = core.drive_phase_plan(
        [(make_run_seg2, 0, 0.0, 4, "f64")], state, reg0, 12, buf_cap, jnp.float64
    )
    assert int(status) == core.STATUS_MAXITER and it == 12


def test_seg_open_caps():
    from distributedlpsolver_tpu.ipm import core

    # auto mode: tiny per-iteration estimate caps at SEG_OPEN_CAP
    assert core.seg_open(None, 1e-6) == core.SEG_OPEN_CAP
    # big per-iteration estimate: few iterations per segment
    assert core.seg_open(None, 7.5) == 2
    # explicit segment_iters is a hard cap
    assert core.seg_open(8, 1e-6) == 8
    assert core.seg_open(8, 7.5) == 2
