"""Batched-solver tests (BASELINE.json:11 workload, SURVEY.md §4
"vmap'd solve equals per-problem loop solve")."""

import jax
import numpy as np
import pytest

from distributedlpsolver_tpu.backends.batched import solve_batched
from distributedlpsolver_tpu.ipm import Status, solve
from distributedlpsolver_tpu.models.generators import random_batched_lp
from distributedlpsolver_tpu.parallel import make_mesh
from tests.oracle import highs_on_general


@pytest.fixture(scope="module")
def batch():
    return random_batched_lp(12, 16, 40, seed=3)


@pytest.fixture(scope="module")
def result(batch):
    return solve_batched(batch)


def test_all_converge(batch, result):
    assert result.n_optimal == batch.batch
    assert (result.rel_gap <= 1e-8).all()
    assert (result.pinf <= 1e-7).all()


def test_matches_per_problem_solve(batch, result):
    for k in [0, 4, 9]:
        r = solve(batch.problem(k), backend="tpu")
        assert r.status == Status.OPTIMAL
        assert result.objective[k] == pytest.approx(r.objective, rel=1e-9, abs=1e-9)


def test_matches_highs(batch, result):
    for k in [1, 7]:
        hi = highs_on_general(batch.problem(k))
        assert result.objective[k] == pytest.approx(hi.fun, rel=1e-6)


def test_ragged_convergence_masking(batch, result):
    """Problems converge at different iteration counts; each must report
    its own count (masking, not a common early exit)."""
    assert result.iterations.min() >= 1
    assert len(set(result.iterations.tolist())) > 1  # genuinely ragged


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_batch_sharded_over_mesh(batch):
    """DP in this domain: shard the batch axis; results must match the
    unsharded solve exactly (SURVEY.md §2.2)."""
    mesh = make_mesh(axis_names=("batch",))
    unsharded = solve_batched(batch)
    # pad batch 12 → 16 not needed: 12 not divisible by 8 → use batch of 16
    b16 = random_batched_lp(16, 16, 40, seed=3)
    r_mesh = solve_batched(b16, mesh=mesh)
    r_ref = solve_batched(b16)
    assert r_mesh.n_optimal == 16
    np.testing.assert_allclose(r_mesh.objective, r_ref.objective, rtol=1e-9)
    with pytest.raises(ValueError):
        solve_batched(batch, mesh=mesh)  # 12 % 8 != 0


def test_pcg_middle_phase_full_tol(batch):
    """solve_mode="pcg" adds the full-tolerance PCG middle phase (f32
    preconditioner + f64 matrix-free CG). Every member must still reach
    1e-8 with objectives matching the direct path."""
    r_pcg = solve_batched(batch, solve_mode="pcg")
    r_dir = solve_batched(batch)
    assert r_pcg.n_optimal == len(r_pcg.status)
    assert (r_pcg.rel_gap <= 1e-8).all() and (r_pcg.pinf <= 1e-8).all()
    np.testing.assert_allclose(r_pcg.objective, r_dir.objective, rtol=1e-8)


def test_pcg_phase_keeps_optimal_members_settled():
    """Members a full-tol phase proved OPTIMAL must NOT re-enter the next
    phase: the keep-optimal carry reset leaves them inactive and settled
    (this boundary is the PCG middle phase's whole payoff), while the
    provisional reset re-activates everyone."""
    import jax.numpy as jnp
    import distributedlpsolver_tpu.backends.batched as bt

    B = 6
    states = jnp.zeros((B, 3))  # any pytree-of-arrays works for the reset
    iters = jnp.arange(B, dtype=jnp.int32)
    status = jnp.asarray(
        [bt._OPTIMAL, bt._RUNNING, bt._OPTIMAL, bt._STALL, bt._NUMERR,
         bt._RUNNING], jnp.int32
    )
    carry = bt._fresh_batch_carry(
        states, iters, B, 1e-10, jnp.float64, status=status
    )
    active, new_status = np.asarray(carry[1]), np.asarray(carry[5])
    # optimal members settled+inactive; everyone else re-activated RUNNING
    np.testing.assert_array_equal(
        active, [False, True, False, True, True, True]
    )
    np.testing.assert_array_equal(
        new_status,
        [bt._OPTIMAL, bt._RUNNING, bt._OPTIMAL, bt._RUNNING, bt._RUNNING,
         bt._RUNNING],
    )
    np.testing.assert_array_equal(np.asarray(carry[6]), np.asarray(iters))
    # provisional reset (status=None): everyone re-enters
    carry2 = bt._fresh_batch_carry(states, iters, B, 1e-10, jnp.float64)
    assert np.asarray(carry2[1]).all()
    assert (np.asarray(carry2[5]) == bt._RUNNING).all()


def test_final_phase_compaction_matches_plain():
    # Per-member column scaling staggers convergence (iters ~9..29), so
    # the segmented drive's actives fall below half the program size and
    # compaction shrinks 64 -> 32 while stragglers finish. The compacted
    # path must agree with the unsegmented whole-batch solve
    # member-for-member (same math, smaller programs) — including on the
    # members that do NOT reach optimality.
    from unittest import mock

    from distributedlpsolver_tpu.backends import batched as batched_mod
    from distributedlpsolver_tpu.models.generators import BatchedLP

    b = random_batched_lp(64, 16, 40, seed=11)
    rng = np.random.default_rng(0)
    A = np.asarray(b.A) * 10.0 ** rng.uniform(-1, 1, (64, 1, 40))
    b2 = BatchedLP(c=b.c, A=A, b=b.b, name="staggered")
    r_plain = solve_batched(b2, segment_iters=0)
    calls = []
    orig = batched_mod._compact_gather
    with mock.patch.object(
        batched_mod, "_compact_gather",
        side_effect=lambda *a, **k: calls.append(a[3]) or orig(*a, **k),
    ):
        r_comp = solve_batched(b2, segment_iters=2)
    assert calls, "compaction never triggered — the staggered batch no longer staggers"
    assert all(s <= 32 for s in calls)
    assert r_comp.n_optimal == r_plain.n_optimal
    np.testing.assert_allclose(r_comp.objective, r_plain.objective, rtol=1e-6)


# ---------------------------------------------------------------------------
# Every route returns each member's duals: x and y certify in float64.

def _standard_form_batch(n_lps, seed=2**31 + 5):
    """``n_lps`` LPs of 12 x 40 from the lp128x512 configuration's
    generator (Gaussian A, b = A·x0, c = Aᵀy0 + s0), and their batch."""
    import os

    from benchmark import spec
    from distributedlpsolver_tpu.models.generators import BatchedLP

    build = spec.load_module(os.path.join(
        spec.ROOT, "benchmark", "generators", "standard_form.py")).build
    params = {"m": 12, "n": 40, "base_seed": 0}
    problems = [build(params, seed, k) for k in range(n_lps)]
    return problems, BatchedLP(
        c=np.stack([p.c for p in problems]),
        A=np.stack([p.A for p in problems]),
        b=np.stack([p.rlb for p in problems]), name="sf")


def _lp128x512_limits():
    import json
    import os

    from benchmark import spec

    with open(os.path.join(spec.ROOT, "benchmark", "configs",
                           "lp128x512.json")) as fh:
        return json.load(fh)["limits"]


def _demote(monkeypatch, members):
    """The whole-batch program reports ``members`` unfinished, with their
    duals halved, so the solo clean-up re-solves them and only the
    clean-up's own y certifies."""
    import jax.numpy as jnp

    from distributedlpsolver_tpu.backends import batched

    run = batched._solve_batched_jit

    def demoted(*a, **k):
        out = run(*a, **k)
        idx = jnp.asarray(members)
        states = out[0]._replace(y=out[0].y.at[idx].multiply(0.5))
        status = out[1].at[idx].set(batched._MAXITER)
        return (states, status) + tuple(out[2:])

    monkeypatch.setattr(batched, "_solve_batched_jit", demoted)


def _spy_cleanup(monkeypatch):
    """{member: iterations} of every solo clean-up solve."""
    import re

    from distributedlpsolver_tpu.ipm import driver

    solo, solve_ = {}, driver.solve

    def spy(inf, *a, **k):
        r = solve_(inf, *a, **k)
        chunk0, i = re.search(r"(?:\[(\d+):\d+\])?\[(\d+)\]$",
                              inf.name).groups()
        solo[int(chunk0 or 0) + int(i)] = int(r.iterations)
        return r

    monkeypatch.setattr(driver, "solve", spy)
    return solo


# route: (LPs, solve_batched options, members the loop is made to leave
# unfinished). "segmented" is the chip's route: host segments, compaction
# (64 -> 32 once the first 10 iterations settle half the batch) and tail
# extraction.
ROUTES = {
    "unchunked": (8, {}, ()),
    "chunked": (8, {"chunk": 4}, ()),
    "cleanup": (8, {}, (2, 5)),
    "segmented": (64, {"segment_iters": 1}, ()),
}


@pytest.mark.parametrize("route", list(ROUTES))
def test_every_member_certifies_with_its_own_duals(route, monkeypatch):
    """Each member's x and y form a float64 certificate on its own data
    (benchmark/references/kkt.py, under the lp128x512 limits) and its
    objective matches HiGHS's; the call's record carries its spans and
    counters."""
    from scipy.optimize import linprog

    from benchmark.references import kkt
    from distributedlpsolver_tpu.obs import trace as obs_trace

    n_lps, opts, demoted = ROUTES[route]
    problems, batch = _standard_form_batch(n_lps)
    if demoted:
        _demote(monkeypatch, demoted)
    solo = _spy_cleanup(monkeypatch)
    r = solve_batched(batch, tol=1e-8, **opts)
    limits = _lp128x512_limits()
    assert r.y.shape == (n_lps, 12)
    for k, p in enumerate(problems):
        assert r.status[k] == Status.OPTIMAL, k
        nums = kkt.check(p, {"x": r.x[k], "y": r.y[k]})
        assert all(v <= limits[n] for n, v in nums.items()), (k, nums)
        hi = linprog(p.c, A_eq=p.A, b_eq=p.rlb, method="highs")
        assert r.objective[k] == pytest.approx(hi.fun, rel=1e-7), k

    rec = obs_trace.recent_solves()[-1]
    assert rec["counters"] == r.counters
    c = r.counters
    spans = {"batch.prepare", "batch.loop", "batch.readback",
             "batch.cleanup"} | ({"batch.compact"} if c["compactions"]
                                 else set())
    assert spans <= set(rec["spans"])
    assert c["chunks"] == (2 if route == "chunked" else 1)
    assert c["cleanup_lps"] == len(solo)
    assert set(demoted) <= set(solo)
    before = r.iterations.copy()
    for k, its in solo.items():
        before[k] -= its
    assert c["member_iters"] == before.sum()
    assert 0 < c["member_iters"] <= c["lane_iters"]
    if route == "segmented":
        assert c["compactions"] >= 1
