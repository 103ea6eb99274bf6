"""Golden MPS fixtures: exact parse checks + hand-derived optima.

review round 1 item 7: real Netlib files are unreachable (zero
egress), so these vendored hand-written files carry the real-world
quirks instead — RANGES on all three row types (incl. negative range on
an E row), the negative-UP lower-bound quirk, MI/FX bounds, extra free N
rows, objective-row RHS constants, duplicate COLUMNS entries, OBJSENSE
section-body form — with optima derived BY HAND (independent of any
solver), plus a ≥10 MB file emitted by an independent writer (not
io/mps.py's) for parser performance and cross-writer compatibility.
"""

import io
import os
import time

import numpy as np
import pytest
import scipy.sparse as sp

from distributedlpsolver_tpu.io import read_mps
from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.ipm.state import Status

from tests.oracle import highs_on_general

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


class TestQuirksFixture:
    """quirks.mps — feasible set derivation (all by hand):

    rows   LIM1 (L, rhs 4, range 4)   → X1+X2 ∈ [0, 4]
           LIM2 (G, rhs 0, range 3)   → X1+X4 ∈ [0, 3]
           EQ1  (E, rhs 2, range 1.5) → X2+X3 ∈ [2, 3.5]
           EQ2  (E, rhs 3, range -1)  → X3+X4 ∈ [2, 3]
    bounds X1 ≤ -1 (UP −1 on default lb ⇒ lb −∞), X2 ∈ (−∞, 5],
           X3 ≥ 0, X4 = 1.5 (FX)
    obj    min X1 + 2·X2 + X3 + 10   (X3's two 0.5 entries sum; RHS −10
           on COST ⇒ constant +10)

    X4 = 1.5 ⇒ X3 ∈ [0.5, 1.5]; LIM2 ⇒ X1 ∈ [−1.5, −1]. On LIM1's lower
    face X1 = −X2 the objective is X2 + X3 + 10 ≥ EQ1's lower bound 2
    + 10 = 12, attained along the segment X2 = 2 − X3, X2 ∈ [1, 1.5]
    (X1 = −X2, X3 = 2 − X2). The VALUE 12.0 is unique; the optimal set
    is that segment — HiGHS returns the vertex X2 = 1.5, an IPM returns
    the segment's analytic center, so only the vertex oracle asserts x.
    """

    OPT = 12.0
    X_OPT = np.array([-1.5, 1.5, 0.5, 1.5])  # the HiGHS vertex

    def parse(self):
        return read_mps(os.path.join(FIXTURES, "quirks.mps"))

    def test_exact_parse(self):
        p = self.parse()
        assert p.name == "QUIRKS"
        assert p.row_names == ["LIM1", "LIM2", "EQ1", "EQ2"]  # FREEROW dropped
        assert p.col_names == ["X1", "X2", "X3", "X4"]
        np.testing.assert_allclose(p.c, [1.0, 2.0, 1.0, 0.0])  # 0.5+0.5 summed
        assert p.c0 == 10.0
        A = np.asarray(p.A.todense() if sp.issparse(p.A) else p.A)
        np.testing.assert_allclose(
            A,
            [[1, 1, 0, 0],
             [1, 0, 0, 1],
             [0, 1, 1, 0],
             [0, 0, 1, 1]],
        )
        np.testing.assert_allclose(p.rlb, [0.0, 0.0, 2.0, 2.0])
        np.testing.assert_allclose(p.rub, [4.0, 3.0, 3.5, 3.0])
        np.testing.assert_allclose(p.lb, [-np.inf, -np.inf, 0.0, 1.5])
        np.testing.assert_allclose(p.ub, [-1.0, 5.0, np.inf, 1.5])
        assert not p.maximize

    def test_highs_agrees_with_hand_optimum(self):
        p = self.parse()
        ref = highs_on_general(p)  # oracle solves min cᵀx without c0
        assert ref.fun + p.c0 == pytest.approx(self.OPT, abs=1e-8)
        np.testing.assert_allclose(ref.x, self.X_OPT, atol=1e-8)

    def test_solver_reaches_hand_optimum(self):
        p = self.parse()
        r = solve(p, backend="cpu")
        assert r.status == Status.OPTIMAL
        assert r.objective == pytest.approx(self.OPT, abs=1e-6)
        # Any point of the optimal segment is acceptable: x lies on it iff
        # x1 = -x2, x3 = 2 - x2, x2 ∈ [1, 1.5], x4 = 1.5.
        x = r.x
        assert x[0] == pytest.approx(-x[1], abs=1e-5)
        assert x[2] == pytest.approx(2.0 - x[1], abs=1e-5)
        assert 1.0 - 1e-5 <= x[1] <= 1.5 + 1e-5
        assert x[3] == pytest.approx(1.5, abs=1e-7)


class TestMaximizeFixture:
    """maximize.mps — max 3A+5B, 2A+4B ≤ 10, A ≥ −2, A∈[0,3], B∈[0,2].

    A yields 1.5/unit-capacity vs B's 1.25 ⇒ saturate A = 3 (capacity 6),
    B = (10−6)/4 = 1. Optimum 3·3 + 5·1 = 14.0.
    """

    OPT = 14.0

    def test_parse_and_optima(self):
        p = read_mps(os.path.join(FIXTURES, "maximize.mps"))
        assert p.maximize
        np.testing.assert_allclose(p.rlb, [-np.inf, -2.0])
        np.testing.assert_allclose(p.rub, [10.0, np.inf])
        ref = highs_on_general(p)  # minimized internal form
        assert -ref.fun == pytest.approx(self.OPT, abs=1e-8)
        r = solve(p, backend="cpu")
        assert r.status == Status.OPTIMAL
        assert r.objective == pytest.approx(self.OPT, abs=1e-6)
        np.testing.assert_allclose(r.x, [3.0, 1.0], atol=1e-5)


def _emit_big_mps(fh, m_blocks: int, rows_per: int, cols_per: int, seed: int):
    """An INDEPENDENT MPS emitter (deliberately not io/mps.write_mps):
    fixed-format-ish columns, varying pair counts per line, interleaved
    comments, tab separators, and an RHS set name — the formatting
    variety a parser meets in the wild."""
    rng = np.random.default_rng(seed)
    fh.write("* big generated instance\nNAME BIGGEN\nROWS\n N  obj\n")
    for b in range(m_blocks):
        for i in range(rows_per):
            fh.write(f" {'LG'[i % 2]}  r{b}_{i}\n")
    fh.write("COLUMNS\n")
    for b in range(m_blocks):
        if b % 7 == 0:
            fh.write(f"* block {b}\n")
        for j in range(cols_per):
            name = f"x{b}_{j}"
            fh.write(f"    {name}\tobj\t{rng.uniform(0.5, 2.0):.6f}\n")
            # two constraint entries, sometimes paired on one line
            i1, i2 = rng.integers(0, rows_per, size=2)
            v1, v2 = rng.uniform(-2, 2, size=2)
            if j % 3 == 0:
                fh.write(f"    {name}  r{b}_{i1}  {v1:.6f}  r{b}_{i2}  {v2:.6f}\n")
            else:
                fh.write(f"    {name}  r{b}_{i1}  {v1:.6f}\n")
                fh.write(f"    {name}  r{b}_{i2}  {v2:.6f}\n")
    fh.write("RHS\n")
    for b in range(m_blocks):
        for i in range(rows_per):
            fh.write(f"    rhs\tr{b}_{i}\t{rng.uniform(1.0, 5.0):.6f}\n")
    fh.write("BOUNDS\n")
    for b in range(0, m_blocks, 3):
        fh.write(f" UP bnd  x{b}_0  {rng.uniform(3.0, 9.0):.6f}\n")
    fh.write("ENDATA\n")


def test_large_file_parse_performance(tmp_path):
    # ≥10 MB emitted by the independent writer above; the parser must get
    # through it in well under a minute and land exact dimensions.
    path = tmp_path / "big.mps"
    m_blocks, rows_per, cols_per = 560, 40, 220
    with open(path, "w") as fh:
        _emit_big_mps(fh, m_blocks, rows_per, cols_per, seed=0)
    size = os.path.getsize(path)
    assert size >= 10 * 1024 * 1024, f"fixture too small: {size} bytes"
    t0 = time.perf_counter()
    p = read_mps(path)
    dt = time.perf_counter() - t0
    assert p.shape == (m_blocks * rows_per, m_blocks * cols_per)
    assert sp.issparse(p.A)
    assert p.A.nnz > 0
    assert dt < 60.0, f"parse took {dt:.1f}s"


def test_independent_writer_round_trips_through_ours(tmp_path):
    # Parse an independently-emitted small instance, write it with OUR
    # writer, re-read, and require identical problem data.
    buf = io.StringIO()
    _emit_big_mps(buf, 2, 8, 12, seed=7)
    buf.seek(0)
    from distributedlpsolver_tpu.io import read_mps as _read
    from distributedlpsolver_tpu.io import write_mps

    p1 = _read(buf)
    path = tmp_path / "rt.mps"
    write_mps(p1, path)
    p2 = _read(path)
    np.testing.assert_allclose(p1.c, p2.c)
    A1 = np.asarray(p1.A.todense() if sp.issparse(p1.A) else p1.A)
    A2 = np.asarray(p2.A.todense() if sp.issparse(p2.A) else p2.A)
    np.testing.assert_allclose(A1, A2)
    np.testing.assert_allclose(p1.rlb, p2.rlb)
    np.testing.assert_allclose(p1.rub, p2.rub)
    np.testing.assert_allclose(p1.lb, p2.lb)
    np.testing.assert_allclose(p1.ub, p2.ub)
