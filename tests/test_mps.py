"""MPS reader/writer tests: hand-written fixtures with known semantics plus
write→read round-trips on random general LPs (SURVEY.md §4 unit plan)."""

import numpy as np
import pytest
import scipy.sparse as sp

from distributedlpsolver_tpu.io import read_mps, read_mps_string, write_mps
from distributedlpsolver_tpu.models import random_general_lp

TINY = """\
* tiny test problem
NAME          TINY
ROWS
 N  COST
 L  LIM1
 G  LIM2
 E  MYEQN
COLUMNS
    X1        COST         1.0   LIM1         1.0
    X1        LIM2         1.0
    X2        COST         2.0   LIM1         1.0
    X2        MYEQN       -1.0
    X3        COST        -1.0   MYEQN        1.0
RHS
    RHS1      LIM1         4.0   LIM2         1.0
    RHS1      MYEQN        7.0
BOUNDS
 UP BND1      X1           4.0
 LO BND1      X2          -1.0
ENDATA
"""


class TestReader:
    def test_tiny(self):
        p = read_mps_string(TINY)
        assert p.name == "TINY"
        assert p.shape == (3, 3)
        np.testing.assert_allclose(p.c, [1.0, 2.0, -1.0])
        A = np.asarray(p.A)
        np.testing.assert_allclose(
            A, [[1.0, 1.0, 0.0], [1.0, 0.0, 0.0], [0.0, -1.0, 1.0]]
        )
        np.testing.assert_allclose(p.rlb, [-np.inf, 1.0, 7.0])
        np.testing.assert_allclose(p.rub, [4.0, np.inf, 7.0])
        np.testing.assert_allclose(p.lb, [0.0, -1.0, 0.0])
        np.testing.assert_allclose(p.ub, [4.0, np.inf, np.inf])

    def test_objective_constant_and_maximize(self):
        text = """\
NAME X
OBJSENSE
    MAX
ROWS
 N obj
 L r1
COLUMNS
    x obj 3.0 r1 1.0
RHS
    RHS obj 5.0 r1 10.0
ENDATA
"""
        p = read_mps_string(text)
        # RHS 5.0 on the obj row ⇒ constant −5, so this is MAX 3x − 5,
        # stored as MIN −3x + 5.
        np.testing.assert_allclose(p.c, [-3.0])
        assert p.c0 == 5.0
        assert p.maximize

    def test_ranges(self):
        text = """\
NAME R
ROWS
 N obj
 L l1
 G g1
 E e1
 E e2
COLUMNS
    x obj 1.0 l1 1.0
    x g1 1.0 e1 1.0
    x e2 1.0
RHS
    R l1 10.0 g1 2.0
    R e1 5.0 e2 5.0
RANGES
    RNG l1 4.0 g1 3.0
    RNG e1 2.0 e2 -2.0
ENDATA
"""
        p = read_mps_string(text)
        np.testing.assert_allclose(p.rlb, [6.0, 2.0, 5.0, 3.0])
        np.testing.assert_allclose(p.rub, [10.0, 5.0, 7.0, 5.0])

    def test_negative_up_bound_quirk(self):
        text = """\
NAME Q
ROWS
 N obj
 E e1
COLUMNS
    x obj 1.0 e1 1.0
RHS
    R e1 1.0
BOUNDS
 UP B x -2.0
ENDATA
"""
        p = read_mps_string(text)
        assert p.ub[0] == -2.0
        assert p.lb[0] == -np.inf  # classic quirk fired

    def test_integer_markers_relaxed(self):
        text = """\
NAME I
ROWS
 N obj
 G r
COLUMNS
    MARKER                 'MARKER'                 'INTORG'
    xi obj 1.0 r 1.0
    MARKER                 'MARKER'                 'INTEND'
    xc obj 1.0 r 1.0
RHS
    R r 2.0
ENDATA
"""
        p = read_mps_string(text)
        assert p.integer_cols == [0]
        assert p.shape == (1, 2)

    def test_free_extra_n_rows_dropped(self):
        text = """\
NAME F
ROWS
 N obj
 N freerow
 E e1
COLUMNS
    x obj 1.0 freerow 9.0
    x e1 1.0
RHS
    R e1 1.0
ENDATA
"""
        p = read_mps_string(text)
        assert p.shape == (1, 1)

    def test_duplicate_entries_summed(self):
        text = """\
NAME D
ROWS
 N obj
 E e1
COLUMNS
    x obj 1.0 e1 1.0
    x e1 2.0
RHS
    R e1 3.0
ENDATA
"""
        p = read_mps_string(text)
        assert np.asarray(p.A)[0, 0] == 3.0

    def test_sparse_output(self):
        p = read_mps_string(TINY, dense=False)
        assert sp.issparse(p.A)


class TestRoundTrip:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_write_read_roundtrip(self, tmp_path, seed):
        p = random_general_lp(8, 13, seed=seed)
        path = tmp_path / "rt.mps"
        write_mps(p, path)
        q = read_mps(path)
        np.testing.assert_allclose(q.c, p.c, rtol=1e-15)
        np.testing.assert_allclose(np.asarray(q.A), np.asarray(p.A), rtol=1e-15)
        np.testing.assert_allclose(q.rlb, p.rlb, rtol=1e-12)
        np.testing.assert_allclose(q.rub, p.rub, rtol=1e-12)
        np.testing.assert_allclose(q.lb, p.lb, rtol=1e-15)
        np.testing.assert_allclose(q.ub, p.ub, rtol=1e-15)

    def test_gzip_roundtrip(self, tmp_path):
        import gzip

        p = random_general_lp(5, 7, seed=3)
        path = tmp_path / "rt.mps"
        write_mps(p, path)
        gz = tmp_path / "rt.mps.gz"
        with open(path, "rb") as f, gzip.open(gz, "wb") as g:
            g.write(f.read())
        q = read_mps(gz)
        np.testing.assert_allclose(np.asarray(q.A), np.asarray(p.A))


class TestReviewRegressions:
    """Regressions for the round-trip/parsing bugs found in code review."""

    def test_zero_column_survives_roundtrip(self, tmp_path):
        import numpy as np
        from distributedlpsolver_tpu.models import LPProblem

        p = LPProblem(
            c=np.array([0.0, 1.0]),
            A=np.array([[0.0, 1.0]]),  # col 0 appears nowhere
            rlb=np.array([1.0]),
            rub=np.array([1.0]),
            lb=np.zeros(2),
            ub=np.array([5.0, np.inf]),
        )
        path = tmp_path / "zero.mps"
        write_mps(p, path)
        q = read_mps(path)
        assert q.n == 2
        np.testing.assert_allclose(q.ub, p.ub)

    def test_obj_name_collision(self, tmp_path):
        import numpy as np
        from distributedlpsolver_tpu.models import LPProblem

        p = LPProblem(
            c=np.array([2.0]), A=np.array([[1.0]]),
            rlb=np.array([-np.inf]), rub=np.array([3.0]),
            lb=np.zeros(1), ub=np.array([np.inf]),
            row_names=["OBJ"], col_names=["x"],
        )
        path = tmp_path / "obj.mps"
        write_mps(p, path)
        q = read_mps(path)
        assert q.m == 1
        np.testing.assert_allclose(q.c, [2.0])
        np.testing.assert_allclose(np.asarray(q.A), [[1.0]])
        np.testing.assert_allclose(q.rub, [3.0])

    def test_coefficient_on_row_named_marker(self):
        import numpy as np

        text = """\
NAME M
ROWS
 N obj
 E MARKER
COLUMNS
    X1 MARKER 2.0
    X1 obj 1.0
RHS
    R MARKER 4.0
ENDATA
"""
        p = read_mps_string(text)
        assert np.asarray(p.A)[0, 0] == 2.0
        assert p.rlb[0] == 4.0

    def test_rhs_setname_collides_with_row(self):
        import numpy as np

        # RHS set named like a row: parity rule must still parse correctly.
        text = """\
NAME C
ROWS
 N obj
 E r1
 E r2
COLUMNS
    x obj 1.0 r1 1.0
    x r2 1.0
RHS
    r1 r1 5.0 r2 6.0
ENDATA
"""
        p = read_mps_string(text)
        np.testing.assert_allclose(p.rlb, [5.0, 6.0])

    def test_free_row_emitted_as_n_and_dropped(self, tmp_path):
        import numpy as np
        from distributedlpsolver_tpu.models import LPProblem

        p = LPProblem(
            c=np.array([1.0]), A=np.array([[1.0], [2.0]]),
            rlb=np.array([-np.inf, 1.0]), rub=np.array([np.inf, 1.0]),
            lb=np.zeros(1), ub=np.array([np.inf]),
        )
        path = tmp_path / "free.mps"
        write_mps(p, path)
        q = read_mps(path)
        # free row dropped, feasible set preserved
        assert q.m == 1
        np.testing.assert_allclose(q.rlb, [1.0])


class TestAdversarial:
    """Adversarial parser inputs beyond the two hand-written fixtures:
    fixed-format layout quirks, RANGES sign conventions per row type,
    duplicate entries in every section, and a writer-driven fuzz
    round-trip (review "What's missing" #5)."""

    def test_fixed_format_column_layout(self):
        # Genuine fixed-column layout (fields at columns 2/5/15/25/40/50,
        # wide name fields padded with blanks) plus trailing whitespace —
        # must tokenize identically to free format.
        text = (
            "NAME          FIXED\n"
            "ROWS\n"
            " N  COST\n"
            " L  LIM1      \n"
            " E  EQ2\n"
            "COLUMNS\n"
            "    X1        COST            1.0   LIM1            2.0\n"
            "    X1        EQ2             1.0\n"
            "    X2        COST            3.0   EQ2             1.0   \n"
            "RHS\n"
            "    RHS       LIM1            4.0   EQ2             5.0\n"
            "BOUNDS\n"
            " UP BND       X1              9.0\n"
            "ENDATA\n"
        )
        p = read_mps_string(text)
        assert p.shape == (2, 2)
        np.testing.assert_allclose(np.asarray(p.A), [[2.0, 0.0], [1.0, 1.0]])
        np.testing.assert_allclose(p.c, [1.0, 3.0])
        np.testing.assert_allclose(p.ub, [9.0, np.inf])

    def test_fortran_d_exponents(self):
        # Old fixed-format Netlib files carry Fortran D exponents in
        # values; every value-bearing section must accept them.
        text = """\
NAME D
ROWS
 N obj
 L l1
COLUMNS
    x obj 1.5D+01 l1 -2.5d-01
RHS
    R l1 1.0D2
RANGES
    RNG l1 4.0D0
BOUNDS
 UP B x 1.0D+03
ENDATA
"""
        p = read_mps_string(text)
        assert p.c[0] == 15.0
        assert np.asarray(p.A)[0, 0] == -0.25
        np.testing.assert_allclose([p.rlb[0], p.rub[0]], [96.0, 100.0])
        assert p.ub[0] == 1000.0

    def test_ranges_sign_conventions_all_row_types(self):
        # |r| on L and G regardless of sign; signed convention on E;
        # zero range on E collapses to the equality itself.
        text = """\
NAME R
ROWS
 N obj
 L l1
 L l2
 G g1
 G g2
 E e0
COLUMNS
    x obj 1.0 l1 1.0
    x l2 1.0 g1 1.0
    x g2 1.0 e0 1.0
RHS
    R l1 10.0 l2 10.0
    R g1 2.0 g2 2.0
    R e0 5.0
RANGES
    RNG l1 4.0 l2 -4.0
    RNG g1 3.0 g2 -3.0
    RNG e0 0.0
ENDATA
"""
        p = read_mps_string(text)
        np.testing.assert_allclose(p.rlb, [6.0, 6.0, 2.0, 2.0, 5.0])
        np.testing.assert_allclose(p.rub, [10.0, 10.0, 5.0, 5.0, 5.0])

    def test_ranges_on_objective_and_free_rows_ignored(self):
        # A range on an N row has no constraint to widen; classic parsers
        # drop it like RHS entries on free rows — ours must not crash.
        text = """\
NAME N
ROWS
 N obj
 N free2
 L l1
COLUMNS
    x obj 1.0 l1 1.0
RHS
    R l1 8.0
RANGES
    RNG obj 3.0 free2 2.0
    RNG l1 2.0
ENDATA
"""
        p = read_mps_string(text)
        np.testing.assert_allclose([p.rlb[0], p.rub[0]], [6.0, 8.0])

    def test_duplicate_entries_within_one_line_summed(self):
        text = """\
NAME D2
ROWS
 N obj
 E e1
COLUMNS
    x obj 1.0 e1 1.0 e1 2.0
    x obj 0.5
RHS
    R e1 3.0
ENDATA
"""
        p = read_mps_string(text)
        assert np.asarray(p.A)[0, 0] == 3.0  # duplicates summed
        assert p.c[0] == 1.5  # objective duplicates summed too

    def test_duplicate_rhs_ranges_bounds_last_wins(self):
        # Pins the overwrite semantics for duplicate RHS/RANGES/BOUNDS
        # entries (classic parsers disagree; ours is last-wins).
        text = """\
NAME D3
ROWS
 N obj
 L l1
COLUMNS
    x obj 1.0 l1 1.0
RHS
    R l1 5.0 l1 9.0
RANGES
    RNG l1 2.0 l1 4.0
BOUNDS
 UP B x 7.0
 UP B x 3.0
ENDATA
"""
        p = read_mps_string(text)
        np.testing.assert_allclose([p.rlb[0], p.rub[0]], [5.0, 9.0])
        assert p.ub[0] == 3.0

    @pytest.mark.parametrize("seed", range(6))
    def test_fuzz_roundtrip_via_writer(self, tmp_path, seed):
        # Writer-driven fuzz: random general LPs at random shapes (mixed
        # row senses, ranges, boxed/free/one-sided columns) must survive
        # write→read bit-exactly on every field the format carries.
        rng = np.random.default_rng(1000 + seed)
        m = int(rng.integers(2, 20))
        n = int(rng.integers(2, 30))
        p = random_general_lp(m, n, seed=seed)
        path = tmp_path / f"fuzz{seed}.mps"
        write_mps(p, path)
        q = read_mps(path)
        assert q.shape == p.shape
        np.testing.assert_allclose(q.c, p.c, rtol=1e-15)
        np.testing.assert_allclose(
            np.asarray(q.A), np.asarray(p.A), rtol=1e-15
        )
        np.testing.assert_allclose(q.rlb, p.rlb, rtol=1e-12)
        np.testing.assert_allclose(q.rub, p.rub, rtol=1e-12)
        np.testing.assert_allclose(q.lb, p.lb, rtol=1e-15)
        np.testing.assert_allclose(q.ub, p.ub, rtol=1e-15)


def test_objsense_max_round_trip(tmp_path):
    """A maximize problem must survive write->read: OBJSENSE MAX emitted,
    stored-minimized c/c0 identical, and the sense-corrected objective of
    a solve matches."""
    import dataclasses

    from distributedlpsolver_tpu.models.generators import random_general_lp

    p = random_general_lp(8, 14, seed=3)
    pm = dataclasses.replace(p, maximize=True, c=-p.c, c0=1.5)
    path = tmp_path / "maxp.mps"
    write_mps(pm, path)
    assert "OBJSENSE" in path.read_text()
    q = read_mps(path)
    assert q.maximize is True
    np.testing.assert_allclose(q.c, pm.c)
    assert q.c0 == pytest.approx(pm.c0)


def test_columns_odd_fields_clear_error(tmp_path):
    bad = """NAME T
ROWS
 N  OBJ
 E  R1
COLUMNS
    X  OBJ  1.0  R1
RHS
    RHS1  R1  1.0
ENDATA
"""
    path = tmp_path / "bad.mps"
    path.write_text(bad)
    with pytest.raises(ValueError, match="COLUMNS line has"):
        read_mps(path)
