"""The benchmark harness's own arithmetic, on the CPU: generators, the
float64 references, metric readers, the trace reduction, the no-chip
refusal and auto's route for the pds10 configuration."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import run, spec, trace
from benchmark.references import kkt

REPO = spec.ROOT
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tiny.xplane.pb")


def _config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as fh:
        return json.load(fh)


def _generator(name):
    return spec.load_module(
        os.path.join(REPO, "benchmark", "generators", name + ".py"))


TINY = {"commodities": 3, "nodes": 12, "arcs": 30, "joint_arcs": 6,
        "supply_arcs": 2, "max_degree": 10}


def test_generator_is_a_function_of_seed_and_k():
    build = _generator("pds_network").build
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    a, b, c = build(TINY, big, 0), build(TINY, big, 0), build(TINY, big, 1)
    for f in ("c", "rlb", "rub", "lb", "ub"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.c, c.c)
    assert a.block_structure is None
    # Feasible and bounded by construction: HiGHS finds an optimum.
    assert _highs_answer(a)["status"] == 0


def test_base_seed_gives_every_seed_the_same_lp_in_other_signs():
    build = _generator("pds_network").build
    params = dict(TINY, base_seed=0)
    a, b = build(params, 2**31 + 1, 0), build(params, 2**31 + 2, 0)
    assert not np.array_equal(a.c, b.c)
    np.testing.assert_array_equal(np.abs(a.c), np.abs(b.c))
    assert (abs(a.A) != abs(b.A)).nnz == 0
    assert (a.A != b.A).nnz > 0 and (a.ub < np.inf).any()
    assert _highs_answer(a)["objective"] == pytest.approx(
        _highs_answer(b)["objective"], rel=1e-9)


def test_other_signs_give_the_solver_the_same_work_bit_for_bit(monkeypatch):
    """Negation is exact, so the interior form and every iterate are the
    same up to sign: the same iterations and the same x and y, on the
    route auto takes on the chip (detection, the block backend)."""
    from distributedlpsolver_tpu.backends import auto
    from distributedlpsolver_tpu.ipm import solve

    choose = auto.choose_backend_name
    monkeypatch.setattr(auto, "choose_backend_name",
                        lambda inf, platform, detect=False:
                        choose(inf, "tpu", detect))
    build = _generator("pds_network").build
    params = {"commodities": 6, "nodes": 60, "arcs": 100, "joint_arcs": 30,
              "supply_arcs": 6, "max_degree": 5, "base_seed": 0}
    a, b = build(params, 2**31 + 1, 0), build(params, 2**31 + 2, 0)
    ra, rb = solve(a, backend="auto"), solve(b, backend="auto")
    assert ra.backend == rb.backend == "auto(block)"
    assert ra.status.value == rb.status.value == "optimal"
    assert ra.iterations == rb.iterations
    # Columns: the sign of c; node rows: the sign of b or of the row.
    sc = np.sign(a.c) * np.sign(b.c)
    np.testing.assert_array_equal(np.abs(ra.x), np.abs(rb.x))
    np.testing.assert_array_equal(ra.x * sc, rb.x)
    np.testing.assert_array_equal(np.abs(ra.y), np.abs(rb.y))


def test_pds_network_has_the_shape_of_a_multicommodity_flow():
    """Node rows hold an arc's tail (-1) and head (+1), or a supply arc's
    one +1, no node more than max_degree; every capacity row holds one
    +1 per commodity, on columns of one arc."""
    p = _generator("pds_network").build(TINY, 5, 0)
    K, N, E, J = 3, 12, 30, 6
    A = p.A.tocsr()
    assert A.shape == (K * N + J, K * E)
    assert set(np.unique(A.data)) <= {-1.0, 1.0}
    nnz = np.diff(A.indptr)
    assert nnz[:K * N].max() <= 10 and (nnz[K * N:] == K).all()
    for i in range(K * N, K * N + J):
        cols = A.indices[A.indptr[i]:A.indptr[i + 1]]
        assert sorted(cols // E) == list(range(K))
        assert len(set(cols % E)) == 1
    colnnz = np.diff(A[:K * N].tocsc().indptr)
    assert (colnnz == 1).sum() == K * 2 and (colnnz == 2).sum() == K * 28


def test_pds10_has_pds10s_counts_and_routes_to_block_on_the_host():
    """auto's routing runs on the host: the hint-less pds10 instance at
    its real size must be detected as 11 commodity blocks and 1,169
    linking rows, and sent to the block Schur backend (below
    _HUGE_SPARSE_ROWS, under the 2**28 block-tensor veto)."""
    from distributedlpsolver_tpu.backends.auto import choose_backend_name
    from distributedlpsolver_tpu.models.problem import to_interior_form

    cfg = _config("pds10")
    p = _generator(cfg["generator"]).build(cfg["params"], 7, 0)
    assert p.shape == (16558, 48763) and p.block_structure is None
    assert p.A.nnz == 108845
    name, hint = choose_backend_name(to_interior_form(p), "tpu", detect=True)
    assert name == "block" and hint["num_blocks"] == 11
    rb = hint["row_block"]
    assert (rb == -1).sum() == 1169
    assert (np.bincount(rb[rb >= 0]) == 1399).all()


def _tiny_lp():
    return _generator("pds_network").build(TINY, 3, 0)


def _highs_answer(p):
    from scipy.optimize import linprog

    eq = p.rlb == p.rub
    up = ~eq
    res = linprog(p.c, A_ub=p.A[up], b_ub=p.rub[up], A_eq=p.A[eq],
                  b_eq=p.rlb[eq], bounds=np.stack([p.lb, p.ub], axis=1),
                  method="highs")
    y = np.zeros(p.m)
    y[eq] = res.eqlin.marginals
    y[up] = res.ineqlin.marginals
    return {"x": res.x, "y": y, "objective": float(res.fun),
            "status": res.status}


def test_kkt_reference_accepts_an_optimum_and_rejects_faults():
    p = _tiny_lp()
    ans = _highs_answer(p)
    good = kkt.check(p, ans)
    assert max(good.values()) < 1e-7
    moved = dict(ans, x=ans["x"] + 1e-3)
    assert kkt.check(p, moved)["primal_rel"] > 1e-5
    assert kkt.check(p, dict(ans, y=ans["y"] * 1.01))["gap_rel"] > 1e-5
    assert kkt.check(p, dict(ans, y=-ans["y"]))["gap_rel"] > 1e-3


@pytest.mark.parametrize("y", [None, "short"])
def test_kkt_reference_reads_a_missing_dual_as_no_certificate(y):
    """No y, or a y of another length than the rows: the gap is NaN, which
    fails any limit (a path that returns no dual is incorrect, and the
    check does not crash); the primal residual is still read."""
    p = _tiny_lp()
    ans = _highs_answer(p)
    nums = kkt.check(p, dict(ans, y=None if y is None else ans["y"][:-1]))
    assert np.isnan(nums["gap_rel"])
    assert nums["primal_rel"] == kkt.check(p, ans)["primal_rel"]


def test_kkt_reference_prices_weight_on_an_infinite_bound():
    """min x1 + x2 s.t. x1 + x2 >= 1, x >= 0 (optimum 1). The feasible,
    suboptimal x = (1, 1) with y = 2 closes the gap if the reduced costs
    r = (-1, -1), which sit on x's infinite upper bounds, are skipped."""
    import scipy.sparse as sp
    from distributedlpsolver_tpu.models.problem import LPProblem

    p = LPProblem(c=np.array([1.0, 1.0]), A=sp.csr_matrix([[1.0, 1.0]]),
                  rlb=np.array([1.0]), rub=np.array([np.inf]),
                  lb=np.zeros(2), ub=np.full(2, np.inf))
    assert kkt.check(p, {"x": np.array([0.5, 0.5]),
                         "y": np.array([1.0])}) == {"primal_rel": 0.0,
                                                    "gap_rel": 0.0}
    bad = kkt.check(p, {"x": np.array([1.0, 1.0]), "y": np.array([2.0])})
    assert bad["primal_rel"] == 0.0 and bad["gap_rel"] > 0.5


def _metric(name):
    return spec.load_module(
        os.path.join(REPO, "benchmark", "metrics", name + ".py")).read


def _run(answers, **kw):
    rec = {"setup_s": 12.5, "seconds": 10.0, "window_start": 100.0,
           "window_end": 110.0, "answers": answers,
           "trace": None}
    rec.update(kw)
    return rec


def _req(k, t0, t1, ok=True, **kw):
    return {"k": k, "t_submit": t0, "t_done": t1, "ok": ok, **kw}


@pytest.mark.parametrize("name,rec,want", [
    ("setup_s", _run([]), 12.5),
    # Three solves, the last overhanging the close at 110 s: 15 s / 3.
    ("solve_s", _run([_req(0, 100, 104), _req(1, 104, 109),
                      _req(2, 109, 115)]), 5.0),
    ("host_setup_ms.solve", _run([_req(0, 0, 1, setup_time=0.5,
                                       solve_time=1.0, iterations=10),
                                  _req(1, 1, 2, setup_time=1.5,
                                       solve_time=2.0, iterations=40)]),
     1000.0),
    ("ipm_iters.solve", _run([_req(0, 0, 1, solve_time=1.0, iterations=10),
                              _req(1, 1, 2, solve_time=2.0, iterations=40),
                              _req(2, 2, 3, solve_time=2.0, iterations=30)]),
     30.0),
    ("iter_ms.solve", _run([_req(0, 0, 1, solve_time=1.0, iterations=10),
                            _req(1, 1, 2, solve_time=2.0, iterations=40)]),
     75.0),
    ("device_idle_pct.solve", _run([], trace={"busy_s": 0.75,
                                              "window_s": 1.0}), 25.0),
    ("device_idle_pct.solve", _run([]), None),
])
def test_metric_arithmetic(name, rec, want):
    got = _metric(name)(rec)
    assert got == pytest.approx(want) if want is not None else got is None


def test_every_metric_of_the_benchmark_has_a_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert [m.name for m in cell.metrics["end_to_end"]][0] == "setup_s"
        assert len(cell.metrics["end_to_end"]) >= 2
        assert cell.metrics["per_layer"]
        moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
        e2e = {m.name for m in cell.metrics["end_to_end"]}
        assert all(moves[m.name] in e2e for m in cell.metrics["per_layer"])


def test_trace_reduction_of_a_recorded_chip_trace():
    """tiny.xplane.pb was recorded on a TPU v5 lite: three small jitted
    calls inside a ``bench.window`` span, each inside ``bench.solve``."""
    r = trace.reduce(FIXTURE)
    assert r is not None and r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    assert sum(s for _, s in r["device_ops"]) >= r["busy_s"] * 0.999
    assert len(r["idle_gaps"]) <= 10
    gaps = [s for _, s in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert any("bench.solve" in label for label, _ in r["idle_gaps"])


def test_interval_union():
    assert trace._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]


def test_no_chip_is_refused_before_anything_runs(capsys):
    rc = run.run(run.parse(["--workload", "pds10.solve", "--seed", "1",
                            "--seconds", "1"]))
    out = capsys.readouterr()
    assert rc == 3 and out.out == "" and "no TPU" in out.err


def test_unknown_chip_kind_is_an_error():
    with pytest.raises(KeyError):
        run.peaks("TPU v0 imaginary")


def test_importing_the_harness_describes_no_topology():
    code = ("import sys, benchmark.run, benchmark.load, benchmark.control, "
            "benchmark.trace, benchmark.spec, benchmark.meter; "
            "print(sorted(m for m in sys.modules if m == 'jax' or "
            "m.startswith('jax.experimental.topologies')))")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"


def test_a_checkout_of_only_the_benchmark_prints_no_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "pds10.solve",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONPATH": ""})
    assert out.returncode != 0 and out.stdout == ""
