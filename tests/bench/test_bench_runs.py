"""Whole benchmark runs on the CPU at tiny sizes, past the harness's look
for a chip: the cell's result lines, a cell and an entry added as files
alone, the faults that the check must catch, and the float32 control.
Besides ``pds10.solve``, the test checkout holds a batch cell,
``lp12x40.batch``: 8 standard-form LPs of 12 x 40 a call through the
``batch`` entry (``solve_batched``), with pds10's reference and limits."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark import control, run, spec
from benchmark.run import check

TINY = {
    "pds10": {"commodities": 3, "nodes": 10, "arcs": 24, "joint_arcs": 4,
              "supply_arcs": 2, "max_degree": 8},
}
STANDARD_FORM = os.path.join(os.path.dirname(__file__), "fixtures",
                             "standard_form.py")
BATCH = "lp12x40.batch"


@pytest.fixture
def jax_cache_config():
    """The runs point JAX's persistent cache at their own checkout; give
    the next test of this worker the config it had."""
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.fixture
def root(tmp_path, jax_cache_config):
    """A checkout holding the benchmark with its configurations cut to a
    tiny size and its mixes to a short, light load."""
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, params in TINY.items():
        path = tmp_path / "benchmark" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["params"].update(params)
        path.write_text(json.dumps(cfg))
    mix = tmp_path / "benchmark" / "traffic" / "solve.json"
    mix.write_text(json.dumps({**json.loads(mix.read_text()), "pool": 4,
                               "trace_seconds": 0.3}))
    _add_batch_cell(str(tmp_path))
    return str(tmp_path)


def _add_cell(root, cell, config, traffic, mix, per_layer=()):
    """Write a configuration and a mix, and name the cell in the copy's
    BENCHMARK.json, under ``solve_s`` and the per-layer metrics given."""
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs", config["name"] + ".json"),
              "w") as fh:
        json.dump(config, fh)
    with open(os.path.join(bdir, "traffic", traffic + ".json"), "w") as fh:
        json.dump(mix, fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    name = config["name"]
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"benchmark/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": traffic, "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "solve_s" or m["name"] in per_layer:
            m["workloads"].append(cell)
    with open(path, "w") as fh:
        json.dump(bench, fh)


def _add_batch_cell(root):
    gen = os.path.join(root, "benchmark", "generators", "standard_form.py")
    shutil.copy(STANDARD_FORM, gen)
    with open(os.path.join(root, "benchmark", "configs", "pds10.json")) as fh:
        pds10 = json.load(fh)
    config = {"name": "lp12x40", "generator": "standard_form",
              "params": {"m": 12, "n": 40}, "solve": {"tol": 1e-8},
              "precision": "float64",
              "control": {"solve": {"dtype": "float32"}},
              "reference": "kkt", "limits": pds10["limits"]}
    _add_cell(root, BATCH, config, "batch8",
              {"entry": "batch", "batch": 8, "pool": 1, "warm": 1,
               "trace_seconds": 0.3}, per_layer=("ipm_iters.solve",))


@pytest.fixture
def duals(monkeypatch):
    """``solve_batched`` as committed returns no y (``BatchedResult.y`` is
    None off the bucket path), and the float64 certificate needs one: fill
    each member's y, where the program gives none, with HiGHS's duals of
    that member. The gap then reads |cᵀx − the optimum|, so the program's
    x is still what is judged. A program that returns y is left as it is."""
    from scipy.optimize import linprog

    from distributedlpsolver_tpu.backends import batched

    orig = batched.solve_batched

    def solve_batched(batch, *a, **k):
        r = orig(batch, *a, **k)
        if r.y is None:
            r.y = np.stack([
                linprog(batch.c[i], A_eq=batch.A[i], b_eq=batch.b[i],
                        method="highs").eqlin.marginals
                for i in range(batch.batch)])
        return r

    monkeypatch.setattr(batched, "solve_batched", solve_batched)


def _run(root, workload, capsys, trace=0, seconds=1.0, seed=2**31 + 7):
    rc = run.run(run.parse(["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace",
                            str(trace)]), root=root, require_tpu=False)
    out = capsys.readouterr()
    assert rc == 0, out.err
    lines = out.out.strip().splitlines()
    return [json.loads(x) for x in lines], out.err.strip().splitlines()


@pytest.mark.parametrize("workload,trace", [
    ("pds10.solve", 0), ("pds10.solve", 1), (BATCH, 0), (BATCH, 1),
])
def test_cell_runs_and_prints_the_contract_line(root, capsys, monkeypatch,
                                                duals, workload, trace):
    seen = {}
    monkeypatch.setattr(run, "check", lambda cell, seed, answers, **k:
                        check(cell, seed, seen.setdefault("a", answers), **k))
    rows, err = _run(root, workload, capsys, trace=trace)
    setup, window, last = rows[0]["setup"], rows[1]["window"], rows[-1]
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == window["answers"] > 0
    assert window["compiles_in_window"]["compiles"] == 0
    cell = spec.load_cell(workload, root)
    kind = "per_layer" if trace else "end_to_end"
    want = {m.name for m in cell.metrics[kind]}
    got = set(last["metrics"])
    # Without a device plane (CPU) the idle share has nothing to read.
    assert got == {m for m in want if not m.startswith("device_idle")}
    for m in cell.metrics[kind]:
        if m.name in got:
            assert last["metrics"][m.name]["unit"] == m.unit
            assert last["metrics"][m.name]["value"] > 0
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # The compared numbers close stderr, each beside its limit.
    assert err[-len(last["checks"]):] == [
        f"check {k}: {v['value']!r} (limit {v['limit']!r})"
        for k, v in last["checks"].items()]
    if workload == "pds10.solve":
        assert all(r.startswith("auto(") for r in setup["warm_routes"])
        assert window["repeats"] >= 0 and window["routes"]
    else:
        # One answer per LP, 8 to a call; the call in flight at the close
        # runs out, and solve_s is seconds per LP from window start.
        calls = window["calls"]
        assert [c["lps"] for c in setup["warm_calls"]] == [8]
        assert window["routes"] == ["batched"]
        assert calls and all(c["lps"] == 8 for c in calls)
        assert last["attempted"] == 8 * len(calls)
        answers = seen["a"]
        assert sorted(a["k"] for a in answers) == sorted(
            list(range(8)) * len(calls))
        assert all(a["y"] is not None for a in answers)
        if not trace:
            t0 = run._T_START + last["metrics"]["setup_s"]["value"]
            end = max(a["t_done"] for a in answers)
            assert last["metrics"]["solve_s"]["value"] == pytest.approx(
                (end - t0) / last["attempted"], rel=1e-9)


def test_a_cell_added_as_files_alone_runs(root, capsys):
    """A configuration, a mix and a metric that are new files, and new
    entries in BENCHMARK.json, make a runnable cell: no harness edit."""
    bdir = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(bdir, "configs", "pds10.json")))
    cfg.update(name="pds2x8", params={"commodities": 2, "nodes": 8,
                                      "arcs": 16, "joint_arcs": 3,
                                      "supply_arcs": 1, "max_degree": 8})
    json.dump(cfg, open(os.path.join(bdir, "configs", "pds2x8.json"), "w"))
    json.dump({"entry": "solve", "pool": 7, "warm": 2, "trace_seconds": 0.2},
              open(os.path.join(bdir, "traffic", "burst.json"), "w"))
    with open(os.path.join(bdir, "metrics", "answers.py"), "w") as fh:
        fh.write("def read(run):\n    return float(len(run['answers']))\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "pds2x8", "source": "test",
                             "file": "benchmark/configs/pds2x8.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "pds2x8.burst", "config": "pds2x8",
                               "traffic": "burst", "chips": 1,
                               "why": "test"})
    next(m for m in bench["end_to_end"] if m["name"] == "solve_s")[
        "workloads"].append("pds2x8.burst")
    bench["per_layer"].append({"name": "answers", "unit": "LP",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "solve_s",
                               "workloads": ["pds2x8.burst"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    rows, _ = _run(root, "pds2x8.burst", capsys, trace=0)
    assert rows[-1]["correct"] is True
    assert rows[0]["setup"]["pool"] == 7
    assert set(rows[-1]["metrics"]) == {"setup_s", "solve_s"}
    rows, _ = _run(root, "pds2x8.burst", capsys, trace=1)
    assert rows[-1]["metrics"]["answers"]["value"] == rows[-1]["attempted"]


ONCE = """\
import time

from benchmark.load import WARM_BASE


class Entry:
    \"\"\"Solves LPs 0, 1 and 2 once, whatever the window's length.\"\"\"

    def __init__(self, cell, seed):
        self.cell, self.seed = cell, seed
        self.opts = dict(cell.config.get("solve", {}))

    def _solve(self, k):
        from distributedlpsolver_tpu.ipm import solve

        p = self.cell.generator.build(self.cell.config["params"], self.seed, k)
        t0 = time.perf_counter()
        r = solve(p, **self.opts)
        return {"k": k, "t_submit": t0, "t_done": time.perf_counter(),
                "status": r.status.value, "iterations": int(r.iterations),
                "x": r.x, "y": r.y}

    def setup(self):
        self._solve(WARM_BASE)
        return {"entry": "once"}

    def window(self, seconds, profiler):
        t0 = time.perf_counter()
        answers = self.answers()
        return answers, t0, {"solved": len(answers)}

    def answers(self, n=None):
        return [self._solve(k) for k in range(3 if n is None else n)]

    def close(self):
        pass
"""


def test_an_entry_added_as_a_file_alone_runs(root, capsys):
    """A mix that names a new entry file makes a runnable cell: no file
    of the harness is edited, and the entry's own notes come through."""
    bdir = os.path.join(root, "benchmark")

    def harness():
        return {os.path.join(d, f): open(os.path.join(d, f), "rb").read()
                for d, _, files in os.walk(bdir) for f in files
                if f.endswith(".py") and "__pycache__" not in d}

    before = harness()
    with open(os.path.join(bdir, "entries", "once.py"), "w") as fh:
        fh.write(ONCE)
    with open(os.path.join(bdir, "configs", "pds10.json")) as fh:
        cfg = dict(json.load(fh), name="pds10once")
    _add_cell(root, "pds10once.once", cfg, "once", {"entry": "once"})
    rows, _ = _run(root, "pds10once.once", capsys)
    assert rows[0]["setup"]["entry"] == "once"
    assert rows[1]["window"]["solved"] == 3
    assert rows[-1]["correct"] is True and rows[-1]["attempted"] == 3
    assert set(rows[-1]["metrics"]) == {"setup_s", "solve_s"}
    after = harness()
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {os.path.join(bdir, "entries",
                                                     "once.py")}


def _broken_step(monkeypatch):
    """Every IPM step returns its state unchanged (solo path)."""
    from distributedlpsolver_tpu.ipm import driver

    step = driver._step_once
    monkeypatch.setattr(driver, "_try_fused", lambda *a, **k: None)
    monkeypatch.setattr(driver, "_step_once",
                        lambda be, st: (st, step(be, st)[1]))
    monkeypatch.setattr(driver.SolverConfig, "max_iter", 30)


def _solve_result(monkeypatch, change):
    import distributedlpsolver_tpu.ipm as ipm

    orig = ipm.solve

    def solve(*a, **k):
        r = orig(*a, **k)
        r.x = np.array(r.x)
        change(r)
        return r

    monkeypatch.setattr(ipm, "solve", solve)


def _alter_x(x):
    """One entry of an answer moved where it is produced."""
    x.flat[0] += 1e-3 * (1.0 + abs(x.flat[0]))


def _broken_batch_step(monkeypatch):
    """Every batched IPM step returns its state unchanged. The step runs
    inside jitted programs: drop those compiled before, so that the
    broken step is traced (the test drops these after the run)."""
    import jax

    from distributedlpsolver_tpu.backends import batched

    step = batched._single_step
    monkeypatch.setattr(batched, "_single_step",
                        lambda *a, **k: (a[2], step(*a, **k)[1]))
    jax.clear_caches()


def _batch_result(monkeypatch, change):
    from distributedlpsolver_tpu.backends import batched

    orig = batched.solve_batched

    def solve_batched(batch, *a, **k):
        r = orig(batch, *a, **k)
        r.x = np.array(r.x)
        change(batch, r, orig, a, k)
        return r

    monkeypatch.setattr(batched, "solve_batched", solve_batched)


def _half_left_out(batch, r, solve, a, k):
    """The call solves the first half of its batch and hands that half's
    answers out for the other half too."""
    from distributedlpsolver_tpu.models.generators import BatchedLP

    h = batch.batch // 2
    half = solve(BatchedLP(c=batch.c[:h], A=batch.A[:h], b=batch.b[:h]),
                 *a, **k)
    for f in ("x", "y", "status", "objective", "iterations"):
        v = getattr(half, f)
        setattr(r, f, None if v is None else np.concatenate([v, v]))


FAULTS = {
    ("pds10.solve", "state_unchanged"): _broken_step,
    ("pds10.solve", "answer_altered"):
        lambda mp: _solve_result(mp, lambda r: _alter_x(r.x)),
    (BATCH, "state_unchanged"): _broken_batch_step,
    (BATCH, "answer_altered"):
        lambda mp: _batch_result(mp, lambda b, r, *_: _alter_x(r.x[3])),
    (BATCH, "half_left_out"): lambda mp: _batch_result(mp, _half_left_out),
}


@pytest.mark.parametrize("workload,fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(root, capsys, monkeypatch,
                                            duals, workload, fault):
    """The exchange between chips does not exist in these one-chip cells,
    nor a batch in the one-LP cell; every other fault the contract lists
    must turn ``correct`` false. In the batch cell an altered member
    fails alone: the others of its call still pass."""
    import jax

    FAULTS[workload, fault](monkeypatch)
    try:
        rows, _ = _run(root, workload, capsys, seconds=0.5)
    finally:
        monkeypatch.undo()
        if fault == "state_unchanged":
            jax.clear_caches()
    assert rows[-1]["correct"] is False
    assert rows[-1]["failed"] > 0
    if (workload, fault) == (BATCH, "answer_altered"):
        assert rows[-1]["failed"] == len(rows[1]["window"]["calls"])
    if fault == "half_left_out":
        assert rows[-1]["failed"] == 4 * len(rows[1]["window"]["calls"])


def test_a_batch_path_that_returns_no_dual_is_incorrect_not_a_crash(
        root, capsys, monkeypatch):
    """Every answer with no y fails the certificate: the run ends with
    its result line, ``gap_rel`` NaN beside its limit."""
    _batch_result(monkeypatch, lambda b, r, *_: setattr(r, "y", None))
    rows, err = _run(root, BATCH, capsys, seconds=0.5)
    last = rows[-1]
    assert last["correct"] is False
    assert last["failed"] == last["attempted"] > 0
    assert np.isnan(last["checks"]["gap_rel"]["value"])
    assert last["checks"]["primal_rel"]["value"] <= 1e-4
    assert any(line.startswith("check gap_rel: nan") for line in err)


@pytest.mark.parametrize("broken,what", [
    (lambda p: setattr(p, "rlb", p.rlb - 1.0), "not an equality"),
    (lambda p: setattr(p, "lb", p.lb - 1.0), "lower bound"),
    (lambda p: setattr(p, "ub", np.full_like(p.ub, 5.0)), "finite upper"),
    (lambda p: setattr(p, "c0", 2.0), "objective constant"),
    (lambda p: setattr(p, "A", p.A[:, :-1]), "not the batch's"),
])
def test_the_batch_entry_refuses_an_lp_not_in_standard_form(root, broken,
                                                            what):
    cell = spec.load_cell(BATCH, root)
    build = cell.generator.build

    def build_broken(params, seed, k):
        p = build(params, seed, k)
        if k == 5:
            broken(p)
        return p

    cell.generator.build = build_broken
    with pytest.raises(ValueError, match=rf"\(member 5\).*{what}"):
        cell.entry.Entry(cell, 3).setup()


def test_the_batch_control_runs_solve_batched_in_both_modes(root, duals,
                                                            monkeypatch):
    """``control.readings`` takes its answers from the cell's own entry:
    ``solve_batched`` on the window's LPs (k < 10: two calls of 8), at
    float64 for ``program`` and with the float32 control on top, which
    fails a limit (at this size by its status alone: float32 never
    reaches the 1e-8 tolerance)."""
    from distributedlpsolver_tpu.backends import batched

    run.use_cache(root)
    cell = spec.load_cell(BATCH, root)
    calls = []
    solve = batched.solve_batched

    def spy(batch, **opts):
        calls.append((batch.batch, opts.get("dtype", "float64")))
        return solve(batch, **opts)

    monkeypatch.setattr(batched, "solve_batched", spy)
    limits = cell.config["limits"]
    rows = {mode: control.readings(cell, [21], mode, 10, emit=lambda s: 0)
            for mode in ("program", "control")}
    assert calls == [(8, "float64")] * 2 + [(8, "float32")] * 2
    assert [r["answers"] for r in rows["program"] + rows["control"]] == [10,
                                                                        10]
    for row in rows["program"]:
        assert row["statuses"] == ["optimal"]
        assert all(v <= limits[k] for k, v in row["numbers"].items()), row
    for row in rows["control"]:
        assert any(not v <= limits[k] for k, v in row["numbers"].items()), row


def _chip_route(monkeypatch):
    """auto routes as it does on the chip: on the CPU it would send every
    problem to cpu-native, which has no float32 path."""
    from distributedlpsolver_tpu.backends import auto

    choose = auto.choose_backend_name
    monkeypatch.setattr(auto, "choose_backend_name",
                        lambda inf, platform, detect=False:
                        choose(inf, "tpu", detect))


@pytest.mark.parametrize("seeds", [[11, 12], [13]])
def test_float32_control_fails_the_limits_and_the_program_passes(
        root, monkeypatch, seeds):
    """The control (the program's own path at float32, the precision below
    the float64 the configuration states) must fail at least one compared
    number; the program at float64 passes every one. pds10 at about the
    smallest size that auto sends to the block backend, as on the chip."""
    run.use_cache(root)
    cell = spec.load_cell("pds10.solve", root)
    _chip_route(monkeypatch)
    cell.config["params"].update(commodities=6, nodes=60, arcs=100,
                                 joint_arcs=30, supply_arcs=6, max_degree=5)
    limits = cell.config["limits"]
    rows = {mode: control.readings(cell, seeds, mode, 2, emit=lambda s: 0)
            for mode in ("program", "control")}
    for row in rows["program"]:
        assert all(v <= limits[k] for k, v in row["numbers"].items()), row
    for row in rows["control"]:
        assert any(not v <= limits[k] for k, v in row["numbers"].items()), row
