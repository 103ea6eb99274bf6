"""The ``lp128x512.batch`` cell on the CPU, cut to 8 LPs of 12 x 40 a
call in a test checkout: runs judged correct on the program's own duals,
its per-layer metrics read from the batched calls' records, the float32
control failing the configuration's limits; and the readers' arithmetic
on hand-made records."""

import json
import os
import shutil

import pytest

from benchmark import control, run, spec
from distributedlpsolver_tpu.obs import trace as obs_trace

CELL = "lp128x512.batch"
NEW = ("loop_ms.batch", "host_ms.batch", "cleanup_ms.batch",
       "cleanup_lps.batch", "occupancy_pct.batch", "device_idle_pct.batch")


@pytest.fixture
def root(tmp_path):
    """A checkout holding the benchmark with lp128x512 cut to 12 x 40 and
    its mix to calls of 8 LPs; the runs point JAX's persistent cache at
    it, so the next test of this worker gets the config it had back."""
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for path, update in (
            (tmp_path / "benchmark" / "configs" / "lp128x512.json",
             lambda d: d["params"].update(m=12, n=40)),
            (tmp_path / "benchmark" / "traffic" / "batch.json",
             lambda d: d.update(batch=8, trace_seconds=0.3))):
        d = json.loads(path.read_text())
        update(d)
        path.write_text(json.dumps(d))
    yield str(tmp_path)
    for k, v in saved.items():
        jax.config.update(k, v)


def _run(root, capsys, trace, seed=2**31 + 11):
    rc = run.run(run.parse(["--workload", CELL, "--seed", str(seed),
                            "--seconds", "0.5", "--trace", str(trace)]),
                 root=root, require_tpu=False)
    out = capsys.readouterr()
    assert rc == 0, out.err
    return [json.loads(x) for x in out.out.strip().splitlines()]


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_is_correct_on_the_programs_own_duals(root, capsys,
                                                       monkeypatch, trace):
    """No HiGHS duals filled in: every LP's y is the program's, and every
    LP certifies under the configuration's limits."""
    seen = {}
    check = run.check
    monkeypatch.setattr(run, "check", lambda cell, seed, answers, **k:
                        check(cell, seed, seen.setdefault("a", answers), **k))
    rows = _run(root, capsys, trace)
    setup, window, last = rows[0]["setup"], rows[1]["window"], rows[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert [c["lps"] for c in setup["warm_calls"]] == [8]
    assert last["attempted"] == 8 * len(window["calls"]) > 0
    assert all(a["y"] is not None and a["y"].shape == (12,)
               for a in seen["a"])
    limits = spec.load_cell(CELL, root).config["limits"]
    for name, c in last["checks"].items():
        assert c["limit"] == limits[name] and c["value"] <= c["limit"]
    assert window["compiles_in_window"]["compiles"] == 0
    if trace:
        got = last["metrics"]
        # No device plane on the CPU: the idle share has nothing to read.
        assert set(NEW) - set(got) == {"device_idle_pct.batch"}
        for name in NEW[:3]:
            assert got[name]["unit"] == "ms" and got[name]["value"] >= 0
        assert got["loop_ms.batch"]["value"] > 0
        assert 0 < got["occupancy_pct.batch"]["value"] <= 100
        assert got["cleanup_lps.batch"]["value"] >= 0
        assert got["cleanup_lps.batch"]["unit"] == "LPs"
    else:
        assert set(last["metrics"]) == {"setup_s", "solve_s"}


@pytest.mark.parametrize("mode", ["program", "control"])
def test_the_float32_control_fails_the_limits_the_program_passes(root,
                                                                 mode):
    """The program at float64 passes every compared number; the control,
    the same path at float32, fails at least one."""
    run.use_cache(root)
    cell = spec.load_cell(CELL, root)
    limits = cell.config["limits"]
    (row,) = control.readings(cell, [2**31 + 3], mode, 8, emit=lambda s: 0)
    ok = [v <= limits[k] for k, v in row["numbers"].items()]
    assert all(ok) if mode == "program" else not all(ok), row


def _metric(name):
    return spec.load_module(
        os.path.join(spec.ROOT, "benchmark", "metrics", name + ".py")).read


def _call(t0, t1, spans, counters):
    return {"t0": t0, "t1": t1, "spans": spans, "phase_report": [],
            "counters": counters}


# The warm-up call (before the window) does not count; the window's two
# calls each hold a clean-up solve's own record, which is not a call.
CALLS = [
    _call(0.5, 9.0, {"batch.loop": 9.0, "batch.prepare": 9.0},
          {"member_iters": 1, "lane_iters": 9, "cleanup_lps": 9}),
    _call(10.1, 19.9, {"batch.prepare": 0.1, "batch.loop": 0.5,
                       "ipm.segment": 4.0, "batch.compact": 0.2,
                       "batch.readback": 0.3, "batch.cleanup": 0.0},
          {"member_iters": 60, "lane_iters": 100, "cleanup_lps": 0}),
    {"t0": 19.0, "t1": 19.5, "spans": {"lp.solve": 0.5},
     "phase_report": None, "counters": None},
    _call(20.1, 29.9, {"batch.prepare": 0.3, "batch.loop": 1.5,
                       "ipm.segment": 6.0, "batch.readback": 0.5,
                       "batch.cleanup": 2.0},
          {"member_iters": 80, "lane_iters": 100, "cleanup_lps": 2}),
]
# Four LPs a call: each call is the record of four answers.
RUN = {"answers": [{"k": k, "t_submit": t0, "t_done": t0 + 10.0}
                   for t0 in (10.0, 20.0) for k in range(4)],
       "trace": None}


@pytest.mark.parametrize("name,want", [
    ("loop_ms.batch", 6000.0),  # median of 4.5 s and 7.5 s
    ("host_ms.batch", 600.0),  # median of 0.4 s and 0.8 s
    ("cleanup_ms.batch", 1000.0),
    ("cleanup_lps.batch", 1.0),
    ("occupancy_pct.batch", 70.0),  # median of 60% and 80%
    ("device_idle_pct.batch", None),
])
def test_batch_metric_arithmetic(monkeypatch, name, want):
    monkeypatch.setattr(obs_trace, "recent_solves", lambda: CALLS)
    got = _metric(name)(RUN)
    assert got == pytest.approx(want) if want is not None else got is None


@pytest.mark.parametrize("name", NEW[:5])
def test_batch_metrics_read_nothing_from_a_program_without_the_records(
        monkeypatch, name):
    """A program whose batched calls leave no record (only the clean-up's
    solves, or nothing at all) gives no value, and no error."""
    monkeypatch.setattr(obs_trace, "recent_solves", lambda: [CALLS[2]])
    assert _metric(name)(RUN) is None
    monkeypatch.delattr(obs_trace, "recent_solves")
    assert _metric(name)(RUN) is None
