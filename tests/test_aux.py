"""Auxiliary subsystems (SURVEY.md §5): CLI surface, checkpoint/resume,
warm start, and the JSONL metrics stream."""

import json
import os

import numpy as np
import pytest

from distributedlpsolver_tpu.cli import main as cli_main
from distributedlpsolver_tpu.io import write_mps
from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.ipm.state import IPMState, Status
from distributedlpsolver_tpu.models.generators import random_general_lp
from distributedlpsolver_tpu.utils import checkpoint as ckpt


@pytest.fixture
def mps_file(tmp_path):
    p = random_general_lp(10, 24, seed=21)
    path = str(tmp_path / "prob.mps")
    write_mps(p, path)
    return path, p


# ------------------------------------------------------------------- CLI
def test_cli_solve_json(mps_file, capsys):
    path, _ = mps_file
    rc = cli_main(["solve", path, "--backend=cpu", "--quiet", "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["status"] == "optimal"
    assert out["rel_gap"] <= 1e-8
    assert out["backend"] == "cpu"


def test_cli_solve_writes_solution(mps_file, tmp_path, capsys):
    path, p = mps_file
    x_out = str(tmp_path / "x.npy")
    rc = cli_main(["solve", path, "--backend=cpu", "--quiet", "--x-out", x_out])
    assert rc == 0
    x = np.load(x_out)
    assert x.shape == (p.n,)
    assert p.max_violation(x) <= 1e-6


def test_cli_backends_lists_registry(capsys):
    assert cli_main(["backends"]) == 0
    names = capsys.readouterr().out.split()
    for expected in ("tpu", "cpu", "cpu-native", "cpu-sparse", "sharded", "block"):
        assert expected in names


def test_cli_generate_round_trips(tmp_path, capsys):
    out = str(tmp_path / "gen.mps")
    rc = cli_main(["generate", "block", out, "--m", "8", "--n", "20", "--blocks", "2",
                   "--link", "4"])
    assert rc == 0
    rc = cli_main(["solve", out, "--backend=cpu", "--quiet", "--json"])
    assert rc == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["status"] == "optimal"


def test_cli_nonoptimal_exit_code(tmp_path, capsys):
    # An infeasible problem must exit 2, not 0 (scripting contract).
    from distributedlpsolver_tpu.models.problem import LPProblem

    p = LPProblem(
        c=[1.0, 1.0], A=[[1.0, 1.0], [1.0, 1.0]],
        rlb=[2.0, -np.inf], rub=[2.0, 1.0],
        lb=[0.0, 0.0], ub=[np.inf, np.inf], name="infeas",
    )
    path = str(tmp_path / "infeas.mps")
    write_mps(p, path)
    rc = cli_main(["solve", path, "--backend=cpu", "--quiet", "--json"])
    assert rc == 2
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["status"] in ("primal_infeasible", "numerical_error")


# -------------------------------------------------- checkpoint / restart
def test_checkpoint_save_load_round_trip(tmp_path):
    path = str(tmp_path / "ck.npz")
    state = IPMState(
        x=np.arange(4.0), y=np.ones(2), s=np.full(4, 2.0),
        w=np.ones(4), z=np.zeros(4),
    )
    ckpt.save_state(path, state, 17, "prob")
    loaded, it, name = ckpt.load_state(path)
    assert (it, name) == (17, "prob")
    for f in IPMState._fields:
        np.testing.assert_array_equal(getattr(loaded, f), getattr(state, f))
    assert ckpt.maybe_load(None) is None
    assert ckpt.maybe_load(str(tmp_path / "missing.npz")) is None


def test_solve_resumes_from_checkpoint(tmp_path):
    p = random_general_lp(10, 24, seed=5)
    ck = str(tmp_path / "it.npz")
    # Interrupted run: checkpoint every iteration, stop early.
    r1 = solve(p, backend="cpu", checkpoint_path=ck, checkpoint_every=1, max_iter=4)
    assert r1.status == Status.ITERATION_LIMIT
    assert os.path.exists(ck)
    # Resumed run finds the checkpoint and needs fewer iterations than a
    # cold solve to reach optimality.
    cold = solve(p, backend="cpu")
    r2 = solve(p, backend="cpu", checkpoint_path=ck, checkpoint_every=1)
    assert r2.status == Status.OPTIMAL
    assert r2.iterations < cold.iterations
    np.testing.assert_allclose(r2.objective, cold.objective, rtol=1e-7, atol=1e-8)


def test_warm_start_accepts_prior_state(tmp_path):
    # The checkpoint payload is the documented warm-start carrier.
    p = random_general_lp(8, 18, seed=6)
    ck = str(tmp_path / "ws.npz")
    solve(p, backend="cpu", checkpoint_path=ck, checkpoint_every=1, max_iter=6)
    state, _, _ = ckpt.load_state(ck)
    r2 = solve(p, backend="cpu", warm_start=state)
    assert r2.status == Status.OPTIMAL


# ------------------------------------------------------------ JSONL logs
def test_jsonl_iteration_log(tmp_path):
    p = random_general_lp(10, 24, seed=7)
    log = str(tmp_path / "iters.jsonl")
    r = solve(p, backend="cpu", log_jsonl=log)
    assert r.status == Status.OPTIMAL
    records = [json.loads(line) for line in open(log)]
    assert len(records) == r.iterations
    assert [rec["iter"] for rec in records] == list(range(1, r.iterations + 1))
    for key in ("mu", "gap", "rel_gap", "pinf", "dinf", "alpha_p", "alpha_d",
                "sigma", "pobj", "dobj", "t_iter"):
        assert key in records[0]
    # the trajectory the metric surface promises: gap decreases to tol
    assert records[-1]["rel_gap"] <= 1e-8


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir_rule(tmp_path, from_env):
    """One cache rule: JAX_COMPILATION_CACHE_DIR names the directory when
    set (nothing in the package replaces it); otherwise the fixed
    in-checkout path. Checked in a fresh interpreter, since the package
    decides at import."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "TPULP_NO_COMPILE_CACHE")
    }
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax-cache")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, distributedlpsolver_tpu; "
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=str(tmp_path), env={**env, "PYTHONPATH": repo},
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout.strip().splitlines()[-1]
    want = (
        str(tmp_path / "jax-cache") if from_env
        else os.path.join(repo, ".tpulp_xla_cache")
    )
    assert out == want


def test_profile_dir_always_yields_a_report(tmp_path):
    """--profile-dir honesty: whatever jax.profiler.trace writes, the
    profile dir must come back with
    the dispatch-level timing report, one entry per iteration."""
    prof = tmp_path / "prof"
    p = random_general_lp(8, 18, seed=4)
    r = solve(p, backend="cpu", profile_dir=str(prof), verbose=False)
    assert r.status == Status.OPTIMAL
    report = json.loads((prof / "dispatch_timings.json").read_text())
    assert report["iterations"] == r.iterations > 0
    assert len(report["t_iter_s"]) == r.iterations
    assert report["solve_s"] > 0
    assert "jax_profiler_trace_wrote_files" in report


def test_profile_dir_forces_host_loop(tmp_path):
    """The fused on-device loop has no iteration boundaries to profile;
    profile_dir must force the per-iteration host driver (else the trace
    wraps nothing and the report has no rows)."""
    prof = tmp_path / "prof2"
    p = random_general_lp(8, 18, seed=4)
    r = solve(
        p, backend="cpu", profile_dir=str(prof), fused_loop=None,
        verbose=False,
    )
    report = json.loads((prof / "dispatch_timings.json").read_text())
    # host loop ran: true per-iteration wall times, not one fused average
    assert len(set(report["t_iter_s"])) > 1 or r.iterations <= 1


def test_native_object_keyed_by_source_flags_and_cpu(monkeypatch):
    """A native object built elsewhere is never loaded: the .so name
    carries a hash of source, flags and host CPU, and a stale object
    under the old fixed name is ignored."""
    import os

    from distributedlpsolver_tpu.native import build as nb

    path = nb.object_path()
    stale = os.path.join(os.path.dirname(path), "libdlps_kernels.so")
    assert os.path.basename(path) != os.path.basename(stale)
    monkeypatch.setattr(nb, "_cpu_signature", lambda: "another-host")
    assert nb.object_path() != path
    monkeypatch.undo()
    monkeypatch.setattr(nb, "_FLAGS", nb._FLAGS + ("-DDIFFERENT",))
    assert nb.object_path() != path
    monkeypatch.undo()
    assert nb.object_path() == path
    if nb.available():
        assert nb.load()._name == path


@pytest.mark.parametrize(
    "kind,has_peak", [("TPU v5 lite", True), ("some unknown chip", False)]
)
def test_chip_peak_table_keyed_by_device_kind(kind, has_peak):
    from distributedlpsolver_tpu.utils.utilization import fold_utilization

    rows = fold_utilization(
        [{"phase": 0, "mode": "f32", "iters": 10, "wall_s": 1.0},
         {"phase": 1, "mode": "f64", "iters": 5, "wall_s": 1.0}],
        flops_per_iter=1e10, device_kind=kind,
    )
    assert all("pct_of_seed_rate" in r for r in rows)
    assert all(("pct_of_chip_peak" in r) is has_peak for r in rows)
