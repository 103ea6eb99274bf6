"""CPU rehearsal of chip_smoke.py (on-chip-measurement guide §2.1).

Each phase function runs at a tiny size on the CPU. The tests steer the
routing the chip would take — ``auto`` deciding as on a TPU, the two-phase
schedule, the Pallas kernel in interpret mode — here, never through an
option of the script. Only ``chip_smoke.main()`` refuses a non-TPU
device; that refusal is tested through the script's entry point.
"""

import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

import chip_smoke
from tests.oracle import highs_on_general

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Route and schedule as on a TPU, with Pallas interpreted. Yields
    the list of shapes the kernel was called with."""
    from distributedlpsolver_tpu import ops
    from distributedlpsolver_tpu.backends import auto
    from distributedlpsolver_tpu.ipm.config import SolverConfig

    choose = auto.choose_backend_name
    monkeypatch.setattr(
        auto, "choose_backend_name",
        lambda inf, platform, detect=False: choose(inf, "tpu", detect),
    )
    monkeypatch.setattr(
        SolverConfig, "two_phase_enabled",
        lambda self, platform: self.factor_dtype == "auto",
    )
    monkeypatch.setattr(
        ops, "supports_pallas",
        lambda dtype, platform=None: jnp.dtype(dtype)
        in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)),
    )
    calls = []
    kernel = ops.normal_eq_pallas

    def interpreted(A, d, **kw):
        calls.append(A.shape)
        return kernel(A, d, interpret=True, **kw)

    monkeypatch.setattr(ops, "normal_eq_pallas", interpreted)
    yield calls


def _assert_checks(row):
    failed = [k for k, ok in row["checks"].items() if not ok]
    assert not failed, (failed, row)


def test_phase_dense_rehearsal(as_on_tpu):
    from distributedlpsolver_tpu.models.generators import random_dense_lp

    # m·n just above auto's small-problem cut, so auto picks `tpu`.
    m, n, seed = 64, 3200, 2
    ref = highs_on_general(random_dense_lp(m, n, seed=seed)).fun
    meter = chip_smoke.CompileMeter()
    row = chip_smoke.phase_dense(m, n, seed, ref, meter=meter)
    _assert_checks(row)
    assert row["backend"] == "auto(tpu)"
    # The f32 phase called the kernel; interpreted, it lowers to plain
    # HLO, so no program holds a Mosaic call here.
    assert as_on_tpu and row["mosaic_programs"] == 0
    # The profiler's short re-solve reuses (a)'s programs.
    from distributedlpsolver_tpu.ipm import solve
    from distributedlpsolver_tpu.models.generators import random_dense_lp

    c0 = meter.snapshot()
    solve(random_dense_lp(m, n, seed=seed), backend="auto", tol=1e-8,
          max_iter=3)
    assert meter.snapshot()["compiles"] == c0["compiles"]


def test_phase_sparse_rehearsal(monkeypatch):
    from distributedlpsolver_tpu.backends import auto
    from distributedlpsolver_tpu.models.generators import block_angular_lp
    from distributedlpsolver_tpu.models.problem import to_interior_form
    from tests.oracle import highs_on_interior

    # The huge-sparse gate lowered, so the tiny instance takes the route
    # the storm-class instance takes (on every platform).
    monkeypatch.setattr(auto, "_HUGE_SPARSE_ROWS", 100)
    shape = {"num_blocks": 6, "block_m": 24, "block_n": 48, "link_m": 12,
             "seed": 3, "density": 0.15}
    p = block_angular_lp(6, 24, 48, 12, seed=3, sparse=True, density=0.15)
    p.block_structure = None
    inf = to_interior_form(p)
    ref = highs_on_interior(inf).fun + inf.c0
    row = chip_smoke.phase_sparse(shape, ref, auto_max_iter=2)
    _assert_checks(row)
    assert row["auto_route"] == "sparse-iterative"
    assert row["auto"]["status"] == "iteration_limit"
    assert row["auto"]["cg_iters"] > 0 and len(row["auto"]["t_iter_s"]) == 2


def test_phase_serve_rehearsal():
    row = chip_smoke.phase_serve(requests=4, m=8, n=24, tol=1e-8, batch=2)
    _assert_checks(row)
    assert row["engines"] == ["ipm"] and row["dispatches"] >= 2
    assert [b[2] for b in row["buckets"]] == [2]  # one program, 2 slots


def test_phase_four_chips_rehearsal():
    # conftest gives 8 virtual CPU devices: the mesh spans all of them.
    from distributedlpsolver_tpu.models.generators import random_dense_lp

    m, n, seed = 24, 96, 5
    ref = highs_on_general(random_dense_lp(m, n, seed=seed)).fun
    row = chip_smoke.phase_four_chips(m, n, seed, ref)
    _assert_checks(row)
    assert row["devices"] == 8 and len(row["A_share_per_device"]) == 8


def test_profile_window_reports_xplane():
    from distributedlpsolver_tpu.ipm import solve
    from distributedlpsolver_tpu.models.generators import random_dense_lp

    p = random_dense_lp(8, 24, seed=0)
    row = chip_smoke.profile_window(lambda: solve(p, backend="cpu"))
    assert set(row) >= {"xplane_written", "xplane_bytes"}
    if row["xplane_written"]:
        assert row["xplane_bytes"] > 0 and row["planes"]


def test_refs_cover_the_phase_instances():
    refs = chip_smoke.load_refs()
    assert chip_smoke.dense_key(**chip_smoke.DENSE) in refs
    assert chip_smoke.storm_key(**chip_smoke.STORM) in refs


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=240,
    )


def test_main_refuses_cpu():
    proc = _run_script(_REPO, "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_main_fails_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    proc = _run_script(str(tmp_path), "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
