"""pds-20-class Schur-path run (review round 2 item 2): the K=64,
432x1400-per-block, 1600 linking-row instance (~29k rows) on the TPU
block backend (two-phase segmented Schur), plus an optional 8-virtual-
device mesh run proving the K-sharded memory story.

Writes /root/repo/.pds20_tpu.json. The CPU baseline is measured
separately (scripts/run_pds20_cpu.py) because one cpu-sparse iteration
takes ~40 min at this scale — its artifact records measured s/iter.

Usage: python scripts/run_pds20_tpu.py [mesh]
  'mesh' runs on 8 virtual CPU devices instead (set
  XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
import json
import sys
import time

sys.path.insert(0, "/root/repo")


on_mesh = len(sys.argv) > 1 and sys.argv[1] == "mesh"
if on_mesh:
    import jax

    jax.config.update("jax_platforms", "cpu")
else:
    # Measurement envelope: no TPU aborts (exit 4) before anything is
    # measured.
    from distributedlpsolver_tpu.utils.accel import require_tpu

    require_tpu()

from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.models.generators import block_angular_lp

K, mb, nb, link = 64, 432, 1400, 1600
print(f"building K={K} {mb}x{nb} link={link}...", flush=True)
p = block_angular_lp(K, mb, nb, link, seed=0, sparse=True, density=0.005)
print(f"built {p.shape}, nnz={p.A.nnz}", flush=True)

import jax

from distributedlpsolver_tpu.backends.block_angular import (
    BlockAngularBackend,
)

if on_mesh:
    from distributedlpsolver_tpu.parallel import make_mesh

    mesh = make_mesh(devices=jax.devices()[:8])
    be = BlockAngularBackend(mesh=mesh)
    tag = "block@8dev-mesh"
else:
    be = BlockAngularBackend()  # explicit instance: phase_report access
    tag = "block@tpu"

# Auto mode resolves to the lowering-safe huge-shape plan: f32 phase 1 →
# PCG at the handoff tol (ew-f64 matrix-free operator — no emulated-f64
# dot_generals, whose 8×-f32 operand-split temps OOMed this shape) →
# n-chunked true-f64 Schur finisher ("f64c") at 1e-8.
solve(p, backend=be, max_iter=3)  # compile warm-up
t0 = time.time()
r = solve(p, backend=be, max_iter=120)
wall = time.time() - t0
print(
    f"{tag}: {r.status.name} obj={r.objective:.6f} iters={r.iterations} "
    f"gap={r.rel_gap:.2e} pinf={r.pinf:.2e} dinf={r.dinf:.2e} "
    f"solve={r.solve_time:.2f}s wall={wall:.1f}s",
    flush=True,
)
row = {
    "config": f"pds-20-class block_angular(K={K},{mb}x{nb},link={link}), "
              f"{p.shape[0]} rows (BASELINE.json:8 target class)",
    "backend": tag,
    "time_s": round(r.solve_time, 3),
    "iters": int(r.iterations),
    "iters_per_sec": round(r.iters_per_sec, 2),
    "status": r.status.value,
    "tol": 1e-8,
    "objective": float(r.objective),
}

# Utilization (review round 3 item 4): per-phase wall split from the
# shared segment driver, FLOP/s vs seed rates keyed by the
# backend-recorded phase mode (utils/utilization.py).
from distributedlpsolver_tpu.utils.utilization import fold_utilization

report = list(getattr(be, "phase_report", []))
if report and not on_mesh:
    # mesh mode is correctness-only (virtual CPU devices emulate f64 in
    # software) — a % of the TPU seed rates would be meaningless there.
    flops_it = float(be._f64_flops)  # same op count for f32 and f64c
    row["flops_per_iter_est"] = f"{flops_it:.3g}"
    row["phase_report"] = fold_utilization(report, flops_it)
out = "/root/repo/.pds20_mesh.json" if on_mesh else "/root/repo/.pds20_tpu.json"
with open(out, "w") as fh:
    json.dump(row, fh, indent=2)
print(json.dumps(row), flush=True)
