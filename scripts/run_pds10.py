"""pds-10-class block-angular run with a FULLY MEASURED CPU baseline
(review round 3 item 5): a size where the cpu-sparse baseline finishes
end-to-end (hours, not the >1-day pds-20-class solve), so the block
backend's vs_baseline is a measured ratio, not an s/iter extrapolation.

Size: K=32, 432x1400 per block, 800 linking rows -> 14624 rows — the
pds-10 row class (real pds-10: 16558 rows; BASELINE.json:8's smaller
sibling). The 800 dense linking rows still fill the sparse factorization
(the pds-20 cost mechanism), but at ~1/8 the link-cube cost the full CPU
solve completes.

Usage: python scripts/run_pds10.py tpu|cpu
  tpu: block backend on the real chip  -> .pds10_tpu.json
  cpu: cpu-sparse end-to-end baseline  -> .pds10_cpu.json
"""
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

mode = sys.argv[1] if len(sys.argv) > 1 else "tpu"
if mode == "cpu":
    import jax

    jax.config.update("jax_platforms", "cpu")
else:
    # Measurement envelope: no TPU aborts (exit 4) before anything is
    # measured.
    from distributedlpsolver_tpu.utils.accel import require_tpu

    require_tpu()

from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.models.generators import block_angular_lp

K, mb, nb, link = 32, 432, 1400, 800
print(f"building K={K} {mb}x{nb} link={link}...", flush=True)
p = block_angular_lp(K, mb, nb, link, seed=0, sparse=True, density=0.005)
print(f"built {p.shape}, nnz={p.A.nnz}", flush=True)

t0 = time.time()
if mode == "cpu":
    r = solve(p, backend="cpu-sparse", verbose=True, max_iter=120)
    tag = "cpu-sparse (SciPy sparse-direct normal equations, 1 host core)"
else:
    from distributedlpsolver_tpu.backends.block_angular import (
        BlockAngularBackend,
    )

    solve(p, backend="block", max_iter=3)  # compile warm-up
    be = BlockAngularBackend()  # explicit instance: phase_report access
    t0 = time.time()
    r = solve(p, backend=be, max_iter=120)
    tag = "block@tpu"
wall = time.time() - t0
print(
    f"{tag}: {r.status.name} obj={r.objective:.6f} iters={r.iterations} "
    f"gap={r.rel_gap:.2e} pinf={r.pinf:.2e} dinf={r.dinf:.2e} "
    f"solve={r.solve_time:.2f}s wall={wall:.1f}s",
    flush=True,
)
row = {
    "config": f"pds-10-class block_angular(K={K},{mb}x{nb},link={link}), "
              f"{p.shape[0]} rows (BASELINE.json:8 smaller sibling)",
    "backend": tag,
    "time_s": round(r.solve_time, 3),
    "iters": int(r.iterations),
    "iters_per_sec": round(r.iters_per_sec, 3),
    "status": r.status.value,
    "tol": 1e-8,
    "objective": float(r.objective),
}
if mode == "tpu":
    # Per-phase wall split + FLOP/s vs seed rates, keyed by the
    # backend-recorded phase mode (utils/utilization.py — shared with
    # run_pds20_tpu.py).
    from distributedlpsolver_tpu.utils.utilization import fold_utilization

    report = list(getattr(be, "phase_report", []))
    if report:
        flops_it = float(be._f64_flops)
        row["flops_per_iter_est"] = f"{flops_it:.3g}"
        row["phase_report"] = fold_utilization(report, flops_it)

out = os.path.join(_REPO, f".pds10_{mode}.json")
with open(out, "w") as fh:
    json.dump(row, fh, indent=2)
print(json.dumps(row), flush=True)
