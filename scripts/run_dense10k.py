"""The reference-scale dense config (BASELINE.json:9): 10000 x 50000 to a
1e-8 relative duality gap on the IPM `tpu` backend (two-phase + PCG).

Writes the suite row to /root/repo/BENCH_10K.json on success. Run with
TPULP_SEG_VERBOSE=1 for live segment progress.
"""
import json
import os
import sys
import time

sys.path.insert(0, "/root/repo")

# Measurement envelope: no TPU aborts (exit 4) before anything is measured.
from distributedlpsolver_tpu.utils.accel import require_tpu

require_tpu()

m, n = (int(sys.argv[1]), int(sys.argv[2])) if len(sys.argv) > 2 else (10000, 50000)
max_iter = int(sys.argv[3]) if len(sys.argv) > 3 else 200
# CG sweep cap: one PCG-phase Mehrotra iteration is ONE device program
# holding 2 CG solves, and near the f32 floor each runs its full cap at
# ~0.5 s/sweep (ew-f64 GEMV pair, measured) — cap 40 keeps the worst
# program ~40 s, under the ~60 s program limit of the remote chip used
# before PR 21 (see core.use_segments).
cg_iters = int(sys.argv[4]) if len(sys.argv) > 4 else 40

from distributedlpsolver_tpu.backends.dense import DenseJaxBackend
from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.models.generators import random_dense_lp

print(f"building {m}x{n}...", flush=True)
t0 = time.time()
p = random_dense_lp(m, n, seed=2)  # same seed as the bench suite row
print(f"built in {time.time()-t0:.0f}s", flush=True)

# Explicit backend instance so the endgame's per-dispatch timings
# (be.endgame_timings) can be folded into the artifact after the solve.
be = DenseJaxBackend()
t0 = time.time()
r = solve(p, backend=be, max_iter=max_iter, cg_iters=cg_iters)
wall = time.time() - t0
print(
    f"RESULT: {r.status.name} obj={r.objective:.8f} iters={r.iterations} "
    f"gap={r.rel_gap:.2e} pinf={r.pinf:.2e} dinf={r.dinf:.2e} "
    f"solve={r.solve_time:.1f}s setup={r.setup_time:.1f}s wall={wall:.1f}s",
    flush=True,
)
row = {
    "config": f"random dense {m}x{n} (reference scale, BASELINE.json:9)",
    "backend": r.backend,
    "time_s": round(r.solve_time, 2),
    "iters": int(r.iterations),
    "iters_per_sec": round(r.iters_per_sec, 3),
    "status": r.status.value,
    "tol": 1e-8,
    "rel_gap": float(r.rel_gap),
    "pinf": float(r.pinf),
    "dinf": float(r.dinf),
    "setup_s": round(r.setup_time, 1),
    "wall_s": round(wall, 1),
    "phase_report": list(getattr(be, "phase_report", [])),
    "endgame_timings": getattr(be, "endgame_timings", []),
}
with open("/root/repo/BENCH_10K.json", "w") as fh:
    json.dump(row, fh, indent=2)
print(json.dumps(row), flush=True)
