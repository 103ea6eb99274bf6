"""stormG2-class decisive-win config (review round 2 item 4): a ≥20k-row,
hundreds-of-blocks sparse block-angular instance, arriving HINT-LESS like
a real MPS file. Structure detection recovers the natural partition
(256 blocks after the round-3 detector tuning — merging blocks squares
their flop share), the TPU block backend solves via the two-phase
segmented Schur path, and cpu-sparse is the baseline.

Writes /root/repo/.storm20k.json. Run with TPULP_SEG_VERBOSE=1 for live
segment progress. Optional argv: K mb nb link density max_iter.
"""
import json
import os
import sys
import time

sys.path.insert(0, "/root/repo")

# Measurement envelope: no TPU aborts (exit 4) before anything is measured.
from distributedlpsolver_tpu.utils.accel import require_tpu

require_tpu()

K, mb, nb, link = (
    (int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    if len(sys.argv) > 4 else (256, 80, 160, 48)
)
density = float(sys.argv[5]) if len(sys.argv) > 5 else 0.08
max_iter = int(sys.argv[6]) if len(sys.argv) > 6 else 120
skip_baseline = os.environ.get("STORM_SKIP_BASELINE") == "1"

from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.models.generators import block_angular_lp
from distributedlpsolver_tpu.models.structure import detect_block_structure

print(f"building K={K} {mb}x{nb} link={link} density={density}...", flush=True)
p = block_angular_lp(K, mb, nb, link, seed=3, sparse=True, density=density)
p.block_structure = None  # what a real file looks like
print(f"built {p.shape}, nnz={p.A.nnz}", flush=True)

t0 = time.time()
hint = detect_block_structure(p)
t_detect = time.time() - t0
assert hint is not None, "detection declined the structure"
print(f"detected K={hint['num_blocks']} in {t_detect:.2f}s", flush=True)
p.block_structure = hint

# Warm-up (compile) then timed solve, same discipline as bench.py.
solve(p, backend="block", max_iter=3)
t0 = time.time()
r = solve(p, backend="block", max_iter=max_iter)
wall = time.time() - t0
print(
    f"TPU block: {r.status.name} obj={r.objective:.6f} iters={r.iterations} "
    f"gap={r.rel_gap:.2e} solve={r.solve_time:.2f}s wall={wall:.1f}s",
    flush=True,
)

row = {
    "config": f"stormG2-like sparse block_angular({K},{mb}x{nb},link={link}) "
              f"hint-less, {p.shape[0]} rows",
    "backend": r.backend,
    "time_s": round(r.solve_time, 3),
    "iters": int(r.iterations),
    "iters_per_sec": round(r.iters_per_sec, 2),
    "status": r.status.value,
    "tol": 1e-8,
    "detect_s": round(t_detect, 3),
    "detected_blocks": int(hint["num_blocks"]),
    "vs_baseline": None,
}
if not skip_baseline:
    rb = solve(p, backend="cpu-sparse", max_iter=max_iter)
    print(
        f"cpu-sparse: {rb.status.name} obj={rb.objective:.6f} "
        f"iters={rb.iterations} solve={rb.solve_time:.2f}s",
        flush=True,
    )
    row["baseline_backend"] = "cpu-sparse"
    row["baseline_time_s"] = round(rb.solve_time, 3)
    row["vs_baseline"] = round(rb.solve_time / max(r.solve_time, 1e-9), 2)
with open("/root/repo/.storm20k.json", "w") as fh:
    json.dump(row, fh, indent=2)
print(json.dumps(row), flush=True)
