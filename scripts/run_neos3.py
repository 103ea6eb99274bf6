"""neos3-class UNSTRUCTURED sparse row (review round-4 item 6 /
BASELINE.json:10): a sparse LP whose random pattern defeats block-angular
detection, measured through BOTH candidate executors at 1e-8 —
`cpu-sparse` (the sparse-direct host path the auto rule routes to) and
`pdlp` (the TPU first-order backend) — so the routing decision is a
recorded measurement instead of an implicit default.

Writes /root/repo/.neos3_sparse.json.
"""
import json, resource, sys, time

sys.path.insert(0, "/root/repo")

# Measurement envelope: no TPU aborts (exit 4) before anything is measured.
from distributedlpsolver_tpu.utils.accel import require_tpu

require_tpu()
import numpy as np

m, n, density = 20000, 40000, 0.0005
from distributedlpsolver_tpu.models.generators import random_sparse_lp
from distributedlpsolver_tpu.models.problem import to_interior_form
from distributedlpsolver_tpu.models.structure import detect_block_structure
from distributedlpsolver_tpu.ipm import solve

p = random_sparse_lp(m, n, density=density, seed=0)
inf = to_interior_form(p)
print(f"built {p.A.shape}, nnz={p.A.nnz}", flush=True)
t0 = time.time()
hint = detect_block_structure(inf.A)
t_detect = time.time() - t0
print(f"detection: {hint if hint is None else 'FOUND ' + str(hint.get('num_blocks'))} "
      f"in {t_detect:.2f}s", flush=True)

out = {"config": f"unstructured sparse {m}x{n} d={density} seed=0 (neos3-class, B:10)",
       "nnz": int(p.A.nnz), "detection": None if hint is None else int(hint["num_blocks"]),
       "detect_s": round(t_detect, 3), "tol": 1e-8}

# ---- pdlp on TPU at 1e-8 (bounded budget; record where it lands) ------
import jax
if jax.default_backend() == "tpu":
    try:
        r1 = solve(p, backend="pdlp", tol=1e-4, max_iter=200000)  # warm
        t0 = time.time()
        rp = solve(p, backend="pdlp", tol=1e-8, max_iter=400000)
        out["pdlp"] = {
            "status": rp.status.value, "time_s": round(time.time() - t0, 2),
            "rel_gap": float(rp.rel_gap), "pinf": float(rp.pinf),
            "dinf": float(rp.dinf), "iters": int(rp.iterations),
            "note": "TPU restarted PDHG; 1e-8 target",
        }
    except Exception as e:  # a worker crash must not sink the CPU half
        out["pdlp"] = {"failed": f"{type(e).__name__}: {str(e)[:300]}"}
    print("pdlp:", out["pdlp"], flush=True)

# ---- cpu-sparse at 1e-8 (quiet host required) -------------------------
u0 = resource.getrusage(resource.RUSAGE_SELF)
t0 = time.time()
rc = solve(p, backend="cpu-sparse", max_iter=120)
wall = time.time() - t0
u1 = resource.getrusage(resource.RUSAGE_SELF)
out["cpu_sparse"] = {
    "status": rc.status.value, "time_s": round(rc.solve_time, 2),
    "wall_s": round(wall, 2),
    "process_cpu_s": round((u1.ru_utime - u0.ru_utime) + (u1.ru_stime - u0.ru_stime), 2),
    "objective": rc.objective, "iters": int(rc.iterations),
    "rel_gap": float(rc.rel_gap),
}
print("cpu-sparse:", out["cpu_sparse"], flush=True)

# ---- the recorded routing decision ------------------------------------
pd = out.get("pdlp", {})
winner = "cpu-sparse"
if pd.get("status") == "optimal" and pd.get("time_s", 1e30) < out["cpu_sparse"]["time_s"]:
    winner = "pdlp"
out["route_at_1e-8"] = winner
out["routing_rule"] = (
    "auto routes hint-less sparse (detection finds nothing) to cpu-sparse; "
    "measured here against pdlp at the same 1e-8 target"
)
with open("/root/repo/.neos3_sparse.json", "w") as fh:
    json.dump(out, fh, indent=1)
print("wrote .neos3_sparse.json; winner:", winner, flush=True)
