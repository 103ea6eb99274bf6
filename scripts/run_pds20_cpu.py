"""CPU baseline for the pds-20-class block-angular config (review item 2):
the sparse-direct CPU backend on the ~30k-row K=64 instance."""
import json, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, "/root/repo")
from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.models.generators import block_angular_lp

K, mb, nb, link = 64, 432, 1400, 1600
print("building...", flush=True)
p = block_angular_lp(K, mb, nb, link, seed=0, sparse=True, density=0.005)
print(f"built {p.shape}, nnz={p.A.nnz}", flush=True)
t0 = time.time()
r = solve(p, backend="cpu-sparse", verbose=True, max_iter=120)
wall = time.time() - t0
print(f"CPU-SPARSE RESULT: {r.status.name} obj={r.objective:.6f} iters={r.iterations} "
      f"gap={r.rel_gap:.2e} solve={r.solve_time:.1f}s wall={wall:.1f}s", flush=True)
with open("/root/repo/.pds20_cpu_baseline.json", "w") as fh:
    json.dump({"backend": "cpu-sparse", "status": r.status.value,
               "objective": r.objective, "iters": int(r.iterations),
               "solve_s": round(r.solve_time, 2)}, fh)
