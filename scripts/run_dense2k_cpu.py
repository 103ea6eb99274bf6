"""CPU baseline for the dense 2048x10240 config (review round-4 item 3):
end-to-end `cpu-native` solve to 1e-8 on the exact suite-row instance
(random_dense_lp(2048, 10240, seed=2) — bench.py's [3/6] row at the
--quick size that the suite actually times on TPU).

Records BOTH wall-clock and process CPU time: the host has one core, so
wall >> cpu_time flags a contended (invalid) measurement — round 4's
contaminated-run lesson, made mechanically checkable.
"""
import json, os, resource, sys, time

import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, "/root/repo")
from distributedlpsolver_tpu.ipm import solve
from distributedlpsolver_tpu.models.generators import random_dense_lp

m, n = 2048, 10240
print("building...", flush=True)
p = random_dense_lp(m, n, seed=2)
print(f"built {p.shape}", flush=True)
u0 = resource.getrusage(resource.RUSAGE_SELF)
t0 = time.time()
r = solve(p, backend="cpu-native", verbose=True, max_iter=100)
wall = time.time() - t0
u1 = resource.getrusage(resource.RUSAGE_SELF)
cpu_s = (u1.ru_utime - u0.ru_utime) + (u1.ru_stime - u0.ru_stime)
print(f"CPU-NATIVE RESULT: {r.status.name} obj={r.objective:.6f} "
      f"iters={r.iterations} gap={r.rel_gap:.2e} solve={r.solve_time:.1f}s "
      f"wall={wall:.1f}s cpu={cpu_s:.1f}s", flush=True)
with open("/root/repo/.dense2k_cpu.json", "w") as fh:
    json.dump({"config": f"random dense {m}x{n} seed=2", "backend": "cpu-native",
               "status": r.status.value, "objective": r.objective,
               "iters": int(r.iterations), "rel_gap": r.rel_gap,
               "solve_s": round(r.solve_time, 2), "wall_s": round(wall, 2),
               "process_cpu_s": round(cpu_s, 2),
               "contention_check": "wall ~= process_cpu_s => quiet host"},
              fh, indent=1)
print("wrote .dense2k_cpu.json", flush=True)
