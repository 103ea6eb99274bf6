"""Measured FULL-LOOP CPU baseline for the batched config (B:11):
all 1024 (128x512) members solved one at a time through `cpu-native` —
the reference's natural "one LP per rank" shape — with NO sampling or
extrapolation (review round-4 item 1 demanded a measured loop).

Wall vs process-CPU time both recorded (1-core host: a gap flags
contention, the round-4 lesson)."""
import json, resource, sys, time

import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, "/root/repo")
import numpy as np
from distributedlpsolver_tpu.backends.batched import member_interior_form
from distributedlpsolver_tpu.ipm.driver import solve
from distributedlpsolver_tpu.models.generators import random_batched_lp

B, m, n = 1024, 128, 512
batch = random_batched_lp(B, m, n, seed=0)
print(f"looping {B} members through cpu-native...", flush=True)
u0 = resource.getrusage(resource.RUSAGE_SELF)
t0 = time.time()
n_opt, iters, per = 0, 0, []
for i in range(B):
    r = solve(member_interior_form(batch, i), backend="cpu-native")
    n_opt += r.status.value == "optimal"
    iters += int(r.iterations)
    per.append(r.solve_time)
    if (i + 1) % 128 == 0:
        print(f"  {i+1}/{B}  elapsed {time.time()-t0:.1f}s", flush=True)
wall = time.time() - t0
u1 = resource.getrusage(resource.RUSAGE_SELF)
cpu_s = (u1.ru_utime - u0.ru_utime) + (u1.ru_stime - u0.ru_stime)
per = np.asarray(per)
print(f"LOOP RESULT: {n_opt}/{B} optimal, total wall {wall:.1f}s cpu {cpu_s:.1f}s "
      f"sum(solve) {per.sum():.1f}s mean {per.mean()*1e3:.1f}ms", flush=True)
with open("/root/repo/.batched_cpu_loop.json", "w") as fh:
    json.dump({"config": f"{B} x ({m}x{n}) seed=0 looped cpu-native",
               "n_optimal": int(n_opt), "B": B, "total_iters": iters,
               "wall_s": round(wall, 2), "process_cpu_s": round(cpu_s, 2),
               "sum_solve_s": round(float(per.sum()), 2),
               "mean_solve_ms": round(float(per.mean() * 1e3), 3),
               "sampled": False,
               "contention_check": "wall ~= process_cpu_s => quiet host"},
              fh, indent=1)
print("wrote .batched_cpu_loop.json", flush=True)
