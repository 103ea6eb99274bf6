"""Steady-state timings with FORCED value readback (float(sum(r))) —
block_until_ready alone did not force execution on the remote chip used
before PR 21."""
import sys, time
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, "/root/repo")

m = int(sys.argv[1]) if len(sys.argv) > 1 else 10000
rng = np.random.default_rng(0)
B64 = jnp.asarray(rng.standard_normal((m, 2048)) / 45.0, dtype=jnp.float64)
mk = jax.jit(lambda B, eps: B @ B.T + (1.0 + eps) * jnp.eye(m, dtype=B.dtype))
rhs = jnp.asarray(rng.standard_normal(m), dtype=jnp.float64)

def tme(label, fn, argf, reps=3):
    t0 = time.perf_counter(); s = float(jnp.sum(fn(*argf(0)))); t1 = time.perf_counter()
    ts = []
    for i in range(1, reps + 1):
        t2 = time.perf_counter(); s = float(jnp.sum(fn(*argf(i)))); ts.append(time.perf_counter() - t2)
    print(f"{label}: first={t1-t0:.1f}s steady={min(ts):.3f}s (chk {s:.3e})", flush=True)

M0 = mk(B64, 0.0)
float(jnp.sum(M0))
chol = jax.jit(jnp.linalg.cholesky)
tme(f"f64 cholesky m={m}", chol, lambda i: (mk(B64, 1e-7 * i),), reps=2)
L64 = chol(M0)
cs = jax.jit(lambda L, r: jax.scipy.linalg.cho_solve((L, True), r))
tme("f64 cho_solve 1rhs", cs, lambda i: (L64, rhs + i), reps=3)
chol32 = jax.jit(lambda M: jnp.linalg.cholesky(M.astype(jnp.float32)))
tme("f32 cholesky", chol32, lambda i: (mk(B64, 1e-7 * i),), reps=2)
L32 = chol32(M0)
cs32 = jax.jit(lambda L, r: jax.scipy.linalg.cho_solve((L, True), r.astype(jnp.float32)))
tme("f32 cho_solve 1rhs", cs32, lambda i: (L32, rhs + i), reps=3)
print("DONE", flush=True)
