"""Corrected-timing probe of the PCG phase-2 pieces at reference scale.
All timings force value readback; repeated ops run inside ONE jit via
fori_loop so dispatch latency doesn't mask per-op cost."""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, "/root/repo")
from distributedlpsolver_tpu.backends import dense as D
from distributedlpsolver_tpu.ops import normal_eq_pallas, pad_for_pallas

m, n = (int(sys.argv[1]), int(sys.argv[2])) if len(sys.argv) > 2 else (10000, 50000)
rng = np.random.default_rng(0)
print(f"shape {m}x{n}", flush=True)
A64 = jnp.asarray(rng.standard_normal((m, n)) / np.sqrt(n), dtype=jnp.float64)
Af = pad_for_pallas(A64.astype(jnp.float32))
d64 = jnp.asarray(10.0 ** rng.uniform(-5, 5, size=n), dtype=jnp.float64)
v0 = jnp.asarray(rng.standard_normal(m), dtype=jnp.float64)


def t_run(label, fn, *args, reps=2):
    t0 = time.perf_counter()
    s = float(jnp.sum(fn(*args)))
    t1 = time.perf_counter()
    ts = []
    for _ in range(reps):
        t2 = time.perf_counter()
        s = float(jnp.sum(fn(*args)))
        ts.append(time.perf_counter() - t2)
    print(f"{label}: first={t1 - t0:.1f}s steady={min(ts):.3f}s (chk {s:.3e})",
          flush=True)


asm = jax.jit(lambda Af, d: normal_eq_pallas(Af, d.astype(jnp.float32), out_m=m))
t_run("pallas f32 assembly", asm, Af, d64)


@jax.jit
def chol_prep(Af, d):
    M = normal_eq_pallas(Af, d.astype(jnp.float32), out_m=m)
    dg = jnp.diagonal(M)
    s = jax.lax.rsqrt(jnp.maximum(dg, 1e-30))
    Ms = M * s[:, None] * s[None, :] + 1e-8 * jnp.eye(m, dtype=M.dtype)
    L = jnp.linalg.cholesky(Ms)
    return D._tri_inv_paneled(L)


t_run("f32 asm+chol+paneled-Linv", chol_prep, Af, d64)


@jax.jit
def gemv20(v):
    def body(i, v):
        w = D._matvec_chunked(A64, d64 * D._rmatvec_chunked(A64, v))
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    return jax.lax.fori_loop(0, 20, body, v)


t_run("20x f64 chunked GEMV pair", gemv20, v0, reps=1)
print("PROBE DONE", flush=True)
