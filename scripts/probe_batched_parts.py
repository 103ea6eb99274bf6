"""Component-level timing of the batched f64 Mehrotra step at the
reference member shape (B=128 of 128x512) — the measurement that decides
where the df32 (float-float) layer must land (review round-4 item 1).

Every timed call varies its inputs (scale by 1+1e-6*k): the remote chip
used before PR 21 cached results of bitwise-identical dispatches, so
repeat-the-same-call microbenchmarks lied there; varying inputs costs
nothing on a local chip.
"""
import sys, time
sys.path.insert(0, "/root/repo")
import distributedlpsolver_tpu  # noqa: F401  (enables x64)
import jax
import jax.numpy as jnp
import numpy as np

from distributedlpsolver_tpu.backends.batched import _single_step, _single_start
from distributedlpsolver_tpu.backends.dense import _make_ops
from distributedlpsolver_tpu.ipm import core
from distributedlpsolver_tpu.ipm.config import SolverConfig
from distributedlpsolver_tpu.models.generators import random_batched_lp

B, m, n = 128, 128, 512
batch = random_batched_lp(B, m, n, seed=0)
dtype = jnp.float64
A = jnp.asarray(np.asarray(batch.A), dtype)
b = jnp.asarray(np.asarray(batch.b), dtype)
c = jnp.asarray(np.asarray(batch.c), dtype)
u = jnp.full((B, n), jnp.inf, dtype)
data = jax.vmap(lambda cc, bb, uu: core.make_problem_data(jnp, cc, bb, uu, dtype))(c, b, u)
cfg = SolverConfig()
params = cfg.step_params()
reg0 = jnp.full(B, 1e-10, dtype)

states = jax.jit(
    lambda A, d: jax.vmap(
        lambda a, dd: _single_start(a, dd, jnp.asarray(1e-10, dtype), params, dtype)
    )(A, d)
)(A, data)
jax.block_until_ready(states)


def timeit(name, fn, *args, reps=6):
    # Warm-up (compile), then time with a FULL value fetch: on the remote
    # chip used before PR 21 block_until_ready returned instantly for these vmapped
    # programs while np.asarray paid the real 650 ms — only fetched
    # values are trustworthy timing barriers here.
    np.asarray(fn(*args, 0))
    ts = []
    for k in range(1, reps + 1):
        t0 = time.perf_counter()
        np.asarray(fn(*args, k))
        ts.append(time.perf_counter() - t0)
    print(f"{name:42s} best {min(ts)*1e3:9.1f} ms  med {sorted(ts)[len(ts)//2]*1e3:9.1f} ms")
    return min(ts)


def scale_state(states, k):
    f = 1.0 + 1e-7 * k
    return jax.tree_util.tree_map(lambda v: v * f, states)


# --- 1. full f64 step --------------------------------------------------
@jax.jit
def full_step_f64(A, data, states, regs, k):
    st = scale_state(states, k)
    new, stats = jax.vmap(
        lambda a, d, s, rg: _single_step(a, d, s, rg, params, jnp.float64)
    )(A, data, st, regs)
    return stats.rel_gap

timeit("full f64 step", full_step_f64, A, data, states, reg0)

# --- 2. full f32-factor step (f64 state) -------------------------------
A32 = A.astype(jnp.float32)

@jax.jit
def full_step_f32factor(A, A32, data, states, regs, k):
    st = scale_state(states, k)
    new, stats = jax.vmap(
        lambda a, a32, d, s, rg: _single_step(a, d, s, rg, params, jnp.float32, a32)
    )(A, A32, data, states, regs)
    return stats.rel_gap

timeit("f32-factor step (f64 state)", full_step_f32factor, A, A32, data, states, reg0)

# --- 3. all-f32 step ---------------------------------------------------
data32 = jax.tree_util.tree_map(
    lambda v: v.astype(jnp.float32) if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating) else v,
    data,
)
states32 = jax.tree_util.tree_map(lambda v: v.astype(jnp.float32), states)
reg32 = reg0.astype(jnp.float32)

@jax.jit
def full_step_f32(A32, data32, states32, regs, k):
    st = jax.tree_util.tree_map(lambda v: v * (1.0 + 1e-6 * k), states32)
    new, stats = jax.vmap(
        lambda a, d, s, rg: _single_step(a, d, s, rg, params, jnp.float32)
    )(A32, data32, st, regs)
    return stats.rel_gap

timeit("all-f32 step", full_step_f32, A32, data32, states32, reg32)

# --- 4. factorize only (f64) ------------------------------------------
@jax.jit
def fact_f64(A, data, states, k):
    st = scale_state(states, k)

    def one(a, d, s):
        ops = _make_ops(a, jnp.asarray(1e-10, dtype), jnp.float64, 0)
        dd = core.scaling_d(s, d, params)
        L, M = ops.factorize(dd)
        return L[0, 0]

    return jax.vmap(one)(A, data, st)

timeit("factorize only (assembly+chol, f64)", fact_f64, A, data, states)

# --- 5. factorize + 6 solves (f64) ------------------------------------
@jax.jit
def fact_solve_f64(A, b, data, states, k):
    st = scale_state(states, k)

    def one(a, bb, d, s):
        ops = _make_ops(a, jnp.asarray(1e-10, dtype), jnp.float64, 0)
        dd = core.scaling_d(s, d, params)
        f = ops.factorize(dd)
        y = bb
        for _ in range(6):
            y = ops.solve(f, y)
        return y[0]

    return jax.vmap(one)(A, b, data, st)

timeit("factorize + 6 triangular solves (f64)", fact_solve_f64, A, b, data, states)

# --- 6. elementwise back-substitution block (f64), no factor/solve ----
@jax.jit
def backsub_f64(A, data, states, k):
    st = scale_state(states, k)

    def one(a, d, s):
        x, y, sdu, w, z = s
        hub = d.hub
        dd = core.scaling_d(s, d, params)
        r_p = d.b - a @ x
        r_u = hub * (d.u_f - x - w)
        r_d = d.c - a.T @ y - sdu + z
        r_xs = -x * sdu
        r_wz = -(w * z) * hub
        # back-substitution arithmetic with dy := r_p (no solve)
        h = r_d - r_xs / x + (r_wz - z * r_u) / w
        dy = r_p + a @ (dd * h)
        dx = dd * (a.T @ dy - h)
        ds = (r_xs - sdu * dx) / x
        dw = r_u - dx
        dz = hub * (r_wz - z * dw) / w
        return dx[0] + ds[0] + dw[0] + dz[0]

    return jax.vmap(one)(A, data, st)

timeit("residuals+backsub arith (f64, 1 round)", backsub_f64, A, data, states)

# --- 7. centrality backoff grid (f64) ----------------------------------
@jax.jit
def backoff_f64(data, states, k):
    st = scale_state(states, k)

    def one(d, s):
        x, y, sdu, w, z = s
        dirs = (-0.1 * x, -0.1 * sdu, -0.1 * w, -0.1 * z)
        ap, ad = core._centrality_backoff(
            jnp, s, d.hub, dirs, jnp.asarray(0.9, dtype), jnp.asarray(0.9, dtype),
            d.ncomp, params.gamma_cent,
        )
        return ap + ad

    return jax.vmap(one)(data, st)

timeit("centrality backoff grid (f64)", backoff_f64, data, states)

# --- 8. ratio tests (f64) ---------------------------------------------
@jax.jit
def ratio_f64(data, states, k):
    st = scale_state(states, k)

    def one(d, s):
        x, y, sdu, w, z = s
        a1 = core._max_step(jnp, x, -0.3 * x, w, -0.2 * w, d.hub)
        a2 = core._max_step(jnp, sdu, -0.3 * sdu, z, -0.2 * z, d.hub)
        return a1 + a2

    return jax.vmap(one)(data, st)

timeit("2x ratio test (f64)", ratio_f64, data, states)

# --- 9. df32 calibration: fused elementwise chain ---------------------
key = jax.random.PRNGKey(0)
a64 = jax.random.uniform(key, (B, n), jnp.float64) + 0.5
b64 = jax.random.uniform(jax.random.PRNGKey(1), (B, n), jnp.float64) + 0.5
c64 = jax.random.uniform(jax.random.PRNGKey(2), (B, n), jnp.float64) + 0.5

@jax.jit
def chain_f64(a, b, c, k):
    x = a * (1.0 + 1e-7 * k)
    for _ in range(10):
        x = (x * b + c) / (b + 0.5)
    return x[:, 0]

timeit("10x fused (x*b+c)/(b+.5) on (B,n) f64", chain_f64, a64, b64, c64)

a32h = a64.astype(jnp.float32); a32l = (a64 - a32h.astype(jnp.float64)).astype(jnp.float32)
b32 = b64.astype(jnp.float32); c32 = c64.astype(jnp.float32)

def two_sum(ah, al, bh, bl):
    s = ah + bh
    v = s - ah
    e = (ah - (s - v)) + (bh - v) + al + bl
    hi = s + e
    lo = e - (hi - s)
    return hi, lo

def split(a):
    t = a * 4097.0  # 2^12+1 splitter for f32
    hi = t - (t - a)
    return hi, a - hi

def two_prod(ah, al, bh, bl):
    p = ah * bh
    a1, a2 = split(ah)
    b1, b2 = split(bh)
    e = ((a1 * b1 - p) + a1 * b2 + a2 * b1) + a2 * b2
    e = e + ah * bl + al * bh
    hi = p + e
    lo = e - (hi - p)
    return hi, lo

@jax.jit
def chain_df32(ah, al, b, c, k):
    xh, xl = ah * (1.0 + 1e-7 * k), al
    d = b + 0.5
    for _ in range(10):
        ph, pl = two_prod(xh, xl, b, jnp.zeros_like(b))
        sh, sl = two_sum(ph, pl, c, jnp.zeros_like(c))
        # df32 division by plain f32: one Newton step off f32 quotient
        q = sh / d
        rh, rl = two_prod(q, jnp.zeros_like(q), d, jnp.zeros_like(d))
        # remainder = s - q*d  (df32)
        remh, reml = two_sum(sh, sl, -rh, -rl)
        xh = q + remh / d
        xl = (q - xh) + remh / d + reml / d
    return xh[:, 0]

timeit("10x same chain in df32 (two_prod/two_sum)", chain_df32, a32h, a32l, b32, c32)

@jax.jit
def chain_f32(a, b, c, k):
    x = a * (1.0 + 1e-6 * k)
    d = b + 0.5
    for _ in range(10):
        x = (x * b + c) / d
    return x[:, 0]

timeit("10x same chain f32", chain_f32, a32h, b32, c32)
print("done")
