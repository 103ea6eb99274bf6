"""First-order (restarted PDHG / "PDLP"-style) backend for huge sparse LPs.

The reference's large-sparse configs (neos3, stormG2 — BASELINE.json:10)
strain a normal-equations IPM on TPU: unstructured sparsity densifies, and
the Cholesky is the wrong tool at Mittelmann scale. The TPU-native answer
for this problem class is a matrix-free first-order method — each
iteration is two SpMV/GEMV passes plus vector arithmetic, which maps to
HBM bandwidth instead of MXU Cholesky flops and shards trivially (prior
art: MPAX, PAPERS.md:7 — patterns only, clean-room implementation).

Algorithm: primal-dual hybrid gradient on the interior form
``min cᵀx s.t. Ax = b, 0 ≤ x ≤ u`` —

    x⁺ = clip(x − τ·(c − Aᵀy), 0, u)
    y⁺ = y + σ·(b − A·(2x⁺ − x))

with the PDLP toolbox on top:

* step sizes ``τ = η/ω, σ = η·ω`` where ``η = 0.9/‖A‖₂`` (power-iteration
  estimate) and ω is the primal weight;
* Polyak–Ruppert averaging inside each restart cycle;
* adaptive restarts: restart at the average when its normalized KKT error
  beats the last restart point's by ``restart_beta``, or on a fixed long
  cycle as a safety net;
* primal-weight updates at restarts from the primal/dual movement ratio.

The whole loop — including restart bookkeeping — is one
``lax.while_loop`` device program; only final scalars return to the host.
Sparse inputs use BCOO SpMV (gather/scatter on TPU, bandwidth-bound);
dense inputs use plain GEMV.

This backend has no analogue in the reference (its sparse path is a
direct solver); it is an addition for the problem class the reference's
own benchmarks name. Accuracy: first-order methods earn their keep at
1e-4..1e-6; 1e-8 is reachable on well-conditioned problems but can take
many restarts — the default ``tol`` here is still read from the config, so
callers choose.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from distributedlpsolver_tpu.backends.base import SolverBackend, register_backend
from distributedlpsolver_tpu.ipm import core
from distributedlpsolver_tpu.ipm.config import SolverConfig
from distributedlpsolver_tpu.ipm.state import IPMState, StepStats
from distributedlpsolver_tpu.models.problem import InteriorForm


class PDHGState(NamedTuple):
    x: jnp.ndarray
    y: jnp.ndarray
    x_sum: jnp.ndarray  # running averages within the restart cycle
    y_sum: jnp.ndarray
    n_avg: jnp.ndarray
    x_restart: jnp.ndarray  # cycle start (for primal-weight updates)
    y_restart: jnp.ndarray
    err_restart: jnp.ndarray  # KKT error at the last restart point
    omega: jnp.ndarray  # primal weight
    it_cycle: jnp.ndarray


def _estimate_norm(matvec, rmatvec, n, dtype, iters: int = 30, seed=0):
    """Power iteration for ‖A‖₂ (σ_max) — sets the PDHG step size.

    ``seed`` may be a Python int or a traced int32 scalar — the batched
    bucket program threads each lane's slot index through here, so lane
    k of every dispatch runs the identical power iteration (deterministic
    per slot; the old fixed seed=0 made every lane share one start
    vector, which tied lane results to the batch layout)."""
    v = jax.random.normal(
        jax.random.PRNGKey(jnp.asarray(seed, jnp.uint32)), (n,), dtype=dtype
    )
    v = v / jnp.linalg.norm(v)

    def body(_, v):
        w = rmatvec(matvec(v))
        return w / jnp.maximum(jnp.linalg.norm(w), 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v)
    return jnp.sqrt(jnp.linalg.norm(rmatvec(matvec(v))))


def _kkt_error(matvec, rmatvec, data, x, y):
    """(pinf, dinf, gap_rel, pobj, dobj) of an (x, y) pair.

    Reduced costs split by bound structure: r = c − Aᵀy; on finite-u
    columns a negative r is priced by the upper bound (contributes r·u to
    the dual objective); on unbounded columns a negative r is dual
    infeasibility.
    """
    c, b, u_f, hub = data.c, data.b, data.u_f, data.hub
    r_p = b - matvec(x)
    r = c - rmatvec(y)
    r_neg = jnp.minimum(r, 0.0)
    dinf_vec = jnp.where(hub > 0, 0.0, r_neg)  # unbounded cols: r must be ≥ 0
    pinf = jnp.linalg.norm(r_p) / data.norm_b
    dinf = jnp.linalg.norm(dinf_vec) / data.norm_c
    pobj = c @ x
    dobj = b @ y + (hub * u_f) @ r_neg
    gap = jnp.abs(pobj - dobj) / (1.0 + jnp.abs(pobj) + jnp.abs(dobj))
    return pinf, dinf, gap, pobj, dobj


def _err_of(matvec, rmatvec, data, x, y):
    pinf, dinf, gap, _, _ = _kkt_error(matvec, rmatvec, data, x, y)
    return jnp.maximum(pinf, jnp.maximum(dinf, gap))


@functools.partial(
    jax.jit, static_argnames=("check_every", "restart_len", "restart_beta")
)
def _pdhg_solve(
    A, AT, data, x0, y0, eta, omega0, err_restart0, max_iter, tol,
    check_every=40, restart_len=2000, restart_beta=0.5,
):
    """Fused restarted-PDHG loop. ``A``/``AT`` are dense arrays or BCOO
    pytrees — both trace as ordinary jit operands, so one compiled program
    serves every problem of the same shape/sparsity pattern.

    ``omega0``/``err_restart0`` make the loop resumable: a caller driving
    bounded bursts feeds back the returned ``(omega, err_restart)`` so the
    adaptive primal weight and restart baseline survive burst boundaries
    (a fresh start passes ``omega0=1, err_restart0=inf``)."""
    matvec = lambda v: A @ v
    rmatvec = lambda v: AT @ v
    dtype = x0.dtype
    u = jnp.where(data.hub > 0, data.u_f, jnp.inf)

    def one_pdhg(x, y, omega):
        tau = eta / omega
        sigma = eta * omega
        x_new = jnp.clip(x - tau * (data.c - rmatvec(y)), 0.0, u)
        y_new = y + sigma * (data.b - matvec(2.0 * x_new - x))
        return x_new, y_new

    err0 = _err_of(matvec, rmatvec, data, x0, y0)
    st0 = PDHGState(
        x=x0, y=y0,
        x_sum=jnp.zeros_like(x0), y_sum=jnp.zeros_like(y0),
        n_avg=jnp.asarray(0.0, dtype),
        x_restart=x0, y_restart=y0,
        err_restart=jnp.minimum(jnp.asarray(err_restart0, dtype), err0),
        omega=jnp.asarray(omega0, dtype),
        it_cycle=jnp.asarray(0, jnp.int32),
    )

    def cond(carry):
        st, it, err = carry
        return (it < max_iter) & (err > tol)

    def body(carry):
        st, it, _ = carry

        # `check_every` inner PDHG steps, fully fused.
        def inner(_, xy):
            x, y = xy
            return one_pdhg(x, y, st.omega)

        x, y = jax.lax.fori_loop(0, check_every, inner, (st.x, st.y))
        x_sum = st.x_sum + x * check_every  # cheap running average proxy
        y_sum = st.y_sum + y * check_every
        n_avg = st.n_avg + check_every
        x_avg = x_sum / n_avg
        y_avg = y_sum / n_avg

        err_cur = _err_of(matvec, rmatvec, data, x, y)
        err_avg = _err_of(matvec, rmatvec, data, x_avg, y_avg)
        it_cycle = st.it_cycle + check_every

        # Restart candidate: whichever of (current, average) is better.
        use_avg = err_avg < err_cur
        x_cand = jnp.where(use_avg, x_avg, x)
        y_cand = jnp.where(use_avg, y_avg, y)
        err_cand = jnp.minimum(err_avg, err_cur)
        do_restart = (err_cand <= restart_beta * st.err_restart) | (
            it_cycle >= restart_len
        )

        # Primal-weight update at restarts (PDLP rule: ratio of movements).
        dx = jnp.linalg.norm(x_cand - st.x_restart)
        dy = jnp.linalg.norm(y_cand - st.y_restart)
        omega_new = jnp.where(
            (dx > 1e-30) & (dy > 1e-30),
            jnp.exp(0.5 * jnp.log(st.omega) + 0.5 * jnp.log(dy / dx)),
            st.omega,
        )

        st_restart = PDHGState(
            x=x_cand, y=y_cand,
            x_sum=jnp.zeros_like(x), y_sum=jnp.zeros_like(y),
            n_avg=jnp.asarray(0.0, dtype),
            x_restart=x_cand, y_restart=y_cand,
            err_restart=err_cand,
            omega=omega_new,
            it_cycle=jnp.asarray(0, jnp.int32),
        )
        st_cont = st._replace(
            x=x, y=y, x_sum=x_sum, y_sum=y_sum, n_avg=n_avg, it_cycle=it_cycle
        )
        st_new = jax.tree_util.tree_map(
            lambda a, b: jnp.where(do_restart, a, b), st_restart, st_cont
        )
        best_err = jnp.minimum(err_cand, err_cur)
        return st_new, it + check_every, best_err

    st, it, err = jax.lax.while_loop(
        cond, body, (st0, jnp.asarray(0, jnp.int32), st0.err_restart)
    )
    # Report the better of (last, average-of-cycle).
    has_avg = st.n_avg > 0
    x_avg = jnp.where(has_avg, st.x_sum / jnp.maximum(st.n_avg, 1.0), st.x)
    y_avg = jnp.where(has_avg, st.y_sum / jnp.maximum(st.n_avg, 1.0), st.y)
    err_avg = _err_of(matvec, rmatvec, data, x_avg, y_avg)
    err_cur = _err_of(matvec, rmatvec, data, st.x, st.y)
    use_avg = err_avg < err_cur
    x_fin = jnp.where(use_avg, x_avg, st.x)
    y_fin = jnp.where(use_avg, y_avg, st.y)
    return x_fin, y_fin, it, jnp.minimum(err_avg, err_cur), st.omega, st.err_restart


@register_backend("pdlp", "first-order", "pdhg")
class FirstOrderBackend(SolverBackend):
    """Restarted-PDHG execution backend (matrix-free; huge-sparse class).

    Plugs into the same driver/CLI surface as every other backend; the
    IPM-shaped ``iterate`` contract is satisfied by running a bounded
    number of PDHG sweeps per call and reporting KKT stats.
    """

    def __init__(
        self,
        mesh: Optional[jax.sharding.Mesh] = None,
        seed: Optional[int] = None,
    ):
        self._sparse = False
        self._mesh = mesh
        # Norm-estimate seed: explicit wins; else derived from the
        # problem name at setup — deterministic per request, so two
        # solves of the same instance share step sizes bit-for-bit
        # while distinct requests stop sharing one fixed seed=0.
        self._seed = seed

    def setup(self, inf: InteriorForm, config: SolverConfig) -> None:
        self._cfg = config
        # Working precision. PDHG needs no f64 operands at the accuracy it
        # targets, and on TPU an emulated-f64 GEMV materializes ~8
        # full-size f32 component copies of A (observed: a 15 GB temp for
        # ONE 10k×50k matvec — OOM where the f32 operand is 1.9 GB). Under
        # the default "auto" schedule on TPU, run everything in f32 as
        # long as the tolerance is above f32's ~1e-6 noise floor; an
        # explicit factor_dtype or a tighter tol keeps full precision.
        dtype = jnp.dtype(config.dtype)
        if config.factor_dtype == "float32" or (
            config.factor_dtype == "auto"
            and jax.default_backend() == "tpu"
            and config.tol >= 1e-6
        ):
            dtype = jnp.dtype(jnp.float32)
        self._dtype = dtype
        self._n_pad = 0
        self._col_sharding = None
        A = inf.A
        mesh_explicit = self._mesh is not None
        if self._mesh is None and config.mesh_shape is not None and not sp.issparse(A):
            # A config-supplied mesh applies to dense operands only —
            # sparse inputs keep the single-device BCOO path (this
            # backend's whole purpose is huge sparse; a shared
            # config.mesh_shape must not hijack it).
            from distributedlpsolver_tpu.parallel import make_mesh

            self._mesh = make_mesh(shape=config.mesh_shape)
        if self._mesh is not None and sp.issparse(A):
            # Only an EXPLICITLY passed mesh reaches here: densify small
            # sparse inputs, refuse ones where densification is the hazard.
            assert mesh_explicit
            if A.shape[0] * A.shape[1] > (1 << 26):
                raise ValueError(
                    "mesh-sharded pdlp supports dense operands; sparse input "
                    f"of shape {A.shape} is too large to densify "
                    "(drop the mesh to use the single-device BCOO path)"
                )
            A = np.asarray(A.todense())
        self._sparse = sp.issparse(A)
        if self._sparse:
            from jax.experimental import sparse as jsparse

            Ac = sp.coo_matrix(A)
            self._A = jsparse.BCOO(
                (jnp.asarray(Ac.data, dtype=dtype),
                 jnp.asarray(np.stack([Ac.row, Ac.col], axis=1))),
                shape=Ac.shape,
            )
            AT = Ac.T.tocoo()
            self._AT = jsparse.BCOO(
                (jnp.asarray(AT.data, dtype=dtype),
                 jnp.asarray(np.stack([AT.row, AT.col], axis=1))),
                shape=AT.shape,
            )
        else:
            A_host = np.asarray(A, dtype=dtype)
            if self._mesh is not None:
                # PDHG distributes for free under GSPMD: shard A's columns
                # (and x) over the mesh; Aᵀ shards its rows to match. The
                # GEMV in matvec then reduces partial products with one
                # all-reduce over ICI — the same dataflow as the Schur
                # psum, at O(m) volume per iteration instead of O(m²).
                from jax.sharding import NamedSharding, PartitionSpec as P

                axis = self._mesh.axis_names[0]
                n_pad = (-A_host.shape[1]) % self._mesh.shape[axis]
                if n_pad:
                    # Zero columns with +1 cost never leave x=0 under PDHG
                    # projections from a zero start; sliced off in to_host.
                    A_host = np.hstack(
                        [A_host, np.zeros((A_host.shape[0], n_pad), dtype)]
                    )
                self._n_pad = n_pad
                sh = lambda *spec: NamedSharding(self._mesh, P(*spec))
                self._A = jax.device_put(A_host, sh(None, axis))
                self._AT = jax.device_put(A_host.T.copy(), sh(axis, None))
                self._col_sharding = sh(axis)
            else:
                self._A = jnp.asarray(A_host)
                self._AT = self._A.T
        c_host = np.asarray(inf.c, dtype=np.float64)
        u_host = np.asarray(inf.u, dtype=np.float64)
        self._n_orig = inf.n
        if self._n_pad:
            # Padded zero columns: cost 1, no upper bound — PDHG's
            # projection pins them at 0 from a zero start (r = 1 > 0).
            c_host = np.concatenate([c_host, np.ones(self._n_pad)])
            u_host = np.concatenate([u_host, np.full(self._n_pad, np.inf)])
        put_col = (
            (lambda v: jax.device_put(v, self._col_sharding))
            if self._col_sharding is not None
            else jnp.asarray
        )
        self._data = core.make_problem_data(
            jnp,
            put_col(c_host.astype(dtype)),
            jnp.asarray(np.asarray(inf.b), dtype=dtype),
            put_col(u_host.astype(dtype)),
            dtype,
        )
        A_, AT_ = self._A, self._AT
        self._matvec = lambda v: A_ @ v
        self._rmatvec = lambda v: AT_ @ v
        if self._seed is not None:
            seed = int(self._seed)
        else:
            import zlib

            seed = zlib.crc32(inf.name.encode()) & 0x7FFFFFFF
        nrm = _estimate_norm(
            self._matvec, self._rmatvec, inf.n + self._n_pad, dtype,
            seed=seed,
        )
        self._eta = float(0.9 / max(float(nrm), 1e-12))
        self._it_done = 0
        self._reset_adaptive()

    def _reset_adaptive(self) -> None:
        # Adaptive PDHG state persisted ACROSS bursts (iterate calls and
        # solve_full segments): the learned primal weight and the restart
        # baseline. Discarding these at every burst boundary makes the
        # non-fused driver path converge measurably slower than one fused
        # loop on the same budget (round-1 advisor finding).
        self._omega = 1.0
        self._err_restart = float("inf")

    def _pdhg_iter_seconds(self) -> float:
        """Conservative per-inner-iteration time estimate for watchdog
        segmentation. PDHG is two matvec passes (+ periodic KKT checks ~
        two more per check_every block) — bandwidth-bound, not MXU-bound,
        so the effective flop rate is far below core.SEG_RATE_F32.
        Measured anchor: 167 it/s at 10000x50000 dense f32 (round 5)
        -> ~3.3e11 effective flops/s on 4mn flops/iter; seed at 2e11.
        Sparse BCOO SpMV gathers/scatters instead of riding the MXU —
        seed an order of magnitude lower per nonzero."""
        if self._sparse:
            return 4.0 * float(self._A.nse) / 2e10
        m, n = self._A.shape
        return 4.0 * float(m) * float(n) / 2e11

    def starting_point(self) -> IPMState:
        n = self._data.c.shape[0]
        m = self._data.b.shape[0]
        x = jnp.zeros(n, dtype=self._dtype)
        y = jnp.zeros(m, dtype=self._dtype)
        return self._wrap(x, y)

    def _wrap(self, x, y) -> IPMState:
        # Carry (x, y) through the IPMState container; s/w/z are derived
        # quantities for PDHG and reported as reduced costs at the end.
        r = self._data.c - self._rmatvec(y)
        s = jnp.maximum(r, 0.0)
        z = jnp.maximum(-r, 0.0) * (self._data.hub > 0)
        w = jnp.where(self._data.hub > 0, self._data.u_f - x, 1.0)
        return IPMState(x=x, y=y, s=s, w=w, z=z)

    def iterate(self, state: IPMState) -> Tuple[IPMState, StepStats]:
        # One driver "iteration" = a bounded PDHG burst; stats are true KKT
        # measures so the host convergence test stays meaningful. The
        # adaptive primal weight and restart baseline persist across
        # bursts (self._omega / self._err_restart).
        x, y, it, err, omega, err_restart = _pdhg_solve(
            self._A, self._AT, self._data,
            state.x, state.y,
            jnp.asarray(self._eta, self._dtype),
            jnp.asarray(self._omega, self._dtype),
            jnp.asarray(self._err_restart, self._dtype),
            jnp.asarray(400, jnp.int32),
            jnp.asarray(self._cfg.tol, self._dtype),
        )
        self._omega = float(omega)
        self._err_restart = float(err_restart)
        pinf, dinf, gap, pobj, dobj = _kkt_error(
            self._matvec, self._rmatvec, self._data, x, y
        )
        zero = jnp.asarray(0.0, self._dtype)
        stats = StepStats(
            mu=gap, gap=jnp.abs(pobj - dobj), rel_gap=gap, pinf=pinf,
            dinf=dinf, pobj=pobj, dobj=dobj, alpha_p=zero, alpha_d=zero,
            sigma=zero, bad=~jnp.isfinite(gap),
        )
        return self._wrap(x, y), stats

    def bump_regularization(self) -> bool:
        return False  # nothing to regularize

    def solve_full(self, state: IPMState):
        cfg = self._cfg
        import time as _time

        # PDHG counts iterations in the thousands; interpret the config's
        # (IPM-scaled) max_iter as bursts of 400 inner steps.
        max_inner = int(cfg.max_iter) * 400
        eta = jnp.asarray(self._eta, self._dtype)
        tol = jnp.asarray(cfg.tol, self._dtype)
        x, y = state.x, state.y
        omega = jnp.asarray(self._omega, self._dtype)
        err_restart = jnp.asarray(self._err_restart, self._dtype)
        if core.use_segments(cfg.segment_iters, jax.default_backend()):
            # Host-segmented bursts: one unbounded lax.while_loop at, say,
            # 57 s for the flagship config sat right at the ~60 s program
            # limit of the remote chip used before PR 21 (see
            # core.use_segments) — a slightly harder problem got the run
            # killed instead of returning ITERATION_LIMIT. Carry
            # (x, y, omega, err_restart) across bounded bursts instead;
            # burst length is seeded from the bandwidth estimate and then
            # adapted to the measured rate, mirroring core.drive_segments.
            if cfg.segment_iters is not None:
                burst = max(400, int(cfg.segment_iters) * 400)
            else:
                est = self._pdhg_iter_seconds()
                burst = max(400, min(40000, int(15.0 / max(est, 1e-9))))
            it_total, err, first = 0, float("inf"), True
            while it_total < max_inner:
                this = min(burst, max_inner - it_total)
                t0 = _time.perf_counter()
                x, y, it_b, err_b, omega, err_restart = _pdhg_solve(
                    self._A, self._AT, self._data, x, y, eta,
                    omega, err_restart,
                    jnp.asarray(this, jnp.int32), tol,
                )
                err_b.block_until_ready()
                dt = _time.perf_counter() - t0
                it_b, err = int(it_b), float(err_b)
                it_total += it_b
                if err <= float(cfg.tol) or it_b == 0:
                    break
                if not first:  # first burst's wall time includes compile
                    burst = max(
                        400, min(200000, int(burst * 15.0 / max(dt, 1e-3)))
                    )
                first = False
            it = jnp.asarray(it_total, jnp.int32)
        else:
            x, y, it, err, omega, err_restart = _pdhg_solve(
                self._A, self._AT, self._data, x, y, eta,
                omega, err_restart,
                jnp.asarray(max_inner, jnp.int32), tol,
            )
        self._omega = float(omega)
        self._err_restart = float(err_restart)
        pinf, dinf, gap, pobj, dobj = _kkt_error(
            self._matvec, self._rmatvec, self._data, x, y
        )
        ok = (gap <= cfg.tol) & (pinf <= cfg.tol) & (dinf <= cfg.tol)
        status = jnp.where(ok, core.STATUS_OPTIMAL, core.STATUS_MAXITER)
        zero = jnp.asarray(0.0, self._dtype)
        row = jnp.stack(
            [gap, jnp.abs(pobj - dobj), gap, pinf, dinf, pobj, dobj,
             zero, zero, zero]
        )
        # One summary stats record, but the REAL inner-iteration count —
        # the driver reports iterations from it (and caps the history read
        # at the buffer's length), so iters/sec reflects actual PDHG work.
        # Floor at 1: an immediately-optimal start (it == 0) must still
        # surface its stats row, or the result reports infinite residuals.
        buf = row[None, :]
        return self._wrap(x, y), jnp.maximum(it, 1), status, buf

    def to_host(self, state: IPMState) -> IPMState:
        n = self._n_orig
        return IPMState(
            x=np.asarray(state.x)[:n],
            y=np.asarray(state.y),
            s=np.asarray(state.s)[:n],
            w=np.asarray(state.w)[:n],
            z=np.asarray(state.z)[:n],
        )

    def from_host(self, state: IPMState) -> IPMState:
        # A restored iterate invalidates the burst-adaptive baselines.
        self._reset_adaptive()
        x, y, s, w, z = (np.asarray(v, dtype=self._dtype) for v in state)
        if self._n_pad:
            x = np.concatenate([x, np.zeros(self._n_pad, dtype=self._dtype)])
            s = np.concatenate([s, np.ones(self._n_pad, dtype=self._dtype)])
            w = np.concatenate([w, np.ones(self._n_pad, dtype=self._dtype)])
            z = np.concatenate([z, np.zeros(self._n_pad, dtype=self._dtype)])
        put = (
            (lambda v: jax.device_put(v, self._col_sharding))
            if self._col_sharding is not None
            else jnp.asarray
        )
        return IPMState(
            x=put(x), y=jnp.asarray(y), s=put(s), w=put(w), z=put(z)
        )

    def block_until_ready(self, obj) -> None:
        jax.block_until_ready(obj)


# -- bucketed batched PDHG: the serve ladder's first-order engine -----------
#
# One compiled program per (B, m, n, dtype) bucket shape — tol and
# max_iter are traced operands, so the tolerance tiers share the
# executable and a warm bucket NEVER recompiles (the same invariant as
# backends/batched._solve_bucket_jit). Each lane runs the restarted-PDHG
# loop of this module (averaging + adaptive restarts + primal-weight
# updates), vectorized over the batch with per-lane convergence masks;
# per-lane step sizes come from a slot-seeded power iteration
# (deterministic per slot — the norm-estimate seed satellite). Verdicts
# are crossover-honest: a lane is OPTIMAL only when its true KKT error
# (pinf, dinf, relative gap) passes the REQUEST tolerance.


class _PDHGLanes(NamedTuple):
    x: jnp.ndarray  # (B, n)
    y: jnp.ndarray  # (B, m)
    x_sum: jnp.ndarray
    y_sum: jnp.ndarray
    n_avg: jnp.ndarray  # (B,)
    x_restart: jnp.ndarray
    y_restart: jnp.ndarray
    err_restart: jnp.ndarray  # (B,)
    omega: jnp.ndarray  # (B,)
    it_cycle: jnp.ndarray  # (B,) int32


def _lanes_kkt(A, b, c, x, y):
    """Per-lane (pinf, dinf, gap, pobj, dobj) for bucket standard form
    (x ≥ 0, no upper bounds)."""
    r_p = b - jnp.einsum("bmn,bn->bm", A, x)
    r = c - jnp.einsum("bmn,bm->bn", A, y)
    pinf = jnp.linalg.norm(r_p, axis=1) / (
        1.0 + jnp.linalg.norm(b, axis=1)
    )
    dinf = jnp.linalg.norm(jnp.minimum(r, 0.0), axis=1) / (
        1.0 + jnp.linalg.norm(c, axis=1)
    )
    pobj = jnp.sum(c * x, axis=1)
    dobj = jnp.sum(b * y, axis=1)
    gap = jnp.abs(pobj - dobj) / (1.0 + jnp.abs(pobj) + jnp.abs(dobj))
    return pinf, dinf, gap, pobj, dobj


def _lanes_err(A, b, c, x, y):
    pinf, dinf, gap, _, _ = _lanes_kkt(A, b, c, x, y)
    return jnp.maximum(pinf, jnp.maximum(dinf, gap))


@functools.partial(
    jax.jit, static_argnames=("check_every", "restart_len", "restart_beta")
)
def _pdhg_bucket_jit(
    A, b, c, active, tol, max_iter,
    check_every=40, restart_len=2000, restart_beta=0.5,
):
    """Fused batched restarted-PDHG over one padded bucket.

    Carry: per-lane PDHG state + iteration counts + a live mask. Every
    trip runs ``check_every`` fused primal-dual steps for ALL lanes
    (finished lanes' updates are masked out), then re-measures each
    lane's KKT error and applies the restart/averaging bookkeeping
    per lane. The loop exits when no live lane remains.
    """
    B, m, n = A.shape
    dtype = A.dtype

    # Per-lane ‖A_k‖₂ from a slot-seeded power iteration (slot index IS
    # the seed — deterministic per slot across dispatches).
    def one_norm(Ak, slot):
        return _estimate_norm(
            lambda v: Ak @ v, lambda v: Ak.T @ v, n, dtype, seed=slot
        )

    nrm = jax.vmap(one_norm)(A, jnp.arange(B, dtype=jnp.int32))
    eta = 0.9 / jnp.maximum(nrm, 1e-12)

    def one_pdhg(x, y, omega, Ak, bk, ck, eta_k):
        tau = eta_k / omega
        sigma = eta_k * omega
        x_new = jnp.maximum(x - tau * (ck - Ak.T @ y), 0.0)
        y_new = y + sigma * (bk - Ak @ (2.0 * x_new - x))
        return x_new, y_new

    zB = jnp.zeros((B,), dtype=dtype)
    err0 = _lanes_err(A, b, c, jnp.zeros_like(c), jnp.zeros_like(b))
    st0 = _PDHGLanes(
        x=jnp.zeros_like(c), y=jnp.zeros_like(b),
        x_sum=jnp.zeros_like(c), y_sum=jnp.zeros_like(b),
        n_avg=zB,
        x_restart=jnp.zeros_like(c), y_restart=jnp.zeros_like(b),
        err_restart=err0,
        omega=jnp.ones((B,), dtype=dtype),
        it_cycle=jnp.zeros((B,), jnp.int32),
    )
    live0 = active & (err0 > tol)

    def cond(carry):
        st, it, err, live = carry
        return jnp.any(live)

    def body(carry):
        st, it, err, live = carry

        def inner(_, xy):
            x, y = xy
            xn, yn = jax.vmap(one_pdhg)(x, y, st.omega, A, b, c, eta)
            x = jnp.where(live[:, None], xn, x)
            y = jnp.where(live[:, None], yn, y)
            return x, y

        x, y = jax.lax.fori_loop(0, check_every, inner, (st.x, st.y))
        ce = jnp.asarray(check_every, dtype)
        x_sum = st.x_sum + x * ce
        y_sum = st.y_sum + y * ce
        n_avg = st.n_avg + ce
        x_avg = x_sum / n_avg[:, None]
        y_avg = y_sum / n_avg[:, None]

        err_cur = _lanes_err(A, b, c, x, y)
        err_avg = _lanes_err(A, b, c, x_avg, y_avg)
        it_cycle = st.it_cycle + check_every

        use_avg = err_avg < err_cur
        x_cand = jnp.where(use_avg[:, None], x_avg, x)
        y_cand = jnp.where(use_avg[:, None], y_avg, y)
        err_cand = jnp.minimum(err_avg, err_cur)
        do_restart = (err_cand <= restart_beta * st.err_restart) | (
            it_cycle >= restart_len
        )

        dx = jnp.linalg.norm(x_cand - st.x_restart, axis=1)
        dy = jnp.linalg.norm(y_cand - st.y_restart, axis=1)
        omega_new = jnp.where(
            (dx > 1e-30) & (dy > 1e-30),
            jnp.exp(0.5 * jnp.log(st.omega) + 0.5 * jnp.log(dy / dx)),
            st.omega,
        )

        rs = do_restart & live
        rcol = rs[:, None]
        st_new = _PDHGLanes(
            x=jnp.where(rcol, x_cand, x),
            y=jnp.where(rcol, y_cand, y),
            x_sum=jnp.where(rcol, jnp.zeros_like(x), x_sum),
            y_sum=jnp.where(rcol, jnp.zeros_like(y), y_sum),
            n_avg=jnp.where(rs, zB, n_avg),
            x_restart=jnp.where(rcol, x_cand, st.x_restart),
            y_restart=jnp.where(rcol, y_cand, st.y_restart),
            err_restart=jnp.where(rs, err_cand, st.err_restart),
            omega=jnp.where(rs, omega_new, st.omega),
            it_cycle=jnp.where(rs, jnp.zeros_like(it_cycle), it_cycle),
        )
        # Frozen lanes keep their previous state verbatim.
        st_new = jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                live.reshape((B,) + (1,) * (new.ndim - 1)), new, old
            ),
            st_new, st,
        )
        err_new = jnp.where(live, jnp.minimum(err_cand, err_cur), err)
        it = jnp.where(live, it + check_every, it)
        live = live & (err_new > tol) & (it < max_iter) & jnp.isfinite(err_new)
        return st_new, it, err_new, live

    st, it, err, live = jax.lax.while_loop(
        cond, body, (st0, jnp.zeros((B,), jnp.int32), err0, live0)
    )
    # Report the better of (last, cycle average) per lane.
    has_avg = st.n_avg > 0
    x_avg = jnp.where(
        has_avg[:, None], st.x_sum / jnp.maximum(st.n_avg, 1.0)[:, None], st.x
    )
    y_avg = jnp.where(
        has_avg[:, None], st.y_sum / jnp.maximum(st.n_avg, 1.0)[:, None], st.y
    )
    err_avg = _lanes_err(A, b, c, x_avg, y_avg)
    err_cur = _lanes_err(A, b, c, st.x, st.y)
    use_avg = err_avg < err_cur
    x_fin = jnp.where(use_avg[:, None], x_avg, st.x)
    y_fin = jnp.where(use_avg[:, None], y_avg, st.y)
    pinf, dinf, gap, pobj, dobj = _lanes_kkt(A, b, c, x_fin, y_fin)
    return x_fin, y_fin, it, pinf, dinf, gap, pobj


def pdhg_bucket_cache_size() -> int:
    """Compiled bucket-PDHG program count — the serve layer's
    zero-warm-recompile accounting (summed into
    backends.batched.bucket_cache_size)."""
    return int(_pdhg_bucket_jit._cache_size())


def solve_pdhg_bucket(
    batch,
    active,
    config: Optional[SolverConfig] = None,
    mesh=None,
    batch_axis: str = "batch",
    max_iter: Optional[int] = None,
    **config_overrides,
):
    """Solve one pre-padded serving bucket with batched restarted PDHG —
    the first-order engine of the tolerance-tiered serve ladder
    (requests at tol ≥ ServiceConfig.pdhg_tol route here; see
    serve/service.py).

    Mirrors :func:`backends.batched.solve_bucket`'s contract: ``batch``
    is (B, m, n)/(B, m)/(B, n) arrays already padded to the bucket
    shape, ``active`` the live-slot mask; returns a ``BatchedResult``.
    ``config.max_iter`` is interpreted as bursts of 400 inner PDHG
    steps (the same scaling as the solo backend's ``solve_full``).
    Verdicts are crossover-honest: OPTIMAL only where the final true
    KKT error meets the request tolerance — anything else is
    ITERATION_LIMIT and the service's solo ladder (IPM polish at the
    same tolerance) owns it. ``y``/``s``/``w``/``z`` are deliberately
    left None: a tol-loose PDHG iterate must not seed the warm cache
    the IPM engine draws from.
    """
    import time as _time

    from distributedlpsolver_tpu.backends.batched import (
        BatchedResult,
        place_bucket,
    )
    from distributedlpsolver_tpu.ipm.state import Status

    cfg = config or SolverConfig()
    if config_overrides:
        cfg = cfg.replace(**config_overrides)
    dtype = jnp.dtype(cfg.dtype)

    t0 = _time.perf_counter()
    if isinstance(batch.A, jax.Array) and batch.A.dtype == dtype:
        A, b, c = batch.A, batch.b, batch.c
        if not isinstance(active, jax.Array):
            # Commit a host mask against the same mesh sharding as the
            # pre-placed batch — a bare jnp.asarray pins it to the
            # default local device, which a multi-process program
            # cannot consume (see batched.solve_bucket).
            from distributedlpsolver_tpu.parallel import mesh as mesh_lib

            act_h = np.asarray(active, dtype=bool)
            if mesh is not None:
                active = jax.device_put(
                    act_h, mesh_lib.batch_sharding(mesh, 1, batch_axis)
                )
            else:
                active = jnp.asarray(act_h)
    else:
        placed, active = place_bucket(
            batch, active, cfg, mesh=mesh, batch_axis=batch_axis
        )
        A, b, c = placed.A, placed.b, placed.c
    setup_time = _time.perf_counter() - t0

    inner_cap = int(max_iter if max_iter is not None else cfg.max_iter) * 400
    t1 = _time.perf_counter()
    x, y, it, pinf, dinf, gap, pobj = _pdhg_bucket_jit(
        A, b, c, active,
        jnp.asarray(cfg.tol, dtype),
        jnp.asarray(inner_cap, jnp.int32),
    )
    jax.block_until_ready(x)
    solve_time = _time.perf_counter() - t1

    # Multi-process-safe demux (see backends/batched.solve_bucket): a
    # batch axis spanning processes rides one replicating gather
    # program; single-process meshes take the plain np.asarray path.
    from distributedlpsolver_tpu.parallel.mesh import host_values

    pinf, dinf, gap, act_h, pobj_h, x_h, it_host = host_values(
        (pinf, dinf, gap, active, pobj, x, it)
    )
    pinf = np.asarray(pinf, dtype=np.float64)
    dinf = np.asarray(dinf, dtype=np.float64)
    gap = np.asarray(gap, dtype=np.float64)
    ok = (gap <= cfg.tol) & (pinf <= cfg.tol) & (dinf <= cfg.tol)
    # Inactive (padding) slots report the same placeholder OPTIMAL as
    # solve_bucket — demux by slot and ignore them.
    ok = ok | ~act_h.astype(bool)
    status = np.array(
        [Status.OPTIMAL if o else Status.ITERATION_LIMIT for o in ok],
        dtype=object,
    )
    return BatchedResult(
        status=status,
        objective=np.asarray(pobj_h, dtype=np.float64),
        x=np.asarray(x_h, dtype=np.float64),
        iterations=it_host,
        rel_gap=gap,
        pinf=pinf,
        dinf=dinf,
        solve_time=solve_time,
        setup_time=setup_time,
        phase_report=[
            {"phase": 0, "engine": "pdhg", "tol": float(cfg.tol),
             "iters": int(it_host.max(initial=0))}
        ],
        fused_iters=40,  # check_every inner steps per while trip
    )
