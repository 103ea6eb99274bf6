"""Batched solver: many independent small LPs as one device program.

BASELINE.json:11 names the workload — 1024 independent (m=128, n=512)
problems solved concurrently. The reference plausibly loops problems over
ranks (SURVEY.md §2 "Batched solver"); the TPU-native design makes the
batch a *first-class array axis*: the Mehrotra step is ``vmap``-ed over
the batch, the outer iteration is a ``lax.while_loop`` on device, and
per-problem convergence is handled by masking (never early exit — shapes
stay static, SURVEY.md §7 "ragged convergence ... masking, not early
exit"). The whole solve — every iteration of every problem — is ONE
compiled XLA program; nothing crosses the host boundary until the final
states come back.

Batch parallelism over a mesh (SURVEY.md §2.2: batch-axis sharding *is*
the data parallelism of this domain) falls out of placement: shard the
leading axis of (A, b, c) over the mesh and the same compiled program
runs B/K problems per device with no per-iteration collectives at all —
the only cross-device reduction is the cheap ``any(active)`` loop
predicate.

Converged problems are frozen by masking rather than dropped: their
iterates stay exactly at the accepted solution while stragglers continue.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributedlpsolver_tpu.backends.dense import _make_ops
from distributedlpsolver_tpu.ipm import core
from distributedlpsolver_tpu.ipm.config import SolverConfig
from distributedlpsolver_tpu.ipm.state import IPMState, Status
from distributedlpsolver_tpu.models.generators import BatchedLP
from distributedlpsolver_tpu.obs import metrics as obs_metrics
from distributedlpsolver_tpu.parallel import mesh as mesh_lib

_RUNNING, _OPTIMAL, _MAXITER, _NUMERR = 0, 1, 2, 3
_STALL = 6  # aligned with core.STATUS_STALL


@dataclasses.dataclass
class BatchedResult:
    """Per-problem outcomes of a batched solve."""

    status: np.ndarray  # (B,) Status values
    objective: np.ndarray  # (B,)
    x: np.ndarray  # (B, n)
    iterations: np.ndarray  # (B,)
    rel_gap: np.ndarray  # (B,)
    pinf: np.ndarray  # (B,)
    dinf: np.ndarray  # (B,)
    solve_time: float = 0.0
    setup_time: float = 0.0
    # Per-phase iters/wall rows (segmented path; per CHUNK when chunked) —
    # the utilization split the scale artifacts record. Bucket solves
    # fill it with their precision-schedule rows
    # ({"phase", "engine", "tol", "iters"}) instead.
    phase_report: Optional[list] = None
    # Iterations fused per while-loop trip of the device loop (the
    # serve telemetry's fused-iterations-per-dispatch figure).
    fused_iters: int = 1
    # Full final iterates of the bucket path (the warm cache stores the
    # complete (x, y, s, w, z) per member); None where not populated.
    y: Optional[np.ndarray] = None
    s: Optional[np.ndarray] = None
    w: Optional[np.ndarray] = None
    z: Optional[np.ndarray] = None
    # Per-slot warm-start acceptance (bucket path): True where a warm
    # iterate was offered AND survived the in-program safeguard; False
    # for cold slots and safeguard fallbacks. None off the bucket path.
    warm_used: Optional[np.ndarray] = None

    @property
    def n_optimal(self) -> int:
        return int(np.sum(self.status == Status.OPTIMAL))


def _single_step(A, data, state, reg, params, factor_dtype, Af=None,
                 cg_iters=0, cg_tol=0.0):
    # Af: loop-invariant precast copy — with a low-precision factor_dtype
    # the O(m²n) normal-equations assembly then runs at that precision on
    # the MXU instead of in emulated f64 (see dense._cholesky_ops).
    # cg_iters > 0 selects the PCG ops (f32 preconditioner + matrix-free
    # full-precision CG — dense._pcg_ops, everything traceable, so the
    # whole solve vmaps over the batch).
    ops = _make_ops(A, reg, factor_dtype, 0, False, Af, cg_iters, cg_tol)
    return core.mehrotra_step(ops, data, params, state)


def _single_start(A, data, reg, params, factor_dtype, Af=None):
    ops = _make_ops(A, reg, factor_dtype, 0, False, Af)
    return core.starting_point(ops, data, params)


def _batched_phase(
    A, data, carry, params, max_iter, max_refactor, reg_grow, fdt,
    it_stop=None, stall_window=0, stall_status=_RUNNING, A32=None,
    cg_iters=0, cg_tol=0.0, fuse_iters=1,
):
    """One masked batched IPM while_loop phase over the whole batch.

    ``carry = (states, active, it, regs, badcount, status, iters, best,
    since)``; ``it`` is phase-local, ``iters`` counts accepted steps per
    problem globally, ``best``/``since`` drive per-problem stall detection
    (``stall_window`` accepted steps without 10% improvement in
    max(gap,pinf,dinf) deactivates a problem with ``stall_status`` — in a
    non-final phase that's _RUNNING, so the next phase picks it up; without
    this, f32-stalled problems grind the whole max_iter budget).
    ``it_stop`` (traced) additionally bounds this call for host
    segmentation (core.drive_segments' watchdog guard).

    ``fuse_iters`` (static) > 1 fuses that many masked micro-steps into
    ONE while-loop trip via an inner ``fori_loop``: each micro-step
    re-evaluates the loop guard itself and commits its writes only under
    it, so results are bitwise-identical in k while the while predicate
    — the only cross-device collective of a mesh-sharded batch — and the
    loop bookkeeping run k× less often. At most k−1 guarded no-op steps
    are wasted where a block straddles the finish.
    """
    B = A.shape[0]

    def guard(active, it):
        go = jnp.any(active) & (it < max_iter)
        if it_stop is not None:
            go = go & (it < it_stop)
        return go

    def cond(carry):
        _, active, it, *_ = carry
        return guard(active, it)

    def body(carry):
        states, active, it, regs, badcount, status, iters, best, since = carry
        if A32 is not None:
            new_states, stats = jax.vmap(
                lambda a, a32, d, st, rg: _single_step(
                    a, d, st, rg, params, fdt, a32, cg_iters, cg_tol
                )
            )(A, A32, data, states, regs)
        else:
            new_states, stats = jax.vmap(
                lambda a, d, st, rg: _single_step(
                    a, d, st, rg, params, fdt, None, cg_iters, cg_tol
                )
            )(A, data, states, regs)
        bad = stats.bad
        conv = (
            (stats.rel_gap <= params.tol)
            & (stats.pinf <= params.tol)
            & (stats.dinf <= params.tol)
        )
        accept = active & ~bad
        # Freeze non-accepted problems component-wise.
        states = jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                accept.reshape((B,) + (1,) * (new.ndim - 1)), new, old
            ),
            new_states,
            states,
        )
        iters = iters + accept.astype(jnp.int32)
        # Per-problem regularization escalation on failed factorizations.
        regs = jnp.where(active & bad, jnp.maximum(regs, 1e-12) * reg_grow, regs)
        badcount = jnp.where(active & bad, badcount + 1, badcount)
        give_up = badcount > max_refactor
        newly_opt = accept & conv
        err = jnp.maximum(stats.rel_gap, jnp.maximum(stats.pinf, stats.dinf))
        improved = accept & (err < 0.9 * best)
        best = jnp.where(improved, err, best)
        since = jnp.where(
            active & ~bad, jnp.where(improved, 0, since + 1), since
        )
        if stall_window:
            stalled = active & (since > stall_window)
            if stall_status == _STALL:
                # Final phase: near-tol plateaus deserve patience — only
                # give up while still far (>1e3·tol) from tolerance.
                stalled = stalled & (best > 1e3 * params.tol)
        else:
            stalled = jnp.zeros_like(active)
        status = jnp.where(newly_opt, _OPTIMAL, status)
        status = jnp.where(active & give_up, _NUMERR, status)
        status = jnp.where(stalled & ~newly_opt & ~give_up, stall_status, status)
        active = active & ~newly_opt & ~give_up & ~stalled
        return states, active, it + 1, regs, badcount, status, iters, best, since

    if fuse_iters > 1:
        def micro(carry):
            # The while cond admits the whole k-block; each micro-step
            # re-checks the same guard on its own carry and commits only
            # under it — the guarded tail steps are exact no-ops, so the
            # fused loop's accepted-state sequence matches k=1 bitwise.
            go = guard(carry[1], carry[2])
            new = body(carry)
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(go, n, o), new, carry
            )

        fused_body = lambda c: jax.lax.fori_loop(
            0, fuse_iters, lambda _, cc: micro(cc), c
        )
        return jax.lax.while_loop(cond, fused_body, carry)
    return jax.lax.while_loop(cond, body, carry)


@functools.partial(
    jax.jit,
    static_argnames=(
        "params", "factor_dtype", "stall_window", "stall_status",
        "cg_iters", "cg_tol", "fuse_iters",
    ),
    # The carry is consumed: drive_segments rebinds it on every segment
    # and nothing reads the old one, so the (B, n)/(B, m, m) state
    # buffers recycle in place instead of doubling peak device memory.
    # A/data are loop-invariant across segments and must NOT donate.
    donate_argnums=(2,),
)
def _batched_segment_jit(
    A, data, carry, it_stop, max_iter, max_refactor, reg_grow, params,
    factor_dtype, stall_window=0, stall_status=_RUNNING, A32=None,
    cg_iters=0, cg_tol=0.0, fuse_iters=1,
):
    out = _batched_phase(
        A, data, carry, params, max_iter, max_refactor, reg_grow,
        jnp.dtype(factor_dtype), it_stop, stall_window, stall_status, A32,
        cg_iters, cg_tol, fuse_iters,
    )
    # Packed [it, status, n_active, n_unfinished] in core.drive_segments'
    # meta layout (one device→host transfer per segment — separate scalar
    # fetches cost a device round trip each). Per-problem statuses/stall
    # live inside the loop, so the batch-level "status" is just the
    # all-settled predicate; the active and total-unfinished counts ride
    # the best_err/since slots for tail-extraction early stops.
    f = A.dtype
    settled = jnp.where(jnp.any(out[1]), core.STATUS_RUNNING, core.STATUS_OPTIMAL)
    unfinished = jnp.sum(out[5] != _OPTIMAL)
    meta = jnp.stack(
        [out[2].astype(f), settled.astype(f), jnp.sum(out[1]).astype(f),
         unfinished.astype(f)]
    )
    return out, meta


@functools.partial(jax.jit, static_argnames=("factor_dtype",))
def _batched_norms_jit(A, data, states, factor_dtype):
    fdt = jnp.dtype(factor_dtype)

    def final_norms(a, d, st):
        ops = _make_ops(a, jnp.asarray(0.0, a.dtype), fdt, 0)
        pinf, dinf, _, rel_gap, pobj, _, _ = core.residual_norms(ops, d, st)
        return pinf, dinf, rel_gap, pobj

    return jax.vmap(final_norms)(A, data, states)


@functools.partial(jax.jit, static_argnames=("params", "factor_dtype"))
def _batched_start_jit(A, data, reg0, params, factor_dtype):
    fdt = jnp.dtype(factor_dtype)
    return jax.vmap(lambda a, d: _single_start(a, d, reg0, params, fdt))(A, data)


@functools.partial(
    jax.jit,
    static_argnames=(
        "params", "params_p1", "factor_dtype", "two_phase", "stall_window",
        "cg_iters", "cg_tol", "fuse_iters",
    ),
)
def _solve_batched_jit(
    A, data, reg0, params, params_p1, max_iter, max_refactor, reg_grow,
    factor_dtype, two_phase, stall_window=0, cg_iters=0, cg_tol=0.0,
    fuse_iters=1,
):
    # max_iter / max_refactor / reg_grow are traced scalars so one compile
    # serves every iteration-limit config (warm-up shares the timed compile).
    # With ``two_phase`` the batch first runs with f32 factorizations to the
    # handoff tolerance (params_p1.tol), then every problem — including
    # phase-1 "optimal"/"numerical-error"/stalled ones, whose verdicts are
    # provisional — re-enters a full-precision loop warm-started from its
    # phase-1 iterate (same design as the dense two-phase, SURVEY.md §7;
    # each phase has its own ``max_iter`` budget). Per-problem stall
    # detection keeps f32-stalled members from grinding the whole budget.
    fdt = jnp.dtype(factor_dtype)
    B = A.shape[0]
    dtype = A.dtype
    # Loop-invariant f32 copy for f32 factorizations AND their assembly
    # (without it the O(m²n) assembly runs emulated-f64) — used by the
    # two-phase first phase, the PCG middle phase's preconditioner, and
    # an explicit single-phase f32 config.
    f32 = jnp.dtype(jnp.float32)
    A32 = A.astype(f32) if (two_phase or fdt == f32 or cg_iters) else None
    # The starting point stays at full precision even under two-phase: it
    # is ONE factorization amortized over the whole solve, and an f32
    # Mehrotra least-squares start can be bad enough on an ill-conditioned
    # member to strand its entire trajectory (observed: a problem that
    # solves solo in 16 iterations stalls at gap 6e-2 from an f32 start).
    states0 = jax.vmap(lambda a, d: _single_start(a, d, reg0, params, fdt))(
        A, data
    )

    carry = _fresh_batch_carry(states0, jnp.zeros(B, jnp.int32), B, reg0, dtype)
    if two_phase:
        carry = _batched_phase(
            A, data, carry, params_p1, max_iter, max_refactor, reg_grow,
            jnp.dtype(jnp.float32), None, stall_window, _RUNNING, A32,
            fuse_iters=fuse_iters,
        )
        # keep states + per-problem iters; reset provisional verdicts
        carry = _fresh_batch_carry(carry[0], carry[6], B, reg0, dtype)
    if cg_iters:
        # PCG middle phase at FULL tolerance: f32 preconditioner + f64
        # matrix-free CG — no emulated-f64 assembly or Cholesky. Its
        # OPTIMAL verdicts are final (honest f64 residuals); only
        # stalled/unconverged members re-enter the f64 finish.
        carry = _batched_phase(
            A, data, carry, params, max_iter, max_refactor, reg_grow,
            jnp.dtype(jnp.float32), None, stall_window, _RUNNING, A32,
            cg_iters, cg_tol, fuse_iters,
        )
        carry = _fresh_batch_carry(
            carry[0], carry[6], B, reg0, dtype, status=carry[5]
        )
    states, active, _, _, _, status, iters, _, _ = _batched_phase(
        A, data, carry, params, max_iter, max_refactor, reg_grow, fdt,
        None, 2 * stall_window if stall_window else 0, _STALL,
        A32 if fdt == f32 else None, fuse_iters=fuse_iters,
    )
    status = jnp.where(status == _RUNNING, _MAXITER, status)

    # Final per-problem diagnostics.
    def final_norms(a, d, st):
        ops = _make_ops(a, jnp.asarray(0.0, dtype), fdt, 0)
        pinf, dinf, _, rel_gap, pobj, _, _ = core.residual_norms(ops, d, st)
        return pinf, dinf, rel_gap, pobj

    pinf, dinf, rel_gap, pobj = jax.vmap(final_norms)(A, data, states)
    return states, status, iters, pinf, dinf, rel_gap, pobj


_CHUNK_DEFAULT = 256  # per-device-program batch slice; see solve_batched


def _cleanup_cap(B: int) -> int:
    """Max members the solo-cleanup pass will re-solve — ONE definition,
    shared by tail extraction's early stop (which promises every abandoned
    member a cleanup solve) and the cleanup gate itself."""
    return max(4, B // 8)


# Backend name the solo-cleanup pass re-solves through — exported so a
# warm-up (bench.py) can pre-compile the exact path cleanup will take.
CLEANUP_BACKEND = "tpu"


# Member size (m·n entries) above which the multi-phase schedules can pay
# for themselves in the batched loop. MEASURED at the reference batched
# config (B=256 of 128×512 members, one chip, 2026-07-31):
#     single-phase f64 direct      39.6 s   <- auto
#     two-phase (all-f32 phase 1)  60.7 s
#     two-phase (f32 factor only)  66.8 s
#     PCG middle phase            575   s   (and its chunk>=256 programs
#                                            crash the current TPU worker)
# Small members invert every large-scale intuition: the per-iteration
# factorization is microseconds of MXU work, the real cost is ELEMENTWISE
# emulated-f64 arithmetic (~100 ns/element measured — a 648 ms f64 step
# vs 108 ms all-f32 at B=128), and a phase-1 handoff at 3e-5 does NOT cut
# the f64 finish's iteration count enough to amortize the phase's own
# cost (observed: 27 f64 iterations after handoff vs ~30 from scratch).
# PCG is strictly worse: it multiplies the elementwise work per solve.
# Both schedules only win where the f64 FACTORIZATION is the wall (dense
# 10k-scale); below this threshold auto runs the single-phase f64 loop.
_PHASED_MEMBER_ENTRIES = 1 << 24


def _phase_plan(cfg: SolverConfig, member_entries: Optional[int] = None):
    """(two_phase, use_pcg, n_phases) — the batched loop's phase schedule,
    ONE definition shared by solve_batched and the cleanup-budget helper
    so the per-problem iteration budget (n_phases·max_iter) cannot
    silently diverge from the schedule that spends it.

    ``member_entries`` (m·n of ONE member) gates the auto phase rules; None
    (the cleanup-budget helper, which has no batch in hand) assumes the
    reference batched class — small members, single phase."""
    phased_pays = (
        member_entries is not None and member_entries >= _PHASED_MEMBER_ENTRIES
    )
    two_phase = cfg.two_phase_enabled(jax.default_backend()) and phased_pays
    use_pcg = cfg.cg_iters > 0 and (
        cfg.solve_mode == "pcg" or (cfg.solve_mode is None and two_phase)
    )
    return two_phase, use_pcg, 1 + (1 if two_phase else 0) + (1 if use_pcg else 0)


def cleanup_solo_max_iter(config: Optional[SolverConfig] = None,
                          member_entries: Optional[int] = None,
                          typical_spent: int = 40) -> int:
    """The ``max_iter`` a typical solo-cleanup solve runs with (cleanup
    budget = n_phases·max_iter − iterations already spent in the batched
    loop, via the shared :func:`_phase_plan`). Compile-cache buckets
    (core.buffer_cap) are keyed by this figure, so a warm-up must use it —
    a hardcoded number silently compiles a never-reused executable
    whenever the defaults move. Pass the batch's ``member_entries``
    (m·n of one member) so the phase count matches the member-gated
    schedule the real solve will run."""
    cfg = config or SolverConfig()
    _, _, n_phases = _phase_plan(cfg, member_entries=member_entries)
    return max(1, n_phases * cfg.max_iter - typical_spent)


def _fresh_batch_carry(states, iters, B, reg0, dtype, status=None):
    """Phase-boundary carry reset. With ``status=None`` every member
    re-enters the next phase (the f32 phase-1 reset: its verdicts are
    provisional — tol was loosened). Passing the previous phase's status
    keeps _OPTIMAL members SETTLED: a full-tolerance phase (the PCG
    middle phase) judged them with honest f64 residuals, so re-running
    them through the f64 finish would burn its per-iteration cost on
    already-final members."""
    if status is None:
        active = jnp.ones(B, dtype=bool)
        status = jnp.full(B, _RUNNING, jnp.int32)
    else:
        active = status != _OPTIMAL
        status = jnp.where(status == _OPTIMAL, _OPTIMAL, _RUNNING)
    return (
        states,
        active,
        jnp.asarray(0, jnp.int32),
        jnp.full(B, reg0, dtype=dtype),
        jnp.zeros(B, jnp.int32),
        status,
        iters,
        jnp.full(B, jnp.inf, dtype=dtype),
        jnp.zeros(B, jnp.int32),
    )


def _cast_batch_carry(carry, dtype):
    """Cast the batched carry's floating leaves (state, regs, best) to
    ``dtype`` across an f32-phase boundary; integer/bool lanes (active,
    counters, status) pass through untouched."""
    states, active, it, regs, badcount, status, iters, best, since = carry
    cast = lambda v: v.astype(dtype)
    states = jax.tree_util.tree_map(cast, states)
    return (states, active, it, cast(regs), badcount, status, iters,
            cast(best), since)


_COMPACT_FLOOR = 32  # smallest compacted program size


def _compact_gather(carry, order, keep_idx, new_size, B):
    """Gather the ``keep_idx`` members of a batched carry into a
    ``new_size`` program (padding by repeating the first kept member,
    padded entries forced inactive/settled with sentinel scatter target
    ``B`` so they can never write back)."""
    states, active, it, regs, badcount, status, iters, best, since = carry
    k = len(keep_idx)
    pad = np.full(new_size - k, keep_idx[0] if k else 0, np.int64)
    sel = jnp.asarray(np.concatenate([keep_idx, pad]))
    valid = jnp.arange(new_size) < k
    g = lambda v: v[sel]
    carry2 = (
        jax.tree_util.tree_map(g, states),
        g(active) & valid,
        it,
        g(regs),
        g(badcount),
        jnp.where(valid, g(status), _OPTIMAL),
        g(iters),
        g(best),
        g(since),
    )
    order2 = jnp.where(valid, order[sel], B)
    return carry2, order2, sel


def _scatter_out(outs, order, carry):
    """Scatter a (possibly compacted) carry's per-member lanes into the
    full-size out buffers (one sentinel row at index B absorbs pads)."""
    states_out, status_out, iters_out = outs
    states, _, _, _, _, status, iters, _, _ = carry
    states_out = jax.tree_util.tree_map(
        lambda o, v: o.at[order].set(v), states_out, states
    )
    return states_out, status_out.at[order].set(status), iters_out.at[order].set(iters)


def _solve_batched_segmented(
    A, data, cfg, params, params_p1, fname, two_phase, seg, cg=(0, 0.0),
    compact_ok=False, fuse_iters=1,
):
    """Host-segmented batched solve: same phases as _solve_batched_jit but
    each device program is bounded to ~15s (the segmenting rule of
    core.use_segments — long fused batched solves tripped the ~60s
    program limit of the remote chip used before PR 21).

    ``compact_ok`` additionally enables FINAL-phase compaction: whenever
    the active-member count falls to half the current program size, the
    still-active members are gathered into a half-size program
    (B → B/2 → … → 32) and the loop continues there. Rationale
    (measured, 2026-08-01): the masked whole-batch loop runs to the
    slowest member — ~62 accepted steps per 256-chunk while the MEAN
    member needs 16, so ~60% of step compute was spent advancing frozen
    members. Program sizes are fixed halvings, so each size compiles
    once and is reused by every chunk. Disabled under a mesh (the batch
    axis is sharded; gathers would reshard it)."""
    B = A.shape[0]
    dtype = A.dtype
    f32 = jnp.float32
    reg0 = jnp.asarray(cfg.reg_dual, dtype)
    mi = jnp.asarray(cfg.max_iter, jnp.int32)
    mr = jnp.asarray(cfg.max_refactor, jnp.int32)
    rg = jnp.asarray(cfg.reg_grow, dtype)
    cgi, cgt = cg
    A32 = (
        A.astype(f32)
        if (two_phase or fname == "float32" or cgi)
        else None
    )
    # Phase 1 runs ENTIRELY in f32 — state, residuals, ratio tests,
    # backoff, not just the factorization. Measured at the reference
    # batched member shape (B=128 of 128×512): a full step with f64
    # state costs 578 ms (f64-factor) / 121 ms (f32-factor) while the
    # MXU dots in it are microseconds — the cost is ELEMENTWISE
    # emulated-f64 arithmetic over the (B, n) vectors (~100 ns/element:
    # divisions in scaling_d and the ratio tests, the (B, 24, n)
    # backoff grid, residual updates). f32 elementwise is native VPU
    # work, so the f32 phase's per-iteration cost drops by an order of
    # magnitude, and the f64 finish only pays the emulation tax for the
    # last 3 orders of magnitude. The f32 noise floor (~1e-6 relative)
    # sits safely below the 3e-5 handoff tolerance that phase-1 params
    # already encode.
    data32 = (
        jax.tree_util.tree_map(
            lambda v: v.astype(f32)
            if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating)
            else v,
            data,
        )
        if two_phase
        else None
    )
    # Starting point at the resolved factor dtype (== full dtype under the
    # auto two-phase schedule) — see _solve_batched_jit for why an f32
    # start under two-phase is dangerous.
    states0 = _batched_start_jit(A, data, reg0, params, fname)

    # Phase tuples: (step params, factor dtype, stall window, stall
    # status, cg iters, keep-optimal-at-exit, f32-state). The PCG middle
    # phase runs at FULL tolerance, so its optimal verdicts survive the
    # boundary; the f32 phase-1 verdicts are provisional and reset.
    w = cfg.stall_window
    phases = []
    if two_phase:
        phases.append((params_p1, "float32", w, _RUNNING, 0, False, True))
    if cgi:
        phases.append((params, "float32", w, _RUNNING, cgi, True, False))
    phases.append((params, fname, 2 * w if w else 0, _STALL, 0, False, False))
    carry = _fresh_batch_carry(states0, jnp.zeros(B, jnp.int32), B, reg0, dtype)
    # Tail extraction: a handful of stragglers would otherwise keep the
    # full-batch masked loop running at whole-batch cost per iteration.
    # Once ≤ tail problems are active in the FINAL phase, stop — the
    # leftover problems finish solo through the dense path (solve_batched
    # cleanup), warm-started from their batched iterates. tail = B//32 is
    # 0 for small batches (no extraction — a lone member might converge in
    # the very next segment), and the stop also requires the TOTAL
    # unfinished count to fit the solo-cleanup bound, so an abandoned
    # problem is never left without its cleanup solve.
    tail = B // 32
    cleanup_cap = _cleanup_cap(B)
    phase_report = []  # same shape as drive_phase_plan's report rows
    for pi, (p, f, win, wstat, pcgi, keep_opt, f32_state) in enumerate(phases):
        final = pi == len(phases) - 1
        t_ph = time.perf_counter()
        if f32_state:
            # Enter the all-f32 phase: cast the CARRY's state and float
            # trackers down; the phase program then sees f32 arrays
            # everywhere and every op in the step runs native-f32.
            carry = _cast_batch_carry(carry, f32)
            Ap, datap, A32p = A32, data32, None  # factor from Ap itself
        else:
            if carry[0].x.dtype != dtype:  # leaving the f32 phase
                carry = _cast_batch_carry(carry, dtype)
            Ap, datap = A, data
            A32p = A32 if f == "float32" else None

        def mk_run_seg(Ax, dx, A32x, _p=(p, f, win, wstat, pcgi)):
            pp, ff, w, ws, ci = _p

            def run_seg(c, stop):
                # reg_grow cast to the PHASE dtype: an f64 scalar would
                # promote the f32 carry's regs lane out of its
                # while_loop carry type.
                return _batched_segment_jit(
                    Ax, dx, c, jnp.asarray(stop, jnp.int32), mi, mr,
                    rg.astype(Ax.dtype), pp, ff, w, ws, A32x, ci,
                    cgt if ci else 0.0, fuse_iters,
                )

            return run_seg

        # Batch-level stall/status live per problem inside the device loop;
        # the driver only watches the all-settled predicate (window 0).
        if final and compact_ok and B >= 2 * _COMPACT_FLOOR:
            carry = _drive_compacting(
                mk_run_seg, carry, Ap, datap, A32p, cfg, seg, B, tail,
                cleanup_cap, dtype,
            )
        else:
            carry, _ = core.drive_segments(
                mk_run_seg(Ap, datap, A32p), carry, cfg.max_iter, 0, seg,
                early_stop=(
                    (
                        lambda it, status, n_active, n_unfinished: 0
                        < n_active
                        <= tail
                        and n_unfinished <= cleanup_cap
                    )
                    if final and tail
                    else None
                ),
            )
        phase_report.append({
            "phase": pi,
            "mode": ("f32-state" if f32_state
                     else ("pcg" if pcgi else f)),
            "iters": int(carry[2]),  # phase-local iteration count
            "wall_s": round(time.perf_counter() - t_ph, 3),
        })
        if not final:
            # Phase boundary: iterates kept; verdicts reset — except a
            # full-tolerance phase's OPTIMAL members, which stay settled.
            if carry[0].x.dtype != dtype:
                carry = _cast_batch_carry(carry, dtype)
            carry = _fresh_batch_carry(
                carry[0], carry[6], B, reg0, dtype,
                status=carry[5] if keep_opt else None,
            )

    states, _, _, _, _, status, iters, _, _ = carry
    status = jnp.where(status == _RUNNING, _MAXITER, status)
    pinf, dinf, rel_gap, pobj = _batched_norms_jit(A, data, states, fname)
    return states, status, iters, pinf, dinf, rel_gap, pobj, phase_report


def _drive_compacting(
    mk_run_seg, carry, A, data, A32, cfg, seg, B, tail, cleanup_cap, dtype
):
    """Final-phase segment drive with program compaction (see
    _solve_batched_segmented). Returns a FULL-SIZE carry whose states /
    status / iters lanes hold every member's final values (the only
    lanes the caller consumes after the final phase)."""
    states_out = jax.tree_util.tree_map(
        lambda v: jnp.zeros((B + 1,) + v.shape[1:], v.dtype), carry[0]
    )
    status_out = jnp.full(B + 1, _OPTIMAL, jnp.int32)
    iters_out = jnp.zeros(B + 1, jnp.int32)
    order = jnp.arange(B)
    size = B
    out_nonopt = 0  # non-optimal members already scattered out
    it_g, status_g = 0, core.STATUS_RUNNING
    run_seg = mk_run_seg(A, data, A32)
    while True:
        def early(it, status, n_active, n_unfinished, _size=size,
                  _out=out_nonopt):
            if (
                tail
                and 0 < n_active <= max(1, _size // 32)
                and n_unfinished + _out <= cleanup_cap
            ):
                return True
            return _size > _COMPACT_FLOOR and n_active <= _size // 2

        prev_it = it_g
        # Short segments (4 s target, ≤8 iterations) keep boundaries —
        # the only points compaction can act — frequent; the ~0.1 s
        # meta fetch per segment is noise against the step cost.
        carry, (it_g, status_g, n_act, n_unf) = core.drive_segments(
            run_seg, carry, cfg.max_iter, 0, min(seg, 8), target_s=4.0,
            early_stop=early, it0_status0=(it_g, status_g), seg_cap=8,
        )
        n_act, n_unf = int(n_act), int(n_unf)
        if (
            status_g != core.STATUS_RUNNING
            or it_g >= cfg.max_iter
            or n_act == 0
            or (
                tail
                and n_act <= max(1, size // 32)
                and n_unf + out_nonopt <= cleanup_cap
            )
            or size <= _COMPACT_FLOOR
            or it_g == prev_it  # spin guard: drive made no progress
        ):
            break
        # Shrink: gather actives into the smallest half-size that fits.
        act = np.asarray(carry[1])
        stat_host = np.asarray(carry[5])
        keep = np.flatnonzero(act)
        new_size = size // 2
        while new_size > _COMPACT_FLOOR and len(keep) <= new_size // 2:
            new_size //= 2
        if len(keep) > new_size:
            break  # defensive: actives cannot exceed the early trigger
        out_nonopt += int(np.sum(~act & (stat_host != _OPTIMAL)))
        states_out, status_out, iters_out = _scatter_out(
            (states_out, status_out, iters_out), order, carry
        )
        carry, order, sel = _compact_gather(carry, order, keep, new_size, B)
        A = A[sel]
        A32 = A32[sel] if A32 is not None else None
        data = jax.tree_util.tree_map(lambda v: v[sel], data)
        size = new_size
        run_seg = mk_run_seg(A, data, A32)
    states_out, status_out, iters_out = _scatter_out(
        (states_out, status_out, iters_out), order, carry
    )
    states = jax.tree_util.tree_map(lambda v: v[:B], states_out)
    zi = jnp.zeros(B, jnp.int32)
    return (
        states,
        jnp.zeros(B, bool),
        carry[2],
        jnp.full(B, cfg.reg_dual, dtype),
        zi,
        status_out[:B],
        iters_out[:B],
        jnp.full(B, jnp.inf, dtype),
        zi,
    )


# ---------------------------------------------------------------------------
# Bucket entry point (serve/): a pre-padded batch + active mask, one compiled
# program per bucket shape, reused verbatim across service dispatches.


def _warm_build_single(a, d, x, y, s, w, z, reg0, fdt):
    """Traced twin of ipm.warm.interior_candidate for ONE bucket slot
    (vmapped by :func:`_warm_select`): interior shift → primal
    projection onto the new b (one AAᵀ solve — same-A delta-solve
    refresh) → dual slack refresh on the new c → residual-aware
    centrality lift. Policy constants come from ipm/warm.py — one
    definition, two engines. Returns (candidate, merit, μ_w)."""
    from distributedlpsolver_tpu.ipm import warm as warm_mod

    dtype = a.dtype
    floor = jnp.asarray(warm_mod.INTERIOR_FLOOR, dtype)
    one = jnp.asarray(1.0, dtype)
    tiny = jnp.asarray(1e-30, dtype)
    ops = _make_ops(a, reg0, fdt, 0)
    xm = jnp.maximum(jnp.mean(jnp.abs(x)), one)
    sm = jnp.maximum(jnp.mean(jnp.abs(s)), one)
    x1 = jnp.maximum(x, floor * xm)
    # Primal projection: x += Aᵀ(AAᵀ)⁻¹(b − Ax) lands the candidate on
    # the new feasible affine (the clip after re-opens a floor-sized
    # residual at worst). A degenerate factorization NaNs the merit and
    # the slot falls back to cold — the safeguard's job.
    fac = ops.factorize(jnp.ones_like(x))
    x1 = x1 + ops.rmatvec(ops.solve(fac, d.b - ops.matvec(x1)))
    x1 = jnp.maximum(x1, floor * xm)
    hub, u_f = d.hub, d.u_f
    x1 = jnp.where(hub > 0, jnp.clip(x1, 0.01 * u_f, 0.99 * u_f), x1)
    w1 = jnp.where(hub > 0, u_f - x1, jnp.ones_like(w))
    # Dual refresh: s − z = c − Aᵀy exactly wherever the positive split
    # allows, a floor-shift on both parts elsewhere.
    s_hat = d.c - ops.rmatvec(y)
    z1 = jnp.where(hub > 0, jnp.maximum(z, floor * sm), jnp.zeros_like(z))
    s1 = jnp.where(hub > 0, s_hat + z1, jnp.maximum(s_hat, floor * sm))
    deficit = jnp.where(
        hub > 0, jnp.maximum(floor * sm - s1, 0.0), jnp.zeros_like(s1)
    )
    s1 = s1 + deficit
    z1 = z1 + deficit
    mu_w = (x1 @ s1 + (hub * w1) @ z1) / d.ncomp
    pinf, dinf, *_ = core.residual_norms(
        ops, d, IPMState(x=x1, y=y, s=s1, w=w1, z=z1)
    )
    merit = jnp.maximum(pinf, dinf)
    # Residual-aware centrality lift (MERIT_MU_FLOOR): raise the SMALLER
    # factor of any pair whose product trails the recentre target.
    pobj = d.c @ x1
    target = jnp.maximum(
        jnp.asarray(warm_mod.CENTRALITY_BETA, dtype) * mu_w,
        jnp.asarray(warm_mod.MERIT_MU_FLOOR, dtype)
        * merit * (one + jnp.abs(pobj)) / d.ncomp,
    )
    lift = jnp.sqrt(jnp.clip(target / jnp.maximum(x1 * s1, tiny), 1.0, 1e16))
    x2 = jnp.where(x1 <= s1, x1 * lift, x1)
    s2 = jnp.where(s1 < x1, s1 * lift, s1)
    liftw = jnp.sqrt(jnp.clip(target / jnp.maximum(w1 * z1, tiny), 1.0, 1e16))
    w2 = jnp.where((hub > 0) & (w1 <= z1), w1 * liftw, w1)
    z2 = jnp.where((hub > 0) & (z1 < w1), z1 * liftw, z1)
    return IPMState(x=x2, y=y, s=s2, w=w2, z=z2), merit, mu_w


def _warm_select(A, data, states_cold, warm_raw, warm_mask, fdt, reg0):
    """Per-slot safeguarded warm-start selection: candidates built by
    :func:`_warm_build_single`, each compared against the cold start's
    initial residual merit AND complementarity (the refresh makes even
    far-off priors nearly feasible; μ is what still tells them apart);
    a slot takes the warm iterate only where the mask requests it and
    both guards accept. Runs INSIDE the bucket programs — warm arrays
    are ordinary traced inputs (zeros on cold dispatches), so one
    compiled program serves any warm/cold mix with zero warm
    recompiles. Returns (states0, warm_used)."""
    from distributedlpsolver_tpu.ipm import warm as warm_mod

    dtype = A.dtype
    wx, wy, ws_, ww, wz = warm_raw
    cand, merit_w, mu_w = jax.vmap(
        lambda a, d, x, y, s, w, z: _warm_build_single(
            a, d, x, y, s, w, z, reg0, fdt
        )
    )(A, data, wx, wy, ws_, ww, wz)

    def cold_stats(a, d, st):
        ops = _make_ops(a, jnp.asarray(0.0, dtype), fdt, 0)
        pinf, dinf, _, _, _, _, mu = core.residual_norms(ops, d, st)
        return jnp.maximum(pinf, dinf), mu

    merit_c, mu_c = jax.vmap(cold_stats)(A, data, states_cold)
    tiny = jnp.asarray(1e-12, dtype)
    ok = (
        warm_mask
        & jnp.isfinite(merit_w)
        & jnp.isfinite(mu_w)
        & (
            merit_w
            <= jnp.asarray(warm_mod.WARM_ACCEPT_FACTOR, dtype)
            * jnp.maximum(merit_c, tiny)
        )
        & (
            mu_w
            <= jnp.asarray(warm_mod.MU_ACCEPT_FACTOR, dtype)
            * jnp.maximum(mu_c, tiny)
        )
    )
    B = A.shape[0]
    pick = lambda wv, cv: jnp.where(
        ok.reshape((B,) + (1,) * (wv.ndim - 1)), wv, cv
    )
    return jax.tree_util.tree_map(pick, cand, states_cold), ok


def _bucket_phase_carry(states, iters, B, reg0, dtype, active0, status=None):
    """Bucket phase-entry carry: :func:`_fresh_batch_carry` with the
    padding mask re-applied — padding slots are inactive and report a
    placeholder _OPTIMAL in EVERY schedule phase, not just the first
    (the all-settled loop predicate and the demux logic treat them as
    finished; serve/service.py demuxes by slot index, so a padding
    verdict is never read)."""
    c = _fresh_batch_carry(states, iters, B, reg0, dtype, status=status)
    states, active, it, regs, bad, st, iters, best, since = c
    return (
        states,
        active & active0,
        it,
        regs,
        bad,
        jnp.where(active0, st, _OPTIMAL),
        iters,
        best,
        since,
    )


@functools.partial(
    jax.jit,
    static_argnames=("schedule", "factor_dtype", "stall_window", "fuse_iters"),
)
def _solve_bucket_jit(
    A, data, active0, warm_x, warm_y, warm_s, warm_w, warm_z, warm_mask,
    reg0, max_iter, max_refactor, reg_grow, schedule,
    factor_dtype, stall_window, fuse_iters=1,
):
    # ``schedule`` is the static per-tolerance-tier precision ladder from
    # SolverConfig.bucket_phases — a tuple of (engine, StepParams) pairs,
    # sequenced as masked phases INSIDE this one program, so one compiled
    # executable still serves every dispatch of a (bucket, tol) pair.
    # The legacy behavior is the single-phase ("f64", params) schedule.
    # Serving members sit far below _PHASED_MEMBER_ENTRIES, where the
    # LARGE-member schedules (PCG, all-f32 state) lose; the df32 ladder
    # is different — it attacks the elementwise emulation tax, which IS
    # the wall at bucket shapes (ROUND5_NOTES lever 3). max_iter /
    # max_refactor / reg_grow are traced so per-request iteration budgets
    # never fork the compile cache; ``active0`` masks padding slots
    # inactive from iteration 0 — the same machinery that freezes
    # converged members freezes slots that never held a request.
    fdt = jnp.dtype(factor_dtype)
    B = A.shape[0]
    dtype = A.dtype
    # Starting point at the RESOLVED factor dtype regardless of an f32
    # first phase — it is one factorization amortized over the whole
    # solve, and an f32 least-squares start can strand an
    # ill-conditioned member (see _solve_batched_jit).
    start_params = schedule[-1][1]
    states0 = jax.vmap(
        lambda a, d: _single_start(a, d, reg0, start_params, fdt)
    )(A, data)
    # Warm slots override the cold start where the in-program safeguard
    # accepts (cold dispatches pass zero warm arrays + an all-false mask
    # — same shapes, same program, zero warm recompiles).
    states0, warm_used = _warm_select(
        A, data, states0, (warm_x, warm_y, warm_s, warm_w, warm_z),
        warm_mask, fdt, reg0,
    )
    need_f32 = any(e == "f32" for e, _ in schedule)
    # Loop-invariant precast copy: f32 phases factor AND assemble from it
    # on the MXU instead of in emulated f64 (dense._cholesky_ops).
    A32 = A.astype(jnp.float32) if need_f32 else None
    final_tol = schedule[-1][1].tol
    carry = _bucket_phase_carry(
        states0, jnp.zeros(B, jnp.int32), B, reg0, dtype, active0
    )
    phase_its = []
    for pi, (engine, pp) in enumerate(schedule):
        final = pi == len(schedule) - 1
        fdt_p = jnp.dtype(jnp.float32) if engine == "f32" else fdt
        win = (2 * stall_window if stall_window else 0) if final else stall_window
        carry = _batched_phase(
            A, data, carry, pp, max_iter, max_refactor, reg_grow, fdt_p,
            None, win, _STALL if final else _RUNNING,
            A32 if engine == "f32" else None, fuse_iters=fuse_iters,
        )
        phase_its.append(carry[2])
        if not final:
            # Phase boundary: iterates kept, provisional verdicts reset.
            # A phase that ran at the FINAL tolerance judged its members
            # with honest full-precision residuals (state, residual
            # norms, and convergence tests stay f64 in every engine), so
            # its OPTIMAL verdicts survive; loosened-tol phases are
            # provisional and every member re-enters.
            carry = _bucket_phase_carry(
                carry[0], carry[6], B, reg0, dtype, active0,
                status=carry[5] if pp.tol <= final_tol else None,
            )
    states, _, _, _, _, status, iters, _, _ = carry
    status = jnp.where(status == _RUNNING, _MAXITER, status)

    def final_norms(a, d, st):
        ops = _make_ops(a, jnp.asarray(0.0, dtype), fdt, 0)
        pinf, dinf, _, rel_gap, pobj, _, _ = core.residual_norms(ops, d, st)
        return pinf, dinf, rel_gap, pobj

    pinf, dinf, rel_gap, pobj = jax.vmap(final_norms)(A, data, states)
    return (states, status, iters, pinf, dinf, rel_gap, pobj,
            jnp.stack(phase_its), warm_used)


@functools.partial(jax.jit, static_argnames=("params", "factor_dtype"))
def _bucket_start_jit(
    A, data, warm_x, warm_y, warm_s, warm_w, warm_z, warm_mask, reg0,
    params, factor_dtype,
):
    """Starting point of the SEGMENTED bucket drive (own cache so
    :func:`bucket_cache_size` accounts every bucket-path program), with
    the same safeguarded per-slot warm override as the fused program.
    Returns (states0, warm_used)."""
    fdt = jnp.dtype(factor_dtype)
    states0 = jax.vmap(lambda a, d: _single_start(a, d, reg0, params, fdt))(
        A, data
    )
    return _warm_select(
        A, data, states0, (warm_x, warm_y, warm_s, warm_w, warm_z),
        warm_mask, fdt, reg0,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "params", "factor_dtype", "stall_window", "stall_status", "fuse_iters",
    ),
    # The carry is consumed: the bucket segment drive rebinds it on every
    # dispatch and nothing reads the old one, so the per-bucket state
    # buffers recycle in place (donation satellite — same rationale as
    # _batched_segment_jit; verified by bucket_donation_report / the
    # compiled program's memory analysis). A / data / A32 are
    # loop-invariant across segments and shared with retry dispatches,
    # so they must NOT donate.
    donate_argnums=(2,),
)
def _bucket_segment_jit(
    A, data, carry, it_stop, max_iter, max_refactor, reg_grow, params,
    factor_dtype, stall_window=0, stall_status=_RUNNING, A32=None,
    fuse_iters=1,
):
    out = _batched_phase(
        A, data, carry, params, max_iter, max_refactor, reg_grow,
        jnp.dtype(factor_dtype), it_stop, stall_window, stall_status, A32,
        fuse_iters=fuse_iters,
    )
    f = A.dtype
    settled = jnp.where(jnp.any(out[1]), core.STATUS_RUNNING, core.STATUS_OPTIMAL)
    unfinished = jnp.sum(out[5] != _OPTIMAL)
    meta = jnp.stack(
        [out[2].astype(f), settled.astype(f), jnp.sum(out[1]).astype(f),
         unfinished.astype(f)]
    )
    return out, meta


@functools.partial(jax.jit, static_argnames=("factor_dtype",))
def _bucket_norms_jit(A, data, states, factor_dtype):
    """Final per-member diagnostics of the segmented bucket drive (own
    cache — see :func:`_bucket_start_jit`)."""
    fdt = jnp.dtype(factor_dtype)

    def final_norms(a, d, st):
        ops = _make_ops(a, jnp.asarray(0.0, a.dtype), fdt, 0)
        pinf, dinf, _, rel_gap, pobj, _, _ = core.residual_norms(ops, d, st)
        return pinf, dinf, rel_gap, pobj

    return jax.vmap(final_norms)(A, data, states)


def _solve_bucket_segmented(
    A, data, active0, cfg, schedule, fname, seg, fuse, warm_raw, warm_mask
):
    """Host-segmented bucket drive (TPU watchdog guard, same design as
    _solve_batched_segmented): each device dispatch is one bounded
    :func:`_bucket_segment_jit` continuation with the carry DONATED —
    the bucket's state buffers recycle in place across dispatches — and
    ``fuse`` IPM iterations fused per while-loop trip, so the serve
    solve thread crosses the host boundary once per segment instead of
    once per iteration. No compaction (bucket batches are small and may
    be mesh-sharded) and no cleanup (the service owns the retry
    budget)."""
    B = A.shape[0]
    dtype = A.dtype
    fdt = jnp.dtype(fname)
    reg0 = jnp.asarray(cfg.reg_dual, dtype)
    mi = jnp.asarray(cfg.max_iter, jnp.int32)
    mr = jnp.asarray(cfg.max_refactor, jnp.int32)
    rg = jnp.asarray(cfg.reg_grow, dtype)
    need_f32 = any(e == "f32" for e, _ in schedule)
    A32 = A.astype(jnp.float32) if need_f32 else None
    states0, warm_used = _bucket_start_jit(
        A, data, *warm_raw, warm_mask, reg0, schedule[-1][1], fname
    )
    carry = _bucket_phase_carry(
        states0, jnp.zeros(B, jnp.int32), B, reg0, dtype, active0
    )
    final_tol = schedule[-1][1].tol
    w = cfg.stall_window
    phase_its = []
    for pi, (engine, pp) in enumerate(schedule):
        final = pi == len(schedule) - 1
        fdt_name = "float32" if engine == "f32" else jnp.dtype(fdt).name
        win = (2 * w if w else 0) if final else w
        wstat = _STALL if final else _RUNNING
        A32p = A32 if engine == "f32" else None

        def run_seg(c, stop, _pp=pp, _f=fdt_name, _w=win, _ws=wstat,
                    _a32=A32p):
            return _bucket_segment_jit(
                A, data, c, jnp.asarray(stop, jnp.int32), mi, mr, rg,
                _pp, _f, _w, _ws, _a32, fuse,
            )

        carry, (it, _, _, _) = core.drive_segments(
            run_seg, carry, cfg.max_iter, 0, seg
        )
        phase_its.append(it)
        if not final:
            carry = _bucket_phase_carry(
                carry[0], carry[6], B, reg0, dtype, active0,
                status=carry[5] if pp.tol <= final_tol else None,
            )
    states, _, _, _, _, status, iters, _, _ = carry
    status = jnp.where(status == _RUNNING, _MAXITER, status)
    pinf, dinf, rel_gap, pobj = _bucket_norms_jit(A, data, states, fname)
    return states, status, iters, pinf, dinf, rel_gap, pobj, phase_its, warm_used


def bucket_cache_size() -> int:
    """Number of compiled bucket programs in this process — the serve
    layer's recompile telemetry, and the warm-bucket zero-recompile
    assertion in tests (repeat dispatches to a warm bucket must not grow
    this). Sums every bucket-path program: the fused single-program
    route plus the segmented start/segment/norms route. The cache keys
    include the input shardings, so the invariant holds per
    (bucket, mesh) pair: the same bucket dispatched over a different
    mesh compiles once more, then stays warm there too. The PDHG
    bucket engine's programs (backends/first_order) count too — the
    serve layer's zero-warm-recompile invariant covers every engine of
    the tolerance-tiered ladder."""
    from distributedlpsolver_tpu.backends.first_order import (
        pdhg_bucket_cache_size,
    )

    return (
        _solve_bucket_jit._cache_size()
        + _bucket_start_jit._cache_size()
        + _bucket_segment_jit._cache_size()
        + _bucket_norms_jit._cache_size()
        + pdhg_bucket_cache_size()
    )


def bucket_donation_report(
    m: int, n: int, batch: int, config: Optional[SolverConfig] = None
):
    """AOT-compile the bucket segment program at the given shape and
    return its memory-analysis figures — ``alias_bytes`` is the donated
    input/output aliasing XLA actually established (0 would mean the
    donated carry is being COPIED, defeating the in-place reuse). Uses
    ``jit.lower().compile()``, which bypasses the dispatch cache, so the
    zero-warm-recompile accounting is untouched. Returns None where the
    backend exposes no memory analysis."""
    cfg = config or SolverConfig()
    dtype = jnp.dtype(cfg.dtype)
    B = batch
    A = jnp.zeros((B, m, n), dtype)
    b = jnp.ones((B, m), dtype)
    c = jnp.ones((B, n), dtype)
    u = jnp.full((B, n), jnp.inf, dtype=dtype)
    data = jax.vmap(
        lambda cc, bb, uu: core.make_problem_data(jnp, cc, bb, uu, dtype)
    )(c, b, u)
    states0 = IPMState(
        x=jnp.ones((B, n), dtype), y=jnp.zeros((B, m), dtype),
        s=jnp.ones((B, n), dtype), w=jnp.ones((B, n), dtype),
        z=jnp.zeros((B, n), dtype),
    )
    reg0 = jnp.asarray(cfg.reg_dual, dtype)
    carry = _bucket_phase_carry(
        states0, jnp.zeros(B, jnp.int32), B, reg0, dtype,
        jnp.ones(B, dtype=bool),
    )
    pp = cfg.bucket_phase_params("f64", cfg.tol)
    lowered = _bucket_segment_jit.lower(
        A, data, carry, jnp.asarray(8, jnp.int32),
        jnp.asarray(cfg.max_iter, jnp.int32),
        jnp.asarray(cfg.max_refactor, jnp.int32),
        jnp.asarray(cfg.reg_grow, dtype), pp,
        jnp.dtype(cfg.factor_dtype_resolved()).name, 0, _RUNNING, None, 1,
    )
    try:
        # Force a REAL compile: an executable deserialized from the
        # persistent compilation cache (the package enables one by
        # default) reports zero alias/temp figures, which would read as
        # "donation silently copied" when the donation is fine. The
        # enable flag alone is not enough once the cache backend has
        # initialized, so the cache is reset around the compile (and
        # again afterwards, so later compiles re-init with the restored
        # config).
        from jax.experimental.compilation_cache import compilation_cache

        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            ma = lowered.compile().memory_analysis()
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()
    except Exception:
        return None
    if ma is None:
        return None

    def _get(attr):
        try:
            v = getattr(ma, attr)
        except Exception:
            return None
        return None if v is None else int(v)

    return {
        "alias_bytes": _get("alias_size_in_bytes"),
        "argument_bytes": _get("argument_size_in_bytes"),
        "output_bytes": _get("output_size_in_bytes"),
        "temp_bytes": _get("temp_size_in_bytes"),
    }


def place_bucket(
    batch: BatchedLP,
    active,
    config: Optional[SolverConfig] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axis: str = "batch",
):
    """Host→device transfer of a pre-padded bucket — the PACK stage of the
    serving pipeline. Casts to the solve dtype and places the leading
    (batch) axis over ``mesh`` (data parallelism: the same compiled
    program then runs B/K problems per device), or on the default device
    unsharded. Split out of :func:`solve_bucket` so the service can run
    this host work for batch k+1 while the device still solves batch k;
    ``solve_bucket`` accepts the returned (batch, active) verbatim and
    skips its own conversion.
    """
    cfg = config or SolverConfig()
    dtype = jnp.dtype(cfg.dtype)
    A = np.asarray(batch.A, dtype=dtype)
    b = np.asarray(batch.b, dtype=dtype)
    c = np.asarray(batch.c, dtype=dtype)
    act = np.asarray(active, dtype=bool)
    Bsz = A.shape[0]
    if act.shape != (Bsz,):
        raise ValueError(f"active mask shape {act.shape} != ({Bsz},)")
    if mesh is not None:
        k = mesh.shape[batch_axis]
        if Bsz % k != 0:
            raise ValueError(
                f"bucket batch {Bsz} not divisible by mesh axis {k}"
            )
        sh = lambda nd: mesh_lib.batch_sharding(mesh, nd, batch_axis)
        A = jax.device_put(A, sh(3))
        b = jax.device_put(b, sh(2))
        c = jax.device_put(c, sh(2))
        act = jax.device_put(act, sh(1))
    else:
        A, b, c = jax.device_put(A), jax.device_put(b), jax.device_put(c)
        act = jax.device_put(act)
    return BatchedLP(c=c, A=A, b=b, name=batch.name), act


def place_warm(
    warm: Optional[IPMState],
    warm_mask,
    shape,
    config: Optional[SolverConfig] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axis: str = "batch",
):
    """Host→device transfer of a bucket's warm-start lanes — the warm
    half of :func:`place_bucket`, run by the serve pack stage. ``warm``
    is an IPMState of (B, n)/(B, m) host arrays (None = cold dispatch:
    zeros), ``warm_mask`` the (B,) offered-slots mask; ``shape`` is the
    bucket's (B, m, n). The lanes are placed with the SAME batch-axis
    sharding as the bucket data, so a warm dispatch reuses the exact
    compiled program a cold/warm-up dispatch built (the warm arrays are
    ordinary traced inputs, never part of the cache key)."""
    cfg = config or SolverConfig()
    dtype = jnp.dtype(cfg.dtype)
    B, m, n = shape
    if warm is None:
        wx = np.zeros((B, n), dtype=dtype)
        wy = np.zeros((B, m), dtype=dtype)
        ws_ = np.zeros((B, n), dtype=dtype)
        ww = np.zeros((B, n), dtype=dtype)
        wz = np.zeros((B, n), dtype=dtype)
        wm = np.zeros(B, dtype=bool)
    else:
        wx = np.asarray(warm.x, dtype=dtype)
        wy = np.asarray(warm.y, dtype=dtype)
        ws_ = np.asarray(warm.s, dtype=dtype)
        ww = np.asarray(warm.w, dtype=dtype)
        wz = np.asarray(warm.z, dtype=dtype)
        wm = np.asarray(warm_mask, dtype=bool)
    if wm.shape != (B,):
        raise ValueError(f"warm mask shape {wm.shape} != ({B},)")
    if mesh is not None:
        sh = lambda nd: mesh_lib.batch_sharding(mesh, nd, batch_axis)
        wx, ws_, ww, wz = (jax.device_put(v, sh(2)) for v in (wx, ws_, ww, wz))
        wy = jax.device_put(wy, sh(2))
        wm = jax.device_put(wm, sh(1))
    else:
        wx, wy, ws_, ww, wz, wm = (
            jax.device_put(v) for v in (wx, wy, ws_, ww, wz, wm)
        )
    return IPMState(x=wx, y=wy, s=ws_, w=ww, z=wz), wm


def solve_bucket(
    batch: BatchedLP,
    active,
    config: Optional[SolverConfig] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axis: str = "batch",
    warm: Optional[IPMState] = None,
    warm_mask=None,
    **config_overrides,
) -> BatchedResult:
    """Solve one pre-padded serving bucket: ``batch`` is (B, m, n) arrays
    already padded to the bucket shape (serve/buckets.py), ``active`` a
    (B,) bool mask — False slots are padding and are frozen from the
    first iteration (their returned status is a placeholder OPTIMAL;
    demux by slot and ignore them).

    ``mesh`` shards the batch axis over its devices (B must divide by the
    mesh size) — batch-axis data parallelism, the same placement-only
    scheme as :func:`solve_batched`: one dispatch drives every device and
    the only cross-device traffic is the ``any(active)`` loop predicate.
    Inputs already placed by :func:`place_bucket` (the serve pipeline's
    pack stage) are used as-is.

    Unlike :func:`solve_batched` there is no chunking and no solo
    cleanup: the service owns the retry budget of unfinished members
    (supervisor ladder / solo re-solve). The per-bucket PRECISION
    schedule (``config.bucket_schedule`` → :meth:`SolverConfig.
    bucket_phases`: f32-gram early phase → df32-elementwise mid → f64c
    finisher, tiered by the request tolerance) runs as masked phases
    inside the one jitted program per (B, m, n, dtype, tol, schedule,
    sharding) key, reused across every dispatch — a warm bucket never
    recompiles (:func:`bucket_cache_size`). On TPU the drive is
    host-segmented (watchdog guard) with the carry donated per segment;
    results are identical either way.

    ``warm``/``warm_mask`` offer per-slot warm-start iterates (an
    IPMState of (B, n)/(B, m) arrays, see :func:`place_warm`): offered
    slots start from the shifted-and-recentred prior iterate when the
    in-program safeguard accepts it; cold slots (and safeguard
    fallbacks) run Mehrotra's start — one dispatch freely mixes both,
    and ``BatchedResult.warm_used`` reports the per-slot outcome. The
    warm lanes are ordinary traced inputs (zeros when omitted), so
    offering them never compiles a new program.
    """
    cfg = config or SolverConfig()
    if config_overrides:
        cfg = cfg.replace(**config_overrides)
    dtype = jnp.dtype(cfg.dtype)
    fname = jnp.dtype(cfg.factor_dtype_resolved()).name
    platform = jax.default_backend()
    tiers = cfg.bucket_phases(cfg.tol, platform)
    schedule = tuple(
        (e, cfg.bucket_phase_params(e, t)) for e, t in tiers
    )
    fuse = cfg.fused_iters_resolved(platform)

    t0 = time.perf_counter()
    if isinstance(batch.A, jax.Array) and batch.A.dtype == dtype:
        # Pre-placed by place_bucket (pack stage): np.asarray here would
        # drag the arrays back to host and forfeit the overlapped
        # transfer. Divisibility was checked at placement time.
        A, b, c, active = batch.A, batch.b, batch.c, active
        if not isinstance(active, jax.Array):
            # A host mask next to a pre-placed batch must still commit
            # against the SAME mesh sharding as the data: a bare
            # jnp.asarray pins it to the default local device, which a
            # multi-process mesh program cannot consume.
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
    Bsz, _, n = A.shape
    u = jnp.full((Bsz, n), jnp.inf, dtype=dtype)
    data = jax.vmap(
        lambda cc, bb, uu: core.make_problem_data(jnp, cc, bb, uu, dtype)
    )(c, b, u)
    # Warm lanes ALWAYS enter the program (zeros + all-false mask on a
    # cold dispatch) so warm-up, cold, warm, and mixed dispatches share
    # one executable — the zero-warm-recompile invariant extends to the
    # warm path by construction.
    if (
        warm is not None
        and isinstance(warm.x, jax.Array)
        and warm.x.dtype == dtype
    ):
        warm_states, wm = warm, warm_mask  # pre-placed by place_warm
        if not isinstance(wm, jax.Array):
            wm_h = np.asarray(wm, dtype=bool)
            if mesh is not None:
                wm = jax.device_put(
                    wm_h, mesh_lib.batch_sharding(mesh, 1, batch_axis)
                )
            else:
                wm = jnp.asarray(wm_h)
    else:
        warm_states, wm = place_warm(
            warm, warm_mask, (Bsz, A.shape[1], n), cfg,
            mesh=mesh, batch_axis=batch_axis,
        )
    setup_time = time.perf_counter() - t0

    t1 = time.perf_counter()
    cache0 = bucket_cache_size()
    seg_cfg = cfg.segment_iters
    warm_raw = (
        warm_states.x, warm_states.y, warm_states.s, warm_states.w,
        warm_states.z,
    )
    if core.use_segments(seg_cfg, platform):
        (states, status, iters, pinf, dinf, rel_gap, pobj,
         phase_its, warm_used) = _solve_bucket_segmented(
            A, data, active, cfg, schedule, fname,
            seg_cfg if seg_cfg else 8, fuse, warm_raw, wm,
        )
    else:
        (states, status, iters, pinf, dinf, rel_gap, pobj,
         phase_its, warm_used) = _solve_bucket_jit(
            A,
            data,
            active,
            *warm_raw,
            wm,
            jnp.asarray(cfg.reg_dual, dtype),
            jnp.asarray(cfg.max_iter, jnp.int32),
            jnp.asarray(cfg.max_refactor, jnp.int32),
            jnp.asarray(cfg.reg_grow, dtype),
            schedule,
            fname,
            cfg.stall_window,
            fuse,
        )
    jax.block_until_ready(states)
    solve_time = time.perf_counter() - t1
    compiled = bucket_cache_size() - cache0
    phase_report = [
        {"phase": pi, "engine": tiers[pi][0], "tol": tiers[pi][1],
         "iters": int(v)}
        for pi, v in enumerate(np.asarray(phase_its))
    ]
    if compiled:  # recompile accounting at the cache itself: every
        # caller (service dispatch, warm_buckets, direct tests) is
        # covered, and the warm path costs one cache-size read.
        obs_metrics.get_registry().counter(
            "bucket_programs_compiled_total",
            help="batched bucket programs compiled in this process",
        ).inc(compiled)

    code_map = {
        _OPTIMAL: Status.OPTIMAL,
        _MAXITER: Status.ITERATION_LIMIT,
        _NUMERR: Status.NUMERICAL_ERROR,
        _STALL: Status.STALLED,
    }
    # Demux through the multi-process-safe fetch: on a single-process
    # mesh this is np.asarray verbatim; on a multi-process (pod-slice)
    # mesh the batch axis spans processes and every result field rides
    # ONE replicating gather program all ranks reach together.
    (status_h, pobj_h, x_h, iters_h, rel_gap_h, pinf_h, dinf_h, y_h,
     s_h, w_h, z_h, warm_h) = mesh_lib.host_values(
        (status, pobj, states.x, iters, rel_gap, pinf, dinf, states.y,
         states.s, states.w, states.z, warm_used)
    )
    status_arr = np.array(
        [code_map[int(sc)] for sc in status_h], dtype=object
    )
    return BatchedResult(
        status=status_arr,
        objective=np.asarray(pobj_h, dtype=np.float64),
        x=np.asarray(x_h, dtype=np.float64),
        iterations=iters_h,
        rel_gap=np.asarray(rel_gap_h, dtype=np.float64),
        pinf=np.asarray(pinf_h, dtype=np.float64),
        dinf=np.asarray(dinf_h, dtype=np.float64),
        solve_time=solve_time,
        setup_time=setup_time,
        phase_report=phase_report,
        fused_iters=fuse,
        y=np.asarray(y_h, dtype=np.float64),
        s=np.asarray(s_h, dtype=np.float64),
        w=np.asarray(w_h, dtype=np.float64),
        z=np.asarray(z_h, dtype=np.float64),
        warm_used=warm_h,
    )


def member_interior_form(batch: BatchedLP, i: int):
    """One batch member as a standalone InteriorForm — the solo-cleanup
    path's input, exported so bench warm-ups can compile the SAME dense
    solo programs the cleanup will run (its first compile otherwise lands
    inside the timed solve)."""
    from distributedlpsolver_tpu.models.problem import InteriorForm, _SHIFT

    n = np.asarray(batch.A).shape[2]
    return InteriorForm(
        c=np.asarray(batch.c[i], dtype=np.float64),
        A=np.asarray(batch.A[i], dtype=np.float64),
        b=np.asarray(batch.b[i], dtype=np.float64),
        u=np.full(n, np.inf), c0=0.0, orig_n=n,
        col_kind=np.full(n, _SHIFT, dtype=np.int8),
        col_orig=np.arange(n), col_shift=np.zeros(n),
        col_sign=np.ones(n), name=f"{batch.name}[{i}]",
    )


def _concat_results(parts, solve_time, setup_time) -> BatchedResult:
    cat = lambda f: np.concatenate([getattr(p, f) for p in parts])
    return BatchedResult(
        status=cat("status"),
        objective=cat("objective"),
        x=cat("x"),
        iterations=cat("iterations"),
        rel_gap=cat("rel_gap"),
        pinf=cat("pinf"),
        dinf=cat("dinf"),
        solve_time=solve_time,
        setup_time=setup_time,
        # Flat rows with a chunk tag — same shape chunked or not, so
        # consumers never branch on the solve's chunking.
        phase_report=[
            {**ph, "chunk": ci}
            for ci, p in enumerate(parts)
            for ph in (p.phase_report or [])
        ],
    )


def solve_batched(
    batch: BatchedLP,
    config: Optional[SolverConfig] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    batch_axis: str = "batch",
    chunk: Optional[int] = None,
    **config_overrides,
) -> BatchedResult:
    """Solve every problem in ``batch`` concurrently on device.

    ``mesh`` shards the batch axis (data parallelism); the batch size must
    then be divisible by the mesh size.

    ``chunk`` bounds how many problems one device program holds (HBM: the
    per-iteration temps of B emulated-f64 batched GEMMs are ~64·B·m·n
    bytes — B=1024×(128,512) alone exceeds a v5e's 16 GB). Chunks run
    sequentially through ONE compiled executable, so throughput is
    unaffected once B saturates the chip. Default: 256 on TPU (None —
    no chunking — elsewhere); chunking preserves mesh divisibility by
    requiring chunk % mesh size == 0.
    """

    cfg = config or SolverConfig()
    if config_overrides:
        cfg = cfg.replace(**config_overrides)
    # kkt_refine stays at the global default (2): a refine=1 schedule
    # measured 2.6× faster on one easy 256-draw but LOST on the real
    # 1024-row — masked iterations rose 62→76 per chunk and one member
    # left optimality (1023/1024, 133.8 s vs 115.7 s) — the second
    # round is what keeps the hard tail's directions accurate enough
    # to converge (A/B 2026-08-01).
    dtype = jnp.dtype(cfg.dtype)
    fname = jnp.dtype(cfg.factor_dtype_resolved()).name

    B_total = np.asarray(batch.A).shape[0]
    if chunk is None and jax.default_backend() == "tpu":
        chunk = _CHUNK_DEFAULT
    if chunk and mesh is not None and chunk % mesh.shape[batch_axis] != 0:
        raise ValueError(
            f"chunk {chunk} not divisible by mesh axis {mesh.shape[batch_axis]}"
        )
    if chunk and B_total > chunk:
        # A non-multiple B leaves one smaller remainder chunk (one extra
        # compile at that shape) — still chunked: falling through to a
        # single whole-batch program is exactly the HBM blow-up chunking
        # exists to prevent. With a mesh, the remainder must still divide
        # the mesh axis (checked by the recursive call).
        t0 = time.perf_counter()
        parts = [
            solve_batched(
                BatchedLP(
                    c=batch.c[i : i + chunk],
                    A=batch.A[i : i + chunk],
                    b=batch.b[i : i + chunk],
                    name=f"{batch.name}[{i}:{i + chunk}]",
                ),
                cfg,
                mesh=mesh,
                batch_axis=batch_axis,
                chunk=0,  # no further splitting
            )
            for i in range(0, B_total, chunk)
        ]
        wall = time.perf_counter() - t0
        solve_time = sum(p.solve_time for p in parts)
        return _concat_results(
            parts,
            solve_time=solve_time,
            setup_time=max(wall - solve_time, 0.0),  # wall minus solve, no double count
        )

    t0 = time.perf_counter()
    A = np.asarray(batch.A, dtype=dtype)
    b = np.asarray(batch.b, dtype=dtype)
    c = np.asarray(batch.c, dtype=dtype)
    Bsz, m, n = A.shape
    if mesh is not None:
        if Bsz % mesh.shape[batch_axis] != 0:
            raise ValueError(
                f"batch {Bsz} not divisible by mesh axis {mesh.shape[batch_axis]}"
            )
        sh = lambda nd: mesh_lib.batch_sharding(mesh, nd, batch_axis)
        A = jax.device_put(A, sh(3))
        b = jax.device_put(b, sh(2))
        c = jax.device_put(c, sh(2))
    else:
        A, b, c = jnp.asarray(A), jnp.asarray(b), jnp.asarray(c)

    u = jnp.full((Bsz, n), jnp.inf, dtype=dtype)
    data = jax.vmap(lambda cc, bb, uu: core.make_problem_data(jnp, cc, bb, uu, dtype))(
        c, b, u
    )
    params = cfg.step_params()
    setup_time = time.perf_counter() - t0

    t1 = time.perf_counter()
    # Phase schedule (shared _phase_plan): phases are auto-gated on
    # MEMBER size — at the reference batched shape single-phase f64 was
    # measured fastest and PCG 5.6× worse (see _PHASED_MEMBER_ENTRIES),
    # and the PCG chunk≥256 programs crash the current TPU worker;
    # "pcg" still opts in explicitly.
    two_phase, use_pcg, n_phases = _phase_plan(cfg, member_entries=m * n)
    params_p1 = cfg.phase1_params()
    cg = (cfg.cg_iters, cfg.cg_tol) if use_pcg else (0, 0.0)
    fuse = cfg.fused_iters_resolved(jax.default_backend())
    seg = cfg.segment_iters
    if seg is None:
        seg = 8 if jax.default_backend() == "tpu" else 0
    phase_report = []
    if seg:
        (states, status, iters, pinf, dinf, rel_gap, pobj,
         phase_report) = _solve_batched_segmented(
            A, data, cfg, params, params_p1, fname, two_phase, seg, cg,
            compact_ok=mesh is None, fuse_iters=fuse,
        )
        # Same row shape chunked or not (the chunked path tags rows in
        # _concat_results) — consumers never branch on chunking.
        phase_report = [{**ph, "chunk": 0} for ph in phase_report]
    else:
        states, status, iters, pinf, dinf, rel_gap, pobj = _solve_batched_jit(
            A,
            data,
            jnp.asarray(cfg.reg_dual, dtype),
            params,
            params_p1,
            cfg.max_iter,
            cfg.max_refactor,
            cfg.reg_grow,
            fname,
            two_phase,
            cfg.stall_window,
            cg[0],
            cg[1],
            fuse,
        )
    jax.block_until_ready(states)

    code_map = {
        _OPTIMAL: Status.OPTIMAL,
        _MAXITER: Status.ITERATION_LIMIT,
        _NUMERR: Status.NUMERICAL_ERROR,
        _STALL: Status.STALLED,
    }
    status_arr = np.array(
        [code_map[int(sc)] for sc in np.asarray(status)], dtype=object
    )
    # .array (not .asarray): device arrays convert to read-only views and
    # the solo cleanup below writes per-member rows.
    objective = np.array(pobj, dtype=np.float64)
    x = np.array(states.x, dtype=np.float64)
    iterations = np.array(iters)
    rel_gap = np.array(rel_gap, dtype=np.float64)
    pinf = np.array(pinf, dtype=np.float64)
    dinf = np.array(dinf, dtype=np.float64)

    # Solo cleanup: members the batched loop left unfinished (tail
    # extraction stopped early, stalls, iteration limits) re-solve
    # individually through the dense path, warm-started from their batched
    # iterates — a handful of solo solves beats keeping the whole batch's
    # masked loop alive at full-batch cost per iteration. Bounded so a
    # pathological batch can't turn into B sequential solves.
    bad = [i for i in range(Bsz) if status_arr[i] != Status.OPTIMAL]
    if bad and len(bad) <= _cleanup_cap(Bsz):
        from distributedlpsolver_tpu.ipm.driver import solve as _solve

        base_cfg = cfg.replace(
            verbose=False, log_jsonl=None, checkpoint_path=None,
            checkpoint_every=0, profile_dir=None,
        )
        # The batched loop's total budget is max_iter PER PHASE (the f32
        # phase's accepted steps land in the same per-problem counter;
        # n_phases from the shared _phase_plan above), so the cleanup
        # comparison must use the same total — comparing against a single
        # max_iter would deny tail-extracted members the cleanup solve
        # the early stop promised them.
        for i in bad:
            # The solo solve only gets what the batched loop left unspent
            # (tail-extracted members keep most of theirs; genuine
            # iteration-limit members get none and keep that verdict).
            remaining = n_phases * cfg.max_iter - int(iterations[i])
            if remaining <= 0:
                continue
            solo_cfg = base_cfg.replace(max_iter=remaining)
            # Per-member host conversion — full-batch f64 copies just to
            # patch a handful of rows would be ~hundreds of MB transient.
            inf_i = member_interior_form(batch, i)
            ws = IPMState(
                x=x[i],
                y=np.asarray(states.y[i], dtype=np.float64),
                s=np.asarray(states.s[i], dtype=np.float64),
                w=np.asarray(states.w[i], dtype=np.float64),
                z=np.asarray(states.z[i], dtype=np.float64),
            )
            r = _solve(inf_i, backend=CLEANUP_BACKEND, config=solo_cfg,
                       warm_start=ws)
            status_arr[i] = r.status
            objective[i] = r.objective
            x[i] = r.x
            iterations[i] += r.iterations
            rel_gap[i], pinf[i], dinf[i] = r.rel_gap, r.pinf, r.dinf

    solve_time = time.perf_counter() - t1
    return BatchedResult(
        status=status_arr,
        objective=objective,
        x=x,
        iterations=iterations,
        rel_gap=rel_gap,
        pinf=pinf,
        dinf=dinf,
        solve_time=solve_time,
        setup_time=setup_time,
        phase_report=phase_report,
    )
