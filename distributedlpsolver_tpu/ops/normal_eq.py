"""Fused normal-equations assembly ``M = A·diag(d)·Aᵀ`` as a Pallas TPU kernel.

The jnp expression ``(A * d) @ A.T`` materializes the scaled matrix
``A·diag(d)`` — an m×n HBM round trip per IPM iteration that at the
random-dense benchmark shape (10k×50k, BASELINE.json:9) is 4 GB of pure
bandwidth waste in f64.  This kernel (SURVEY.md §7 stage 7) streams A tiles
through VMEM once per (i, k) block, applies the column scaling in-register,
and feeds the MXU directly, accumulating ``M[i, j] += (A[i,k]·d[k])·A[j,k]ᵀ``
in an f32 VMEM scratch accumulator.

Only f32/bf16 inputs are supported — TPUs have no native f64 and Pallas does
not emulate it — so the dense backend routes through here exactly when its
assembly dtype is single precision (the mixed-precision configuration from
SURVEY.md §7: f32 factorization + KKT-level refinement in f64).
:func:`normal_eq` is the dispatching entry point; it falls back to the jnp
expression for f64 or non-TPU platforms, so callers never need to branch.

Reference parity note: the reference's analogue is its BLAS dsyrk/dgemm call
inside normal-equations assembly (capability pinned by BASELINE.json:5 —
"normal equations A·D²·Aᵀ"; the reference tree itself is unavailable,
SURVEY.md §0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


# Shared tile-size defaults: pad_for_pallas and normal_eq_pallas MUST agree,
# or the kernel re-pads the m×n matrix every call (the exact per-iteration
# HBM copy the setup-time pre-pad exists to avoid) — guarded by the out_m
# alignment check below.
BLOCK_M = 256
BLOCK_K = 512


def _ne_kernel(a_i_ref, a_j_ref, d_ref, out_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    scaled = a_i_ref[:] * d_ref[:]  # (bm, bk) * (1, bk) — fused in VMEM
    acc_ref[:] += jax.lax.dot_general(
        scaled,
        a_j_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),  # contract both on axis 1
        preferred_element_type=jnp.float32,
        # HIGHEST = true-f32 MXU passes. The TPU default is bf16 multiplies
        # (~1e-3 relative error), which poisons the Cholesky preconditioner
        # enough that KKT iterative refinement diverges near convergence.
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def pad_for_pallas(A, block_m: int = BLOCK_M, block_k: int = BLOCK_K):
    """Zero-pad ``A`` to the kernel's tile multiples ONCE (call at setup).

    ``A`` is loop-invariant across IPM iterations; padding it per
    ``normal_eq_pallas`` call would re-materialize an m×n HBM copy every
    factorization. Pass the padded matrix plus ``out_m=<true m>`` instead.
    """
    m, n = A.shape
    mp, np_ = _round_up(m, block_m), _round_up(n, block_k)
    if (mp, np_) == (m, n):
        return A
    return jnp.pad(A, ((0, mp - m), (0, np_ - n)))


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_k", "interpret", "out_dtype", "out_m")
)
def normal_eq_pallas(
    A,
    d,
    *,
    block_m: int = BLOCK_M,
    block_k: int = BLOCK_K,
    out_dtype=None,
    interpret: bool = False,
    out_m: int | None = None,
):
    """``A @ diag(d) @ A.T`` without materializing the scaled matrix.

    A: (m, n) f32/bf16; d: (n',) with n' ≤ n — both padded to tile
    multiples (zero-padding d zeroes the padded columns' contribution, so
    the result is exact). ``A`` may be pre-padded via :func:`pad_for_pallas`
    with ``out_m`` giving the true row count; padding here is skipped when
    shapes are already aligned. Returns (out_m, out_m) in ``out_dtype``
    (default f32).
    """
    m, n = A.shape
    out_dtype = jnp.dtype(out_dtype or jnp.float32)
    mp, np_ = _round_up(m, block_m), _round_up(n, block_k)
    if out_m is not None and (mp, np_) != (m, n):
        raise ValueError(
            f"A {A.shape} with out_m={out_m} must be pre-padded to tile "
            f"multiples ({block_m}, {block_k}) — use pad_for_pallas with "
            "matching block sizes"
        )
    # Without out_m, A is unpadded and d must match its columns exactly;
    # with out_m, d is the pre-pad-length vector (shorter than the padded
    # n) and the zero-extension below is the intended semantics.
    if out_m is None and d.shape[0] != n:
        raise ValueError(f"d has shape {d.shape}, expected ({n},) to match A")
    out_m = out_m if out_m is not None else m
    Ap = A if (mp, np_) == (m, n) else jnp.pad(A, ((0, mp - m), (0, np_ - n)))
    dp = jnp.pad(d.astype(A.dtype), (0, np_ - d.shape[0])).reshape(1, np_)

    grid = (mp // block_m, mp // block_m, np_ // block_k)
    out = pl.pallas_call(
        _ne_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (j, k)),
            # (k - k, k) not (0, k): a literal 0 traces as i64 under x64
            # mode and Mosaic rejects the mixed i64/i32 index map.
            pl.BlockSpec((1, block_k), lambda i, j, k: (k - k, k)),
        ],
        out_specs=pl.BlockSpec((block_m, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, mp), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_m), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(Ap, Ap, dp)
    return out[:out_m, :out_m]


def normal_eq_reference(A, d):
    """The plain-XLA expression (also the oracle for the kernel tests)."""
    return (A * d[None, :]) @ A.T


def supports_pallas(dtype, platform: str | None = None) -> bool:
    platform = platform or jax.default_backend()
    return platform == "tpu" and jnp.dtype(dtype) in (
        jnp.dtype(jnp.float32),
        jnp.dtype(jnp.bfloat16),
    )


def normal_eq(A, d, *, use_pallas: bool | None = None, interpret: bool = False):
    """Dispatching assembly: Pallas when (requested or auto-)supported,
    plain XLA otherwise. Safe to call under jit/trace in either path."""
    if use_pallas is None:
        use_pallas = supports_pallas(A.dtype)
    if use_pallas and (interpret or supports_pallas(A.dtype)):
        return normal_eq_pallas(A, d, out_dtype=A.dtype, interpret=interpret)
    return normal_eq_reference(A, d)
