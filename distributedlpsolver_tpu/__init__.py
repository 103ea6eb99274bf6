"""distributedlpsolver_tpu — a TPU-native distributed LP solver.

A from-scratch, TPU-first rebuild of the capabilities of
shidanxu/DistributedLPSolver (see SURVEY.md; the reference mount was empty at
survey time, so the capability surface is pinned by BASELINE.json — a
primal-dual interior-point LP solver with Mehrotra predictor-corrector,
pluggable ``SolverBackend`` execution backends selected by ``--backend=``,
an MPS reader for the Netlib/Mittelmann suites, a batched solver, and a
distributed path that shards the constraint matrix over a device mesh and
combines Schur-complement / normal-equation blocks with ``jax.lax.psum``
over ICI, replacing the reference's per-iteration ``MPI_Allreduce``).

Design notes
------------
* The Mehrotra predictor-corrector driver and step-length logic live on the
  host; per-iteration linear algebra (normal-equations assembly
  ``A·diag(d)²·Aᵀ``, Cholesky, triangular solves) runs on device under a
  single jitted step (BASELINE.json:5).
* IPM to a 1e-8 duality gap needs f64 accumulation, so the package enables
  JAX x64 at import (opt out with ``TPULP_NO_X64=1``). Backends that target
  hardware without native f64 (TPU MXU) use f32/f64 mixed precision with
  iterative refinement — see ``distributedlpsolver_tpu.ops``.
"""

from __future__ import annotations

import os

if not os.environ.get("TPULP_NO_X64"):
    import jax

    jax.config.update("jax_enable_x64", True)

if not os.environ.get("TPULP_NO_COMPILE_CACHE"):
    # Persistent XLA compilation cache, so a process after the first
    # starts warm. One rule: JAX_COMPILATION_CACHE_DIR (read by JAX
    # itself) names the directory when it is set, and nothing here
    # replaces it; otherwise the cache lives at the fixed path
    # <checkout>/.tpulp_xla_cache. The path is part of what makes a
    # cache hit, so it never depends on the working directory or a
    # temp dir. Opt out with TPULP_NO_COMPILE_CACHE=1.
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                ".tpulp_xla_cache",
            ),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

__version__ = "0.1.0"

from distributedlpsolver_tpu.models.problem import (  # noqa: E402
    InteriorForm,
    LPProblem,
    to_interior_form,
)

__all__ = [
    "LPProblem",
    "InteriorForm",
    "to_interior_form",
    "__version__",
]
