"""Size/structure-aware backend dispatch (``--backend=auto``).

The right execution target depends on the problem, not just the hardware:
a 27×51 afiro-class LP solves in ~10 ms on the CPU but paid ~0.5 s of
device dispatch on the remote TPU used before PR 21, while anything with
real FLOPs wants the accelerated path, and block-angular structure wants the explicit
Schur backend. This dispatcher applies those rules once at ``setup`` and
then delegates every call to the chosen concrete backend — the
reference's ``--backend=`` selection surface with a sensible default on
top (BASELINE.json:5; the reference itself appears to require an explicit
choice, so this is an addition, not a parity item).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from distributedlpsolver_tpu.backends.base import (
    SolverBackend,
    get_backend,
    register_backend,
)
from distributedlpsolver_tpu.ipm.config import SolverConfig
from distributedlpsolver_tpu.ipm.state import IPMState, StepStats
from distributedlpsolver_tpu.models.problem import InteriorForm

# Below this many matrix entries the whole solve is cheaper than device
# dispatch (measured before PR 21 on a remote TPU: 27×51 → ~10 ms CPU vs
# ~0.5 s TPU; not re-measured on a local chip).
_SMALL_ENTRIES = 200_000

# At/above this many rows a sparse problem routes to the matrix-free
# inexact-IPM backend: the dense normal-equations tiers hit the
# storm-class wall (ROUND5_NOTES lever 4 kernel faults, the 10 GB
# assembly arena) and cpu-sparse's sparse-direct factorization fill-in
# grows superlinearly past this scale.
_HUGE_SPARSE_ROWS = 20_000

# Supervisor degradation order (supervisor/supervisor.py): each step trades
# throughput for independence from whatever the faulting layer was —
# multi-device sharding → single-device dense → matrix-free inexact IPM
# (sparse-iterative: PCG normal equations, no ADAᵀ — it sidesteps both
# the dense assembly arena and the large-f64-program kernel-fault class
# ROUND5_NOTES lever 4 pins on the dense path) → CPU sparse-direct →
# plain CPU numpy, which shares no device runtime at all. Note that a
# mesh backend gets one rung ABOVE this chain: on device loss (or hangs
# the health probe pins to a shard) the supervisor first tries to SHRINK
# the mesh over the surviving devices (backend.reshard on
# parallel.mesh.reform_mesh) — dropping one participant of a healthy pod
# beats abandoning the pod for a single device or the CPU.
DEGRADATION_CHAIN = ("sharded", "tpu", "sparse-iterative", "cpu-sparse", "cpu")

# The scenario-decomposed engine degrades onto the rungs that solve its
# LOWERED block-angular form without the two_stage layout contract:
# sparse-iterative (whose bordered-Woodbury preconditioner was built for
# exactly this pattern) → cpu-sparse → cpu. The dense accelerator rungs
# are deliberately skipped — a storm-scale lowered form would have to be
# densified first, the failure class the sparse tier exists to end.
_SCENARIO_CHAIN = ("sparse-iterative", "cpu-sparse", "cpu")


def degradation_chain(name: str) -> list:
    """Fallback backend names strictly *after* ``name`` in the degradation
    order. Aliases resolve through the registry ("dense" → "tpu"); names
    outside the chain ("auto", "block", custom backends) get the full
    chain minus themselves — any rung is a degradation from a specialized
    or unknown backend."""
    from distributedlpsolver_tpu.backends.base import _REGISTRY

    key = (name or "").lower()
    cls = _REGISTRY.get(key)
    primary = cls.name if cls is not None else key
    if primary == "scenario":
        return list(_SCENARIO_CHAIN)
    if primary in DEGRADATION_CHAIN:
        i = DEGRADATION_CHAIN.index(primary)
        return list(DEGRADATION_CHAIN[i + 1:])
    return [n for n in DEGRADATION_CHAIN if n != primary]


def choose_backend_name(
    inf: InteriorForm, platform: str, detect: bool = False
) -> Tuple[str, Optional[dict]]:
    """Pick a backend for ``inf``; returns ``(name, hint)``.

    With ``detect`` (the AutoBackend path), hint-less sparse problems get
    a block-angular detection pass (models/structure.py); a successful
    detection is RETURNED as the hint rather than attached to ``inf`` —
    this function is pure so callers can use it to inspect routing without
    mutating the problem object (AutoBackend.setup attaches the hint)."""
    import scipy.sparse as sp

    # Huge-sparse tier (platform-independent — no other rung can even
    # assemble these): a bordered (two-stage / dual block-angular) hint
    # routes to the matrix-free inexact IPM, whose Woodbury
    # preconditioner that pattern was built for, and any storm-class
    # sparse problem past the dense tier's row wall goes there too —
    # densifying A (or ADAᵀ) at that scale is the 10 GB arena /
    # kernel-fault class this tier exists to end.
    hint0 = inf.block_structure or {}
    # Stochastic scenario tier: an explicit two_stage hint (the
    # ScenarioLP lowering, or a prior detection cached by the warm
    # layer) routes to the scenario-decomposed IPM on every platform —
    # the decomposition is the only rung that never assembles the
    # lowered form's normal matrix AND batches the per-scenario work.
    if hint0.get("kind") == "two_stage":
        return "scenario", None
    if hint0.get("kind") == "bordered":
        return "sparse-iterative", None
    if (
        sp.issparse(inf.A)
        and inf.m >= _HUGE_SPARSE_ROWS
        and inf.A.nnz / max(inf.m * inf.n, 1) < 0.1
    ):
        return "sparse-iterative", None
    # Hint-less two-stage recovery (detect mode): a lowered ScenarioLP
    # whose hint was stripped (MPS round-trip, external producers) still
    # routes to the scenario engine off the sparsity pattern alone.
    # After the huge-sparse gate so storm-scale instances keep the
    # matrix-free rung's measured behavior.
    if detect and sp.issparse(inf.A) and not hint0:
        from distributedlpsolver_tpu.models.structure import detect_two_stage

        ts = detect_two_stage(inf.A)
        if ts is not None:
            return "scenario", ts
    if platform == "cpu":
        return "cpu-native", None
    # Any accelerator (tpu/gpu/...): tiny problems still go to the CPU —
    # device dispatch dominates them — everything else runs the JAX path
    # ("tpu" is the registry name of the accelerated dense backend on
    # whatever platform jax is using), with block structure preferring the
    # explicit Schur backend.
    m, n = inf.m, inf.n
    if m * n <= _SMALL_ENTRIES:
        return "cpu-native", None
    K = int((inf.block_structure or {}).get("num_blocks", 0))
    if K >= 2:
        return "block", None
    # Large genuinely-sparse problems without block structure must not hit
    # the dense path — its setup densifies A (a Mittelmann-scale LP would
    # be a multi-terabyte allocation). Recoverable block-angular structure
    # (pds/stormG2-class) routes to the TPU Schur backend; truly
    # unstructured sparsity goes to the sparse-direct CPU backend
    # (SURVEY.md §7).
    if sp.issparse(inf.A):
        density = inf.A.nnz / max(m * n, 1)
        if density < 0.1:
            if detect:
                from distributedlpsolver_tpu.models.structure import (
                    detect_block_structure,
                    estimate_block_tensor_entries,
                )

                hint = detect_block_structure(inf.A)
                # Veto detections whose padded dense block tensors would
                # not fit (~2 GiB f64): the structure may be real, but the
                # sparse-direct path is then the better executor.
                if hint is not None and (
                    estimate_block_tensor_entries(inf.A, hint) <= 1 << 28
                ):
                    return "block", hint
            return "cpu-sparse", None
    return "tpu", None


@register_backend("auto")
class AutoBackend(SolverBackend):
    """Delegates to the backend :func:`choose_backend_name` picks."""

    def __init__(self):
        self._inner: SolverBackend | None = None

    def setup(self, inf: InteriorForm, config: SolverConfig) -> None:
        name, hint = choose_backend_name(
            inf, jax.default_backend(), detect=True
        )
        if hint is not None:
            inf.block_structure = hint
        self._inner = get_backend(name)
        self.name = f"auto({name})"
        self._inner.setup(inf, config)

    def starting_point(self) -> IPMState:
        return self._inner.starting_point()

    def iterate(self, state: IPMState) -> Tuple[IPMState, StepStats]:
        return self._inner.iterate(state)

    def bump_regularization(self) -> bool:
        return self._inner.bump_regularization()

    def solve_full(self, state: IPMState):
        return self._inner.solve_full(state)

    def to_host(self, state: IPMState) -> IPMState:
        return self._inner.to_host(state)

    def from_host(self, state: IPMState) -> IPMState:
        return self._inner.from_host(state)

    def block_until_ready(self, obj) -> None:
        self._inner.block_until_ready(obj)

    @property
    def mesh(self):
        return getattr(self._inner, "mesh", None) if self._inner else None

    def reshard(self, mesh):
        # The auto decision already happened at setup; a shrink re-places
        # the CHOSEN backend — returning the inner reshard (not a fresh
        # AutoBackend) keeps the new mesh from being second-guessed.
        return self._inner.reshard(mesh) if self._inner else None
