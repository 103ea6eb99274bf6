"""Solver configuration (SURVEY.md §5.6: flag system → frozen dataclass).

One frozen dataclass carries every tunable the CLI exposes; backends receive
it at ``setup`` time. Defaults reproduce the reference's published behavior
(convergence at a 1e-8 duality gap, BASELINE.json:2) with TPU-appropriate
numerics (f64 accumulation; optionally f32 factorization with iterative
refinement on hardware where f64 is emulated).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    tol: float = 1e-8  # relative gap + infeasibility tolerance [BASELINE.json:2]
    max_iter: int = 200
    eta: float = 0.99995  # fraction-to-boundary damping (Mehrotra)
    sigma_power: float = 3.0  # σ = (μ_aff/μ)^power
    sigma_min: float = 1e-8
    sigma_max: float = 0.99
    gamma_cent: float = 1e-3  # N₋∞ centrality neighborhood (0 disables)
    # Static primal regularization added to 1/d. 1e-8 caps the scaling
    # spread d_max at ~1e8, keeping the noise floor of the normal-equations
    # back-substitution below the 1e-8 gap tolerance; the resulting
    # direction perturbation is corrected by kkt_refine (the regularized
    # factorization acts as a preconditioner for true-KKT refinement).
    reg_primal: float = 1e-8
    reg_dual: float = 1e-10  # static dual regularization added to M's diagonal
    reg_grow: float = 100.0  # factor applied on factorization failure
    max_refactor: int = 5  # NaN-recovery attempts per iteration
    dtype: str = "float64"  # iterate/residual dtype
    # Cholesky/assembly dtype. "auto" (default) = two-phase on TPU: f32
    # factorizations (MXU-native) until optimal or stalled, then f64
    # warm-started to the full tolerance — elsewhere plain ``dtype``.
    # A concrete name ("float32"/"float64") forces single-phase at that
    # precision; None = same as dtype.
    factor_dtype: Optional[str] = "auto"
    # Accepted steps without ≥10% improvement in max(gap, pinf, dinf)
    # before a fused-loop phase gives up (phase 1 hands over to f64;
    # a final phase reports Status.STALLED). 0 disables.
    stall_window: int = 8
    # Two-phase handoff tolerance: phase 1 (f32) converges to
    # max(tol, phase1_tol) and hands the iterate to f64 — safely above the
    # f32 noise floor (~1e-6), where grinding injures the iterate's
    # centrality beyond what f64 can repair (observed). Phase 1's μ-floor
    # is also keyed to this, keeping the handoff iterate well-centered.
    phase1_tol: float = 3e-5
    # Fused Pallas normal-equations assembly (ops/normal_eq.py). None =
    # auto: on for single-device TPU placement with a single-precision
    # factor_dtype and refine_steps == 0.
    use_pallas: Optional[bool] = None
    refine_steps: int = 0  # normal-equations-level refinement sweeps per solve
    # Full-accuracy solve mode of the dense TPU path. "direct" = the f64
    # factorization phase 2; "pcg" = f32-Cholesky-preconditioned conjugate
    # gradient whose operator applies A·diag(d)·Aᵀ matrix-free in f64 (two
    # chunked GEMVs per CG step) — no f64 assembly or Cholesky ever runs,
    # which is what makes reference-scale dense (10k×50k, BASELINE.json:9)
    # tractable on emulated-f64 hardware. None = auto: "pcg" on
    # single-device TPU two-phase placement above ~16M matrix entries.
    solve_mode: Optional[str] = None
    cg_iters: int = 100  # PCG iteration cap per Newton solve
    cg_tol: float = 1e-11  # PCG relative-residual target
    # PCG-phase handoff tolerance of the DENSE two-phase schedule, the
    # exact phase1_tol mechanism one level down: the f32-assembled
    # preconditioner floors PCG directions near ~1e-6 at scale, and a
    # phase whose μ-floor is keyed to the FINAL tol grinds μ to ~1e-9 on
    # floor-limited directions — an off-center iterate the full-precision
    # finish cannot repair (observed at 10k×50k: the endgame oscillated
    # at 7e-6 from such a handoff). The dense PCG phase therefore
    # converges to max(tol, pcg_handoff_tol) with its μ-floor keyed
    # there, and the f64 finish (fused phase or endgame) owns the last
    # orders. The BLOCK backend's segmented PCG plan applies the same
    # clamp, finishing with the n-chunked true-f64 Schur mode ("f64c" —
    # one-shot f64 assembly cannot be lowered at its huge shapes; see
    # block_angular._solve_segmented).
    pcg_handoff_tol: float = 1e-6
    kkt_refine: int = 2  # KKT-level refinement rounds per Newton solve
    # KKT-refinement rounds of the dense ENDGAME step (ROUND5_NOTES
    # lever 1). The old hardwired kkt_refine=0 was a host-era
    # program-size constraint — each refinement round added a full eager
    # host solve + device residual pair and ~3×'d the emulated-f64
    # program whose compile had to stay under the earlier remote
    # chip's response limit. The round-5 endgame's solves are cheap panel substitutions
    # (ops/chol_mxu.py), so one round is restored by default: it
    # recovers the cancellation digits the regularized normal-equations
    # back-substitution loses, exactly where the terminal μ-stall cycle
    # burns iterations. None = auto (1); 0 restores the legacy
    # no-refinement endgame; host-factor endgame steps still cap at 1
    # (see endgame_host below). CPU equivalence is test-pinned; the TPU
    # iteration-count measurement is deferred to the next accelerator
    # round.
    endgame_kkt_refine: Optional[int] = None
    # Endgame factorization placement (dense huge-m finish). On hardware
    # whose f64 is emulated (TPU), the endgame's Cholesky breaks down
    # (NaN) orders of magnitude above real-f64 breakdown — measured at
    # 10k×50k: unfactorable below reg ≈ 1e-7 on-device while host LAPACK
    # factors the same matrix at reg ≈ 1e-11 — and the attainable
    # pinf/μ floor scales with the reg actually used. True moves ONLY
    # the m×m factorization and triangular solves to host LAPACK (true
    # f64); the O(m²·n) assembly and all refinement matvecs stay on
    # device. False forces the on-device factorization. None = auto:
    # host on TPU, device elsewhere (where device f64 already IS
    # LAPACK-grade). Note: host-endgame steps cap kkt_refine at 1
    # regardless of the setting here — each eager KKT round is a full
    # host solve + device residual pair, and the host solve already
    # refines against the true operator internally; one round restores
    # the cancellation digits, more only adds host↔device latency.
    endgame_host: Optional[bool] = None
    # Gondzio correctors in the ENDGAME only (StepParams.mcc): there the
    # factorization dwarfs a solve (10k×50k: ~10 s mxu factor vs ~2 s
    # extra solve), so extra centrality correctors that lengthen
    # collapsed steps are nearly free per saved iteration. 0 disables.
    endgame_mcc: int = 2
    # Ruiz-equilibrate the interior form before solving (presolve scaling;
    # convergence is then tested in the scaled space, standard practice).
    scale: bool = True
    # Structural presolve (models/presolve.py): singleton/empty/redundant
    # rows, fixed/empty columns, early infeasibility/unboundedness — with
    # exact primal+dual postsolve. Applied to general-form problems only
    # (an InteriorForm input or a block_structure hint skips it).
    presolve: bool = True
    # distribution (sharded backends)
    mesh_shape: Optional[Tuple[int, ...]] = None  # None = all local devices
    mesh_axis: str = "cols"  # axis name for the variable-sharded mesh dim
    # Per-bucket mixed-precision schedule of the SERVING path
    # (backends/batched.solve_bucket): "df32" runs the tolerance-tiered
    # f32-gram → df32-elementwise → f64c-finisher phase ladder (see
    # :meth:`bucket_phases` — the round-5 dense/block schedules pushed
    # into the bucket programs), "f64" forces the legacy single-phase
    # bucket loop at ``factor_dtype_resolved``. None/"auto" = "df32" on
    # TPU (where emulated-f64 elementwise is the measured wall,
    # ROUND5_NOTES lever 3), "f64" elsewhere (native f64 beats the extra
    # phases on CPU). The schedule is a static key of the one compiled
    # program per (bucket, tol) — it never adds warm recompiles.
    bucket_schedule: Optional[str] = None
    # Iterations fused per while-loop trip of the batched/bucket device
    # loops (traced inner fori_loop over the masked step): the loop
    # predicate — the only cross-device collective of a sharded bucket
    # dispatch — and the segment-boundary bookkeeping run k× less often.
    # Semantics are exactly k=1 (each fused micro-step re-checks the
    # loop guard and masks all writes), so results are bitwise stable in
    # k. None = auto: 8 on TPU, 1 elsewhere.
    fused_iters: Optional[int] = None
    # Fused on-device solve loop (lax.while_loop over iterations; no
    # per-iteration host round trip). None = auto: used when the backend
    # supports it and per-iteration checkpointing is off.
    fused_loop: Optional[bool] = None
    # Segment the fused loop into host-driven chunks of ~this many
    # iterations (adaptively resized toward ~15s of device time each).
    # Bounds single-program runtime: the remote chip used before PR 21
    # killed programs that ran past ~60 s. Whether a local TPU needs the
    # bound is not measured yet; the rule is kept as it was.
    # None = auto: 8 on TPU, 0 (unsegmented) elsewhere.
    segment_iters: Optional[int] = None
    # diagnostics
    verbose: bool = False
    log_jsonl: Optional[str] = None  # per-iteration JSONL path (SURVEY.md §5.5)
    # fsync the JSONL stream after every record: telemetry survives a
    # machine crash, not just a process crash (flush alone covers the
    # latter). Off by default — a per-iteration syscall is noise next to a
    # device step but not next to a 10ms CPU solve.
    log_fsync: bool = False
    # Open the JSONL stream in append mode instead of truncating: the
    # supervisor's retries each re-enter the driver, and attempt N must
    # not erase the telemetry (and fault/resume event records) of
    # attempts 1..N-1. The supervisor truncates the file once up front.
    log_append: bool = False
    checkpoint_path: Optional[str] = None  # iterate checkpoint (SURVEY.md §5.4)
    checkpoint_every: int = 0  # 0 = disabled
    profile_dir: Optional[str] = None  # jax.profiler trace dir (SURVEY.md §5.1)

    def __post_init__(self):
        if self.endgame_host is not None and not isinstance(
            self.endgame_host, bool
        ):
            # A string ("host"/"device") would be truthy and silently
            # select host mode either way — reject like solve_mode does.
            raise ValueError(
                f"endgame_host must be None, True, or False; "
                f"got {self.endgame_host!r}"
            )
        if self.solve_mode not in (None, "direct", "pcg"):
            # A typo ("PCG", "cg") silently selecting the direct path
            # would re-enable the emulated-f64 work the mode exists to
            # avoid — reject it here like the use_pallas checks do.
            raise ValueError(
                f"solve_mode must be None, 'direct', or 'pcg'; "
                f"got {self.solve_mode!r}"
            )
        if self.bucket_schedule not in (None, "auto", "f64", "df32"):
            # A typo ("DF32", "mixed") silently selecting the legacy
            # single-phase loop would drop the mixed-precision win
            # without a trace — reject like solve_mode does.
            raise ValueError(
                f"bucket_schedule must be None, 'auto', 'f64', or "
                f"'df32'; got {self.bucket_schedule!r}"
            )
        if self.fused_iters is not None and self.fused_iters < 1:
            raise ValueError(
                f"fused_iters must be None or >= 1; got {self.fused_iters!r}"
            )

    def replace(self, **kw) -> "SolverConfig":
        return dataclasses.replace(self, **kw)

    def factor_dtype_resolved(self) -> str:
        """Concrete factorization dtype for single-phase execution paths
        ("auto" resolves to ``dtype`` — the two-phase schedule is a backend
        decision, see :meth:`two_phase_enabled`)."""
        fd = self.factor_dtype
        return self.dtype if fd in (None, "auto") else fd

    def two_phase_enabled(self, platform: str) -> bool:
        """Whether the f32→f64 two-phase fused solve should be used."""
        return self.factor_dtype == "auto" and platform == "tpu"

    def bucket_schedule_resolved(self, platform: str) -> str:
        """Concrete bucket schedule name ("df32" or "f64") — auto picks
        "df32" exactly on TPU (ROUND5_NOTES lever 3: the emulated-f64
        elementwise wall the schedule removes doesn't exist on CPU)."""
        bs = self.bucket_schedule
        if bs in (None, "auto"):
            return "df32" if platform == "tpu" else "f64"
        return bs

    def fused_iters_resolved(self, platform: str) -> int:
        """Concrete fused-iterations-per-while-trip for the batched and
        bucket device loops (auto: 8 on TPU, 1 elsewhere)."""
        if self.fused_iters is not None:
            return self.fused_iters
        return 8 if platform == "tpu" else 1

    def bucket_phases(self, tol: float, platform: str):
        """The serving bucket's precision-phase ladder for one tolerance
        tier: a static tuple of ``(engine, phase_tol)`` pairs consumed by
        backends/batched._solve_bucket_jit as part of its compile key
        (one program per (bucket, tol) — the schedule never forks the
        warm cache).

        Engines: ``"f32"`` — f32 factorization + assembly on the precast
        copy (gram-form MXU route; iterates/residuals stay f64, so its
        verdicts are honest whenever its phase tol equals the final
        tol); ``"df32"`` — full-precision factorization route with the
        KKT back-substitution and scaling elementwise chains in df32
        (ops/df32.py, ~1e-13 direction error); ``"f64"`` — the plain
        full-precision loop (the f64c finisher on TPU, where f64 is the
        emulated two-float chain). Tiers mirror what round 5 gave the
        dense/block backends: tight tolerances take all three phases,
        mid tiers stop at df32 (its noise floor is orders below), loose
        tiers run f32 alone.
        """
        if self.bucket_schedule_resolved(platform) != "df32":
            return (("f64", tol),)
        p1 = max(tol, self.phase1_tol)
        if tol <= 1e-6:
            return (("f32", p1), ("df32", tol), ("f64", tol))
        if tol <= 1e-3:
            return (("f32", p1), ("df32", tol))
        return (("f32", tol),)

    def phase1_params(self) -> "StepParams":
        """Step params of the two-phase f32 phase: tol loosened to the
        handoff tolerance (single source of the handoff rule — the
        loosened tol also keys the μ-floor that keeps the handoff iterate
        centered), plus the μ-vs-pinf balance floor — an f32 phase's
        directions bound how fast pinf can fall, and letting μ race
        orders of magnitude below that bound hands the full-precision
        phase an injured iterate (StepParams.mu_pinf_floor)."""
        return self.replace(tol=max(self.tol, self.phase1_tol)).step_params(
            mu_pinf_floor=0.03
        )

    def step_params(self, mu_pinf_floor: float = 0.0,
                    mcc: int = 0, elementwise: str = "native") -> "StepParams":
        return StepParams(
            tol=self.tol,
            eta=self.eta,
            sigma_power=self.sigma_power,
            sigma_min=self.sigma_min,
            sigma_max=self.sigma_max,
            gamma_cent=self.gamma_cent,
            reg_primal=self.reg_primal,
            kkt_refine=self.kkt_refine,
            mu_pinf_floor=mu_pinf_floor,
            mcc=mcc,
            elementwise=elementwise,
        )

    def bucket_phase_params(self, engine: str, phase_tol: float) -> "StepParams":
        """StepParams of one :meth:`bucket_phases` phase. The f32 phase
        carries the μ-vs-pinf balance floor exactly like
        :meth:`phase1_params` (limited-precision directions bound how
        fast pinf can fall); the df32 phase flips the step's elementwise
        engine and needs no floor — its ~1e-13 noise sits five orders
        under the 1e-8 tolerance."""
        base = self.replace(tol=phase_tol)
        if engine == "f32":
            return base.step_params(mu_pinf_floor=0.03)
        return base.step_params(
            elementwise="df32" if engine == "df32" else "native"
        )


@dataclasses.dataclass(frozen=True)
class StepParams:
    """The numeric subset of :class:`SolverConfig` the traced step actually
    reads. This — not the full config — is the static jit key, so changing
    diagnostic fields (log paths, checkpoint paths, verbosity, max_iter)
    never forces an XLA recompile."""

    tol: float
    eta: float
    sigma_power: float
    sigma_min: float
    sigma_max: float
    gamma_cent: float
    reg_primal: float
    kkt_refine: int
    # Pure centering step: skip the predictor entirely and aim every
    # complementarity product at the CURRENT μ (σ=1, no second-order
    # cross term). The blocked-step remedy (dense endgame anti-stagnation
    # ladder): a Mehrotra direction that anti-centers the minimum pair
    # can pin both ratio tests at ~0 while σ stays tiny (the affine step
    # keeps predicting progress the N₋∞ guard cannot accept) — the
    # centering direction is admissible by construction and restores the
    # step room the next Mehrotra iteration needs.
    center: bool = False
    # μ-vs-feasibility balance floor (0 disables): keep the centering
    # target μ ≥ this · pinf_rel · (1+|pobj|)/ncomp, so complementarity
    # cannot run arbitrarily far below the remaining primal
    # infeasibility. Exists for LIMITED-PRECISION phases: the gram-form
    # f32 block phase drove rel_gap to 2e-4 while its f32 directions
    # floored pinf at 3e-3 (μ ~1e5× below pinf) — an injured iterate
    # the f64 finisher could not repair and the divergence heuristic
    # misread as PRIMAL_INFEASIBLE (observed, pds-20-class 2026-08-01).
    mu_pinf_floor: float = 0.0
    # Gondzio-style multiple centrality correctors: up to this many
    # extra complementarity-only solves per iteration, each reusing the
    # factorization to pull outlier pair products back into a band
    # around the centering target and re-testing the step lengths — a
    # candidate is kept only if it lengthens the step. Exists for
    # phases where the factorization dwarfs a solve (the 10k endgame:
    # BENCH_10K.json round 4 shows α collapsing to 0.03–0.18 with
    # near-pure-centering σ across its 41–48 — the textbook signature
    # these correctors fix). 0 = off (every non-endgame path).
    mcc: int = 0
    # Elementwise engine of the KKT back-substitution and scaling chains
    # inside the traced step: "native" runs them in the iterate dtype
    # (emulated f64 on TPU); "df32" routes them through the two-float
    # layer (ops/df32.py — f32 VPU speed, ~1e-13 relative error), the
    # round-5 lever-3 schedule of the serving bucket programs. Residuals,
    # matvecs, factorizations, and the convergence tests stay native, so
    # a df32 phase's OPTIMAL verdicts are honest. jax paths only.
    elementwise: str = "native"
