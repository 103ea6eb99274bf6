"""The solve service: async request multiplexing onto bucketed batched
device programs.

``SolveService.submit(problem, deadline=..., tol=...) -> Future`` accepts
independent, asynchronously-arriving LP requests and multiplexes them
onto the device the way the batched backend proved is right for this
domain (one vmap'd masked program per shape bucket — see
backends/batched.solve_bucket and MPAX, arXiv:2412.09734). The
dispatcher is a three-stage pipeline across three threads:

    submit → admission control → per-(bucket, tol) queue ─┐ scheduler
    flush (full batch OR oldest age > flush_s) ───────────┘ thread
         │ pop
         ▼
    pack: pad + stack + host→device transfer               pack thread
         │ (pack of batch k+1 overlaps the device
         ▼  solve of batch k — two-deep pipeline)
    solve: one compiled device program → demux to futures  solve thread

Stages communicate over bounded queues, so the host prepares the next
bucket while the device is busy with the current one; each dispatch
records ``pack_ms`` / ``solve_ms`` / ``overlap_ms`` (how much of its
pack ran under an earlier dispatch's solve window).

Mesh data parallelism: with ``ServiceConfig(mesh_devices=K)`` the pack
stage shards the bucket's batch axis over a K-device mesh
(parallel/mesh.py placement — the same compiled program runs B/K
problems per device, SPMD), bucket batch sizes are enforced
K-divisible by the BucketTable, and :meth:`SolveService.reshard`
re-forms the mesh over survivors when devices are lost mid-service
(elastic recovery; the clamp keeps batches divisible).

Standard-form requests (min cᵀx, Ax=b, x≥0 — the serving workload) ride
the bucketed fast path; general-form problems (finite bounds, ranged
rows, sparse A) take the solo path through ``ipm.solve`` — same futures,
same records, batch=1.

Fault tolerance: a dispatch that raises (or blows ``batch_timeout_s``)
is retried whole once, then degrades to per-request solo solves through
``supervisor.supervised_solve`` — the existing recovery ladder — so a
wedged batch costs its members a retry, never a silent drop. Members the
batch leaves unfinished (stall/iteration limit) take the same solo
ladder individually.

Telemetry: one JSONL record per request (queue/pack/compile/solve split,
padding waste, request shape, faults), one per dispatched batch, and a
service summary at shutdown — all through utils/logging.IterLogger. The
bucket ladder can be refined offline from that stream
(serve/autotune.py) and swapped in live at a safe epoch boundary via
:meth:`SolveService.apply_ladder` (drain → swap → warm).
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from concurrent.futures import Future
from queue import Queue
from typing import Callable, List, Optional, Sequence

import numpy as np

from distributedlpsolver_tpu.ipm.config import SolverConfig
from distributedlpsolver_tpu.ipm.state import (
    FaultKind,
    FaultRecord,
    Status,
)
from distributedlpsolver_tpu.models.problem import LPProblem
from distributedlpsolver_tpu.obs import context as obs_context
from distributedlpsolver_tpu.obs import metrics as obs_metrics
from distributedlpsolver_tpu.obs import trace as obs_trace
from distributedlpsolver_tpu.serve.buckets import (
    BucketSpec,
    BucketTable,
    pad_standard_form,
    padding_waste,
)
from distributedlpsolver_tpu.serve.records import (
    RequestResult,
    latency_summary,
)
from distributedlpsolver_tpu.serve.scheduler import (
    PendingRequest,
    QueueKey,
    Scheduler,
    ServiceOverloaded,
)
from distributedlpsolver_tpu.supervisor.watchdog import (
    StepDeadlineExceeded,
    run_with_deadline,
)
from distributedlpsolver_tpu.utils.logging import IterLogger

_INF = np.inf


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Tunables of the serving loop (see README "Serving")."""

    # Explicit bucket ladder; None = auto power-of-two buckets of ``batch``
    # slots created on demand.
    buckets: Optional[Sequence[BucketSpec]] = None
    batch: int = 16
    # Oldest-request age that forces a part-full bucket to launch. The
    # latency/padding-waste tradeoff knob: lower = snappier tails, more
    # padding; higher = fuller batches.
    flush_s: float = 0.05
    # Admission control: total queued requests across all buckets before
    # submit raises ServiceOverloaded.
    max_queue_depth: int = 1024
    # Default per-request deadline (seconds from submit); None = no
    # deadline. A request past deadline at dispatch time is returned
    # TIMEOUT without occupying a batch slot.
    default_deadline_s: Optional[float] = None
    # Watchdog over one batch dispatch (supervisor/watchdog.py semantics:
    # abandonment, not cancellation). None/0 disables.
    batch_timeout_s: Optional[float] = None
    # Whole-batch retries before degrading to per-request solo recovery.
    max_batch_retries: int = 1
    # Route batch-fault survivors and unfinished members through the
    # supervisor's recovery ladder individually (False: fail them fast).
    solo_recovery: bool = True
    solo_backend: str = "auto"
    # Service telemetry JSONL path (request/batch/fault/summary events).
    log_jsonl: Optional[str] = None
    # Deterministic fault injection (tests): called with
    # (dispatch_index, bucket_key) before each batch launch; raising makes
    # that dispatch attempt fault.
    fault_injector: Optional[Callable[[int, tuple], None]] = None
    # Retired fixed poll tick (drain is event-driven now); kept so stored
    # configs keep loading.
    drain_poll_s: float = 0.005
    # Batch-axis data parallelism: shard each bucket dispatch over this
    # many local devices (0/1 = unsharded single-device dispatch; -1 =
    # every local device). Bucket batch sizes are rounded/validated to be
    # divisible by this (BucketTable).
    mesh_devices: int = 0
    # Dispatch pipeline depth: bound on popped batches sitting between
    # the scheduler and solve stages. 2 = classic two-deep pipeline
    # (host pack of batch k+1 runs under the device solve of batch k);
    # smaller keeps batches in the queues longer so late submits can
    # still fill them, larger lets the pack stage run further ahead.
    pipeline_depth: int = 2
    # Observability (obs/): write a Prometheus-text metrics snapshot
    # here at shutdown (also enables a per-service registry; the JSON
    # snapshot rides the shutdown summary event). None = inherit the
    # module-default registry (a no-op unless something enabled it).
    metrics_path: Optional[str] = None
    # Write a Chrome-trace (Perfetto-loadable) JSON here at shutdown:
    # one async track per request connected across the three pipeline
    # threads, one lane per thread, instant markers for faults /
    # reshards / ladder swaps. None = inherit the module-default tracer.
    trace_path: Optional[str] = None
    # Warm-start & amortization layer (serve/warmcache.py): cache each
    # structural fingerprint's last OPTIMAL iterate and seed
    # same-structure requests from it (safeguarded in-program; warm and
    # cold members mix freely in one batch with zero warm recompiles).
    warm_start: bool = True
    # Bounded LRU capacity of the fingerprint cache.
    warm_cache_entries: int = 512
    # SLO-aware admission (net/admission.AdmissionConfig): per-tenant
    # token-bucket quotas + weighted-fair shares + priority flush
    # shading, layered ABOVE max_queue_depth (which stays as the global
    # backstop). None = the classic depth-only admission.
    admission: Optional[object] = None
    # Tolerance-tiered engine routing: standard-form requests at
    # tol ≥ pdhg_tol dispatch to the bucketed batched PDHG engine
    # (backends/first_order.solve_pdhg_bucket — matrix-free first-order,
    # the accuracy regime it owns), tighter requests to the bucketed
    # IPM. Crossover honesty: a PDHG lane is OPTIMAL only at its true
    # KKT error ≤ the REQUEST tolerance; anything else re-solves through
    # the solo IPM ladder at that same tolerance (first-order pre-solve,
    # interior-point polish). pdhg_routing=False pins every request to
    # the IPM engine.
    pdhg_routing: bool = True
    pdhg_tol: float = 1e-4
    # Durable job journal (serve/journal.py): a write-ahead JSONL log of
    # request lifecycle plus a bounded on-disk async-result store under
    # this directory. A restarted service pointed at the same directory
    # replays admitted-but-unfinished requests (idempotent via request
    # fingerprints, honest TIMEOUT for work whose deadline died with
    # the process) and re-binds every issued poll id. None = the
    # classic in-memory-only service.
    journal_dir: Optional[str] = None
    # WAL persistence per record: "none" (stdio buffer), "flush"
    # (survives kill -9 — default), "always" (flush + fsync, survives
    # power loss).
    journal_fsync: str = "flush"
    # WAL records between compactions (rewrites keeping only
    # unfinished entries) and the on-disk result-store bound.
    journal_compact_every: int = 4096
    journal_results_cap: int = 4096
    # Stochastic scenario tier: scenarios per admission fair-share
    # unit. A K-scenario request charges ceil(K / scenario_k_unit)
    # units against its tenant's token bucket and fair share — more
    # than one plain request, far fewer than K (the batched Schur
    # decomposition amortizes the per-scenario work).
    scenario_k_unit: int = 16
    # Overload brownout ladder (net/admission.BrownoutConfig): staged
    # degradation under sustained saturation — stage 1 sheds batch
    # priority with a structured verdict + honest Retry-After, stage 2
    # widens every flush window, stage 3 re-routes tol-eligible work to
    # the cheaper PDHG engine (never tightening below its tol floor —
    # tight-tol correctness is untouched). Auto-releases on recovery;
    # None = no brownout.
    brownout: Optional[object] = None


def standard_form(problem: LPProblem):
    """(c, A, b) when ``problem`` is a pure standard-form LP the bucketed
    path consumes directly (dense A, all-equality rows, x ≥ 0, no upper
    bounds, no constant, minimized); None routes it to the solo path."""
    A = problem.A
    if not isinstance(A, np.ndarray):
        return None
    if problem.maximize or problem.c0 != 0.0:
        return None
    if not (
        np.array_equal(problem.rlb, problem.rub)
        and np.all(np.isfinite(problem.rlb))
        and np.all(problem.lb == 0.0)
        and np.all(problem.ub == _INF)
    ):
        return None
    return (
        np.asarray(problem.c, dtype=np.float64),
        np.asarray(A, dtype=np.float64),
        np.asarray(problem.rlb, dtype=np.float64),
    )


@dataclasses.dataclass
class _Packed:
    """Output of the pack stage: a device-resident padded bucket."""

    batch: object  # BatchedLP of device arrays (placed, possibly sharded)
    active: object  # (B,) device bool mask
    waste: float
    pack_ms: float
    mesh: object = None  # the mesh snapshot this bucket was placed on
    # Warm-start lanes (backends/batched.place_warm output): prior
    # iterates per slot + offered mask; None = warm start disabled.
    warm: object = None  # IPMState of placed (B, ·) arrays
    warm_mask: object = None  # (B,) device bool mask of offered slots
    warm_hits: object = None  # host list: cache hit per live slot
    # Host-side lane arrays kept for the solve stage's LATE lookup: the
    # pack stage runs pipeline_depth batches ahead of the demux that
    # stores entries, so a slot that missed at pack may hit by dispatch
    # time (back-to-back duplicates); the solve stage fills it in and
    # re-places. IPMState of (B, ·) numpy arrays.
    warm_host: object = None


@dataclasses.dataclass
class _PackJob:
    """One popped batch travelling through the pipeline queues."""

    key: QueueKey
    live: List[PendingRequest]
    expired: List[PendingRequest]
    packed: Optional[_Packed] = None
    pack_error: Optional[Exception] = None


class SolveService:
    """In-process async batching front-end over the batched backend."""

    def __init__(
        self,
        config: Optional[ServiceConfig] = None,
        solver_config: Optional[SolverConfig] = None,
        auto_start: bool = True,
        metrics: Optional[obs_metrics.MetricsRegistry] = None,
        tracer=None,
        mesh=None,
        slice_runner=None,
    ):
        self.config = config or ServiceConfig()
        # The bucket path solves raw standard form — presolve/scaling and
        # per-iteration diagnostics are general-form driver concerns.
        self.solver_config = (solver_config or SolverConfig()).replace(
            verbose=False, log_jsonl=None, checkpoint_path=None,
            checkpoint_every=0, profile_dir=None,
        )
        # Observability: an explicit registry/tracer wins (bench, tests);
        # else config paths create per-service ones; else inherit the
        # module defaults — NULL no-ops unless the CLI enabled them, so
        # the undecorated path costs nothing (the zero-warm-recompile
        # and pipeline-timing invariants are measured without obs on).
        if metrics is not None:
            self.metrics = metrics
        elif self.config.metrics_path:
            self.metrics = obs_metrics.MetricsRegistry()
        else:
            self.metrics = obs_metrics.get_registry()
        if tracer is not None:
            self.tracer = tracer
            self._owns_tracer = False
        elif self.config.trace_path:
            self.tracer = obs_trace.Tracer(
                self.config.trace_path, process_name="dlps-serve"
            )
            self._owns_tracer = True
        else:
            self.tracer = obs_trace.get_tracer()
            self._owns_tracer = False
        m = self.metrics
        self._m_requests_by_status: dict = {}
        self._m_dispatches = m.counter(
            "serve_dispatches_total", help="bucket batch dispatches"
        )
        self._m_compiles = m.counter(
            "serve_bucket_compiles_total",
            help="bucket programs compiled (warm paths must not grow this)",
        )
        self._m_solo = m.counter(
            "serve_solo_fallbacks_total",
            help="requests routed through the per-request solo ladder",
        )
        self._m_queue_ms = m.histogram(
            "serve_queue_ms", help="submit -> dispatch wait per request"
        )
        self._m_total_ms = m.histogram(
            "serve_total_ms", help="submit -> result latency per request"
        )
        self._m_pack_ms = m.histogram(
            "serve_pack_ms", help="host pack wall per dispatch"
        )
        self._m_solve_ms = m.histogram(
            "serve_solve_ms", help="device solve wall per dispatch"
        )
        self._m_overlap_ms = m.histogram(
            "serve_overlap_ms",
            help="host pack time under an earlier dispatch's solve window",
        )
        self._m_waste = m.histogram(
            "serve_padding_waste", buckets=obs_metrics.RATIO_BUCKETS,
            help="padded-entries fraction wasted per dispatch",
        )
        # Mixed-precision schedule telemetry: iterations per precision
        # engine (f32/df32/f64), phase switches per dispatch, and the
        # fused-iterations-per-while-trip the bucket programs run with.
        self._m_phase_iters: dict = {}  # engine -> counter (created lazily)
        # Tolerance-tiered ladder: dispatches by solve engine (ipm/pdhg).
        self._m_engine_dispatches: dict = {}  # engine -> counter (lazy)
        # Stochastic scenario tier: solves by terminal engine (the
        # degradation ladder may finish one on sparse-iterative), the
        # K distribution, and the decomposition's stage split.
        self._m_scenario_solves: dict = {}  # engine -> counter (lazy)
        self._m_scenario_k = m.histogram(
            "scenario_k", buckets=obs_metrics.SCENARIO_K_BUCKETS,
            help="scenario count per scenario-tier request",
        )
        self._m_scenario_schur_ms = m.histogram(
            "scenario_schur_ms",
            help="batched per-scenario Schur program wall per solve",
        )
        self._m_scenario_link_ms = m.histogram(
            "scenario_link_ms",
            help="first-stage linking factor/solve wall per solve",
        )
        self._m_phase_switches = m.counter(
            "serve_phase_switches_total",
            help="precision-phase transitions across bucket dispatches",
        )
        self._m_fused = m.gauge(
            "serve_fused_iters",
            help="IPM iterations fused per device while-loop trip",
        )
        # Warm-start & amortization layer: bounded LRU of prior iterates
        # keyed on structural fingerprints (serve/warmcache.py); the
        # cache's hit/miss counters land on this same registry.
        if self.config.warm_start:
            from distributedlpsolver_tpu.serve.warmcache import WarmCache

            self._warm_cache: Optional[object] = WarmCache(
                self.config.warm_cache_entries, metrics=m
            )
        else:
            self._warm_cache = None
        self._m_warm_rejected = m.counter(
            "warm_start_rejected_total",
            help="safeguard fallbacks: offered warm starts rejected for "
            "the cold start",
        )
        self._m_iters_by_start: dict = {}  # start label -> histogram
        # SLO-aware admission (net/admission.py): token-bucket quotas +
        # weighted-fair shares consulted on the submit path BEFORE the
        # scheduler's depth backstop; priorities shade flush windows.
        if self.config.admission is not None:
            from distributedlpsolver_tpu.net.admission import (
                AdmissionController,
            )

            self._admission: Optional[object] = AdmissionController(
                self.config.admission,
                max_depth=self.config.max_queue_depth,
                flush_s=self.config.flush_s,
                metrics=m,
            )
        else:
            self._admission = None
        # Read-only surface for the HTTP front-end (shared tenant
        # labeler) and introspection; None without the SLO layer.
        self.admission = self._admission
        # Overload brownout ladder (net/admission.BrownoutController):
        # sustained saturation (queue depth + reject rate) engages
        # staged degradation on the submit path — shed batch priority,
        # widen flush windows, re-route tol-eligible work to PDHG —
        # auto-releasing on recovery. None = no brownout.
        if self.config.brownout is not None:
            from distributedlpsolver_tpu.net.admission import (
                BrownoutController,
            )

            self._brownout: Optional[object] = BrownoutController(
                self.config.brownout,
                max_depth=self.config.max_queue_depth,
                metrics=m,
            )
        else:
            self._brownout = None
        # Multi-host slice mode (distributed/slice.py): an explicit
        # slice_runner routes every bucket dispatch through the slice
        # control plane so follower ranks execute the same programs; an
        # explicit mesh (usually the runner's global mesh) overrides the
        # local mesh_devices construction. Bucket batch divisibility is
        # enforced against the GLOBAL device count.
        self._slice = slice_runner
        if slice_runner is not None and mesh is None:
            mesh = slice_runner.mesh
        if slice_runner is not None and self.config.solo_backend == "auto":
            # Solo fallbacks run on rank 0 ONLY (no follower mirrors a
            # solo solve): pin them to the single-device dense backend —
            # "auto" could pick a mesh backend over the GLOBAL device
            # set and enter a collective no other rank is running.
            self.config = dataclasses.replace(
                self.config, solo_backend="dense"
            )
        self._mesh = (  # guarded-by: _lock
            mesh
            if mesh is not None
            else self._build_mesh(self.config.mesh_devices)
        )
        n_dev = int(self._mesh.devices.size) if self._mesh is not None else 1
        self.scheduler = Scheduler(  # guarded-by: _lock
            BucketTable(
                self.config.buckets, batch=self.config.batch, devices=n_dev
            ),
            self.config.max_queue_depth,
            self.config.flush_s,
            metrics=m,
        )
        self._logger = IterLogger(
            verbose=False, jsonl_path=self.config.log_jsonl
        )
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._idle = threading.Condition(self._lock)
        self._results: List[RequestResult] = []  # guarded-by: _lock
        self._next_id = 0  # guarded-by: _lock
        self._dispatch_seq = 0  # guarded-by: _lock
        self._inflight = 0  # guarded-by: _lock
        self._stopping = False  # guarded-by: _lock
        self._warm: set = set()  # guarded-by: _lock
        self._compiles = 0  # guarded-by: _lock
        # Pipeline queues: the scheduler thread pushes popped batches, the
        # pack thread fills in device-resident arrays, the solve thread
        # dispatches. Bounds keep the pipeline two-deep so batches aren't
        # popped long before the device can take them (late-arriving
        # requests still fill later buckets).
        depth = max(1, self.config.pipeline_depth)
        self._pack_q: Queue = Queue(maxsize=depth)
        self._solve_q: Queue = Queue(maxsize=max(1, depth - 1))
        # Pack-interval telemetry for overlap_ms: recent completed pack
        # windows plus the start stamp of the pack currently in flight.
        self._pack_spans: List[tuple] = []  # guarded-by: _span_lock
        self._pack_current: Optional[float] = None  # guarded-by: _span_lock
        self._span_lock = threading.Lock()
        self._dispatch_rows: List[dict] = []  # guarded-by: _lock
        self._overlap_ms_total = 0.0  # guarded-by: _lock
        self._pack_ms_total = 0.0  # guarded-by: _lock
        self._phase_iters: dict = {}  # engine -> total iters; guarded-by: _lock
        self._engine_dispatches: dict = {}  # guarded-by: _lock
        # Idle telemetry: how the dispatcher sleeps (satellite: the loop
        # waits exactly until Scheduler.next_event_in, surfaced here).
        self._idle_waits = 0  # guarded-by: _lock
        self._idle_sleep_s = 0.0  # guarded-by: _lock
        self._last_idle_timeout: Optional[float] = None  # guarded-by: _lock
        self._thread: Optional[threading.Thread] = None
        self._pack_thread: Optional[threading.Thread] = None
        self._solve_thread: Optional[threading.Thread] = None
        # Graceful drain: once set, submit sheds with a structured
        # "draining" verdict while accepted work runs to completion.
        self._draining = False  # guarded-by: _lock
        self._m_draining = m.gauge(
            "serve_draining", help="1 while the service is draining"
        )
        # Durable job journal: WAL + on-disk result store; replay
        # happens BEFORE the pipeline threads start so recovered work
        # is queued (in admit order) ahead of any new traffic.
        self._jobs: dict = {}  # jid -> Future of pending jobs; guarded-by: _lock
        self._replayed_by_fp: dict = {}  # jfp -> jid; guarded-by: _lock
        if self.config.journal_dir:
            from distributedlpsolver_tpu.serve.journal import JobJournal

            self._journal: Optional[object] = JobJournal(
                self.config.journal_dir,
                fsync=self.config.journal_fsync,
                compact_every=self.config.journal_compact_every,
                results_cap=self.config.journal_results_cap,
                metrics=m,
            )
            self._replay_journal()
        else:
            self._journal = None
        if auto_start:
            self.start()

    @staticmethod
    def _build_mesh(mesh_devices: int):
        if mesh_devices in (0, 1):
            return None
        import jax

        from distributedlpsolver_tpu.parallel import mesh as mesh_lib

        devs = jax.devices()
        k = len(devs) if mesh_devices == -1 else mesh_devices
        if k > len(devs):
            raise ValueError(
                f"mesh_devices={mesh_devices} but only {len(devs)} local "
                f"devices are present"
            )
        if k <= 1:
            return None
        return mesh_lib.make_mesh((k,), axis_names=("batch",), devices=devs[:k])

    @property
    def mesh_devices(self) -> int:
        """Devices the batch axis is currently sharded over (1 = unsharded)."""
        with self._lock:
            mesh = self._mesh
        return int(mesh.devices.size) if mesh is not None else 1

    @staticmethod
    def _mesh_key(mesh):
        return (
            None if mesh is None else tuple(int(d.id) for d in mesh.devices.flat)
        )

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "SolveService":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="dlps-serve-sched"
            )
            self._pack_thread = threading.Thread(
                target=self._run_pack, daemon=True, name="dlps-serve-pack"
            )
            self._solve_thread = threading.Thread(
                target=self._run_solve, daemon=True, name="dlps-serve-solve"
            )
            self._solve_thread.start()
            self._pack_thread.start()
            self._thread.start()
        return self

    def __enter__(self) -> "SolveService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    def _is_idle(self) -> bool:  # holds: _lock
        # _inflight covers every popped-but-unfinished request, including
        # batches sitting in the pipeline queues.
        return self.scheduler.depth() == 0 and self._inflight == 0

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Block until every accepted request has a result. False iff
        ``timeout`` expired first. Event-driven: waits on the idle
        condition the solve stage signals, no poll tick."""
        with self._idle:
            return self._idle.wait_for(self._is_idle, timeout)

    def shutdown(self, drain: bool = True, timeout: Optional[float] = None):
        """Stop accepting work; by default finish what was accepted
        (drain), then stop the pipeline threads and emit the summary
        record."""
        with self._wake:
            self._stopping = True
            self._wake.notify_all()
        if drain:
            self.drain(timeout)
        with self._wake:
            self._wake.notify_all()
        for t in (self._thread, self._pack_thread, self._solve_thread):
            if t is not None:
                t.join(timeout=10.0)
        self._thread = self._pack_thread = self._solve_thread = None
        summary = {"event": "service", **self.stats()}
        if self.metrics.enabled:
            # The summary event carries the JSON metrics snapshot, so a
            # single JSONL stream is self-describing for `cli report`.
            summary["metrics"] = self.metrics.snapshot()
        self._logger.event(summary)
        self._logger.close()
        if self._journal is not None:
            self._journal.close()
        if self.config.metrics_path and self.metrics.enabled:
            self.metrics.write_prometheus(self.config.metrics_path)
        if self._owns_tracer:
            self.tracer.close()

    # -- graceful drain ---------------------------------------------------

    @property
    def draining(self) -> bool:
        """True once :meth:`drain_for_shutdown` flipped the flag — the
        ``/readyz`` signal (healthz stays live; admission is closed)."""
        with self._lock:
            return self._draining

    def begin_draining(self) -> None:
        """Flip the draining flag synchronously: admission closes (and
        ``/readyz`` goes 503) the moment this returns, while accepted
        work keeps running. The blocking wait lives in
        :meth:`drain_for_shutdown`."""
        with self._wake:
            first = not self._draining
            self._draining = True
            depth = self.scheduler.depth()
            inflight = self._inflight
            self._wake.notify_all()
        if first:
            self._m_draining.set(1)
            self.tracer.instant(
                "serve.drain", args={"queue_depth": depth}, cat="serve"
            )
            self._logger.event(
                {
                    "event": "drain",
                    "phase": "begin",
                    "queue_depth": depth,
                    "inflight": inflight,
                }
            )

    def drain_for_shutdown(self, timeout: Optional[float] = None) -> bool:
        """Graceful drain: stop admission (submit raises a structured
        ``"draining"`` :class:`ServiceOverloaded` — the HTTP 503 +
        Retry-After path), finish every in-flight and queued request,
        then flush the journal. The pipeline threads stay up — callers
        own the final :meth:`shutdown` — and ``/healthz`` stays
        truthful throughout (the process is alive, just not ready).
        Returns True iff the service fully drained within ``timeout``.
        Idempotent: a second call just waits on the same drain."""
        self.begin_draining()
        drained = self.drain(timeout)
        if self._journal is not None:
            self._journal.flush()
        with self._lock:
            depth_end = self.scheduler.depth()
        self._logger.event(
            {
                "event": "drain",
                "phase": "end",
                "drained": drained,
                "queue_depth": depth_end,
            }
        )
        return drained

    # -- durable-journal recovery ----------------------------------------

    def _replay_journal(self) -> None:
        """Crash recovery: re-enqueue every admitted-but-unfinished job
        the WAL holds (in admit order), resolving ones whose wall-clock
        deadline died with the previous process to an honest TIMEOUT —
        an acknowledged request always ends in a verdict, never a
        silent disappearance."""
        from distributedlpsolver_tpu.models.problem import LPProblem
        from distributedlpsolver_tpu.serve.journal import JournaledJob

        rep = self._journal.replay()
        now_ts = time.time()
        reenqueued = expired = failed = 0
        for job in rep.unfinished:
            if job.deadline_ts is not None and job.deadline_ts <= now_ts:
                self._finish_replayed(
                    job, Status.TIMEOUT,
                    "deadline expired while the service was down",
                )
                expired += 1
                continue
            try:
                problem = LPProblem.from_dict(job.spec["problem"])
                remaining = (
                    None
                    if job.deadline_ts is None
                    else max(0.001, job.deadline_ts - now_ts)
                )
                self.submit(
                    problem,
                    deadline=remaining,
                    tol=job.spec.get("tol"),
                    name=job.spec.get("name"),
                    tenant=job.tenant,
                    priority=job.priority,
                    _replay_job=job,
                )
                reenqueued += 1
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                # Malformed spec or overflow: the job still resolves —
                # a FAILED verdict is honest, dropping it is not.
                self._finish_replayed(
                    job, Status.FAILED, f"{type(e).__name__}: {e}"
                )
                failed += 1
        self._logger.event(
            {
                "event": "journal_replay",
                "replayed": len(rep.unfinished),
                "reenqueued": reenqueued,
                "expired": expired,
                "failed": failed,
                "torn": rep.torn,
                "skipped": rep.skipped,
                "results": rep.results,
            }
        )

    def _finish_replayed(
        self, job, status: Status, detail: str
    ) -> None:
        """Resolve one replayed job without re-running it (expired
        deadline, unreplayable spec) through the normal finish funnel so
        the journal, telemetry, and result store all agree."""
        now = time.perf_counter()
        p = PendingRequest(
            request_id=-1,
            name=str(job.spec.get("name") or "replayed"),
            c=None, A=None, b=None,
            tol=self.solver_config.tol,
            future=Future(),
            t_submit=now,
            problem=None,
            tenant=job.tenant,
            priority=job.priority,
            jid=job.jid,
            jfp=job.fp,
        )
        with self._lock:
            p.request_id = self._next_id
            self._next_id += 1
        fault = FaultRecord(
            FaultKind.CRASH, -1, "journal", detail, action="give_up"
        )
        fault.at_time = time.time()
        self._finish(
            p,
            RequestResult(
                request_id=p.request_id,
                name=p.name,
                status=status,
                objective=float("nan"),
                x=None,
                iterations=0,
                rel_gap=_INF,
                pinf=_INF,
                dinf=_INF,
                bucket=None,
                queue_ms=0.0,
                compile_ms=0.0,
                solve_ms=0.0,
                total_ms=0.0,
                padding_waste=0.0,
                faults=[fault],
                t_submit=now,
                t_done=now,
            ),
        )

    def job_result(self, jid: str) -> tuple:
        """Poll surface for durable job ids: ``("done", record)`` with
        the stored result record, ``("pending", None)`` while the job is
        queued or in flight (including replayed-but-unfinished), or
        ``("unknown", None)`` — never minted here, or evicted past the
        result-store bound."""
        if self._journal is None or not jid:
            return ("unknown", None)
        rec = self._journal.result(jid)
        if rec is not None:
            return ("done", rec)
        with self._lock:
            fut = self._jobs.get(jid)
        if (fut is not None and not fut.done()) or self._journal.is_pending(
            jid
        ):
            return ("pending", None)
        return ("unknown", None)

    def cancel(self, jid: str) -> tuple:
        """Cancel the queued-but-not-dispatched job ``jid`` — the hedge
        loser's path (``POST /v1/cancel/{jid}``). Returns
        ``(cancelled, state)`` where state is one of ``"cancelled"``
        (removed from the queue and resolved through the normal finish
        funnel: admission units released, journal stamped ``cancelled``,
        future resolved with the CANCELLED verdict), ``"dispatched"``
        (already riding a compiled batch — lanes are never torn
        mid-program, the solve runs to completion), ``"finished"``
        (verdict already durable), or ``"unknown"``."""
        if not jid:
            return False, "unknown"
        with self._wake:
            p = self.scheduler.remove(jid)
            fut = None if p is not None else self._jobs.get(jid)
        if p is None:
            # Journal reads happen outside the service lock (the result
            # store is disk-backed).
            if fut is not None and not fut.done():
                return False, "dispatched"
            if self._journal is not None:
                if self._journal.result(jid) is not None:
                    return False, "finished"
                if self._journal.is_pending(jid):
                    return False, "dispatched"
            return False, "unknown"
        now = time.perf_counter()
        waited_ms = (now - p.t_submit) * 1e3
        self._finish(
            p,
            RequestResult(
                request_id=p.request_id,
                name=p.name,
                status=Status.CANCELLED,
                objective=float("nan"),
                x=None,
                iterations=0,
                rel_gap=_INF,
                pinf=_INF,
                dinf=_INF,
                bucket=None,
                queue_ms=waited_ms,
                compile_ms=0.0,
                solve_ms=0.0,
                total_ms=waited_ms,
                padding_waste=0.0,
                m=p.m,
                n=p.n,
                t_submit=p.t_submit,
                t_done=now,
            ),
        )
        self._logger.event(
            {
                "event": "cancel",
                "jid": jid,
                "id": p.request_id,
                "name": p.name,
                "tenant": p.tenant,
                "state": "cancelled",
                "queue_ms": round(waited_ms, 3),
            }
        )
        return True, "cancelled"

    # -- submission ------------------------------------------------------

    def submit(
        self,
        problem: LPProblem,
        deadline: Optional[float] = None,
        tol: Optional[float] = None,
        name: Optional[str] = None,
        tenant: str = "default",
        priority: str = "normal",
        trace=None,
        _replay_job=None,
    ) -> Future:
        """Enqueue one LP; the Future resolves to a RequestResult.

        ``trace`` is the request's :class:`obs.context.TraceContext`
        (or None): it annotates the request's spans and records, is
        journaled with the job so a replay resumes the original trace,
        and never touches the solve itself.

        ``deadline`` is seconds from now: a request still queued when it
        expires is returned ``Status.TIMEOUT`` (it never poisons its
        batch — expiry is checked before a slot is assigned). ``tol``
        defaults to the service solver config's tolerance; a novel tol
        compiles its own bucket program once, then shares it.

        ``tenant``/``priority`` feed the SLO-aware admission layer when
        one is configured (``ServiceConfig.admission``): quota and
        fair-share rejections raise :class:`ServiceOverloaded` with the
        structured verdict (reason + retry_after_s), the priority class
        shades the request's flush window, and deadlines order slot
        assignment (EDF) inside its bucket queue.

        With a durable journal (``ServiceConfig.journal_dir``) the
        request is write-ahead logged before it is queued, the returned
        Future carries the durable job id as ``fut.jid`` (the poll
        token that survives restarts), and a resubmit whose content
        fingerprint matches a replayed-but-unfinished job attaches to
        that job's Future instead of solving twice (crash-retry
        idempotency). ``_replay_job`` is the journal's own re-enqueue
        path — never pass it.
        """
        sf = standard_form(problem)
        fp = None
        if self._warm_cache is not None:
            from distributedlpsolver_tpu.utils.fingerprint import (
                structural_fingerprint,
            )

            # Structural identity on the SUBMIT thread (a hash over A's
            # bytes — microseconds at request shapes): correlated
            # requests (same A, new b/c) land on one cache key.
            fp = structural_fingerprint(
                problem.A, problem.m, problem.n, problem.lb, problem.ub
            )
        now = time.perf_counter()
        if deadline is None:
            deadline = self.config.default_deadline_s
        req_tol = tol if tol is not None else self.solver_config.tol
        # Stochastic scenario tier: a lowered two-stage problem (the
        # ScenarioLP lowering attaches the hint; sparse A keeps it off
        # the bucketed path) routes to the scenario-decomposed engine
        # and charges admission by its fair-share units.
        hint = problem.block_structure or {}
        n_scen = scen_bucket = None
        units = 1
        if hint.get("kind") == "two_stage":
            from distributedlpsolver_tpu.models.scenario import (
                scenario_k_bucket,
            )

            n_scen = int(hint.get("num_blocks", 1))
            scen_bucket = scenario_k_bucket(n_scen)
            units = max(
                1, -(-n_scen // max(1, self.config.scenario_k_unit))
            )
            engine = "scenario"
            # Always the solo route: a dense-stored lowered form would
            # otherwise pass the standard_form gate and ride a bucket
            # program mislabeled as scenario.
            sf = None
        else:
            # Tolerance-tiered engine routing: loose standard-form
            # requests ride the matrix-free PDHG engine, tight ones the
            # IPM buckets.
            engine = (
                "pdhg"
                if (
                    self.config.pdhg_routing
                    and sf is not None
                    and req_tol >= self.config.pdhg_tol
                )
                else "ipm"
            )
        # Durable journal: serialize the request OUTSIDE the lock (the
        # spec encode is the expensive part), write-ahead log it inside.
        job_spec = jfp = None
        if self._journal is not None and _replay_job is None:
            from distributedlpsolver_tpu.serve import journal as journal_mod

            job_spec = journal_mod.request_spec(
                problem, tol=tol, tenant=tenant, priority=priority,
                name=name,
            )
            jfp = journal_mod.request_fingerprint(job_spec)
        p = PendingRequest(
            request_id=-1,
            name=name or problem.name,
            c=sf[0] if sf else None,
            A=sf[1] if sf else None,
            b=sf[2] if sf else None,
            tol=req_tol,
            future=Future(),
            t_submit=now,
            deadline=None if deadline is None else now + deadline,
            problem=None if sf else problem,
            fp=fp,
            tenant=tenant,
            priority=priority,
            flush_scale=(
                self._admission.flush_scale(priority)
                if self._admission is not None
                else 1.0
            ),
            engine=engine,
            jid=_replay_job.jid if _replay_job is not None else None,
            jfp=_replay_job.fp if _replay_job is not None else jfp,
            units=units,
            n_scenarios=n_scen,
            scenario_bucket=scen_bucket,
            trace=(
                _replay_job.trace_context()
                if _replay_job is not None and trace is None
                else trace
            ),
        )
        # Overload brownout ladder: observe saturation (logging any
        # stage transitions), then apply the current stage's rungs —
        # shed batch priority with a structured verdict, widen the
        # flush window, re-route tol-eligible work to PDHG. Replays are
        # exempt: they were admitted before the crash and the journal
        # owes them a verdict.
        if self._brownout is not None and _replay_job is None:
            with self._lock:
                depth_now = self.scheduler.depth()
            for ev in self._brownout.observe(depth_now, now):
                self._logger.event(ev)
            if self._brownout.should_shed(priority):
                retry = self._brownout.config.retry_after_s
                self._log_reject(p, "brownout", retry)
                raise ServiceOverloaded(
                    "brownout: batch-priority work shed under overload "
                    f"(stage {self._brownout.stage()})",
                    reason="brownout",
                    retry_after_s=retry,
                    tenant=tenant,
                )
            p.flush_scale *= self._brownout.flush_widen()
            if (
                p.engine == "ipm"
                and sf is not None
                and self.config.pdhg_routing
                and self._brownout.reroute_pdhg(req_tol)
            ):
                # Stage 3: the cheaper first-order engine takes the
                # tol-eligible traffic. Crossover honesty still holds —
                # a PDHG lane is OPTIMAL only at true KKT ≤ the request
                # tol, else it re-solves through the solo IPM ladder.
                p.engine = "pdhg"
        with self._wake:
            if self._stopping:
                raise RuntimeError("SolveService is shut down")
            if self._draining and _replay_job is None:
                raise ServiceOverloaded(
                    "service is draining for shutdown",
                    reason="draining",
                    retry_after_s=max(1.0, self.config.flush_s * 10),
                    tenant=tenant,
                )
            if jfp is not None:
                # Crash-retry idempotency: a resubmit of a replayed
                # pending job rides the existing Future — one solve,
                # one journal entry, one verdict.
                existing = self._replayed_by_fp.get(jfp)
                if existing is not None:
                    fut = self._jobs.get(existing)
                    if fut is not None and not fut.done():
                        return fut
                    self._replayed_by_fp.pop(jfp, None)
            p.request_id = self._next_id
            self._next_id += 1
            if self._admission is not None and _replay_job is None:
                v = self._admission.admit(tenant, priority, now, units=units)
                if not v.admitted:
                    self._log_reject(p, v.reason, v.retry_after_s)
                    raise ServiceOverloaded(
                        f"admission rejected tenant {tenant!r}: "
                        f"{v.reason} — {v.detail}",
                        reason=v.reason,
                        retry_after_s=v.retry_after_s,
                        tenant=tenant,
                    )
            try:
                # Replays are depth-exempt for the same reason they are
                # admission-exempt: the journal owes them a verdict.
                key = self.scheduler.add(p, exempt=_replay_job is not None)
            except ServiceOverloaded as e:
                self._log_reject(p, e.reason, e.retry_after_s)
                raise
            if self._admission is not None:
                self._admission.on_admitted(tenant, units=units)
            if self._journal is not None:
                if _replay_job is not None:
                    self._journal.readmit(_replay_job)
                    self._replayed_by_fp[_replay_job.fp] = _replay_job.jid
                else:
                    p.jid = self._journal.admit(
                        job_spec, jfp, tenant, priority,
                        deadline_ts=(
                            None if deadline is None
                            else time.time() + deadline
                        ),
                        # Trace rides the WAL OUTSIDE the spec: the
                        # content fingerprint (idempotency key) must not
                        # change because a retry re-traced the request.
                        trace=(
                            p.trace.to_header()
                            if p.trace is not None
                            else None
                        ),
                    )
                self._jobs[p.jid] = p.future
            # Request track opens on the submit thread; the nested queue
            # span (and later pack/solve) begin/end on whichever pipeline
            # thread handles them — same (cat, id) keeps the track
            # connected across threads.
            req_args = {
                "id": p.request_id, "name": p.name,
                "m": p.m, "n": p.n,
                "bucket": list(key[0].key()), "tol": key[1],
                "engine": key[2],
            }
            if p.trace is not None:
                req_args.update(p.trace.span_args())
            self.tracer.async_begin(
                "request", p.request_id, args=req_args
            )
            self.tracer.async_begin("queue", p.request_id)
            self._wake.notify_all()
        # The durable poll token rides the Future (None without a
        # journal): the HTTP front-end issues it as the async id, so
        # GET /v1/solve/{jid} keeps resolving across restarts.
        p.future.jid = p.jid
        return p.future

    def _log_reject(
        self, p: PendingRequest, reason: str, retry_after_s: float
    ) -> None:  # holds: _lock
        """One reject record per shed request: the verdict reason and
        wait hint ride the event so overload post-mortems can tell a
        quota-limited tenant from a depth wall."""
        if self._brownout is not None and reason != "brownout":
            # Non-brownout rejections feed the saturation signal's
            # reject-rate half; brownout's own sheds are excluded or
            # stage 1 would sustain itself forever.
            self._brownout.note_reject()
        self.tracer.instant(
            "serve.reject",
            args={"id": p.request_id, "name": p.name, "reason": reason},
            cat="serve",
        )
        self._logger.event(
            {
                "event": "reject",
                "id": p.request_id,
                "name": p.name,
                "tenant": p.tenant,
                "priority": p.priority,
                "reason": reason,
                "retry_after_s": round(retry_after_s, 6),
                "queue_depth": self.scheduler.depth(),
            }
        )

    # -- pipeline stage 1: scheduler -------------------------------------

    def _run(self) -> None:
        while True:
            with self._wake:
                now = time.perf_counter()
                ready = self.scheduler.ready(now)
                if not ready:
                    if self._stopping and self.scheduler.depth() == 0:
                        break
                    # Part-full buckets flush on a clock; sleep for
                    # exactly the earliest flush/request deadline (or
                    # until a submit notifies) — never a fixed poll tick.
                    timeout = self.scheduler.next_event_in(now)
                    self._idle_waits += 1
                    self._last_idle_timeout = timeout
                    t_w = time.perf_counter()
                    self._wake.wait(timeout=timeout)
                    self._idle_sleep_s += time.perf_counter() - t_w
                    continue
                jobs = []
                for key in ready:
                    live, expired = self.scheduler.pop(key, now)
                    jobs.append(_PackJob(key, live, expired))
                    self._inflight += len(live) + len(expired)
                    for p in live:
                        self.tracer.async_end("queue", p.request_id)
                    for p in expired:
                        self.tracer.async_end(
                            "queue", p.request_id,
                            args={"expired": True},
                        )
            for job in jobs:  # bounded put: pipeline backpressure
                self._pack_q.put(job)
        self._pack_q.put(None)  # sentinel flows sched → pack → solve

    # -- pipeline stage 2: pack ------------------------------------------

    def _run_pack(self) -> None:
        while True:
            job = self._pack_q.get()
            if job is None:
                self._solve_q.put(None)
                return
            if job.live:
                # Second expiry gate: pop splits expired requests against
                # the scheduler's timestamp, captured before the queue
                # lock — a sub-millisecond deadline submitted while the
                # dispatcher is waking can race it and pop as live. Slot
                # assignment happens HERE, so this is the last honest
                # moment to split TIMEOUT verdicts out before device
                # work is committed on their behalf.
                t_gate = time.perf_counter()
                still, late = [], []
                for p in job.live:
                    dst = (
                        late
                        if p.deadline is not None and p.deadline <= t_gate
                        else still
                    )
                    dst.append(p)
                if late:
                    job.live = still
                    job.expired.extend(late)
            if job.live and job.live[0].A is not None:
                spec = job.key[0]
                for p in job.live:
                    self.tracer.async_begin("pack", p.request_id)
                t0 = time.perf_counter()
                with self._span_lock:
                    self._pack_current = t0
                pack_args = {"live": len(job.live)}
                if self.tracer.enabled:
                    # Batch spans carry every member's trace_id: one
                    # dispatch serves many traces, so the aggregator
                    # joins on the list rather than a single id.
                    tids = [
                        p.trace.trace_id
                        for p in job.live
                        if p.trace is not None
                    ]
                    if tids:
                        pack_args["trace_ids"] = tids
                try:
                    with self.tracer.span(
                        f"pack {spec.m}x{spec.n}x{spec.batch}",
                        cat="pipeline",
                        args=pack_args,
                    ):
                        job.packed = self._pack_bucket(job.key, job.live)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    # The solve stage fails the batch's futures; the pack
                    # thread must survive whatever a malformed request
                    # throws at it.
                    job.pack_error = e
                t1 = time.perf_counter()
                with self._span_lock:
                    self._pack_current = None
                    self._pack_spans.append((t0, t1))
                    del self._pack_spans[:-128]
                for p in job.live:
                    self.tracer.async_end("pack", p.request_id)
            if self._journal is not None and job.pack_error is None:
                for p in job.live:
                    if p.jid is not None:
                        self._journal.mark(p.jid, "packed")
            self._solve_q.put(job)

    def _pack_bucket(self, key: QueueKey, live: List[PendingRequest]) -> _Packed:
        """Host work of one dispatch: pad each member onto the bucket
        shape, stack, and transfer to the device(s) — sharded over the
        serving mesh's batch axis when one is configured. Runs in the
        pack thread, concurrently with the previous dispatch's solve."""
        from distributedlpsolver_tpu.backends.batched import (
            place_bucket,
            place_warm,
        )
        from distributedlpsolver_tpu.ipm.state import IPMState
        from distributedlpsolver_tpu.models.generators import BatchedLP

        spec, tol, engine = key
        B = spec.batch
        t0 = time.perf_counter()
        A = np.zeros((B, spec.m, spec.n))
        b = np.zeros((B, spec.m))
        c = np.zeros((B, spec.n))
        active = np.zeros(B, dtype=bool)
        for k, p in enumerate(live):
            c[k], A[k], b[k] = pad_standard_form(p.c, p.A, p.b, spec.m, spec.n)
            active[k] = True
        for k in range(len(live), B):  # inactive slots: well-posed copies
            A[k], b[k], c[k] = A[0], b[0], c[0]
        batch = BatchedLP(c=c, A=A, b=b, name=f"bucket_{spec.m}x{spec.n}")
        if engine == "pdhg":
            # The first-order engine neither consumes nor produces warm
            # iterates (a tol-loose PDHG point must not seed the IPM
            # warm cache); its lanes stay cold by design.
            warm_states, warm_mask, warm_hits = None, None, None
        else:
            warm_states, warm_mask, warm_hits = self._build_warm_lanes(
                spec, live
            )
        # Snapshot: a reshard mid-pipeline only affects later packs; this
        # bucket solves on the mesh it was placed on.
        with self._lock:
            mesh = self._mesh
        cfg = self.solver_config.replace(tol=tol)
        if self._slice is not None:
            # Slice mode: the batch stays HOST-side — the dispatch seam
            # publishes it to the follower ranks and every rank (0
            # included) places its own addressable shards at execute
            # time. The pad/stack/mask work above is still the pack
            # stage's overlap win; only the device transfer moves.
            pack_ms = (time.perf_counter() - t0) * 1e3
            return _Packed(
                batch=batch,
                active=active,
                waste=padding_waste(sum(p.m * p.n for p in live), spec),
                pack_ms=pack_ms,
                mesh=mesh,
                warm=None,
                warm_mask=warm_mask,  # host (B,) bool mask
                warm_hits=warm_hits,
                warm_host=warm_states,
            )
        placed, act = place_bucket(batch, active, cfg, mesh=mesh)
        warm_placed = mask_placed = None
        if warm_states is not None:
            warm_placed, mask_placed = place_warm(
                warm_states, warm_mask, (B, spec.m, spec.n), cfg, mesh=mesh
            )
        pack_ms = (time.perf_counter() - t0) * 1e3
        return _Packed(
            batch=placed,
            active=act,
            waste=padding_waste(sum(p.m * p.n for p in live), spec),
            pack_ms=pack_ms,
            mesh=mesh,
            warm=warm_placed,
            warm_mask=mask_placed,
            warm_hits=warm_hits,
            warm_host=warm_states,
        )

    def _build_warm_lanes(self, spec, live: List[PendingRequest]):
        """Warm lanes for one bucket: look each member's fingerprint up
        in the cache and pad its prior iterate onto the bucket shape.
        The pad block's fill (x=1, y=0, s=1) is EXACTLY feasible for the
        padding scheme's trivial 1x1 sub-LPs, so a warm slot's padded
        iterate is as interior as its real block. Cache misses leave the
        slot cold — one dispatch freely mixes both. Returns
        (host IPMState, mask, hits) or (None, None, None) when the warm
        layer is disabled."""
        from distributedlpsolver_tpu.ipm.state import IPMState

        if self._warm_cache is None:
            return None, None, None
        B = spec.batch
        wx = np.ones((B, spec.n))
        wy = np.zeros((B, spec.m))
        ws_ = np.ones((B, spec.n))
        ww = np.ones((B, spec.n))
        wz = np.zeros((B, spec.n))
        wm = np.zeros(B, dtype=bool)
        hits = []
        for k, p in enumerate(live):
            entry = self._warm_cache.lookup(p.fp, p.m, p.n) if p.fp else None
            if entry is not None and entry.state is not None:
                st = entry.state
                wx[k, : p.n] = st.x
                wy[k, : p.m] = st.y
                ws_[k, : p.n] = st.s
                ww[k, : p.n] = st.w
                wz[k, : p.n] = st.z
                wm[k] = True
            hits.append(bool(wm[k]))
        return IPMState(x=wx, y=wy, s=ws_, w=ww, z=wz), wm, hits

    def _late_warm_lookup(self, spec, tol, live, packed, mesh) -> None:
        """Solve-stage re-lookup for slots that missed the cache at pack
        time: the pack stage runs pipeline_depth batches AHEAD of the
        demux that stores entries, so back-to-back same-fingerprint
        requests would otherwise never warm. Only previously-missed
        slots are looked up again; a new hit patches the retained host
        lanes and re-places them (small arrays — a few µs of transfer
        before the device dispatch)."""
        from distributedlpsolver_tpu.backends.batched import place_warm

        if (
            self._warm_cache is None
            or packed.warm_host is None
            or packed.warm_hits is None
        ):
            return
        hits = packed.warm_hits
        if all(h or not p.fp for p, h in zip(live, hits)):
            return
        st = packed.warm_host
        new_hit = False
        for k, p in enumerate(live):
            if hits[k] or not p.fp:
                continue
            entry = self._warm_cache.lookup(p.fp, p.m, p.n)
            if entry is not None and entry.state is not None:
                e = entry.state
                st.x[k, : p.n] = e.x
                st.y[k, : p.m] = e.y
                st.s[k, : p.n] = e.s
                st.w[k, : p.n] = e.w
                st.z[k, : p.n] = e.z
                hits[k] = True
                new_hit = True
        if not new_hit:
            return
        wm = np.zeros(spec.batch, dtype=bool)
        wm[: len(hits)] = hits
        if self._slice is not None:
            # Slice mode keeps host lanes; the dispatch seam publishes
            # the patched warm_host + mask — re-placement happens on
            # every rank at execute time.
            packed.warm_mask = wm
            return
        packed.warm, packed.warm_mask = place_warm(
            st, wm, (spec.batch, spec.m, spec.n),
            self.solver_config.replace(tol=tol), mesh=mesh,
        )

    def _overlap_ms(self, t1: float, t2: float) -> float:
        """How much host pack time fell inside the solve window [t1, t2]
        — the pipeline's measured overlap (pack of batch k+1 concurrent
        with solve of batch k)."""
        with self._span_lock:
            spans = list(self._pack_spans)
            current = self._pack_current
        o = 0.0
        for ps, pe in spans:
            o += max(0.0, min(t2, pe) - max(t1, ps))
        if current is not None:  # a pack still in flight at solve end
            o += max(0.0, t2 - max(t1, current))
        return o * 1e3

    # -- pipeline stage 3: solve -----------------------------------------

    def _run_solve(self) -> None:
        while True:
            job = self._solve_q.get()
            if job is None:
                return
            key, live, expired = job.key, job.live, job.expired
            try:
                if job.pack_error is not None:
                    raise job.pack_error
                self._dispatch(key, live, expired, job.packed)
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                # Last-ditch guard: an exception escaping _dispatch
                # would kill the solve stage and strand every queued
                # future forever. Fail the batch's unresolved members
                # instead.
                self._fail_batch(key, live + expired, e)
            finally:
                with self._lock:
                    self._inflight -= len(live) + len(expired)
                    if self._is_idle():
                        self._idle.notify_all()

    def _dispatch(
        self,
        key: QueueKey,
        live: List[PendingRequest],
        expired: List[PendingRequest],
        packed: Optional[_Packed] = None,
    ) -> None:
        now = time.perf_counter()
        for p in expired:
            self._finish(
                p,
                RequestResult(
                    request_id=p.request_id,
                    name=p.name,
                    status=Status.TIMEOUT,
                    objective=float("nan"),
                    x=None,
                    iterations=0,
                    rel_gap=_INF,
                    pinf=_INF,
                    dinf=_INF,
                    bucket=key[0].key(),
                    queue_ms=(now - p.t_submit) * 1e3,
                    compile_ms=0.0,
                    solve_ms=0.0,
                    total_ms=(now - p.t_submit) * 1e3,
                    padding_waste=0.0,
                    t_submit=p.t_submit,
                    t_done=now,
                    m=p.m,
                    n=p.n,
                    engine=p.engine,
                ),
            )
        if not live:
            return
        if self._journal is not None:
            for p in live:
                if p.jid is not None:
                    self._journal.mark(p.jid, "dispatched")
        if live[0].A is None:  # general-form solo pseudo-bucket
            for p in live:
                self._solo(p, key, now, [], retried=False)
            return
        self._dispatch_bucket(key, live, now, packed)

    def _dispatch_bucket(
        self,
        key: QueueKey,
        live: List[PendingRequest],
        t_dispatch: float,
        packed: Optional[_Packed] = None,
    ) -> None:
        from distributedlpsolver_tpu.backends.batched import (
            bucket_cache_size,
            solve_bucket,
        )
        from distributedlpsolver_tpu.backends.first_order import (
            solve_pdhg_bucket,
        )

        spec, tol, engine = key
        if packed is None:
            # Direct-call fallback (tests, pipeline disabled): pack inline.
            packed = self._pack_bucket(key, live)
        batch, active, mesh = packed.batch, packed.active, packed.mesh
        cfg = self.solver_config.replace(tol=tol)
        waste = packed.waste
        if engine != "pdhg":
            self._late_warm_lookup(spec, tol, live, packed, mesh)
        with self._lock:
            seq = self._dispatch_seq
            self._dispatch_seq += 1

        solve_engine_fn = solve_pdhg_bucket if engine == "pdhg" else solve_bucket
        warm_key = (spec.key(), tol, cfg.dtype, self._mesh_key(mesh), engine)
        compile_ms = 0.0

        faults: List[FaultRecord] = []
        res = None
        for p in live:
            self.tracer.async_begin("solve", p.request_id)
        t_sol0 = time.perf_counter()
        for attempt in range(1 + self.config.max_batch_retries):
            try:
                if self.config.fault_injector is not None:
                    self.config.fault_injector(seq, key)

                # Cold bucket: one max_iter=1 call compiles the program
                # (max_iter is traced, so it is the SAME executable the
                # real solve reuses) — the compile cost is stamped as
                # compile_ms on this batch's requests instead of polluting
                # solve_ms forever after. Inside the fault loop so a
                # compile failure (XLA OOM, device error) degrades like
                # any other dispatch fault rather than escaping. Keyed
                # per (bucket, tol, dtype, mesh): a re-formed mesh
                # legitimately compiles once more.
                with self._lock:
                    cold = warm_key not in self._warm
                if cold:
                    size0 = bucket_cache_size()
                    t0 = time.perf_counter()
                    with self.tracer.span(
                        f"compile {spec.m}x{spec.n}x{spec.batch}/{engine}",
                        cat="pipeline",
                    ):
                        if self._slice is not None:
                            # Every rank of the slice must compile this
                            # program: the warm-up rides the dispatch
                            # seam like any other bucket call.
                            self._slice.dispatch(
                                spec, tol, engine, batch, active,
                                max_iter=1,
                            )
                        else:
                            solve_engine_fn(
                                batch, active, cfg, mesh=mesh, max_iter=1
                            )
                    compile_ms = (time.perf_counter() - t0) * 1e3
                    new_programs = bucket_cache_size() - size0
                    self._m_compiles.inc(new_programs)
                    with self._lock:
                        self._warm.add(warm_key)
                        self._compiles += new_programs

                def _solve():
                    if self._slice is not None:
                        return self._slice.dispatch(
                            spec, tol, engine, batch, active,
                            warm_host=(
                                None if engine == "pdhg" else packed.warm_host
                            ),
                            warm_mask=packed.warm_mask,
                            # Rank 0 publishes the members' trace headers
                            # in the dispatch journal meta; followers
                            # join as rank-stamped child spans. Host-side
                            # JSON only — never a program static.
                            trace=[
                                p.trace.to_header()
                                for p in live
                                if p.trace is not None
                            ] or None,
                        )
                    if engine == "pdhg":
                        return solve_pdhg_bucket(batch, active, cfg, mesh=mesh)
                    return solve_bucket(
                        batch, active, cfg, mesh=mesh,
                        warm=packed.warm, warm_mask=packed.warm_mask,
                    )

                res = run_with_deadline(
                    _solve, self.config.batch_timeout_s, seq
                )
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except StepDeadlineExceeded as e:
                fault = FaultRecord(
                    FaultKind.HANG, -1, "batched", str(e),
                    action="retry_batch"
                    if attempt < self.config.max_batch_retries
                    else "solo_fallback",
                )
            except Exception as e:
                fault = FaultRecord(
                    FaultKind.CRASH, -1, "batched",
                    f"{type(e).__name__}: {e}",
                    action="retry_batch"
                    if attempt < self.config.max_batch_retries
                    else "solo_fallback",
                )
            fault.at_time = time.time()
            faults.append(fault)
            self.tracer.instant(
                "serve.fault",
                args={
                    "dispatch": seq, "kind": fault.kind.value,
                    "action": fault.action,
                },
                cat="serve",
            )
            self._logger.event(
                {
                    "event": "fault",
                    "dispatch": seq,
                    "bucket": list(spec.key()),
                    "kind": fault.kind.value,
                    "action": fault.action,
                    "detail": fault.detail[:300],
                }
            )
        t_sol1 = time.perf_counter()
        for p in live:
            self.tracer.async_end("solve", p.request_id)
        solve_args = {"dispatch": seq, "live": len(live),
                      "attempts": len(faults) + (1 if res is not None else 0)}
        if self.tracer.enabled:
            tids = [
                p.trace.trace_id for p in live if p.trace is not None
            ]
            if tids:
                solve_args["trace_ids"] = tids
        self.tracer.complete(
            f"solve {spec.m}x{spec.n}x{spec.batch} #{seq}",
            t_sol1 - t_sol0, cat="pipeline",
            args=solve_args,
            end_us=t_sol1 * 1e6,
        )
        # Pack work (for LATER batches) that ran inside this dispatch's
        # device window — the pipeline's realized overlap.
        overlap_ms = self._overlap_ms(t_sol0, t_sol1)
        self._m_dispatches.inc()
        ctr = self._m_engine_dispatches.get(engine)
        if ctr is None:
            ctr = self.metrics.counter(
                "serve_engine_dispatches_total",
                labels={"engine": engine},
                help="bucket dispatches by solve engine (ipm/pdhg)",
            )
            self._m_engine_dispatches[engine] = ctr
        ctr.inc()
        self._m_pack_ms.observe(packed.pack_ms)
        self._m_solve_ms.observe((t_sol1 - t_sol0) * 1e3)
        self._m_overlap_ms.observe(overlap_ms)
        self._m_waste.observe(waste)
        # Precision-schedule telemetry (phase rows come back host-side on
        # the BatchedResult — no device sync here): per-engine iteration
        # counters, phase-switch count, and the fused-k the program ran.
        sched_rows = (res.phase_report or []) if res is not None else []
        schedule_str = "→".join(
            f"{r['engine']}@{r['tol']:g}" for r in sched_rows
        ) or None
        fused_k = res.fused_iters if res is not None else None
        n_warm = (
            int(np.sum(res.warm_used[: len(live)]))
            if res is not None and res.warm_used is not None
            else 0
        )
        for r in sched_rows:
            ctr = self._m_phase_iters.get(r["engine"])
            if ctr is None:
                ctr = self.metrics.counter(
                    "serve_phase_iters_total",
                    labels={"engine": r["engine"]},
                    help="bucket IPM iterations by precision engine",
                )
                self._m_phase_iters[r["engine"]] = ctr
            ctr.inc(r["iters"])
        if len(sched_rows) > 1:
            self._m_phase_switches.inc(len(sched_rows) - 1)
        if fused_k is not None:
            self._m_fused.set(fused_k)

        with self._lock:
            depth = self.scheduler.depth()
            occupancy = self.scheduler.occupancy()
            self._overlap_ms_total += overlap_ms
            self._pack_ms_total += packed.pack_ms
            for r in sched_rows:
                self._phase_iters[r["engine"]] = (
                    self._phase_iters.get(r["engine"], 0) + r["iters"]
                )
            self._engine_dispatches[engine] = (
                self._engine_dispatches.get(engine, 0) + 1
            )
            self._dispatch_rows.append(
                {
                    "dispatch": seq,
                    "bucket": list(spec.key()),
                    "engine": engine,
                    "live": len(live),
                    "pack_ms": round(packed.pack_ms, 3),
                    "compile_ms": round(compile_ms, 3),
                    "solve_ms": round((t_sol1 - t_sol0) * 1e3, 3),
                    "overlap_ms": round(overlap_ms, 3),
                    "schedule": schedule_str,
                    "fused_iters": fused_k,
                    "warm": n_warm,
                    "mesh_devices": (
                        int(mesh.devices.size) if mesh is not None else 1
                    ),
                }
            )
            del self._dispatch_rows[:-2048]
        self._logger.event(
            {
                "event": "batch",
                "dispatch": seq,
                "bucket": list(spec.key()),
                "tol": tol,
                "engine": engine,
                "live": len(live),
                "padding_waste": round(waste, 4),
                "pack_ms": round(packed.pack_ms, 3),
                "compile_ms": round(compile_ms, 3),
                "solve_ms": round(res.solve_time * 1e3, 3) if res else None,
                "overlap_ms": round(overlap_ms, 3),
                "schedule": schedule_str,
                "fused_iters": fused_k,
                "warm": n_warm,
                "mesh_devices": (
                    int(mesh.devices.size) if mesh is not None else 1
                ),
                "attempts": len(faults) + (1 if res is not None else 0),
                "queue_depth": depth,
                "occupancy": occupancy,
            }
        )

        if res is None:
            # Batch recovery exhausted: every member goes through the
            # supervisor's ladder individually — retried or failed one by
            # one, never silently dropped.
            for p in live:
                self._solo(p, key, t_dispatch, list(faults), retried=True)
            return

        solve_ms = res.solve_time * 1e3
        hits = packed.warm_hits or []
        for k, p in enumerate(live):
            status = res.status[k]
            # Warm-start outcome per member: offered (cache hit at pack)
            # × accepted (the in-program safeguard's verdict).
            offered = bool(hits[k]) if k < len(hits) else False
            used = (
                bool(res.warm_used[k]) if res.warm_used is not None else False
            )
            warm_label = "warm" if used else ("rejected" if offered else "cold")
            if offered and not used:
                self._m_warm_rejected.inc()
            start = "warm" if used else "cold"
            hist = self._m_iters_by_start.get(start)
            if hist is None:
                hist = self.metrics.histogram(
                    "ipm_iterations", buckets=obs_metrics.ITER_BUCKETS,
                    labels={"start": start},
                    help="IPM iterations per finished solve, by start kind",
                )
                self._m_iters_by_start[start] = hist
            hist.observe(int(res.iterations[k]))
            if status is not Status.OPTIMAL and self.config.solo_recovery:
                member_fault = FaultRecord(
                    FaultKind.NUMERICAL,
                    int(res.iterations[k]),
                    "batched",
                    f"batched member finished {status.value}",
                    action="solo_fallback",
                )
                self._solo(
                    p, key, t_dispatch, faults + [member_fault], retried=True
                )
                continue
            if p.fp and self._warm_cache is not None and res.y is not None:
                # Amortize: this member's full iterate (real slice only —
                # pads are re-synthesized at pack time) seeds the next
                # same-fingerprint request.
                from distributedlpsolver_tpu.ipm.state import IPMState

                self._warm_cache.store(
                    p.fp, m=p.m, n=p.n,
                    state=IPMState(
                        x=res.x[k, : p.n].copy(),
                        y=res.y[k, : p.m].copy(),
                        s=res.s[k, : p.n].copy(),
                        w=res.w[k, : p.n].copy(),
                        z=res.z[k, : p.n].copy(),
                    ),
                    tol=tol,
                )
            x_real = res.x[k, : p.n]
            done = time.perf_counter()
            self._finish(
                p,
                RequestResult(
                    request_id=p.request_id,
                    name=p.name,
                    status=status,
                    # Real-column objective: pad rows pin their pad
                    # columns at cost 1 each, so the padded pobj is
                    # offset — recompute on the request's own c. These
                    # float() reads are the sanctioned demux point:
                    # solve_bucket already synchronized, res is host-side.
                    objective=float(p.c @ x_real),  # graftcheck: disable=host-sync (demux)
                    x=x_real,
                    iterations=int(res.iterations[k]),
                    rel_gap=float(res.rel_gap[k]),  # graftcheck: disable=host-sync (demux)
                    pinf=float(res.pinf[k]),  # graftcheck: disable=host-sync (demux)
                    dinf=float(res.dinf[k]),  # graftcheck: disable=host-sync (demux)
                    bucket=spec.key(),
                    queue_ms=(t_dispatch - p.t_submit) * 1e3,
                    compile_ms=compile_ms,
                    solve_ms=solve_ms,
                    total_ms=(done - p.t_submit) * 1e3,
                    padding_waste=waste,
                    dispatch_index=seq,
                    slot=k,
                    faults=list(faults),
                    t_submit=p.t_submit,
                    t_done=done,
                    m=p.m,
                    n=p.n,
                    pack_ms=packed.pack_ms,
                    overlap_ms=overlap_ms,
                    warm=warm_label,
                    engine=engine,
                ),
            )

    def _solo(
        self,
        p: PendingRequest,
        key: QueueKey,
        t_dispatch: float,
        faults: List[FaultRecord],
        retried: bool,
    ) -> None:
        """Per-request path: general-form requests, and bucket members
        whose batch (or own verdict) failed — through the supervisor's
        recovery ladder so they are retried or failed individually."""
        from distributedlpsolver_tpu.ipm.driver import solve
        from distributedlpsolver_tpu.supervisor import (
            SolveFailure,
            SupervisorConfig,
            supervised_solve,
        )

        problem = p.problem
        if problem is None:
            n = p.A.shape[1]
            problem = LPProblem(
                c=p.c, A=p.A, rlb=p.b, rub=p.b,
                lb=np.zeros(n), ub=np.full(n, _INF), name=p.name,
            )
        cfg = self.solver_config.replace(tol=p.tol)
        # Scenario-tier requests pin the scenario-decomposed engine (the
        # supervisor's ladder degrades it onto sparse-iterative /
        # cpu-sparse on the same lowered form); everything else takes
        # the configured solo backend.
        backend_name = (
            "scenario" if p.engine == "scenario" else self.config.solo_backend
        )
        self._m_solo.inc()
        solo_args = {"retried": retried}
        if p.trace is not None:
            solo_args.update(p.trace.span_args())
        self.tracer.async_begin("solo", p.request_id, args=solo_args)
        t0 = time.perf_counter()
        try:
            # Thread-local trace context around the solve: the IPM
            # driver and iterative backends annotate their spans via
            # obs.context.current() without any backend-protocol change.
            with obs_context.use(p.trace):
                if self.config.solo_recovery:
                    r = supervised_solve(
                        problem,
                        backend=backend_name,
                        config=cfg,
                        supervisor=SupervisorConfig(backoff_base=0.01),
                        warm_cache=self._warm_cache,
                    )
                else:
                    r = solve(
                        problem, backend=backend_name, config=cfg,
                        warm_cache=self._warm_cache,
                    )
            status, faults = r.status, faults + list(r.faults)
        except (KeyboardInterrupt, SystemExit):
            raise
        except SolveFailure as e:
            r, status, faults = None, Status.FAILED, faults + list(e.faults)
        except Exception as e:
            r, status = None, Status.FAILED
            faults = faults + [
                FaultRecord(
                    FaultKind.CRASH, -1, backend_name,
                    f"{type(e).__name__}: {e}", action="give_up",
                )
            ]
        done = time.perf_counter()
        self.tracer.async_end("solo", p.request_id)
        schur_ms = link_ms = 0.0
        if p.engine == "scenario":
            # Per-solve decomposition telemetry: the solo path runs
            # solves sequentially on this thread, so the module's
            # last-solve report is this request's (a degraded solve
            # that never entered the scenario backend reports zeros).
            from distributedlpsolver_tpu.backends.scenario import (
                last_solve_report,
            )

            rep = last_solve_report()
            if rep.get("n_scenarios") == p.n_scenarios:
                schur_ms = float(rep.get("schur_ms", 0.0))
                link_ms = float(rep.get("link_ms", 0.0))
            term_engine = (r.backend if r is not None else backend_name) or "?"
            ctr = self._m_scenario_solves.get(term_engine)
            if ctr is None:
                ctr = self.metrics.counter(
                    "scenario_solves_total",
                    labels={"engine": term_engine},
                    help="scenario-tier solves by terminal engine "
                    "(degradations land on their actual rung)",
                )
                self._m_scenario_solves[term_engine] = ctr
            ctr.inc()
            self._m_scenario_k.observe(p.n_scenarios or 0)
            self._m_scenario_schur_ms.observe(schur_ms)
            self._m_scenario_link_ms.observe(link_ms)
        self._finish(
            p,
            RequestResult(
                request_id=p.request_id,
                name=p.name,
                status=status,
                objective=r.objective if r else float("nan"),
                x=r.x if r else None,
                iterations=r.iterations if r else 0,
                rel_gap=r.rel_gap if r else _INF,
                pinf=r.pinf if r else _INF,
                dinf=r.dinf if r else _INF,
                bucket=None if p.A is None else key[0].key(),
                queue_ms=(t_dispatch - p.t_submit) * 1e3,
                compile_ms=0.0,
                solve_ms=(done - t0) * 1e3,
                total_ms=(done - p.t_submit) * 1e3,
                padding_waste=0.0,
                retried_solo=retried,
                faults=faults,
                t_submit=p.t_submit,
                t_done=done,
                m=p.m,
                n=p.n,
                warm=r.warm if r is not None else "cold",
                engine=p.engine,
                n_scenarios=p.n_scenarios,
                scenario_bucket=p.scenario_bucket,
                schur_ms=schur_ms,
                link_ms=link_ms,
            ),
        )

    def _fail_batch(
        self, key: QueueKey, members: List[PendingRequest], exc: Exception
    ) -> None:
        """Fail every unresolved member of a batch whose dispatch raised
        past the per-attempt fault handling — the dispatcher thread must
        survive, and 'never a silent drop' means the futures resolve."""
        fault = FaultRecord(
            FaultKind.CRASH, -1, "dispatcher",
            f"{type(exc).__name__}: {exc}", action="give_up",
        )
        fault.at_time = time.time()
        self._logger.event(
            {
                "event": "dispatch_error",
                "bucket": list(key[0].key()),
                "detail": fault.detail[:300],
            }
        )
        now = time.perf_counter()
        for p in members:
            if p.future.done():
                continue
            self._finish(
                p,
                RequestResult(
                    request_id=p.request_id,
                    name=p.name,
                    status=Status.FAILED,
                    objective=float("nan"),
                    x=None,
                    iterations=0,
                    rel_gap=_INF,
                    pinf=_INF,
                    dinf=_INF,
                    bucket=key[0].key(),
                    queue_ms=(now - p.t_submit) * 1e3,
                    compile_ms=0.0,
                    solve_ms=0.0,
                    total_ms=(now - p.t_submit) * 1e3,
                    padding_waste=0.0,
                    faults=[fault],
                    t_submit=p.t_submit,
                    t_done=now,
                    m=p.m,
                    n=p.n,
                ),
            )

    def _finish(self, p: PendingRequest, result: RequestResult) -> None:
        # Tenant/priority attribution is stamped here — the one funnel
        # every result path (bucket, solo, timeout, fail) flows through
        # — so the record, the future's result, and the admission
        # accounting can never disagree on whose request this was.
        result = dataclasses.replace(
            result, tenant=p.tenant, priority=p.priority, trace=p.trace
        )
        if self._admission is not None:
            self._admission.on_finished(p.tenant, units=p.units)
        if self._journal is not None and p.jid is not None:
            # Persist the verdict BEFORE resolving the future: a crash
            # after set_result but before the WAL write would replay
            # (and re-solve) a request its caller already saw finish.
            rec = result.record()
            if result.x is not None:
                rec["x"] = [float(v) for v in result.x]
            self._journal.finish(p.jid, rec, status=result.status.value)
            with self._lock:
                self._jobs.pop(p.jid, None)
                if p.jfp is not None:
                    self._replayed_by_fp.pop(p.jfp, None)
        with self._lock:
            # Stats only need the scalar fields; retaining every x would
            # grow a long-running service's memory without bound.
            self._results.append(dataclasses.replace(result, x=None))
        status = result.status.value
        ctr = self._m_requests_by_status.get(status)
        if ctr is None:
            ctr = self.metrics.counter(
                "serve_requests_total", labels={"status": status},
                help="finished requests by terminal status",
            )
            self._m_requests_by_status[status] = ctr
        ctr.inc()
        self._m_queue_ms.observe(result.queue_ms)
        self._m_total_ms.observe(result.total_ms)
        end_args = {"status": status,
                    "total_ms": round(result.total_ms, 3)}
        if p.trace is not None:
            end_args.update(p.trace.span_args())
        self.tracer.async_end("request", p.request_id, args=end_args)
        self._logger.event(result.record())
        # A caller may have cancelled its still-pending future (submit
        # never marks it RUNNING, so Future.cancel succeeds). Claiming it
        # first makes set_result safe; if cancellation won the race the
        # telemetry record above still stands.
        if p.future.set_running_or_notify_cancel():
            p.future.set_result(result)

    # -- elasticity & ladder management ----------------------------------

    def reshard(self, exclude: Sequence = ()) -> int:
        """Elastic recovery: re-form the serving mesh over the surviving
        devices (``parallel.mesh.reform_mesh`` semantics — ``exclude``
        lists lost devices or ids). The survivor count is clamped DOWN to
        the largest count that still divides every bucket's batch, so
        in-flight and future dispatches stay shardable; at 1 the mesh is
        dropped and dispatch continues unsharded. Batches already packed
        on the old mesh finish there. Returns the new device count."""
        if self._slice is not None:
            # A slice's mesh spans PROCESSES: losing part of it kills
            # the world as a unit (distributed/world.py), and recovery
            # is the launcher-level world re-initialization — there is
            # no live re-shard seam inside a dead world.
            raise RuntimeError(
                "reshard() is not available in slice mode — multi-host "
                "device loss is recovered by the world supervisor "
                "(relaunch a smaller world; see README 'Multi-host')"
            )
        with self._lock:
            mesh = self._mesh
        if mesh is None:
            return 1
        from distributedlpsolver_tpu.parallel import mesh as mesh_lib

        new = mesh_lib.reform_mesh(mesh, exclude=exclude, axis_name="batch")
        survivors = list(new.devices.flat)
        with self._lock:
            table = self.scheduler.table
            g = table.batch
            for s in table.specs():
                g = math.gcd(g, s.batch)
            k = max(d for d in range(1, len(survivors) + 1) if g % d == 0)
            if k <= 1:
                self._mesh = None
            elif k == len(survivors):
                self._mesh = new
            else:
                self._mesh = mesh_lib.make_mesh(
                    (k,), axis_names=("batch",), devices=survivors[:k]
                )
            n_dev = max(1, k)
        self.metrics.gauge(
            "serve_mesh_devices", help="devices under the batch axis"
        ).set(n_dev)
        self.tracer.instant(
            "serve.reshard", args={"devices": n_dev}, cat="serve"
        )
        self._logger.event(
            {
                "event": "reshard",
                "devices": n_dev,
                "excluded": [int(getattr(d, "id", d)) for d in exclude],
            }
        )
        return n_dev

    def apply_ladder(
        self,
        buckets: Sequence[BucketSpec],
        warm: bool = True,
        drain_timeout: Optional[float] = None,
        batch: Optional[int] = None,
    ) -> int:
        """Swap the bucket ladder at a safe epoch boundary: drain in-flight
        work → replace the scheduler's BucketTable (pending requests
        migrate and re-bucket) → warm every new bucket program so the
        first post-swap dispatches don't pay compiles (the
        zero-warm-recompile invariant holds across the swap). The ladder
        usually comes from serve/autotune.py. Returns the number of
        bucket programs warmed."""
        self.drain(drain_timeout)
        n_dev = self.mesh_devices
        table = BucketTable(
            list(buckets), batch=batch or self.config.batch, devices=n_dev
        )
        with self._wake:
            pending = self.scheduler.drain_pending()
            self.scheduler = Scheduler(
                table, self.config.max_queue_depth, self.config.flush_s,
                metrics=self.metrics,
            )
            misfits = []
            for p in pending:
                try:
                    self.scheduler.add(p)
                except ValueError as e:  # new ladder can't hold this shape
                    misfits.append((p, e))
            self._wake.notify_all()
        for p, e in misfits:
            self._fail_batch(
                (BucketSpec(p.m, p.n, 1), p.tol, p.engine), [p], e
            )
        self.tracer.instant(
            "serve.ladder_swap",
            args={"buckets": len(table.specs()), "migrated": len(pending),
                  "misfits": len(misfits)},
            cat="serve",
        )
        self._logger.event(
            {
                "event": "ladder_swap",
                "buckets": [list(s.key()) for s in table.specs()],
                "migrated": len(pending),
                "misfits": len(misfits),
            }
        )
        if warm:
            return self.warm_buckets(table.specs())
        return 0

    @staticmethod
    def _cache_dir_snapshot():
        """(dir, entries) of JAX's persistent compilation cache: warm-up
        compiles go through it when configured, and the per-bucket
        warmup line classifies each compile as a cache hit (no new entry
        written) or miss."""
        import os

        import jax

        d = jax.config.jax_compilation_cache_dir
        if not d or not os.path.isdir(d):
            return d, None
        try:
            return d, set(os.listdir(d))
        except OSError:
            return d, None

    def warm_buckets(
        self,
        specs: Sequence[BucketSpec],
        tol: Optional[float] = None,
        engines: Optional[Sequence[str]] = None,
    ) -> int:
        """Pre-compile the bucket programs for ``specs`` at ``tol``
        (default: the service tolerance) on the current mesh, so live
        traffic never pays those compiles. Idempotent per warm key.

        Compiles go through the persistent compilation cache when one is
        configured (JAX_COMPILATION_CACHE_DIR or the package default), and every
        warmed bucket logs a ``cache: hit|miss|off`` line — ``hit`` means
        the executable was served without writing a new cache entry (a
        restart after a ladder swap pays deserialization, not XLA), so
        ladder swaps against a warm cache are cheap to verify from the
        JSONL stream alone."""
        from distributedlpsolver_tpu.backends.batched import (
            bucket_cache_size,
            place_bucket,
            solve_bucket,
        )
        from distributedlpsolver_tpu.backends.first_order import (
            solve_pdhg_bucket,
        )
        from distributedlpsolver_tpu.models.generators import random_batched_lp

        tol = self.solver_config.tol if tol is None else tol
        if engines is None:
            # The PDHG engine only ever serves its tolerance tier —
            # warming it below pdhg_tol would compile programs no
            # request can reach.
            engines = ["ipm"]
            if self.config.pdhg_routing and tol >= self.config.pdhg_tol:
                engines.append("pdhg")
        cfg = self.solver_config.replace(tol=tol)
        with self._lock:
            mesh = self._mesh
        warmed = 0
        for spec in specs:
            for engine in engines:
                wk = (spec.key(), tol, cfg.dtype, self._mesh_key(mesh), engine)
                with self._lock:
                    already = wk in self._warm
                if already:
                    continue
                # A feasible+bounded random batch at the exact bucket
                # shape: max_iter is traced, so this max_iter=1 call
                # compiles the same executable real dispatches reuse.
                dummy = random_batched_lp(spec.batch, spec.m, spec.n, seed=0)
                act_host = np.ones(spec.batch, dtype=bool)
                fn = solve_pdhg_bucket if engine == "pdhg" else solve_bucket
                size0 = bucket_cache_size()
                cache_dir, entries0 = self._cache_dir_snapshot()
                t0 = time.perf_counter()
                try:
                    if self._slice is not None:
                        # Warm every RANK of the slice: the warm-up is a
                        # published dispatch, so followers compile the
                        # same executable before live traffic arrives.
                        self._slice.dispatch(
                            spec, tol, engine, dummy, act_host, max_iter=1
                        )
                    else:
                        placed, act = place_bucket(dummy, act_host, cfg, mesh=mesh)
                        fn(placed, act, cfg, mesh=mesh, max_iter=1)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:  # warm-up failure: traffic pays later
                    self._logger.event(
                        {
                            "event": "warmup_error",
                            "bucket": list(spec.key()),
                            "engine": engine,
                            "detail": f"{type(e).__name__}: {e}"[:300],
                        }
                    )
                    continue
                warmed += 1
                new_programs = bucket_cache_size() - size0
                self._m_compiles.inc(new_programs)
                with self._lock:
                    self._warm.add(wk)
                    self._compiles += new_programs
                if not cache_dir:
                    cache = "off"
                else:
                    _, entries1 = self._cache_dir_snapshot()
                    wrote = (
                        entries0 is not None
                        and entries1 is not None
                        and bool(entries1 - entries0)
                    )
                    cache = "miss" if wrote else "hit"
                self._logger.event(
                    {
                        "event": "warmup",
                        "bucket": list(spec.key()),
                        "tol": tol,
                        "engine": engine,
                        "cache": cache,
                        "compile_ms": round(
                            (time.perf_counter() - t0) * 1e3, 3
                        ),
                    }
                )
        return warmed

    # -- introspection ---------------------------------------------------

    def pipeline_alive(self) -> bool:
        """True iff all three dispatcher pipeline threads are running —
        the HTTP front-end's ``/healthz`` dispatcher-liveness check. A
        service that was cleanly shut down (threads joined and nulled)
        reports False; so does one whose thread died to an uncaught
        error (which _run/_run_solve guard against, but the health
        surface must not take that on faith)."""
        threads = (self._thread, self._pack_thread, self._solve_thread)
        return all(t is not None and t.is_alive() for t in threads)

    def progress(self) -> tuple:
        """(dispatch count, queue depth) — a cheap pulse for the HTTP
        front-end's wedge detector: depth > 0 with the dispatch count
        frozen across a window means the pipeline stopped consuming."""
        with self._lock:
            return self._dispatch_seq, self.scheduler.depth()

    def dispatch_report(self) -> List[dict]:
        """Per-dispatch timing rows (pack/compile/solve/overlap ms, mesh
        width), bounded to the most recent 2048 dispatches."""
        with self._lock:
            return list(self._dispatch_rows)

    def _brownout_stats(self) -> Optional[dict]:
        """Brownout state for stats()/statusz — observing on the way
        so status polls drive stage release when traffic is idle."""
        if self._brownout is None:
            return None
        with self._lock:
            depth = self.scheduler.depth()
        for ev in self._brownout.observe(depth):
            self._logger.event(ev)
        return self._brownout.stats()

    def stats(self) -> dict:
        import jax

        platform = jax.default_backend()
        with self._lock:
            results = list(self._results)
            depth = self.scheduler.depth()
            occupancy = self.scheduler.occupancy()
            dispatches = self._dispatch_seq
            compiles = self._compiles
            overlap_total = self._overlap_ms_total
            pack_total = self._pack_ms_total
            phase_iters = dict(self._phase_iters)
            engine_dispatches = dict(self._engine_dispatches)
            buckets = [list(s.key()) for s in self.scheduler.table.specs()]
            idle = {
                "waits": self._idle_waits,
                "sleep_s": round(self._idle_sleep_s, 3),
                "last_timeout_ms": (
                    None
                    if self._last_idle_timeout is None
                    else round(self._last_idle_timeout * 1e3, 3)
                ),
            }
        # Scenario-tier aggregate: per-K-bucket latency percentiles —
        # the table `cli report` reconciles against (same source
        # records, same percentile implementation).
        from distributedlpsolver_tpu.obs.stats import percentile as _pct

        scen_rs = [r for r in results if r.n_scenarios]
        by_bucket: dict = {}
        for r in scen_rs:
            by_bucket.setdefault(r.scenario_bucket or 0, []).append(r)
        scenario = {
            "solves": len(scen_rs),
            "by_bucket": {
                str(b): {
                    "count": len(rs),
                    "k_max": max(r.n_scenarios for r in rs),
                    "total_ms_p50": round(
                        _pct([r.total_ms for r in rs], 50), 3
                    ),
                    "total_ms_p99": round(
                        _pct([r.total_ms for r in rs], 99), 3
                    ),
                    "schur_ms_p50": round(
                        _pct([r.schur_ms for r in rs], 50), 3
                    ),
                    "link_ms_p50": round(
                        _pct([r.link_ms for r in rs], 50), 3
                    ),
                }
                for b, rs in sorted(by_bucket.items())
            },
        }
        return {
            **latency_summary(results),
            "queue_depth": depth,
            "occupancy": occupancy,
            "dispatches": dispatches,
            "programs_compiled": compiles,
            "warm_cache": (
                self._warm_cache.stats()
                if self._warm_cache is not None
                else None
            ),
            "mesh_devices": self.mesh_devices,
            "pack_ms_total": round(pack_total, 3),
            "overlap_ms_total": round(overlap_total, 3),
            "schedule": self.solver_config.bucket_schedule_resolved(platform),
            "fused_iters": self.solver_config.fused_iters_resolved(platform),
            "phase_iters": phase_iters,
            "engine_dispatches": engine_dispatches,
            "scenario": scenario,
            "idle": idle,
            "buckets": buckets,
            # Per-tenant admission accounting (None without the SLO
            # layer): admitted/rejected-by-reason/in-system/tokens —
            # the summary event's overload post-mortem surface, and the
            # /statusz field the router's load tie-break reads past.
            "admission": (
                self._admission.stats()
                if self._admission is not None
                else None
            ),
            # Brownout ladder state (None without one). Reading stats
            # also OBSERVES the current depth: /statusz polls keep the
            # release clock ticking even when submits stop entirely —
            # a brownout must not outlive the overload that caused it
            # just because traffic went to zero.
            "brownout": self._brownout_stats(),
            # Crash-safe fabric: drain state + durable-journal counters
            # (None without a journal) — the /readyz and recovery
            # post-mortem surface.
            "draining": self.draining,
            "journal": (
                self._journal.stats() if self._journal is not None else None
            ),
        }
