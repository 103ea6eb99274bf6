"""Command-line driver: ``solve <file.mps> --backend=<name>``.

The reference's top layer is a CLI that parses flags (including backend
selection via ``--backend=``, BASELINE.json:5), loads the problem, runs
the solver, and reports iterations/gap/wall-clock (the published metric
surface, BASELINE.json:2). Subcommands:

    solve       solve an MPS file (or a generated problem) to tolerance
    serve       async batching solve service (JSONL/MPS requests in)
    serve-http  HTTP front-end over the solve service (POST /v1/solve,
                /metrics, /healthz, /statusz; README "Network serving")
    route       router tier over serve-http backends (shape/load-aware
                routing, health-checked failover)
    autotune    refine a serve bucket ladder from telemetry JSONL
    obs-agg     fleet telemetry aggregator: cross-process trace merge +
                hedge-ledger/record/journal reconciliation
    check       graftcheck static-analysis suite (the tier-1 CI gate)
    backends    list registered SolverBackend names
    generate    write a generated benchmark problem to MPS

Run as ``python -m distributedlpsolver_tpu.cli ...``.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional


def _add_solver_flags(ap: argparse.ArgumentParser) -> None:
    ap.add_argument(
        "--backend",
        default="auto",
        help="SolverBackend name (auto = pick by problem size/structure)",
    )
    ap.add_argument("--tol", type=float, default=1e-8, help="relative gap/infeasibility tolerance")
    ap.add_argument("--max-iter", type=int, default=200)
    ap.add_argument("--quiet", action="store_true", help="suppress per-iteration log")
    ap.add_argument("--log-jsonl", default=None, help="write per-iteration JSONL here")
    ap.add_argument("--checkpoint", default=None, help="iterate checkpoint path")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--profile-dir", default=None, help="jax.profiler trace directory")
    ap.add_argument(
        "--factor-dtype",
        default="auto",
        help="Cholesky dtype: auto = f32→f64 two-phase on TPU; or float32/float64",
    )
    ap.add_argument(
        "--no-presolve",
        action="store_true",
        help="disable structural presolve (singleton/redundant rows, fixed cols)",
    )
    ap.add_argument(
        "--no-scale", action="store_true", help="disable Ruiz equilibration"
    )
    ap.add_argument("--json", action="store_true", help="print result as one JSON object")
    ap.add_argument("--x-out", default=None, help="write solution vector as .npy")
    ap.add_argument(
        "--log-fsync",
        action="store_true",
        help="fsync the JSONL log after each record (crash-proof telemetry)",
    )
    ap.add_argument(
        "--supervise",
        action="store_true",
        help="run under the solve supervisor (watchdog + rollback + "
        "backend degradation; see README 'Fault tolerance')",
    )
    ap.add_argument(
        "--step-timeout",
        type=float,
        default=0.0,
        help="watchdog deadline per device step in seconds (0 = no "
        "watchdog; implies --supervise when set)",
    )
    ap.add_argument(
        "--max-retries",
        type=int,
        default=6,
        help="supervisor recovery attempts before a structured failure",
    )
    ap.add_argument(
        "--adaptive-timeout",
        action="store_true",
        help="size the watchdog deadline adaptively (10x the trailing "
        "median step time, clamped, with compile-grace) instead of the "
        "static --step-timeout; implies --supervise",
    )
    ap.add_argument(
        "--min-devices",
        type=int,
        default=1,
        help="smallest mesh the elastic SHRINK recovery may re-form "
        "after device loss before degrading to the next backend",
    )
    ap.add_argument(
        "--metrics-path",
        default=None,
        help="enable the obs/ metrics registry and write a "
        "Prometheus-text snapshot here at exit (README 'Observability')",
    )
    ap.add_argument(
        "--trace-path",
        default=None,
        help="enable the obs/ span tracer and write a Chrome-trace JSON "
        "here at exit (open at ui.perfetto.dev)",
    )


def _log_jax_cache() -> None:
    """Log the startup hit/miss line for the persistent compilation cache
    JAX was given (JAX_COMPILATION_CACHE_DIR, else the package's fixed
    in-checkout default — see the package ``__init__``)."""
    import os

    import jax

    d = jax.config.jax_compilation_cache_dir
    if not d:
        print("jax compilation cache: off", file=sys.stderr)
        return
    n = (
        sum(1 for f in os.listdir(d) if not f.startswith("."))
        if os.path.isdir(d) else 0
    )
    print(
        f"jax compilation cache: {d} — {n} cached entries "
        f"({'warm start, cold compiles may be cache hits' if n else 'cold start, compiles will be cached'})",
        file=sys.stderr,
    )


def _obs_setup(args):
    """Install a process-wide metrics registry / span tracer when
    --metrics-path / --trace-path are given (every layer — driver,
    supervisor, serve, batched backend — resolves the module defaults,
    so one switch instruments the whole process). Returns a finalizer
    that writes both artifacts and restores the no-op defaults."""
    from distributedlpsolver_tpu.obs import metrics as obs_metrics
    from distributedlpsolver_tpu.obs import trace as obs_trace

    reg = tracer = None
    if getattr(args, "metrics_path", None):
        reg = obs_metrics.MetricsRegistry()
        obs_metrics.set_registry(reg)
    if getattr(args, "trace_path", None):
        tracer = obs_trace.Tracer(args.trace_path)
        obs_trace.set_tracer(tracer)

    def finalize():
        if reg is not None:
            reg.write_prometheus(args.metrics_path)
            obs_metrics.set_registry(None)
            print(f"metrics snapshot -> {args.metrics_path}", file=sys.stderr)
        if tracer is not None:
            tracer.close()
            obs_trace.set_tracer(None)
            print(
                f"trace ({tracer.event_count()} events) -> "
                f"{args.trace_path} (open at ui.perfetto.dev)",
                file=sys.stderr,
            )

    return finalize


def _follower_obs_setup(world, metrics: bool, trace: bool):
    """Observability for a nonzero slice rank: install a process-wide
    registry/tracer and export into the world heartbeat dir under
    per-rank names (``rank<k>.metrics.json`` refreshed on the heartbeat
    cadence — the JSON snapshot form the fleet aggregator scans, rank
    and identity stamped alongside; ``rank<k>.trace.json`` at exit).
    Returns a finalizer; no-op when neither flag is set or the world
    has no heartbeat dir."""
    import os
    import threading

    hb_dir = world.cfg.heartbeat_dir
    if hb_dir is None or not (metrics or trace):
        return lambda: None

    from distributedlpsolver_tpu.obs import metrics as obs_metrics
    from distributedlpsolver_tpu.obs import trace as obs_trace

    os.makedirs(hb_dir, exist_ok=True)
    reg = tracer = None
    stop = threading.Event()
    snap_path = os.path.join(hb_dir, f"rank{world.rank}.metrics.json")

    def write_snapshot():
        doc = {
            "rank": world.rank,
            "pid": os.getpid(),
            "generation": world.cfg.generation,
            "slice_id": world.cfg.slice_id,
            "metrics": reg.snapshot(),
        }
        tmp = f"{snap_path}.{os.getpid()}.tmp"
        with open(tmp, "w") as fh:
            json.dump(doc, fh)
        os.replace(tmp, snap_path)

    if metrics:
        reg = obs_metrics.MetricsRegistry()
        obs_metrics.set_registry(reg)
        period = max(float(world.cfg.heartbeat_period_s), 0.25)

        def snap_loop():
            while not stop.wait(period):
                try:
                    write_snapshot()
                except OSError:
                    pass  # snapshot export must never kill the rank

        threading.Thread(
            target=snap_loop, daemon=True, name="dlps-rank-metrics"
        ).start()
    if trace:
        tracer = obs_trace.Tracer(
            os.path.join(hb_dir, f"rank{world.rank}.trace.json"),
            process_name=f"dlps-rank{world.rank}",
        )
        obs_trace.set_tracer(tracer)

    def finalize():
        stop.set()
        if reg is not None:
            try:
                write_snapshot()
            except OSError:
                pass
            obs_metrics.set_registry(None)
        if tracer is not None:
            tracer.close()
            obs_trace.set_tracer(None)

    return finalize


def _config_from(args) -> "SolverConfig":
    from distributedlpsolver_tpu.ipm.config import SolverConfig

    return SolverConfig(
        tol=args.tol,
        max_iter=args.max_iter,
        verbose=not args.quiet,
        log_jsonl=args.log_jsonl,
        checkpoint_path=args.checkpoint,
        checkpoint_every=args.checkpoint_every,
        profile_dir=args.profile_dir,
        factor_dtype=args.factor_dtype,
        presolve=not args.no_presolve,
        scale=not args.no_scale,
        log_fsync=args.log_fsync,
    )


def _report(result, as_json: bool, x_out: Optional[str]) -> int:
    if x_out and result.x is not None:
        import numpy as np

        np.save(x_out, result.x)
    if as_json:
        print(
            json.dumps(
                {
                    "name": result.name,
                    "status": result.status.value,
                    "objective": result.objective,
                    "iterations": result.iterations,
                    "rel_gap": result.rel_gap,
                    "pinf": result.pinf,
                    "dinf": result.dinf,
                    "solve_time_s": result.solve_time,
                    "setup_time_s": result.setup_time,
                    "iters_per_sec": result.iters_per_sec,
                    "backend": result.backend,
                    "faults": [f.asdict() for f in result.faults],
                }
            )
        )
    else:
        print(result.summary())
    from distributedlpsolver_tpu.ipm.state import Status

    return 0 if result.status == Status.OPTIMAL else 2


def cmd_solve(args) -> int:
    from distributedlpsolver_tpu.io.mps import read_mps

    _log_jax_cache()
    finalize_obs = _obs_setup(args)
    try:
        return _cmd_solve_inner(args, read_mps(args.file))
    finally:
        finalize_obs()


def _cmd_solve_inner(args, problem) -> int:
    cfg = _config_from(args)
    if args.supervise or args.step_timeout > 0 or args.adaptive_timeout:
        from distributedlpsolver_tpu.supervisor import (
            SolveFailure,
            SupervisorConfig,
            supervised_solve,
        )

        sup = SupervisorConfig(
            step_timeout=args.step_timeout or None,
            adaptive_timeout=args.adaptive_timeout,
            max_retries=args.max_retries,
            min_devices=args.min_devices,
        )
        try:
            result = supervised_solve(
                problem, backend=args.backend, config=cfg, supervisor=sup
            )
        except SolveFailure as e:
            payload = {
                "name": problem.name,
                "status": e.status.value,
                "error": str(e),
                "faults": [f.asdict() for f in e.faults],
            }
            if args.json:
                print(json.dumps(payload))
            else:
                print(f"{problem.name}: FAILED — {e}", file=sys.stderr)
            return 3
    else:
        from distributedlpsolver_tpu.ipm import solve

        result = solve(problem, backend=args.backend, config=cfg)
    return _report(result, args.json, args.x_out)


def _iter_request_specs(args):
    """Yield request-spec dicts from --requests (JSONL file or '-' =
    stdin) or --dir (sorted *.mps files, each one request, plus *.jsonl
    files of specs). A spec is ``{"mps": path}`` or
    ``{"m": .., "n": .., "seed": ..}`` (generated standard-form), plus
    optional ``"id"``, ``"tol"``, ``"deadline_s"``."""
    import os

    if args.dir:
        for fname in sorted(os.listdir(args.dir)):
            path = os.path.join(args.dir, fname)
            if fname.endswith(".mps") or fname.endswith(".mps.gz"):
                yield {"mps": path, "id": fname}
            elif fname.endswith(".jsonl"):
                with open(path) as fh:
                    for line in fh:
                        if line.strip():
                            yield json.loads(line)
        return
    fh = sys.stdin if args.requests == "-" else open(args.requests)
    try:
        for line in fh:
            if line.strip():
                yield json.loads(line)
    finally:
        if fh is not sys.stdin:
            fh.close()


def _admission_from(args):
    """AdmissionConfig from ``--quotas`` (inline JSON or ``@file``):
    ``{"tenants": {"acme": {"rate": 10, "burst": 20, "weight": 2}},
    "default": {...}, "fair_start": 0.5}``. None when the flag is
    absent — the classic depth-only admission."""
    spec = getattr(args, "quotas", None)
    if not spec:
        return None
    from distributedlpsolver_tpu.net.admission import (
        AdmissionConfig,
        TenantQuota,
    )

    if spec.startswith("@"):
        with open(spec[1:]) as fh:
            spec = fh.read()
    cfg = json.loads(spec)

    def _quota(d: dict) -> TenantQuota:
        return TenantQuota(
            rate=float(d.get("rate", float("inf"))),
            burst=float(d.get("burst", float("inf"))),
            weight=float(d.get("weight", 1.0)),
        )

    kwargs = {
        "quotas": {
            t: _quota(q) for t, q in (cfg.get("tenants") or {}).items()
        },
    }
    if "default" in cfg:
        kwargs["default_quota"] = _quota(cfg["default"])
    if "fair_start" in cfg:
        kwargs["fair_start"] = float(cfg["fair_start"])
    if "priority_flush_scale" in cfg:
        kwargs["priority_flush_scale"] = {
            k: float(v) for k, v in cfg["priority_flush_scale"].items()
        }
    return AdmissionConfig(**kwargs)


def _brownout_from(args):
    """BrownoutConfig from ``--brownout`` (``on`` for defaults, or
    inline JSON overriding any BrownoutConfig field, e.g.
    ``{"depth_high": 0.6, "engage_after_s": 0.5}``). None when the
    flag is absent — no brownout ladder."""
    spec = getattr(args, "brownout", None)
    if not spec:
        return None
    from distributedlpsolver_tpu.net.admission import BrownoutConfig

    if spec.strip().lower() == "on":
        return BrownoutConfig()
    return BrownoutConfig(**json.loads(spec))


def _service_config_from(args) -> "ServiceConfig":
    """The ServiceConfig both ``serve`` and ``serve-http`` build from
    the shared serving flags."""
    from distributedlpsolver_tpu.serve import ServiceConfig, ladder_from_json

    buckets = None
    if args.buckets:
        with open(args.buckets) as fh:
            buckets = ladder_from_json(fh.read())
    return ServiceConfig(
        buckets=buckets,
        batch=args.batch,
        flush_s=args.flush_ms / 1e3,
        max_queue_depth=args.queue_depth,
        default_deadline_s=args.deadline_s or None,
        log_jsonl=args.log_jsonl,
        mesh_devices=args.mesh_devices,
        warm_start=not args.no_warm_start,
        warm_cache_entries=args.warm_cache_entries,
        solo_backend=getattr(args, "solo_backend", "auto"),
        admission=_admission_from(args),
        journal_dir=getattr(args, "journal_dir", None),
        journal_fsync=getattr(args, "journal_fsync", "flush"),
        brownout=_brownout_from(args),
    )


def cmd_serve(args) -> int:
    """Serve loop: read LP requests, multiplex them through the async
    batching SolveService, write one JSONL result record per request."""
    import time

    from distributedlpsolver_tpu.io.mps import read_mps
    from distributedlpsolver_tpu.models.generators import random_dense_lp
    from distributedlpsolver_tpu.serve import (
        ServiceOverloaded,
        SolveService,
    )

    _log_jax_cache()
    finalize_obs = _obs_setup(args)
    svc_cfg = _service_config_from(args)
    out = sys.stdout if args.out == "-" else open(args.out, "w")
    n_failed = 0
    backoffs = 0
    backoff_s = 0.0
    try:
        with SolveService(svc_cfg, solver_config=_config_from(args).replace(
            verbose=False
        )) as svc:
            submitted = []
            for spec in _iter_request_specs(args):
                if "mps" in spec:
                    problem = read_mps(spec["mps"])
                else:
                    problem = random_dense_lp(
                        int(spec["m"]), int(spec["n"]),
                        seed=int(spec.get("seed", 0)),
                    )
                while True:
                    try:
                        fut = svc.submit(
                            problem,
                            deadline=spec.get("deadline_s"),
                            tol=spec.get("tol"),
                            name=str(spec.get("id", problem.name)),
                            tenant=str(spec.get("tenant", "default")),
                            priority=str(spec.get("priority", "normal")),
                        )
                        break
                    except ServiceOverloaded as e:
                        # Backpressure: the reader outran the solver.
                        # The admission verdict says exactly how long a
                        # retry is pointless for THIS tenant (token
                        # refill / drain window) — sleep that, not a
                        # blind flush tick.
                        # Clamped on both sides: the verdict is already
                        # finite, but a sleep(inf) here would be fatal.
                        wait = min(max(e.retry_after_s, 1e-3), 60.0)
                        backoffs += 1
                        backoff_s += wait
                        time.sleep(wait)
                submitted.append(fut)
            svc.drain()
            from distributedlpsolver_tpu.utils.logging import stamp_record

            for fut in submitted:
                r = fut.result()
                n_failed += r.status.value == "failed"
                # The CLI's result stream rides the same record schema
                # as every IterLogger stream (cli report merges both).
                out.write(json.dumps(stamp_record(r.record())) + "\n")
            out.flush()
            # The summary surfaces rejects: the service stats carry the
            # per-tenant admission table (admitted / rejected-by-reason),
            # and the client-side backoff loop reports how often (and
            # how long) submission was shed back onto it.
            summary = {
                **svc.stats(),
                "submit_backoffs": backoffs,
                "submit_backoff_s": round(backoff_s, 3),
            }
            print(json.dumps(summary), file=sys.stderr)
    finally:
        if out is not sys.stdout:
            out.close()
        finalize_obs()
    return 2 if n_failed else 0


def cmd_serve_http(args) -> int:
    """HTTP front-end: bind a SolveHTTPServer over one SolveService and
    serve until interrupted (README "Network serving")."""
    from distributedlpsolver_tpu.net import NetConfig, SolveHTTPServer
    from distributedlpsolver_tpu.obs import metrics as obs_metrics
    from distributedlpsolver_tpu.serve import SolveService

    _log_jax_cache()
    finalize_obs = _obs_setup(args)
    svc_cfg = _service_config_from(args)
    net_cfg = NetConfig(
        host=args.host,
        port=args.port,
        max_wait_s=args.max_wait_s,
        wedge_s=args.wedge_s,
        log_jsonl=args.net_log_jsonl,
        deadline_propagation=getattr(args, "deadline_propagation", True),
    )
    # A serving process ADVERTISES /metrics, so it always gets a live
    # registry — the zero-cost NULL default is for the in-process
    # library path, not a front-end whose scrape surface would
    # otherwise be permanently empty. --metrics-path (via _obs_setup)
    # installed a process-wide registry already; reuse it so the
    # shutdown snapshot and the scrape agree.
    reg = obs_metrics.get_registry()
    if not reg.enabled:
        reg = obs_metrics.MetricsRegistry()
    try:
        svc = SolveService(
            svc_cfg,
            solver_config=_config_from(args).replace(verbose=False),
            metrics=reg,
            # Warm-up (below) runs BEFORE the pipeline threads start so
            # even journal-replayed work recovered at construction
            # dispatches against compiled programs.
            auto_start=not args.warm_buckets,
        )
        if args.warm_buckets:
            n = svc.warm_buckets(svc.scheduler.table.specs())
            print(f"warmed {n} bucket programs", file=sys.stderr)
        with svc:
            server = SolveHTTPServer(svc, net_cfg).start()
            import threading

            stopped = threading.Event()
            # The /quitquitquit drain path closes the listener, then
            # this callback lets the process exit cleanly.
            server.on_drained = lambda drained: stopped.set()
            # Self-registration + heartbeats into the shared registry —
            # strictly AFTER warm-up and the listener bind, so an
            # elastic rollout never exposes a backend whose bucket
            # ladder isn't compiled yet (the zero-warm-recompile
            # rollout contract; same beat loop as cli serve-slice).
            hb_stop = threading.Event()
            if getattr(args, "registry", None):
                from distributedlpsolver_tpu.net.registry import (
                    BackendRegistry,
                )

                breg = BackendRegistry(
                    args.registry, logger=svc._logger, metrics=reg
                )
                breg.register(server.url)

                def _beat():
                    while not hb_stop.wait(args.heartbeat_s):
                        breg.heartbeat(server.url)

                threading.Thread(
                    target=_beat, daemon=True, name="dlps-http-hb"
                ).start()
            print(
                f"serving on {server.url} "
                f"(POST /v1/solve; GET /metrics /healthz /readyz "
                f"/statusz; POST /quitquitquit drains)",
                file=sys.stderr,
            )
            try:
                stopped.wait()  # serve until SIGINT or drained
                print("drained; exiting", file=sys.stderr)
            except KeyboardInterrupt:
                print("shutting down", file=sys.stderr)
            finally:
                hb_stop.set()
                server.shutdown()
    finally:
        finalize_obs()
    return 0


def cmd_serve_slice(args) -> int:
    """One-service-per-slice multi-host serving (README "Multi-host").

    Without ``--rank``: SUPERVISOR mode — spawn ``--world-size`` rank
    processes of this same command (the single-machine harness of a TPU
    pod slice), watch them, and on world death relaunch a smaller world
    on the same port + journal (coordinator-level recovery; emits
    ``world_reinit`` events with ``recovery_overhead_s``).

    With ``--rank`` (spawned by the supervisor; env contract set by the
    launcher): rank 0 runs the HTTP front-end whose SolveService
    dispatches onto the slice's GLOBAL mesh and self-registers into the
    shared backend registry (``--registry``) with heartbeats; nonzero
    ranks run the follower loop off the slice dispatch journal.
    """
    import os
    import threading
    import time

    if args.rank is None:
        # ---------------- supervisor mode ----------------------------
        import sys as _sys

        from distributedlpsolver_tpu.distributed.launcher import (
            SupervisorConfig,
            WorldSupervisor,
        )

        workdir = args.slice_workdir or os.path.join(
            args.journal_dir or ".", f"slice-{args.slice_id}-world"
        )
        base_argv = [a for a in _sys.argv[1:]]

        def argv_for_gen(generation, world_size, port):
            def argv_for(rank):
                return (
                    [_sys.executable, "-m", "distributedlpsolver_tpu.cli"]
                    + base_argv
                    + ["--rank", str(rank)]
                )

            return argv_for

        sup = WorldSupervisor(
            argv_for_gen,
            world_size=args.world_size,
            workdir=workdir,
            local_devices=args.local_devices,
            config=SupervisorConfig(
                min_world=1,
                max_reforms=args.max_reforms,
                # Own stream, never the ranks' net log: a relaunched
                # rank re-opens (truncates) its log path, which would
                # eat the very world_reinit record describing it.
                log_jsonl=os.path.join(workdir, "world.jsonl"),
            ),
            slice_id=args.slice_id,
        )
        try:
            sup.run(timeout=args.supervise_timeout_s)
        except KeyboardInterrupt:
            if sup.handle is not None:
                sup.handle.kill_all()
            print("slice supervisor: interrupted", file=sys.stderr)
        return 0

    # -------------------- rank mode ----------------------------------
    from distributedlpsolver_tpu.distributed.slice import (
        FileControlPlane,
        SliceRunner,
        canonical_bucket_config,
        follower_loop,
    )
    from distributedlpsolver_tpu.distributed.world import (
        WorldConfig,
        init_world,
    )

    cfg = WorldConfig.from_env()
    world = init_world(cfg)
    world.start_heartbeat()
    ctrl_dir = os.path.join(
        args.control_dir
        or os.path.join(os.environ.get("DLPS_HEARTBEAT_DIR", "."), ".."),
        f"ctrl-gen{cfg.generation}",
    )
    solver_cfg = canonical_bucket_config(_config_from(args))
    try:
        if world.rank != 0:
            # Follower observability (README "Distributed tracing"):
            # every rank spawns from the SAME argv, so --metrics-path /
            # --trace-path name rank-0's artifacts; followers derive
            # per-rank paths in the world heartbeat dir instead —
            # rank<k>.metrics.json snapshots (JSON form, exemplars
            # included — what `cli obs-agg` scans) refreshed on the
            # heartbeat cadence, rank<k>.trace.json at exit.
            finalize_follower = _follower_obs_setup(
                world,
                metrics=bool(getattr(args, "metrics_path", None)),
                trace=bool(getattr(args, "trace_path", None)),
            )
            try:
                n = follower_loop(
                    world, FileControlPlane(ctrl_dir), solver_cfg
                )
            finally:
                finalize_follower()
            print(
                f"slice follower rank {world.rank}: executed {n} "
                f"dispatches; exiting",
                file=sys.stderr,
            )
            return 0

        # ---- rank 0: front-end + scheduler + demux -------------------
        from distributedlpsolver_tpu.net import NetConfig, SolveHTTPServer
        from distributedlpsolver_tpu.obs import metrics as obs_metrics
        from distributedlpsolver_tpu.serve import SolveService

        _log_jax_cache()
        finalize_obs = _obs_setup(args)
        runner = SliceRunner(world, FileControlPlane(ctrl_dir), solver_cfg)
        svc_cfg = _service_config_from(args)
        net_cfg = NetConfig(
            host=args.host,
            port=args.port,
            max_wait_s=args.max_wait_s,
            wedge_s=args.wedge_s,
            log_jsonl=args.net_log_jsonl,
            deadline_propagation=getattr(
                args, "deadline_propagation", True
            ),
        )
        reg = obs_metrics.get_registry()
        if not reg.enabled:
            reg = obs_metrics.MetricsRegistry()
        try:
            svc = SolveService(
                svc_cfg,
                solver_config=solver_cfg,
                metrics=reg,
                auto_start=not args.warm_buckets,
                slice_runner=runner,
            )
            if args.warm_buckets:
                n = svc.warm_buckets(svc.scheduler.table.specs())
                print(
                    f"warmed {n} bucket programs across "
                    f"{world.world_size} ranks",
                    file=sys.stderr,
                )
            with svc:
                server = SolveHTTPServer(svc, net_cfg).start()
                stopped = threading.Event()
                server.on_drained = lambda drained: stopped.set()

                # Self-registration + heartbeats into the shared
                # registry: routers adopt the slice with no manual
                # config and TTL-eject it when the beats stop.
                hb_stop = threading.Event()
                if args.registry:
                    from distributedlpsolver_tpu.net.registry import (
                        BackendRegistry,
                    )

                    breg = BackendRegistry(
                        args.registry,
                        logger=svc._logger,
                        metrics=reg,
                    )
                    breg.register(
                        server.url,
                        slice_id=args.slice_id,
                        world_size=world.world_size,
                    )

                    def _beat():
                        n_beats = 0
                        while not hb_stop.wait(args.heartbeat_s):
                            breg.heartbeat(server.url)
                            n_beats += 1
                            if n_beats % 60 == 0:
                                # Sparse liveness trace: one heartbeat
                                # event a minute-ish, not one per beat.
                                svc._logger.event(
                                    {
                                        "event": "heartbeat",
                                        "rank": 0,
                                        "slice_id": args.slice_id,
                                        "backend": server.url,
                                    }
                                )

                    threading.Thread(
                        target=_beat, daemon=True, name="dlps-slice-hb"
                    ).start()
                print(
                    f"slice {args.slice_id} gen {cfg.generation} serving "
                    f"on {server.url} (world {world.world_size}, "
                    f"{world.describe()['global_devices']} global devices)",
                    file=sys.stderr,
                )
                try:
                    stopped.wait()
                    print("slice drained; exiting", file=sys.stderr)
                except KeyboardInterrupt:
                    print("slice shutting down", file=sys.stderr)
                finally:
                    hb_stop.set()
                    server.shutdown()
                    runner.stop()  # followers exit their loop cleanly
        finally:
            finalize_obs()
        return 0
    finally:
        world.close()


def cmd_route(args) -> int:
    """Router tier: health-checked, shape/load-aware routing over
    serve-http backends (README "Network serving")."""
    from distributedlpsolver_tpu.net.router import (
        Router,
        RouterConfig,
        RouterHTTPServer,
    )
    from distributedlpsolver_tpu.obs import metrics as obs_metrics

    finalize_obs = _obs_setup(args)
    # Same as serve-http: a router process advertises /metrics, so it
    # always runs with a live registry.
    reg = obs_metrics.get_registry()
    if not reg.enabled:
        reg = obs_metrics.MetricsRegistry()
    if not args.backend and not args.registry:
        print(
            "route: need --backend URLs or a --registry slices register "
            "into",
            file=sys.stderr,
        )
        return 2
    router = Router(
        args.backend or [],
        RouterConfig(
            poll_s=args.poll_s,
            eject_after=args.eject_after,
            log_jsonl=args.log_jsonl,
            registry_path=args.registry,
            probe_backoff_cap_s=args.probe_backoff_cap_s,
            registry_ttl_s=args.registry_ttl_s,
            hedge_enabled=args.hedge,
            hedge_rate_cap=args.hedge_rate_cap,
            retry_budget_rate=args.retry_budget,
            retry_budget_burst=args.retry_budget_burst,
            deadline_propagation=args.deadline_propagation,
        ),
        metrics=reg,
    )
    try:
        router.start()
        server = RouterHTTPServer(router, host=args.host, port=args.port)
        server.start()
        print(
            f"routing on {server.url} over {len(args.backend or [])} "
            f"configured backends ({router.healthy_count()} healthy)",
            file=sys.stderr,
        )
        try:
            import threading

            threading.Event().wait()
        except KeyboardInterrupt:
            print("shutting down", file=sys.stderr)
        finally:
            server.shutdown()
    finally:
        router.shutdown()
        finalize_obs()
    return 0


def cmd_elastic(args) -> int:
    """Closed-loop elasticity controller: telemetry-driven backend pool
    autoscaling over the shared registry (README "Elasticity & overload
    protection")."""
    from distributedlpsolver_tpu.obs import metrics as obs_metrics
    from distributedlpsolver_tpu.serve.elastic import (
        ElasticConfig,
        ElasticController,
    )

    finalize_obs = _obs_setup(args)
    reg = obs_metrics.get_registry()
    if not reg.enabled:
        reg = obs_metrics.MetricsRegistry()
    backend_flags = []
    for item in args.backend_flag or []:
        backend_flags.extend(item.split())
    ctl = ElasticController(
        ElasticConfig(
            registry_path=args.registry,
            min_backends=args.min_backends,
            max_backends=args.max_backends,
            poll_s=args.poll_s,
            load_high=args.load_high,
            load_low=args.load_low,
            reject_rate_high=args.reject_rate_high,
            out_sustain_s=args.out_sustain_s,
            in_sustain_s=args.in_sustain_s,
            cooldown_s=args.cooldown_s,
            host=args.host,
            workdir=args.workdir,
            buckets_json=args.buckets,
            backend_flags=tuple(backend_flags),
            heartbeat_s=args.heartbeat_s,
            log_jsonl=args.log_jsonl,
        ),
        metrics=reg,
    )
    try:
        ctl.start()
        print(
            f"elastic controller over {args.registry}: pool "
            f"{args.min_backends}..{args.max_backends}, "
            f"{ctl.pool_size()} up",
            file=sys.stderr,
        )
        try:
            import threading

            threading.Event().wait()
        except KeyboardInterrupt:
            print("draining managed pool", file=sys.stderr)
    finally:
        ctl.shutdown(drain=True)
        finalize_obs()
    return 0


def cmd_autotune(args) -> int:
    """Refine a serve bucket ladder from a telemetry JSONL file and write
    it as a ladder JSON ``cli serve --buckets`` consumes."""
    from distributedlpsolver_tpu.serve import (
        AutotuneConfig,
        autotune_from_jsonl,
        ladder_from_json,
        ladder_to_json,
    )

    current = None
    if args.current:
        with open(args.current) as fh:
            current = ladder_from_json(fh.read())
    specs, report = autotune_from_jsonl(
        args.telemetry,
        current=current,
        config=AutotuneConfig(
            waste_threshold=args.waste_threshold,
            max_programs=args.max_programs,
            batch=args.batch or None,
            devices=args.devices,
        ),
    )
    if not specs:
        print("no bucketed request telemetry found; nothing to tune",
              file=sys.stderr)
        return 2
    with open(args.out, "w") as fh:
        fh.write(ladder_to_json(specs) + "\n")
    print(json.dumps(report))
    return 0


def cmd_report(args) -> int:
    """Merge telemetry JSONL streams (iteration rows, serve records,
    fault/resume events — stamped or legacy) plus JSON metric snapshots
    into per-phase latency breakdowns, padding-waste-by-bucket tables,
    recovery-overhead summaries, and the iters/sec trajectory."""
    import os

    from distributedlpsolver_tpu.obs import report as obs_report

    for p in args.files:
        if not os.path.exists(p):
            print(f"report: {p!r}: file not found", file=sys.stderr)
            return 2
    rep = obs_report.report_from_paths(args.files)
    if args.json:
        print(json.dumps(rep))
    else:
        print(obs_report.render(rep))
    return 0


def cmd_obs_agg(args) -> int:
    """Fleet telemetry aggregator (README "Distributed tracing & fleet
    telemetry"): discover the serving fleet (backend registry + world
    heartbeat dirs + explicit URLs), pull every process's /statusz and
    /metrics, merge per-process trace files into ONE Perfetto trace
    connected by trace_id, surface histogram exemplars, and print the
    reconciliation table lining up the router hedge ledger, the
    backends' request records, and the journals' lifecycle counts."""
    import os

    from distributedlpsolver_tpu.obs import agg as obs_agg

    traces = []
    for spec in args.trace or []:
        # Either label=path or a bare path (label = basename).
        label, sep, path = spec.partition("=")
        if not sep:
            label, path = os.path.basename(spec), spec
        traces.append((label, path))
    fleet, merged = obs_agg.fleet_view(
        registry_path=args.registry,
        heartbeat_dirs=args.heartbeat_dir or [],
        routers=args.router or [],
        backends=args.backend or [],
        traces=traces,
        metrics_json=args.metrics_json or [],
        timeout_s=args.timeout_s,
    )
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        fleet_path = os.path.join(args.out, "fleet.json")
        with open(fleet_path, "w") as fh:
            json.dump(fleet, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"fleet view -> {fleet_path}", file=sys.stderr)
        if merged is not None:
            trace_path = os.path.join(args.out, "trace_merged.json")
            with open(trace_path, "w") as fh:
                json.dump(merged, fh)
                fh.write("\n")
            print(
                f"merged trace ({len(merged['traceEvents'])} events, "
                f"{merged['otherData']['traces_connected']} trace(s) "
                f"connected) -> {trace_path} (open at ui.perfetto.dev)",
                file=sys.stderr,
            )
    if args.json:
        print(json.dumps(fleet))
    else:
        print(obs_agg.render_text(fleet), end="")
    rec = fleet.get("reconciliation") or {}
    return 0 if rec.get("consistent", True) else 1


def cmd_check(args) -> int:
    """graftcheck: run the repo's static-analysis suite (jit/recompile
    hygiene, dtype discipline, lock + static deadlock discipline, SPMD
    discipline, JSONL schema) over the given paths. Exit 0 iff there are
    no unsuppressed findings — this is the tier-1 CI gate (README
    "Static analysis"). With ``--baseline`` the gate is incremental:
    only findings NOT in the committed baseline fail (the cheap
    diff-gate downstream PRs ride; this repo commits an EMPTY baseline).
    Pure stdlib: no jax import, a few seconds on CPU."""
    import os

    from distributedlpsolver_tpu import analysis

    if args.list_rules:
        for name, doc in analysis.all_rules().items():
            print(f"{name}: {doc}")
        return 0
    paths = args.paths or [os.path.dirname(os.path.abspath(__file__))]
    for p in paths:
        if not os.path.exists(p):
            print(f"check: {p!r}: path not found", file=sys.stderr)
            return 2
    rules = args.rules.split(",") if args.rules else None
    try:
        findings = analysis.check_paths(paths, rules=rules)
    except ValueError as e:  # unknown rule name
        print(f"check: {e}", file=sys.stderr)
        return 2
    if args.write_baseline:
        with open(args.write_baseline, "w") as fh:
            fh.write(analysis.write_baseline(findings) + "\n")
        print(
            f"check: wrote baseline of "
            f"{sum(1 for f in findings if not f.suppressed)} finding(s) "
            f"to {args.write_baseline}",
            file=sys.stderr,
        )
        return 0
    gating = [f for f in findings if not f.suppressed]
    if args.baseline:
        try:
            with open(args.baseline) as fh:
                doc = json.load(fh)
        except (OSError, ValueError) as e:
            print(f"check: --baseline {args.baseline!r}: {e}", file=sys.stderr)
            return 2
        gating = analysis.diff_baseline(findings, doc)
        known = sum(1 for f in findings if not f.suppressed) - len(gating)
        if known:
            print(
                f"check: {known} known finding(s) covered by baseline "
                f"{args.baseline}",
                file=sys.stderr,
            )
    if args.json:
        print(analysis.render_json(findings))
    else:
        print(analysis.render_text(findings, show_suppressed=args.show_suppressed))
    return 1 if gating else 0


def cmd_backends(_args) -> int:
    from distributedlpsolver_tpu.backends import available_backends

    for name in available_backends():
        print(name)
    return 0


def cmd_generate(args) -> int:
    from distributedlpsolver_tpu.io.mps import write_mps
    from distributedlpsolver_tpu.models import generators as gen

    if args.kind == "dense":
        p = gen.random_dense_lp(args.m, args.n, seed=args.seed)
    elif args.kind == "general":
        p = gen.random_general_lp(args.m, args.n, seed=args.seed)
    elif args.kind == "scenario":
        # Lowered two-stage stochastic LP. The hint is not representable
        # in MPS; for sparse-stored ingests (m·n > 200k) `solve
        # --backend auto` recovers it from the sparsity pattern
        # (models/structure.detect_two_stage) and routes back to the
        # scenario engine — smaller files solve on the dense path,
        # which beats device dispatch at that size anyway.
        from distributedlpsolver_tpu.models.scenario import two_stage_storm

        p = two_stage_storm(
            args.scenarios, block_m=args.m, block_n=args.n,
            seed=args.seed,
        ).to_block_angular()
    else:
        p = gen.block_angular_lp(
            args.blocks, args.m, args.n, args.link, seed=args.seed
        )
    write_mps(p, args.out)
    print(f"wrote {p.name} ({p.m}x{p.n}) to {args.out}")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="distributedlpsolver_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    ap_solve = sub.add_parser("solve", help="solve an MPS file")
    ap_solve.add_argument("file", help="MPS path (optionally .gz)")
    _add_solver_flags(ap_solve)
    ap_solve.set_defaults(fn=cmd_solve)

    def _add_serving_flags(p) -> None:
        p.add_argument("--batch", type=int, default=16, help="bucket slots")
        p.add_argument(
            "--flush-ms", type=float, default=50.0,
            help="oldest-request age that launches a part-full bucket "
            "(priority classes shade this per request when --quotas "
            "enables the SLO-aware admission layer)",
        )
        p.add_argument(
            "--queue-depth", type=int, default=1024,
            help="admission-control bound on total queued requests "
            "(the global backstop beneath per-tenant quotas)",
        )
        p.add_argument(
            "--deadline-s", type=float, default=0.0,
            help="default per-request deadline (0 = none)",
        )
        p.add_argument(
            "--mesh-devices", type=int, default=0,
            help="shard each bucket dispatch's batch axis over this many "
            "local devices (0/1 = unsharded, -1 = all local devices)",
        )
        p.add_argument(
            "--buckets", default=None,
            help="explicit bucket ladder JSON (the `autotune` output) "
            "instead of auto power-of-two buckets",
        )
        p.add_argument(
            "--solo-backend", default="auto",
            help="solver backend for the per-request solo path "
            "(general-form / retried requests); 'auto' picks by "
            "problem structure (see `backends`)",
        )
        p.add_argument(
            "--no-warm-start", action="store_true",
            help="disable the warm-start & amortization layer (fingerprint "
            "cache + safeguarded warm-started IPM for correlated requests; "
            "README 'Warm-start & amortization')",
        )
        p.add_argument(
            "--warm-cache-entries", type=int, default=512,
            help="bounded LRU capacity of the problem-fingerprint warm cache",
        )
        p.add_argument(
            "--quotas", default=None,
            help="SLO-aware admission policy, inline JSON or @file: "
            '{"tenants": {"acme": {"rate": 10, "burst": 20, '
            '"weight": 2}}, "default": {...}, "fair_start": 0.5} '
            "(README 'Network serving')",
        )
        p.add_argument(
            "--journal-dir", default=None,
            help="durable job journal directory: write-ahead request "
            "log + on-disk async results; a restart against the same "
            "directory replays unfinished work and re-binds poll URLs "
            "(README 'Durability & graceful shutdown')",
        )
        p.add_argument(
            "--journal-fsync", default="flush",
            choices=["none", "flush", "always"],
            help="journal persistence per record: flush survives "
            "kill -9 (default), always additionally fsyncs",
        )
        p.add_argument(
            "--brownout", default=None,
            help="overload brownout ladder: 'on' for defaults, or "
            "inline JSON overriding BrownoutConfig fields, e.g. "
            '{"depth_high": 0.6, "engage_after_s": 0.5} '
            "(README 'Elasticity & overload protection')",
        )

    ap_srv = sub.add_parser(
        "serve",
        help="async batching solve service: JSONL/MPS requests in, "
        "result records out (README 'Serving')",
    )
    src = ap_srv.add_mutually_exclusive_group(required=True)
    src.add_argument(
        "--requests", help="JSONL request file, or '-' for stdin"
    )
    src.add_argument(
        "--dir", help="directory of *.mps requests and/or *.jsonl spec files"
    )
    ap_srv.add_argument("--out", default="-", help="result JSONL path ('-' = stdout)")
    _add_serving_flags(ap_srv)
    _add_solver_flags(ap_srv)
    ap_srv.set_defaults(fn=cmd_serve, quiet=True)

    ap_http = sub.add_parser(
        "serve-http",
        help="HTTP front-end over the solve service: POST /v1/solve, "
        "GET /metrics /healthz /statusz (README 'Network serving')",
    )
    ap_http.add_argument("--host", default="127.0.0.1")
    ap_http.add_argument(
        "--port", type=int, default=8080,
        help="bind port (0 = OS-assigned ephemeral)",
    )
    ap_http.add_argument(
        "--max-wait-s", type=float, default=300.0,
        help="sync-POST wait bound for requests without a deadline",
    )
    ap_http.add_argument(
        "--wedge-s", type=float, default=30.0,
        help="queued depth with zero dispatch progress for this long "
        "flips /healthz unhealthy",
    )
    ap_http.add_argument(
        "--net-log-jsonl", default=None,
        help="http_request JSONL event stream (stamped schema)",
    )
    ap_http.add_argument(
        "--warm-buckets", action="store_true",
        help="pre-compile the explicit --buckets ladder before binding "
        "the listener (restart recovery runs warm from request one)",
    )
    ap_http.add_argument(
        "--registry", default=None,
        help="shared backend-registry file: self-register AFTER "
        "warm-up + listener bind and heartbeat (routers and the "
        "elastic controller adopt this backend with no manual config)",
    )
    ap_http.add_argument(
        "--heartbeat-s", type=float, default=1.0,
        help="registry heartbeat cadence when --registry is set",
    )
    ap_http.add_argument(
        "--deadline-propagation", action=argparse.BooleanOptionalAction,
        default=True,
        help="honor the X-DLPS-Deadline-Ms remaining-budget header: "
        "clamp the request deadline to it and admission-reject work "
        "whose budget expired in flight (README 'Tail tolerance')",
    )
    _add_serving_flags(ap_http)
    _add_solver_flags(ap_http)
    ap_http.set_defaults(fn=cmd_serve_http, quiet=True)

    ap_slice = sub.add_parser(
        "serve-slice",
        help="multi-host slice: N-process world serving one HTTP "
        "front-end over the slice's global mesh, with coordinator-"
        "level recovery (README 'Multi-host')",
    )
    ap_slice.add_argument(
        "--world-size", type=int, default=2,
        help="processes in the slice (harness: CPU processes; pod: "
        "one per host)",
    )
    ap_slice.add_argument(
        "--rank", type=int, default=None,
        help="run ONE rank (spawned by the supervisor; env contract "
        "from the launcher). Omit to run the slice supervisor.",
    )
    ap_slice.add_argument(
        "--local-devices", type=int, default=2,
        help="virtual CPU devices per rank process (harness only)",
    )
    ap_slice.add_argument(
        "--slice-id", default="slice0",
        help="logical slice name stamped into registry entries and "
        "world_reinit events",
    )
    ap_slice.add_argument(
        "--registry", default=None,
        help="shared backend-registry file to self-register into "
        "(routers adopt the slice with no manual config)",
    )
    ap_slice.add_argument(
        "--heartbeat-s", type=float, default=1.0,
        help="registry heartbeat cadence (routers TTL-eject a slice "
        "whose beats stop)",
    )
    ap_slice.add_argument(
        "--control-dir", default=None,
        help="slice dispatch-journal directory (default: next to the "
        "launcher's heartbeat dir)",
    )
    ap_slice.add_argument(
        "--slice-workdir", default=None,
        help="supervisor workdir (heartbeats, rank logs, xla cache)",
    )
    ap_slice.add_argument(
        "--max-reforms", type=int, default=3,
        help="world re-initializations before the supervisor gives up",
    )
    ap_slice.add_argument(
        "--supervise-timeout-s", type=float, default=86400.0,
        help="supervisor wall-clock budget",
    )
    ap_slice.add_argument("--host", default="127.0.0.1")
    ap_slice.add_argument(
        "--port", type=int, default=8080,
        help="rank-0 HTTP port — must be explicit so a re-initialized "
        "world rebinds the same poll URLs",
    )
    ap_slice.add_argument("--max-wait-s", type=float, default=300.0)
    ap_slice.add_argument("--wedge-s", type=float, default=30.0)
    ap_slice.add_argument(
        "--net-log-jsonl", default=None,
        help="http_request / world_reinit JSONL event stream",
    )
    ap_slice.add_argument(
        "--warm-buckets", action="store_true",
        help="pre-compile the bucket ladder on EVERY rank before the "
        "listener binds",
    )
    _add_serving_flags(ap_slice)
    _add_solver_flags(ap_slice)
    ap_slice.set_defaults(fn=cmd_serve_slice, quiet=True)

    ap_rt = sub.add_parser(
        "route",
        help="router tier over serve-http backends: shape/load-aware "
        "routing, health-checked failover (README 'Network serving')",
    )
    ap_rt.add_argument(
        "--backend", action="append",
        help="backend base URL (repeatable), e.g. http://10.0.0.2:8080; "
        "optional when --registry is given (slices self-register)",
    )
    ap_rt.add_argument("--host", default="127.0.0.1")
    ap_rt.add_argument(
        "--port", type=int, default=8079,
        help="bind port (0 = OS-assigned ephemeral)",
    )
    ap_rt.add_argument(
        "--poll-s", type=float, default=1.0,
        help="backend health/status poll cadence",
    )
    ap_rt.add_argument(
        "--eject-after", type=int, default=2,
        help="consecutive failed health probes before ejection",
    )
    ap_rt.add_argument(
        "--log-jsonl", default=None,
        help="route/ejection JSONL event stream (stamped schema)",
    )
    ap_rt.add_argument(
        "--registry", default=None,
        help="shared backend-registry file: replicated routers pointed "
        "at the same path share one consistent view of backends, "
        "ejections and re-admissions (README 'Durability & graceful "
        "shutdown')",
    )
    ap_rt.add_argument(
        "--probe-backoff-cap-s", type=float, default=30.0,
        help="ceiling on the exponential re-probe backoff of ejected "
        "backends",
    )
    ap_rt.add_argument(
        "--registry-ttl-s", type=float, default=0.0,
        help="eject self-registered backends whose registry heartbeat "
        "is older than this (0 = off; README 'Multi-host')",
    )
    ap_rt.add_argument(
        "--hedge", action=argparse.BooleanOptionalAction, default=True,
        help="adaptive hedged solves: when a primary forward is silent "
        "past the backend's recent p95, race ONE duplicate on the "
        "next-best backend (README 'Tail tolerance')",
    )
    ap_rt.add_argument(
        "--hedge-rate-cap", type=float, default=0.05,
        help="global bound on hedges as a fraction of solve forwards",
    )
    ap_rt.add_argument(
        "--retry-budget", type=float, default=5.0,
        help="per-tenant retry-budget refill rate (tokens/s); retries "
        "drain it, hedges require a whole token",
    )
    ap_rt.add_argument(
        "--retry-budget-burst", type=float, default=20.0,
        help="per-tenant retry-budget bucket capacity",
    )
    ap_rt.add_argument(
        "--deadline-propagation", action=argparse.BooleanOptionalAction,
        default=True,
        help="stamp every forward/retry/hedge with the REMAINING "
        "deadline budget (X-DLPS-Deadline-Ms + body re-stamp)",
    )
    ap_rt.add_argument("--metrics-path", default=None, help=argparse.SUPPRESS)
    ap_rt.add_argument("--trace-path", default=None, help=argparse.SUPPRESS)
    ap_rt.set_defaults(fn=cmd_route)

    ap_el = sub.add_parser(
        "elastic",
        help="closed-loop elasticity controller: scale serve-http "
        "backends out/in from pool telemetry (README 'Elasticity & "
        "overload protection')",
    )
    ap_el.add_argument(
        "--registry", required=True,
        help="shared backend-registry file the pool lives in",
    )
    ap_el.add_argument("--min-backends", type=int, default=1)
    ap_el.add_argument("--max-backends", type=int, default=4)
    ap_el.add_argument(
        "--poll-s", type=float, default=0.5, help="decision cadence"
    )
    ap_el.add_argument(
        "--load-high", type=float, default=8.0,
        help="mean per-backend queued+inflight at/above which the pool "
        "counts as overloaded",
    )
    ap_el.add_argument(
        "--load-low", type=float, default=1.0,
        help="mean load at/below which the pool counts as idle",
    )
    ap_el.add_argument(
        "--reject-rate-high", type=float, default=1.0,
        help="pool-wide admission rejects/sec that count as overload",
    )
    ap_el.add_argument(
        "--out-sustain-s", type=float, default=1.0,
        help="overload must hold this long before a scale-out",
    )
    ap_el.add_argument(
        "--in-sustain-s", type=float, default=5.0,
        help="idleness must hold this long before a scale-in",
    )
    ap_el.add_argument(
        "--cooldown-s", type=float, default=5.0,
        help="minimum quiet time between target changes",
    )
    ap_el.add_argument("--host", default="127.0.0.1")
    ap_el.add_argument(
        "--workdir", default=".",
        help="spawned backends' journals and logs live here",
    )
    ap_el.add_argument(
        "--buckets", default=None,
        help="bucket ladder JSON spawned backends warm before they "
        "register (the zero-warm-recompile rollout contract)",
    )
    ap_el.add_argument(
        "--backend-flag", action="append", default=None,
        help="extra serve-http flag(s) for spawned backends "
        "(repeatable; each value is whitespace-split)",
    )
    ap_el.add_argument(
        "--heartbeat-s", type=float, default=0.5,
        help="registry heartbeat cadence of spawned backends",
    )
    ap_el.add_argument(
        "--log-jsonl", default=None,
        help="scale_out/scale_in/scale_veto JSONL event stream",
    )
    ap_el.add_argument("--metrics-path", default=None, help=argparse.SUPPRESS)
    ap_el.add_argument("--trace-path", default=None, help=argparse.SUPPRESS)
    ap_el.set_defaults(fn=cmd_elastic)

    ap_at = sub.add_parser(
        "autotune",
        help="refine a serve bucket ladder from telemetry JSONL "
        "(README 'Serving performance')",
    )
    ap_at.add_argument(
        "--telemetry", required=True,
        help="service telemetry JSONL (the serve --log-jsonl stream)",
    )
    ap_at.add_argument("--out", required=True, help="ladder JSON output path")
    ap_at.add_argument(
        "--current", default=None,
        help="current ladder JSON (reported against, seeds split decisions)",
    )
    ap_at.add_argument("--waste-threshold", type=float, default=0.35)
    ap_at.add_argument("--max-programs", type=int, default=12)
    ap_at.add_argument("--batch", type=int, default=0, help="slots per bucket")
    ap_at.add_argument(
        "--devices", type=int, default=1,
        help="mesh width bucket batches must divide (serve --mesh-devices)",
    )
    ap_at.set_defaults(fn=cmd_autotune)

    ap_r = sub.add_parser(
        "report",
        help="analyze telemetry JSONL streams + metric snapshots: "
        "per-phase p50/p95/p99, padding waste by bucket, recovery "
        "overhead, iters/sec trajectory (README 'Observability')",
    )
    ap_r.add_argument(
        "files", nargs="+",
        help="telemetry JSONL files and/or JSON metric snapshots "
        "(solve/serve --log-jsonl streams, serve --out records)",
    )
    ap_r.add_argument(
        "--json", action="store_true",
        help="emit the full report as one JSON object",
    )
    ap_r.set_defaults(fn=cmd_report)

    ap_oa = sub.add_parser(
        "obs-agg",
        help="fleet telemetry aggregator: pull /statusz + /metrics "
        "across routers/backends/ranks, merge per-process traces into "
        "one Perfetto file connected by trace_id, and reconcile the "
        "hedge ledger against backend records and journal counts "
        "(README 'Distributed tracing & fleet telemetry')",
    )
    ap_oa.add_argument(
        "--registry", default=None,
        help="shared backend-registry JSON — backends are discovered "
        "from it (slice_id/world_size/ejected ride along)",
    )
    ap_oa.add_argument(
        "--router", action="append", default=None, metavar="URL",
        help="router URL to pull the hedge ledger from (repeatable)",
    )
    ap_oa.add_argument(
        "--backend", action="append", default=None, metavar="URL",
        help="extra backend URL beyond the registry (repeatable)",
    )
    ap_oa.add_argument(
        "--heartbeat-dir", action="append", default=None, metavar="DIR",
        help="world heartbeat dir to scan for rank*.hb liveness and "
        "rank*.metrics.json snapshots (repeatable)",
    )
    ap_oa.add_argument(
        "--trace", action="append", default=None, metavar="[LABEL=]PATH",
        help="per-process Chrome-trace JSON to merge (repeatable; "
        "label defaults to the file name)",
    )
    ap_oa.add_argument(
        "--metrics-json", action="append", default=None, metavar="PATH",
        help="JSON metrics snapshot to mine for histogram exemplars "
        "(repeatable)",
    )
    ap_oa.add_argument(
        "--out", default=None, metavar="DIR",
        help="write fleet.json + trace_merged.json here",
    )
    ap_oa.add_argument(
        "--timeout-s", type=float, default=2.0,
        help="per-pull HTTP timeout (unreachable processes degrade to "
        "error rows, never crash the aggregation)",
    )
    ap_oa.add_argument(
        "--json", action="store_true",
        help="print the fleet view as one JSON object",
    )
    ap_oa.set_defaults(fn=cmd_obs_agg)

    ap_c = sub.add_parser(
        "check",
        help="graftcheck static-analysis suite: jit/recompile hygiene, "
        "dtype discipline, lock discipline, JSONL schema — the tier-1 "
        "CI gate (README 'Static analysis')",
    )
    ap_c.add_argument(
        "paths", nargs="*",
        help="files/directories to check (default: the installed "
        "distributedlpsolver_tpu package)",
    )
    ap_c.add_argument(
        "--json", action="store_true",
        help="machine-readable findings (the gate's artifact format)",
    )
    ap_c.add_argument(
        "--rules", default=None,
        help="comma-separated rule subset (see --list-rules)",
    )
    ap_c.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalogue and exit",
    )
    ap_c.add_argument(
        "--show-suppressed", action="store_true",
        help="also print findings silenced by graftcheck directives",
    )
    ap_c.add_argument(
        "--baseline", default=None, metavar="JSON",
        help="incremental diff-gate: fail only on findings absent from "
        "this committed baseline (see --write-baseline); the tier-1 "
        "gate runs against the empty BASELINE_GRAFTCHECK.json",
    )
    ap_c.add_argument(
        "--write-baseline", default=None, metavar="JSON",
        help="write the current unsuppressed findings as a baseline "
        "document and exit 0 (adopt-then-ratchet for existing trees)",
    )
    ap_c.set_defaults(fn=cmd_check)

    ap_b = sub.add_parser("backends", help="list registered backends")
    ap_b.set_defaults(fn=cmd_backends)

    ap_g = sub.add_parser("generate", help="write a generated problem to MPS")
    ap_g.add_argument("kind", choices=["dense", "general", "block", "scenario"])
    ap_g.add_argument("out")
    ap_g.add_argument("--m", type=int, default=100)
    ap_g.add_argument("--n", type=int, default=250)
    ap_g.add_argument("--blocks", type=int, default=4)
    ap_g.add_argument("--link", type=int, default=20)
    ap_g.add_argument("--scenarios", type=int, default=8,
                      help="scenario count K of the two-stage instance "
                      "(kind=scenario; --m/--n are the recourse block "
                      "shape)")
    ap_g.add_argument("--seed", type=int, default=0)
    ap_g.set_defaults(fn=cmd_generate)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
