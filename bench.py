"""Benchmark harness — prints ONE JSON line for the round driver.

Metric (BASELINE.json:2): IPM iterations/sec and wall-clock to a 1e-8
relative duality gap. The reference publishes no numbers and no Netlib/
Mittelmann files are fetchable in this zero-egress image, so
each of the reference's five benchmark configs (BASELINE.json:7-11) runs on
a generated stand-in of the same structure and scale class, and
``vs_baseline`` compares the accelerated backend against this package's own
host/CPU path on the same problem — the stand-in for the reference's 8-rank
MPI/CPU baseline until real files are present in ``data/`` (drop
``pds-20.mps`` there to switch the headline bench to it automatically).

Usage:
  python bench.py [--quick] [--backend tpu|sharded] [--mps FILE]
  python bench.py --suite [--quick]    # all five reference configs,
                                       # detailed rows → BENCH_SUITE.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

_REPO = os.path.dirname(os.path.abspath(__file__))


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _obs_enable() -> None:
    """Install a process-wide metrics registry for the whole bench run,
    so every row can embed the snapshot (the BENCH_*.json trajectories
    become self-describing: a row says how many programs compiled and
    what the padding waste was, not just how fast it went)."""
    from distributedlpsolver_tpu.obs import metrics as obs_metrics

    obs_metrics.set_registry(obs_metrics.MetricsRegistry())


def _obs_row(platform: str) -> dict:
    """Compact metrics snapshot stamped into each suite row: platform,
    cumulative compile/iteration counters, and the serve-path padding
    waste + pack/solve overlap ratio (None until a serve row ran)."""
    from distributedlpsolver_tpu.obs import metrics as obs_metrics

    snap = obs_metrics.get_registry().snapshot()

    def _hist(name):
        h = snap.get(name)
        return h if isinstance(h, dict) and h.get("count") else None

    waste = _hist("serve_padding_waste")
    overlap, solve = _hist("serve_overlap_ms"), _hist("serve_solve_ms")
    return {
        "platform": platform,
        "ipm_iterations_total": int(snap.get("ipm_iterations_total", 0)),
        "bucket_programs_compiled": int(
            snap.get("bucket_programs_compiled_total", 0)
        ),
        "serve_bucket_compiles": int(
            snap.get("serve_bucket_compiles_total", 0)
        ),
        "serve_padding_waste_mean": (
            round(waste["sum"] / waste["count"], 4) if waste else None
        ),
        "serve_overlap_ratio": (
            round(overlap["sum"] / solve["sum"], 4)
            if overlap and solve and solve["sum"] > 0 else None
        ),
    }


def _solve(problem, backend: str, **cfg):
    """``ipm.solve`` with the backend given by name (imported lazily, so
    argument errors exit before JAX starts)."""
    from distributedlpsolver_tpu.ipm import solve

    return solve(problem, backend=backend, **cfg)


def _headline_problem(args):
    """The headline config: a real pds-20.mps if present, else the pds-like
    block-angular stand-in (BASELINE.json:8 structure)."""
    from distributedlpsolver_tpu.io.mps import read_mps
    from distributedlpsolver_tpu.models.generators import block_angular_lp

    pds20_path = args.mps or os.path.join(_REPO, "data", "pds-20.mps")
    if os.path.exists(pds20_path):
        return read_mps(pds20_path), os.path.basename(pds20_path)
    if args.quick:
        return (
            block_angular_lp(4, 24, 48, 12, seed=0, sparse=False),
            "block_angular(K=4,24x48,link=12) [quick]",
        )
    return (
        block_angular_lp(8, 96, 256, 64, seed=0, sparse=False),
        "block_angular(K=8,96x256,link=64) pds-like stand-in",
    )


def _bench_one(problem, backend: str, baseline: str | None, **cfg):
    """Warm-up (compile) + timed solve on ``backend``; optional baseline
    solve for the speedup ratio. Returns a result row dict."""
    from distributedlpsolver_tpu.backends import available_backends

    # Warm-up at the SAME config as the timed solve: segmented backends
    # key their compiled programs on buffer_cap(n_phases·max_iter), so a
    # small-max_iter warm-up compiles a never-reused bucket and the timed
    # solve pays the real compile (observed: storm-class row 74 s cold vs
    # 10 s warm). A full warm solve costs seconds; a cold compile in the
    # timed region costs the row its meaning. The timed figure is the
    # best of two warm solves.
    _solve(problem, backend, **cfg)
    r = _solve(problem, backend, **cfg)
    r2 = _solve(problem, backend, **cfg)
    if r2.solve_time < r.solve_time:
        r = r2
    _log(f"  {backend}: " + r.summary())
    row = {
        "backend": getattr(r, "backend", backend),
        "time_s": round(r.solve_time, 4),
        "iters": int(r.iterations),
        "iters_per_sec": round(r.iters_per_sec, 2),
        "status": r.status.value,
        # Every row records the tolerance it was solved to — rows at a
        # looser tol (e.g. first-order configs) must say so (review).
        "tol": cfg.get("tol", 1e-8),
        # null until a baseline is actually measured (same rule as the
        # batched row): a fabricated neutral 1.0 reads as "measured, no
        # speedup" — e.g. the dense 2048x10240 row, whose CPU baseline
        # is deliberately not run at full size.
        "vs_baseline": None,
    }
    if baseline and baseline in available_backends() and baseline != backend:
        try:
            # Baselines are CPU paths (no segmented buffer_cap buckets to
            # warm) — a tiny warm-up covers any lazy init. The baseline is
            # best-of-two like the backend figure: filtering noise from
            # only one side of the ratio would bias vs_baseline upward.
            _solve(problem, baseline, max_iter=3)
            rb = _solve(problem, baseline)
            rb2 = _solve(problem, baseline)
            if rb2.solve_time < rb.solve_time:
                rb = rb2
            _log(f"  baseline {baseline}: " + rb.summary())
            if rb.solve_time > 0 and r.solve_time > 0:
                row["baseline_backend"] = baseline
                row["baseline_time_s"] = round(rb.solve_time, 4)
                row["vs_baseline"] = round(rb.solve_time / r.solve_time, 3)
        except Exception as e:  # baseline must never sink the bench
            _log(f"  baseline {baseline} failed: {e}")
    return row


def _bench_batched(quick: bool):
    """Config 5 (BASELINE.json:11): 1024 independent (128, 512) LPs.

    The baseline is the reference's natural shape for this config — one
    LP at a time through the host/CPU path ("one LP per rank", looped).
    Solving all 1024 serially would dominate the bench budget, so a
    random subsample is measured and extrapolated (the problems are
    i.i.d. draws from one generator, so the mean is unbiased); the row
    records the sample size and per-problem mean alongside the estimate.
    """
    from distributedlpsolver_tpu.backends.batched import solve_batched
    from distributedlpsolver_tpu.models.generators import random_batched_lp

    B, m, n = (32, 16, 40) if quick else (1024, 128, 512)
    batch = random_batched_lp(B, m, n, seed=0)

    solve_batched(batch, max_iter=3)  # compile warm-up (full-size programs)
    # One full untimed solve: final-phase compaction runs half-size
    # programs (256→128→64→32) whose compiles only happen once actives
    # drain — a max_iter=3 warm-up never reaches them, and ~100 s of
    # one-time compile inside the first timed figure would make
    # best-of-two load-bearing instead of a noise guard.
    solve_batched(batch)
    try:
        # Warm the solo-cleanup path too: tail-extracted stragglers
        # re-solve through the dense backend, and its first compile
        # (~60 s observed for the two-phase segment programs at the
        # member shape) otherwise lands inside the timed solve. The
        # warm-up max_iter must land in the SAME buffer_cap bucket as a
        # real cleanup solve (buffer caps are static jit keys), so both
        # the figure and the backend name come from batched's own
        # cleanup logic — a hardcoded pair silently compiles a
        # never-reused executable whenever the defaults move. The solve
        # itself converges in ~20 iterations, so the large bound only
        # shapes the bucket, not the runtime.
        from distributedlpsolver_tpu.backends.batched import (
            CLEANUP_BACKEND,
            cleanup_solo_max_iter,
            member_interior_form,
        )
        from distributedlpsolver_tpu.ipm.driver import solve as _solo_solve

        _solo_solve(member_interior_form(batch, 0), backend=CLEANUP_BACKEND,
                    max_iter=cleanup_solo_max_iter(member_entries=m * n))
    except Exception as e:
        _log(f"  solo-path warm-up failed (non-fatal): {e}")
    t0 = time.perf_counter()
    res = solve_batched(batch)
    dt = time.perf_counter() - t0
    ok = sum(1 for s in res.status if s.value == "optimal")
    _log(f"  batched: {B} LPs in {res.solve_time:.3f}s, {ok}/{B} optimal")
    # Per-member status breakdown (review round 3 item 2: the artifact
    # must say WHAT the non-optimal members are, not just how many).
    breakdown: dict = {}
    for s in res.status:
        breakdown[s.value] = breakdown.get(s.value, 0) + 1
    non_opt = [
        {"i": int(i), "status": res.status[i].value,
         "rel_gap": float(res.rel_gap[i]), "pinf": float(res.pinf[i])}
        for i in range(B) if res.status[i].value != "optimal"
    ]
    row = {
        "backend": "batched(vmap)",
        "time_s": round(res.solve_time, 4),
        "problems": B,
        "problems_per_sec": round(B / max(res.solve_time, 1e-9), 1),
        "optimal": ok,
        "status_breakdown": breakdown,
        "non_optimal_members": non_opt[:16],  # cap: artifact readability
        "wall_s": round(dt, 4),
        "tol": 1e-8,
        # null until the baseline measurement actually succeeds — a
        # fabricated neutral 1.0 would read as "measured, no speedup".
        "vs_baseline": None,
    }
    try:
        # MEASURED full-loop baseline first (review round-4 item 1: no
        # sampling/extrapolation): scripts/run_batched_cpu_loop.py solves
        # all 1024 members one at a time through cpu-native on a quiet
        # host and records the artifact consumed here. Falls back to the
        # sampled estimate only when the artifact is absent or doesn't
        # match this row's config.
        import json as _json

        loop_art = os.path.join(_REPO, ".batched_cpu_loop.json")
        used_artifact = False
        if not quick and os.path.exists(loop_art):
            art = _json.load(open(loop_art))
            # the full config string must match — B alone would accept a
            # stale artifact measured on a different shape/seed
            expected_cfg = f"{B} x ({m}x{n}) seed=0 looped cpu-native"
            if art.get("config") == expected_cfg and art.get("n_optimal", 0) == B:
                base_s = art["sum_solve_s"]  # per-solve sum: contention-free
                row.update(
                    baseline_backend="cpu-native (loop, one LP at a time)",
                    baseline_sample=B,
                    baseline_measured_full_loop=True,
                    baseline_time_s=base_s,
                    baseline_artifact=".batched_cpu_loop.json",
                    vs_baseline=round(base_s / max(res.solve_time, 1e-9), 2),
                )
                _log(
                    f"  baseline cpu-native loop (MEASURED, all {B}): "
                    f"{base_s:.1f}s ({row['vs_baseline']}x)"
                )
                used_artifact = True
        if not used_artifact:
            sample = min(16, B) if quick else min(128, B)
            rng = __import__("numpy").random.default_rng(7)
            idx = rng.choice(B, size=sample, replace=False)
            probs = [batch.problem(int(i)) for i in idx]
            _solve(probs[0], "cpu-native")  # warm any lazy init
            t0 = time.perf_counter()
            base_ok = 0
            for p in probs:
                rb = _solve(p, "cpu-native")
                base_ok += rb.status.value == "optimal"
            t_sample = time.perf_counter() - t0
            per = t_sample / sample
            est = per * B
            row.update(
                baseline_backend="cpu-native (loop, one LP at a time)",
                baseline_sample=sample,
                baseline_sample_optimal=base_ok,
                baseline_per_problem_s=round(per, 4),
                baseline_time_est_s=round(est, 2),
                vs_baseline=round(est / max(res.solve_time, 1e-9), 2),
            )
            _log(
                f"  baseline cpu-native loop: {sample} sampled, "
                f"{per:.3f}s/problem -> est {est:.1f}s for {B} "
                f"({row['vs_baseline']}x)"
            )
    except Exception as e:  # baseline must never sink the bench
        _log(f"  batched baseline failed: {e}")
    return row


def _bench_serve(quick: bool) -> dict:
    """Serving-throughput row: drive the async batching SolveService with
    the standard random request stream and report the service's own
    telemetry — rps, latency percentiles, padding waste, and the warm
    recompile count (the zero-warm-recompile invariant as a bench
    figure). The cold wave warms every bucket program; the timed wave is
    the steady-state serving figure BENCH_SUITE tracks over rounds."""
    import numpy as _np

    from distributedlpsolver_tpu.backends.batched import bucket_cache_size
    from distributedlpsolver_tpu.models.generators import random_request_stream
    from distributedlpsolver_tpu.serve import ServiceConfig, SolveService

    n = 48 if quick else 200
    cfg = ServiceConfig(batch=8, flush_s=0.02)
    with SolveService(cfg) as svc:
        futs = [svc.submit(p) for p in random_request_stream(n, seed=21)]
        svc.drain(timeout=1200)
        cold = [f.result(timeout=60) for f in futs]
        cache0 = bucket_cache_size()
        t0 = time.perf_counter()
        futs = [svc.submit(p) for p in random_request_stream(n, seed=22)]
        svc.drain(timeout=1200)
        rs = [f.result(timeout=60) for f in futs]
        wall = time.perf_counter() - t0
        warm_recompiles = bucket_cache_size() - cache0
        stats = svc.stats()
    from distributedlpsolver_tpu.obs.stats import percentile

    lat = [r.total_ms for r in rs]
    ok = sum(r.status.value == "optimal" for r in rs)
    row = {
        "backend": "serve(batched bucket dispatch)",
        "requests": n,
        "optimal": ok,
        "cold_optimal": sum(r.status.value == "optimal" for r in cold),
        "time_s": round(wall, 4),
        "rps": round(n / max(wall, 1e-9), 2),
        "latency_ms_p50": round(percentile(lat, 50), 3),
        "latency_ms_p99": round(percentile(lat, 99), 3),
        "mean_padding_waste": round(
            float(_np.mean([r.padding_waste for r in rs])), 4
        ),
        "warm_recompiles": int(warm_recompiles),
        "overlap_ms_total": stats["overlap_ms_total"],
        "buckets": stats["buckets"],
        # Mixed-precision attribution: which precision schedule the
        # bucket programs ran (per-phase iteration totals by engine) and
        # how many IPM iterations each device while-trip fused — future
        # BENCH rows can attribute serving wins to the df32/fused-k
        # levers instead of guessing.
        "schedule": stats["schedule"],
        "phase_iters": stats["phase_iters"],
        "fused_iters": stats["fused_iters"],
        "tol": 1e-8,
        "vs_baseline": None,
    }
    _log(
        f"  serve: {n} requests at {row['rps']} rps warm, "
        f"p50={row['latency_ms_p50']:.0f}ms p99={row['latency_ms_p99']:.0f}ms, "
        f"waste={row['mean_padding_waste']:.2f}, "
        f"warm recompiles={warm_recompiles}, "
        f"schedule={row['schedule']} (phase iters {row['phase_iters']}), "
        f"fused_iters={row['fused_iters']}"
    )
    row["warm_start"] = _bench_serve_warm(quick)
    return row


def _bench_obs(args) -> list:
    """Tracing-overhead A/B: the steady-state serve shape (same stream,
    seeds, and service config as the in-process serve row) with the
    distributed-tracing layer OFF (null tracer, no contexts — the
    baseline every request pays today) vs ON (a live Chrome tracer plus
    a per-request root TraceContext threaded through submit, the full
    span-emission path the fleet aggregator consumes). Tracing is
    host-side bookkeeping by construction — the A/B pins the two
    figures that claim rests on: warm-path latency overhead (p50) and
    the warm recompile count (contexts must never reach program
    identity)."""
    import shutil as _shutil
    import tempfile as _tempfile

    from distributedlpsolver_tpu.backends.batched import bucket_cache_size
    from distributedlpsolver_tpu.models.generators import random_request_stream
    from distributedlpsolver_tpu.obs import trace as obs_trace
    from distributedlpsolver_tpu.obs.context import new_context
    from distributedlpsolver_tpu.obs.stats import percentile
    from distributedlpsolver_tpu.serve import ServiceConfig, SolveService

    n = 48 if args.quick else 200
    rows = []
    for mode in ("off", "on"):
        traced = mode == "on"
        tmpdir = prev = None
        trace_events = None
        if traced:
            tmpdir = _tempfile.mkdtemp(prefix="dlps-bench-obs-")
            prev = obs_trace.set_tracer(obs_trace.Tracer(
                os.path.join(tmpdir, "bench.trace.json"),
                process_name="bench-obs",
            ))
        try:
            with SolveService(ServiceConfig(batch=8, flush_s=0.02)) as svc:
                futs = [
                    svc.submit(p, trace=new_context() if traced else None)
                    for p in random_request_stream(n, seed=21)
                ]
                svc.drain(timeout=1200)
                cold_ok = sum(
                    f.result(timeout=60).status.value == "optimal"
                    for f in futs
                )
                cache0 = bucket_cache_size()
                t0 = time.perf_counter()
                futs = [
                    svc.submit(p, trace=new_context() if traced else None)
                    for p in random_request_stream(n, seed=22)
                ]
                svc.drain(timeout=1200)
                rs = [f.result(timeout=60) for f in futs]
                wall = time.perf_counter() - t0
                warm_recompiles = bucket_cache_size() - cache0
        finally:
            if traced:
                tracer = obs_trace.get_tracer()
                obs_trace.set_tracer(prev)
                tracer.close()
                try:
                    with open(tracer.path) as fh:
                        trace_events = len(json.load(fh)["traceEvents"])
                finally:
                    _shutil.rmtree(tmpdir, ignore_errors=True)
        lat = sorted(r.total_ms for r in rs)
        row = {
            "mode": f"tracing-{mode}",
            "requests": n,
            "optimal": sum(r.status.value == "optimal" for r in rs),
            "cold_optimal": cold_ok,
            "time_s": round(wall, 4),
            "rps": round(n / max(wall, 1e-9), 2),
            "latency_ms_p50": round(percentile(lat, 50), 3),
            "latency_ms_p99": round(percentile(lat, 99), 3),
            "warm_recompiles": int(warm_recompiles),
        }
        if traced:
            row["trace_events"] = trace_events
        rows.append(row)
        _log(
            f"  obs[{row['mode']}]: {n} requests at {row['rps']} rps, "
            f"p50={row['latency_ms_p50']:.1f}ms "
            f"p99={row['latency_ms_p99']:.1f}ms, "
            f"warm recompiles={warm_recompiles}"
            + (f", trace events={trace_events}" if traced else "")
        )
    off, on = rows
    base = max(off["latency_ms_p50"], 1e-9)
    on["p50_overhead_pct"] = round(
        100.0 * (on["latency_ms_p50"] - off["latency_ms_p50"]) / base, 2
    )
    base99 = max(off["latency_ms_p99"], 1e-9)
    on["p99_overhead_pct"] = round(
        100.0 * (on["latency_ms_p99"] - off["latency_ms_p99"]) / base99, 2
    )
    _log(
        f"  obs: tracing-on p50 overhead {on['p50_overhead_pct']:+.2f}% "
        f"(p99 {on['p99_overhead_pct']:+.2f}%)"
    )
    return rows


def _bench_serve_http(quick: bool, inproc_row: Optional[dict] = None) -> dict:
    """HTTP-path serving row: the same steady-state request stream as
    the in-process serve row, but submitted over the network plane
    (POST /v1/solve against a SolveHTTPServer on localhost) from
    concurrent client threads — so the network overhead (HTTP parse,
    JSON encode, socket round-trip, handler-thread dispatch) is
    attributed as the delta against the in-process row's figures."""
    import json as _json
    import threading as _threading
    import urllib.request as _urlreq

    from distributedlpsolver_tpu.backends.batched import bucket_cache_size
    from distributedlpsolver_tpu.models.generators import random_request_stream
    from distributedlpsolver_tpu.net import NetConfig, SolveHTTPServer
    from distributedlpsolver_tpu.obs.stats import percentile
    from distributedlpsolver_tpu.serve import ServiceConfig, SolveService

    n = 48 if quick else 200
    with SolveService(ServiceConfig(batch=8, flush_s=0.02)) as svc:
        server = SolveHTTPServer(svc, NetConfig()).start()
        # Cold wave in-process: warm every bucket program so the HTTP
        # wave measures the network path, not XLA.
        futs = [svc.submit(p) for p in random_request_stream(n, seed=21)]
        svc.drain(timeout=1200)
        for f in futs:
            f.result(timeout=60)
        cache0 = bucket_cache_size()

        problems = list(random_request_stream(n, seed=22))
        lat: list = []
        codes: list = []
        lock = _threading.Lock()

        def client(idx0, step):
            for i in range(idx0, n, step):
                p = problems[i]
                body = _json.dumps(
                    {
                        "problem": {
                            "c": p.c.tolist(),
                            "A": p.A.tolist(),
                            "b": p.rlb.tolist(),
                        },
                        "include_x": False,
                    }
                ).encode()
                req = _urlreq.Request(
                    server.url + "/v1/solve", data=body,
                    headers={"Content-Type": "application/json"},
                )
                t0 = time.perf_counter()
                try:
                    with _urlreq.urlopen(req, timeout=120) as r:
                        out = _json.loads(r.read())
                    code = 200 if out.get("status") == "optimal" else -1
                except Exception:
                    code = -2
                with lock:
                    lat.append((time.perf_counter() - t0) * 1e3)
                    codes.append(code)

        n_clients = 8
        t0 = time.perf_counter()
        threads = [
            _threading.Thread(target=client, args=(i, n_clients))
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1200)
        wall = time.perf_counter() - t0
        warm_recompiles = bucket_cache_size() - cache0
        server.shutdown()
    row = {
        "backend": "serve-http(localhost HTTP front-end)",
        "requests": n,
        "optimal": sum(c == 200 for c in codes),
        "clients": n_clients,
        "time_s": round(wall, 4),
        "rps": round(n / max(wall, 1e-9), 2),
        "latency_ms_p50": round(percentile(lat, 50), 3),
        "latency_ms_p99": round(percentile(lat, 99), 3),
        "warm_recompiles": int(warm_recompiles),
        "tol": 1e-8,
    }
    if inproc_row:
        # Network overhead, attributed: the HTTP row against the
        # in-process row it rode next to.
        row["inproc_rps"] = inproc_row["rps"]
        row["http_overhead_ms_p50"] = round(
            row["latency_ms_p50"] - inproc_row["latency_ms_p50"], 3
        )
    _log(
        f"  serve-http: {n} requests at {row['rps']} rps over "
        f"{n_clients} clients, p50={row['latency_ms_p50']:.0f}ms "
        f"p99={row['latency_ms_p99']:.0f}ms, warm recompiles="
        f"{warm_recompiles}"
        + (
            f", in-process rps={row['inproc_rps']} "
            f"(http p50 overhead {row['http_overhead_ms_p50']:+.1f}ms)"
            if inproc_row
            else ""
        )
    )
    return row


def _bench_serve_warm(quick: bool) -> dict:
    """Warm-start & amortization sub-row: drive the seeded CORRELATED
    stream (same models, perturbed b/c — models/generators.
    correlated_request_stream) through one service with the fingerprint
    cache on, after a cold leg that populates it. Reports median
    iterations-per-request and p50/p99 latency warm-vs-cold, the
    cache-hit ratio, safeguard rejections, and the zero-warm-recompile
    check across the warm leg — the measurements the warm layer is
    accepted on."""
    from distributedlpsolver_tpu.backends.batched import bucket_cache_size
    from distributedlpsolver_tpu.models.generators import (
        correlated_request_stream,
    )
    from distributedlpsolver_tpu.obs.stats import percentile
    from distributedlpsolver_tpu.serve import ServiceConfig, SolveService

    n_cold = 24 if quick else 64
    n_warm = 32 if quick else 128
    with SolveService(ServiceConfig(batch=8, flush_s=0.02)) as svc:
        futs = [
            svc.submit(p)
            for p in correlated_request_stream(n_cold, seed=31)
        ]
        svc.drain(timeout=1200)
        cold_leg = [f.result(timeout=60) for f in futs]
        cache0 = bucket_cache_size()
        t0 = time.perf_counter()
        futs = [
            svc.submit(p)
            for p in correlated_request_stream(
                n_warm, seed=31, offset=n_cold
            )
        ]
        svc.drain(timeout=1200)
        rs = [f.result(timeout=60) for f in futs]
        wall = time.perf_counter() - t0
        warm_recompiles = bucket_cache_size() - cache0
        stats = svc.stats()

    warm_rs = [r for r in rs if r.warm == "warm"]
    # Cold baseline over BOTH legs: at a 100% hit ratio the warm leg
    # alone has no cold members left to compare against.
    cold_rs = [r for r in cold_leg + rs if r.warm != "warm"]
    row = {
        "requests": n_warm,
        "optimal": sum(r.status.value == "optimal" for r in rs),
        "time_s": round(wall, 4),
        "warm_requests": len(warm_rs),
        "hit_ratio": round(len(warm_rs) / max(n_warm, 1), 4),
        "rejected": sum(1 for r in rs if r.warm == "rejected"),
        "iters_median_warm": percentile([r.iterations for r in warm_rs], 50),
        "iters_median_cold": percentile([r.iterations for r in cold_rs], 50),
        "latency_ms_p50_warm": round(
            percentile([r.total_ms for r in warm_rs], 50), 3
        ),
        "latency_ms_p99_warm": round(
            percentile([r.total_ms for r in warm_rs], 99), 3
        ),
        "latency_ms_p50_cold": round(
            percentile([r.total_ms for r in cold_rs], 50), 3
        ),
        "latency_ms_p99_cold": round(
            percentile([r.total_ms for r in cold_rs], 99), 3
        ),
        "warm_recompiles": int(warm_recompiles),
        "warm_cache": stats["warm_cache"],
    }
    _log(
        f"  serve warm-start: {row['warm_requests']}/{n_warm} warm "
        f"(hit {row['hit_ratio']:.0%}, {row['rejected']} rejected), "
        f"median iters {row['iters_median_cold']:.0f} cold -> "
        f"{row['iters_median_warm']:.0f} warm, "
        f"p50 {row['latency_ms_p50_cold']:.0f} -> "
        f"{row['latency_ms_p50_warm']:.0f} ms, "
        f"warm recompiles={warm_recompiles}"
    )
    return row


def _bench_fixtures(quick: bool) -> list:
    """Vendored golden MPS fixtures (+ a ≥10 MB generated file) as suite
    rows: parse → auto-dispatch solve → check the hand-derived optimum
    (review round 2 item 8 — the realism available without Netlib
    egress; the expected values are derived by hand in
    tests/test_fixtures.py and cross-checked against HiGHS there)."""
    from distributedlpsolver_tpu.io.mps import read_mps, write_mps

    rows = []
    for name, opt in (("quirks.mps", 12.0), ("maximize.mps", 14.0)):
        path = os.path.join(_REPO, "tests", "fixtures", name)
        t0 = time.perf_counter()
        p = read_mps(path)
        t_parse = time.perf_counter() - t0
        r = _solve(p, "auto")
        matches = (
            r.status.value == "optimal"
            and abs(r.objective - opt) <= 1e-6 * max(1.0, abs(opt))
        )
        _log(f"  fixture {name}: {r.summary()} (expected obj {opt})")
        rows.append({
            "config": f"fixture {name}",
            "backend": r.backend,
            "time_s": round(r.solve_time, 4),
            "iters": int(r.iterations),
            "status": r.status.value,
            "tol": 1e-8,
            "parse_s": round(t_parse, 4),
            "objective": round(float(r.objective), 9),
            "expected_objective": opt,
            "matches_known_optimum": bool(matches),
            "vs_baseline": None,
        })
    if quick:
        return rows
    # ≥10 MB round-trip realism: generate, WRITE through the package's
    # writer, parse it back, and the solved objective must match the
    # in-memory problem's solve bit-for-bit-ish (same solver, same tol).
    import tempfile

    from distributedlpsolver_tpu.models.generators import random_dense_lp

    p = random_dense_lp(512, 1024, seed=4)
    with tempfile.NamedTemporaryFile("w", suffix=".mps", delete=False) as fh:
        tmp = fh.name
    try:
        write_mps(p, tmp)
        size_mb = os.path.getsize(tmp) / 1e6
        t0 = time.perf_counter()
        q = read_mps(tmp)
        t_parse = time.perf_counter() - t0
        _solve(q, "auto", max_iter=3)  # compile warm-up
        r = _solve(q, "auto")
        r_direct = _solve(p, "auto")
        agree = abs(r.objective - r_direct.objective) <= 1e-7 * (
            1.0 + abs(r_direct.objective)
        )
        _log(
            f"  big file: {size_mb:.1f} MB parsed in {t_parse:.2f}s; "
            f"{r.summary()} (direct-solve agreement: {agree})"
        )
        rows.append({
            "config": "generated dense 512x1024 via 10MB+ MPS round-trip",
            "backend": r.backend,
            "time_s": round(r.solve_time, 4),
            "iters": int(r.iterations),
            "status": r.status.value,
            "tol": 1e-8,
            "file_mb": round(size_mb, 1),
            "parse_s": round(t_parse, 3),
            "agrees_with_direct_solve": bool(agree),
            "vs_baseline": None,
        })
    finally:
        os.unlink(tmp)
    return rows


def run_suite(args) -> list:
    """All five reference benchmark configs (BASELINE.json:7-11), plus
    the golden-fixture rows."""
    from distributedlpsolver_tpu.models.generators import (
        block_angular_lp,
        random_dense_lp,
        random_general_lp,
    )

    q = args.quick
    accel = args.backend
    rows = []

    def add(config, row):
        # Cumulative-to-here metrics snapshot: each row records the
        # observability state at the time it completed.
        row = {"config": config, **row, "metrics": _obs_row(args.platform)}
        rows.append(row)
        _log(json.dumps(row))

    # 1. afiro-class tiny dense (BASELINE.json:7) — 27x51, general form.
    # Measured through --backend auto: structure/size-aware dispatch is
    # the production answer for a dispatch-bound tiny LP (device
    # dispatch costs more than the ~10 ms CPU solve); the row records
    # which backend auto picked.
    _log("[1/7] afiro-class dense 27x51 (auto dispatch)")
    add(
        "afiro-like general LP 27x51",
        _bench_one(random_general_lp(27, 51, seed=0), "auto", "cpu"),
    )

    # 2. pds-02/pds-10-class block-angular (BASELINE.json:8) — the
    # reference's 4-rank row-partitioned configs; here the Schur-complement
    # block backend vs the dense CPU path.
    _log("[2/7] pds-class block-angular (Schur backend)")
    shape = (4, 24, 48, 12) if q else (4, 64, 160, 32)
    add(
        f"pds-02-like block_angular{shape}",
        _bench_one(
            block_angular_lp(*shape, seed=1, sparse=False), "block", "cpu-native"
        ),
    )

    # 3. Random dense full-Cholesky path (BASELINE.json:9; m=10k n=50k in
    # the reference — scaled to fit a single v5e's HBM and test budget,
    # --full restores the reference shape). The default auto two-phase
    # schedule (f32 Pallas phase + f64 finish) does the mixed precision;
    # forcing single-phase f32 here stalls short of the 1e-8 gap.
    m, n = (128, 320) if q else ((10_000, 50_000) if args.full else (2_048, 10_240))
    _log(f"[3/7] random dense {m}x{n} (two-phase mixed precision)")
    row3 = _bench_one(
        random_dense_lp(m, n, seed=2),
        accel,
        "cpu-native" if q else None,  # in-suite CPU solve only at quick size
    )
    if (m, n) == (2048, 10240) and row3.get("vs_baseline") is None:
        # MEASURED end-to-end dense baseline (review round-4 item 3):
        # scripts/run_dense2k_cpu.py solved this exact instance
        # (seed=2) through cpu-native on a quiet host — 839 s, 26
        # iters, OPTIMAL — far too long to re-run inside every suite,
        # so the artifact is consumed like the batched loop baseline.
        art_p = os.path.join(_REPO, ".dense2k_cpu.json")
        if os.path.exists(art_p):
            art = json.load(open(art_p))
            if (
                art.get("config") == f"random dense {m}x{n} seed=2"
                and art.get("status") == "optimal"
            ):
                row3.update(
                    baseline_backend="cpu-native (end-to-end measured)",
                    baseline_time_s=art["solve_s"],
                    baseline_process_cpu_s=art["process_cpu_s"],
                    baseline_artifact=".dense2k_cpu.json",
                    vs_baseline=round(
                        art["solve_s"] / max(row3["time_s"], 1e-9), 1
                    ),
                )
    add(f"random dense {m}x{n}", row3)

    # 4. Large-sparse class (BASELINE.json:10, neos3/stormG2-like):
    # stormG2 IS block-angular (stochastic program). The stand-in arrives
    # HINT-LESS (like a real MPS file); structure detection
    # (models/structure.py) recovers the partition — run explicitly here so
    # the row measures the same detect→Schur path on every host platform
    # (auto's platform rules would divert to cpu-native on a CPU-only box)
    # — and the Schur backend executes it, vs the sparse-direct baseline.
    _log("[4/7] large sparse, hint-less (structure detection → Schur backend)")
    # Non-quick shape is the stormG2-class scale target (review round 2
    # item 4): ≥20k rows, hundreds of natural blocks — detection recovers
    # K=256 and the Schur backend must beat cpu-sparse decisively
    # (measured 2026-07-31: 10.2 s vs 187 s, 18×).
    shape, dens = ((4, 24, 48, 12), 0.15) if q else ((256, 80, 160, 48), 0.08)
    sparse_lp = block_angular_lp(*shape, seed=3, sparse=True, density=dens)
    sparse_lp.block_structure = None  # what a real file looks like
    from distributedlpsolver_tpu.models.structure import detect_block_structure

    t_detect = time.perf_counter()
    hint = detect_block_structure(sparse_lp)
    t_detect = time.perf_counter() - t_detect
    if hint is not None:
        sparse_lp.block_structure = hint
        row = _bench_one(sparse_lp, "block", "cpu-sparse")
        row["detect_s"] = round(t_detect, 4)
        row["detected_blocks"] = hint["num_blocks"]
    else:  # detection declined: honest fallback, still measured
        row = _bench_one(sparse_lp, "cpu-sparse", "cpu")
    add(f"stormG2-like sparse block_angular{shape} (hint-less)", row)

    # 4b. UNSTRUCTURED sparse (BASELINE.json:10, the neos3 half of the
    # class): a uniformly random pattern defeats detection, and the
    # measured routing decision (scripts/run_neos3.py) sends it to the
    # sparse-direct host backend. The row exercises exactly that route
    # through auto so a routing regression shows up as a changed
    # backend name. No baseline: the only honest comparator would be
    # the dense-LAPACK host path, whose m²n-per-iteration cost at this
    # shape is hours — the cross-executor measurement at 1e-8 lives in
    # scripts/run_neos3.py's artifact instead.
    _log("[4b] unstructured sparse, detection-defeating (auto -> cpu-sparse)")
    from distributedlpsolver_tpu.models.generators import random_sparse_lp

    # Sized for the suite budget: _bench_one runs THREE full solves
    # (warm-up + best-of-two), and the sparse-direct factorization's
    # fill-in makes an 8000x16000 instance a ~20-minute-per-solve row
    # (observed) — the scale-class record lives in .neos3_sparse.json,
    # this row pins the ROUTE end-to-end.
    ushape = (400, 800, 0.01) if q else (2000, 4000, 0.002)
    add(
        f"neos3-like unstructured sparse {ushape[0]}x{ushape[1]}",
        _bench_one(
            random_sparse_lp(ushape[0], ushape[1], density=ushape[2], seed=0),
            "auto", None,
        ),
    )

    # 5. Batched concurrent LPs (BASELINE.json:11).
    _log("[5/7] batched 1024x(128,512) vmap solve")
    add("batched 1024x(128x512)" if not q else "batched 32x(16x40)", _bench_batched(q))

    # 5b. Serving throughput over the same batched machinery (the
    # continuous-batching front-end BENCH_SUITE tracks as a trajectory).
    _log("[6/7] serve throughput (async batching solve service)")
    add(f"serve throughput {48 if q else 200} requests", _bench_serve(q))

    # 6. Golden MPS fixtures + big-file round trip (real-file realism).
    _log("[7/7] golden MPS fixtures (hand-derived optima)")
    fixture_rows = _bench_fixtures(q)
    rows.extend(fixture_rows)
    for row in fixture_rows:
        _log(json.dumps(row))

    return rows


def run_scale(args) -> list:
    """Pass/fail regression tier for the 10k-scale machinery (review
    round 3 item 7): the scale behaviors dense.py's design encodes
    (two-phase + PCG handoff, host-LAPACK endgame, direction-level primal
    closure) were established by one-off probe scripts; this tier freezes
    them into envelopes that fail loudly if they regress. Run once per
    round: ``python bench.py --scale``.

    Envelopes (TPU; wall-clock checks skip on other platforms where the
    emulated-f64 cost model doesn't apply):
      1. dense 2048x10240 via the auto schedule: OPTIMAL at 1e-8,
         pinf <= 1e-8, solve <= 3 s warm (measured 2026-07-31: ~0.7 s;
         3 s = 4x headroom over dispatch-latency noise).
      2. dense 1024x5120 with the endgame FORCED (the 10k finish path at
         a minutes-not-hours size): OPTIMAL with final pinf <= 1e-12 —
         the host-factor + primal-closure guarantee (entry pinf ~1e-8
         must DROP through the endgame, not floor).
      3. batched 1024x(128,512) headline: all 1024 members OPTIMAL,
         warm solve <= 240 s (measured ~116 s).
      4. storm-20k hint-less block-angular headline: detection recovers
         K=256, Schur solve OPTIMAL <= 30 s warm (measured 6.3-10.2 s).
    """
    import jax

    from distributedlpsolver_tpu.backends import dense as D
    from distributedlpsolver_tpu.models.generators import random_dense_lp

    on_tpu = jax.default_backend() == "tpu"
    rows = []

    _log("[scale 1/4] dense 2048x10240 auto schedule (envelope: optimal, "
         "pinf<=1e-8, warm solve<=3s)")
    p = random_dense_lp(2048, 10240, seed=2)
    # Warm-up at DEFAULT config: buffer caps are static jit keys bucketed
    # from n_phases·max_iter (core.buffer_cap), so a small-max_iter
    # warm-up would compile a different (never reused) bucket and the
    # timed solve would pay the real compile inside its 3 s envelope.
    # Best-of-two like the suite rows (ADVICE round 4): a single sample
    # against a 3 s envelope would fail the tier on one slow run.
    _solve(p, args.backend)
    r = _solve(p, args.backend)
    r2 = _solve(p, args.backend)
    if r2.solve_time < r.solve_time:
        r = r2
    row = {
        "check": "dense_2048x10240",
        "status": r.status.value,
        "time_s": round(r.solve_time, 3),
        "iters": int(r.iterations),
        "rel_gap": float(r.rel_gap),
        "pinf": float(r.pinf),
        "envelope": {"status": "optimal", "pinf_max": 1e-8,
                     "time_s_max": 3.0 if on_tpu else None},
        "pass": bool(
            r.status.value == "optimal"
            and r.pinf <= 1e-8
            and (not on_tpu or r.solve_time <= 3.0)
        ),
    }
    rows.append(row)
    _log(json.dumps(row))

    if not on_tpu:
        # The endgame only triggers from the two-phase+PCG schedule, which
        # is TPU-only (off-TPU, device f64 is LAPACK-grade and the direct
        # path runs) — forcing it here would test a path production never
        # takes on this platform and fail spuriously. The batched and
        # storm headline envelopes are wall-clock envelopes calibrated on
        # the chip, so they skip off-TPU too (their MATH is covered at
        # small scale by tier-1 tests).
        for check, why in (
            ("dense_1024x5120_forced_endgame",
             "endgame is a TPU-only path (emulated-f64 finish)"),
            ("batched_1024x128x512",
             "wall-clock envelope calibrated on the TPU chip"),
            ("storm20k_block_angular",
             "wall-clock envelope calibrated on the TPU chip"),
        ):
            row2 = {"check": check, "skipped": True,
                    "reason": f"{why}; run this tier on the TPU chip",
                    "pass": True}
            rows.append(row2)
            _log(json.dumps(row2))
        return rows

    _log("[scale 2/4] dense 1024x5120 forced endgame (envelope: optimal, "
         "final pinf<=1e-12)")
    entries_save = D.DenseJaxBackend._ENDGAME_ENTRIES
    try:
        D.DenseJaxBackend._ENDGAME_ENTRIES = 1  # force the 10k finish path
        be = D.DenseJaxBackend()
        p2 = random_dense_lp(1024, 5120, seed=2)
        r2 = _solve(p2, be, solve_mode="pcg", max_iter=120)
    finally:
        D.DenseJaxBackend._ENDGAME_ENTRIES = entries_save
    row2 = {
        "check": "dense_1024x5120_forced_endgame",
        "status": r2.status.value,
        "time_s": round(r2.solve_time, 3),
        "iters": int(r2.iterations),
        "rel_gap": float(r2.rel_gap),
        "pinf": float(r2.pinf),
        "dinf": float(r2.dinf),
        # Accepted endgame iterations only — a raw row count would also
        # count bad-step retry attempts (ADVICE round 4).
        "endgame_iters": sum(
            1 for t in getattr(be, "endgame_timings", [])
            if "t_step" in t and not t.get("bad")
        ),
        "envelope": {"status": "optimal", "pinf_max": 1e-12},
        "pass": bool(r2.status.value == "optimal" and r2.pinf <= 1e-12),
    }
    rows.append(row2)
    _log(json.dumps(row2))

    # 3. Batched headline config (BASELINE.json:11; review "What's weak"
    # #3 — the 2.06×-vs-CPU-loop figure had no regression envelope).
    # Measured 2026-08-01: ~116 s warm with all 1024 members optimal;
    # 240 s = ~2× headroom.
    _log("[scale 3/4] batched 1024x(128,512) vmap solve (envelope: "
         "1024/1024 optimal, solve<=240s warm)")
    from distributedlpsolver_tpu.backends.batched import solve_batched
    from distributedlpsolver_tpu.models.generators import random_batched_lp

    batch = random_batched_lp(1024, 128, 512, seed=0)
    solve_batched(batch, max_iter=3)  # compile warm-up (full-size programs)
    # One full untimed solve: the final-phase compaction programs
    # (256→…→32) only compile once actives drain — see _bench_batched.
    solve_batched(batch)
    r3 = solve_batched(batch)
    r3b = solve_batched(batch)
    if r3b.solve_time < r3.solve_time:
        r3 = r3b
    row3 = {
        "check": "batched_1024x128x512",
        "optimal": int(r3.n_optimal),
        "problems": len(r3.status),
        "time_s": round(r3.solve_time, 3),
        "envelope": {"n_optimal": 1024, "time_s_max": 240.0},
        "pass": bool(r3.n_optimal == 1024 and r3.solve_time <= 240.0),
    }
    rows.append(row3)
    _log(json.dumps(row3))

    # 4. storm-20k headline config (scripts/run_storm20k.py, review
    # round 2 item 4): hint-less ≥20k-row block-angular — detection must
    # recover K=256 and the Schur path must stay in its measured class
    # (6.3–10.2 s observed; 30 s = ~3× headroom).
    _log("[scale 4/4] storm-20k hint-less detect→Schur (envelope: optimal, "
         "K=256 detected, solve<=30s warm)")
    from distributedlpsolver_tpu.models.generators import block_angular_lp
    from distributedlpsolver_tpu.models.structure import detect_block_structure

    p4 = block_angular_lp(256, 80, 160, 48, seed=3, sparse=True, density=0.08)
    p4.block_structure = None  # what a real file looks like
    hint = detect_block_structure(p4)
    detected = int(hint["num_blocks"]) if hint else 0
    row4 = {
        "check": "storm20k_block_angular",
        "detected_blocks": detected,
        "envelope": {"status": "optimal", "detected_blocks": 256,
                     "time_s_max": 30.0},
    }
    if hint is None:
        row4.update(status="detection_declined")
        row4["pass"] = False
    else:
        p4.block_structure = hint
        _solve(p4, "block", max_iter=3)  # compile warm-up
        r4 = _solve(p4, "block")
        r4b = _solve(p4, "block")
        if r4b.solve_time < r4.solve_time:
            r4 = r4b
        row4.update(
            status=r4.status.value,
            time_s=round(r4.solve_time, 3),
            iters=int(r4.iterations),
            rel_gap=float(r4.rel_gap),
        )
        row4["pass"] = bool(
            r4.status.value == "optimal"
            and detected == 256
            and r4.solve_time <= 30.0
        )
    rows.append(row4)
    _log(json.dumps(row4))
    return rows


def _bench_sparse(args) -> list:
    """Huge-sparse tier rows (``--sparse``): the SAME storm-profile
    instance through each engine of the tier so the win is attributable —
    matrix-free inexact IPM (PCG normal equations, 1e-8), restarted PDHG
    (matrix-free first-order, its 1e-4 tier), and the dense baseline
    (only where the dense assembly fits — at storm scale the row records
    WHY it is absent instead of silently shrinking the instance).
    Columns include density/nnz/cg_iters/precond so BENCH_SPARSE.json
    tracks preconditioner quality over rounds, not just wall clock."""
    from distributedlpsolver_tpu.backends.base import get_backend
    from distributedlpsolver_tpu.models.generators import storm_sparse_lp

    K = 32 if args.quick else 320
    p = storm_sparse_lp(K, 64, 96, 64, seed=1)
    m, n = p.A.shape
    nnz = int(p.A.nnz)
    base = {
        "family": "sparse",
        "instance": p.name,
        "m": m,
        "n": n,
        "nnz": nnz,
        "density": round(nnz / (m * n), 6),
    }
    rows = []

    def add(row):
        row["platform"] = args.platform
        rows.append(row)
        _log(json.dumps(row))

    # 1. matrix-free inexact IPM at full tolerance.
    be = get_backend("sparse-iterative")
    r = _solve(p, be, tol=1e-8, max_iter=200)
    rep = be.cg_report()
    add(
        dict(
            base,
            engine="sparse-iterative",
            tol=1e-8,
            status=r.status.value,
            iters=int(r.iterations),
            cg_iters=int(rep["cg_iters"]),
            precond=rep["precond"],
            time_s=round(r.solve_time, 4),
            setup_s=round(r.setup_time, 4),
            max_operand_mb=round(be.max_operand_nbytes() / 1e6, 2),
        )
    )

    # 2. restarted PDHG at its tolerance tier (matrix-free first-order).
    r = _solve(p, "pdlp", tol=1e-4)
    add(
        dict(
            base,
            engine="pdhg",
            tol=1e-4,
            status=r.status.value,
            iters=int(r.iterations),
            time_s=round(r.solve_time, 4),
            setup_s=round(r.setup_time, 4),
        )
    )

    # 3. dense baseline on the SAME instance — only while the dense
    # assembly fits (~256 MB f64); past that the row says so explicitly.
    if m * n <= 1 << 25:
        r = _solve(p, "cpu-native", tol=1e-8)
        add(
            dict(
                base,
                engine="dense(cpu-native)",
                tol=1e-8,
                status=r.status.value,
                iters=int(r.iterations),
                time_s=round(r.solve_time, 4),
                setup_s=round(r.setup_time, 4),
            )
        )
    else:
        add(
            dict(
                base,
                engine="dense(cpu-native)",
                tol=1e-8,
                status="skipped",
                skip_reason=(
                    f"dense assembly would be {m * n * 8 / 1e9:.1f} GB "
                    "(the arena this tier exists to avoid)"
                ),
            )
        )

    # 4. Distributed row family (row-sharded matrix-free tier): the
    # SAME storm-profile instance on 1 device vs every N-way row mesh
    # this host can form. Per-device max live operand bytes is THE
    # column (the ≈1/N law the tier exists for); psum_per_iter makes
    # the communication cost explicit — one n-vector all-reduce per CG
    # iteration, regardless of N.
    import jax as _jax

    from distributedlpsolver_tpu.backends.sparse_iterative import (
        SparseIterativeBackend,
    )
    from distributedlpsolver_tpu.parallel import mesh as mesh_lib

    Kd = 8 if args.quick else 64
    pd_spec = dict(
        scenarios=Kd, block_m=32, block_n=48, first_stage_n=24, seed=3
    )

    def _pd():
        return storm_sparse_lp(
            Kd, block_m=32, block_n=48, first_stage_n=24, seed=3
        )

    md, nd = _pd().A.shape
    dbase = {"family": "sparse-distributed", "instance": _pd().name,
             "m": md, "n": nd}
    ndev = len(_jax.devices())
    for width in [1] + [w for w in (2, 4, 8) if w <= ndev]:
        if width == 1:
            be = SparseIterativeBackend()
        else:
            mesh = mesh_lib.make_mesh(
                (width,),
                axis_names=("batch",),
                devices=_jax.devices()[:width],
            )
            be = SparseIterativeBackend(mesh=mesh)
        try:
            r = _solve(_pd(), be, tol=1e-8, max_iter=200)
            rep = be.cg_report()
            add(
                dict(
                    dbase,
                    engine="sparse-iterative",
                    devices=width,
                    shards=int(rep["shards"]),
                    psum_per_iter=int(rep["psum_per_iter"]),
                    tol=1e-8,
                    status=r.status.value,
                    iters=int(r.iterations),
                    cg_iters=int(rep["cg_iters"]),
                    precond=rep["precond"],
                    time_s=round(r.solve_time, 4),
                    max_operand_mb=round(be.max_operand_nbytes() / 1e6, 3),
                    max_operand_per_device_mb=round(
                        be.max_operand_nbytes(per_device=True) / 1e6, 3
                    ),
                )
            )
        except Exception as e:
            add(
                dict(
                    dbase,
                    engine="sparse-iterative",
                    devices=width,
                    status="failed",
                    error=f"{type(e).__name__}: {str(e)[:200]}",
                )
            )

    # 4b. 2-process world through the launcher (the multi-host seam):
    # same instance, row shards spanning a process boundary. Best-effort
    # — the CPU harness transport is lossy by design; a failed world is
    # recorded, not fatal.
    try:
        import tempfile

        from distributedlpsolver_tpu.distributed.launcher import run_world

        with tempfile.TemporaryDirectory(prefix="bench-sprows-") as wd:
            res = run_world(
                "sparse_rows",
                dict(pd_spec, tol=1e-8),
                world_size=2,
                workdir=wd,
                local_devices=2,
                timeout=600,
            )
        out0 = res[0]
        add(
            dict(
                dbase,
                engine="sparse-iterative",
                devices="2proc x 2dev",
                shards=int(out0["shards"]),
                psum_per_iter=int(out0["psum_per_iter"]),
                tol=1e-8,
                status=out0["status"],
                iters=int(out0["iterations"]),
                cg_iters=int(out0["cg_iters"]),
                precond=out0["precond"],
                max_operand_per_device_mb=round(
                    out0["max_operand_per_device"] / 1e6, 3
                ),
                ranks_agree=len(
                    {o["objective"] for o in res.values()}
                ) == 1,
            )
        )
    except Exception as e:
        add(
            dict(
                dbase,
                engine="sparse-iterative",
                devices="2proc x 2dev",
                status="failed",
                error=f"{type(e).__name__}: {str(e)[:200]}",
            )
        )

    # 5. ILDL-vs-Jacobi on the unstructured endgame (the instance that
    # used to degrade to cpu-sparse): jacobi's honest failure next to
    # auto's mid-solve escalation to incomplete-LDLᵀ, cg_iters side by
    # side so the preconditioner win is attributable over rounds.
    from distributedlpsolver_tpu.models.generators import netlib_sparse_lp

    pu = netlib_sparse_lp(120, 220, seed=10)
    ubase = {
        "family": "sparse-ildl",
        "instance": pu.name,
        "m": int(pu.A.shape[0]),
        "n": int(pu.A.shape[1]),
    }
    ildl_pair = {}
    for label, kw in (("jacobi", {"precond": "jacobi"}), ("auto", {})):
        be = SparseIterativeBackend(**kw)
        try:
            r = _solve(
                netlib_sparse_lp(120, 220, seed=10), be, tol=1e-8,
                _retries=0,
            )
            rep = be.cg_report()
            row = dict(
                ubase,
                engine=f"sparse-iterative({label})",
                tol=1e-8,
                status=r.status.value,
                iters=int(r.iterations),
                cg_iters=int(rep["cg_iters"]),
                precond=rep["precond"],
                time_s=round(r.solve_time, 4),
            )
            ildl_pair[label] = row
            add(row)
        except Exception as e:
            row = dict(
                ubase,
                engine=f"sparse-iterative({label})",
                status="failed",
                error=f"{type(e).__name__}: {str(e)[:200]}",
            )
            ildl_pair[label] = row
            add(row)
    j, a = ildl_pair.get("jacobi", {}), ildl_pair.get("auto", {})

    def _cg_rate(row):
        if row.get("cg_iters") and row.get("iters"):
            return round(row["cg_iters"] / row["iters"], 1)
        return None

    jr, ar = _cg_rate(j), _cg_rate(a)
    add(
        dict(
            ubase,
            engine="ildl-vs-jacobi",
            jacobi_status=j.get("status"),
            jacobi_cg_iters=j.get("cg_iters"),
            jacobi_cg_per_ipm_iter=jr,
            ildl_status=a.get("status"),
            ildl_cg_iters=a.get("cg_iters"),
            ildl_cg_per_ipm_iter=ar,
            ildl_engaged=a.get("precond") == "ildl",
            # The win: ildl finishes where jacobi faulted, at a strictly
            # lower CG cost per IPM iteration (totals are not comparable
            # — jacobi died early, ildl ran the full endgame).
            ildl_wins=bool(
                a.get("status") == "optimal"
                and (j.get("status") != "optimal" or (jr or 0) > (ar or 0))
                and (jr is None or ar is None or ar < jr)
            ),
        )
    )
    return rows


def _bench_scenario(args) -> list:
    """Stochastic scenario tier rows (``--scenario``): the SAME
    two-stage storm instance through each engine that can hold it —
    the scenario-decomposed IPM (batched per-scenario Schur + arrow
    linking solve), the sparse-iterative rung on the lowered
    block-angular form (the degradation target, bordered-Woodbury
    preconditioner), and the dense baseline on the lowered form where
    its assembly fits. Columns carry K, the schur/link wall split, and
    peak operand bytes so BENCH_SCENARIO.json tracks how the
    decomposition scales in K across rounds."""
    from distributedlpsolver_tpu.backends import scenario as scn
    from distributedlpsolver_tpu.backends.base import get_backend
    from distributedlpsolver_tpu.models.problem import to_interior_form
    from distributedlpsolver_tpu.models.scenario import (
        scenario_k_bucket,
        two_stage_storm,
    )

    K = 16 if args.quick else 128
    slp = two_stage_storm(
        K, block_m=24, block_n=36, first_stage_n=24, first_stage_m=8,
        seed=1,
    )
    lowered = slp.to_block_angular()
    m, n = lowered.A.shape
    base = {
        "family": "scenario",
        "instance": slp.name,
        "K": K,
        "scenario_bucket": scenario_k_bucket(K),
        "m": m,
        "n": n,
        "nnz": int(lowered.A.nnz),
    }
    rows = []

    def add(row):
        row["platform"] = args.platform
        rows.append(row)
        _log(json.dumps(row))

    # 1. Scenario-decomposed IPM (warm-up first so the timed figure is
    # the warm-program number every later solve in the bucket pays).
    be = get_backend("scenario")
    r = _solve(lowered, be, tol=1e-8)
    rep = scn.last_solve_report()
    add(
        dict(
            base,
            engine="scenario",
            tol=1e-8,
            status=r.status.value,
            iters=int(r.iterations),
            time_s=round(r.solve_time, 4),
            setup_s=round(r.setup_time, 4),
            schur_ms=round(float(rep.get("schur_ms", 0.0)), 3),
            link_ms=round(float(rep.get("link_ms", 0.0)), 3),
            cg_iters=int(rep.get("cg_iters", 0)),
            max_operand_mb=round(be.operand_nbytes() / 1e6, 2),
        )
    )

    # 2. Lowered block-angular form through the matrix-free inexact IPM
    # (the degradation rung; its bordered preconditioner consumes the
    # same two_stage pattern).
    be_si = get_backend("sparse-iterative")
    r = _solve(lowered, be_si, tol=1e-8, max_iter=200)
    rep_si = be_si.cg_report()
    add(
        dict(
            base,
            engine="sparse-iterative(lowered)",
            tol=1e-8,
            status=r.status.value,
            iters=int(r.iterations),
            cg_iters=int(rep_si["cg_iters"]),
            precond=rep_si["precond"],
            time_s=round(r.solve_time, 4),
            setup_s=round(r.setup_time, 4),
            max_operand_mb=round(be_si.max_operand_nbytes() / 1e6, 2),
        )
    )

    # 3. Dense baseline on the lowered form — only while the assembly
    # fits; past that the row records WHY it is absent.
    if m * n <= 1 << 25:
        low2 = slp.to_block_angular()
        low2.block_structure = None  # keep it off the scenario route
        r = _solve(low2, "cpu-native", tol=1e-8)
        add(
            dict(
                base,
                engine="dense(cpu-native,lowered)",
                tol=1e-8,
                status=r.status.value,
                iters=int(r.iterations),
                time_s=round(r.solve_time, 4),
                setup_s=round(r.setup_time, 4),
                max_operand_mb=round(m * m * 8 / 1e6, 2),
            )
        )
    else:
        add(
            dict(
                base,
                engine="dense(cpu-native,lowered)",
                tol=1e-8,
                status="skipped",
                skip_reason=(
                    f"dense normal-equations assembly would be "
                    f"{m * m * 8 / 1e9:.1f} GB at m={m}"
                ),
            )
        )
    return rows


def _bench_multihost(args) -> list:
    """Multi-host harness rows (``--multihost``): the SAME storm-class
    instance solved by the sharded backend in 1-process and N-process
    `jax.distributed` worlds (distributed/launcher — each world spawned
    fresh, 2 virtual CPU devices per process on the harness, ICI/DCN on
    a pod). Wall time is the slowest rank's in-process solve wall (the
    SPMD program finishes in lockstep; process spawn/import is reported
    separately as launch overhead). CPU-harness figures measure the
    cross-process dataflow, not TPU speed — the TPU-pod measurement is
    the ROADMAP follow-on. The ranks are CPU processes (the launcher is a
    CPU harness), so the rows are stamped ``"platform": "cpu"``."""
    import tempfile

    from distributedlpsolver_tpu.distributed.launcher import run_world

    K = 8 if args.quick else 24
    spec = {
        "instance": "storm",
        "scenarios": K,
        "block_m": 24,
        "block_n": 36,
        "first_stage_n": 24,
        "seed": 1,
        "tol": 1e-8,
    }
    m = K * 24
    n = 24 + K * 36
    worlds = [1, 2] if args.quick else [1, 2, 4]
    rows = []
    base_wall = None
    for ws in worlds:
        workdir = tempfile.mkdtemp(prefix=f"dlps-bench-mh-{ws}-")
        t0 = time.perf_counter()
        res = run_world(
            "sharded_solve", spec, world_size=ws, workdir=workdir,
            local_devices=2, timeout=600,
        )
        launch_wall = time.perf_counter() - t0
        solve_wall = max(r["wall_s"] for r in res.values())
        objs = sorted(r["objective"] for r in res.values())
        statuses = {r["status"] for r in res.values()}
        if ws == 1:
            base_wall = solve_wall
        row = {
            "family": "multihost",
            "instance": f"storm K={K} ({m}x{n})",
            "m": m,
            "n": n,
            "world_size": ws,
            "global_devices": 2 * ws,
            "status": sorted(statuses)[0] if len(statuses) == 1 else "mixed",
            "iters": int(next(iter(res.values()))["iterations"]),
            "solve_wall_s": round(solve_wall, 3),
            "launch_wall_s": round(launch_wall, 3),
            "objective_spread": round(objs[-1] - objs[0], 12),
            "speedup_vs_1proc": (
                round(base_wall / solve_wall, 3) if base_wall else None
            ),
            "platform": "cpu",
        }
        rows.append(row)
        _log(json.dumps(row))
    return rows


def _bench_elastic(args) -> list:
    """Closed-loop elasticity rows (``--elastic``): a deterministic
    LoadRamp over a LIVE plane — one router over the shared registry, a
    backend pool owned by an in-process ElasticController — measuring
    what the closed loop buys and costs: sync p50/p99 before / during /
    after the ramp, the pool-size trajectory, scale-out lead times
    (signal observed -> backend serving), the brownout ladder's engaged
    window and shed count, and graceful scale-in on release. Shapes are
    calibrated so one CPU backend genuinely saturates under the peak
    (~32 rps capacity at batch 4 vs the 48 rps peak) — the overload is
    real, not simulated. CPU-harness figures measure the control loop
    and serving plane, not TPU speed."""
    import shutil
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from distributedlpsolver_tpu.net.chaos import ChaosPlane, LoadRamp
    from distributedlpsolver_tpu.obs.stats import percentile
    from distributedlpsolver_tpu.serve.elastic import (
        ElasticConfig,
        ElasticController,
    )

    shape = (96, 288)
    n_ramp = 120 if args.quick else 240

    def post(url, body=None, timeout=60.0):
        req = urllib.request.Request(
            url,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"} if body else {},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                return e.code, json.loads(e.read())
            except Exception:
                return e.code, {}
        except (urllib.error.URLError, OSError, ValueError) as e:
            return 599, {"error": f"{type(e).__name__}: {e}"}

    workdir = tempfile.mkdtemp(prefix="dlps-bench-elastic-")
    plane = ChaosPlane(workdir)
    registry_path = os.path.join(workdir, "registry.json")
    buckets_json = os.path.join(workdir, "ladder.json")
    with open(buckets_json, "w") as fh:
        fh.write(json.dumps([{"m": shape[0], "n": shape[1], "batch": 4}]))
    brownout = {
        "depth_high": 0.5, "depth_low": 0.125, "reject_rate_high": 1.0,
        "engage_after_s": 0.2, "escalate_after_s": 0.4,
        "release_after_s": 0.5, "retry_after_s": 0.05,
    }
    ctl = ElasticController(ElasticConfig(
        registry_path=registry_path,
        min_backends=1,
        max_backends=3,
        poll_s=0.2,
        load_high=6.0,
        reject_rate_high=0.5,
        out_sustain_s=0.4,
        load_low=1.0,
        in_sustain_s=2.0,
        cooldown_s=1.0,
        flap_window_s=60.0,
        flap_max_actions=24,
        workdir=workdir,
        buckets_json=buckets_json,
        backend_flags=(
            "--flush-ms", "20", "--batch", "4", "--queue-depth", "16",
            "--brownout", json.dumps(brownout, separators=(",", ":")),
            "--quiet",
        ),
        heartbeat_s=0.25,
    ))
    try:
        t0 = time.perf_counter()
        ctl.start()
        if ctl.pool_size() < 1:
            raise RuntimeError("elastic bench: min pool never came up")
        router = plane.spawn_router("bench-router", [], registry_path)
        if not plane.wait_ready(router, 60):
            raise RuntimeError("elastic bench: router never came up")
        adopt_deadline = time.perf_counter() + 30.0
        while time.perf_counter() < adopt_deadline:
            c, o = post(router.url + "/statusz", timeout=5.0)
            if c == 200 and any(
                b.get("healthy") for b in o.get("backends", [])
            ):
                break
            time.sleep(0.2)
        else:
            raise RuntimeError("elastic bench: router never adopted pool")
        _log(
            f"elastic plane up in {time.perf_counter() - t0:.1f}s "
            f"(pool {ctl.pool_size()}, router {router.url})"
        )

        def sync_wave(n, tag, gap_fn):
            """Fire n sync solves (one thread each, gap_fn(k)-paced) and
            return per-request submit->verdict walls in ms. 429s retry
            after the structured hint — the retry wait is PART of the
            measured latency, which is the point: brownout converts
            overload into bounded extra latency, not lost work."""
            lat, lock = [], threading.Lock()

            def drive(k):
                t = time.perf_counter()
                deadline = t + 120.0
                while True:
                    c, o = post(
                        router.url + "/v1/solve",
                        {"m": shape[0], "n": shape[1], "seed": k,
                         "tenant": "bench", "id": f"{tag}-{k}"},
                    )
                    if c == 429:
                        time.sleep(min(
                            float(o.get("retry_after_s", 0.05) or 0.05), 1.0
                        ))
                    elif c in (502, 503, 599):
                        if time.perf_counter() > deadline:
                            return
                        time.sleep(0.05)
                    else:
                        break
                if c == 200 and o.get("status") == "optimal":
                    with lock:
                        lat.append((time.perf_counter() - t) * 1e3)

            ws = []
            for k in range(n):
                w = threading.Thread(target=drive, args=(k,), daemon=True)
                w.start()
                ws.append(w)
                time.sleep(gap_fn(k))
            for w in ws:
                w.join(timeout=180)
            return lat

        # Phase 1 — base: trickle load on the min pool (steady-state
        # latency floor the ramp phases are compared against).
        n_base = 8 if args.quick else 12
        lat_base = sync_wave(n_base, "base", lambda k: 0.5)

        # Phase 2 — ramp: LoadRamp to a saturating peak; a monitor
        # samples pool size and the max brownout stage across backends.
        ramp = LoadRamp(n_ramp, peak_rps=48.0, base_rps=3.0)
        done = threading.Event()
        pool_peak = [ctl.pool_size()]
        brownout_samples = []  # (t_rel_s, max stage across the pool)
        t_ramp = time.perf_counter()

        def monitor():
            while not done.is_set():
                pool_peak[0] = max(pool_peak[0], ctl.pool_size())
                stage = 0
                for m in ctl.statusz()["pool"]:
                    c, o = post(m["url"] + "/statusz", timeout=2.0)
                    if c == 200:
                        bo = (o.get("stats") or {}).get("brownout") or {}
                        stage = max(stage, int(bo.get("stage", 0) or 0))
                brownout_samples.append(
                    (time.perf_counter() - t_ramp, stage)
                )
                done.wait(0.1)

        mon = threading.Thread(target=monitor, daemon=True)
        mon.start()
        n_actions_pre = len(ctl.actions())
        lat_ramp = sync_wave(n_ramp, "ramp", ramp.gap_s)
        ramp_wall = time.perf_counter() - t_ramp
        done.set()
        mon.join(timeout=30)

        # Phase 3 — settle: wait for the drain back to min_backends,
        # then re-measure the trickle (did release restore the floor?).
        t_in = time.perf_counter()
        while time.perf_counter() - t_in < 120.0:
            if ctl.pool_size() <= ctl.config.min_backends:
                break
            time.sleep(0.3)
        scale_in_wall = time.perf_counter() - t_in
        lat_settle = sync_wave(n_base, "settle", lambda k: 0.5)

        actions = ctl.actions()[n_actions_pre:]
        outs = [a for a in actions if a["event"] == "scale_out"]
        ins = [a for a in actions if a["event"] == "scale_in"]
        engaged = [t for t, s in brownout_samples if s >= 1]
        hist = ctl.history()
        rows = []
        for phase, lat, extra in (
            ("base", lat_base, {"n": n_base, "pool": 1}),
            ("ramp", lat_ramp, {
                "n": n_ramp,
                "wall_s": round(ramp_wall, 3),
                "peak_rps": 48.0,
                "pool_peak": pool_peak[0],
                "scale_outs": len(outs),
                "scale_out_lead_ms": (
                    [round(a["ms"]) for a in outs] or None
                ),
                "brownout_stage_peak": max(
                    (s for _, s in brownout_samples), default=0
                ),
                "brownout_engaged_s": round(
                    max(engaged) - min(engaged), 3
                ) if engaged else 0.0,
            }),
            ("settle", lat_settle, {
                "n": n_base,
                "pool": ctl.pool_size(),
                "scale_ins": len(ins),
                "drained": sum(bool(a.get("drained")) for a in ins),
                "scale_in_wall_s": round(scale_in_wall, 3),
            }),
        ):
            row = {
                "family": "elastic",
                "phase": phase,
                "instance": f"dense {shape[0]}x{shape[1]} batch=4",
                "completed": len(lat),
                "latency_ms_p50": (
                    round(percentile(lat, 50), 3) if lat else None
                ),
                "latency_ms_p99": (
                    round(percentile(lat, 99), 3) if lat else None
                ),
                "platform": args.platform,
                **extra,
            }
            rows.append(row)
            _log(json.dumps(row))
        # The trajectory rides the ramp row (it IS the ramp's story);
        # sampled at the controller's own control cycle.
        rows[1]["pool_trajectory"] = [
            [round(t, 2), n] for t, n in hist
        ]
        return rows
    finally:
        ctl.shutdown(drain=False)
        plane.shutdown_all()
        shutil.rmtree(workdir, ignore_errors=True)


def _bench_tail(args) -> list:
    """Tail-tolerance rows (``--tail``): one sync wave through a LIVE
    3-backend plane with one backend SIGSTOPped mid-wave, measured with
    hedging OFF and then ON — what adaptive hedging buys on the p99
    when a straggler appears, against the same healthy-floor wave. OFF
    rows censor straggler-stuck requests at the client timeout (the
    honest rendering of "this request would have waited out the full
    forward timeout"); ON rows carry the router's hedging ledger. CPU
    harness: these rows measure the routing plane, not TPU speed."""
    import shutil
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from distributedlpsolver_tpu.net.chaos import ChaosPlane
    from distributedlpsolver_tpu.obs.stats import percentile

    shape = (96, 288)
    n_wave = 16 if args.quick else 24
    cap_s = 15.0  # censor bound for straggler-stuck requests (OFF mode)

    def post(url, body=None, timeout=60.0):
        req = urllib.request.Request(
            url,
            data=None if body is None else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"} if body else {},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                return e.code, json.loads(e.read())
            except Exception:
                return e.code, {}
        except (urllib.error.URLError, OSError, ValueError) as e:
            return 599, {"error": f"{type(e).__name__}: {e}"}

    workdir = tempfile.mkdtemp(prefix="dlps-bench-tail-")
    plane = ChaosPlane(workdir)
    buckets_json = os.path.join(workdir, "ladder.json")
    with open(buckets_json, "w") as fh:
        fh.write(json.dumps([{"m": shape[0], "n": shape[1], "batch": 4}]))
    try:
        t0 = time.perf_counter()
        names = ["tail-be-a", "tail-be-b", "tail-be-c"]
        for name in names:
            plane.spawn_backend(
                name,
                buckets_json=buckets_json,
                extra_flags=["--flush-ms", "20", "--batch", "4"],
            )
        for name in names:
            if not plane.wait_ready(plane.procs[name], 180):
                raise RuntimeError(f"tail bench: {name} never came up")
        _log(f"tail plane up in {time.perf_counter() - t0:.1f}s")
        victim = names[-1]
        rows = []
        for mode in ("off", "on"):
            rname = f"tail-router-{mode}"
            router = plane.spawn_router(
                rname,
                [plane.procs[n].url for n in names],
                os.path.join(workdir, f"registry-{mode}.json"),
                extra_flags=(
                    ["--hedge", "--hedge-rate-cap", "0.5",
                     "--retry-budget", "50", "--retry-budget-burst", "50"]
                    if mode == "on"
                    else ["--no-hedge"]
                ),
            )
            if not plane.wait_ready(router, 60):
                raise RuntimeError(f"tail bench: {rname} never came up")

            def fire(n, base, timeout_s):
                """n near-simultaneous sync solves; returns (walls_ms,
                censored_count) with client-timeout walls censored at
                the cap instead of dropped."""
                walls, censored, lock = [], [0], threading.Lock()

                def drive(k):
                    t = time.perf_counter()
                    c, o = post(
                        router.url + "/v1/solve",
                        {"m": shape[0], "n": shape[1], "seed": base + k,
                         "tenant": "bench", "id": f"tail-{mode}-{base + k}"},
                        timeout=timeout_s,
                    )
                    wall = (time.perf_counter() - t) * 1e3
                    with lock:
                        if c == 599:
                            censored[0] += 1
                            walls.append(timeout_s * 1e3)
                        else:
                            walls.append(wall)

                ws = []
                for k in range(n):
                    w = threading.Thread(target=drive, args=(k,), daemon=True)
                    w.start()
                    ws.append(w)
                    time.sleep(0.02)
                for w in ws:
                    w.join(timeout=timeout_s + 30)
                return walls, censored[0]

            # Warm until every backend's digest is warm (ON mode needs
            # >= hedge_min_samples; OFF gets the same treatment so the
            # healthy floors are comparable).
            sent = 0
            while sent < 120:
                fire(6, 1000 + sent, 90.0)
                sent += 6
                c, o = post(router.url + "/statusz", timeout=5.0)
                fwd = [
                    b.get("forwards", 0) for b in o.get("backends", [])
                ]
                if c == 200 and fwd and min(fwd) >= 10:
                    break
            healthy, _ = fire(n_wave, 2000, 90.0)
            plane.sigstop(victim)
            straggler, censored = fire(n_wave, 3000, cap_s)
            plane.sigcont(victim)
            plane.wait_ready(plane.procs[victim], 60)
            c, o = post(router.url + "/statusz", timeout=5.0)
            row = {
                "family": "tail",
                "phase": f"hedge_{mode}",
                "instance": f"dense {shape[0]}x{shape[1]} batch=4",
                "n": n_wave,
                "healthy_ms_p50": round(percentile(healthy, 50), 3),
                "healthy_ms_p99": round(percentile(healthy, 99), 3),
                "latency_ms_p50": round(percentile(straggler, 50), 3),
                "latency_ms_p99": round(percentile(straggler, 99), 3),
                "censored_at_ms": cap_s * 1e3,
                "censored": censored,
                "platform": args.platform,
            }
            if mode == "on" and c == 200:
                row["hedging"] = o.get("hedging")
            rows.append(row)
            _log(json.dumps(row))
            # This mode's router is done; the stuck OFF-mode legs died
            # with it rather than lingering into the ON measurement.
            plane.kill9(rname)
        return rows
    finally:
        plane.shutdown_all()
        shutil.rmtree(workdir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small shapes (smoke)")
    ap.add_argument("--suite", action="store_true", help="all five reference configs")
    ap.add_argument("--full", action="store_true", help="reference-scale shapes")
    ap.add_argument("--scale", action="store_true",
                    help="pass/fail scale-regression tier -> SCALE_CHECK.json")
    ap.add_argument("--serve", action="store_true",
                    help="serving-throughput row only (rps, p50/p99, "
                    "padding waste, warm recompiles) as the stdout JSON line")
    ap.add_argument("--sparse", action="store_true",
                    help="huge-sparse tier rows (sparse-iterative vs "
                    "PDHG vs dense on one storm-profile instance; "
                    "density/nnz/cg_iters columns) -> BENCH_SPARSE.json")
    ap.add_argument("--scenario", action="store_true",
                    help="stochastic scenario tier rows (scenario-"
                    "decomposed IPM vs lowered block-angular vs sparse-"
                    "iterative on one two-stage storm instance; K + "
                    "schur/link split + peak operand bytes) -> "
                    "BENCH_SCENARIO.json")
    ap.add_argument("--multihost", action="store_true",
                    help="multi-host harness rows: the storm instance "
                    "through 1 vs N jax.distributed processes "
                    "(sharded backend, CPU harness) -> BENCH_MULTIHOST.json")
    ap.add_argument("--elastic", action="store_true",
                    help="closed-loop elasticity rows: sync p50/p99 "
                    "before/during/after a saturating LoadRamp over a "
                    "live router + ElasticController pool, with the "
                    "pool trajectory, scale-out lead times, and the "
                    "brownout engaged window -> BENCH_ELASTIC.json")
    ap.add_argument("--tail", action="store_true",
                    help="tail-tolerance rows: p50/p99 of a sync wave "
                    "over a live 3-backend plane with one backend "
                    "SIGSTOPped mid-wave, hedging off vs on (the "
                    "hedging ledger rides the on row) -> BENCH_TAIL.json")
    ap.add_argument("--obs", action="store_true",
                    help="tracing-overhead A/B rows: the steady-state "
                    "serve shape with the distributed-tracing layer off "
                    "vs on (per-request contexts + live Chrome tracer), "
                    "pinning p50/p99 overhead and the zero-warm-"
                    "recompile invariant -> BENCH_OBS.json")
    ap.add_argument("--serve-http", action="store_true",
                    help="serving rows incl. the HTTP network plane: the "
                    "in-process row plus a localhost POST /v1/solve row, "
                    "so network overhead is attributed (README 'Network "
                    "serving')")
    # "tpu" (the north-star backend name, BASELINE.json:5) — the dense
    # two-phase path, which measures fastest on the headline config
    # (0.72 s vs 0.90 s via the Schur backend, whose per-iteration flop
    # advantage is below the dispatch-latency floor at this size but pays
    # 7 extra iterations). Pass --backend auto for structure-aware routing.
    ap.add_argument("--backend", default="tpu")
    ap.add_argument("--baseline-backend", default="cpu-native")
    ap.add_argument("--mps", default=None, help="bench this MPS file instead")
    args = ap.parse_args()
    if args.mps and not os.path.exists(args.mps):
        ap.error(f"--mps {args.mps!r}: file not found")  # before any solve

    from distributedlpsolver_tpu.backends import available_backends

    if args.backend not in available_backends():
        ap.error(
            f"--backend {args.backend!r} is not one of "
            f"{sorted(available_backends())}"
        )
    backend = args.backend

    # No accelerator is an error, never a switch to the CPU: a figure
    # measured on the wrong platform is worse than none.
    from distributedlpsolver_tpu.utils.accel import require_tpu

    require_tpu()
    import jax

    _log(f"devices: {jax.devices()}")
    args.platform = jax.default_backend()

    _obs_enable()

    if args.multihost:
        rows = _bench_multihost(args)
        for r in rows:
            r.setdefault("metrics", _obs_row(args.platform))
        out = os.path.join(_REPO, "BENCH_MULTIHOST.json")
        with open(out, "w") as fh:
            json.dump(rows, fh, indent=2)
        _log(f"multihost rows -> {out}")
        print(json.dumps(rows[-1]))  # headline: the widest world's row
        return 0  # multihost tier is its own run; no headline solve after

    if args.elastic:
        rows = _bench_elastic(args)
        for r in rows:
            r.setdefault("metrics", _obs_row(args.platform))
        out = os.path.join(_REPO, "BENCH_ELASTIC.json")
        with open(out, "w") as fh:
            json.dump(rows, fh, indent=2)
        _log(f"elastic rows -> {out}")
        print(json.dumps(rows[1]))  # headline: the ramp row
        return 0  # elasticity tier is its own run; no headline solve after

    if args.tail:
        rows = _bench_tail(args)
        for r in rows:
            r.setdefault("metrics", _obs_row(args.platform))
        out = os.path.join(_REPO, "BENCH_TAIL.json")
        with open(out, "w") as fh:
            json.dump(rows, fh, indent=2)
        _log(f"tail rows -> {out}")
        print(json.dumps(rows[-1]))  # headline: the hedging-on row
        return 0  # tail tier is its own run; no headline solve after

    if args.obs:
        rows = _bench_obs(args)
        for r in rows:
            r.setdefault("platform", args.platform)
            r.setdefault("metrics", _obs_row(args.platform))
        out = os.path.join(_REPO, "BENCH_OBS.json")
        with open(out, "w") as fh:
            json.dump(rows, fh, indent=2)
        _log(f"obs rows -> {out}")
        print(json.dumps(rows[-1]))  # headline: the tracing-on row
        return 0  # obs tier is its own run; no headline solve after

    if args.scenario:
        rows = _bench_scenario(args)
        for r in rows:
            r.setdefault("metrics", _obs_row(args.platform))
        out = os.path.join(_REPO, "BENCH_SCENARIO.json")
        with open(out, "w") as fh:
            json.dump(rows, fh, indent=2)
        _log(f"scenario rows -> {out}")
        print(json.dumps(rows[0]))  # headline: the decomposed-IPM row
        return 0  # scenario tier is its own run; no headline solve after

    if args.sparse:
        rows = _bench_sparse(args)
        for r in rows:
            r.setdefault("metrics", _obs_row(args.platform))
        out = os.path.join(_REPO, "BENCH_SPARSE.json")
        with open(out, "w") as fh:
            json.dump(rows, fh, indent=2)
        _log(f"sparse rows -> {out}")
        print(json.dumps(rows[0]))  # headline: the matrix-free IPM row
        return 0  # sparse tier is its own run; no headline solve after

    if args.serve or args.serve_http:
        row = _bench_serve(args.quick)
        row["platform"] = args.platform
        row["metrics"] = _obs_row(args.platform)
        print(json.dumps(row))
        if args.serve_http:
            http_row = _bench_serve_http(args.quick, inproc_row=row)
            http_row["platform"] = args.platform
            print(json.dumps(http_row))
        return 0  # serve tier is its own run; no headline solve after

    if args.scale:
        rows = run_scale(args)
        for r in rows:
            r.setdefault("platform", args.platform)
            r.setdefault("metrics", _obs_row(args.platform))
        out = os.path.join(_REPO, "SCALE_CHECK.json")
        with open(out, "w") as fh:
            json.dump(rows, fh, indent=2)
        _log(f"scale-check rows -> {out}")
        failed = [r["check"] for r in rows if not r["pass"]]
        if failed:
            _log(f"SCALE CHECK FAILED: {failed}")
            return 1
        return 0  # scale tier is its own run; no headline solve after

    if args.suite:
        rows = run_suite(args)
        for r in rows:
            r.setdefault("platform", args.platform)
        out = os.path.join(_REPO, "BENCH_SUITE.json")
        with open(out, "w") as fh:
            json.dump(rows, fh, indent=2)
        _log(f"suite rows -> {out}")

    # Headline metric (always printed last, the ONE stdout JSON line).
    problem, config_name = _headline_problem(args)
    _log(f"headline: {config_name} on backend={backend}")
    row = _bench_one(problem, backend, args.baseline_backend)
    row["metrics"] = _obs_row(args.platform)

    print(
        json.dumps(
            {
                "metric": (
                    "wall-clock to 1e-8 rel duality gap, "
                    f"{config_name}, backend={backend} "
                    f"[{row['iters']} iters, {row['iters_per_sec']:.2f} it/s, "
                    f"status={row['status']}]"
                ),
                "value": row["time_s"],
                "unit": "seconds",
                "platform": args.platform,
                "vs_baseline": row["vs_baseline"],
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
