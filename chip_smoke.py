"""Bring-up check: the solver's main paths on a TPU, through the entry
points a user calls, at the sizes the repo's benchmark configs use.

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips  # sharded dense on 4 chips vs 1 chip

Phases (one process, one chip):

(a) dense 2048x10240 through ``ipm.solve(backend="auto")`` — must resolve
    to ``tpu`` and run its f32 phase through the compiled Pallas kernel;
(b) the hint-less storm-class sparse instance, solved through ``auto``
    — the ``cli solve`` route, which sends it to the matrix-free
    ``sparse-iterative`` tier — bounded to ``AUTO_MAX_ITER`` iterations
    (its Jacobi PCG stalls on this instance's linking rows: PERF.md §7),
    and then to OPTIMAL through ``block`` after structure detection;
(c) 16 served requests of 128x512 through ``SolveService`` in buckets of
    8 — the batched IPM engine, no solo fallback.

Every phase must run on a device backend with no fault records. (a), (c)
and (b)'s ``block`` solve must be OPTIMAL and match their CPU reference:
HiGHS objectives precomputed by scripts/chip_smoke_refs.py for (a)/(b)
(HiGHS takes minutes there), computed here for (c). (b)'s bounded
``auto`` solve must reach its iteration bound without a numerical
verdict; it reports its status, CG iterations and compile/solve split.
Each phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {...}}`` and is printed only when every check
passed. No TPU means a non-zero exit before any phase runs; the CPU
rehearsal of the phases is tests/test_chip_smoke.py, which calls the
phase functions directly.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
REFS_PATH = os.path.join(_REPO, "scripts", "chip_smoke_refs.json")

DENSE = {"m": 2048, "n": 10240, "seed": 2}
STORM = {"num_blocks": 256, "block_m": 80, "block_n": 160, "link_m": 48,
         "seed": 3, "density": 0.08}
SERVE = {"requests": 16, "m": 128, "n": 512, "tol": 1e-8, "batch": 8}
# Iteration bound of (b)'s ``auto`` (sparse-iterative) solve.
AUTO_MAX_ITER = 3
OBJ_RTOL = 1e-6
SERVE_OBJ_RTOL = 1e-7


def dense_key(m, n, seed):
    return f"random_dense_lp({m}, {n}, seed={seed})"


def storm_key(num_blocks, block_m, block_n, link_m, seed, density):
    return (f"block_angular_lp({num_blocks}, {block_m}, {block_n}, "
            f"{link_m}, seed={seed}, sparse=True, density={density})")


def load_refs(path=REFS_PATH) -> dict:
    with open(path) as fh:
        return json.load(fh)


def rel_err(a: float, b: float) -> float:
    return abs(a - b) / max(1.0, abs(b))


def finishing_backend(name: str) -> str:
    """The concrete backend behind a result's ``backend`` label:
    ``auto(tpu)`` → ``tpu``."""
    return name[5:-1] if name.startswith("auto(") else name


class CompileMeter:
    """JAX compile events, from ``jax.monitoring``: backend compiles (with
    their seconds) and persistent-cache hits and misses."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.hits, "cache_misses": self.misses}


def _delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _result_row(r) -> dict:
    return {
        "backend": r.backend,
        "status": r.status.value,
        "objective": float(r.objective),
        "iterations": int(r.iterations),
        "setup_s": r.setup_time,
        "solve_s": r.solve_time,
        "faults": len(r.faults),
    }


def mosaic_programs(fn):
    """Run ``fn`` while JAX writes the StableHLO of every program it
    lowers; return ``fn``'s result and how many of those programs call a
    compiled Pallas (Mosaic) kernel — a ``tpu_custom_call``, which
    neither the plain-XLA reference nor an interpreted kernel contains."""
    import shutil

    import jax

    d = tempfile.mkdtemp(prefix="chip-smoke-ir-")
    prev = jax.config.values["jax_dump_ir_to"]
    jax.config.update("jax_dump_ir_to", d)
    try:
        out = fn()
    finally:
        jax.config.update("jax_dump_ir_to", prev)
    try:
        n = 0
        for f in os.listdir(d):
            with open(os.path.join(d, f), errors="replace") as fh:
                n += "tpu_custom_call" in fh.read()
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return out, n


def phase_dense(m, n, seed, ref_obj, meter=None, tol=1e-8) -> dict:
    """(a) Dense LP through ``auto``: must finish on ``tpu``, OPTIMAL, no
    faults. Reports how many of its lowered programs hold the compiled
    Pallas assembly (``main`` requires at least one on the chip)."""
    from distributedlpsolver_tpu.ipm import Status, solve
    from distributedlpsolver_tpu.models.generators import random_dense_lp

    problem = random_dense_lp(m, n, seed=seed)
    c0 = meter.snapshot() if meter else None
    t0 = time.perf_counter()
    r, mosaic = mosaic_programs(
        lambda: solve(problem, backend="auto", tol=tol))
    wall = time.perf_counter() - t0
    row = {"phase": "a-dense", "instance": dense_key(m, n, seed),
           **_result_row(r), "wall_s": wall, "mosaic_programs": mosaic,
           "ref_objective": ref_obj,
           "rel_err": rel_err(r.objective, ref_obj)}
    if meter:
        row.update(_delta(c0, meter.snapshot()))
    row["checks"] = {
        "optimal": r.status is Status.OPTIMAL,
        "backend_tpu": finishing_backend(r.backend) == "tpu",
        "no_faults": not r.faults,
        "matches_ref": row["rel_err"] <= OBJ_RTOL,
    }
    return row


def phase_sparse(shape: dict, ref_obj, meter=None, tol=1e-8,
                 auto_max_iter=AUTO_MAX_ITER) -> dict:
    """(b) The hint-less storm-class instance, twice: through ``auto``
    (the ``cli solve`` route, which picks ``sparse-iterative``), bounded
    to ``auto_max_iter`` iterations, and through ``block`` after
    structure detection (bench.py's route), to OPTIMAL."""
    from distributedlpsolver_tpu.ipm import Status, solve
    from distributedlpsolver_tpu.models.generators import block_angular_lp
    from distributedlpsolver_tpu.models.structure import (
        detect_block_structure,
    )
    from distributedlpsolver_tpu.obs import metrics as obs_metrics

    problem = block_angular_lp(
        shape["num_blocks"], shape["block_m"], shape["block_n"],
        shape["link_m"], seed=shape["seed"], sparse=True,
        density=shape["density"],
    )
    problem.block_structure = None  # what a real MPS file looks like
    solves = {}
    for route in ("auto", "block"):
        bound = {"max_iter": auto_max_iter} if route == "auto" else {}
        if route == "block":
            problem.block_structure = detect_block_structure(problem)
        # The sparse tier counts its CG iterations in the metrics registry.
        registry = obs_metrics.MetricsRegistry()
        prev = obs_metrics.set_registry(registry)
        c0 = meter.snapshot() if meter else None
        t0 = time.perf_counter()
        try:
            r = solve(problem, backend=route, tol=tol, **bound)
        finally:
            obs_metrics.set_registry(prev)
        row = {**_result_row(r), "wall_s": time.perf_counter() - t0,
               "t_iter_s": [h.t_iter for h in r.history],
               "rel_err": rel_err(r.objective, ref_obj), **bound}
        cg = [v for k, v in registry.snapshot().items()
              if k.startswith("sparse_cg_iters_total")]
        if cg:
            row["cg_iters"] = int(sum(cg))
        if meter:
            row.update(_delta(c0, meter.snapshot()))
        solves[route] = (r, row)
    auto_r, block_r = solves["auto"][0], solves["block"][0]
    row = {"phase": "b-sparse", "instance": storm_key(**shape),
           "auto_route": finishing_backend(auto_r.backend),
           "ref_objective": ref_obj,
           "auto": solves["auto"][1], "block": solves["block"][1]}
    row["checks"] = {
        "auto_sparse_iterative":
            finishing_backend(auto_r.backend) == "sparse-iterative",
        "auto_reached_bound": auto_r.status is Status.OPTIMAL or (
            auto_r.status is Status.ITERATION_LIMIT
            and auto_r.iterations == auto_max_iter),
        "block_optimal": block_r.status is Status.OPTIMAL,
        "block_backend": block_r.backend == "block",
        "no_faults": not auto_r.faults and not block_r.faults,
        "matches_ref": solves["block"][1]["rel_err"] <= OBJ_RTOL,
    }
    return row


def highs_objective(problem) -> float:
    """HiGHS on a standard-form ``min cᵀx, Ax=b, x≥0`` problem."""
    from scipy.optimize import linprog

    res = linprog(problem.c, A_eq=problem.A, b_eq=problem.rlb,
                  bounds=(0, None), method="highs")
    if res.status != 0:
        raise RuntimeError(f"HiGHS reference failed: {res.message}")
    return float(res.fun)


def phase_serve(requests, m, n, tol, batch, meter=None) -> dict:
    """(c) Served requests through ``SolveService`` (the service behind
    ``cli serve``) in buckets of ``batch``: every request OPTIMAL on the
    batched IPM engine, no solo fallback and no fault event, objectives
    equal to HiGHS."""
    from distributedlpsolver_tpu.ipm import Status
    from distributedlpsolver_tpu.models.generators import random_dense_lp
    from distributedlpsolver_tpu.serve import ServiceConfig, SolveService

    problems = [random_dense_lp(m, n, seed=k) for k in range(requests)]
    with tempfile.TemporaryDirectory(prefix="chip-smoke-serve-") as d:
        log = os.path.join(d, "serve.jsonl")
        c0 = meter.snapshot() if meter else None
        t0 = time.perf_counter()
        with SolveService(ServiceConfig(log_jsonl=log, batch=batch)) as svc:
            futs = [svc.submit(p, tol=tol) for p in problems]
            svc.drain()
            results = [f.result() for f in futs]
        wall = time.perf_counter() - t0
        with open(log) as fh:
            events = [json.loads(line) for line in fh if line.strip()]
    refs = [highs_objective(p) for p in problems]
    errs = [rel_err(r.objective, ref) for r, ref in zip(results, refs)]
    batches = [e for e in events if e.get("event") == "batch"]
    row = {
        "phase": "c-serve", "requests": requests, "m": m, "n": n,
        "tol": tol, "batch": batch, "wall_s": wall,
        "statuses": sorted({r.status.value for r in results}),
        "engines": sorted({r.engine for r in results}),
        "buckets": sorted({tuple(r.bucket) for r in results if r.bucket}),
        "iterations_max": max(r.iterations for r in results),
        "compile_ms_max": max(r.compile_ms for r in results),
        "solve_ms_max": max(r.solve_ms for r in results),
        "dispatches": len(batches),
        "max_rel_err": max(errs),
    }
    if meter:
        row.update(_delta(c0, meter.snapshot()))
    faults = [e for e in events if e.get("event") == "fault"]
    row["checks"] = {
        "all_optimal": all(r.status is Status.OPTIMAL for r in results),
        "engine_ipm": all(r.engine == "ipm" for r in results)
        and all(b.get("engine") == "ipm" for b in batches),
        "batched": all(r.bucket is not None for r in results),
        "no_solo_fallback": not any(r.retried_solo for r in results),
        "no_faults": not faults and not any(r.faults for r in results),
        "matches_ref": max(errs) <= SERVE_OBJ_RTOL,
    }
    return row


def phase_four_chips(m, n, seed, ref_obj, tol=1e-8) -> dict:
    """The dense instance on a mesh over every device with the
    ``sharded`` backend, against the same instance on one chip with
    ``tpu``; each device must hold about 1/ndev of A."""
    import jax

    from distributedlpsolver_tpu.backends import get_backend
    from distributedlpsolver_tpu.ipm import Status, solve
    from distributedlpsolver_tpu.models.generators import random_dense_lp

    ndev = len(jax.devices())
    problem = random_dense_lp(m, n, seed=seed)
    be = get_backend("sharded")
    t0 = time.perf_counter()
    r_mesh = solve(problem, backend=be, tol=tol)
    wall_mesh = time.perf_counter() - t0
    A = be._A
    total = A.size * A.dtype.itemsize
    per_dev = {}
    for sh in A.addressable_shards:
        per_dev[str(sh.device)] = per_dev.get(str(sh.device), 0) + (
            sh.data.size * sh.data.dtype.itemsize
        )
    shares = sorted(v / total for v in per_dev.values())
    t0 = time.perf_counter()
    r_one = solve(problem, backend="tpu", tol=tol)
    wall_one = time.perf_counter() - t0
    row = {
        "phase": "four-chips", "instance": dense_key(m, n, seed),
        "devices": ndev, "ref_objective": ref_obj,
        "sharded": {**_result_row(r_mesh), "wall_s": wall_mesh,
                    "rel_err": rel_err(r_mesh.objective, ref_obj)},
        "one_chip": {**_result_row(r_one), "wall_s": wall_one,
                     "rel_err": rel_err(r_one.objective, ref_obj)},
        "A_shape": list(A.shape), "A_share_per_device": shares,
    }
    row["checks"] = {
        "mesh_optimal": r_mesh.status is Status.OPTIMAL,
        "one_chip_optimal": r_one.status is Status.OPTIMAL,
        "no_faults": not r_mesh.faults and not r_one.faults,
        "objectives_agree":
            rel_err(r_mesh.objective, r_one.objective) <= OBJ_RTOL,
        "matches_ref": max(row["sharded"]["rel_err"],
                           row["one_chip"]["rel_err"]) <= OBJ_RTOL,
        "A_split_evenly": len(per_dev) == ndev and all(
            abs(s - 1.0 / ndev) <= 0.25 / ndev for s in shares
        ),
    }
    return row


def profile_window(fn) -> dict:
    """Run ``fn`` under ``jax.profiler.trace`` and report whether an
    ``.xplane.pb`` was written and which planes it holds."""
    import shutil

    import jax

    d = tempfile.mkdtemp(prefix="chip-smoke-trace-")
    try:
        t0 = time.perf_counter()
        with jax.profiler.trace(d):
            fn()
        files = [os.path.join(root, f) for root, _, fs in os.walk(d)
                 for f in fs if f.endswith(".xplane.pb")]
        row = {"profile": "warm re-solve of (a), max_iter=3",
               "wall_s": time.perf_counter() - t0,
               "xplane_written": bool(files),
               "xplane_bytes": sum(os.path.getsize(f) for f in files)}
        if files:
            try:
                pd = jax.profiler.ProfileData.from_file(files[0])
                row["planes"] = sorted(p.name for p in pd.planes)
            except Exception as e:  # informational: never fails the run
                row["planes_error"] = f"{type(e).__name__}: {e}"
        return row
    finally:
        shutil.rmtree(d, ignore_errors=True)


def cache_entries(d) -> int:
    if not d or not os.path.isdir(d):
        return 0
    return sum(1 for f in os.listdir(d) if not f.startswith("."))


def emit(row: dict) -> None:
    print(json.dumps(row, default=str), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 4-device path and its "
                    "one-chip comparison")
    args = ap.parse_args(argv)

    import jax

    import distributedlpsolver_tpu  # noqa: F401  (x64 + compile cache)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing "
              "was run", file=sys.stderr)
        return 3
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 3

    refs = load_refs()
    cache_dir = jax.config.jax_compilation_cache_dir
    default_dir = os.path.join(_REPO, ".tpulp_xla_cache")
    cache0 = {"dir": cache_dir,
              "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
              "entries_before": cache_entries(cache_dir),
              "default_dir_entries_before": cache_entries(default_dir)}
    emit({"cache": cache0})
    meter = CompileMeter()
    t_start = time.perf_counter()
    rows = []
    if args.four_chips:
        rows.append(phase_four_chips(
            **DENSE, ref_obj=refs[dense_key(**DENSE)]))
        emit(rows[-1])
    else:
        rows.append(phase_dense(**DENSE, ref_obj=refs[dense_key(**DENSE)],
                                meter=meter))
        # On the chip the f32 phase must run the compiled Mosaic kernel.
        rows[-1]["checks"]["pallas_compiled"] = (
            rows[-1]["mosaic_programs"] > 0)
        rows[-1]["peak_bytes_in_use"] = peak_bytes()
        emit(rows[-1])
        from distributedlpsolver_tpu.ipm import solve
        from distributedlpsolver_tpu.models.generators import random_dense_lp

        # A short window: max_iter is a traced input, so the re-solve
        # reuses (a)'s programs and stops after a few iterations.
        dense = random_dense_lp(**DENSE)
        emit(profile_window(
            lambda: solve(dense, backend="auto", tol=1e-8, max_iter=3)))
        rows.append(phase_sparse(STORM, refs[storm_key(**STORM)],
                                 meter=meter))
        rows[-1]["peak_bytes_in_use"] = peak_bytes()
        emit(rows[-1])
        rows.append(phase_serve(**SERVE, meter=meter))
        rows[-1]["peak_bytes_in_use"] = peak_bytes()
        emit(rows[-1])
    emit({"cache": {
        "dir": cache_dir,
        "entries_after": cache_entries(cache_dir),
        "default_dir_entries_after": cache_entries(default_dir),
        **meter.snapshot()},
        "total_s": time.perf_counter() - t_start})
    failed = [f"{row['phase']}:{name}" for row in rows
              for name, ok in row["checks"].items() if not ok]
    if failed:
        print(f"chip_smoke: FAILED {failed}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
