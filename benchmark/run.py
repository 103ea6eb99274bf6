"""Run one cell of BENCHMARK.json on the chip this process finds.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Stages: set-up (build the cell's LPs from the seed and solve the warm-up
LPs at the window's exact solve options, so every program compiles or
loads from the cache here), the window (the driver that the mix names,
``entries/<entry>.py``, for ``--seconds``), the check (every answer of
the window against the configuration's float64 reference), the result.
Earlier stdout lines are JSON objects (``setup``, ``window``,
``trace``); the last stdout line is the result, and the last stderr
lines each compared number beside its limit. No TPU, fewer chips than
the cell asks for, or a chip kind with no published peaks: exit 3 with
no result.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from benchmark import load, meter, spec, trace  # noqa: E402
from benchmark.peaks import peaks  # noqa: E402


class NoChip(RuntimeError):
    pass


def find_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks(devs[0].device_kind)
    return devs


def use_cache(root: str) -> str:
    """JAX's persistent cache at one fixed path inside the checkout, set
    before the program is imported (which then keeps it), and every
    program kept, however quick to compile, so that a run after the
    first compiles nothing."""
    import jax

    d = os.path.join(root, ".bench_cache", "jax")
    jax.config.update("jax_compilation_cache_dir", d)
    import distributedlpsolver_tpu  # noqa: F401  (x64; keeps d)

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return d


def check(cell, seed: int, answers: list, any_status: bool = False) -> dict:
    """Judge every answer by the configuration's reference; mark each
    ``ok``. Returns each compared number (the worst over answers) with
    its limit. ``any_status`` also reads the numbers of answers that are
    not OPTIMAL but carry a point (the control's readings)."""
    limits = cell.config["limits"]
    seen = {name: [0.0] for name in limits if name != "not_optimal"}
    bad = 0
    for a in answers:
        optimal = a["status"] == "optimal"
        bad += not optimal
        a["ok"] = False
        if not (optimal or (any_status and a.get("x") is not None)):
            continue
        p = cell.generator.build(cell.config["params"], seed, a["k"])
        nums = cell.reference.check(p, a)
        a["ok"] = optimal and all(v <= limits[n] for n, v in nums.items())
        for n, v in nums.items():
            seen[n].append(v)
    out = {"not_optimal": {"value": bad, "limit": limits["not_optimal"]}}
    for n, vs in seen.items():
        # A NaN is the worst reading: it fails its limit.
        worst = float("nan") if np.isnan(vs).any() else max(vs)
        out[n] = {"value": worst, "limit": limits[n]}
    return out


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def run(args, root: str = spec.ROOT, require_tpu: bool = True) -> int:
    try:
        cell = spec.load_cell(args.workload, root)
    except (KeyError, OSError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        import jax

        devs = find_chips(cell.chips) if require_tpu else jax.devices()
    except (NoChip, KeyError, RuntimeError) as e:
        print(f"benchmark: {e}; nothing was run", file=sys.stderr)
        return 3
    cache_dir = use_cache(root)
    compiles = meter.CompileMeter()
    entry = cell.entry.Entry(cell, args.seed)
    c0 = compiles.snapshot()
    info = entry.setup()
    emit({"setup": {**info, "cache_dir": cache_dir,
                    **meter.delta(c0, compiles.snapshot())}})

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(root, ".bench_cache", "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    profiler = load.Profiler(trace_dir)
    c1 = compiles.snapshot()
    answers, t0, notes = entry.window(args.seconds, profiler)
    setup_s = t0 - _T_START
    in_window = meter.delta(c1, compiles.snapshot())
    peak = meter.peak_bytes(devs)
    entry.close()
    emit({"window": {**notes, "answers": len(answers),
                     "compiles_in_window": in_window}})

    reduced = None
    if trace_dir:
        path = trace.find_xplane(trace_dir)
        reduced = trace.reduce(path) if path else None
        emit({"trace": {"xplane": path,
                        "bytes": os.path.getsize(path) if path else 0,
                        "traced_s": profiler.seconds,
                        **({k: reduced[k] for k in
                            ("busy_s", "window_s", "devices")}
                           if reduced else {})}})

    checks = check(cell, args.seed, answers)
    correct = bool(answers) and all(c["value"] <= c["limit"]
                                    for c in checks.values())
    rec = {"setup_s": setup_s, "seconds": args.seconds, "window_start": t0,
           "window_end": t0 + args.seconds, "answers": answers,
           "trace": reduced}
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell.metrics[kind]:
        v = m.read(rec)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(answers),
              "failed": sum(1 for a in answers if not a["ok"]),
              "metrics": metrics, "device": device}
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    emit(result)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    return 0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    sys.exit(run(parse()))
