"""Readings that set the limits of a cell's correctness check.

    python3 -m benchmark.control --workload <cell> --seeds 11,12,13 --mode control|program

For each seed, the LPs the cell's window would get from that seed (k = 0,
1, ...) go through the cell's own entry (``entries/<entry>.py``: the
calls of the program that the window times) at the cell's sizes, and the
configuration's reference judges every answer. ``program`` runs the
solve options the configuration states: its readings are the lower ones.
``control`` runs them with the configuration's ``control`` options on
top, the program's own path in the precision below the stated one
(float32 for float64): its readings are the upper ones, which the limits
must fail. One JSON line per seed gives the worst of each compared
number. The benchmark's runs never run this; the tests run it at a small
size.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def readings(cell, seeds, mode: str, answers=None, emit=print):
    """One row per seed: statuses and the worst compared numbers over the
    LPs k < ``answers`` (default: the window's pool)."""
    from benchmark.run import check

    rows = []
    for seed in seeds:
        entry = cell.entry.Entry(cell, seed)
        if mode == "control":
            entry.opts.update(cell.config["control"]["solve"])
        t0 = time.perf_counter()
        ans = entry.answers(answers)
        nums = check(cell, seed, ans, any_status=True)
        row = {"seed": seed, "mode": mode, "answers": len(ans),
               "statuses": sorted({a["status"] for a in ans}),
               "iterations_max": max(a["iterations"] for a in ans),
               "seconds": time.perf_counter() - t0,
               "numbers": {k: v["value"] for k, v in nums.items()}}
        emit(json.dumps(row))
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--mode", choices=("program", "control"),
                    required=True)
    ap.add_argument("--answers", type=int, default=None,
                    help="LPs per seed (default: the LPs of the mix's pool)")
    args = ap.parse_args(argv)
    from benchmark import run, spec

    cell = spec.load_cell(args.workload)
    try:
        run.find_chips(cell.chips)
    except (run.NoChip, KeyError) as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 3
    run.use_cache(spec.ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    readings(cell, seeds, args.mode, args.answers)
    return 0


if __name__ == "__main__":
    sys.exit(main())
