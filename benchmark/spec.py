"""Find a cell's parts by name.

``BENCHMARK.json`` names a cell's configuration and traffic mix; the
files that hold them sit at fixed places under ``benchmark/``:

- ``configs/<config>.json``: the deployment (instance generator and its
  sizes, tolerance, guarantees, the limits of the correctness check);
- ``generators/<generator>.py``: ``build(params, seed, k) -> LPProblem``;
- ``references/<reference>.py``: ``check(problem, answer) -> {name: x}``;
- ``traffic/<traffic>.json``: the mix: its ``entry`` and that entry's
  parameters;
- ``entries/<entry>.py``: the driver a mix names, ``Entry(cell, seed)``
  with ``setup()``, ``window(seconds, profiler)``, ``answers(n)`` and
  ``close()`` (see ``load.py``);
- ``metrics/<metric>.py``: ``read(run) -> float | None``.

A later cell, mix, entry, generator, reference or metric is a new file
and a new entry: nothing here needs an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

PKG = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PKG)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    kind: str  # "end_to_end" | "per_layer"
    read: object  # callable(run) -> float | None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object  # module with build()
    reference: object  # module with check()
    entry: object  # module with Entry
    metrics: dict  # kind -> [Metric]


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, ROOT).replace(os.sep, "_")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _applies(entry: dict, cell: str, e2e_of_cell: set) -> bool:
    if "workloads" in entry:
        return cell in entry["workloads"]
    # No list: an end-to-end metric is in every cell; a per-layer one in
    # every cell that reports the end-to-end metric it moves.
    return entry.get("moves") is None or entry["moves"] in e2e_of_cell


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    here = os.path.join(root, "benchmark")
    traffic = _read_json(os.path.join(here, "traffic", w["traffic"] + ".json"))
    generator = load_module(
        os.path.join(here, "generators", config["generator"] + ".py"))
    reference = load_module(
        os.path.join(here, "references", config["reference"] + ".py"))
    entry = load_module(
        os.path.join(here, "entries", traffic["entry"] + ".py"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, set())]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, workload, e2e_names)]
    metrics = {}
    for kind, entries in (("end_to_end", e2e), ("per_layer", layer)):
        metrics[kind] = [
            Metric(m["name"], m["unit"], m["better"], m["source"], kind,
                   load_module(os.path.join(here, "metrics",
                                            m["name"] + ".py")).read)
            for m in entries
        ]
    return Cell(workload, int(w["chips"]), config, traffic, generator,
                reference, entry, metrics)
