"""Discovery: everything a run needs, found by name from ``BENCHMARK.json``.

A later PR adds a configuration, a traffic mix, a cell or a per-layer metric
as new files plus one ``BENCHMARK.json`` entry. Nothing here (or anywhere in
the harness) holds the name of a cell, a model or a metric.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List

BENCH_FILE = "BENCHMARK.json"


class BenchError(RuntimeError):
    """The benchmark's own data is inconsistent; no result is printed."""


def checkout_root() -> str:
    """The checkout this file lives in (``<root>/benchmark/lib/cells.py``)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchError(f"missing benchmark file: {path}") from e


def load_module(path: str, name: str):
    """Import one file by path (builders, drivers, layer metrics)."""
    if not os.path.exists(path):
        raise BenchError(f"missing benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""
    name: str
    chips: int
    why: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    spec: Dict[str, Any]                 # benchmark/workloads/<cell>.json
    end_to_end: List[Dict[str, Any]]     # BENCHMARK.json entries it reports
    per_layer: List[Dict[str, Any]]      # BENCHMARK.json entries it reports
    root: str
    bench_dir: str
    facts: Dict[str, Any] = field(default_factory=dict)  # filled by a run

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _reports(metric: dict, cell_name: str) -> bool:
    """A metric without a ``workloads`` key is reported wherever the
    end-to-end metric it belongs to is."""
    listed = metric.get("workloads")
    return listed is None or cell_name in listed


def load_cell(name: str, root: str | None = None) -> Cell:
    root = root or checkout_root()
    bench = _load_json(os.path.join(root, BENCH_FILE))
    bench_dir = os.path.join(root, bench["paths"][0])
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(
            f"no workload {name!r} in {BENCH_FILE}; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BenchError(f"workload {name!r} names config "
                         f"{entry['config']!r}, which {BENCH_FILE} lacks")
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(
        bench_dir, "traffic", entry["traffic"] + ".json"))
    spec = _load_json(os.path.join(bench_dir, "workloads", name + ".json"))
    for key in ("config", "traffic", "chips"):
        if spec.get(key) != entry[key]:
            raise BenchError(
                f"workloads/{name}.json says {key}={spec.get(key)!r}, "
                f"{BENCH_FILE} says {entry[key]!r}")
    reported = set(spec["end_to_end"])
    end_to_end = [m for m in bench["end_to_end"] if m["name"] in reported]
    missing = reported - {m["name"] for m in end_to_end}
    if missing:
        raise BenchError(f"workloads/{name}.json reports {sorted(missing)}, "
                         f"which {BENCH_FILE} does not define")
    for m in end_to_end:
        if not _reports(m, name):
            raise BenchError(f"{m['name']} does not list workload {name!r}")
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in reported and _reports(m, name)]
    return Cell(name=name, chips=int(entry["chips"]), why=entry["why"],
                config=config, traffic=traffic, spec=spec,
                end_to_end=end_to_end, per_layer=per_layer, root=root,
                bench_dir=bench_dir)


def builder_for(cell: Cell):
    """The module that makes this configuration's weights and hands them to
    the program under test: ``builders/<config["builder"]>.py``."""
    name = cell.config["builder"]
    return load_module(os.path.join(cell.bench_dir, "builders", name + ".py"),
                       f"bench_builder_{name}")


def driver_for(cell: Cell):
    """The loop that runs this traffic: ``drivers/<traffic["driver"]>.py``."""
    name = cell.traffic["driver"]
    return load_module(os.path.join(cell.bench_dir, "drivers", name + ".py"),
                       f"bench_driver_{name}")


def layer_metric_modules(bench_dir: str) -> list:
    """Every per-layer metric reader: one file each under
    ``layer_metrics/``, found by listing the directory."""
    folder = os.path.join(bench_dir, "layer_metrics")
    out = []
    for fname in sorted(os.listdir(folder)):
        if fname.endswith(".py") and not fname.startswith("_"):
            out.append(load_module(os.path.join(folder, fname),
                                   "bench_layer_metric_" + fname[:-3]
                                   .replace(".", "_").replace("-", "_")))
    return out


def read_layer_metrics(cell: Cell, trace, host: dict) -> Dict[str, dict]:
    """Run every reader whose ``KINDS`` holds this cell's kind; a reader
    that finds nothing returns None and its metric is left out."""
    wanted = {m["name"]: m for m in cell.per_layer}
    out = {}
    for mod in layer_metric_modules(cell.bench_dir):
        if cell.kind not in mod.KINDS or mod.NAME not in wanted:
            continue
        if wanted[mod.NAME]["unit"] != mod.UNIT:
            raise BenchError(f"{mod.NAME}: unit {mod.UNIT!r} in its reader, "
                             f"{wanted[mod.NAME]['unit']!r} in {BENCH_FILE}")
        value = mod.read(trace, host, cell)
        if value is not None:
            out[mod.NAME] = {"value": float(value), "unit": mod.UNIT}
    return out
