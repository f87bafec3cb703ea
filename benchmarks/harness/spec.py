"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is an entry of ``workloads``: it names a configuration
(``configs/<config>.json`` through the ``file`` of its ``configs`` entry) and
a traffic mix (``traffic/<traffic>.json``). The traffic file names its driver
(``drivers/<driver>.py``), the configuration file its operation count
(``ops_count/<family>.py``); a metric ``<name>`` is the reader
``layer_metrics/<name>.py`` or ``end_to_end/<name>.py``; a chip's peaks are
``peaks/<device kind>.json``. Adding any of them is adding a file and, for a
cell or a metric, an entry: no table here knows their names.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str):
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: dict     # name -> unit, the metrics a --trace 0 run owes
    per_layer: dict      # name -> unit, the metrics a --trace 1 run owes
    run_seconds: int
    bench_dir: str

    def driver(self):
        return load_module("drivers", self.traffic["driver"],
                            self.bench_dir)

    def _ops_count(self):
        return load_module("ops_count", self.config["ops_count"],
                            self.bench_dir)

    def train_flops_per_image(self) -> float:
        return float(self._ops_count().train_flops_per_image(
            self.config["architecture"]))

    def parameter_count(self) -> int:
        return int(self._ops_count().parameter_count(
            self.config["architecture"]))

    def peak(self, device_kind: str) -> dict:
        """The device kind's peaks, from ``peaks/<kind>.json`` (the kind
        with ``_`` for each character a file name may not have). An unknown
        kind is an error, never a default."""
        slug = re.sub(r"[^A-Za-z0-9_.\-]", "_", device_kind)
        path = os.path.join(self.bench_dir, "peaks", slug + ".json")
        if not os.path.isfile(path):
            raise KeyError(f"device kind {device_kind!r} has no {path}: "
                           f"an unknown chip has no peak")
        peak = _load_json(path)
        if peak.get("device_kind") != device_kind:
            raise KeyError(f"{path} is for {peak.get('device_kind')!r}, "
                           f"not {device_kind!r}")
        return peak


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; it has "
                       f"{sorted(cells)}")
    w = cells[workload]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == w["config"])
    bench_dir = os.path.join(root, os.path.dirname(
        os.path.dirname(config_entry["file"])))

    def owed(metrics):
        return {m["name"]: m["unit"] for m in metrics
                if workload in m.get("workloads", [workload])}

    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, config_entry["file"])),
        traffic=_load_json(os.path.join(bench_dir, "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=owed(bench["end_to_end"]),
        per_layer=owed(bench["per_layer"]),
        run_seconds=int(bench["run_seconds"]), bench_dir=bench_dir)


def declared_layer_metrics(bench_dir: str = BENCH_DIR) -> dict:
    """Every reader under ``layer_metrics/``, by listing the directory."""
    out = {}
    for fn in sorted(os.listdir(os.path.join(bench_dir, "layer_metrics"))):
        if fn.endswith(".py"):
            out[fn[:-3]] = load_module("layer_metrics", fn[:-3], bench_dir)
    return out
