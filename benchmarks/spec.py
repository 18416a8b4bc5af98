"""Finds a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own (configs/<config>.json,
traffic/<traffic>.json, layer_metrics/<metric>.json) and the code a metric
or a kernel needs is a module found by name (readers/<reader>.py,
kernels/<kernel>.py, modes/<mode>.py), so an addition edits nothing here.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def overlay(base: dict, over: dict) -> dict:
    """`base` with `over`'s keys laid over it, one level of dicts deep."""
    out = dict(base)
    for k, v in over.items():
        out[k] = {**out[k], **v} if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def load_module(path: str):
    """The module at `path`: one of this package's by its name, one from
    a directory outside it (the tests add files in a temporary one) by file."""
    if os.path.abspath(path).startswith(HERE + os.sep):
        rel = os.path.relpath(path, ROOT)[:-3].replace(os.sep, ".")
        return importlib.import_module(rel)
    spec = importlib.util.spec_from_file_location(
        f"_bench_ext_{abs(hash(path))}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of `workloads`, with its files resolved."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list          # metric entries of BENCHMARK.json for this cell
    per_layer: list
    tiny: bool = False
    data_dir: str = HERE      # looked in first; then this package
    run_seconds: float = 10.0

    def find(self, *parts: str) -> str:
        """The file `<data_dir>/<parts>` or, failing that, the package's."""
        for base in (self.data_dir, HERE):
            path = os.path.join(base, *parts)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"no {os.path.join(*parts)} under {self.data_dir} or {HERE}")

    def module(self, kind: str, name: str):
        """The module `<kind>/<name>.py` (readers, kernels, modes)."""
        return load_module(self.find(kind, name.replace("-", "_") + ".py"))

    @property
    def mode(self) -> str:
        return self.traffic["mode"]

    def depth(self) -> int:
        """Layers as run: the traffic's own number (a mesh decides what
        fits) or the configuration's for this mode."""
        if "num_hidden_layers" in self.traffic:
            return int(self.traffic["num_hidden_layers"])
        return int(self.config["num_hidden_layers"][self.mode.split("-")[0]])


def _for_cell(metrics: list, cell_name: str) -> list:
    return [m for m in metrics
            if "workloads" not in m or cell_name in m["workloads"]]


def load_cell(workload: str, tiny: bool = False, benchmark_json: str = None,
              data_dir: str = None) -> Cell:
    benchmark_json = benchmark_json or os.path.join(ROOT, "BENCHMARK.json")
    bench = load_json(benchmark_json)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    # a configuration's file is given from the benchmark file's directory
    config = load_json(os.path.join(os.path.dirname(benchmark_json),
                                    cfg_entry["file"]))
    cell = Cell(name=workload, chips=int(entry["chips"]),
                config_name=entry["config"], traffic_name=entry["traffic"],
                config=config, traffic={},
                end_to_end=_for_cell(bench["end_to_end"], workload),
                per_layer=_for_cell(bench["per_layer"], workload),
                tiny=tiny, data_dir=data_dir or HERE,
                run_seconds=float(bench["run_seconds"]))
    cell.traffic = load_json(cell.find("traffic", entry["traffic"] + ".json"))
    if tiny:
        cell.config = overlay(config, config.get("tiny", {}))
        cell.traffic = overlay(cell.traffic, cell.traffic.get("tiny", {}))
    return cell


def layer_metric(cell: Cell, name: str) -> dict:
    return load_json(cell.find("layer_metrics", name + ".json"))


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         "benchmarks/peaks.json; add it with its source")
    return table[device_kind]
