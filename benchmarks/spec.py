"""The benchmark's data: manifest, configurations, traffic mixes, cells
and per-layer metric readers, each found by name.

Nothing here imports JAX, so the parent of ``run.py`` can use it.  A
later PR adds a file and an entry; no function here knows a name.
"""

import importlib
import importlib.util
import json
import os
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
MANIFEST = os.path.join(REPO, "BENCHMARK.json")


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(path: str = MANIFEST) -> dict:
    return _load_json(path)


class Cell:
    """One cell: its workload file and the configuration and traffic
    files that file names, all under ``root``."""

    def __init__(self, name: str, root: str = HERE):
        self.name = name
        self.root = root
        self.workload = _load_json(
            os.path.join(root, "workloads", name + ".json"))
        self.config_name = self.workload["config"]
        self.traffic_name = self.workload["traffic"]
        self.chips = int(self.workload["chips"])
        self.config = _load_json(
            os.path.join(root, "configs", self.config_name + ".json"))
        self.traffic = _load_json(
            os.path.join(root, "traffic", self.traffic_name + ".json"))

    @property
    def trainer_path(self) -> str:
        return os.path.join(HERE, "trainers",
                            self.traffic["trainer"] + ".py")

    @property
    def processes(self) -> int:
        return int(self.traffic["processes"])


def cell_names(root: str = HERE) -> List[str]:
    return sorted(f[:-5] for f in os.listdir(os.path.join(root, "workloads"))
                  if f.endswith(".json"))


def load_module(path: str, name: str):
    """Import one file as a module of its own (a metric reader, which
    may live under another root than this package's)."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_family(config: dict):
    """The family module the configuration names: it builds the step,
    makes batches, counts the required FLOPs and calls the reference."""
    return importlib.import_module("benchmarks.models." + config["family"])


def metric_readers(root: str = HERE) -> Dict[str, object]:
    """Every reader under ``layer_metrics/``, by the metric's name."""
    directory = os.path.join(root, "layer_metrics")
    readers = {}
    for f in sorted(os.listdir(directory)):
        if f.endswith(".py") and not f.startswith("_"):
            name = f[:-3]
            readers[name] = load_module(os.path.join(directory, f),
                                        "benchmarks_metric_" + name)
    return readers


def metrics_of_cell(manifest: dict, section: str, cell: str) -> List[dict]:
    """The manifest's metrics of ``section`` that ``cell`` reports."""
    return [m for m in manifest[section]
            if cell in m.get("workloads", [cell])]


def read_layer_metrics(run: dict, cell: str, manifest: Optional[dict] = None,
                       root: str = HERE) -> Dict[str, dict]:
    """Call the reader of every per-layer metric the manifest gives this
    cell.  A reader that finds nothing to read returns None and its
    metric is left out."""
    manifest = manifest or load_manifest()
    readers = metric_readers(root)
    out = {}
    for m in metrics_of_cell(manifest, "per_layer", cell):
        reader = readers.get(m["name"])
        value = reader.read(run) if reader else None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
