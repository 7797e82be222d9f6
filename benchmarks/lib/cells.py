"""``BENCHMARK.json`` joined to the files it names.  Everything that belongs
to one configuration, one traffic mix or one per-layer metric sits in a file
of its own, found by the name in ``BENCHMARK.json``; nothing here knows a
cell, a model or a metric by name."""

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")


class BenchmarkError(Exception):
    """The benchmark's files do not fit together."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def merge(into, patch):
    """``patch``'s leaves over ``into``'s, group by group."""
    for key, value in patch.items():
        if isinstance(value, dict) and isinstance(into.get(key), dict):
            merge(into[key], value)
        else:
            into[key] = value


def resolve(dotted):
    """``"package.module:attribute"`` -> the attribute."""
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)


def kind_module(kind):
    """The module that runs a traffic ``kind``: ``serve-open-loop`` is
    ``benchmarks/kinds/serve_open_loop.py``."""
    return importlib.import_module("benchmarks.kinds." + kind.replace("-", "_"))


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic mix and
    the metrics ``BENCHMARK.json`` lists for it."""

    def __init__(self, name, root=ROOT):
        bench = load_benchmark(root)
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise BenchmarkError(
                f"no workload {name!r} in BENCHMARK.json; it has "
                f"{[w['name'] for w in bench['workloads']]}")
        cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
        self.name, self.chips = name, int(entry["chips"])
        self.config_name, self.traffic_name = entry["config"], entry["traffic"]
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            root, "benchmarks", "traffic", entry["traffic"] + ".json"))
        self.kind = kind_module(self.traffic["kind"])
        here = lambda m: name in m.get("workloads", [name])
        self.end_to_end = [m for m in bench["end_to_end"] if here(m)]
        self.per_layer = [m for m in bench["per_layer"] if here(m)]
        self.run_seconds = bench["run_seconds"]

    def reader(self, metric_name, root=ROOT):
        """(function, keyword arguments) of a per-layer metric, from its own
        file ``benchmarks/metrics/<name>.json``."""
        spec = load_json(os.path.join(root, "benchmarks", "metrics",
                                      metric_name + ".json"))
        return resolve(spec["reader"]), spec.get("args", {})


def per_layer_values(cell, run):
    """name -> {"value", "unit"} for every per-layer metric of the cell whose
    reader finds something to read."""
    out = {}
    for m in cell.per_layer:
        fn, args = cell.reader(m["name"])
        value = fn(run, **args)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
