"""Where everything the benchmark runs is looked up: by name, in data files.

``BENCHMARK.json`` (at the root of the checkout) names cells, configurations
and metrics. Whatever belongs to one of them sits in a file of its own under
``benchmark/``, found by that name:

    workloads[].name    -> benchmark/cells/<name>.json       how the cell is run
    workloads[].config  -> configs[].file                   the sizes as run
    workloads[].traffic -> benchmark/traffic/<traffic>.json the traffic mix
    per_layer[].name    -> benchmark/layer_metrics/<name>.py its reader

So a later PR adds a cell, a configuration, a traffic mix or a layer metric
with new files and new entries, and edits no file that is already here.
This module imports nothing but the standard library.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"{path}: {e}") from e


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def resolve_cell(name: str, root: str = ROOT) -> dict:
    """Everything one run of cell ``name`` needs, gathered from the files
    its names lead to. Raises SpecError when a name leads nowhere."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; it has "
                        f"{sorted(cells)}")
    workload = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if workload["config"] not in configs:
        raise SpecError(f"workload {name!r} names configuration "
                        f"{workload['config']!r}, which BENCHMARK.json lacks")
    config_entry = configs[workload["config"]]
    bench_dir = os.path.join(root, os.path.relpath(BENCH_DIR, ROOT))
    return {
        "name": name,
        "chips": int(workload["chips"]),
        "workload": workload,
        "config_name": workload["config"],
        "config": _load_json(os.path.join(root, config_entry["file"])),
        "traffic_name": workload["traffic"],
        "traffic": _load_json(os.path.join(
            bench_dir, "traffic", workload["traffic"] + ".json")),
        "cell": _load_json(os.path.join(bench_dir, "cells", name + ".json")),
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
        "bench_dir": bench_dir,
        "root": root,
    }


def load_layer_reader(metric_name: str, bench_dir: str = BENCH_DIR):
    """The ``read(ctx)`` function of benchmark/layer_metrics/<name>.py."""
    path = os.path.join(bench_dir, "layer_metrics", metric_name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"per-layer metric {metric_name!r} has no reader "
                        f"at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if not callable(getattr(module, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return module.read
