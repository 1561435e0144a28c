"""Where everything the benchmark runs is looked up: by name, in data files.

``BENCHMARK.json`` (at the root of the checkout) names cells, configurations
and metrics. Whatever belongs to one of them sits in a file of its own under
``benchmark/``, found by that name:

    workloads[].name    -> benchmark/cells/<name>.json       how the cell is run
    workloads[].config  -> configs[].file                   the sizes as run
    workloads[].traffic -> benchmark/traffic/<traffic>.json the traffic mix
    per_layer[].name    -> benchmark/layer_metrics/<name>.py its reader

What belongs to one architecture is named by its configuration file, or is
a file in a directory that is read whole:

    "reference": "<name>"   -> benchmark/references/<name>.py  its plain
                               reference (absent: benchmark/lib/reference.py)
    "program_model": "<module>:<callable>"   the program's constructor of
                               its model (absent: DEFAULT_PROGRAM_MODEL)
    benchmark/rules/*.json  -> laid over lib/span_rules.json and
                               lib/trace_rules.json: more kernels, programs,
                               span groups

So a later PR adds a cell, a configuration, a traffic mix, a layer metric or
a whole architecture with new files and new entries, and edits no file that
is already here. This module imports nothing but the standard library.
"""

from __future__ import annotations

import ast
import glob
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


DEFAULT_PROGRAM_MODEL = "dlti_tpu.models:LlamaForCausalLM"
# What a reference module offers (benchmark/references/README.md); a cell
# that trains needs the gradient too.
REFERENCE_OFFERS = {"serve": ("sizes", "forward"),
                    "train": ("sizes", "forward", "grad")}
# The sections of the rules a file under benchmark/rules/ may add to.
SPAN_SECTIONS = ("kernels", "kernels_per", "groups", "clock_check")
TRACE_SECTIONS = ("programs",)


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
    config = _load_json(os.path.join(root, config_entry["file"]))
    cell = _load_json(os.path.join(bench_dir, "cells", name + ".json"))
    program_model(config)
    # run.py's two kinds: what is not "train" serves
    check_reference_file(
        reference_file(config, bench_dir),
        REFERENCE_OFFERS["train" if cell.get("kind") == "train" else "serve"])
    return {
        "name": name,
        "chips": int(workload["chips"]),
        "workload": workload,
        "config_name": workload["config"],
        "config": config,
        "traffic_name": workload["traffic"],
        "traffic": _load_json(os.path.join(
            bench_dir, "traffic", workload["traffic"] + ".json")),
        "cell": cell,
        "end_to_end": [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])],
        "bench_dir": bench_dir,
        "root": root,
    }


def _load_module(label: str, path: str):
    spec = importlib.util.spec_from_file_location(
        label.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_layer_reader(metric_name: str, bench_dir: str = BENCH_DIR):
    """The ``read(ctx)`` function of benchmark/layer_metrics/<name>.py."""
    path = os.path.join(bench_dir, "layer_metrics", metric_name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"per-layer metric {metric_name!r} has no reader "
                        f"at {path}")
    module = _load_module("bench_layer_metric_" + metric_name, path)
    if not callable(getattr(module, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return module.read


# -- what a configuration names ------------------------------------------------

def reference_file(config: dict, bench_dir: str = BENCH_DIR) -> str:
    """The plain reference a configuration is held against."""
    name = config.get("reference")
    if name is None:
        return os.path.join(bench_dir, "lib", "reference.py")
    return os.path.join(bench_dir, "references", f"{name}.py")


def check_reference_file(path: str, offers: tuple) -> None:
    """Refuse a reference module that does not define or assign, at its top
    level, every name of ``offers``. Read as text, not imported: a reference
    imports JAX, which the process that starts the children never does, and
    a module that lacks a name must be refused before any process starts."""
    try:
        with open(path) as f:
            tree = ast.parse(f.read(), path)
    except (OSError, SyntaxError) as e:
        raise SpecError(f"reference module {path}: {e}") from e
    bound = set()
    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            bound.add(node.name)
        elif isinstance(node, ast.Assign):
            bound.update(t.id for t in node.targets
                         if isinstance(t, ast.Name))
    missing = [name for name in offers if name not in bound]
    if missing:
        raise SpecError(f"reference module {path} defines no "
                        f"{', '.join(missing)} (it has to offer "
                        f"{', '.join(offers)}: benchmark/references/"
                        f"README.md)")


def load_reference(config: dict, kind: str, bench_dir: str = BENCH_DIR):
    """The configuration's reference module, loaded by path (imports JAX:
    for check.py, never for the process that starts the children)."""
    path = reference_file(config, bench_dir)
    module = _load_module(
        "bench_reference_" + os.path.basename(path)[:-3], path)
    for name in REFERENCE_OFFERS[kind]:
        if not callable(getattr(module, name, None)):
            raise SpecError(f"{path}: {name} is not a function")
    return module


def program_model(config: dict) -> tuple:
    """``(module, callable)`` names of the program's model constructor."""
    name = config.get("program_model", DEFAULT_PROGRAM_MODEL)
    module, _, attr = str(name).partition(":")
    if not module or not attr:
        raise SpecError(f'"program_model": {name!r} is not '
                        f'"<module>:<callable>"')
    return module, attr


# -- rules of the trace reduction ------------------------------------------------

def load_rules(base_file: str, sections: tuple,
               bench_dir: str = BENCH_DIR) -> dict:
    """``benchmark/lib/<base_file>`` with ``sections`` of every
    ``benchmark/rules/*.json`` (in the order of their names) laid over it,
    object by object. A rules file adds keys; one that repeats a key of the
    base or of another rules file is refused, so that an addition can
    never re-point a metric that is there."""
    base_path = os.path.join(bench_dir, "lib", base_file)
    out = _load_json(base_path)
    origin = {(s, k): base_path for s in sections for k in out.get(s, {})}
    for path in sorted(glob.glob(os.path.join(bench_dir, "rules",
                                              "*.json"))):
        extra = _load_json(path)
        unknown = sorted(set(extra) - set(SPAN_SECTIONS + TRACE_SECTIONS))
        if unknown:
            raise SpecError(f"{path}: a rules file may hold "
                            f"{', '.join(SPAN_SECTIONS + TRACE_SECTIONS)}; "
                            f"not {', '.join(unknown)}")
        for section in sections:
            added = extra.get(section, {})
            if not isinstance(added, dict):
                raise SpecError(f"{path}: {section!r} is not an object")
            for key, value in added.items():
                if (section, key) in origin:
                    raise SpecError(
                        f"{path} repeats {section}.{key}, which "
                        f"{origin[section, key]} has: a rules file adds "
                        f"names and changes none")
                origin[section, key] = path
                out.setdefault(section, {})[key] = value
    return out
