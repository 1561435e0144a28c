"""BENCHMARK.json and the data files it names: the contract's limits, and
that a new cell, configuration, traffic mix or layer metric is a new file."""

import json
import os
import re
import shutil

import pytest

from bench_paths import BENCH, ROOT

import spec as spec_lib

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
LAYER_METRICS = [m["name"] for m in BENCHMARK["per_layer"]]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# Published sizes no cut may touch (config.json of each source).
PUBLISHED = {
    "mistral_7b": dict(hidden_size=4096, intermediate_size=14336,
                       num_attention_heads=32, num_key_value_heads=8,
                       head_dim=128, vocab_size=32000, sliding_window=4096),
    "qwen2_7b": dict(hidden_size=3584, intermediate_size=18944,
                     num_attention_heads=28, num_key_value_heads=4,
                     head_dim=128, vocab_size=152064),
}


def test_top_level_keys_and_command():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["command"] == ["python3", "benchmark/run.py"]
    assert BENCHMARK["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(BENCHMARK["run_seconds"], int)
    assert 10 <= BENCHMARK["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lines_are_within_the_allowed_characters():
    names = []
    for c in BENCHMARK["configs"]:
        names.append(c["name"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCHMARK["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BENCHMARK["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCHMARK["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        got = [x["name"] for x in BENCHMARK[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in BENCHMARK["end_to_end"]
               + BENCHMARK["per_layer"]]
    assert len(metrics) == len(set(metrics))


def test_every_configuration_has_a_cell_and_each_pair_appears_once():
    used = {w["config"] for w in BENCHMARK["workloads"]}
    assert used == {c["name"] for c in BENCHMARK["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in BENCHMARK["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(pairs) // 4)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_files_that_exist(cell):
    got = spec_lib.resolve_cell(cell)
    assert got["cell"]["kind"] in ("train", "serve")
    assert os.path.isfile(os.path.join(ROOT, got["cell"]["entry"]))
    assert got["config"]["model"]["hidden_size"] > 0
    assert "shape_seed" in got["traffic"]
    e2e = {m["name"] for m in got["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert got["per_layer"], "every cell reports a per-layer metric"
    for m in got["per_layer"]:
        assert m["moves"] in e2e, (m["name"], "moves", m["moves"])


@pytest.mark.parametrize("metric", LAYER_METRICS)
def test_layer_metric_has_a_reader_that_agrees_with_its_entry(metric):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    read = spec_lib.load_layer_reader(metric)
    consts = read.__globals__
    for key, const in (("name", "NAME"), ("unit", "UNIT"),
                       ("better", "BETTER"), ("layer", "LAYER"),
                       ("moves", "MOVES"), ("source", "SOURCE")):
        assert consts[const] == entry[key], (metric, key)


@pytest.mark.parametrize("config", sorted(PUBLISHED))
def test_configuration_keeps_every_published_width(config):
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == config)
    body = json.load(open(os.path.join(ROOT, entry["file"])))
    assert body["source"] == entry["source"]
    assert entry["reduced"] == body["reduced"] == ["num_hidden_layers"]
    for key, value in PUBLISHED[config].items():
        assert body["model"][key] == value, key
    assert (body["model"]["num_hidden_layers"]
            < body["published"]["num_hidden_layers"])


def test_files_under_paths_are_named_from_the_allowed_characters():
    for path in BENCHMARK["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_a_new_cell_configuration_mix_and_metric_are_new_files(tmp_path):
    """Driven by data: a dummy of each is added to a copy, touching no
    file that exists, and the harness's resolution finds them all."""
    root = str(tmp_path / "copy")
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for base, _, files in os.walk(root):
        for f in files:
            p = os.path.join(base, f)
            before[p] = open(p, "rb").read()
    bench_dir = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(bench_dir, "configs",
                                      "mistral_7b.json")))
    cfg["name"] = "dummy_model"
    json.dump(cfg, open(os.path.join(bench_dir, "configs",
                                     "dummy_model.json"), "w"))
    mix = json.load(open(os.path.join(bench_dir, "traffic", "chat.json")))
    mix["arrivals"]["rate_per_s"] = 1.0
    json.dump(mix, open(os.path.join(bench_dir, "traffic",
                                     "dummy_mix.json"), "w"))
    shutil.copy(os.path.join(bench_dir, "cells", "serve.mistral_7b.chat.json"),
                os.path.join(bench_dir, "cells", "serve.dummy.cell.json"))
    with open(os.path.join(bench_dir, "layer_metrics", "dummy_metric.py"),
              "w") as f:
        f.write("def read(ctx):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({
        "name": "dummy_model", "source": cfg["source"],
        "file": "benchmark/configs/dummy_model.json",
        "reduced": ["num_hidden_layers"], "why": "a dummy"})
    bench["workloads"].append({
        "name": "serve.dummy.cell", "config": "dummy_model",
        "traffic": "dummy_mix", "chips": 1, "why": "a dummy"})
    bench["per_layer"].append({
        "name": "dummy_metric", "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": "device",
        "moves": "ttft_mean_ms", "workloads": ["serve.dummy.cell"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_mean_ms", "itl_mean_ms"):
            m["workloads"].append("serve.dummy.cell")
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    got = spec_lib.resolve_cell("serve.dummy.cell", root=root)
    assert got["config"]["name"] == "dummy_model"
    assert got["traffic"]["arrivals"]["rate_per_s"] == 1.0
    assert [m["name"] for m in got["per_layer"]] == ["dummy_metric"]
    read = spec_lib.load_layer_reader("dummy_metric", got["bench_dir"])
    assert read({}) == 42.0
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"


def test_an_unknown_cell_is_an_error_that_names_the_known_ones():
    with pytest.raises(spec_lib.SpecError, match="serve.mistral_7b.chat"):
        spec_lib.resolve_cell("no.such.cell")
