"""A whole architecture is added to the benchmark as new files: in a copy of
the checkout's benchmark, a configuration that names its own reference and
its model constructor, that reference, a rules file with one more kernel
and one more program, a cell with ``program_overrides``, a mix and a reader
are added, no file that was there is edited, and the serving rehearsal runs
through them. Beside it: what a configuration without the new keys resolves
to, and what is refused before any process starts."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import BENCH, ROOT

import attribute_idle
import harness
import reduce_trace
import spec as spec_lib

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
TOY_CELL = "serve.toy.chat"
TOY_MODEL = "dlti_tpu.models.llama:LlamaForCausalLM"  # not the default name

# toy.py: the default reference behind another name, loaded by path.
TOY_REFERENCE = '''"""A toy family's reference: the default one, behind its own name."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "toy_default_reference", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "lib", "reference.py"))
_default = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_default)
sizes = _default.sizes


def forward(params, sizes, ids):
    return %s_default.forward(params, sizes, ids)
'''
SOUND, ZEROS = TOY_REFERENCE % "", TOY_REFERENCE % "0.0 * "


def _read_tree(top):
    out = {}
    for base, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[p] = fh.read()
    return out


def _write_json(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """(root of the copy, bytes of every benchmark file before the toy
    architecture was added). The program is linked in, not copied."""
    root = str(tmp_path_factory.mktemp("bench_copy"))
    bench = os.path.join(root, "benchmark")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for part in ("dlti_tpu", "scripts", "native"):
        os.symlink(os.path.join(ROOT, part), os.path.join(root, part))
    before = _read_tree(bench)

    def data(kind, name):
        with open(os.path.join(BENCH, kind, name + ".json")) as f:
            return json.load(f)

    config = data("configs", "mistral_7b")
    config.update(name="toy", reference="toy", program_model=TOY_MODEL)
    _write_json(os.path.join(bench, "configs", "toy.json"), config)
    with open(os.path.join(bench, "references", "toy.py"), "w") as f:
        f.write(SOUND)
    _write_json(os.path.join(bench, "rules", "toy.json"), {
        "programs": {"toy_draft": "jit_toy_draft"},
        "kernels": {"toy_attention": "toy_attention_kernel"},
        "kernels_per": {"toy_attention": "decode"}})
    cell = data("cells", "serve.mistral_7b.chat")
    cell["rehearsal"]["program_overrides"] = {"attention_impl": "reference"}
    _write_json(os.path.join(bench, "cells", TOY_CELL + ".json"), cell)
    _write_json(os.path.join(bench, "traffic", "toy_mix.json"),
                data("traffic", "chat"))
    with open(os.path.join(bench, "layer_metrics", "toy_attention_ms.py"),
              "w") as f:
        f.write("import attribute_idle\n\n\ndef read(ctx):\n    return "
                "attribute_idle.kernel_ms_per_step(ctx, 'toy_attention')\n")
    benchmark = json.loads(json.dumps(BENCHMARK))
    benchmark["configs"].append({
        "name": "toy", "source": config["source"],
        "file": "benchmark/configs/toy.json",
        "reduced": ["num_hidden_layers"], "why": "a toy architecture"})
    benchmark["workloads"].append({
        "name": TOY_CELL, "config": "toy", "traffic": "toy_mix", "chips": 1,
        "why": "a toy cell"})
    benchmark["per_layer"].append({
        "name": "toy_attention_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "itl_mean_ms", "workloads": [TOY_CELL]})
    for m in benchmark["end_to_end"]:
        if m["name"] in ("ttft_mean_ms", "itl_mean_ms"):
            m["workloads"].append(TOY_CELL)
    _write_json(os.path.join(root, "BENCHMARK.json"), benchmark)
    return root, before


def _untouched(before):
    for p, content in before.items():
        with open(p, "rb") as f:
            assert f.read() == content, f"{p} was edited"


def _rehearse(root, cell=TOY_CELL):
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483659", "--seconds", "3",
         "--trace", "0", "--rehearsal"],
        cwd=root, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    return proc, lines


@pytest.mark.parametrize("body,correct", [(SOUND, True), (ZEROS, False)],
                         ids=["sound_reference", "reference_of_zeros"])
def test_the_rehearsal_is_judged_by_the_reference_the_configuration_names(
        copy, body, correct):
    root, before = copy
    with open(os.path.join(root, "benchmark", "references", "toy.py"),
              "w") as f:
        f.write(body)
    proc, lines = _rehearse(root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(lines[-1])
    check = json.loads(lines[-2])["notes"]["reference_check"]
    assert check["reference"] == "benchmark/references/toy.py"
    assert check["program_model"] == TOY_MODEL
    # other bytes of toy.py, another key: nothing an earlier toy.py left
    # in .bench_cache/checks/ vouches for this one
    assert check["from_cache"] is False
    assert result["correct"] is correct, check
    assert (check["max_abs_logprob_diff"] < 1e-2) is correct
    assert result["failed"] == 0 and result["attempted"] > 0
    as_run = json.load(open(os.path.join(root, ".bench_runs", TOY_CELL,
                                         "model.json")))
    assert as_run["program"] == {"attention_impl": "reference"}
    assert as_run["model"]["hidden_size"] == 64, "model_overrides as before"
    _untouched(before)


def test_the_kept_references_key_covers_the_named_module_and_the_file(copy):
    root, before = copy
    cell = spec_lib.resolve_cell(TOY_CELL, root=root)
    toy = os.path.join(root, "benchmark", "references", "toy.py")

    def kept(cell=cell):
        run = harness.Run(cell, 1, 1.0, False, True, 0.0)
        path, _ = run.cached_reference(["inputs"],
                                       lambda out: open(out, "w").close())
        return path

    with open(toy, "w") as f:
        f.write(SOUND)
    first = kept()
    assert kept() == first, "the same bytes, the same key"
    with open(toy, "w") as f:
        f.write(SOUND + "# one more line\n")
    assert kept() != first, "the reference module's bytes"
    with open(toy, "w") as f:
        f.write(SOUND)
    for key, value in (("program_model", "dlti_tpu.models:LlamaForCausalLM"),
                       ("program", {"remat": False}), ("reference", None)):
        config = {**cell["config"], key: value}
        if value is None:
            del config[key]
        assert kept({**cell, "config": config}) != first, key
    _untouched(before)


def test_a_rules_file_adds_a_kernel_and_a_program_to_the_reduction(copy):
    root, before = copy
    bench = os.path.join(root, "benchmark")
    rule, device_rule = attribute_idle.rules(bench), reduce_trace.rules(bench)
    with open(os.path.join(BENCH, "fixtures", "hand_trace_spans.json")) as f:
        trace = json.load(f)
    device = trace["planes"][0]
    assert device["name"] == "/device:TPU:0"
    lines = {ln["name"]: ln["events"] for ln in device["lines"]}
    lines["XLA Ops"].append(
        ["%toy_attention_kernel.7 = bf16[] custom-call()", 2010, 40])
    lines["XLA Modules"].append(["jit_toy_draft(3)", 2200, 50])
    got = attribute_idle.attribute(trace, rule, device_rule)
    # 40 ns of the kernel over the two jit_decode executions of the trace
    assert got["kernels"]["toy_attention"]["events"] == 1
    assert got["kernels"]["toy_attention"]["ms_per_step"] == \
        pytest.approx(40 / 1e6 / 2)
    assert got["executions"]["toy_draft"] == 1
    assert reduce_trace.reduce(trace, device_rule)["programs"][
        "toy_draft"]["count"] == 1
    # what was there reads what it read
    base = attribute_idle.attribute(trace)
    assert got["kernels"]["paged_attention"] == \
        base["kernels"]["paged_attention"]
    assert "toy_attention" not in base["kernels"]
    assert got["spans"] == base["spans"]
    _untouched(before)


# -- a configuration without the new keys ------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_a_configuration_without_the_new_keys_resolves_to_todays_files(
        tmp_path, cell):
    got = spec_lib.resolve_cell(cell)
    assert not {"reference", "program_model"} & set(got["config"])
    assert spec_lib.reference_file(got["config"]) == \
        os.path.join(BENCH, "lib", "reference.py")
    assert spec_lib.program_model(got["config"]) == \
        ("dlti_tpu.models", "LlamaForCausalLM")
    run_config = harness.Run({**got, "root": str(tmp_path)}, 1, 1.0, False,
                             False, 0.0).config
    assert run_config == got["config"], "nothing is laid over a real run"


def test_without_rules_files_the_rules_are_the_two_base_files():
    assert not [f for f in os.listdir(os.path.join(BENCH, "rules"))
                if f.endswith(".json")], "this PR adds no rule"
    for name, got in (("span_rules.json", attribute_idle.rules()),
                      ("trace_rules.json", reduce_trace.rules())):
        with open(os.path.join(BENCH, "lib", name)) as f:
            assert got == json.load(f), name


# -- what is refused, and when -------------------------------------------------

def _rules_dir(tmp_path, files):
    bench = tmp_path / "benchmark"
    (bench / "lib").mkdir(parents=True)
    (bench / "rules").mkdir()
    for name in ("span_rules.json", "trace_rules.json"):
        shutil.copy(os.path.join(BENCH, "lib", name), bench / "lib" / name)
    for name, body in files.items():
        _write_json(bench / "rules" / name, body)
    return str(bench)


@pytest.mark.parametrize("section,key,value,rules", [
    ("kernels", "paged_attention", "another_kernel", attribute_idle.rules),
    ("kernels_per", "flash_attention", "decode", attribute_idle.rules),
    ("groups", "admit", {"under": ["engine/other"]}, attribute_idle.rules),
    ("clock_check", "decode", {"wait": [], "any_wait": []},
     attribute_idle.rules),
    ("programs", "decode", "jit_other", reduce_trace.rules),
])
def test_a_rules_file_that_repeats_a_key_of_the_base_is_refused(
        tmp_path, section, key, value, rules):
    bench = _rules_dir(tmp_path, {"mine.json": {section: {key: value}}})
    with pytest.raises(spec_lib.SpecError) as e:
        rules(bench)
    base = "trace_rules.json" if section == "programs" else "span_rules.json"
    assert "mine.json" in str(e.value) and base in str(e.value)
    assert f"{section}.{key}" in str(e.value)


def test_two_rules_files_with_one_key_are_refused_with_both_names(tmp_path):
    one = {"kernels": {"new": "k"}, "kernels_per": {"new": "decode"}}
    bench = _rules_dir(tmp_path, {"a.json": one,
                                  "b.json": {"kernels": {"new": "other"}}})
    with pytest.raises(spec_lib.SpecError) as e:
        attribute_idle.rules(bench)
    assert "a.json" in str(e.value) and "b.json" in str(e.value)


@pytest.mark.parametrize("body,word", [
    ({"stepper_marks": ["mine/step"]}, "stepper_marks"),
    ({"kernels": ["not", "an", "object"]}, "kernels"),
    ({"kernels": {"new": "k"}}, "kernels_per"),
], ids=["a_section_of_the_base_alone", "not_an_object",
        "a_kernel_without_its_program"])
def test_a_malformed_rules_file_is_refused(tmp_path, body, word):
    bench = _rules_dir(tmp_path, {"mine.json": body})
    with pytest.raises(spec_lib.SpecError, match=word):
        attribute_idle.rules(bench)


@pytest.mark.parametrize("lacks", ["sizes", "forward"])
def test_a_reference_that_lacks_a_name_is_refused_before_any_process_starts(
        copy, lacks):
    root, before = copy
    toy = os.path.join(root, "benchmark", "references", "toy.py")
    body = SOUND.replace("sizes = _default.sizes\n", "") \
        if lacks == "sizes" else SOUND.split("def forward")[0]
    with open(toy, "w") as f:
        f.write(body)
    try:
        with pytest.raises(spec_lib.SpecError, match=lacks):
            spec_lib.resolve_cell(TOY_CELL, root=root)
        shutil.rmtree(os.path.join(root, ".bench_runs"), ignore_errors=True)
        proc, lines = _rehearse(root)
        assert proc.returncode == 2 and not lines
        assert lacks in proc.stderr and "toy.py" in proc.stderr
        assert not os.path.exists(os.path.join(root, ".bench_runs")), \
            "no run directory, so no child"
    finally:
        with open(toy, "w") as f:
            f.write(SOUND)
    _untouched(before)


def test_a_training_cell_needs_the_references_gradient(copy):
    root, _ = copy
    bench = os.path.join(root, "benchmark")
    shutil.copy(os.path.join(bench, "cells", "train.mistral_7b.lora_sft.json"),
                os.path.join(bench, "cells", "train.toy.lora_sft.json"))
    benchmark = json.load(open(os.path.join(root, "BENCHMARK.json")))
    benchmark["workloads"].append({
        "name": "train.toy.lora_sft", "config": "toy", "traffic": "lora_sft",
        "chips": 1, "why": "a toy training cell"})
    _write_json(os.path.join(root, "BENCHMARK.json"), benchmark)
    with pytest.raises(spec_lib.SpecError, match="grad"):
        spec_lib.resolve_cell("train.toy.lora_sft", root=root)
    spec_lib.resolve_cell(TOY_CELL, root=root)  # serving needs none


@pytest.mark.parametrize("name", ["LlamaForCausalLM", "dlti_tpu.models:",
                                  ":LlamaForCausalLM"])
def test_a_model_constructor_that_is_not_module_and_callable_is_refused(name):
    with pytest.raises(spec_lib.SpecError, match="program_model"):
        spec_lib.program_model({"program_model": name})
