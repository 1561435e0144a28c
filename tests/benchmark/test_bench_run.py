"""benchmark/run.py end to end on the CPU: each cell's tiny rehearsal prints
a well-formed last line; without an accelerator there is no result; the
parent never imports JAX; outside a checkout of the program it refuses."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_paths import ROOT

BENCHMARK = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(cell, trace, extra=(), cwd=ROOT, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483659", "--seconds", "3",
         "--trace", str(trace), *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def last_line(proc):
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    return json.loads(lines[-1]), (json.loads(lines[-2]) if len(lines) > 1
                                   else {})


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_a_well_formed_result(cell):
    proc = run_cell(cell, 0, ["--rehearsal"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    result, notes = last_line(proc)
    assert set(result) == RESULT_KEYS | {"rehearsal"}
    assert result["rehearsal"] is True, "a rehearsal says what it is"
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, notes
    assert result["attempted"] > 0 and result["failed"] == 0
    wanted = {m["name"] for m in BENCHMARK["end_to_end"]
              if cell in m.get("workloads", [cell])}
    assert set(result["metrics"]) == wanted
    for name, m in result["metrics"].items():
        unit = next(x["unit"] for x in BENCHMARK["end_to_end"]
                    if x["name"] == name)
        assert m["unit"] == unit and m["value"] > 0
    assert notes["notes"]["compilations_in_window"] == 0
    check = notes["notes"]["reference_check"]
    if "max_abs_logprob_diff" in check:
        # prefill then paged decode against the reference's full forward
        assert check["max_abs_logprob_diff"] < 1e-2


def test_traced_rehearsal_reports_per_layer_metrics_only():
    cell = "serve.mistral_7b.chat"
    proc = run_cell(cell, 1, ["--rehearsal"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    result, _ = last_line(proc)
    allowed = {m["name"] for m in BENCHMARK["per_layer"]
               if cell in m.get("workloads", [cell])}
    assert result["metrics"], "counters can be read on the CPU too"
    assert set(result["metrics"]) <= allowed
    assert "setup_s" not in result["metrics"]


def test_no_accelerator_no_result_and_no_jax_in_the_parent(tmp_path):
    script = tmp_path / "drive.py"
    script.write_text(
        "import runpy, sys\n"
        "sys.argv = ['benchmark/run.py', '--workload',\n"
        "            'train.mistral_7b.lora_sft', '--seed', '7',\n"
        "            '--seconds', '1', '--trace', '0']\n"
        "code = None\n"
        "try:\n"
        "    runpy.run_path('benchmark/run.py', run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    code = e.code\n"
        "print('PARENT', code, 'jax' in sys.modules)\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(script)], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    lines = proc.stdout.strip().splitlines()
    assert lines[-1] == "PARENT 3 False", (proc.stdout, proc.stderr[-800:])
    assert not any(ln.startswith("{") for ln in lines), "no result line"
    assert "no accelerator" in proc.stderr


def test_outside_a_checkout_of_the_program_it_refuses(tmp_path):
    bare = str(tmp_path / "bare")
    os.makedirs(bare)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    for path in BENCHMARK["paths"]:
        shutil.copytree(os.path.join(ROOT, path), os.path.join(bare, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell("serve.qwen2_7b.batch", 0, cwd=bare, timeout=120)
    assert proc.returncode != 0
    assert not proc.stdout.strip(), "no result line"
    assert "checkout of the program" in proc.stderr


def test_an_unknown_workload_is_refused_with_the_known_ones_named():
    proc = run_cell("no.such.cell", 0)
    assert proc.returncode == 2 and not proc.stdout.strip()
    assert "serve.mistral_7b.chat" in proc.stderr
