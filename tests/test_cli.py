"""CLI surface tests: prepare -> train -> compare, serve args.

The reference's user surface is CLI scripts driven by a notebook
(SURVEY.md §1 L2/L4); these tests pin our equivalents end-to-end in fresh
interpreters (subprocess) exactly as a user would invoke them.
"""

import json
import os
import subprocess
import sys

import pytest

# Heavy jit-compile tier: excluded from the fast pre-commit gate
# (`pytest -m 'not slow'`); the full suite runs them.
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=420, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable] + args, cwd=REPO, env=env,
        capture_output=True, text=True, timeout=timeout,
    )


@pytest.fixture(scope="module")
def workdir(tmp_path_factory):
    return tmp_path_factory.mktemp("cli")


@pytest.fixture(scope="module")
def prepared_data(workdir):
    out = workdir / "data"
    proc = _run(["scripts/prepare_dataset.py", "--synthetic", "48",
                 "--output-dir", str(out)])
    assert proc.returncode == 0, proc.stderr[-2000:]
    return out


def test_prepare_dataset_format_contract(prepared_data):
    """Rows must follow the Llama-2 chat contract byte-for-byte."""
    from datasets import load_from_disk

    ds = load_from_disk(str(prepared_data))
    assert len(ds) == 48
    t = ds[0]["text"]
    assert t.startswith("<s>[INST] ") and " [/INST] " in t and t.endswith("</s>")


def test_prepare_dataset_from_jsonl(workdir):
    src = workdir / "pairs.jsonl"
    with open(src, "w") as f:
        f.write(json.dumps({"question": " q1 ", "answer": " a1 "}) + "\n")
    out = workdir / "from_jsonl"
    proc = _run(["scripts/prepare_dataset.py", "--input-json", str(src),
                 "--output-dir", str(out)])
    assert proc.returncode == 0, proc.stderr[-2000:]
    from datasets import load_from_disk

    assert load_from_disk(str(out))[0]["text"] == "<s>[INST] q1 [/INST] a1</s>"


@pytest.fixture(scope="module")
def trained_csv(workdir, prepared_data):
    csv = workdir / "metrics.csv"
    for preset, ndev in (("baseline", "1"), ("zero1", "8")):
        proc = _run([
            "scripts/train.py", "--preset", preset, "--num-devices", ndev,
            "--model", "llama_tiny", "--tokenizer", "byte",
            "--dataset-path", str(prepared_data),
            "--max-steps", "2", "--max-seq-len", "64", "--lora-r", "4",
            "--gradient-accumulation-steps", "1", "--warmup-steps", "1",
            "--save-strategy", "no", "--metrics-csv", str(csv),
            "--output-dir", str(workdir / f"ckpt_{preset}"),
        ])
        assert proc.returncode == 0, proc.stderr[-3000:]
    return csv


def test_train_cli_pipe_composes_with_zero_preset(workdir, prepared_data):
    """r05: --pipe composes with ZeRO presets from the CLI — --data sets
    the batch-row extent (zero1: 'data' axis) alongside the pipe stages."""
    proc = _run([
        "scripts/train.py", "--preset", "zero1", "--pipe", "2",
        "--data", "2",
        "--model", "llama_tiny", "--tokenizer", "byte",
        "--dataset-path", str(prepared_data),
        "--max-steps", "2", "--max-seq-len", "64", "--lora-r", "4",
        "--per-device-batch-size", "1",
        "--gradient-accumulation-steps", "2", "--warmup-steps", "1",
        "--save-strategy", "no",
        "--metrics-csv", str(workdir / "pipe_zero1.csv"),
        "--output-dir", str(workdir / "ckpt_pipe_zero1"),
    ])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert (workdir / "pipe_zero1.csv").exists()


def test_train_cli_plans_remat_unless_the_user_stated_one(workdir,
                                                          prepared_data):
    """With a budget to plan against (the CPU states no limit) the trainer
    keeps blocks and says so; ``--remat-stride`` is held to instead."""
    def run(name, *extra):
        log = workdir / f"{name}.jsonl"
        proc = _run([
            "scripts/train.py", "--preset", "baseline", "--num-devices", "1",
            "--model", "llama_debug", "--tokenizer", "byte",
            "--dataset-path", str(prepared_data),
            "--max-steps", "2", "--max-seq-len", "64", "--lora-r", "4",
            "--per-device-batch-size", "1", "--warmup-steps", "1",
            "--save-strategy", "no", "--hbm-budget-bytes", str(1 << 30),
            "--step-log", str(log),
            "--metrics-csv", str(workdir / f"{name}.csv"),
            "--output-dir", str(workdir / f"ckpt_{name}"), *extra])
        assert proc.returncode == 0, proc.stderr[-3000:]
        with open(log) as fh:
            rows = [json.loads(line) for line in fh]
        return proc.stderr + proc.stdout, next(
            r for r in rows if r.get("type") == "step")

    out, first = run("remat_planned")
    assert "remat: 4 of 4 blocks keep their activations; planned" in out
    assert first["remat_kept_blocks"] == 4
    assert 0 < first["remat_planned_bytes"] <= first["remat_limit_bytes"]
    out, first = run("remat_stated", "--remat-stride", "2")
    assert "unplanned (the kept-block count is stated)" in out
    assert first["remat_kept_blocks"] == 0


def test_train_cli_writes_reference_schema(trained_csv):
    import pandas as pd

    df = pd.read_csv(trained_csv)
    assert len(df) == 2
    for col in ("experiment", "num_gpus", "zero_stage", "strategy",
                "training_time_hours", "samples_per_second",
                "peak_memory_gb", "final_loss"):
        assert col in df.columns, f"reference CSV column {col} missing"
    assert set(df["experiment"]) == {"baseline", "zero1_8dev"}
    assert df["final_loss"].notna().all()


def test_train_cli_eval_loop(workdir, prepared_data):
    """--eval-dataset/--eval-steps reach Trainer._run_eval and the metrics
    CSV carries the eval_loss column."""
    csv = workdir / "metrics_eval.csv"
    proc = _run([
        "scripts/train.py", "--preset", "baseline", "--num-devices", "1",
        "--model", "llama_tiny", "--tokenizer", "byte",
        "--dataset-path", str(prepared_data),
        "--eval-dataset", str(prepared_data), "--eval-steps", "2",
        "--max-steps", "2", "--max-seq-len", "64", "--lora-r", "4",
        "--gradient-accumulation-steps", "1", "--warmup-steps", "1",
        "--save-strategy", "no", "--metrics-csv", str(csv),
        "--output-dir", str(workdir / "ckpt_eval"),
    ])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "eval @ step 2" in proc.stderr + proc.stdout
    import pandas as pd

    df = pd.read_csv(csv)
    assert "eval_loss" in df.columns and df["eval_loss"].notna().all()
    assert "peak_memory_source" in df.columns
    assert df["peak_memory_source"].isin(["device", "host_rss", "none"]).all()


def test_compare_cli(workdir, trained_csv):
    plot = workdir / "plots" / "cmp.png"
    proc = _run(["scripts/compare_training.py", "--csv", str(trained_csv),
                 "--plot-out", str(plot)], timeout=180)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "TRAINING COMPARISON" in proc.stdout
    assert "KEY FINDINGS" in proc.stdout
    assert plot.is_file()


def test_serve_cli_rejects_missing_model():
    proc = _run(["scripts/serve.py", "--tokenizer", "byte"], timeout=120)
    assert proc.returncode != 0
    assert "--model-dir or --random-init" in proc.stderr
