"""The jamba2_3b configuration, its training cell, the batch mix on
mistral_7b, the plain reference, the work functions and the readers this
brings: what the files say, read without a chip. Entries of
``BENCHMARK.json`` are found by name, never by position, so that a later
PR's additions leave this file green. (The cells' ``--rehearsal`` runs on
the CPU are ``test_bench_run.py``'s cases, which every cell of
``BENCHMARK.json`` gets.)"""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, os.path.join(BENCH, "lib"))

import flops  # noqa: E402
import jamba_work  # noqa: E402
import spec as spec_lib  # noqa: E402
import traffic as traffic_lib  # noqa: E402
from chip_child import model_fields  # noqa: E402

CELL = "train.jamba2_3b.long_doc_sft"
BATCH = "serve.mistral_7b.batch"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the issue's count, restated in the file's ``deployment``
MLP = 3 * 2560 * 8192
MIXER = (2560 * 10240 + 5120 * (4 + 1) + 5120 * 192 + 192 + 160 * 5120 + 5120
         + 5120 * 16 + 5120 + 5120 * 2560)
ATTENTION = 2 * 2560 * 2560 + 2 * 2560 * 128
PARAMETERS = (26 * (MIXER + MLP + 2 * 2560) + 2 * (ATTENTION + MLP + 2 * 2560)
              + 65536 * 2560 + 2560)
MATMUL = (26 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560 + MLP)
          + 2 * (ATTENTION + MLP) + 2560 * 65536)
ADAPTERS = 16 * (26 * ((2560 + 10240) + (5120 + 192) + (5120 + 2560))
                 + 2 * (2 * (2560 + 2560) + 2 * (2560 + 128)))
PATTERN = "SSSSSSSASSSSSSSSSSSSSASSSSSS"
NEW = ["mfu_pct.train.ssm", "ssm_scan_device_ms_per_step.train",
       "ssm_scan_hbm_pct.train", "ssm_state_resets_per_step.train"]
TARGETS = ["q_proj", "k_proj", "v_proj", "o_proj",
           "in_proj", "x_proj", "out_proj"]


@pytest.fixture(scope="module")
def cell():
    return spec_lib.resolve_cell(CELL)


def rehearsal_config(cell):
    config = copy.deepcopy(cell["config"])
    over = cell["cell"]["rehearsal"]
    config["model"].update(over["model_overrides"])
    config["program"].update(over["program_overrides"])
    return config


def test_the_training_cell_is_the_issues(cell):
    assert cell["chips"] == 1 and cell["traffic_name"] == "long_doc_sft"
    assert cell["config_name"] == "jamba2_3b"
    spec = cell["cell"]
    assert spec["kind"] == "train" and spec["entry"] == "scripts/train.py"
    args = spec["args"]
    assert (args["--lora-r"], args["--tokenizer"], args["--max-seq-len"],
            args["--per-device-batch-size"],
            args["--gradient-accumulation-steps"], args["--prefetch-depth"],
            args["--num-devices"]) == ("16", "byte", "8192", "2", "1", "2",
                                       "1")
    assert "--pack" in args and args["--pack"] is None
    assert int(args["--loss-chunk"]) > 0
    # the other arguments as the Mistral cell has them
    with open(os.path.join(BENCH, "cells",
                           "train.mistral_7b.lora_sft.json")) as f:
        mistral = json.load(f)["args"]
    for key in ("--preset", "--warmup-steps", "--num-train-epochs",
                "--save-strategy", "--logging-steps"):
        assert args[key] == mistral[key]
    assert set(args) - set(mistral) == {"--loss-chunk"}   # no new option
    check = spec["check"]
    assert (check["lora_r"], check["rows"], check["seq_len"]) == (16, 1, 8192)
    assert set(check["tolerance"]) == {"loss_abs", "token_logprob_rms",
                                       "grad_norm_rel", "grad_cosine_min"}
    over = spec["rehearsal"]["model_overrides"]
    assert (over["num_hidden_layers"], over["attn_layer_period"],
            over["attn_layer_offset"]) == (4, 4, 2)
    assert spec["rehearsal"]["program_overrides"]["layer_pattern"] == "SSAS"
    assert spec["rehearsal"]["check"]["tolerance"] == {
        "loss_abs": 0.001, "token_logprob_rms": 0.001,
        "grad_norm_rel": 0.01, "grad_cosine_min": 0.999}


def test_the_mix_is_long_documents_and_the_same_for_every_seed(cell):
    mix = cell["traffic"]
    assert mix["documents"] == {"count": 2048, "tokens": {
        "median": 1500, "sigma": 1.0, "min": 64, "max": 8192}}
    a = traffic_lib.training_documents(mix, 1)
    b = traffic_lib.training_documents(mix, 2147488001)
    lengths = sorted(len(t) for t in a)
    assert lengths == sorted(len(t) for t in b) and a != b
    assert len(lengths) == 2048
    assert 1300 < lengths[1024] < 1700                   # the median
    assert min(lengths) >= 62 and max(lengths) == 8190   # + BOS and EOS
    fills_a_row = sum(n == 8190 for n in lengths) / 2048
    assert 0.03 < fills_a_row < 0.07                     # about one in twenty
    tokens = traffic_lib.document_tokens(a, 8192)
    per_step = jamba_work.documents_per_step(a, 8192, 16384 * 0.99)
    assert 6.0 < per_step < 9.0 and tokens > 2048 * 1500


def test_the_batch_cell_is_the_chat_cells_server_on_the_batch_mix():
    cell = spec_lib.resolve_cell(BATCH)
    assert (cell["config_name"], cell["traffic_name"], cell["chips"]) == (
        "mistral_7b", "batch", 1)
    with open(os.path.join(BENCH, "cells", "serve.mistral_7b.chat.json")) as f:
        chat = json.load(f)
    assert cell["cell"]["args"] == chat["args"]
    assert cell["cell"]["check"] == chat["check"]
    mix = cell["traffic"]
    assert mix["arrivals"]["loop"] == "closed"
    assert (mix["arrivals"]["clients"], mix["arrivals"]["pool"]) == (32, 640)
    assert (mix["prompt_tokens"]["median"], mix["prompt_tokens"]["min"],
            mix["prompt_tokens"]["max"]) == (768, 65, 2048)
    assert (mix["output_tokens"]["median"], mix["output_tokens"]["min"],
            mix["output_tokens"]["max"]) == (256, 32, 1024)
    # every bucket a prompt of the mix can fall into is warmed, at every
    # row count the engine forms
    shapes = cell["cell"]["warm_up"]["shapes"]
    assert {int(b) for b in shapes} >= {128, 256, 512, 1024, 2048}
    assert all(rows == [1, 2, 4, 8] for rows in shapes.values())


def test_top_level_model_group_and_catalog_agree_and_nothing_is_cut(cell):
    config = cell["config"]
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"AI21-Jamba2-3B"' in line)
    assert config["source"] == row["source_url"]
    assert config["reduced"] == []
    bench = spec_lib.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "jamba2_3b")
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/jamba2_3b.json"
    assert len(entry["why"]) <= 200
    for key, published in row["config"].items():
        assert config[key] == published, key
        assert config["model"][key] == published, key
    assert set(config["model"]) - set(row["config"]) == {"torch_dtype"}
    m = config["model"]
    assert (m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["vocab_size"], m["attn_layer_period"], m["attn_layer_offset"],
            m["mamba_d_state"], m["mamba_dt_rank"], m["mamba_expand"],
            m["tie_word_embeddings"]) == (
                28, 2560, 8192, 20, 1, 65536, 14, 7, 16, 160, 2, True)
    assumed = config["assumed"]
    for key, value in (("order_of_kinds", PATTERN), ("head_dim", 128),
                       ("lora_targets", TARGETS),
                       ("float32_scan", "float32")):
        assert assumed[key]["value"] == value and assumed[key]["why"]
    for key in ("no_expert_layers", "mamba_inner_norms",
                "no_positional_encoding", "mamba_biases",
                "packed_documents"):
        assert assumed[key]["value"] and assumed[key]["why"]
    for key in ("seeded_weights", "torch_dtype", "model", "not_used"):
        assert assumed[key]
    assert f"{PARAMETERS:,}" in config["deployment"]
    assert f"{ADAPTERS:,}" in config["deployment"]


def test_three_counts_of_the_parameters_are_equal(cell):
    from dlti_tpu.config import ModelConfig

    config = cell["config"]
    cfg = ModelConfig(**model_fields(config))
    counted = jamba_work.parameters(config)
    assert cfg.num_params() == counted["total"] == PARAMETERS \
        == 3_029_337_472
    assert counted["mamba_layers"] == 26 * 104_161_472
    assert counted["attention_layers"] == 2 * 76_682_240
    assert counted["embedding"] == 167_772_160 and "head" not in counted
    assert MIXER == 41_241_792 and ATTENTION == 13_762_560


def test_the_program_is_given_every_size_and_each_convention(cell):
    from dlti_tpu.config import ModelConfig

    cfg = ModelConfig(**model_fields(cell["config"]))
    assert cfg.layer_pattern == PATTERN and cfg.is_jamba
    assert (cfg.rope, cfg.tie_embeddings, cfg.mamba_inner_norms,
            cfg.remat) == (False, True, True, True)
    assert (cfg.mamba_inner_size, cfg.mamba_state_size, cfg.mamba_dt_rank,
            cfg.mamba_conv_kernel, cfg.resolved_head_dim, cfg.num_kv_heads,
            cfg.rms_norm_eps) == (5120, 16, 160, 4, 128, 1, 1e-6)
    assert cfg.lora_targets == tuple(TARGETS)
    assert cfg.dtype == cfg.param_dtype == "bfloat16"
    from dlti_tpu.config import MODEL_PRESETS

    assert MODEL_PRESETS["jamba2_3b"] == cfg    # scripts/train.py --model
    tiny = ModelConfig(**model_fields(rehearsal_config(cell)))
    assert tiny.layer_pattern == "SSAS" and tiny.dtype == "float32"
    assert tiny.lora_targets == tuple(TARGETS)


def test_the_reference_is_one_file_that_knows_nothing_of_the_program(cell):
    path = spec_lib.reference_file(cell["config"])
    assert path.endswith("benchmark/references/jamba2_3b.py")
    with open(path) as f:
        text = f.read()
    body = text.split('"""', 2)[2]                     # past the docstring
    assert "dlti_tpu" not in body
    imports = [line for line in body.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import jax", "import jax.numpy as jnp"]
    spec_lib.check_reference_file(path, spec_lib.REFERENCE_OFFERS["train"])
    reference = spec_lib.load_reference(cell["config"], "train")
    sizes = reference.sizes(cell["config"])
    assert sizes["kinds"] == jamba_work.kinds(cell["config"]) == [
        "attention" if k == "A" else "mamba" for k in PATTERN]
    assert (sizes["heads"], sizes["kv_heads"], sizes["head_dim"],
            sizes["m_inner"], sizes["m_state"], sizes["m_conv"],
            sizes["m_dt_rank"], sizes["eps"], sizes["vocab"]) == (
                20, 1, 128, 5120, 16, 4, 160, 1e-6, 65536)
    # every document by itself: the row is cut where its segments change
    assert reference.documents([1, 1, 2, 2, 2, 0, 0]) == [(0, 2), (2, 5)]
    assert reference.documents([3, 3, 3]) == [(0, 3)]


@pytest.mark.parametrize("key,value,kinds", [
    ("attn_layer_offset", 0, ["attention", "mamba", "mamba", "mamba"]),
    ("attn_layer_period", 2, ["attention", "mamba", "attention", "mamba"]),
    ("num_hidden_layers", 3, ["mamba", "mamba", "attention"]),
])
def test_the_reference_follows_the_files_sizes(cell, key, value, kinds):
    config = rehearsal_config(cell)
    config["model"][key] = value
    if key == "attn_layer_period":
        config["model"]["attn_layer_offset"] = 0
    reference = spec_lib.load_reference(config, "train")
    assert reference.sizes(config)["kinds"] == kinds
    assert jamba_work.kinds(config) == kinds


def test_work_functions_against_a_hand_count(cell):
    config = cell["config"]
    assert jamba_work.is_family(config)
    assert not jamba_work.is_family(
        spec_lib.resolve_cell("train.mistral_7b.lora_sft")["config"])
    assert jamba_work.matmul_parameters(config) == MATMUL == 3_026_124_800
    assert jamba_work.lora_parameters(config, 16) == ADAPTERS == 11_229_184
    # attention in two layers: forward 2 products x 2 x 20 heads x 128 a
    # key, backward twice that
    assert jamba_work.attention_flops_per_token(config, 1000.0) == \
        3 * 2 * (2 * 2 * 20 * 128 * 1000.0)
    per_token = jamba_work.train_flops_per_token(config, 16, 1000.0)
    assert per_token == 4 * MATMUL + 6 * ADAPTERS + 3 * 2 * 10240 * 1000.0
    assert 12.1e9 < per_token < 12.3e9
    # the scan of one layer: 4 x 5120 + 32 values forward, 7 x 5120 + 64
    # backward, float32
    assert jamba_work.scan_bytes_per_token(config) == 4 * (
        (4 * 5120 + 32) + (7 * 5120 + 64)) == 225_664
    assert jamba_work.scan_bytes_per_step(config, 16000.0) == \
        26 * 16000.0 * 225_664
    texts = ["x" * 98, "y" * 298, "z" * 9000]
    assert jamba_work.document_lengths(texts, 8192) == [100, 300, 8192]
    assert jamba_work.documents_per_step(texts, 8192, 2 * 8592) == \
        pytest.approx(6.0)


def _ctx(cell, rows, tokens_per_s=4000.0, kind="TPU v5 lite",
         platform="tpu"):
    return {"cell": cell, "config": cell["config"], "spec": cell["cell"],
            "device": {"platform": platform, "kind": kind},
            "texts": ["x" * 998, "y" * 1998], "seq_len": 8192,
            "tokens_per_step": 16000.0, "rows": rows, "trace": None,
            "values": {"train_tokens_per_s_per_chip": tokens_per_s},
            "profile_dir": None}


ROWS = [{"step": 4, "recurrent_state_resets": 7},
        {"step": 5, "recurrent_state_resets": 10}]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scope_or_counter_reads_as_nothing(
        cell, name, monkeypatch):
    """What the parent's program gives (it cannot run this configuration,
    but the driver lays these readers over its checkout for every cell's
    traced run): another configuration's cell, rows without the counter and
    a trace without the scope, so the reader returns None and the line
    leaves the metric out; nothing raises."""
    import scope_time

    monkeypatch.setattr(scope_time, "scope_s_per_call",
                        lambda ctx, program, prefix: None)
    read = spec_lib.load_layer_reader(name)
    other = spec_lib.resolve_cell("train.mistral_7b.lora_sft")
    assert read(_ctx(other, [{"step": 4}])) is None
    if name != "mfu_pct.train.ssm":        # (a host-clock share of the peak)
        assert read(_ctx(cell, [{"step": 4}])) is None
    assert read(_ctx(cell, [], platform="cpu", kind="cpu")) is None


def test_the_new_readers_read_hand_made_rows_and_a_hand_made_trace(
        cell, monkeypatch):
    import scope_time

    ctx = _ctx(cell, ROWS)
    read = spec_lib.load_layer_reader
    config = cell["config"]
    assert read("ssm_state_resets_per_step.train")(ctx) == 8.5
    keys = flops.mean_keys_seen([1000, 2000], None)
    assert keys == pytest.approx((1000 * 1001 / 2 + 2000 * 2001 / 2) / 3000)
    mfu = read("mfu_pct.train.ssm")(ctx)
    assert mfu == pytest.approx(
        100 * jamba_work.train_flops_per_token(config, 16, keys)
        * 4000.0 / 197e12)
    assert 24 < mfu < 26
    asked = []

    def scope(ctx, program, prefix):
        asked.append((program, prefix))
        return 1.25

    monkeypatch.setattr(scope_time, "scope_s_per_call", scope)
    assert read("ssm_scan_device_ms_per_step.train")(ctx) == 1250.0
    share = read("ssm_scan_hbm_pct.train")(ctx)
    assert share == pytest.approx(
        100 * (26 * 16000.0 * 225_664 / 819e9) / 1.25)
    assert 8 < share < 10
    assert set(asked) == {("train_step", "dlti_selective_scan")}


def test_the_scopes_the_readers_ask_for_are_the_programs():
    from dlti_tpu.models import mamba1

    import inspect

    text = inspect.getsource(mamba1)
    assert 'jax.named_scope("dlti_selective_scan_fwd")' in text
    assert 'jax.named_scope("dlti_selective_scan_bwd")' in text


def test_the_new_entries_are_found_by_name():
    bench = spec_lib.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(by_name) == len(bench["per_layer"])
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s_per_chip"
        reader = spec_lib._load_module(
            "r", os.path.join(BENCH, "layer_metrics", name + ".py"))
        assert (reader.NAME, reader.UNIT, reader.BETTER, reader.LAYER,
                reader.MOVES, reader.SOURCE) == (
            m["name"], m["unit"], m["better"], m["layer"], m["moves"],
            m["source"])
    accepted = {m["layer"] for m in bench["per_layer"]
                if m["name"] not in NEW}
    assert {by_name[n]["layer"] for n in NEW} - accepted == {
        "model (models/jamba.py, models/mamba1.py)"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert (cells[CELL]["config"], cells[CELL]["traffic"],
            cells[CELL]["chips"]) == ("jamba2_3b", "long_doc_sft", 1)
    assert (cells[BATCH]["config"], cells[BATCH]["traffic"],
            cells[BATCH]["chips"]) == ("mistral_7b", "batch", 1)
    assert all(len(cells[c]["why"]) <= 200 for c in (CELL, BATCH))
    # the training cell: the accepted training metrics but the Llama
    # family's share of the peak
    assert CELL in e2e["train_tokens_per_s_per_chip"]["workloads"]
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m["workloads"]}
    assert reported == set(NEW) | {
        "data_wait_share", "train_step_device_ms", "device_idle_share.train",
        "idle_attributed_share.train", "flash_attn_device_ms_per_step"}
    assert CELL not in by_name["mfu_pct.train"]["workloads"]
    # the batch cell: whatever lists both of its neighbours, and the three
    # that a closed loop adds
    for name in ("output_tokens_per_s", "itl_mean_ms"):
        assert BATCH in e2e[name]["workloads"]
    assert BATCH not in e2e["ttft_mean_ms"]["workloads"]
    for m in bench["per_layer"]:
        both = {"serve.mistral_7b.chat", "serve.qwen2_7b.batch"} \
            <= set(m["workloads"])
        extra = m["name"] in ("decode_slot_occupancy",
                              "device_idle_share.serve")
        assert (BATCH in m["workloads"]) == (both or extra), m["name"]
        for c in (CELL, BATCH):
            if c in m["workloads"]:
                assert c in e2e[m["moves"]].get("workloads", [c]), m["name"]
