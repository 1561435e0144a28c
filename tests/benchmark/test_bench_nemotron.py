"""The nemotron3_nano_30b configuration, its cell and the readers it brings:
what the files say, read without a chip."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, os.path.join(BENCH, "lib"))

import hybrid_bytes  # noqa: E402
import spec as spec_lib  # noqa: E402
from chip_child import model_fields  # noqa: E402

CELL = "serve.nemotron3_nano_30b.tool_turns"


@pytest.fixture(scope="module")
def cell():
    return spec_lib.resolve_cell(CELL)


def test_the_cell_is_the_issues(cell):
    assert cell["chips"] == 1 and cell["traffic_name"] == "tool_turns"
    args, mix = cell["cell"]["args"], cell["traffic"]
    assert (args["--max-seqs"], args["--block-size"], args["--num-blocks"],
            args["--max-model-len"]) == ("32", "16", "4096", "4096")
    assert mix["arrivals"] == {"loop": "closed", "clients": 32,
                               "pool": 1280, "stagger_s": 0.1}
    assert mix["prompt_tokens"] == {"median": 768, "sigma": 0.6, "min": 65,
                                    "max": 2048}
    assert mix["output_tokens"] == {"median": 128, "sigma": 0.5, "min": 32,
                                    "max": 384}
    assert mix["ramp_s"] == 12.0
    assert {m["name"] for m in cell["end_to_end"]} == {
        "output_tokens_per_s", "itl_mean_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"decode_hbm_floor_pct", "moe_expert_load_max_over_mean",
            "prefill_device_ms_per_ktok.closed",
            "paged_attn_hbm_pct.hybrid"} <= names
    assert "paged_attn_hbm_pct" not in names  # counts k/v for every layer
    assert "--max-prefill-batch-tokens" not in args  # ISSUE 30's args alone


def test_the_configuration_names_its_reference_and_constructor(cell):
    config = cell["config"]
    assert spec_lib.reference_file(config) == os.path.join(
        BENCH, "references", "nemotron3_nano_30b.py")
    assert spec_lib.program_model(config) == ("dlti_tpu.models",
                                              "build_model")
    model, published = config["model"], config["published"]
    assert sorted(config["reduced"]) == sorted(published)
    for key, whole in published.items():
        assert model[key] != whole
    assert model["hybrid_override_pattern"] == \
        published["hybrid_override_pattern"][:model["num_hidden_layers"]]
    # floors of the cut: 13 layers, 64 of 128 experts, half the vocabulary
    assert model["num_hidden_layers"] == 13
    assert model["n_routed_experts"] * 2 == published["n_routed_experts"]
    assert model["vocab_size"] * 2 == published["vocab_size"]
    assert config["share"]["experts"] == [0, model["n_routed_experts"]]


def test_the_catalogs_keys_stand_at_the_top_level_as_run(cell):
    """The driver holds the file's TOP LEVEL to the catalog's entry (a key
    left out there reads as null and refuses the PR); the harness reads the
    group ``model``. One configuration, so the two say the same."""
    config = cell["config"]
    model = {k: v for k, v in config["model"].items() if k != "torch_dtype"}
    assert len(model) == 46  # the catalog entry's count of keys
    for key, value in model.items():
        assert key in config and config[key] == value, key
        assert type(config[key]) is type(value), key
    for key in config["reduced"]:
        assert config[key] != config["published"][key], key


def test_sizes_read_the_file_alone(cell):
    reference = spec_lib.load_reference(cell["config"], "serve")
    sizes = reference.sizes(cell["config"])
    assert sizes["pattern"] == "MEMEM*EMEMEM*"
    assert (sizes["experts"], sizes["held"], sizes["held_start"],
            sizes["top_k"], sizes["scaling"]) == (128, 64, 0, 6, 2.5)
    assert (sizes["m_heads"], sizes["m_head_dim"], sizes["m_inner"],
            sizes["m_groups"], sizes["m_state"], sizes["m_conv"]) == \
        (64, 64, 4096, 8, 128, 4)
    assert (sizes["heads"], sizes["kv_heads"], sizes["head_dim"],
            sizes["vocab"], sizes["hidden"]) == (32, 2, 128, 65536, 2688)
    # the program's own fields change nothing: sizes never reads them
    bent = {**cell["config"], "program": {"moe_held_count": 3}}
    assert reference.sizes(bent) == sizes
    moved = json.loads(json.dumps(cell["config"]))
    moved["share"]["experts"] = [64, 128]
    assert reference.sizes(moved)["held_start"] == 64


def test_the_programs_translation_agrees_with_the_file(cell):
    from dlti_tpu.config import ModelConfig

    cfg = ModelConfig(**model_fields(cell["config"]))
    model = cell["config"]["model"]
    assert cfg.layer_pattern == model["hybrid_override_pattern"]
    assert (cfg.moe_num_experts, cfg.moe_held, cfg.moe_held_start) == \
        (128, model["n_routed_experts"], 0)
    assert cfg.num_params() == 3_926_018_560  # 7.85 GB in bf16, as stated
    assert "3.926 B = 7.85 GB" in cell["config"]["deployment"]


def test_decode_bytes_are_a_floor_from_the_files_shapes(cell):
    per = hybrid_bytes.layer_weight_bytes(cell["config"]["model"])
    assert per["expert"] == 2 * 2 * 2688 * 1856
    parts = hybrid_bytes.decode_step_bytes(
        cell["config"], kv_itemsize=2, live_slots=32.0,
        context_tokens=32 * 900.0, experts_touched=5 * 50.0)
    assert parts["experts_touched"] == 250 * per["expert"]
    assert parts["head"] == 2 * 2688 * 65536 + 2 * 2688
    slot = 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert parts["recurrent_state"] == 2 * 32 * 6 * slot
    assert parts["keys_values"] == 32 * 900 * 2 * 2 * 2 * 128 * 2
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    # never more than holding every held weight, the state and the cache
    everything = 3_926_018_560 * 2 + 2 * 32 * 6 * slot + parts["keys_values"]
    assert parts["total"] < everything
    assert 5.5e9 < parts["total"] < 7.5e9


@pytest.mark.parametrize("name", ["decode_hbm_floor_pct",
                                  "moe_expert_load_max_over_mean",
                                  "prefill_device_ms_per_ktok.closed"])
def test_a_program_without_the_counters_reads_as_nothing(cell, name):
    """What the parent commit gives: no such series in /metrics, so the
    reader returns None and the line leaves the metric out."""
    read = spec_lib.load_layer_reader(name)
    scrape = {"dlti_decode_steps": 10.0, "dlti_decode_slot_steps": 300.0,
              "dlti_decode_context_tokens": 9000.0}
    ctx = {"trace": {"programs": {"decode": {"count": 5, "total_s": 0.1},
                                  "prefill": {"count": 0, "total_s": 0.0}}},
           "metrics_before": {k: 0.0 for k in scrape},
           "metrics_after": scrape, "config": cell["config"],
           "spec": cell["cell"], "device": {"platform": "tpu",
                                            "kind": "TPU v5 lite"}}
    assert read(ctx) is None


def test_readers_read_the_counters(cell):
    before = {"dlti_decode_steps": 0.0, "dlti_decode_slot_steps": 0.0,
              "dlti_decode_context_tokens": 0.0,
              "dlti_moe_experts_touched_decode": 0.0,
              "dlti_moe_expert_load_max_decode": 0.0,
              "dlti_moe_held_assignments_decode": 0.0}
    after = {"dlti_decode_steps": 100.0, "dlti_decode_slot_steps": 3200.0,
             "dlti_decode_context_tokens": 100 * 32 * 900.0,
             "dlti_moe_experts_touched_decode": 100 * 250.0,
             "dlti_moe_expert_load_max_decode": 600.0,
             "dlti_moe_held_assignments_decode": 100 * 480.0}
    ctx = {"trace": {"programs": {"decode": {"count": 50, "total_s": 1.0}}},
           "metrics_before": before, "metrics_after": after,
           "config": cell["config"], "spec": cell["cell"],
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    need = hybrid_bytes.decode_step_bytes(cell["config"], 2, 32.0,
                                          32 * 900.0, 250.0)["total"]
    floor = spec_lib.load_layer_reader("decode_hbm_floor_pct")(ctx)
    assert floor == pytest.approx(100 * need / 819e9 / 0.020)
    uneven = spec_lib.load_layer_reader("moe_expert_load_max_over_mean")(ctx)
    assert uneven == pytest.approx(6.0 / (480 / (5 * 64)))


def test_the_kernels_share_counts_the_attention_layers_alone(cell, monkeypatch):
    """``paged_attn_hbm_pct.hybrid``: keys and values of the two ``*`` layers
    of ``MEMEM*EMEMEM*``, not of thirteen; nothing without a pattern, the
    kernel's name or the counter."""
    import attribute_idle

    read = spec_lib.load_layer_reader("paged_attn_hbm_pct.hybrid")
    ctx = {"metrics_before": {"dlti_decode_context_tokens": 0.0,
                              "dlti_decode_steps": 0.0},
           "metrics_after": {"dlti_decode_context_tokens": 100 * 32 * 900.0,
                             "dlti_decode_steps": 100.0},
           "config": cell["config"], "spec": cell["cell"],
           "device": {"platform": "tpu", "kind": "TPU v5 lite"}}
    monkeypatch.setattr(attribute_idle, "kernel_ms_per_step",
                        lambda ctx, kernel: 1.85)
    bytes_a_step = 32 * 900 * 2 * 2 * 2 * 128 * 2
    assert read(ctx) == pytest.approx(
        100 * bytes_a_step / 819e9 / 1.85e-3)
    monkeypatch.setattr(attribute_idle, "kernel_ms_per_step",
                        lambda ctx, kernel: None)
    assert read(ctx) is None  # a trace without the kernel
    dense = {**cell["config"], "model": {
        k: v for k, v in cell["config"]["model"].items()
        if k != "hybrid_override_pattern"}}
    assert read({**ctx, "config": dense}) is None
