"""The kanana2_30b configuration as files: the catalog's keys stand at the
top level (where the driver's check reads them) and again in the ``model``
group (which the harness reads), equal; the cut is stated; the program's
translation carries every size the latent-attention family needs; the
benchmark's counts of the new kernel's work are the arithmetic they say."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "lib"))

import latent_bytes  # noqa: E402
import spec as spec_lib  # noqa: E402
from chip_child import model_fields  # noqa: E402

from dlti_tpu.config import ModelConfig  # noqa: E402

CELL = "serve.kanana2_30b.doc_turns"
NOT_OF_THE_CATALOG = {
    "name", "source", "reference", "program_model", "model", "published",
    "reduced", "share", "assumed", "program", "deployment"}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana2_30b.json")) as f:
        return json.load(f)


def test_top_level_and_model_group_are_equal(config):
    top = {k: v for k, v in config.items() if k not in NOT_OF_THE_CATALOG}
    model = {k: v for k, v in config["model"].items() if k != "torch_dtype"}
    assert top == model
    assert len(top) == 34


def test_the_cut_is_depth_experts_held_and_vocabulary_alone(config):
    assert sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 48, "n_routed_experts": 128,
        "vocab_size": 128256}
    m = config["model"]
    assert (m["num_hidden_layers"], m["n_routed_experts"],
            m["vocab_size"]) == (12, 64, 64128)
    share = config["share"]
    assert share["chips_per_layer"] * m["n_routed_experts"] == 128
    assert share["chips_per_layer"] * m["vocab_size"] == 128256
    assert share["pipeline_stages"] * m["num_hidden_layers"] == 48
    assert (share["layers"], share["experts"], share["vocab_rows"]) == (
        [0, 12], [0, 64], [0, 64128])
    # no width is cut
    assert (m["hidden_size"], m["intermediate_size"],
            m["moe_intermediate_size"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
            m["num_experts_per_tok"], m["num_attention_heads"]) == (
                2048, 6144, 768, 512, 128, 64, 128, 6, 32)


def test_the_program_is_given_every_size_of_the_family(config):
    cfg = ModelConfig(**model_fields(config))
    m = config["model"]
    assert cfg.kv_lora_rank == m["kv_lora_rank"]
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"])
    assert cfg.rope_interleave is m["rope_interleave"] is True
    assert cfg.first_k_dense == m["first_k_dense_replace"]
    assert (cfg.moe_num_experts, cfg.moe_held_start, cfg.moe_held) == (
        config["published"]["n_routed_experts"], config["share"]["experts"][0],
        m["n_routed_experts"])
    assert cfg.moe_shared_intermediate_size == \
        m["n_shared_experts"] * m["moe_intermediate_size"]
    assert cfg.moe_routed_scaling == m["routed_scaling_factor"]
    assert cfg.mlp_activation == "silu"  # gated experts
    assert (cfg.num_layers, cfg.vocab_size, cfg.rope_theta,
            cfg.rms_norm_eps) == (12, 64128, 1e6, 1e-6)
    # the deployment's arithmetic: 4,045 M parameters at 2 bytes
    assert round(cfg.num_params() / 1e6) == 4045


def test_the_cell_resolves_and_is_run_as_the_issue_says(config):
    cell = spec_lib.resolve_cell(CELL, ROOT)
    assert cell["config"] == config and cell["chips"] == 1
    args = cell["cell"]["args"]
    assert args == {"--max-seqs": "32", "--block-size": "16",
                    "--num-blocks": "16384", "--max-model-len": "8704",
                    "--kv-cache-dtype": "bfloat16",
                    "--enable-prefix-caching": None}
    mix = cell["traffic"]
    assert mix["arrivals"] == {"loop": "closed", "clients": 32, "pool": 32,
                               "stagger_s": 0.25}
    assert [m["name"] for m in cell["end_to_end"]] == [
        "output_tokens_per_s", "itl_mean_ms", "setup_s"]
    names = {m["name"] for m in cell["per_layer"]}
    assert {"latent_attn_device_ms_per_step", "latent_attn_roofline_pct",
            "decode_hbm_floor_pct.latent", "prefix_hit_token_share",
            "moe_expert_load_max_over_mean.latent"} <= names
    assert "moe_expert_load_max_over_mean" not in names  # nemotron_h's reader
    assert not {n for n in names if n.startswith("paged_attn")}
    # every prefill program the window can meet is a warmed shape: a hit
    # leaves 1-16 tokens, in groups of up to 8 rows
    assert cell["cell"]["warm_up"]["shapes"]["16"] == [1, 2, 4, 8]
    rules = spec_lib.load_rules("span_rules.json", spec_lib.SPAN_SECTIONS,
                                cell["bench_dir"])
    assert rules["kernels"]["latent_attention"] == \
        "dlti_latent_attention_decode"
    assert rules["kernels_per"]["latent_attention"] == "decode"


def test_kernel_work_and_step_bytes_are_the_arithmetic_of_the_issue(config):
    m = config["model"]
    one = latent_bytes.kernel_work(m, 2, 1.0)
    assert one["bytes"] == 1152 * 12 and one["flops"] == 69632 * 12
    step = latent_bytes.decode_step_bytes(config, 2, 32 * 6300.0, 11 * 64.0)
    assert step["latents"] == 32 * 6300 * 13824
    # every held expert touched: the whole model but its embedding
    params = ModelConfig(**model_fields(config)).num_params()
    weights = step["total"] - step["latents"]
    assert weights == pytest.approx(2 * (params - 64128 * 2048), rel=2e-3)
    assert step["experts_touched"] == 11 * 64 * 3 * 2048 * 768 * 2


@pytest.mark.parametrize("name", [
    "latent_attn_device_ms_per_step", "latent_attn_roofline_pct",
    "decode_hbm_floor_pct.latent", "prefix_hit_token_share",
    "moe_expert_load_max_over_mean.latent"])
def test_a_reader_finds_nothing_where_the_program_has_nothing(config, name):
    """On the parent's checkout, and in every cell of another family, the
    new readers return None and do not raise."""
    read = spec_lib.load_layer_reader(name)
    other = {"model": {"hidden_size": 8}}
    for ctx in (
        {"trace": None, "config": other, "device": {"platform": "tpu"},
         "metrics_before": {}, "metrics_after": {}, "spec": {"args": {}}},
        {"trace": {"programs": {"decode": {"count": 0}}}, "config": config,
         "device": {"platform": "cpu", "kind": "cpu"},
         "metrics_before": None, "metrics_after": None,
         "spec": {"args": {"--kv-cache-dtype": "bfloat16"}}},
    ):
        assert read(ctx) is None


def test_prefix_hit_token_share_reads_the_servers_counters():
    read = spec_lib.load_layer_reader("prefix_hit_token_share")
    a = {"dlti_prefix_cached_tokens": 100.0, "dlti_prefill_tokens": 50.0,
         "dlti_prefix_restored_tokens": 0.0}
    b = {"dlti_prefix_cached_tokens": 1090.0, "dlti_prefill_tokens": 60.0,
         "dlti_prefix_restored_tokens": 0.0}
    assert read({"metrics_before": a, "metrics_after": b}) == 99.0


def test_expert_load_ratio_counts_the_layers_after_the_dense_ones(config):
    """11 expert layers of 64 held experts: 1,000 decode steps whose worst
    expert held 9 tokens a step against a mean of 1.5 read 6.0; the other
    reader, which counts layers by ``hybrid_override_pattern``, reads
    nothing here, and this one nothing in a patterned configuration."""
    read = spec_lib.load_layer_reader("moe_expert_load_max_over_mean.latent")
    a = {"dlti_decode_steps": 10.0, "dlti_moe_expert_load_max_decode": 5.0,
         "dlti_moe_held_assignments_decode": 7.0}
    b = {"dlti_decode_steps": 1010.0,
         "dlti_moe_expert_load_max_decode": 9005.0,
         "dlti_moe_held_assignments_decode": 7.0 + 1000 * 11 * 64 * 1.5}
    ctx = {"metrics_before": a, "metrics_after": b, "config": config}
    assert read(ctx) == pytest.approx(6.0)
    assert spec_lib.load_layer_reader("moe_expert_load_max_over_mean")(
        ctx) is None
    patterned = {"model": {"hybrid_override_pattern": "MEM*",
                           "num_hidden_layers": 4, "n_routed_experts": 64}}
    assert read({**ctx, "config": patterned}) is None
