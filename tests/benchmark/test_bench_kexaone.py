"""The kexaone_236b configuration, its cell, its plain reference, its work
functions and the readers it brings: what the files say, read without a
chip. (The cell's ``--rehearsal`` run on the CPU is ``test_bench_run.py``'s
case ``test_rehearsal_prints_a_well_formed_result[serve.kexaone_236b.
mixed_lengths]``, which every cell of ``BENCHMARK.json`` gets.)"""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, os.path.join(BENCH, "lib"))

import spec as spec_lib  # noqa: E402
import traffic as traffic_lib  # noqa: E402
import window_bytes  # noqa: E402
from chip_child import model_fields  # noqa: E402

CELL = "serve.kexaone_236b.mixed_lengths"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the builder's count, restated in the file's ``deployment``
PARAMETERS = 3_712_028_416
NEW = ["kv_bytes_per_context_token", "kv_window_free_us_per_step",
       "paged_attn_hbm_pct.windows", "decode_hbm_floor_pct.windows",
       "prefill_mfu_pct.windows", "attn_over_cache_device_ms_per_ktok",
       "moe_expert_load_max_over_mean.windows"]
CUT = ["num_hidden_layers", "num_experts", "vocab_size",
       "num_nextn_predict_layers"]
CUT_LISTS = ["layer_types", "mlp_layer_types", "sliding_windows"]


@pytest.fixture(scope="module")
def cell():
    return spec_lib.resolve_cell(CELL)


def rehearsal_config(cell):
    config = copy.deepcopy(cell["config"])
    over = cell["cell"]["rehearsal"]
    config["model"].update(over["model_overrides"])
    config["program"].update(over["program_overrides"])
    return config


def test_the_cell_is_the_issues(cell):
    assert cell["chips"] == 1 and cell["traffic_name"] == "mixed_lengths"
    assert cell["config_name"] == "kexaone_236b"
    args, mix = cell["cell"]["args"], cell["traffic"]
    assert args == {"--max-seqs": "32", "--block-size": "16",
                    "--num-blocks": "32768", "--max-model-len": "16384",
                    "--kv-cache-dtype": "bfloat16"}     # no prefix caching
    assert mix["arrivals"] == {"loop": "closed", "clients": 32, "pool": 1280,
                               "stagger_s": 0.1}
    assert mix["prompt_tokens"] == {"median": 1024, "sigma": 1.3,
                                    "min": 128, "max": 16000}
    assert mix["output_tokens"] == {"median": 192, "sigma": 0.5, "min": 48,
                                    "max": 384}
    assert mix["ramp_s"] == 20.0
    # the longest prompt and its answer fit the model's length
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= int(args["--max-model-len"])
    assert cell["cell"]["check"]["prompt_tokens"] == [96, 700, 9000]
    assert {m["name"] for m in cell["end_to_end"]} == {
        "output_tokens_per_s", "itl_mean_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names
    assert {"decode_step_device_ms", "paged_attn_device_ms_per_step",
            "host_ms_per_step.decode_stage", "compiles_in_window",
            "prefill_device_ms_per_ktok.closed"} <= names
    # these would count every layer at the whole context, or other keys
    assert not {"paged_attn_hbm_pct", "decode_hbm_floor_pct",
                "moe_expert_load_max_over_mean.latent"} & names


def test_the_mix_is_one_wide_lognormal_short_and_long_in_one_queue(cell):
    pool = traffic_lib.request_pool(cell["traffic"], 1280, 1, 19200)
    lengths = sorted(q["prompt_tokens"] for q in pool)
    share = lambda n: sum(p > n for p in lengths) / len(lengths)  # noqa: E731
    assert lengths[0] == 128 and lengths[-1] == 16000
    assert 0.12 < 1 - share(255) < 0.17          # under 256
    assert 0.12 < share(4096) < 0.16 and 0.04 < share(8192) < 0.07
    assert 1900 < sum(lengths) / len(lengths) < 2200
    # the first batch of 32 already holds a 40-fold range
    first = [q["prompt_tokens"] for q in pool[:32]]
    assert max(first) / min(first) > 40
    # the same requests in the same order whatever the seed
    again = traffic_lib.request_pool(cell["traffic"], 1280, 2, 19200)
    assert [q["prompt_tokens"] for q in again] == \
        [q["prompt_tokens"] for q in pool]


def test_the_warm_up_covers_every_call_the_mix_can_form(cell):
    """A prompt of 128-16,000 tokens goes as calls of at most 2,048 padded
    tokens: whole prompts in their bucket, as many rows as fit the limit;
    what a longer one leaves after its 2,048-token calls, one row in any
    bucket."""
    shapes = {int(b): rows for b, rows in
              cell["cell"]["warm_up"]["shapes"].items()}
    limit = 2048
    for bucket in (128, 256, 512, 1024, 2048):
        widest = min(8, limit // bucket)
        assert shapes[bucket] == [r for r in (1, 2, 4, 8) if r <= widest]
    for bucket in (16, 32, 64):
        assert shapes[bucket] == [1], bucket
    assert max(b * max(rows) for b, rows in shapes.items()) == limit


def test_top_level_model_group_and_catalog_agree_but_for_the_cut(cell):
    config = cell["config"]
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"K-EXAONE-236B-A23B"' in line)
    assert config["source"] == row["source_url"]
    assert config["reduced"] == CUT + CUT_LISTS
    bench = spec_lib.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "kexaone_236b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    for key, published in row["config"].items():
        assert config["model"][key] == config[key], key
        if key in CUT:
            assert config["published"][key] == published
            assert config[key] != published
        elif key in CUT_LISTS:      # the published list, cut to the depth
            assert config[key] == published[:config["num_hidden_layers"]]
        else:
            assert config[key] == published, key
    assert set(config["model"]) - set(row["config"]) == {"torch_dtype"}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
                5, 16, 19200, 0)
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600,
        "num_nextn_predict_layers": 1}
    assert config["layer_types"] == ["sliding_attention"] * 3 + [
        "full_attention", "sliding_attention"]
    share = config["share"]
    assert share["chips_per_layer"] * config["num_experts"] == 128
    assert share["chips_per_layer"] * config["vocab_size"] == 153600
    assert (share["layers"], share["experts"], share["vocab_rows"]) == (
        [0, 5], [0, 16], [0, 19200])
    # no width is cut
    m = config["model"]
    assert (m["hidden_size"], m["intermediate_size"],
            m["moe_intermediate_size"], m["head_dim"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["num_experts_per_tok"], m["sliding_window"]) == (
                6144, 18432, 2048, 128, 64, 8, 8, 128)
    for key in ("qk_norm", "rope_on_full_layers", "post_sublayer_norm"):
        assert isinstance(config["assumed"][key]["value"], bool)
        assert config["assumed"][key]["why"]
    for key in ("seeded_weights", "shared_expert", "routing", "rope",
                "sliding_window", "layer_lists"):
        assert config["assumed"][key], key
    assert "3,712.0 M = 7.42 GB" in config["deployment"]
    assert "program_model" not in config       # the Llama family


def test_the_program_is_given_every_size_and_each_convention(cell):
    from dlti_tpu.config import ModelConfig
    from dlti_tpu.models import build_model
    from dlti_tpu.models.llama import LlamaForCausalLM

    config = cell["config"]
    cfg = ModelConfig(**model_fields(config))
    m, assumed = config["model"], config["assumed"]
    # the per-layer windows win over the published key's one window
    assert cfg.sliding_window == 128
    assert cfg.layer_windows == (128, 128, 128, 0, 128)
    assert cfg.kv_group_windows == (0, 128)
    assert (cfg.qk_norm, cfg.rope_on_full_layers, cfg.post_sublayer_norm) == (
        assumed["qk_norm"]["value"], assumed["rope_on_full_layers"]["value"],
        assumed["post_sublayer_norm"]["value"]) == (True, False, False)
    assert cfg.rope_theta == m["rope_parameters"]["rope_theta"] == 1e6
    assert cfg.first_k_dense == m["first_k_dense_replace"] == 1
    assert (cfg.moe_num_experts, cfg.moe_held_start, cfg.moe_held) == (
        config["published"]["num_experts"], config["share"]["experts"][0],
        m["num_experts"])
    assert cfg.num_experts == 0                    # not the capacity layer
    assert cfg.moe_shared_intermediate_size == \
        m["num_shared_experts"] * m["moe_intermediate_size"]
    assert cfg.moe_routed_scaling == m["routed_scaling_factor"]
    assert (cfg.num_layers, cfg.vocab_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.num_experts_per_tok) == (
                5, 19200, 64, 8, 128, 8)
    assert cfg.num_nextn_predict_layers == 0
    assert isinstance(build_model(cfg), LlamaForCausalLM)
    assert cfg.num_params() == PARAMETERS
    assert window_bytes.parameters(config)["total"] == PARAMETERS
    # the tiny stand-in of the rehearsal keeps the structure
    tiny = ModelConfig(**model_fields(rehearsal_config(cell)))
    assert tiny.layer_windows == (8, 8, 8, 0, 8) and tiny.moe_held == 4
    assert tiny.kv_group_windows == (0, 8) and tiny.qk_norm


def test_the_reference_is_one_file_that_knows_nothing_of_the_program(cell):
    path = spec_lib.reference_file(cell["config"])
    assert path.endswith("benchmark/references/kexaone_236b.py")
    with open(path) as f:
        text = f.read()
    assert "dlti_tpu" not in text.split('"""', 2)[2]   # past the docstring
    spec_lib.check_reference_file(path, spec_lib.REFERENCE_OFFERS["serve"])
    sizes = spec_lib.load_reference(cell["config"], "serve").sizes(
        cell["config"])
    assert sizes["windows"] == [128, 128, 128, 0, 128]
    assert (sizes["held_start"], sizes["held"], sizes["experts"],
            sizes["top_k"]) == (0, 16, 128, 8)
    assert (sizes["qk_norm"], sizes["rope_on_full_layers"],
            sizes["post_sublayer_norm"]) == (True, False, False)


@pytest.fixture(scope="module")
def tiny_sides(cell):
    """The rehearsal's stand-in: the program's model and weights, and the
    reference's sizes from the same file."""
    import jax
    import jax.numpy as jnp

    from dlti_tpu.config import ModelConfig
    from dlti_tpu.models import LlamaForCausalLM

    config = rehearsal_config(cell)
    model = LlamaForCausalLM(ModelConfig(**model_fields(config)), None)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    reference = spec_lib.load_reference(config, "serve")
    ids = (jnp.arange(150) * 37 + 11) % 509 + 3
    return {"config": config, "model": model, "params": params,
            "reference": reference, "ids": ids}


def test_the_reference_agrees_with_the_program_on_the_stand_in(tiny_sides):
    import jax.numpy as jnp

    t = tiny_sides
    ours = t["model"].apply({"params": t["params"]}, t["ids"][None])[0][0]
    theirs = t["reference"].forward(
        t["params"], t["reference"].sizes(t["config"]), t["ids"])
    assert float(jnp.abs(ours - theirs).max()) < 2e-4


@pytest.mark.parametrize("name", ["qk_norm", "rope_on_full_layers",
                                  "post_sublayer_norm"])
def test_the_reference_follows_the_files_conventions(tiny_sides, name):
    """Each convention flipped in ``assumed`` changes the reference's
    logits, and the program given the same value in ``program`` follows."""
    import jax.numpy as jnp

    from dlti_tpu.config import ModelConfig
    from dlti_tpu.models import LlamaForCausalLM

    t = tiny_sides
    config = copy.deepcopy(t["config"])
    value = not config["assumed"][name]["value"]
    config["assumed"][name]["value"] = value
    config["program"][name] = value
    sizes = t["reference"].sizes(config)
    assert sizes[name] is value
    stated = t["reference"].forward(
        t["params"], t["reference"].sizes(t["config"]), t["ids"])
    flipped = t["reference"].forward(t["params"], sizes, t["ids"])
    assert float(jnp.abs(stated - flipped).max()) > 1e-2
    model = LlamaForCausalLM(ModelConfig(**model_fields(config)), None)
    params = t["params"]
    if name == "qk_norm":       # a tree without the two norms
        params = {**params, "model": {
            n: ({**layer, "attn": {k: v for k, v in layer["attn"].items()
                                   if k not in ("q_norm", "k_norm")}}
                if n.startswith("layers_") else layer)
            for n, layer in params["model"].items()}}
    ours = model.apply({"params": params}, t["ids"][None])[0][0]
    assert float(jnp.abs(ours - flipped).max()) < 2e-4


def test_work_functions_against_a_hand_count(cell):
    config = cell["config"]
    model = config["model"]
    assert window_bytes.layer_kinds(model) == (1, 4, 128)
    assert window_bytes.cache_bytes_a_token(model, 2) == 4096
    assert window_bytes.attention_parameters(model) == \
        2 * 6144 * 8192 + 2 * 6144 * 1024 + 256
    parts = window_bytes.parameters(config)
    assert parts["routed_experts"] == 4 * 16 * 3 * 6144 * 2048
    assert parts["dense_mlp"] == 3 * 6144 * 18432
    assert parts["routers"] == 4 * (6144 * 128 + 128)
    # 32 slots at 2,000 tokens: the full layer reads all, four layers 128
    live = window_bytes.live_cache_bytes(model, 2, 64000.0, 32 * 128.0)
    assert live == 4096 * (64000 + 4 * 4096)
    step = window_bytes.decode_step_bytes(config, 2, 64000.0, 4096.0, 56.0)
    assert step["keys_and_values"] == live
    assert step["experts_touched"] == 56 * 2 * 3 * 6144 * 2048
    assert step["head"] == 2 * 6144 * 19200 + 4 * 6144
    # untouched experts are not in the floor: under the weights as held
    assert step["total"] < 2 * PARAMETERS + live
    assert 6.5e9 < step["total"] < 7.4e9
    flop = window_bytes.prefill_flops(config, 1000.0, 1000.0 * 3000, 1000.0 * 128)
    assert flop["attention_products"] == 4 * 64 * 128 * (
        1000 * 3000 + 4 * 1000 * 128)
    # a token uses top-8 of 128 of which 16 are held: one routed expert
    assert flop["experts"] == 1000 * 4 * 2 * (
        3 * 6144 * 2048 * (1.0 + 1) + 6144 * 128)
    assert 2.3e9 < flop["total"] / 1000 < 2.7e9


def _ctx(cell, before, after, trace):
    return {"metrics_before": before, "metrics_after": after, "trace": trace,
            "config": cell["config"], "spec": cell["cell"],
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "profile_dir": None}


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_as_nothing(cell, name):
    """What the parent's program gives: no such series in /metrics and no
    scope in the trace, so the reader returns None and the line leaves the
    metric out; nothing raises."""
    read = spec_lib.load_layer_reader(name)
    scrape = {"dlti_decode_steps": 10.0, "dlti_decode_context_tokens": 9e3,
              "dlti_prefill_tokens": 4e4, "dlti_prefill_batches": 20.0,
              "dlti_prefill_attention_pairs": 4e7,
              "dlti_moe_experts_touched_decode": 500.0}
    trace = {"programs": {"decode": {"count": 5, "total_s": 0.1},
                          "prefill": {"count": 4, "total_s": 0.7}}}
    assert read(_ctx(cell, dict.fromkeys(scrape, 0.0), scrape, trace)) is None
    assert read(_ctx(cell, {}, {}, None)) is None


def test_the_new_readers_read_a_hand_made_scrape_and_trace(cell, monkeypatch):
    import attribute_idle
    import scope_time

    before = dict.fromkeys((
        "dlti_decode_steps", "dlti_decode_context_tokens",
        "dlti_decode_window_context_tokens",
        "dlti_moe_experts_touched_decode", "dlti_prefill_tokens",
        "dlti_prefill_context_tokens", "dlti_prefill_attention_pairs",
        "dlti_prefill_window_attention_pairs", "dlti_prefill_batches",
        "dlti_kv_window_free_seconds_total",
        "dlti_moe_expert_load_max_decode",
        "dlti_moe_held_assignments_decode"), 0.0)
    after = {"dlti_decode_steps": 100.0,
             "dlti_decode_context_tokens": 100 * 64000.0,
             "dlti_decode_window_context_tokens": 100 * 4096.0,
             "dlti_moe_experts_touched_decode": 100 * 56.0,
             "dlti_prefill_tokens": 40 * 1000.0,
             "dlti_prefill_context_tokens": 40 * 2000.0,
             "dlti_prefill_attention_pairs": 40 * 1000.0 * 3000,
             "dlti_prefill_window_attention_pairs": 40 * 1000.0 * 128,
             "dlti_prefill_batches": 40.0,
             "dlti_kv_window_free_seconds_total": 100 * 30e-6,
             # 32 tokens x top-8 of 128, 16 held: 32 held assignments a
             # layer a step, 2.0 a held expert; the fullest expert holds 7
             "dlti_moe_expert_load_max_decode": 100 * 7.0,
             "dlti_moe_held_assignments_decode": 100 * 4 * 32.0}
    for scrape, tokens, full, window in ((before, 50000.0, 3200.0, 300.0),
                                         (after, 70000.0, 4500.0, 310.0)):
        scrape["dlti_kv_context_tokens"] = tokens
        scrape['dlti_kv_blocks_in_use{group="full"}'] = full
        scrape['dlti_kv_blocks_in_use{group="window"}'] = window
    trace = {"programs": {"decode": {"count": 50, "total_s": 0.6},
                          "prefill": {"count": 10, "total_s": 0.5}}}
    ctx = _ctx(cell, before, after, trace)
    read = spec_lib.load_layer_reader
    block = 16 * 4096
    assert read("kv_bytes_per_context_token")(ctx) == pytest.approx(
        block * ((3200 + 4 * 300) / 50000 + (4500 + 4 * 310) / 70000) / 2)
    assert 5000 < read("kv_bytes_per_context_token")(ctx) < 6000
    assert read("kv_window_free_us_per_step")(ctx) == pytest.approx(30.0)
    assert read("moe_expert_load_max_over_mean.windows")(ctx) == \
        pytest.approx(7.0 / 2.0)
    # the latent family's reader looks for ``n_routed_experts``: not here
    assert "n_routed_experts" not in cell["config"]["model"]
    need = window_bytes.decode_step_bytes(cell["config"], 2, 64000.0, 4096.0,
                                          56.0)["total"]
    floor = read("decode_hbm_floor_pct.windows")(ctx)
    assert floor == pytest.approx(100 * need / 819e9 / 0.012)
    assert 60 < floor < 80
    flop = window_bytes.prefill_flops(cell["config"], 1000.0, 3e6,
                                      128e3)["total"]
    mfu = read("prefill_mfu_pct.windows")(ctx)
    assert mfu == pytest.approx(100 * flop / 197e12 / 0.05)
    assert 15 < mfu < 30
    monkeypatch.setattr(attribute_idle, "kernel_ms_per_step",
                        lambda ctx, kernel: 0.8)
    live = window_bytes.live_cache_bytes(cell["config"]["model"], 2,
                                         64000.0, 4096.0)
    assert read("paged_attn_hbm_pct.windows")(ctx) == pytest.approx(
        100 * live / 819e9 / 0.8e-3)
    monkeypatch.setattr(scope_time, "scope_s_per_call",
                        lambda ctx, program, prefix: 0.006
                        if prefix == "dlti_attn_over_cache" else None)
    assert read("attn_over_cache_device_ms_per_ktok")(ctx) == \
        pytest.approx(6.0 / 3.0)


def test_the_new_entries_are_appended_and_list_the_new_cell_alone():
    bench = spec_lib.load_benchmark()
    assert bench["configs"][-1]["name"] == "kexaone_236b"
    assert bench["workloads"][-1]["name"] == CELL
    assert len(bench["workloads"][-1]["why"]) <= 200
    new = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in new] == NEW
    for m in new:
        assert m["workloads"] == [CELL]
        reader = spec_lib._load_module(
            "r", os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
        assert (reader.NAME, reader.UNIT, reader.BETTER, reader.LAYER,
                reader.MOVES, reader.SOURCE) == (
            m["name"], m["unit"], m["better"], m["layer"], m["moves"],
            m["source"])
    for m in bench["per_layer"][:-len(NEW)] + bench["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL, m["name"]
    assert len(bench["workloads"]) == 7 and len(bench["configs"]) == 6
