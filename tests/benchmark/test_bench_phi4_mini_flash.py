"""The phi4_mini_flash configuration, its cell, its plain reference, its work
functions and the readers it brings: what the files say, read without a
chip. Entries of ``BENCHMARK.json`` are found by name, never by position, so
that a later PR's additions leave this file green. (The cell's
``--rehearsal`` run on the CPU is ``test_bench_run.py``'s case
``test_rehearsal_prints_a_well_formed_result[serve.phi4_mini_flash.
reasoning_turns]``, which every cell of ``BENCHMARK.json`` gets.)"""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, os.path.join(BENCH, "lib"))

import sambay_work  # noqa: E402
import spec as spec_lib  # noqa: E402
import traffic as traffic_lib  # noqa: E402
from chip_child import model_fields  # noqa: E402

CELL = "serve.phi4_mini_flash.reasoning_turns"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the builder's count, restated in the file's ``deployment``
MLP = 2560 * 20480 + 10240 * 2560
MAMBA = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 + 5120
         + 5120 * 16 + 5120 + 5120 * 2560)
ATTENTION = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + 4 * 64 + 128
MEMORY_UNIT = 2 * 2560 * 5120
CROSS = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
PARAMETERS = (200064 * 2560 + 32 * MLP + 9 * MAMBA + 9 * ATTENTION
              + 7 * MEMORY_UNIT + 7 * CROSS + 65 * 2 * 2560)
KV_BYTES_A_TOKEN = 2 * 20 * 64 * 2          # one pool: 10 rows of 128 x 2
STATE_BYTES_A_SLOT = 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
NEW = ["decode_hbm_floor_pct.sambay", "paged_attn_hbm_pct.sambay",
       "ssm_state_device_ms_per_step", "ssm_state_hbm_pct",
       "prefill_mfu_pct.sambay"]
PATTERN = "SDSDSDSDSDSDSDSDSDGXGXGXGXGXGXGX"


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
    assert cell["chips"] == 1 and cell["traffic_name"] == "reasoning_turns"
    assert cell["config_name"] == "phi4_mini_flash"
    assert len(cell["workload"]["why"]) <= 200
    args, mix = cell["cell"]["args"], cell["traffic"]
    # no prefix caching, no chunked prefill
    assert args == {"--max-seqs": "32", "--block-size": "16",
                    "--num-blocks": "4096", "--max-model-len": "1536",
                    "--kv-cache-dtype": "bfloat16"}
    assert mix["arrivals"] == {"loop": "closed", "clients": 32, "pool": 1280,
                               "stagger_s": 0.1}
    assert mix["prompt_tokens"] == {"median": 192, "sigma": 0.5, "min": 64,
                                    "max": 512}
    out = mix["output_tokens"]
    assert (out["median"], out["sigma"], out["min"]) == (448, 0.3, 256)
    # the one adjustment the issue leaves the builder: the upper clamp may
    # come down to 1 % of a window's counted tokens, in multiples of 16
    assert out["max"] <= 704 and out["max"] % 16 == 0
    assert (mix["ramp_s"], mix["after_window_s"], mix["drain_s"]) == (
        20.0, 0.0, 0.0)
    check = cell["cell"]["check"]
    assert check["prompt_tokens"] == [96, 300, 700]
    assert check["max_tokens"] == 16
    assert set(check["tolerance"]) == {"logprob_abs", "greedy_gap"}
    assert check["why"]


def test_the_mix_is_decode_heavy_and_the_same_for_every_seed(cell):
    pool = traffic_lib.request_pool(cell["traffic"], 1280, 1, 200064)
    prompts = sorted(q["prompt_tokens"] for q in pool)
    answers = sorted(q["max_tokens"] for q in pool)
    assert prompts[0] == 64 and prompts[-1] == 512
    assert answers[0] == 256
    assert answers[-1] == cell["traffic"]["output_tokens"]["max"]
    assert 180 < prompts[640] < 205 and 430 < answers[640] < 465
    # a request's prompt and answer fit --max-model-len, 32 of them the pool
    assert prompts[-1] + answers[-1] <= 1536
    assert 32 * -(-(prompts[-1] + answers[-1]) // 16) <= 4096 - 1
    again = traffic_lib.request_pool(cell["traffic"], 1280, 2 ** 31 + 5,
                                     200064)
    assert [(q["prompt_tokens"], q["max_tokens"]) for q in again] == \
        [(q["prompt_tokens"], q["max_tokens"]) for q in pool]
    assert again[0]["prompt"] != pool[0]["prompt"]


def test_the_warm_up_covers_every_call_the_mix_can_form(cell):
    """No prefix is cached and no prompt is chunked, so a prefill call is
    (rows padded to a power of two) x (the bucket of its longest row) with a
    table of bucket / 16 blocks; a call holds at most 2,048 padded tokens."""
    from dlti_tpu.models.sambay import PREFILL_CALL_TOKENS
    from dlti_tpu.serving.engine import EngineConfig

    args = cell["cell"]["args"]
    ec = EngineConfig(max_seqs=int(args["--max-seqs"]),
                      block_size=int(args["--block-size"]),
                      num_blocks=int(args["--num-blocks"]),
                      max_model_len=int(args["--max-model-len"]))
    mix, warm = cell["traffic"], cell["cell"]["warm_up"]

    def bucket(n):
        return next(b for b in ec.buckets() if n <= b)

    formed = {bucket(n) for n in range(mix["prompt_tokens"]["min"],
                                       mix["prompt_tokens"]["max"] + 1)}
    assert formed == {64, 128, 256, 512}
    shapes = {int(b): rows for b, rows in warm["shapes"].items()}
    for b in formed:
        widest = min(8, PREFILL_CALL_TOKENS // b)
        assert shapes[b] == [r for r in (1, 2, 4, 8) if r <= widest], b
        assert bucket(b - warm["below_bucket_by"]) == b
    # the check's prompts, the 700-token one past a 64-512 bucket
    for n in cell["cell"]["check"]["prompt_tokens"]:
        assert 1 in shapes[bucket(n)]
    assert bucket(700) == bucket(warm["blocker_tokens"]) == 1024
    assert set(shapes) == formed | {1024}


def test_top_level_model_group_and_catalog_agree_and_nothing_is_cut(cell):
    config = cell["config"]
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Phi-4-mini-flash-reasoning"' in line)
    assert config["source"] == row["source_url"]
    assert config["reduced"] == []
    bench = spec_lib.load_benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "phi4_mini_flash")
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/phi4_mini_flash.json"
    assert len(entry["why"]) <= 200
    for key, published in row["config"].items():
        assert config[key] == published, key
        assert config["model"][key] == published, key
    assert set(config["model"]) - set(row["config"]) == {"torch_dtype"}
    m = config["model"]
    assert (m["num_hidden_layers"], m["hidden_size"], m["intermediate_size"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["vocab_size"], m["sliding_window"], m["mb_per_layer"],
            m["tie_word_embeddings"]) == (
                32, 2560, 10240, 40, 20, 200064, 512, 2, True)
    assumed = config["assumed"]
    for key, value in (("mamba_d_state", 16), ("mamba_d_conv", 4),
                       ("mamba_expand", 2), ("mamba_dt_rank", 160),
                       ("order_of_kinds", PATTERN)):
        assert assumed[key]["value"] == value and assumed[key]["why"]
    for key in ("mamba_biases", "memory", "differential_attention",
                "attention_biases", "layer_norm", "no_positional_embedding",
                "window_counts_the_query", "mlp", "recurrent_state_dtype"):
        assert assumed[key]["value"] and assumed[key]["why"]
    for key in ("seeded_weights", "torch_dtype", "model", "not_used"):
        assert assumed[key]
    assert f"{PARAMETERS:,}" in config["deployment"]


def test_the_program_is_given_every_size_and_each_convention(cell):
    from dlti_tpu.config import ModelConfig
    from dlti_tpu.models import build_model
    from dlti_tpu.models.sambay import SambaYForCausalLM

    config = cell["config"]
    assert spec_lib.program_model(config) == ("dlti_tpu.models",
                                              "build_model")
    cfg = ModelConfig(**model_fields(config))
    assumed = config["assumed"]
    assert cfg.layer_pattern == PATTERN == assumed["order_of_kinds"]["value"]
    # layer_windows wins over the sliding_window model_fields translates
    assert cfg.sliding_window == 512
    assert cfg.layer_windows == tuple(
        512 if l % 2 and l < 16 else 0 for l in range(32))
    assert [cfg.window_of_layer(l) for l in (1, 15, 17, 19)] == [
        512, 512, None, None]
    assert cfg.kv_group_windows == (0, 512)
    assert (cfg.shared_memory_layer, cfg.shared_kv_layer) == (16, 17)
    assert (cfg.mamba_inner_size, cfg.mamba_state_size, cfg.mamba_conv_kernel,
            cfg.mamba_dt_rank) == (
        assumed["mamba_expand"]["value"] * 2560,
        assumed["mamba_d_state"]["value"], assumed["mamba_d_conv"]["value"],
        assumed["mamba_dt_rank"]["value"]) == (5120, 16, 4, 160)
    assert (cfg.num_layers, cfg.vocab_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.hidden_size, cfg.intermediate_size,
            cfg.max_seq_len) == (32, 200064, 40, 20, 64, 2560, 10240, 262144)
    assert cfg.tie_embeddings and cfg.attention_bias and not cfg.rope
    assert cfg.rms_norm_eps == config["model"]["layer_norm_eps"] == 1e-5
    assert cfg.dtype == cfg.param_dtype == "bfloat16"
    assert cfg.mamba_state_dtype == "float32"
    assert cfg.has_recurrent_state and cfg.is_sambay
    assert isinstance(build_model(cfg), SambaYForCausalLM)
    assert cfg.num_params() == PARAMETERS == 3_852_562_944
    assert sambay_work.parameters(config)["total"] == PARAMETERS
    # the tiny stand-in of the rehearsal keeps the published order of kinds
    tiny = ModelConfig(**model_fields(rehearsal_config(cell)))
    assert (tiny.layer_pattern, tiny.kv_group_windows, tiny.num_layers) == (
        "SDSDSDSDGXGX", (0, 16), 12)
    assert (tiny.shared_memory_layer, tiny.shared_kv_layer) == (6, 7)


def test_the_cache_is_what_the_deployment_says(cell):
    """Nine recurrent entries, eight window pools, one full pool, fourteen
    entries that hold nothing; shapes alone (no array is made)."""
    import jax
    import jax.numpy as jnp

    from dlti_tpu.config import ModelConfig
    from dlti_tpu.ops.kv_cache import init_cache

    cfg = ModelConfig(**model_fields(cell["config"]))
    cache = jax.eval_shape(lambda: init_cache(
        cfg, 4096, 16, 32, jnp.bfloat16, call_tokens=2048))
    by_kind = {}
    for kind, window, entry in zip(cfg.layer_pattern, cfg.layer_windows,
                                   cache):
        shapes = {k: (v.shape, v.dtype.name) for k, v in entry.items()}
        by_kind.setdefault((kind, window), []).append(shapes)
    assert {k: len(v) for k, v in by_kind.items()} == {
        ("S", 0): 9, ("D", 512): 8, ("D", 0): 1, ("G", 0): 7, ("X", 0): 7}
    assert by_kind["S", 0][0] == {"conv": ((32, 3, 5120), "bfloat16"),
                                  "ssm": ((32, 5120, 16), "float32")}
    # fused rows of 10 x 128 values: 5,120 B a token a pool, no padding
    assert by_kind["D", 0][0] == {"k": ((4096, 16, 1280), "bfloat16"),
                                  "v": ((4096, 16, 1280), "bfloat16")}
    assert by_kind["D", 512][0]["k"] == ((1257, 16, 1280), "bfloat16")
    assert by_kind["G", 0][0] == by_kind["X", 0][0] == {}
    nbytes = sum(v.size * v.dtype.itemsize
                 for v in jax.tree_util.tree_leaves(cache))
    assert nbytes == (4096 + 8 * 1257) * 16 * KV_BYTES_A_TOKEN \
        + 32 * STATE_BYTES_A_SLOT
    assert 1.25e9 < nbytes < 1.27e9
    assert (PARAMETERS * 2 + nbytes) / 16e9 > 0.55     # of the chip


def test_the_reference_is_one_file_that_knows_nothing_of_the_program(cell):
    path = spec_lib.reference_file(cell["config"])
    assert path.endswith("benchmark/references/phi4_mini_flash.py")
    with open(path) as f:
        text = f.read()
    body = text.split('"""', 2)[2]                     # past the docstring
    assert "dlti_tpu" not in body
    imports = [line for line in body.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import math", "import jax", "import jax.numpy as jnp"]
    spec_lib.check_reference_file(path, spec_lib.REFERENCE_OFFERS["serve"])
    reference = spec_lib.load_reference(cell["config"], "serve")
    sizes = reference.sizes(cell["config"])
    names = {"S": "mamba", "G": "memory_unit", "X": "cross"}
    assert sizes["kinds"] == sambay_work.kinds(cell["config"]) == [
        names.get(k) or ("window" if l < 16 else "full")
        for l, k in enumerate(PATTERN)]
    assert (sizes["memory_layer"], sizes["shared_kv_layer"], sizes["window"],
            sizes["heads"], sizes["kv_heads"], sizes["head_dim"],
            sizes["m_inner"], sizes["m_state"], sizes["m_conv"],
            sizes["m_dt_rank"], sizes["eps"]) == (
                16, 17, 512, 40, 20, 64, 5120, 16, 4, 160, 1e-5)
    # four plain softmaxes a pair of heads, heads of 64: not the padded form
    assert "a1 = _softmax_av(q1, k1" in text and "a2 = _softmax_av(q2, k2" \
        in text


@pytest.fixture(scope="module")
def tiny_sides(cell):
    """The rehearsal's stand-in: the program's model and weights, and the
    reference's sizes from the same file."""
    import jax
    import jax.numpy as jnp

    from dlti_tpu.config import ModelConfig
    from dlti_tpu.models import build_model

    config = rehearsal_config(cell)
    model = build_model(ModelConfig(**model_fields(config)))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    reference = spec_lib.load_reference(config, "serve")
    ids = (jnp.arange(100) * 37 + 11) % 509 + 3
    return {"config": config, "model": model, "params": params,
            "reference": reference, "ids": ids}


def test_the_reference_agrees_with_the_program_on_the_stand_in(tiny_sides):
    import numpy as np

    t = tiny_sides
    want = t["reference"].forward(
        t["params"], t["reference"].sizes(t["config"]), t["ids"])
    got, _ = t["model"].apply({"params": t["params"]}, t["ids"][None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("key,value", [
    ("sliding_window", 8),          # another window: the windowed layers
    ("mb_per_layer", 3),            # another order of kinds
    ("layer_norm_eps", 0.5),        # the norms' (and the head norm's) eps
])
def test_the_reference_follows_the_files_sizes(tiny_sides, key, value):
    """A size the program mistranslated shows as a disagreement: the
    reference reads the file, not the program."""
    import numpy as np

    t = tiny_sides
    config = copy.deepcopy(t["config"])
    config["model"][key] = value
    stated = t["reference"].forward(
        t["params"], t["reference"].sizes(t["config"]), t["ids"])
    try:
        other = t["reference"].forward(
            t["params"], t["reference"].sizes(config), t["ids"])
    except KeyError:
        return      # another order asks the tree for leaves it has not
    assert float(np.abs(np.asarray(other - stated)).max()) > 1e-2


def test_work_functions_against_a_hand_count(cell):
    config = cell["config"]
    parts = sambay_work.parameters(config)
    assert parts["mamba"] == 9 * MAMBA == 9 * 41_241_600
    assert parts["attention"] == 9 * ATTENTION == 9 * 19_668_864
    assert parts["memory_unit"] == 7 * MEMORY_UNIT == 7 * 26_214_400
    assert parts["cross"] == 7 * CROSS == 7 * 13_112_704
    assert parts["mlp"] == 32 * MLP == 32 * 78_643_200
    assert parts["layer_norms"] == 65 * 5120
    assert parts["embedding_and_head"] == 200064 * 2560     # tied: once
    assert parts["total"] == PARAMETERS
    assert sambay_work.kv_bytes_a_token(config, 2) == KV_BYTES_A_TOKEN == 5120
    assert sambay_work.state_bytes_a_slot(config) == STATE_BYTES_A_SLOT \
        == 3_225_600
    kv = sambay_work.kv_read_bytes(config, 2, 32 * 430.0, 32 * 400.0)
    assert kv["shared_pool"] == 8 * 5120 * 32 * 430       # eight readers
    assert kv["window_pools"] == 8 * 5120 * 32 * 400
    step = sambay_work.decode_step_bytes(config, 2, 32.0, 32 * 430.0,
                                         32 * 400.0)
    assert step["weights"] == 2 * PARAMETERS
    assert step["state_in_and_out"] == 2 * 32 * STATE_BYTES_A_SLOT
    assert step["keys_and_values"] == kv["total"]
    # the issue's floor: 9.4 ms of weights, 0.25 of state, ~1.3 of keys
    assert 9.3 < step["weights"] / 819e6 < 9.5
    assert 0.24 < step["state_in_and_out"] / 819e6 < 0.26
    assert 10.8 < step["total"] / 819e6 < 11.2            # ms
    ssm = sambay_work.ssm_step_bytes(config, 32.0)
    assert ssm["weights"] == 2 * 9 * MAMBA
    assert ssm["total"] == 2 * 9 * MAMBA + 2 * 32 * STATE_BYTES_A_SLOT
    flop = sambay_work.prefill_flops(config, 800.0, 4.0, 800.0 * 100, 800.0)
    early = 9 * (MAMBA - (5120 * 4 + 5120 + 5120 + 5120 * 16 + 5120)) \
        + 8 * (2560 * 5120 + 2560 * 2560) + 17 * MLP + 2560 * 2560
    late = 2 * 2560 * 2560 + MLP + 7 * MEMORY_UNIT + 7 * 2 * 2560 * 2560 \
        + 14 * MLP
    assert flop["every_token"] == 2 * 800 * early
    assert flop["one_token_a_row"] == 2 * 4 * late
    # the issue's split: 1,468 M of 3,340 M matrix parameters are the later
    # layers', 44 %
    assert early + late == PARAMETERS - 200064 * 2560 - 65 * 5120 - (
        9 * (5120 * 4 + 5120 + 5120 + 5120 * 16 + 5120)
        + 9 * (5120 + 2560 + 384) + 7 * (2560 + 2560 + 384))
    assert 1467e6 < late < 1469e6 and 0.43 < late / (early + late) < 0.45
    assert flop["window_attention"] == 20 * 2 * 2 * (64 + 128) * 8 * 80000
    assert flop["last_query_attention"] == 20 * 2 * 2 * (64 + 128) * 8 * 800
    assert flop["scan"] == 6 * 9 * 5120 * 16 * 800
    assert flop["total"] == sum(v for k, v in flop.items() if k != "total")


def _ctx(cell, before, after, trace):
    return {"metrics_before": before, "metrics_after": after, "trace": trace,
            "config": cell["config"], "spec": cell["cell"],
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "profile_dir": None}


SCRAPE = {"dlti_decode_steps": 2000.0,
          "dlti_decode_context_tokens": 2000 * 32 * 430.0,
          "dlti_decode_window_context_tokens": 2000 * 32 * 400.0,
          "dlti_decode_slot_steps": 2000 * 31.0,
          "dlti_recurrent_state_resets": 150.0,
          "dlti_prefill_tokens": 150 * 200.0, "dlti_prefill_batches": 100.0,
          "dlti_prefill_window_attention_pairs": 150 * 200.0 * 90,
          "dlti_prefill_attention_pairs": 150 * 200.0 * 100,
          "dlti_cross_decoder_prefill_tokens": 150 * 200.0}
TRACE = {"programs": {"decode": {"count": 150, "total_s": 150 * 0.0125},
                      "prefill": {"count": 8, "total_s": 8 * 0.050}}}


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_as_nothing(cell, name,
                                                         monkeypatch):
    """What the parent's program gives (it cannot run this configuration,
    but the driver lays these readers over its checkout for every cell's
    traced run): another configuration's cell, or no such series and no such
    scope, so the reader returns None and the line leaves the metric out;
    nothing raises."""
    import attribute_idle
    import scope_time

    monkeypatch.setattr(attribute_idle, "kernel_ms_per_step",
                        lambda ctx, kernel: 0.8)
    monkeypatch.setattr(scope_time, "scope_s_per_call",
                        lambda ctx, program, prefix: None)
    read = spec_lib.load_layer_reader(name)
    zero = dict.fromkeys(SCRAPE, 0.0)
    for other in ("serve.mistral_7b.chat",
                  "serve.nemotron3_nano_30b.tool_turns",
                  "serve.kexaone_236b.mixed_lengths"):
        assert read(_ctx(spec_lib.resolve_cell(other), zero, SCRAPE,
                         TRACE)) is None
    assert read(_ctx(cell, {}, {}, None)) is None
    bare = {k: v for k, v in SCRAPE.items()
            if "recurrent" not in k and "window" not in k}
    assert read(_ctx(cell, dict.fromkeys(bare, 0.0), bare, TRACE)) is None


def test_the_new_readers_read_a_hand_made_scrape_and_trace(cell, monkeypatch):
    import attribute_idle
    import scope_time

    ctx = _ctx(cell, dict.fromkeys(SCRAPE, 0.0), SCRAPE, TRACE)
    read = spec_lib.load_layer_reader
    config = cell["config"]
    need = sambay_work.decode_step_bytes(config, 2, 31.0, 32 * 430.0,
                                         32 * 400.0)["total"]
    floor = read("decode_hbm_floor_pct.sambay")(ctx)
    assert floor == pytest.approx(100 * need / 819e9 / 0.0125)
    assert 85 < floor < 90
    monkeypatch.setattr(attribute_idle, "kernel_ms_per_step",
                        lambda ctx, kernel: 2.4)
    kv = sambay_work.kv_read_bytes(config, 2, 32 * 430.0, 32 * 400.0)
    assert read("paged_attn_hbm_pct.sambay")(ctx) == pytest.approx(
        100 * kv["total"] / 819e9 / 2.4e-3)
    asked = []

    def scope(ctx, program, prefix):
        asked.append((program, prefix))
        return 1.6e-3

    monkeypatch.setattr(scope_time, "scope_s_per_call", scope)
    assert read("ssm_state_device_ms_per_step")(ctx) == pytest.approx(1.6)
    ssm = sambay_work.ssm_step_bytes(config, 31.0)["total"]
    share = read("ssm_state_hbm_pct")(ctx)
    assert share == pytest.approx(100 * ssm / 819e9 / 1.6e-3)
    assert 65 < share < 75
    assert set(asked) == {("decode", "dlti_mamba1")}
    flop = sambay_work.prefill_flops(config, 300.0, 1.5, 300.0 * 90,
                                     300.0)["total"]
    mfu = read("prefill_mfu_pct.sambay")(ctx)
    assert mfu == pytest.approx(100 * flop / 197e12 / 0.050)
    assert 10 < mfu < 14


def test_the_new_entries_are_found_by_name_and_list_the_new_cell_alone():
    bench = spec_lib.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(by_name) == len(bench["per_layer"])
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        reader = spec_lib._load_module(
            "r", os.path.join(BENCH, "layer_metrics", name + ".py"))
        assert (reader.NAME, reader.UNIT, reader.BETTER, reader.LAYER,
                reader.MOVES, reader.SOURCE) == (
            m["name"], m["unit"], m["better"], m["layer"], m["moves"],
            m["source"])
    # an accepted layer's name is used letter for letter; one layer is new
    accepted = {m["layer"] for m in bench["per_layer"]
                if m["name"] not in NEW}
    assert {by_name[n]["layer"] for n in NEW} - accepted == {
        "model (models/sambay.py, models/mamba1.py)"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name in ("output_tokens_per_s", "itl_mean_ms"):
        assert CELL in e2e[name]["workloads"]
    assert CELL not in e2e["ttft_mean_ms"]["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "phi4_mini_flash", "reasoning_turns", 1)
    # what the cell reports beside its own: the accepted metrics whose
    # readers read right for it, the looped stack's left out
    reported = {m["name"] for m in bench["per_layer"]
                if CELL in m["workloads"]}
    assert {"decode_step_device_ms", "paged_attn_device_ms_per_step",
            "kv_window_free_us_per_step", "compiles_in_window",
            "device_idle_share.serve"} <= reported
    assert not any(".loop" in n or n.startswith("loop_") for n in reported)
    assert "kv_bytes_per_context_token" not in reported
    for m in bench["per_layer"]:
        moved = e2e.get(m["moves"], {}).get("workloads", [CELL])
        if CELL in m["workloads"]:
            assert CELL in moved, m["name"]
