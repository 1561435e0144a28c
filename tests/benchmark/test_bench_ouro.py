"""The ouro_2_6b configuration, its cell, its plain reference, its work
functions and the readers it brings: what the files say, read without a
chip. Entries of ``BENCHMARK.json`` are found by name, never by position, so
that a later PR's additions leave this file green. (The cell's
``--rehearsal`` run on the CPU is ``test_bench_run.py``'s case
``test_rehearsal_prints_a_well_formed_result[serve.ouro_2_6b.
short_reasoning]``, which every cell of ``BENCHMARK.json`` gets.)"""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, os.path.join(BENCH, "lib"))

import loop_work  # noqa: E402
import spec as spec_lib  # noqa: E402
import traffic as traffic_lib  # noqa: E402
from chip_child import model_fields  # noqa: E402

CELL = "serve.ouro_2_6b.short_reasoning"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the builder's count, restated in the file's ``deployment``
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
PARAMETERS = 48 * LAYER + 2 * 49152 * 2048 + 2048 + 2049
CACHE_BYTES_A_TOKEN = 4 * 48 * 2 * 16 * 128 * 2
NEW = ["decode_hbm_floor_pct.loop", "paged_attn_hbm_pct.loop",
       "prefill_mfu_pct.loop", "kv_bytes_per_context_token.loop",
       "loop_exit_expected_pass"]


@pytest.fixture(scope="module")
def cell():
    return spec_lib.resolve_cell(CELL)


def rehearsal_config(cell):
    config = copy.deepcopy(cell["config"])
    config["model"].update(cell["cell"]["rehearsal"]["model_overrides"])
    return config


def test_the_cell_is_the_issues(cell):
    assert cell["chips"] == 1 and cell["traffic_name"] == "short_reasoning"
    assert cell["config_name"] == "ouro_2_6b"
    assert len(cell["workload"]["why"]) <= 200
    args, mix = cell["cell"]["args"], cell["traffic"]
    assert args == {"--max-seqs": "8", "--block-size": "16",
                    "--num-blocks": "256", "--max-model-len": "512",
                    "--kv-cache-dtype": "bfloat16"}     # no prefix caching
    assert mix["arrivals"] == {"loop": "closed", "clients": 8, "pool": 1280,
                               "stagger_s": 0.1}
    assert mix["prompt_tokens"] == {"median": 160, "sigma": 0.5, "min": 48,
                                    "max": 320}
    assert mix["output_tokens"] == {"median": 64, "sigma": 0.5, "min": 24,
                                    "max": 160}
    assert (mix["ramp_s"], mix["after_window_s"], mix["drain_s"]) == (
        12.0, 0.0, 0.0)
    assert cell["cell"]["setup_limit_s"] == 1800
    assert cell["cell"]["check"]["prompt_tokens"] == [40, 200, 440]
    assert cell["cell"]["check"]["max_tokens"] == 16
    # the longest prompt and its answer fit the model's length, and eight of
    # them the pool (block 0 is the trash block): no window preempts
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest <= int(args["--max-model-len"])
    assert 8 * -(-longest // 16) <= int(args["--num-blocks"]) - 1
    assert {m["name"] for m in cell["end_to_end"]} == {
        "output_tokens_per_s", "itl_mean_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names
    assert {"decode_step_device_ms", "paged_attn_device_ms_per_step",
            "decode_slot_occupancy", "device_idle_share.serve",
            "host_ms_per_step.decode_stage", "compiles_in_window",
            "prefill_device_ms_per_ktok.closed"} <= names
    # these count ``num_hidden_layers`` entries or another family's bytes
    # and would read four times off here
    assert not {n for n in names if n.split(".")[0] in (
        "paged_attn_hbm_pct", "kv_bytes_per_context_token",
        "decode_hbm_floor_pct", "prefill_mfu_pct")} - set(NEW)


def test_the_mix_is_one_narrow_range_and_a_hundred_completions_a_window(cell):
    pool = traffic_lib.request_pool(cell["traffic"], 1280, 1, 49152)
    prompts = sorted(q["prompt_tokens"] for q in pool)
    answers = sorted(q["max_tokens"] for q in pool)
    assert prompts[0] == 48 and prompts[-1] == 320
    assert answers[0] == 24 and answers[-1] == 160
    assert 150 < prompts[640] < 170 and 58 < answers[640] < 70
    # no request is more than 1 % of a window's tokens at the rate the
    # issue expects (140-200 tokens/s over 40 s), and a window completes a
    # hundred of them
    mean = sum(answers) / len(answers)
    assert 160 / (140 * 40) < 0.03 and 60 < mean < 80
    assert 140 * 40 / mean > 70
    # the same requests in the same order whatever the seed
    again = traffic_lib.request_pool(cell["traffic"], 1280, 2 ** 31 + 5,
                                     49152)
    assert [(q["prompt_tokens"], q["max_tokens"]) for q in again] == \
        [(q["prompt_tokens"], q["max_tokens"]) for q in pool]
    assert again[0]["prompt"] != pool[0]["prompt"]


def test_the_warm_up_covers_every_call_the_mix_can_form(cell):
    """No prefix is cached and no prompt is chunked, so a prefill call is
    (rows padded to a power of two up to 8) x (the bucket of the longest
    row's prompt, or of a preempted sequence's prompt and answer so far)
    with a table of bucket / 16 blocks."""
    from dlti_tpu.serving.engine import EngineConfig

    args = cell["cell"]["args"]
    ec = EngineConfig(max_seqs=int(args["--max-seqs"]),
                      block_size=int(args["--block-size"]),
                      num_blocks=int(args["--num-blocks"]),
                      max_model_len=int(args["--max-model-len"]))
    mix, warm = cell["traffic"], cell["cell"]["warm_up"]

    def bucket(n):
        return next(b for b in ec.buckets() if n <= b)

    lo = mix["prompt_tokens"]["min"]
    hi = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    formed = {bucket(n) for n in range(lo, hi + 1)}
    assert formed == {64, 128, 256, 512}
    shapes = {int(b): rows for b, rows in warm["shapes"].items()}
    assert set(shapes) == formed
    for b, rows in shapes.items():
        # 7 rows beside the blocker pad to the 8-row program
        assert rows == [1, 2, 4, 7], b
        tokens = b - warm["below_bucket_by"]
        assert bucket(tokens) == b
        # the widest group and the blocker fit the pool together
        assert 7 * -(-(tokens + 1) // 16) + -(-(
            warm["blocker_tokens"] + 1) // 16) <= ec.num_blocks - 1
    assert bucket(warm["blocker_tokens"]) == 512
    for n in cell["cell"]["check"]["prompt_tokens"]:
        assert bucket(n) in shapes


def test_top_level_model_group_and_catalog_agree_and_nothing_is_cut(cell):
    config = cell["config"]
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Ouro-2.6B"' in line)
    assert config["source"] == row["source_url"]
    assert config["reduced"] == [] and "published" not in config
    bench = spec_lib.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "ouro_2_6b")
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/ouro_2_6b.json"
    for key, published in row["config"].items():
        assert config[key] == published, key
        assert config["model"][key] == published, key
    assert set(config["model"]) - set(row["config"]) == {"torch_dtype"}
    m = config["model"]
    assert (m["num_hidden_layers"], m["total_ut_steps"], m["hidden_size"],
            m["intermediate_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["vocab_size"],
            m["early_exit_threshold"]) == (
                48, 4, 2048, 5632, 16, 16, 128, 49152, 1)
    for key in ("sandwich_norm", "final_norm_inside_the_loop",
                "cache_entry_per_pass_and_layer", "exit_gate"):
        assert config["assumed"][key]["value"] and \
            config["assumed"][key]["why"]
    for key in ("seeded_weights", "rope", "attention", "torch_dtype",
                "model"):
        assert config["assumed"][key]
    assert f"{PARAMETERS:,}" in config["deployment"]
    assert f"{CACHE_BYTES_A_TOKEN:,}" in config["deployment"]


def test_the_program_is_given_every_size_and_each_convention(cell):
    from dlti_tpu.config import ModelConfig
    from dlti_tpu.models import build_model
    from dlti_tpu.models.llama import LlamaForCausalLM

    config = cell["config"]
    assert spec_lib.program_model(config) == (
        "dlti_tpu.models", "LlamaForCausalLM")
    cfg = ModelConfig(**model_fields(config))
    m = config["model"]
    assert cfg.ut_steps == m["total_ut_steps"] == 4
    assert cfg.sandwich_norm is config["assumed"]["sandwich_norm"]["value"]
    assert not cfg.post_sublayer_norm and not cfg.qk_norm
    assert cfg.early_exit_threshold == m["early_exit_threshold"] == 1
    assert cfg.rope_theta == m["rope_theta"] == 1e6
    assert cfg.sliding_window is None and cfg.kv_group_windows == (0,)
    assert (cfg.num_layers, cfg.vocab_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.hidden_size, cfg.intermediate_size,
            cfg.max_seq_len) == (48, 49152, 16, 16, 128, 2048, 5632, 65536)
    assert not cfg.tie_embeddings and not cfg.attention_bias
    assert cfg.dtype == cfg.param_dtype == "bfloat16"
    assert isinstance(build_model(cfg), LlamaForCausalLM)
    assert cfg.cache_entries == 192
    assert cfg.cache_entries * 2 * cfg.num_kv_heads * cfg.resolved_head_dim \
        * 2 == CACHE_BYTES_A_TOKEN == 1_572_864
    assert cfg.num_params() == PARAMETERS == 2_667_974_657
    assert loop_work.parameters(config)["total"] == PARAMETERS
    # the tiny stand-in of the rehearsal keeps the passes and the norms
    tiny = ModelConfig(**model_fields(rehearsal_config(cell)))
    assert (tiny.ut_steps, tiny.sandwich_norm, tiny.num_layers,
            tiny.cache_entries) == (4, True, 3, 12)


def test_the_reference_is_one_file_that_knows_nothing_of_the_program(cell):
    path = spec_lib.reference_file(cell["config"])
    assert path.endswith("benchmark/references/ouro_2_6b.py")
    with open(path) as f:
        text = f.read()
    body = text.split('"""', 2)[2]                     # past the docstring
    assert "dlti_tpu" not in body
    imports = [line for line in body.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import jax", "import jax.numpy as jnp"]
    spec_lib.check_reference_file(path, spec_lib.REFERENCE_OFFERS["serve"])
    reference = spec_lib.load_reference(cell["config"], "serve")
    assert callable(reference.exit_distribution)
    sizes = reference.sizes(cell["config"])
    assert (sizes["num_layers"], sizes["ut_steps"], sizes["num_heads"],
            sizes["num_kv_heads"], sizes["head_dim"], sizes["rope_theta"],
            sizes["rms_norm_eps"]) == (48, 4, 16, 16, 128, 1e6, 1e-6)
    with pytest.raises(ValueError, match="every pass"):
        reference.sizes({"model": {**cell["config"]["model"],
                                   "early_exit_threshold": 0.5}})


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
    ids = (jnp.arange(100) * 37 + 11) % 509 + 3
    return {"config": config, "model": model, "params": params,
            "reference": reference, "ids": ids}


def test_the_reference_agrees_with_the_program_on_the_stand_in(tiny_sides):
    import jax.numpy as jnp

    t = tiny_sides
    ours = t["model"].apply({"params": t["params"]}, t["ids"][None])[0][0]
    sizes = t["reference"].sizes(t["config"])
    theirs = t["reference"].forward(t["params"], sizes, t["ids"])
    assert float(jnp.abs(ours - theirs).max()) < 2e-5
    p = t["reference"].exit_distribution(t["params"], sizes, t["ids"])
    assert p.shape == (100, 4)
    assert float(jnp.abs(p.sum(-1) - 1.0).max()) < 1e-6


@pytest.mark.parametrize("key,value", [("total_ut_steps", 3),
                                       ("rope_theta", 10000.0),
                                       ("rms_norm_eps", 1e-2)])
def test_the_reference_follows_the_files_sizes(tiny_sides, key, value):
    """A published key changed in the file changes the reference's logits:
    it reads the file, not constants of its own."""
    import jax.numpy as jnp

    t = tiny_sides
    changed = copy.deepcopy(t["config"])
    changed["model"][key] = value
    stated = t["reference"].forward(
        t["params"], t["reference"].sizes(t["config"]), t["ids"])
    other = t["reference"].forward(
        t["params"], t["reference"].sizes(changed), t["ids"])
    assert float(jnp.abs(stated - other).max()) > 1e-3


def test_work_functions_against_a_hand_count(cell):
    config = cell["config"]
    parts = loop_work.parameters(config)
    assert parts["attention"] == 48 * 4 * 2048 * 2048
    assert parts["mlp"] == 48 * 3 * 2048 * 5632
    assert parts["layer_norms"] == 48 * 4 * 2048
    assert (parts["final_norm"], parts["exit_gate"]) == (2048, 2049)
    assert parts["embedding_and_head"] == 2 * 49152 * 2048
    assert parts["total"] == PARAMETERS
    model = config["model"]
    assert loop_work.cache_entries(model) == 192
    assert loop_work.cache_bytes_a_token(model, 2) == CACHE_BYTES_A_TOKEN
    assert loop_work.cache_bytes_a_token(model, 1) == CACHE_BYTES_A_TOKEN // 2
    step = loop_work.decode_step_bytes(config, 2, 1800.0)
    # the layers' weights four times: the passes are in the count
    assert step["layer_weights"] == 4 * 48 * (
        2 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + 4 * 4 * 2048)
    assert step["head"] == 2 * 2048 * 49152
    assert step["final_norm_and_gate"] == 4 * 4 * (2 * 2048 + 1)
    assert step["keys_and_values"] == 1800 * CACHE_BYTES_A_TOKEN
    # the issue's floor: 19.7 GB of weights, 0.2 of head, 2.8 of keys
    assert 19.6e9 < step["layer_weights"] < 19.8e9
    assert 22.5e9 < step["total"] < 23.0e9
    assert 27.0 < step["total"] / 819e9 * 1e3 < 28.5          # ms
    once = copy.deepcopy(config)
    once["model"]["total_ut_steps"] = 1
    assert loop_work.decode_step_bytes(once, 2, 0.0)["layer_weights"] * 4 \
        == step["layer_weights"]
    flop = loop_work.prefill_flops(config, 1000.0, 1000.0 * 100)
    assert flop["layer_weights"] == 1000 * 4 * 48 * 2 * (
        4 * 2048 * 2048 + 3 * 2048 * 5632)
    assert flop["attention_products"] == 4 * 16 * 128 * 192 * 1000 * 100
    # ~2 x 4 x 2.47 G a token: 19.7 GFLOP
    assert 19.5e9 < flop["layer_weights"] / 1000 < 19.9e9


def _ctx(cell, before, after, trace):
    return {"metrics_before": before, "metrics_after": after, "trace": trace,
            "config": cell["config"], "spec": cell["cell"],
            "device": {"platform": "tpu", "kind": "TPU v5 lite"},
            "profile_dir": None}


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_reads_as_nothing(cell, name,
                                                         monkeypatch):
    """What the parent's program gives (it cannot run this configuration,
    but the driver lays these readers over its checkout for every cell's
    traced run): no looped-stack series in /metrics, so the reader returns
    None and the line leaves the metric out; nothing raises. The same in
    another configuration's cell."""
    import attribute_idle

    monkeypatch.setattr(attribute_idle, "kernel_ms_per_step",
                        lambda ctx, kernel: 0.8)
    read = spec_lib.load_layer_reader(name)
    scrape = {"dlti_decode_steps": 10.0, "dlti_decode_context_tokens": 9e3,
              "dlti_decode_slot_steps": 80.0,
              "dlti_prefill_tokens": 4e4, "dlti_prefill_batches": 20.0,
              "dlti_prefill_attention_pairs": 4e7,
              "dlti_kv_context_tokens": 2000.0,
              'dlti_kv_blocks_in_use{group="full"}': 130.0}
    trace = {"programs": {"decode": {"count": 5, "total_s": 0.1},
                          "prefill": {"count": 4, "total_s": 0.7}}}
    assert read(_ctx(cell, dict.fromkeys(scrape, 0.0), scrape, trace)) is None
    assert read(_ctx(cell, {}, {}, None)) is None
    other = spec_lib.resolve_cell("serve.mistral_7b.chat")
    looped = {**scrape, "dlti_loop_passes_decode": 40.0,
              "dlti_loop_passes_prefill": 80.0,
              "dlti_kv_cache_entries": 16.0}
    if name != "loop_exit_expected_pass":
        assert read(_ctx(other, dict.fromkeys(looped, 0.0), looped,
                         trace)) is None


def test_the_new_readers_read_a_hand_made_scrape_and_trace(cell, monkeypatch):
    import attribute_idle

    before = dict.fromkeys((
        "dlti_decode_steps", "dlti_decode_context_tokens",
        "dlti_decode_slot_steps", "dlti_loop_passes_decode",
        "dlti_loop_passes_prefill", "dlti_loop_exit_pass_e3_decode",
        "dlti_prefill_tokens", "dlti_prefill_attention_pairs",
        "dlti_prefill_batches"), 0.0)
    after = {"dlti_decode_steps": 1000.0,
             "dlti_decode_context_tokens": 1000 * 1800.0,
             "dlti_decode_slot_steps": 1000 * 7.9,
             "dlti_loop_passes_decode": 4000.0,
             "dlti_loop_passes_prefill": 4 * 120.0,
             "dlti_loop_exit_pass_e3_decode": 1000 * 7.9 * 2345.0,
             "dlti_prefill_tokens": 120 * 170.0,
             "dlti_prefill_attention_pairs": 120 * 170.0 * 90,
             "dlti_prefill_batches": 120.0}
    for scrape, tokens, blocks in ((before, 1700.0, 110.0),
                                   (after, 1900.0, 123.0)):
        scrape["dlti_kv_context_tokens"] = tokens
        scrape['dlti_kv_blocks_in_use{group="full"}'] = blocks
        scrape["dlti_kv_cache_entries"] = 192.0
    trace = {"programs": {"decode": {"count": 70, "total_s": 70 * 0.040},
                          "prefill": {"count": 9, "total_s": 9 * 0.060}}}
    ctx = _ctx(cell, before, after, trace)
    read = spec_lib.load_layer_reader
    block = 16 * CACHE_BYTES_A_TOKEN
    kv = read("kv_bytes_per_context_token.loop")(ctx)
    assert kv == pytest.approx(block * (110 / 1700 + 123 / 1900) / 2)
    assert CACHE_BYTES_A_TOKEN < kv < CACHE_BYTES_A_TOKEN * 1.1
    assert read("loop_exit_expected_pass")(ctx) == pytest.approx(2.345)
    need = loop_work.decode_step_bytes(cell["config"], 2, 1800.0)["total"]
    floor = read("decode_hbm_floor_pct.loop")(ctx)
    assert floor == pytest.approx(100 * need / 819e9 / 0.040)
    assert 65 < floor < 75
    flop = loop_work.prefill_flops(cell["config"], 170.0,
                                   170.0 * 90)["total"]
    mfu = read("prefill_mfu_pct.loop")(ctx)
    assert mfu == pytest.approx(100 * flop / 197e12 / 0.060)
    assert 25 < mfu < 35
    monkeypatch.setattr(attribute_idle, "kernel_ms_per_step",
                        lambda ctx, kernel: 9.6)
    assert read("paged_attn_hbm_pct.loop")(ctx) == pytest.approx(
        100 * 1800 * CACHE_BYTES_A_TOKEN / 819e9 / 9.6e-3)
    # a scrape of another program's gauge (a cache of other entries) is
    # not this metric's to read
    after["dlti_kv_cache_entries"] = before["dlti_kv_cache_entries"] = 48.0
    assert read("kv_bytes_per_context_token.loop")(ctx) is None


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
        "model (models/llama.py)"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for name in ("output_tokens_per_s", "itl_mean_ms"):
        assert CELL in e2e[name]["workloads"]
    assert CELL not in e2e["ttft_mean_ms"]["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ouro_2_6b", "short_reasoning", 1)
    for m in bench["per_layer"]:
        moved = e2e.get(m["moves"], {}).get("workloads", [CELL])
        if CELL in m["workloads"]:
            assert CELL in moved, m["name"]
