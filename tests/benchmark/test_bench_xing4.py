"""The xing4_29b configuration, its cell, its work functions and the readers
it brings: what the files say, read without a chip. (The cell's
``--rehearsal`` run on the CPU is ``test_bench_run.py``'s case
``test_rehearsal_prints_a_well_formed_result[serve.xing4_29b.fresh_docs]``,
which every cell of ``BENCHMARK.json`` gets.)"""

import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, os.path.join(BENCH, "lib"))

import hyper_work  # noqa: E402
import spec as spec_lib  # noqa: E402
from chip_child import model_fields  # noqa: E402

CELL = "serve.xing4_29b.fresh_docs"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the builder's count, restated in the file's ``deployment``
PARAMETERS = 4_920_866_746


@pytest.fixture(scope="module")
def cell():
    return spec_lib.resolve_cell(CELL)


def test_the_cell_is_the_issues(cell):
    assert cell["chips"] == 1 and cell["traffic_name"] == "fresh_docs"
    assert cell["config_name"] == "xing4_29b"
    args, mix = cell["cell"]["args"], cell["traffic"]
    assert args == {"--max-seqs": "32", "--block-size": "16",
                    "--num-blocks": "8192", "--max-model-len": "8192",
                    "--kv-cache-dtype": "bfloat16"}     # no prefix caching
    assert mix["arrivals"] == {"loop": "closed", "clients": 32,
                               "pool": 1280, "stagger_s": 0.1}
    assert mix["prompt_tokens"] == {"median": 2048, "sigma": 0.5, "min": 512,
                                    "max": 4096}
    assert mix["output_tokens"] == {"median": 128, "sigma": 0.5, "min": 32,
                                    "max": 384}
    assert mix["ramp_s"] == 15.0
    shape_seeds = [json.load(open(os.path.join(BENCH, "traffic", f)))
                   .get("shape_seed") for f in os.listdir(
                       os.path.join(BENCH, "traffic"))]
    assert shape_seeds.count(mix["shape_seed"]) == 1, "a shape seed of its own"
    assert cell["cell"]["check"]["prompt_tokens"] == [96, 700, 6000]
    assert {m["name"] for m in cell["end_to_end"]} == {
        "output_tokens_per_s", "itl_mean_ms", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {"decode_hbm_floor_pct.hyper", "prefill_mfu_pct.hyper",
            "mhc_sinkhorn_residual_ppm", "mhc_device_ms_per_ktok",
            "prefill_device_ms_per_ktok.closed", "latent_attn_roofline_pct",
            "latent_attn_device_ms_per_step", "decode_step_device_ms",
            "moe_expert_load_max_over_mean.latent",
            "device_idle_share.serve"} <= names
    assert "decode_hbm_floor_pct.latent" not in names   # counts one q_proj
    assert "prefix_hit_token_share" not in names        # the cache is off


def test_the_warm_up_covers_every_call_the_mix_can_form(cell):
    """A prompt of 512-4,096 tokens goes as calls of at most 2,048 padded
    tokens: whole prompts in their bucket, as many rows as fit the limit;
    what a longer one leaves after its 2,048-token calls, one row in any
    bucket."""
    shapes = {int(b): rows for b, rows in
              cell["cell"]["warm_up"]["shapes"].items()}
    limit = 2048
    for bucket in (512, 1024, 2048):
        widest = min(8, limit // bucket)
        want = [r for r in (1, 2, 4, 8) if r <= widest]
        assert shapes[bucket] == want, bucket
    for bucket in (16, 32, 64, 128, 256):
        assert shapes[bucket] == [1], bucket
    assert max(b * max(rows) for b, rows in shapes.items()) == limit


def test_top_level_model_group_and_catalog_agree_but_for_the_cut(cell):
    config = cell["config"]
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Xing4.0-29B-A4B"' in line)
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == ["num_hidden_layers",
                                         "num_nextn_predict_layers"]
    for key, published in row["config"].items():
        assert config["model"][key] == config[key], key
        if key in config["reduced"]:
            assert config["published"][key] == published
            assert config[key] != published
        else:
            assert config[key] == published, key
    assert set(config["model"]) - set(row["config"]) == {
        "rope_interleave", "torch_dtype"}
    assert (config["num_hidden_layers"],
            config["num_nextn_predict_layers"]) == (7, 0)
    assert config["share"] == {
        "chips_per_layer": 1, "pipeline_stages": 8, "stage": 1,
        "layers": [0, 7], "experts": [0, 64], "vocab_rows": [0, 131072]}
    for key in ("streams_in", "streams_out", "hc_eps", "sinkhorn_order",
                "maps_norm", "maps_dtype", "rope_interleave",
                "seeded_weights", "num_nextn_predict_layers"):
        assert config["assumed"][key], key
    assert "4,921 M" in config["deployment"]


def test_sizes_and_work_count_the_same_parameters_from_the_file_alone(cell):
    config = cell["config"]
    reference = spec_lib.load_reference(config, "serve")
    sizes = reference.sizes(config)
    assert (sizes["layers"], sizes["dense_layers"], sizes["streams"],
            sizes["sinkhorn_iters"], sizes["q_rank"], sizes["experts"],
            sizes["top_k"]) == (7, 2, 4, 20, 768, 64, 4)
    assert sizes["yarn"]["factor"] == 64 and sizes["interleave"] is True
    parts = hyper_work.parameters(config)
    assert parts["total"] == PARAMETERS
    # the issue's arithmetic, to the rounding it states
    model = config["model"]
    attention = sum(hyper_work.attention_matrices(model).values())
    assert round(attention / 1e6, 2) == 28.41
    assert round(2 * hyper_work.map_parameters(model) / 1e6, 2) == 0.69
    assert round(parts["embedding_and_head"] / 1e6, 1) == 939.5
    assert round(parts["routed_experts"] / 5 / 64 / 1e6, 2) == 11.01
    from dlti_tpu.config import ModelConfig

    cfg = ModelConfig(**model_fields(config))
    assert cfg.num_params() == PARAMETERS
    assert (cfg.hc_mult, cfg.q_lora_rank, cfg.first_k_dense, cfg.moe_held,
            cfg.num_experts_per_tok) == (4, 768, 2, 64, 4)
    assert cfg.yarn == model["rope_scaling"]


def _tiny(cell):
    config = copy.deepcopy(cell["config"])
    config["model"].update(cell["cell"]["rehearsal"]["model_overrides"])
    return config


def test_work_functions_against_a_hand_count_for_the_rehearsal_model(cell):
    """hidden 64, 4 heads of 16 + 8 / 16, latent 32, query latent 24, 4
    layers (2 dense of 96, 2 of 8 experts of 24 top-4 and one shared), 4
    streams, vocabulary 512, float32."""
    config = _tiny(cell)
    model = config["model"]
    mats = hyper_work.attention_matrices(model)
    assert mats == {"q_a": 64 * 24, "q_b": 24 * 4 * 24, "kv_a": 64 * 40,
                    "kv_b": 32 * 4 * 32, "o": 4 * 16 * 64}
    per_sublayer = (4 * 64 + 1) * 24 + 3
    assert hyper_work.map_parameters(model) == per_sublayer == 6171
    attention = sum(mats.values())                              # 14,592
    total = (4 * (attention + 24 + 32) + 4 * 2 * 6171 + 4 * 2 * 64 + 64
             + 2 * 3 * 64 * 96 + 2 * 3 * 64 * 24
             + 2 * (64 * 8 + 8) + 2 * 8 * 3 * 64 * 24 + 2 * 512 * 64)
    assert hyper_work.parameters(config)["total"] == total == 294_920
    flops = hyper_work.prefill_flops(model, 10, 55)
    assert flops["attention_weights"] == 10 * 4 * 2 * attention
    assert flops["stream_maps"] == 10 * 4 * 2 * 2 * 4 * 64 * 24
    assert flops["dense_mlp"] == 10 * 2 * 2 * 3 * 64 * 96
    assert flops["experts"] == 10 * 2 * 2 * (3 * 64 * 24 * 5 + 64 * 8)
    assert flops["attention_products"] == 55 * 4 * 2 * 4 * (16 + 8 + 16)
    assert flops["total"] == sum(v for k, v in flops.items() if k != "total")
    step = hyper_work.decode_step_bytes(config, 2, 100.0, 9.0)
    assert step["attention_weights"] == 4 * (
        4 * attention + 4 * (24 + 32 + 128))
    assert step["stream_maps"] == 4 * 2 * 4 * 6171
    assert step["experts_touched"] == 9 * 4 * 3 * 64 * 24
    assert step["latents"] == 100 * 4 * 40 * 2
    assert step["head"] == 4 * 64 * 512 + 4 * 64
    assert step["total"] == sum(v for k, v in step.items() if k != "total")


def test_the_floor_counts_less_than_the_sibling_reader_would(cell):
    """``latent_bytes.decode_step_bytes`` knows one query projection of
    hidden x heads x 192: 14.5 M parameters a layer more than ``q_a`` and
    ``q_b`` hold, which a floor must not count."""
    import latent_bytes

    config = cell["config"]
    model = config["model"]
    one_q = model["hidden_size"] * 32 * 192
    two_q = 3584 * 768 + 768 * 32 * 192
    assert round((one_q - two_q) / 1e6, 1) == 14.5
    ours = hyper_work.decode_step_bytes(config, 2, 75000.0, 200.0)
    theirs = latent_bytes.decode_step_bytes(config, 2, 75000.0, 200.0)
    assert theirs["attention_weights"] - ours["attention_weights"] \
        == 7 * (2 * (one_q - two_q) - 4 * 768)
    assert ours["latents"] == theirs["latents"]
    assert ours["stream_maps"] == 7 * 2 * 4 * 344_091


def _ctx(cell, before, after, trace=None):
    return {"trace": trace, "metrics_before": before, "metrics_after": after,
            "config": cell["config"], "spec": cell["cell"],
            "device": {"platform": "tpu", "kind": "TPU v5 lite"}}


@pytest.mark.parametrize("name", ["decode_hbm_floor_pct.hyper",
                                  "prefill_mfu_pct.hyper",
                                  "mhc_sinkhorn_residual_ppm",
                                  "mhc_device_ms_per_ktok"])
def test_a_program_without_the_counters_reads_as_nothing(cell, name):
    """What a program that lacks this PR's counters gives: no such series
    in /metrics and no scope in the trace, so the reader returns None and
    the line leaves the metric out; nothing raises."""
    read = spec_lib.load_layer_reader(name)
    scrape = {"dlti_decode_steps": 10.0, "dlti_decode_context_tokens": 9e3,
              "dlti_prefill_tokens": 4e4, "dlti_prefill_batches": 20.0}
    trace = {"programs": {"decode": {"count": 5, "total_s": 0.1},
                          "prefill": {"count": 4, "total_s": 0.7}}}
    assert read(_ctx(cell, dict.fromkeys(scrape, 0.0), scrape, trace)) is None
    assert read(_ctx(cell, {}, {}, None)) is None


def test_the_new_readers_read_the_counters(cell, monkeypatch, tmp_path):
    import scope_time

    before = dict.fromkeys((
        "dlti_decode_steps", "dlti_decode_context_tokens",
        "dlti_moe_experts_touched_decode", "dlti_prefill_tokens",
        "dlti_prefill_attention_pairs", "dlti_prefill_batches",
        "dlti_mhc_sinkhorn_residual_e6_decode"), 0.0)
    after = {"dlti_decode_steps": 100.0,
             "dlti_decode_context_tokens": 100 * 75000.0,
             "dlti_moe_experts_touched_decode": 100 * 210.0,
             "dlti_prefill_tokens": 40 * 1500.0,
             "dlti_prefill_attention_pairs": 40 * 1500.0 * 1100,
             "dlti_prefill_batches": 40.0,
             "dlti_mhc_sinkhorn_residual_e6_decode": 100 * 2500.0}
    trace = {"programs": {"decode": {"count": 50, "total_s": 0.7},
                          "prefill": {"count": 10, "total_s": 1.3}}}
    ctx = _ctx(cell, before, after, trace)
    need = hyper_work.decode_step_bytes(cell["config"], 2, 75000.0,
                                        210.0)["total"]
    assert spec_lib.load_layer_reader("decode_hbm_floor_pct.hyper")(ctx) \
        == pytest.approx(100 * need / 819e9 / 0.014)
    flop = hyper_work.prefill_flops(cell["config"]["model"], 1500.0,
                                    1500.0 * 1100)["total"]
    mfu = spec_lib.load_layer_reader("prefill_mfu_pct.hyper")(ctx)
    assert mfu == pytest.approx(100 * flop / 197e12 / 0.13)
    assert 5 < mfu < 15
    assert spec_lib.load_layer_reader("mhc_sinkhorn_residual_ppm")(ctx) \
        == pytest.approx(2500.0)
    monkeypatch.setattr(scope_time, "scope_s_per_call",
                        lambda ctx, program, prefix: 0.006)
    assert spec_lib.load_layer_reader("mhc_device_ms_per_ktok")(ctx) \
        == pytest.approx(6.0 / 1.5)


def test_scope_time_sums_the_union_under_a_scope_by_program(tmp_path):
    """Two prefill executions and a decode step, as an ``XSpace`` lays them
    out (picoseconds from the line's start; the scope in a stat of the
    event's metadata, as text or as a reference); a loop's event and its
    body's overlap and count once."""
    import types

    import reduce_trace
    import scope_time

    ns = types.SimpleNamespace
    stat_metadata = {1: ns(name="tf_op"), 2: ns(name="hlo_category"),
                     3: ns(name="source"),
                     9: ns(name="jit(prefill)/dlti_mhc_mix/mul:")}
    meta = {
        1: ns(name="jit_prefill(1)", stats=[]),
        2: ns(name="jit_decode(2)", stats=[]),
        3: ns(name="%fusion.1 = f32[8] fusion()", stats=[
            ns(metadata_id=1, str_value="jit(x)/dlti_mhc_map/dot_general:",
               ref_value=0)]),
        4: ns(name="%while.3 = () while()", stats=[
            ns(metadata_id=1, str_value="x/dlti_mhc_mix/while", ref_value=0)]),
        5: ns(name="%fusion.7 = f32[8] fusion()", stats=[
            ns(metadata_id=1, str_value="", ref_value=9)]),
        6: ns(name="%fusion.2 = f32[8] fusion()", stats=[
            ns(metadata_id=1, str_value="layers_0/attn/dot_general:",
               ref_value=0),
            ns(metadata_id=2, str_value="convolution fusion", ref_value=0),
            ns(metadata_id=3, str_value="/root/repo/dlti_tpu/models/x.py:1",
               ref_value=0)]),
    }

    def ev(metadata_id, offset_ns, duration_ns):
        return ns(metadata_id=metadata_id, offset_ps=offset_ns * 1000,
                  duration_ps=duration_ns * 1000)

    plane = ns(name="/device:TPU:0", stat_metadata=stat_metadata,
               event_metadata=meta, lines=[
        ns(name="XLA Modules", timestamp_ns=5, events=[
            ev(1, 0, 1000), ev(2, 2000, 500), ev(1, 3000, 1000)]),
        ns(name="XLA Ops", timestamp_ns=5, events=[
            ev(3, 100, 200), ev(4, 400, 300), ev(5, 450, 100),
            ev(6, 800, 100), ev(3, 2100, 50), ev(3, 3100, 200)])])
    got = scope_time.scoped_events(ns(planes=[ns(
        name="/host:CPU", lines=[]), plane]), reduce_trace.rules())
    prefill = got["programs"]["prefill"]
    assert prefill["count"] == 2 and set(prefill["scopes"]) == {
        "dlti_mhc_map", "dlti_mhc_mix"}
    assert prefill["scopes"]["dlti_mhc_map"] == pytest.approx(400e-9)
    assert prefill["scopes"]["dlti_mhc_mix"] == pytest.approx(300e-9)
    assert got["programs"]["decode"]["scopes"] == {
        "dlti_mhc_map": pytest.approx(50e-9)}      # no "dlti_tpu" of a path
    (tmp_path / scope_time.RESULT_NAME).write_text(json.dumps(got))
    ctx = {"profile_dir": str(tmp_path)}
    assert scope_time.scope_s_per_call(ctx, "prefill", "dlti_mhc_") \
        == pytest.approx(350e-9)
    assert scope_time.scope_s_per_call(ctx, "prefill", "dlti_none") is None
    assert scope_time.scope_s_per_call({}, "prefill", "dlti_mhc_") is None


def test_the_new_entries_are_appended_and_list_the_new_cell_alone():
    bench = spec_lib.load_benchmark()
    assert bench["configs"][-1]["name"] == "xing4_29b"
    assert bench["workloads"][-1]["name"] == CELL
    new = [m["name"] for m in bench["per_layer"][-4:]]
    assert new == ["decode_hbm_floor_pct.hyper", "prefill_mfu_pct.hyper",
                   "mhc_sinkhorn_residual_ppm", "mhc_device_ms_per_ktok"]
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == [CELL]
        reader = spec_lib._load_module(
            "r", os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
        assert (reader.NAME, reader.UNIT, reader.BETTER, reader.LAYER,
                reader.MOVES, reader.SOURCE) == (
            m["name"], m["unit"], m["better"], m["layer"], m["moves"],
            m["source"])
    for m in bench["per_layer"][:-4] + bench["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL, m["name"]
