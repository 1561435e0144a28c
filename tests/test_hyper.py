"""Hyper-connected residual streams (mHC) round latent attention with a query
latent under YaRN, two leading dense layers and all-held experts: the program
against its plain reference (``benchmark/references/xing4_29b.py``), at tiny
sizes on the CPU with seeded weights.

Both sides take their sizes from the benchmark's configuration file laid over
with the cell's rehearsal stand-ins, as the harness does: the program through
``chip_child.model_fields`` -> ``ModelConfig``, the reference through its own
``sizes(config)``.

Tolerances. Everything here is float32 with float32 caches: 5e-5 where one
forward pass is held against another (the program scales ``X phi`` by the
norm where the reference normalises first, and mixes the streams in another
order), 5e-4 through the engine (prefill then decode re-associates every
attention sum over the cache; the maps turn a 1e-6 in a stream into a 1e-6 in
the next sublayer's mixing weights, fourteen times over).
"""

import dataclasses
import hashlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "lib"))

import spec as spec_lib  # noqa: E402
from chip_child import model_fields  # noqa: E402

import dlti_tpu.models.latent as latent  # noqa: E402
from dlti_tpu.config import MODEL_PRESETS, Config, ModelConfig  # noqa: E402
from dlti_tpu.models import build_model  # noqa: E402
from dlti_tpu.models.hyper import (  # noqa: E402
    MHC_COUNTERS, HyperMaps, mix_in, mix_out, sinkhorn,
)
from dlti_tpu.models.latent import LatentAttention  # noqa: E402
from dlti_tpu.ops.kv_cache import init_latent_cache  # noqa: E402
from dlti_tpu.ops.rope import (  # noqa: E402
    rope_frequencies, yarn_correction_range, yarn_inv_freq, yarn_mscale,
    yarn_softmax_factor,
)
from dlti_tpu.serving.engine import EngineConfig, InferenceEngine  # noqa: E402
from dlti_tpu.serving.sampling import SamplingParams  # noqa: E402

CELL = "serve.xing4_29b.fresh_docs"
PUBLISHED_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"}


def tiny_config() -> dict:
    """The configuration file as a rehearsal runs it (tiny stand-ins)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4_29b.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "cells", CELL + ".json")) as f:
        rehearsal = json.load(f)["rehearsal"]
    config["model"] = {**config["model"], **rehearsal["model_overrides"]}
    config["program"] = {**config["program"],
                         **rehearsal["program_overrides"]}
    return config


@pytest.fixture(scope="module")
def tiny():
    config = tiny_config()
    cfg = ModelConfig(**model_fields(config))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    reference = spec_lib.load_reference(config, "serve")
    sizes = reference.sizes(config)
    ref_logprobs = jax.jit(lambda ids: jax.nn.log_softmax(
        reference.forward(params, sizes, ids), -1))
    return {"config": config, "cfg": cfg, "model": model, "params": params,
            "reference": reference, "sizes": sizes,
            "ref_logprobs": ref_logprobs}


def _prompts(lengths, seed=0, vocab=512):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(3, vocab, size=n)] for n in lengths]


def _logits(tiny, ids, params=None, **cfg_over):
    model = build_model(dataclasses.replace(tiny["cfg"], **cfg_over))
    return np.asarray(model.apply(
        {"params": tiny["params"] if params is None else params},
        jnp.asarray(ids)[None])[0][0])


# -- the model against the reference -----------------------------------------

def test_the_stand_in_has_every_part_of_the_model(tiny):
    cfg = tiny["cfg"]
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.q_lora_rank) == (4, 20, 24)
    assert (cfg.first_k_dense, cfg.num_layers) == (2, 4)
    assert (cfg.moe_num_experts, cfg.moe_held, cfg.num_experts_per_tok) \
        == (8, 8, 4)
    assert cfg.yarn["factor"] == 64 and cfg.rope_interleave
    assert cfg.num_nextn_predict_layers == 0
    layer = tiny["params"]["layers_2"]
    assert {"attn_hc", "mlp_hc"} <= set(layer)
    assert {"q_a_proj", "q_a_norm", "q_b_proj"} <= set(layer["attn"])
    assert "q_proj" not in layer["attn"]
    assert "gate_proj" in tiny["params"]["layers_1"]["mlp"]   # dense twice
    assert "router" in layer["mlp"]
    assert tiny["model"].counter_names[-2:] == MHC_COUNTERS


def test_forward_agrees_with_the_reference(tiny):
    ids = jnp.asarray(_prompts([150])[0])     # past the original 64 positions
    logits, _, counted = tiny["model"].apply(
        {"params": tiny["params"]}, ids[None], return_counters=True)
    want = tiny["reference"].forward(tiny["params"], tiny["sizes"], ids)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=5e-5, rtol=1e-4)
    assert int(counted["mhc_maps"]) == 150 * 2 * tiny["cfg"].num_layers


def test_param_count_knows_the_query_latent_and_the_maps(tiny):
    cfg = tiny["cfg"]
    leaves = jax.tree_util.tree_leaves(tiny["params"])
    assert cfg.num_params() == sum(x.size for x in leaves)
    n, h = cfg.hc_mult, cfg.hidden_size
    maps = sum(x.size for x in jax.tree_util.tree_leaves(
        tiny["params"]["layers_0"]["attn_hc"]))
    assert maps == (n * h + 1) * (n * n + 2 * n) + 3
    plain = dataclasses.replace(cfg, hc_mult=0, q_lora_rank=0)
    qk = cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    r = cfg.q_lora_rank
    assert cfg.num_params() - plain.num_params() == cfg.num_layers * (
        2 * maps + h * r + r + r * qk - h * qk)


@pytest.mark.parametrize("what", ["a_zero", "no_query_norm", "five_rounds",
                                  "plain_residual_maps"])
def test_leaving_a_part_out_moves_the_logits(tiny, what):
    """A program that dropped ``x~ phi`` (a = 0), skipped the query latent's
    norm, cut the Sinkhorn rounds or fell back to identity maps computes
    another function: far past the 5e-5 the reference is held to."""
    ids = _prompts([64], seed=3)[0]
    base = _logits(tiny, ids)
    params = jax.tree_util.tree_map(lambda x: x, tiny["params"])
    over = {}
    if what == "a_zero":
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if jax.tree_util.keystr(
                path).split("'")[-2] in ("a_pre", "a_post", "a_res") else x,
            params)
    elif what == "no_query_norm":
        # a norm whose weight undoes it for no token, but changes the scale
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: x * 3.0 if "q_a_norm" in jax.tree_util.keystr(
                path) else x, params)
    elif what == "five_rounds":
        over = {"hc_sinkhorn_iters": 5}
    else:
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x) if "_hc" in
            jax.tree_util.keystr(path) else x, params)
    moved = np.abs(_logits(tiny, ids, params, **over) - base).max()
    assert moved > (1e-3 if what == "five_rounds" else 1e-2), moved


# -- the maps ---------------------------------------------------------------------

def _maps(tiny, iters, tokens=96):
    cfg = dataclasses.replace(tiny["cfg"], hc_sinkhorn_iters=iters)
    streams = jax.random.normal(jax.random.PRNGKey(1),
                                (cfg.hc_mult, 2, tokens // 2,
                                 cfg.hidden_size))
    return HyperMaps(cfg).apply(
        {"params": tiny["params"]["layers_3"]["mlp_hc"]}, streams,
        jnp.ones((2, tokens // 2), bool)), streams


def test_h_res_is_doubly_stochastic_after_twenty_rounds_and_not_after_three(
        tiny):
    def off(h_res):
        return np.maximum(np.abs(np.asarray(h_res).sum(0) - 1).max(0),
                          np.abs(np.asarray(h_res).sum(1) - 1).max(0))

    (h_pre, h_post, h_res, counted), _ = _maps(tiny, 20)
    assert np.median(off(h_res)) < 1e-5 and np.quantile(off(h_res), 0.9) < 1e-4
    assert int(counted[0]) == int(off(h_res).max() * 1e6)
    assert int(counted[1]) == 96
    assert (np.asarray(h_res) > 0).all()
    assert (0 < np.asarray(h_pre)).all() and (np.asarray(h_pre) < 1).all()
    assert (0 < np.asarray(h_post)).all() and (np.asarray(h_post) < 2).all()
    (_, _, h_three, counted_three), _ = _maps(tiny, 3)
    assert np.median(off(h_three)) > 1e-4
    assert int(counted_three[0]) > 10 * max(int(counted[0]), 100)
    # the token moves H_res off its bias's value by tenths, somewhere
    assert np.ptp(np.asarray(h_res), axis=(2, 3)).max() > 0.2


def test_sinkhorn_is_columns_then_rows():
    m = jnp.asarray([[1.0, 3.0], [2.0, 2.0]])[:, :, None]
    once = np.asarray(sinkhorn(m, 1, 0.0))[:, :, 0]
    cols = np.asarray([[1 / 3, 3 / 5], [2 / 3, 2 / 5]])
    np.testing.assert_allclose(once, cols / cols.sum(1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(once.sum(1), 1.0, rtol=1e-6)


def test_the_mixes_are_the_equations(tiny):
    (h_pre, h_post, h_res, _), streams = _maps(tiny, 20, tokens=8)
    x = np.asarray(streams, np.float64)
    u = np.einsum("jbs,jbsc->bsc", np.asarray(h_pre, np.float64), x)
    np.testing.assert_allclose(np.asarray(mix_in(streams, h_pre)), u,
                               atol=1e-5)
    out = jax.random.normal(jax.random.PRNGKey(2), streams.shape[1:])
    want = np.einsum("ijbs,jbsc->ibsc", np.asarray(h_res, np.float64), x) \
        + np.asarray(h_post, np.float64)[..., None] * np.asarray(out)[None]
    np.testing.assert_allclose(
        np.asarray(mix_out(streams, out, h_post, h_res)), want, atol=1e-5)


def test_maps_with_identity_weights_are_the_plain_residual(tiny):
    """H_pre = e_0, H_post = e_0, H_res = I on one stream is x + F(x)."""
    n = 4
    x = jax.random.normal(jax.random.PRNGKey(3), (n, 1, 5, 16))
    eye = jnp.broadcast_to(jnp.eye(n)[:, :, None, None], (n, n, 1, 5))
    e0 = jnp.broadcast_to(jnp.eye(n)[0][:, None, None], (n, 1, 5))
    u = mix_in(x, e0)
    np.testing.assert_allclose(np.asarray(u), np.asarray(x[0]), atol=1e-6)
    new = mix_out(x, 2.0 * u, e0, eye)
    np.testing.assert_allclose(np.asarray(new[0]), 3.0 * np.asarray(x[0]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(new[1:]), np.asarray(x[1:]),
                               atol=1e-6)


# -- YaRN against numbers worked by hand -----------------------------------------

def test_yarn_numbers_worked_by_hand():
    s = PUBLISHED_YARN
    # 64 ln(4096 / (2 pi 32)) / (2 ln 1e4) = 64 x 3.0142 / 18.4207 = 10.47;
    # 64 ln(4096 / (2 pi)) / 18.4207 = 64 x 6.4799 / 18.4207 = 22.51
    assert yarn_correction_range(64, 10000.0, s) == (10, 23)
    assert yarn_mscale(64, 1) == pytest.approx(0.1 * math.log(64) + 1)
    assert yarn_mscale(64, 1) == pytest.approx(1.41589, abs=1e-5)
    assert yarn_softmax_factor(s) == pytest.approx(2.0047, abs=5e-5)
    assert yarn_softmax_factor(None) == 1.0
    inv = np.asarray(yarn_inv_freq(64, 10000.0, s))
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)   # kept
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    # pair 16: m = 1 - 6/13; 1e4^(-0.5) = 0.01
    m = 1 - 6 / 13
    assert inv[16] == pytest.approx(0.01 * (m + (1 - m) / 64), rel=1e-5)
    cos, sin = rope_frequencies(64, 8, 10000.0, s)   # amplitude g(1)/g(1) = 1
    np.testing.assert_allclose(np.asarray(cos)[3], np.cos(3 * inv), atol=1e-6)
    wide = dict(s, mscale_all_dim=0)                 # then the tables carry g
    cos_w, _ = rope_frequencies(64, 8, 10000.0, wide)
    np.testing.assert_allclose(np.asarray(cos_w), np.asarray(cos) * 1.41589,
                               rtol=1e-5)
    assert yarn_softmax_factor(wide) == 1.0


def test_rope_scaling_is_hashable_and_round_trips():
    cfg = ModelConfig(rope_scaling=dict(PUBLISHED_YARN))
    assert cfg.yarn == PUBLISHED_YARN and hash(cfg) == hash(
        ModelConfig(rope_scaling=tuple(PUBLISHED_YARN.items())))
    again = Config.from_json(Config(model=cfg).to_json()).model
    assert again == cfg and again.yarn == PUBLISHED_YARN
    assert ModelConfig().yarn is None


# -- attention: one set of weights, three paths, one scale ------------------------

@pytest.mark.parametrize("chunk", [24, 6])
def test_expanded_and_absorbed_forms_agree_under_the_new_scale(
        tiny, monkeypatch, chunk):
    """A prompt prefilled in calls of ``chunk`` tokens (expanded over the
    cache at 24, absorbed at 6 with the limit set to 8) against the no-cache
    form: the query latent, YaRN's table and the scaled softmax in each."""
    monkeypatch.setattr(latent, "ABSORB_MAX_QUERIES", 8)
    cfg = tiny["cfg"]
    attn = LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 96, cfg.hidden_size))
    params = attn.init(jax.random.PRNGKey(6), x[:, :8], *rope_frequencies(
        cfg.qk_rope_head_dim, 128, cfg.rope_theta, cfg.yarn),
        jnp.arange(8)[None])["params"]
    cos, sin = rope_frequencies(cfg.qk_rope_head_dim, 128, cfg.rope_theta,
                                cfg.yarn)
    pos = jnp.arange(96)[None]
    want, _ = attn.apply({"params": params}, x, cos, sin, pos)
    cache = {**init_latent_cache(16, 8, cfg.latent_dim, jnp.float32),
             "block_tables": jnp.arange(1, 13)[None]}
    got = []
    for start in range(0, 96, chunk):
        out, new = attn.apply({"params": params}, x[:, start:start + chunk],
                              cos, sin, pos[:, start:start + chunk], cache)
        cache = {**cache, **new}
        got.append(out)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               np.asarray(want), atol=2e-5)
    # and the scale is in it: plain RoPE's scale gives other numbers
    plain = LatentAttention(dataclasses.replace(cfg, rope_scaling=None))
    other, _ = plain.apply({"params": params}, x, cos, sin, pos)
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-3


# -- through the engine: prefill then decode against the full forward --------

def _engine(tiny, **over):
    kw = dict(max_seqs=4, block_size=8, num_blocks=96, max_model_len=160,
              cache_dtype="float32")
    kw.update(over)
    cfg = dataclasses.replace(tiny["cfg"], **kw.pop("model", {}))
    return InferenceEngine(cfg, tiny["params"], EngineConfig(**kw))


def _hold_to_reference(tiny, prompts, results, atol=5e-4):
    for prompt, res in zip(prompts, results):
        tokens = res.output_token_ids
        lp = tiny["ref_logprobs"](jnp.asarray(prompt + tokens))
        rows = np.asarray(lp[len(prompt) - 1:len(prompt) - 1 + len(tokens)])
        np.testing.assert_allclose(
            res.output_logprobs, rows[np.arange(len(tokens)), tokens],
            atol=atol)
        assert (rows.max(-1) - rows[np.arange(len(tokens)), tokens]
                <= atol).all()


SCENARIOS = {
    "lone": dict(lengths=[16], engine={}),
    "unequal_batch": dict(lengths=[70, 5, 19], engine={}),
    "chunked_prefill": dict(
        lengths=[45, 23], engine=dict(max_prefill_tokens_per_step=16)),
    # decode through the Pallas kernel (interpreted), not the gather path
    "decode_kernel": dict(
        lengths=[90, 9],
        engine=dict(model=dict(paged_attention_impl="kernel"))),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_engine_prefill_then_decode_agrees_with_full_forward(tiny, name):
    case = SCENARIOS[name]
    eng = _engine(tiny, **case["engine"])
    prompts = _prompts(case["lengths"], seed=len(name))
    results = eng.generate(prompts,
                           SamplingParams(max_tokens=9, temperature=0.0))
    _hold_to_reference(tiny, prompts, results)
    st, cfg = eng.stats, tiny["cfg"]
    tokens = sum(case["lengths"]) + 8 * len(prompts)
    assert st["mhc_maps"] == 2 * cfg.num_layers * tokens
    assert st["mhc_maps_decode"] == 2 * cfg.num_layers * 8 * len(prompts)
    assert st["moe_assignments"] == (cfg.num_layers - cfg.first_k_dense) \
        * cfg.num_experts_per_tok * tokens
    assert 0 < st["mhc_sinkhorn_residual_e6_decode"] \
        <= st["mhc_sinkhorn_residual_e6"]


@pytest.mark.parametrize("form", ["loop", "flash_kernel"])
def test_a_long_prompt_goes_as_calls_over_cached_latents(tiny, monkeypatch,
                                                         form):
    """Three calls of the model's limit, the later ones in expanded form
    over the latents the earlier ones wrote, past the original positions:
    through the loop over all keys, and with each call's own tokens through
    the flash forward kernel (interpreted; whole tiles of 8 tokens, loop
    steps of 16 keys) merged with the loop over what earlier calls wrote."""
    monkeypatch.setattr(latent.LatentForCausalLM, "prefill_call_tokens", 64)
    over = {}
    if form == "flash_kernel":
        monkeypatch.setattr(latent, "ABSORB_MAX_QUERIES", 16)
        monkeypatch.setattr(latent, "KERNEL_TOKENS_MULTIPLE", 8)
        monkeypatch.setattr(latent, "KERNEL_BLOCK", 16)
        monkeypatch.setattr(latent, "KEY_BLOCK", 16)
        over = dict(model=dict(paged_attention_impl="kernel"))
    eng = _engine(tiny, max_model_len=192, **over)
    prompts = _prompts([150], seed=9)
    results = eng.generate(prompts,
                           SamplingParams(max_tokens=6, temperature=0.0))
    assert eng.stats["prefill_batches"] == 3
    _hold_to_reference(tiny, prompts, results)
    # calls of 64, 64 and 22 tokens that start at 0, 64 and 128: no step,
    # four and eight steps of the loop over cached latents
    assert (eng.stats["mla_kernel_query_tokens_total"],
            eng.stats["mla_walked_key_blocks_total"]) \
        == ((150, 12) if form == "flash_kernel" else (0, 0))


def test_the_counters_reach_metrics_with_their_decode_parts(tiny):
    import types

    from dlti_tpu.serving.server import build_registry

    eng = _engine(tiny)
    eng.generate(_prompts([12]), SamplingParams(max_tokens=4,
                                                temperature=0.0))
    text = build_registry(
        types.SimpleNamespace(engine=eng)).render_prometheus()
    for name in MHC_COUNTERS:
        assert f"dlti_{name}" in text and f"dlti_{name}_decode" in text


# -- what the maps cannot be served with refuses, beside the latent family's ----

REFUSED = {
    "int8_weights": (dict(quantization="int8"), "stream maps"),
    "speculative": (dict(speculative="ngram"), "speculative"),
    "adapter_pool": (dict(adapter_slots=2), "adapter"),
    "int8_latents": (dict(cache_dtype="int8"), "int8 layout"),
    "mtp_module": (dict(model=dict(num_nextn_predict_layers=1)),
                   "num_nextn_predict_layers"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_engine_refuses_at_start_up(tiny, name):
    over, said = REFUSED[name]
    with pytest.raises(ValueError, match=said):
        _engine(tiny, **over)


def test_an_mtp_module_is_refused_for_any_family():
    from dlti_tpu.serving.executor import refuse_unsupported

    cfg = dataclasses.replace(MODEL_PRESETS["llama_tiny"],
                              num_nextn_predict_layers=1)
    with pytest.raises(ValueError, match="multi-token-prediction"):
        refuse_unsupported(cfg, EngineConfig())


def test_hand_off_and_a_tensor_mesh_refuse(tiny):
    from jax.sharding import Mesh

    from dlti_tpu.serving.disagg import DisaggController

    with pytest.raises(ValueError, match="latent blocks"):
        DisaggController(tiny["cfg"], tiny["params"], EngineConfig())
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("tensor",))
    with pytest.raises(ValueError, match="stream maps"):
        InferenceEngine(tiny["cfg"], tiny["params"], EngineConfig(),
                        mesh=mesh)


def test_lora_and_other_families_refuse_the_streams(tiny):
    from dlti_tpu.config import LoRAConfig

    model = build_model(tiny["cfg"], LoRAConfig(enabled=True, r=4))
    with pytest.raises(NotImplementedError, match="LoRA"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="hc_mult"):
        build_model(dataclasses.replace(MODEL_PRESETS["llama_tiny"],
                                        hc_mult=4))


# -- the family without streams is the program it was ------------------------------

def test_the_plain_latent_preset_is_the_program_it_was():
    """``hc_mult`` 0, no query latent, no ``rope_scaling``: the same
    parameter tree and the same lowered program as before the streams came
    (hashes taken at the parent commit, under this suite's conftest; a change to the latent family that
    means to change its program re-pins them)."""
    cfg = MODEL_PRESETS["latent_tiny"]
    assert (cfg.hc_mult, cfg.q_lora_rank, cfg.rope_scaling) == (0, 0, None)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    ids = (jnp.arange(37)[None] * 7 + 3) % 512
    paths = [(jax.tree_util.keystr(k), v.shape)
             for k, v in jax.tree_util.tree_leaves_with_path(params)]
    assert len(paths) == 43
    assert hashlib.sha256(repr(paths).encode()).hexdigest() == (
        "8a8704832324a7d330615ff7a086daad2b610f9386e44688c4131934952f5cef")
    lowered = jax.jit(lambda p, i: model.apply({"params": p}, i)[0]).lower(
        params, ids).as_text()
    assert hashlib.sha256(lowered.encode()).hexdigest() == (
        "bfe67f5c51106859b4d0036cea325efff14ed9c536d01cd320347c90969fd17d")
    assert model.counter_names == latent.MOE_COUNTERS
