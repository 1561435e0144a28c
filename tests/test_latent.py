"""The latent-attention family (MLA over a latent paged cache, a leading
dense layer, gated held experts) against its plain reference, at tiny sizes
on the CPU with seeded weights.

Both sides take their sizes from the benchmark's configuration file laid over
with the cell's rehearsal stand-ins, as the harness does: the program through
``chip_child.model_fields`` -> ``ModelConfig``, the reference through its own
``sizes(config)``.

Tolerances. Everything here is float32 with float32 caches: 2e-5 where one
forward pass is held against another (the two differ in the order of a few
hundred additions), 2e-4 through the engine (prefill then decode re-associates
every attention sum over the cache, twelve times over the answer), 5e-5 for one
expert layer.
"""

import dataclasses
import json
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
from dlti_tpu.config import MODEL_PRESETS, ModelConfig  # noqa: E402
from dlti_tpu.models import LlamaForCausalLM, build_model  # noqa: E402
from dlti_tpu.models.latent import (  # noqa: E402
    LatentAttention, LatentForCausalLM,
)
from dlti_tpu.models.moe import HeldExpertsMLP  # noqa: E402
from dlti_tpu.ops.kv_cache import (  # noqa: E402
    init_cache, init_latent_cache, latent_gather, latent_update,
    slot_mapping,
)
from dlti_tpu.ops.rope import apply_rope, rope_frequencies  # noqa: E402
from dlti_tpu.serving.engine import EngineConfig, InferenceEngine  # noqa: E402
from dlti_tpu.serving.sampling import SamplingParams  # noqa: E402

CELL = "serve.kanana2_30b.doc_turns"


def tiny_config(**model_over) -> dict:
    """The configuration file as a rehearsal runs it (tiny stand-ins)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana2_30b.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "cells", CELL + ".json")) as f:
        rehearsal = json.load(f)["rehearsal"]
    config["model"] = {**config["model"], **rehearsal["model_overrides"],
                       **model_over}
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


# -- the model against the reference -----------------------------------------

def test_factory_picks_the_family_from_the_configuration(tiny):
    assert isinstance(tiny["model"], LatentForCausalLM)
    assert isinstance(build_model(MODEL_PRESETS["latent_tiny"]),
                      LatentForCausalLM)
    assert isinstance(build_model(MODEL_PRESETS["llama_tiny"]),
                      LlamaForCausalLM)
    cfg = tiny["cfg"]
    assert (cfg.latent_dim, cfg.first_k_dense) == (40, 1)
    assert cfg.rope_interleave and cfg.mlp_activation == "silu"
    assert (cfg.moe_num_experts, cfg.moe_held) == (128, 64)


def test_forward_agrees_with_the_reference(tiny):
    ids = jnp.asarray(_prompts([37])[0])
    logits, _ = tiny["model"].apply({"params": tiny["params"]}, ids[None])
    want = tiny["reference"].forward(tiny["params"], tiny["sizes"], ids)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_param_count_of_a_latent_model_is_the_tree(tiny):
    cfg = tiny["cfg"]
    leaves = jax.tree_util.tree_leaves(tiny["params"])
    assert cfg.num_params() == sum(x.size for x in leaves)
    fewer = cfg.num_params() - cfg.num_active_params()
    per_layer = 3 * cfg.hidden_size * cfg.moe_intermediate_size * (
        cfg.moe_held - cfg.num_experts_per_tok * cfg.moe_held
        / cfg.moe_num_experts)
    assert fewer == pytest.approx(
        (cfg.num_layers - cfg.first_k_dense) * per_layer, abs=4)


@pytest.mark.parametrize("interleaved", [True, False])
def test_rope_agrees_with_the_references(tiny, interleaved):
    """Pairs (2i, 2i + 1) or halves, against the reference's own rotation
    (written from the published description, not from ops.rope)."""
    sz = {**tiny["sizes"], "interleave": interleaved}
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 19, 3, sz["rope"]))
    cos, sin = rope_frequencies(sz["rope"], 64, sz["theta"])
    pos = jnp.arange(19)[None, :]
    got = apply_rope(x, cos, sin, pos, interleaved=interleaved)
    want = tiny["reference"].rope(x[0], sz)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=1e-6)
    # and the two conventions are not each other
    other = apply_rope(x, cos, sin, pos, interleaved=not interleaved)
    assert float(jnp.abs(other - got).max()) > 0.1


# -- one attention layer: absorbed = expanded, kernel = gather ---------------

@pytest.fixture(scope="module")
def layer():
    cfg = MODEL_PRESETS["latent_tiny"]
    attn = LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    cos, sin = rope_frequencies(cfg.qk_rope_head_dim, 64, cfg.rope_theta)
    params = attn.init(jax.random.PRNGKey(4), x, cos, sin, pos)["params"]
    return cfg, attn, params, x, pos, cos, sin


def _paged(cfg, rows=2, blocks_a_row=8, block=4):
    cache = init_latent_cache(rows * blocks_a_row + 1, block, cfg.latent_dim,
                              jnp.float32)
    tables = 1 + jnp.arange(rows * blocks_a_row).reshape(rows, blocks_a_row)
    return {**cache, "block_tables": tables}


def test_latent_pool_is_one_scatter_and_one_gather_away(layer):
    cfg = layer[0]
    cache = _paged(cfg)
    assert set(cache) == {"latent", "block_tables"}
    assert cache["latent"].shape == (17, 4, 128)  # 40 values in whole lanes
    rows = jax.random.normal(jax.random.PRNGKey(0), (2, 5, cfg.latent_dim))
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, -1, -1]])
    slots = slot_mapping(cache["block_tables"], pos, 4, 17)
    new = latent_update(cache, rows, slots)
    window = latent_gather(new, cache["block_tables"])
    assert window.shape == (2, 32, 128)
    np.testing.assert_array_equal(window[0, :5, :40], rows[0])
    np.testing.assert_array_equal(window[1, 7:10, :40], rows[1, :3])
    assert float(jnp.abs(window[..., 40:]).max()) == 0.0   # padding lanes
    assert float(jnp.abs(window[1, :7]).max()) == 0.0      # -1: dropped
    with pytest.raises(ValueError, match="int8"):
        init_latent_cache(4, 4, 40, "int8")


@pytest.mark.parametrize("chunk", [1, 3, 24])
def test_absorbed_equals_expanded_equals_no_cache(layer, monkeypatch, chunk):
    """The same function of one set of weights, three ways: the expanded form
    over the call's own tokens (no cache), and over the latent cache in calls
    of ``chunk`` tokens in the absorbed and in the expanded form."""
    cfg, attn, params, x, pos, cos, sin = layer
    want, _ = attn.apply({"params": params}, x, cos, sin, pos)
    for absorb_up_to in (1 << 30, 0):
        monkeypatch.setattr(latent, "ABSORB_MAX_QUERIES", absorb_up_to)
        cache, outs = _paged(cfg), []
        for at in range(0, 24, chunk):
            y, new = attn.apply({"params": params}, x[:, at:at + chunk], cos,
                                sin, pos[:, at:at + chunk], cache)
            cache = {**cache, **new}
            outs.append(y)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate(outs, axis=1)), np.asarray(want),
            atol=2e-5)


@pytest.mark.parametrize("absorb_up_to", [1 << 30, 0],
                         ids=["absorbed", "expanded"])
def test_the_loop_over_key_blocks_ends_at_the_calls_highest_position(
        layer, monkeypatch, absorb_up_to):
    """Several steps of 8 keys (two blocks of 4), a table width that is no
    multiple of a step, rows that end in different steps, a padding row."""
    cfg, attn, params, x, pos, cos, sin = layer
    want, _ = attn.apply({"params": params}, x, cos, sin, pos)
    monkeypatch.setattr(latent, "ABSORB_MAX_QUERIES", absorb_up_to)
    monkeypatch.setattr(latent, "KEY_BLOCK", 8)
    cache = _paged(cfg, rows=2, blocks_a_row=7)
    pos = pos.at[1, 13:].set(-1)          # row 1 is 13 tokens and padding
    x3 = jnp.concatenate([x, x[:1]])      # row 2: padding alone
    pos3 = jnp.concatenate([pos, jnp.full((1, 24), -1)])
    cache["block_tables"] = jnp.concatenate(
        [cache["block_tables"], jnp.zeros((1, 7), jnp.int32)])
    got, _ = attn.apply({"params": params}, x3, cos, sin, pos3, cache)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got[1, :13]),
                               np.asarray(want[1, :13]), atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


@pytest.fixture
def kernel_at_tiny_sizes(monkeypatch):
    """The prefill kernel path at sizes a test can run: calls of more than 4
    tokens a row in whole tiles of 8, kernel tiles of 8 queries and keys,
    loop steps of 8 keys (two blocks of 4)."""
    monkeypatch.setattr(latent, "ABSORB_MAX_QUERIES", 4)
    monkeypatch.setattr(latent, "KERNEL_TOKENS_MULTIPLE", 8)
    monkeypatch.setattr(latent, "KERNEL_BLOCK", 8)
    monkeypatch.setattr(latent, "KEY_BLOCK", 8)


@pytest.mark.parametrize("case", [
    "first_call_nothing_cached", "second_call_over_cached_latents",
    "unequal_rows_and_a_padding_row", "rows_from_zero_walk_nothing"])
def test_prefill_through_the_flash_kernel_equals_the_loop_and_the_full_forward(
        layer, kernel_at_tiny_sizes, case):
    """A call's own tokens through the flash forward kernel (interpreted),
    what earlier calls wrote through the loop, merged by their
    log-sum-exps: against the loop over all keys (the CPU form) and against
    the no-cache form over the whole sequence."""
    cfg, attn, params, x, pos, cos, sin = layer
    want, _ = attn.apply({"params": params}, x, cos, sin, pos)
    kernel = LatentAttention(dataclasses.replace(
        cfg, paged_attention_impl="kernel"))
    cache = _paged(cfg, rows=3, blocks_a_row=7)
    cache["block_tables"] = cache["block_tables"].at[2].set(0)
    x3 = jnp.concatenate([x, x[:1]])      # row 2: padding alone
    pad = jnp.full((1, 24), -1)
    if case == "first_call_nothing_cached":
        at, n, pos3 = (0, 0), (24, 24), jnp.concatenate([pos, pad])
    elif case == "rows_from_zero_walk_nothing":
        # a pool of NaNs: a step of the loop over any of it (0 x NaN under
        # the mask) would show, and the loop form does show it past row 1's
        # 13 tokens
        cache["latent"] = jnp.full_like(cache["latent"], jnp.nan)
        at, n = (0, 0), (24, 13)
        pos3 = jnp.concatenate([pos.at[1, 13:].set(-1), pad])
        assert LatentForCausalLM(kernel.cfg).prefill_kernel_counts(
            4, 24, [(24, 0), (13, 0)], 4) == (37, 0)
    else:
        # rows 0 and 1 have 8 tokens cached (the loop form wrote them)
        _, new = attn.apply({"params": params}, x3[:, :8], cos, sin,
                            jnp.concatenate([pos[:, :8], pad[:, :8]]), cache)
        cache = {**cache, **new}
        if case == "second_call_over_cached_latents":
            at, n = (8, 8), (16, 16)
        else:   # row 0 goes on from 8; row 1 is a fresh prompt of 11 tokens
            at, n = (8, 0), (16, 11)
        x3 = jnp.concatenate([x[:1, 8:], x[1:, at[1]:at[1] + 16], x[:1, :16]])
        pos3 = jnp.stack([
            jnp.where(jnp.arange(16) < n[r], at[r] + jnp.arange(16), -1)
            for r in range(2)] + [pad[0, :16]])
    got, pool = kernel.apply({"params": params}, x3, cos, sin, pos3, cache)
    loop, loop_pool = attn.apply({"params": params}, x3, cos, sin, pos3, cache)
    assert np.isfinite(np.asarray(got)).all()      # the padding tokens too
    for r in range(2):
        np.testing.assert_allclose(
            np.asarray(got[r, :n[r]]),
            np.asarray(want[r, at[r]:at[r] + n[r]]), atol=2e-5)
    if case == "rows_from_zero_walk_nothing":
        assert not np.isfinite(np.asarray(loop[1, :13])).all()
        return
    real = np.asarray(pos3 >= 0)
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(loop)[real],
                               atol=2e-5)
    # the pool is the loop form's; the padding row wrote nothing
    np.testing.assert_array_equal(np.asarray(pool["latent"]),
                                  np.asarray(loop_pool["latent"]))
    assert float(jnp.abs(pool["latent"][0]).max()) == 0.0


# contexts of 1, tile - 1, tile, tile + 1 and several tiles, in keys of a
# tile as ``ring_shape`` gives it at these shapes (384: 48 blocks of 8). The
# ``ring_`` cases are counted in tiles against the ring's depth D: a schedule
# of no tile, one, D - 1 (all sent before the loop; every step's own copies
# are the schedule's last tile again), D (one sent inside the loop), and
# 2D + 1 tiles of one row (every slot used three times) with short rows and
# empty rows after it.
KERNEL_CASES = {
    "one_row_one_key": lambda d, keys: [1],
    "one_row_under_a_tile": lambda d, keys: [keys - 1],
    "one_row_a_tile": lambda d, keys: [keys],
    "rows_round_a_tile": lambda d, keys: [keys - 1, keys, keys + 1],
    "rows_of_several_tiles": lambda d, keys: [
        0, 17, 2 * keys + 88, 2 * keys + 1],
    "ring_no_tile": lambda d, keys: [0, 0],
    "ring_one_tile": lambda d, keys: [0, 5],
    "ring_a_tile_short_of_the_depth": lambda d, keys: [keys * (d - 1) - 3],
    "ring_a_tile_short_over_rows": lambda d, keys: [9] * (d - 1),
    "ring_exactly_the_depth": lambda d, keys: [keys, 1, keys * (d - 2)],
    "ring_several_wraps": lambda d, keys: [
        keys * (2 * d + 1) - 7, 3, 0, keys + 1, 0, 40],
}
KERNEL_BLOCK, KERNEL_WIDTH = 8, 128


@pytest.mark.parametrize("name", sorted(KERNEL_CASES))
def test_kernel_interpreted_equals_the_gather_path(name):
    from dlti_tpu.ops.pallas.latent_attention import (
        latent_decode_attention, ring_shape,
    )

    blocks = 480  # a table wide enough for the longest row a case asks for
    tile, depth = ring_shape(KERNEL_BLOCK, blocks, 4 * KERNEL_WIDTH)
    lens = KERNEL_CASES[name](depth, tile * KERNEL_BLOCK)
    assert max(lens) <= blocks * KERNEL_BLOCK and depth >= 3
    lens = jnp.asarray(lens, jnp.int32)
    rows, heads, dim, value_dim, block = len(lens), 4, 40, 32, KERNEL_BLOCK
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (128, block, KERNEL_WIDTH))
    pool = pool.at[..., dim:].set(0.0)
    q = jax.random.normal(jax.random.PRNGKey(1), (rows, heads, dim))
    tables = jax.random.randint(jax.random.PRNGKey(2), (rows, blocks), 0, 128)
    got = latent_decode_attention(q, pool, tables, lens, value_dim=value_dim,
                                  scale=0.2, interpret=True)
    window = pool[tables].reshape(rows, blocks * block, KERNEL_WIDTH)
    s = jnp.einsum("bhd,bkd->bhk", q, window[..., :dim]) * 0.2
    live = jnp.arange(blocks * block)[None, None, :] < lens[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -1e30), -1) \
        * (lens > 0)[:, None, None]
    want = jnp.einsum("bhk,bkd->bhd", p, window[..., :value_dim])
    # float32 both ways; the kernel adds a tile at a time
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


# The cells' pools: rows of 640 bf16 values (1,280 B), blocks of 16; blocks a
# row of the table. What the rule gives there is what the sweep on the chip
# chose (PERF.md section 6, PR 55); a float32 pool's rows are twice as wide.
RING_SHAPES = {
    "doc_turns": (16, 544, 1280, (24, 4)),
    "fresh_docs": (16, 512, 1280, (24, 4)),
    "float32_rows": (16, 544, 2560, (24, 4)),
    "a_table_shorter_than_a_tile": (16, 8, 1280, (8, 4)),
    "rows_too_wide_for_three_whole_tiles": (16, 544, 8192, (6, 4)),
}


@pytest.mark.parametrize("name", sorted(RING_SHAPES))
def test_ring_shape_at_the_cells_shapes_fits_its_budget(name):
    from dlti_tpu.ops.pallas import latent_attention as kernel

    block, max_blocks, row_bytes, want = RING_SHAPES[name]
    tile, depth = kernel.ring_shape(block, max_blocks, row_bytes)
    assert (tile, depth) == want
    assert 3 <= depth <= kernel.RING_DEPTH
    assert tile * block <= kernel.TILE_KEYS and tile <= max_blocks
    assert depth * tile * block * row_bytes <= kernel.VMEM_RING_BUDGET
    assert kernel.tile_tokens(block, max_blocks, row_bytes) == tile * block


def test_a_latent_pools_engine_counts_tiles_by_the_latent_rule(tiny):
    """``decode_kernel_tile_tokens`` counts in the keys a step of the kernel
    that reads the pool covers: observed from the cache, not from a name."""
    from dlti_tpu.ops.pallas import latent_attention, paged_attention

    eng = _engine(tiny, block_size=8, max_model_len=8 * 64, num_blocks=128)
    pool = eng.executor.cache[0]["latent"]
    row_bytes = pool.shape[-1] * pool.dtype.itemsize
    assert eng.executor.decode_tile_tokens \
        == latent_attention.tile_tokens(8, 64, row_bytes) == 384
    assert paged_attention.tile_tokens(8, 64, row_bytes) == 256


def test_decode_through_the_kernel_equals_the_gather_path(layer):
    cfg, attn, params, x, pos, cos, sin = layer
    cache = _paged(cfg)
    _, new = attn.apply({"params": params}, x[:, :23], cos, sin, pos[:, :23],
                        cache)
    cache = {**cache, **new}
    step = (x[:, 23:], cos, sin, pos[:, 23:], cache)
    want, _ = attn.apply({"params": params}, *step)
    kernel = LatentAttention(dataclasses.replace(
        cfg, paged_attention_impl="kernel"))
    got, _ = kernel.apply({"params": params}, *step)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# -- the expert layer: gated experts, shares ---------------------------------

def _expert_layer(cfg, x, seed=5):
    layer = HeldExpertsMLP(cfg)
    return layer, layer.init(jax.random.PRNGKey(seed), x)["params"]


def test_two_shares_and_the_shared_experts_once_sum_to_the_whole(tiny):
    """What both chips of a layer compute, with what they compute alike (the
    shared experts) counted once, adds up to the uncut reference."""
    config = tiny_config(n_routed_experts=8)
    config["published"]["n_routed_experts"] = 8
    whole = dataclasses.replace(
        tiny["cfg"], moe_num_experts=8, moe_held_start=0, moe_held_count=8)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 11, whole.hidden_size))
    flat = x.reshape(33, -1)
    layer, params = _expert_layer(whole, x)
    assert {"w_gate", "w_up", "w_down", "shared_gate"} <= set(params)
    reference = tiny["reference"]
    sizes = reference.sizes(config)
    assert (sizes["experts"], sizes["held"], sizes["held_start"]) == (8, 8, 0)
    want = reference.experts(params, sizes, flat)
    total = -reference.shared_experts(params, flat)  # two shares hold it twice
    counted = 0
    for lo in (0, 4):
        half = dataclasses.replace(whole, moe_held_start=lo, moe_held_count=4)
        mine = {**params, **{k: params[k][lo:lo + 4]
                             for k in ("w_gate", "w_up", "w_down")}}
        y, counters = HeldExpertsMLP(half).apply({"params": mine}, x)
        total = total + y.reshape(33, -1)
        counted += int(counters[1])
        ref_half = reference.experts(
            mine, {**sizes, "held": 4, "held_start": lo}, flat)
        np.testing.assert_allclose(np.asarray(y.reshape(33, -1)),
                                   np.asarray(ref_half), atol=5e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
    assert counted == 33 * whole.num_experts_per_tok  # every assignment once


def test_held_experts_refuse_a_form_they_do_not_compute(tiny):
    x = jnp.zeros((1, 2, tiny["cfg"].hidden_size))
    for over in (dict(mlp_activation="gelu_tanh"),
                 dict(moe_scoring="softmax")):
        with pytest.raises(NotImplementedError, match="gated silu"):
            HeldExpertsMLP(dataclasses.replace(tiny["cfg"], **over)).init(
                jax.random.PRNGKey(0), x)


# -- through the engine: prefill then decode against the full forward --------

def _engine(tiny, **over):
    kw = dict(max_seqs=4, block_size=8, num_blocks=96, max_model_len=160,
              cache_dtype="float32")
    kw.update(over)
    cfg = dataclasses.replace(tiny["cfg"], **kw.pop("model", {}))
    return InferenceEngine(cfg, tiny["params"], EngineConfig(**kw))


def _hold_to_reference(tiny, prompts, results, atol=2e-4):
    """The engine's log-probs of its own greedy tokens against the
    reference's full forward over prompt + answer (no cache, no batch)."""
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
    "padded_bucket": dict(lengths=[21], engine={}),
    "unequal_batch": dict(lengths=[33, 5, 19], engine={}),
    "chunked_prefill": dict(
        lengths=[45, 23], engine=dict(max_prefill_tokens_per_step=16)),
    # decode through the Pallas kernel (interpreted), not the gather path
    "decode_kernel": dict(
        lengths=[40, 9], engine=dict(model=dict(paged_attention_impl="kernel"))),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_engine_prefill_then_decode_agrees_with_full_forward(tiny, name):
    case = SCENARIOS[name]
    eng = _engine(tiny, **case["engine"])
    prompts = _prompts(case["lengths"], seed=len(name))
    results = eng.generate(prompts,
                           SamplingParams(max_tokens=9, temperature=0.0))
    _hold_to_reference(tiny, prompts, results)
    st = eng.stats
    layers = tiny["cfg"].num_layers - tiny["cfg"].first_k_dense
    assert st["moe_assignments"] == layers * tiny["cfg"].num_experts_per_tok \
        * (sum(case["lengths"]) + 8 * len(prompts))
    assert 0 < st["moe_held_assignments_decode"] < st["moe_held_assignments"] \
        <= st["moe_assignments"]
    assert st["moe_expert_load_max_decode"] >= st["decode_steps"] > 0


def test_a_prefix_hit_equals_a_cold_prefill(tiny):
    """The second ask of a prompt meets its whole blocks but the last token's
    in the cache and prefills the 1-8 tokens left over cached latents: the
    same log-probs as the cold prefill, and the reference's."""
    eng = _engine(tiny, enable_prefix_caching=True)
    prompts = _prompts([70, 41, 16], seed=11)
    sp = SamplingParams(max_tokens=7, temperature=0.0)
    cold = eng.generate(prompts, sp)
    assert eng.stats["prefix_cached_tokens"] == 0
    assert eng.stats["prefill_context_tokens"] == 0
    warm = eng.generate(prompts, sp)
    # whole blocks of len - 1 tokens: 64 + 40 + 8
    assert eng.stats["prefix_cached_tokens"] == 112
    assert eng.stats["prefill_context_tokens"] == 112
    assert eng.stats["prefill_tokens"] == 127 + (127 - 112)
    for a, b in zip(cold, warm):
        assert a.output_token_ids == b.output_token_ids
        np.testing.assert_allclose(a.output_logprobs, b.output_logprobs,
                                   atol=2e-5)
    _hold_to_reference(tiny, prompts, warm)


def test_a_document_asked_again_while_it_is_answered_is_a_hit(tiny):
    """A prefilled prompt's whole blocks are matchable at once: the second
    ask, admitted while the first still decodes, shares the running
    sequence's latent blocks and agrees with the reference."""
    eng = _engine(tiny, enable_prefix_caching=True)
    prompt = _prompts([61], seed=17)[0]
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    first = eng.submit(prompt, sp)
    eng.step()
    second = eng.submit(prompt, sp)
    while eng.has_work:
        eng.step()
    assert eng.stats["prefix_cached_tokens"] == 56   # 7 whole blocks of 8
    assert eng.stats["prefill_tokens"] == 61 + 5
    assert first.output_token_ids == second.output_token_ids
    _hold_to_reference(tiny, [prompt, prompt],
                       [eng._result(first), eng._result(second)])


def test_a_long_prompt_goes_as_calls_of_the_models_limit(tiny, monkeypatch):
    """The family holds a prefill call to ``prefill_call_tokens`` padded
    tokens; a longer prompt is several calls, each over the latents the
    earlier ones wrote, and agrees with one call of the whole prompt."""
    prompts = _prompts([100, 37], seed=13)
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    whole = _engine(tiny)
    one_call = whole.generate(prompts, sp)
    assert whole.stats["prefill_batches"] == 2
    monkeypatch.setattr(LatentForCausalLM, "prefill_call_tokens", 32)
    eng = _engine(tiny)
    in_calls = eng.generate(prompts, sp)
    # 100 = 32 + 32 + 32 + 4 (bucket 8), 37 = 32 + 5: six calls
    assert eng.stats["prefill_batches"] == 6
    assert eng.stats["prefill_context_tokens"] == 32 + 64 + 96 + 32
    assert sorted(eng.executor._prefill_fns) == [8, 32]
    for a, b in zip(one_call, in_calls):
        assert a.output_token_ids == b.output_token_ids
        np.testing.assert_allclose(a.output_logprobs, b.output_logprobs,
                                   atol=2e-5)
    _hold_to_reference(tiny, prompts, in_calls)


def test_the_prefill_kernel_counters_reach_metrics(tiny):
    """A fresh 300-token prompt is one call of 512 padded tokens through the
    flash kernel by the rule as it stands (no constant patched): 300 query
    tokens, no step of the loop over cached latents. Asked again with
    another end, its 8-token tail takes the absorbed form and adds nothing
    to either count."""
    import types

    from dlti_tpu.serving.server import build_registry

    eng = _engine(tiny, max_model_len=640, num_blocks=192,
                  enable_prefix_caching=True,
                  model=dict(paged_attention_impl="kernel"))
    sp = SamplingParams(max_tokens=3, temperature=0.0)
    prompt = _prompts([300], seed=21)[0]
    results = eng.generate([prompt], sp)
    _hold_to_reference(tiny, [prompt], results)

    def counted():
        text = build_registry(
            types.SimpleNamespace(engine=eng)).render_prometheus()
        return [int(float(line.split()[-1])) for line in text.splitlines()
                if line.startswith(("dlti_mla_kernel_query_tokens_total ",
                                    "dlti_mla_walked_key_blocks_total "))]

    assert counted() == [300, 0]
    eng.generate([prompt[:296] + _prompts([8], seed=22)[0]], sp)
    assert eng.stats["prefix_cached_tokens"] == 296
    assert eng.stats["prefill_batches"] == 2
    assert counted() == [300, 0]


def test_a_prefill_call_takes_the_whole_block_table(tiny):
    """One program a (rows, bucket) whatever the context: a prefix hit's few
    tokens run the program a cold prompt of that bucket warmed."""
    eng = _engine(tiny, enable_prefix_caching=True)
    sp = SamplingParams(max_tokens=2, temperature=0.0)
    eng.generate(_prompts([6], seed=1), sp)       # bucket 8, cold
    fn = eng.executor._prefill_fns[8]
    before = fn._cache_size()
    prompt = _prompts([90], seed=2)
    eng.generate(prompt, sp)                      # buckets of a cold 90
    eng.generate(prompt, sp)                      # a hit: 88 cached, 2 left
    assert eng.stats["prefix_cached_tokens"] == 88
    assert fn._cache_size() == before


def test_memory_ledger_names_the_latent_pool(tiny):
    eng = _engine(tiny)
    owners = eng.memledger.snapshot()["owners"]
    want = tiny["cfg"].num_layers * 96 * 8 * 128 * 4   # rows in whole lanes
    assert owners["kv_block_pool"]["bytes"] == eng.executor.pool_bytes == want
    cache = init_cache(tiny["cfg"], 96, 8, 4, jnp.float32)
    assert [set(c) for c in cache] == [{"latent"}] * tiny["cfg"].num_layers


# -- what cannot serve a latent cache refuses ---------------------------------

REFUSED = {
    "int8_latents": (dict(cache_dtype="int8"), "int8 layout"),
    "host_tier": (dict(enable_prefix_caching=True, prefix_host_blocks=8),
                  "prefix tiers"),
    "disk_tier": (dict(enable_prefix_caching=True, prefix_disk_blocks=8,
                       prefix_disk_dir="/nonexistent"), "prefix tiers"),
    "speculative": (dict(speculative="ngram"), "speculative"),
    "int8_weights": (dict(quantization="int8"), "int8"),
    "adapter_pool": (dict(adapter_slots=2), "adapter"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_engine_refuses_at_start_up(tiny, name):
    over, said = REFUSED[name]
    with pytest.raises(ValueError, match=said):
        _engine(tiny, **over)


def test_hand_off_and_disaggregated_serving_refuse(tiny):
    from dlti_tpu.serving.disagg import DisaggController

    with pytest.raises(ValueError, match="latent blocks"):
        DisaggController(tiny["cfg"], tiny["params"], EngineConfig())
    eng = _engine(tiny)
    with pytest.raises(ValueError, match="export_handoff"):
        eng.export_handoff(eng.slots[0])
    with pytest.raises(ValueError, match="adopt_handoff"):
        eng.adopt_handoff({})


def test_tensor_parallel_mesh_refuses(tiny):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("tensor",))
    with pytest.raises(ValueError, match="tensor-parallel"):
        InferenceEngine(tiny["cfg"], tiny["params"], EngineConfig(),
                        mesh=mesh)


def test_lora_through_latent_attention_refuses(tiny):
    from dlti_tpu.config import LoRAConfig

    model = build_model(tiny["cfg"], LoRAConfig(enabled=True, r=4))
    with pytest.raises(NotImplementedError, match="LoRA"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
