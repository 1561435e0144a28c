"""A Llama-family model whose layers differ: a window a layer, a norm on
queries and keys, rotation on the window layers alone, held experts after a
leading dense layer. The program against its own full forward through
every cache path, each convention as a value, the walk over the cache
against the gather, the shares of the experts, and the dense presets'
programs as they were."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import MODEL_PRESETS, LoRAConfig, ModelConfig
from dlti_tpu.models import build_model
from dlti_tpu.models import llama as llama_mod
from dlti_tpu.models.llama import LlamaForCausalLM
from dlti_tpu.ops import attention as attention_mod
from dlti_tpu.ops.kv_cache import (
    init_paged_cache, paged_gather, paged_update, slot_mapping,
)
from dlti_tpu.serving.engine import EngineConfig, InferenceEngine
from dlti_tpu.serving.sampling import SamplingParams

TINY = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=5,
    num_heads=8, num_kv_heads=2, head_dim=16, max_seq_len=512,
    rope_theta=1e6, remat=False, dtype="float32", param_dtype="float32",
    layer_windows=(8, 8, 8, 0, 8), qk_norm=True, rope_on_full_layers=False,
    first_k_dense=1, moe_num_experts=8, moe_held_count=4,
    num_experts_per_tok=3, moe_intermediate_size=32,
    moe_shared_intermediate_size=32, moe_routed_scaling=2.5)


def init(cfg, seed=0):
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed),
                             jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module")
def tiny():
    model, params = init(TINY)
    return {"cfg": TINY, "model": model, "params": params}


def prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(3, 512, n)] for n in lengths]


def against_full_forward(model, params, prompt, result):
    """(largest |log-prob difference|, largest gap to the forward's best) of
    an engine's greedy answer, against the model's own pass without a cache."""
    ids = jnp.asarray([prompt + result.output_token_ids])
    lp = jax.nn.log_softmax(model.apply({"params": params}, ids)[0][0], -1)
    rows = lp[len(prompt) - 1:len(prompt) - 1 + len(result.output_token_ids)]
    theirs = np.asarray(rows[np.arange(len(rows)),
                             np.asarray(result.output_token_ids)])
    return (float(np.abs(theirs - np.asarray(result.output_logprobs)).max()),
            float((np.asarray(rows.max(-1)) - theirs).max()))


# -- the configuration ---------------------------------------------------------

def test_build_model_returns_the_llama_class_and_counts_its_parameters(tiny):
    assert isinstance(tiny["model"], LlamaForCausalLM)
    leaves = jax.tree_util.tree_leaves(tiny["params"])
    assert TINY.num_params() == sum(x.size for x in leaves)
    # of the routed experts a token uses top-k x held / all in the mean
    h, f = 64, 32
    assert TINY.num_params() - TINY.num_active_params() == \
        4 * int((4 - 3 * 4 / 8) * 3 * h * f)
    layer = tiny["params"]["model"]["layers_1"]
    assert layer["attn"]["q_norm"]["scale"].shape == (16,)
    assert layer["attn"]["k_norm"]["scale"].shape == (16,)
    assert layer["mlp"]["w_up"].shape == (4, 64, 32)
    assert "gate_proj" in tiny["params"]["model"]["layers_0"]["mlp"]
    assert tiny["model"].counter_names and tiny["model"].prefill_call_tokens


def test_layer_windows_win_over_the_one_window_and_form_the_groups():
    cfg = dataclasses.replace(TINY, sliding_window=128)
    assert [cfg.window_of_layer(i) for i in range(5)] == [8, 8, 8, None, 8]
    assert cfg.kv_group_windows == (0, 8)
    assert [cfg.kv_group_of_layer(i) for i in range(5)] == [1, 1, 1, 0, 1]
    mistral = MODEL_PRESETS["mistral_7b"]
    assert mistral.kv_group_windows == (4096,)
    assert mistral.window_of_layer(7) == 4096
    assert MODEL_PRESETS["qwen2_7b"].kv_group_windows == (0,)
    assert hash(dataclasses.replace(TINY, layer_windows=[8, 8, 8, 0, 8])) \
        == hash(TINY)                                  # a JSON list is a tuple
    with pytest.raises(ValueError, match="5 windows"):
        dataclasses.replace(TINY, num_layers=4)
    with pytest.raises(ValueError, match="several window lengths"):
        dataclasses.replace(TINY, layer_windows=(8, 16, 8, 0, 8))
    with pytest.raises(ValueError, match="several window lengths"):
        dataclasses.replace(TINY, layer_windows=(8, 16, 8, 16, 8))


@pytest.mark.parametrize("field", ["qk_norm", "rope_on_full_layers",
                                   "post_sublayer_norm"])
def test_each_convention_is_a_value_that_changes_the_logits(tiny, field):
    cfg = dataclasses.replace(TINY, **{field: not getattr(TINY, field)})
    model = build_model(cfg)
    params = tiny["params"]
    if field == "qk_norm":      # the same weights, without the two norms
        body = {name: ({**layer, "attn": {
            k: v for k, v in layer["attn"].items()
            if k not in ("q_norm", "k_norm")}}
            if name.startswith("layers_") else layer)
            for name, layer in params["model"].items()}
        params = {**params, "model": body}
        assert jax.tree_util.tree_structure(params) == \
            jax.tree_util.tree_structure(init(cfg)[1])
    else:                        # the other two change no tree
        assert jax.tree_util.tree_structure(params) == \
            jax.tree_util.tree_structure(init(cfg)[1])
    ids = jnp.asarray(prompts([40])[0])[None]
    ours = tiny["model"].apply({"params": tiny["params"]}, ids)[0]
    theirs = model.apply({"params": params}, ids)[0]
    assert float(jnp.abs(ours - theirs).max()) > 1e-2


def test_a_window_one_key_wider_changes_the_logits(tiny):
    wider = build_model(dataclasses.replace(
        TINY, layer_windows=(9, 9, 9, 0, 9)))
    ids = jnp.asarray(prompts([40])[0])[None]
    ours = tiny["model"].apply({"params": tiny["params"]}, ids)[0]
    theirs = wider.apply({"params": tiny["params"]}, ids)[0]
    # the first 8 positions see every key under either window
    assert float(jnp.abs(ours - theirs)[0, :8].max()) == 0.0
    assert float(jnp.abs(ours - theirs)[0, 9:].max()) > 1e-3


def test_lora_through_held_experts_is_refused():
    model = build_model(TINY, LoRAConfig(enabled=True, r=4))
    with pytest.raises(NotImplementedError, match="held experts"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


# -- the eight shares ------------------------------------------------------------

def test_the_shares_of_eight_ranks_add_up_to_the_uncut_layer():
    """Ranks 0-7 hold experts [2 r, 2 r + 2) of 16: their results, the
    shared expert counted once, equal the layer that holds all 16."""
    from dlti_tpu.models.moe import HeldExpertsMLP

    whole = dataclasses.replace(TINY, moe_num_experts=16, moe_held_count=0,
                                num_experts_per_tok=4)
    layer = HeldExpertsMLP(whole)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    full, counted = layer.apply({"params": params}, x)
    assert int(counted[1]) == 2 * 24 * 4            # every assignment held
    routed_only = {k: v for k, v in params.items() if "shared" not in k}
    shared = full - HeldExpertsMLP(dataclasses.replace(
        whole, moe_shared_intermediate_size=0)).apply(
            {"params": routed_only}, x)[0]
    total, held = jnp.zeros_like(full), 0
    for rank in range(8):
        cut = dataclasses.replace(whole, moe_held_start=2 * rank,
                                  moe_held_count=2)
        mine = {**params, **{k: params[k][2 * rank:2 * rank + 2]
                             for k in ("w_gate", "w_up", "w_down")}}
        y, n = HeldExpertsMLP(cut).apply({"params": mine}, x)
        total = total + (y - shared)
        held += int(n[1])
    assert held == 2 * 24 * 4
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(full),
                               atol=2e-5)


# -- through the cache ---------------------------------------------------------

@pytest.mark.parametrize("impl,block_size,call_tokens,cache_dtype", [
    ("gather", 4, 2048, "float32"),     # a window of two blocks
    ("gather", 16, 2048, "float32"),    # a window shorter than a block
    ("kernel", 4, 2048, "float32"),     # the decode kernel, interpreted
    ("gather", 4, 64, "float32"),       # 150 tokens: prefill in three calls
    ("kernel", 4, 32, "float32"),       # ... in five, then the kernel
    ("gather", 4, 64, "int8"),          # int8 keys and values
])
def test_the_engine_agrees_with_the_full_forward(tiny, monkeypatch, impl,
                                                 block_size, call_tokens,
                                                 cache_dtype):
    """Prefill (in one call or several, each over what the earlier wrote)
    then decode through both groups of the cache, short and long prompts
    in one batch, against the model's pass without a cache."""
    monkeypatch.setattr(llama_mod, "PREFILL_CALL_TOKENS", call_tokens)
    cfg = dataclasses.replace(TINY, paged_attention_impl=impl)
    eng = InferenceEngine(cfg, tiny["params"], EngineConfig(
        max_seqs=4, block_size=block_size, num_blocks=256 // block_size * 5,
        max_model_len=256, cache_dtype=cache_dtype))
    asked = prompts([5, 150, 37, 90, 21, 60])
    out = eng.generate(asked, SamplingParams(temperature=0.0, max_tokens=10))
    limit = 0.05 if cache_dtype == "int8" else 2e-4
    for prompt, result in zip(asked, out):
        diff, gap = against_full_forward(tiny["model"], tiny["params"],
                                         prompt, result)
        assert diff < limit and gap < limit, (len(prompt), diff, gap)
    assert eng.kv_freed["window", "window"] > 0
    if call_tokens < 150:
        assert eng.stats["prefill_batches"] >= 6 + 150 // call_tokens
    assert eng.window_manager.num_free == eng.window_manager.num_blocks - 1
    assert eng.block_manager.num_free == eng.block_manager.num_blocks - 1
    assert eng.stats["moe_held_assignments"] > 0


def test_chunked_prefill_over_the_window_group(tiny):
    ec = EngineConfig(max_seqs=4, block_size=4, num_blocks=320,
                      max_model_len=256, cache_dtype="float32",
                      max_prefill_tokens_per_step=48)
    eng = InferenceEngine(TINY, tiny["params"], ec)
    asked = prompts([70, 9, 130, 33, 52], seed=3)
    out = eng.generate(asked, SamplingParams(temperature=0.0, max_tokens=13))
    for prompt, result in zip(asked, out):
        diff, gap = against_full_forward(tiny["model"], tiny["params"],
                                         prompt, result)
        assert diff < 2e-4 and gap < 2e-4, (len(prompt), diff, gap)
    assert eng.window_manager.num_free == eng.window_manager.num_blocks - 1


# -- the walk against the gather -----------------------------------------------

@pytest.mark.parametrize("window", [None, 24])
def test_the_walk_over_the_cache_equals_the_gather(monkeypatch, window):
    """Rows at different depths, one padding row, queries in blocks: the
    walk with its online softmax against one score array over the whole
    table."""
    monkeypatch.setattr(attention_mod, "WALK_KEYS", 32)
    monkeypatch.setattr(attention_mod, "WALK_QUERIES", 16)
    rng = np.random.RandomState(0)
    rows, s, heads, kv, d, bs, nblk = 3, 40, 4, 2, 8, 4, 40
    cache = init_paged_cache(1, rows * nblk + 1, bs, kv, d, jnp.float32)[0]
    tables = jnp.asarray(1 + np.arange(rows * nblk).reshape(rows, nblk),
                         jnp.int32)
    starts = [100, 0, 55]
    # what earlier calls wrote, then this call's own
    for r, start in enumerate(starts):
        pos = jnp.arange(start + s)[None]
        k = jnp.asarray(rng.randn(1, start + s, kv, d), jnp.float32)
        v = jnp.asarray(rng.randn(1, start + s, kv, d), jnp.float32)
        cache = paged_update(cache, k, v, slot_mapping(
            tables[r:r + 1], pos, bs, rows * nblk + 1))
    positions = np.stack([start + np.arange(s) for start in starts])
    positions[1, 25:] = -1                      # a short row's padding
    positions = jnp.asarray(positions, jnp.int32)
    q = jnp.asarray(rng.randn(rows, s, heads, d), jnp.float32)
    walked = attention_mod.attend_over_cache(q, cache, tables, positions,
                                             window)
    ck, cv = paged_gather(cache, tables)
    gathered = attention_mod.reference_attention(
        q, ck, cv, causal=True, q_positions=positions, window=window)
    real = np.asarray(positions >= 0)
    np.testing.assert_allclose(np.asarray(walked)[real],
                               np.asarray(gathered)[real], atol=2e-6)
    assert float(jnp.abs(walked[1, 25:]).max()) == 0.0   # padding reads 0


def test_which_calls_walk_is_read_from_shapes_alone():
    walks = attention_mod.walks_cache
    assert not walks(1, 16384, True)            # a decode step gathers
    assert not walks(2048, 4096, False)         # the dense cells' tables
    assert not walks(5, attention_mod.GATHER_MAX_KEYS, False)
    assert walks(16, 16384, False) and walks(16, 2192, True)


# -- the dense presets are the programs they were ------------------------------

def narrow(name):
    """The preset's family knobs (window, biases, GQA ratio, theta) at test
    widths."""
    cfg = MODEL_PRESETS[name]
    return dataclasses.replace(
        cfg, vocab_size=512, hidden_size=cfg.num_heads * 4, head_dim=None,
        intermediate_size=96, num_layers=2, max_seq_len=256, remat=False,
        dtype="float32", param_dtype="float32")


def sha(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


PARENT = {
    "mistral_7b": {
        "decode": "d0e054da3edad2d30a120025da7855a16df9fd42e2d5cdf4e895f1f03802c261",
        "prefill": "e889ce67c7b9a98a15f6eb6f644f4e7bf0d78cc27f6523ab96edc567a6888a83",
        "train": "5f78927809fbbcc96d06477e3005b3bf4de6a7178f8a1dc8e459b8fd1ab5e04d",
        "tree": "32578896e834d2de20a200433bb5a739413e8ffe7ea44a53800ff6c7a97006d8"},
    "qwen2_7b": {
        "decode": "a9fc0cc93eacc5901a5b912890850f62713ad4d446d91f65d2b3c02861955204",
        "prefill": "71e824a8e45bd7ac4c9b9cf5aab0c7208b29b69673df3a5a3bc0476627faa370",
        "train": "521295162228289d68ffc08c3aeaad2d86a531dc72fe77416d345530eecc5562",
        "tree": "a2cfbee9d3dc1b2803a9c0cdc6ea85523b8383fd965943d0e7528e8d2d948eb3"},
}


@pytest.mark.parametrize("name", ["mistral_7b", "qwen2_7b"])
def test_the_dense_presets_lower_to_the_programs_they_were(name):
    """The parameter tree, the decode program, a prefill program (the
    gather stays for a table of 4,096 keys) and the LoRA training step of
    ``mistral_7b`` and ``qwen2_7b`` at test widths: hashes taken at the
    parent commit with this function under this suite's conftest (a change
    that means to change their programs re-pins them: PR 47 re-took
    ``"prefill"`` alone, whose program now heads one position a row; the
    other three are ``c1c8496``'s and say nothing else moved)."""
    from dlti_tpu.training.step import causal_lm_loss

    cfg = narrow(name)
    model, params = init(cfg)
    ex = InferenceEngine(cfg, params, EngineConfig(
        max_seqs=4, block_size=4, num_blocks=64, max_model_len=128)).executor
    pk = ex.round_packing
    packed = jnp.zeros((pk.num_slots, pk.width), jnp.int32)
    ids = jnp.zeros((2, 32), jnp.int32)
    got = {
        "decode": sha(ex._decode_fn.lower(ex.params, ex.cache, ex._no_prev,
                                          packed)),
        "prefill": sha(ex._prefill_fn(32).lower(
            ex.params, ex.cache, ids, ids, jnp.zeros((2, 16), jnp.int32),
            jnp.zeros((2,), jnp.int32))),
    }
    lmodel = build_model(cfg, LoRAConfig(enabled=True, r=4, alpha=8))
    lparams = lmodel.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]

    def loss(p, ids, seg):
        logits, _ = lmodel.apply({"params": p}, ids, segment_ids=seg)
        total, count = causal_lm_loss(logits, ids,
                                      (seg > 0).astype(jnp.int32))
        return total / count

    got["train"] = sha(jax.jit(jax.value_and_grad(loss)).lower(
        lparams, jnp.zeros((2, 64), jnp.int32), jnp.ones((2, 64), jnp.int32)))
    paths = [(jax.tree_util.keystr(k), v.shape)
             for k, v in jax.tree_util.tree_leaves_with_path(params)]
    got["tree"] = hashlib.sha256(repr(paths).encode()).hexdigest()
    assert got == PARENT[name]
    assert pk.window_blocks == 0 and len(ex.kv_groups) == 1


@pytest.mark.parametrize("keep", [None, 0, 1, 2])
def test_a_call_over_the_cache_is_the_program_it_was_whatever_is_kept(keep):
    """``remat_keep_blocks`` is the trainer's count of blocks that keep
    their activations; a call with a cache has no backward, so the decode
    and prefill programs of a configuration with remat on are the ones
    pinned above for remat off, whatever the count says."""
    cfg = dataclasses.replace(narrow("mistral_7b"), remat=True,
                              remat_keep_blocks=keep)
    _, params = init(cfg)
    ex = InferenceEngine(cfg, params, EngineConfig(
        max_seqs=4, block_size=4, num_blocks=64, max_model_len=128)).executor
    pk = ex.round_packing
    ids = jnp.zeros((2, 32), jnp.int32)
    assert sha(ex._decode_fn.lower(
        ex.params, ex.cache, ex._no_prev,
        jnp.zeros((pk.num_slots, pk.width), jnp.int32))) \
        == PARENT["mistral_7b"]["decode"]
    assert sha(ex._prefill_fn(32).lower(
        ex.params, ex.cache, ids, ids, jnp.zeros((2, 16), jnp.int32),
        jnp.zeros((2,), jnp.int32))) == PARENT["mistral_7b"]["prefill"]


# -- the other held-expert families keep their grouped prefill -------------------

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "yarn"}
# name: (held experts, hidden, expert width as published; the family's knobs
# over ``latent_tiny`` with experts one chunk wide; the kernel's jaxpr at the
# published sizes; the engine's prefill program of 1 x 512 tokens at the
# tiny ones)
GROUPED_PARENT = {
    "kanana2_30b": (
        (64, 2048, 768),
        dict(moe_held_start=0, moe_held_count=4),
        "976d6ef1e0a9d84542ccf5d07ac6b181304f1fdd72209d3c4a6a3952acc7864e",
        "06e3f28e491f52ceea01dc5ab1d74602ecb53463e171ba1cf1cb821fe9a16307"),
    "xing4_29b": (
        (64, 3584, 1024),
        dict(num_layers=4, first_k_dense=2, q_lora_rank=24, rope_scaling=YARN,
             hc_mult=4, moe_shared_intermediate_size=24,
             num_experts_per_tok=4, moe_routed_scaling=2.0),
        "2808634fcc605124d271e202939cf225247e4c0d873b656b0158a051f6237c4f",
        "8dfce5748aec289c87883293b990077a3039a0101a8a7a2f70e931d48a3ec218"),
}


@pytest.mark.parametrize("name", sorted(GROUPED_PARENT))
def test_the_other_grouped_prefills_are_the_programs_they_were(name):
    """``ops/pallas/grouped_experts.py`` gained a second grid axis for an
    expert that does not fit VMEM; an expert that fits keeps the one-axis
    kernel. What Mosaic is handed at the published sizes of ``kanana2_30b``
    and ``xing4_29b`` (the jaxpr of the ``pallas_call``: body, grid, blocks,
    compiler parameters; traced, not run) and the family's whole prefill
    program through the grouped path at test widths: hashes taken at the
    parent commit with this function under this suite's conftest (the
    Mosaic payload itself carries source lines, so its bytes differ with any
    edit of the file). The programs' hashes were re-taken in PR 47 (the
    head over one position a row); the kernel's jaxprs are ``c1c8496``'s."""
    from dlti_tpu.models.moe import takes_grouped
    from dlti_tpu.ops.pallas import grouped_experts as ge

    (experts, h, f), knobs, kernel, program = GROUPED_PARENT[name]
    tile, struct = 128, jax.ShapeDtypeStruct
    rows = ge.num_tiles(2048 * 4, experts, tile) * tile
    jaxpr = jax.make_jaxpr(
        lambda x, te, t, g, u, d: ge._forward_only(
            x, te, t, g, u, d, tile, ge.WIDTH_CHUNK, False))(
        struct((rows, h), jnp.bfloat16), struct((rows // tile,), jnp.int32),
        struct((), jnp.int32), struct((experts, h, f), jnp.bfloat16),
        struct((experts, h, f), jnp.bfloat16),
        struct((experts, f, h), jnp.bfloat16))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() == kernel

    cfg = dataclasses.replace(
        MODEL_PRESETS["latent_tiny"], max_seq_len=1024,
        moe_intermediate_size=256, moe_scoring="sigmoid_bias", **knobs)
    assert takes_grouped(512, cfg.moe_intermediate_size)
    _, params = init(cfg)
    ex = InferenceEngine(cfg, params, EngineConfig(
        max_seqs=4, block_size=4, num_blocks=300,
        max_model_len=1024)).executor
    ids = jnp.zeros((1, 512), jnp.int32)
    assert sha(ex._prefill_fn(512).lower(
        ex.params, ex.cache, ids, ids, jnp.zeros((1, 256), jnp.int32),
        jnp.zeros((1,), jnp.int32))) == program


# -- experts too wide for one block ---------------------------------------------

def test_an_expert_that_does_not_fit_has_its_width_in_grid_blocks(monkeypatch):
    """``width_block`` from shapes alone: the published experts of the other
    held-expert models are one block (the kernel they had), 6,144 x 2,048
    gated is two; and the two-axis grid computes what the one-axis grid
    does."""
    from dlti_tpu.ops.pallas import grouped_experts as ge

    assert ge.width_block(3584, 1024, 2, True) == 1024      # xing4_29b
    assert ge.width_block(2048, 768, 2, True) == 768        # kanana2_30b
    assert ge.width_block(6144, 2048, 2, True) == 1024      # kexaone_236b
    assert 2 * 3 * 6144 * 2048 * 2 > 128 << 20              # whole: past VMEM
    rng = np.random.RandomState(0)
    experts, h, f, tile = 3, 32, 512, 8
    local = jnp.asarray(rng.randint(0, experts + 1, (24, 2)), jnp.int32)
    sizes = jnp.bincount(local.reshape(-1), length=experts + 1)[:experts] \
        .astype(jnp.int32)
    row, source, tile_expert, tiles = ge.group_rows(local, sizes, tile)
    x = jnp.asarray(rng.randn(24, h), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.randn(experts, h, f) * 0.2, jnp.float32)
                    for _ in range(2))
    w_down = jnp.asarray(rng.randn(experts, f, h) * 0.2, jnp.float32)
    rows = jnp.take(x, source, axis=0)

    def run():
        return ge._forward_only(rows, tile_expert, tiles, w_gate, w_up,
                                w_down, tile, ge.WIDTH_CHUNK, True)

    whole = run()
    monkeypatch.setattr(ge, "VMEM_WEIGHTS", 2 * 3 * h * 256 * 4)
    assert ge.width_block(h, f, 4, True) == 256
    blocked = run()
    n = int(tiles) * tile
    np.testing.assert_allclose(np.asarray(blocked)[:n], np.asarray(whole)[:n],
                               rtol=1e-5, atol=1e-5)
