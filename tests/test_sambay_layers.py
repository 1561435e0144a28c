"""The decoder-hybrid-decoder family (phi4flash / SambaY: Mamba-1,
differential attention under a window and over every key, gated memory units
and cross-attention over one shared pool) against its plain reference, at
tiny sizes on the CPU with seeded weights.

Both sides take their sizes from the benchmark's configuration file laid over
with the cell's rehearsal stand-ins, as the harness does: the program through
``chip_child.model_fields`` -> ``ModelConfig``, the reference through its own
``sizes(config)``. The stand-in has 12 layers in the published order of
kinds (``SDSDSDSDGXGX``) and a window of 16 keys.
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

from dlti_tpu.config import MODEL_PRESETS, ModelConfig  # noqa: E402
from dlti_tpu.models import build_model  # noqa: E402
from dlti_tpu.models import sambay as sambay_mod  # noqa: E402
from dlti_tpu.models.mamba1 import Mamba1Mixer  # noqa: E402
from dlti_tpu.models.sambay import SambaYForCausalLM  # noqa: E402
from dlti_tpu.ops.kv_cache import (  # noqa: E402
    bind_call, init_cache, init_recurrent_state, unbind_call,
)
from dlti_tpu.serving.engine import EngineConfig, InferenceEngine  # noqa: E402
from dlti_tpu.serving.sampling import SamplingParams  # noqa: E402

CELL = "serve.phi4_mini_flash.reasoning_turns"
WINDOW = 16


def tiny_config() -> dict:
    """The configuration file as a rehearsal runs it (tiny stand-ins)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4_mini_flash.json")) as f:
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
    ref_logprobs = jax.jit(lambda p, ids: jax.nn.log_softmax(
        reference.forward(p, sizes, ids), -1))
    return {"config": config, "cfg": cfg, "model": model, "params": params,
            "reference": reference, "sizes": sizes,
            "ref_logprobs": ref_logprobs}


def _prompts(lengths, seed=0, vocab=512):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(3, vocab, size=n)] for n in lengths]


# -- the model against the reference -----------------------------------------

def test_factory_picks_the_family_and_the_file_states_the_published_order(
        tiny):
    cfg = tiny["cfg"]
    assert isinstance(tiny["model"], SambaYForCausalLM)
    assert cfg.is_sambay and cfg.has_recurrent_state
    assert cfg.layer_pattern == "SDSDSDSDGXGX"
    assert (cfg.shared_memory_layer, cfg.shared_kv_layer) == (6, 7)
    assert cfg.kv_group_windows == (0, WINDOW)
    # the reference derives the same kinds from the catalog's two keys
    names = {"S": "mamba", "G": "memory_unit", "X": "cross"}
    assert tiny["sizes"]["kinds"] == [
        names.get(k, "window" if w else "full")
        for k, w in zip(cfg.layer_pattern, cfg.layer_windows)]
    assert isinstance(build_model(MODEL_PRESETS["sambay_tiny"]),
                      SambaYForCausalLM)


def test_forward_agrees_with_the_reference_past_the_window(tiny):
    ids = jnp.asarray(_prompts([3 * WINDOW + 5], seed=1)[0])
    want = tiny["reference"].forward(tiny["params"], tiny["sizes"], ids)
    got, _ = tiny["model"].apply({"params": tiny["params"]}, ids[None])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=5e-5, rtol=1e-4)
    # a stack whose outputs ignore its input passes any comparison
    assert len(set(np.asarray(want.argmax(-1)).tolist())) > ids.shape[0] // 2


def test_param_count_of_the_family_is_the_tree(tiny):
    n = sum(x.size for x in jax.tree_util.tree_leaves(tiny["params"]))
    assert tiny["cfg"].num_params() == n


PATTERN_REFUSED = {
    "mixed_families": dict(layer_pattern="SD*M"),
    "memory_unit_before_any_scan": dict(layer_pattern="GDSD"),
    "cross_layer_before_any_pool": dict(layer_pattern="SXSD"),
    "shared_pool_under_a_window": dict(layer_pattern="SDSX",
                                       layer_windows=(0, 8, 0, 0)),
    "window_on_a_layer_without_keys": dict(layer_pattern="SDSD",
                                           layer_windows=(8, 0, 0, 0)),
}


@pytest.mark.parametrize("name", sorted(PATTERN_REFUSED))
def test_pattern_is_checked(name):
    with pytest.raises(ValueError, match="layer_pattern|layer_windows"):
        dataclasses.replace(MODEL_PRESETS["sambay_tiny"], **{
            "num_layers": 4, "layer_windows": (), **PATTERN_REFUSED[name]})


def _without(params, layer, sizes_kind):
    """The tree with layer ``layer``'s mixer output projection zeroed: the
    program as if that mixer were dropped."""
    mixer = dict(params[f"layers_{layer}"]["mixer"])
    key = "o_proj" if sizes_kind in ("cross", "window", "full") else "out_proj"
    mixer[key] = jax.tree_util.tree_map(jnp.zeros_like, mixer[key])
    return {**params, f"layers_{layer}": {
        **params[f"layers_{layer}"], "mixer": mixer}}


@pytest.mark.parametrize("layer", range(12))
def test_a_dropped_mixer_fails_the_comparison(tiny, layer):
    """Every mixer's term is large enough to be seen: the reference on the
    stated weights against the program with one mixer's output dropped
    differs by far more than the agreement's tolerance; so does a program
    whose memory units read nothing (layer 6's ``y`` is the only thing a
    memory unit multiplies by)."""
    ids = jnp.asarray(_prompts([40], seed=2)[0])
    want = tiny["ref_logprobs"](tiny["params"], ids)
    dropped = _without(tiny["params"], layer, tiny["sizes"]["kinds"][layer])
    got = jax.nn.log_softmax(tiny["model"].apply(
        {"params": dropped}, ids[None])[0][0], -1)
    assert float(jnp.abs(got - want).max()) > 0.05


SEEDED_TERMS = {
    "attention_biases": (("mixer", "qkv_proj", "bias"), 1),
    "cross_query_bias": (("mixer", "q_proj", "bias"), 9),
    "out_proj_bias": (("mixer", "o_proj", "bias"), 7),
    "layer_norm_bias": (("input_norm", "bias"), 4),
    "lambda_vector": (("mixer", "lambda_q1"), 3),
    "head_norm_weight": (("mixer", "subln"), 11),
    "conv_bias": (("mixer", "conv_bias"), 0),
}


@pytest.mark.parametrize("name", sorted(SEEDED_TERMS))
def test_seeded_terms_are_away_from_their_means(tiny, name):
    path, layer = SEEDED_TERMS[name]
    leaf = tiny["params"][f"layers_{layer}"]
    for key in path:
        leaf = leaf[key]
    centred = leaf - (1.0 if path[-1] == "subln" else 0.0)
    assert float(jnp.abs(centred).mean()) > 0.05


# -- differential attention: the padded-query form ---------------------------

@pytest.mark.parametrize("window", [None, 5])
def test_padded_queries_give_the_four_plain_softmaxes(tiny, window):
    """``[q1 ; 0]`` and ``[0 ; q2]`` against key rows ``[k1 ; k2]`` under
    the GQA kernels' ``(2 d) ** -0.5``, scaled by sqrt 2, are the two
    softmaxes of a pair at ``d ** -0.5``: the layer against the reference's
    ``differential`` on the same projections."""
    cfg, sizes, ref = tiny["cfg"], tiny["sizes"], tiny["reference"]
    layer = sambay_mod.DiffAttention(cfg, False, window)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 23, cfg.hidden_size))
    pos = jnp.arange(23)[None]
    p = layer.init(jax.random.PRNGKey(4), x, pos, 5)["params"]
    got, _, _ = layer.apply({"params": p}, x, pos, 5)
    d, nh, nkv = sizes["head_dim"], sizes["heads"], sizes["kv_heads"]
    q, k, v = jnp.split(ref._linear(p["qkv_proj"], x[0]),
                        [nh * d, (nh + nkv) * d], axis=-1)
    want = ref.differential(p, sizes, 5, ref._heads(q, nh),
                            ref._heads(k, nkv), ref._heads(v, nkv),
                            window or 0)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("window", [None, 16])
def test_a_fused_pool_reads_as_the_pool_of_separate_heads(window):
    """The decode kernel (interpreted), the gather and the update over a
    fused pool ``(blocks, block, kv_heads * d)`` against the same values in a
    pool of ``(blocks, block, kv_heads, d)``: 5 kv heads, a count the 4-D
    tiling would pad."""
    from dlti_tpu.ops.attention import reference_attention
    from dlti_tpu.ops.kv_cache import paged_gather, paged_update
    from dlti_tpu.ops.pallas.paged_attention import paged_decode_attention

    rng = np.random.RandomState(0)
    nb, bs, kvh, hd, heads, rows, width = 40, 8, 5, 32, 20, 3, 12
    k4, v4 = (jnp.asarray(rng.randn(nb, bs, kvh, hd), jnp.float32)
              for _ in range(2))
    fused = {"k": k4.reshape(nb, bs, -1), "v": v4.reshape(nb, bs, -1)}
    q = jnp.asarray(rng.randn(rows, 1, heads, hd), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb)[:rows * width].reshape(
        rows, width), jnp.int32)
    lens = jnp.asarray([70, 1, 33], jnp.int32)
    want = paged_decode_attention(q, k4, v4, tables, lens, window=window,
                                  interpret=True)
    got = paged_decode_attention(q, fused["k"], fused["v"], tables, lens,
                                 window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    ck, cv = paged_gather(fused, tables, hd)
    assert ck.shape == (rows, width * bs, kvh, hd)
    plain = reference_attention(q, ck, cv, causal=True,
                                q_positions=(lens - 1)[:, None],
                                window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), atol=2e-6)
    new = jnp.asarray(rng.randn(rows, 2, kvh, hd), jnp.float32)
    slots = jnp.asarray([[3, 4], [nb * bs, 9], [17, nb * bs]])  # two dropped
    wrote = paged_update(fused, new, new, slots)
    wrote4 = paged_update({"k": k4, "v": v4}, new, new, slots)
    for key in ("k", "v"):
        assert wrote[key].shape == (nb, bs, kvh * hd)
        np.testing.assert_array_equal(
            np.asarray(wrote[key]).reshape(nb, bs, kvh, hd),
            np.asarray(wrote4[key]))


def test_lambda_depends_on_the_layers_index(tiny):
    assert abs(float(sambay_mod.lambda_init(0)) - 0.2) < 1e-6
    assert abs(float(sambay_mod.lambda_init(17))
               - (0.8 - 0.6 * np.exp(-5.1))) < 1e-6


# -- Mamba-1: scan, single steps, padding ------------------------------------

@pytest.fixture(scope="module")
def mamba(tiny):
    cfg = tiny["cfg"]
    layer = Mamba1Mixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 21, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(21), (2, 21))
    params = layer.init(jax.random.PRNGKey(6), x, pos)["params"]
    return cfg, layer, params, x, pos


def _state(cfg, slots=4):
    return init_recurrent_state(
        slots, cfg.mamba_conv_kernel, cfg.mamba_inner_size,
        (cfg.mamba_inner_size, cfg.mamba_state_size), jnp.float32,
        jnp.float32)


def test_mamba1_scan_equals_one_token_after_another(mamba):
    cfg, layer, params, x, pos = mamba
    want, want_y, _ = layer.apply({"params": params}, x, pos)
    cache = {**_state(cfg, 2), "state_slots": jnp.arange(2),
             "own_rows": True}
    outs, ys = [], []
    for t in range(x.shape[1]):
        out, y, new = layer.apply({"params": params}, x[:, t:t + 1],
                                  pos[:, t:t + 1], cache)
        cache = {**cache, **new}
        outs.append(out)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(want), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(want_y), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("unroll", [1, 8])
def test_mamba1_padding_advances_nothing_and_calls_continue(
        mamba, unroll, monkeypatch):
    """A prompt fed as two padded calls into a slot ends in the state, and
    gives the outputs, of one call over all of it, at any unroll of the
    scan; a padding row writes nothing."""
    import dlti_tpu.models.mamba1 as mamba1_mod

    monkeypatch.setattr(mamba1_mod, "SCAN_UNROLL", unroll)
    cfg, layer, params, x, pos = mamba
    whole = {**_state(cfg), "state_slots": jnp.asarray([2, 0]),
             "own_rows": False}
    want, _, want_state = layer.apply({"params": params}, x, pos, whole)
    cache = {**_state(cfg), "state_slots": jnp.asarray([2, 0, 9]),
             "own_rows": False}
    cache["ssm"] = cache["ssm"].at[1].set(7.0)  # nobody's slot
    outs = []
    for lo, hi in ((0, 13), (13, 21)):
        n, width = hi - lo, 16
        xs = jnp.zeros((3, width, x.shape[2])).at[:2, :n].set(x[:, lo:hi])
        ps = jnp.full((3, width), -1).at[:2, :n].set(pos[:, lo:hi])
        out, _, new = layer.apply({"params": params}, xs, ps, cache)
        cache = {**cache, **new}
        outs.append(out[:2, :n])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(want), atol=2e-5, rtol=1e-4)
    for key in ("conv", "ssm"):
        np.testing.assert_allclose(np.asarray(cache[key][jnp.asarray([2, 0])]),
                                   np.asarray(want_state[key][
                                       jnp.asarray([2, 0])]),
                                   atol=2e-5, rtol=1e-4)
    assert (np.asarray(cache["ssm"][1]) == 7.0).all()
    assert (np.asarray(cache["ssm"][3]) == 0.0).all()


# -- the cache: recurrent entries, two groups, entries that hold nothing ------

def test_cache_has_an_entry_a_layer_by_its_kind(tiny):
    cfg = tiny["cfg"]
    cache = init_cache(cfg, 32, 8, 4, jnp.float32, call_tokens=64)
    d_in, n = cfg.mamba_inner_size, cfg.mamba_state_size
    width = cfg.num_kv_heads * cfg.resolved_head_dim
    from dlti_tpu.ops.kv_cache import window_group_blocks

    window_pool = window_group_blocks(WINDOW, 8, 4, 64)
    for i, (kind, entry) in enumerate(zip(cfg.layer_pattern, cache)):
        shapes = {k: v.shape for k, v in entry.items()}
        if kind == "S":
            assert shapes == {"conv": (4, 3, d_in), "ssm": (4, d_in, n)}
            assert entry["ssm"].dtype == jnp.float32
        elif kind == "D":
            # fused rows: a token's paired heads side by side, whole lanes
            blocks = window_pool if cfg.layer_windows[i] else 32
            assert shapes == {"k": (blocks, 8, width),
                              "v": (blocks, 8, width)}
        else:
            assert shapes == {}
    tables = ({"block_tables": jnp.zeros((2, 3), jnp.int32)},
              {"block_tables": jnp.ones((2, 5), jnp.int32),
               "table_base": jnp.zeros((2,), jnp.int32)})
    groups = [cfg.kv_group_of_layer(i) for i in range(cfg.num_layers)]
    bound = bind_call(cache, tables, jnp.asarray([1, 4]), False,
                      groups=groups)
    for i, (kind, entry) in enumerate(zip(cfg.layer_pattern, bound)):
        assert ("state_slots" in entry) == (kind == "S")
        assert ("table_base" in entry) == bool(cfg.layer_windows[i])
        # a cross layer reads the full group's tables
        assert entry["block_tables"] is tables[groups[i]]["block_tables"]
    assert [sorted(c) for c in unbind_call(bound)] == \
        [sorted(c) for c in cache]


def _entry_shapes(cache):
    return [{k: (v.shape, str(v.dtype)) for k, v in c.items()}
            for c in cache]


# What ``init_cache`` and ``bind_call`` gave the two families that had a
# special case each before they became one code path: key for key and shape
# for shape (the configurations' tiny stand-ins; 16 blocks of 8, 4 slots).
def _kv(blocks, heads, dim):
    return {"k": ((blocks, 8, heads, dim), "float32"),
            "v": ((blocks, 8, heads, dim), "float32")}


AS_BEFORE = {
    "nemotron_h_tiny": dict(
        cfg=lambda: MODEL_PRESETS["nemotron_h_tiny"],
        entries=lambda: [
            {"M": {"conv": ((4, 3, 128), "float32"),
                   "ssm": ((4, 8, 8, 16), "float32")},
             "E": {}, "*": _kv(16, 2, 16)}[k] for k in "MEM*EM"],
        groups=None,
        bound={"M": ["block_tables", "conv", "own_rows", "ssm",
                     "state_slots"],
               "E": ["block_tables"], "*": ["block_tables", "k", "v"]}),
    "window_and_full_layers": dict(
        cfg=lambda: dataclasses.replace(
            MODEL_PRESETS["llama_tiny"], layer_windows=(8, 0)),
        # window_group_blocks(8, 8, 4, 64) = 4 x 4 + 8 + 8 + 1
        entries=lambda: [_kv(33, 2, 16), _kv(16, 2, 16)],
        groups=[1, 0],
        bound={1: ["block_tables", "k", "table_base", "v"],
               0: ["block_tables", "k", "v"]}),
}


@pytest.mark.parametrize("name", sorted(AS_BEFORE))
def test_older_families_caches_are_what_they_were(name):
    case = AS_BEFORE[name]
    cfg = case["cfg"]()
    cache = init_cache(cfg, 16, 8, 4, jnp.float32, call_tokens=64)
    assert _entry_shapes(cache) == case["entries"]()
    tables = jnp.zeros((2, 3), jnp.int32)
    if case["groups"] is None:
        bound = bind_call(cache, tables, jnp.asarray([1, 4]), True)
        kinds = list(cfg.layer_pattern)
    else:
        grouped = ({"block_tables": tables},
                   {"block_tables": tables,
                    "table_base": jnp.zeros((2,), jnp.int32)})
        bound = bind_call(cache, grouped, groups=case["groups"])
        kinds = case["groups"]
        assert [cfg.kv_group_of_layer(i) for i in range(cfg.num_layers)] \
            == case["groups"]
    assert [sorted(e) for e in bound] == [case["bound"][k] for k in kinds]


# -- the engine: prefill, then decode through the cache -----------------------

def _engine(tiny, **over):
    kw = dict(max_seqs=4, block_size=8, num_blocks=64, max_model_len=128,
              cache_dtype="float32")
    kw.update(over)
    return InferenceEngine(tiny["cfg"], tiny["params"], EngineConfig(**kw))


def _hold_to_reference(tiny, prompts, results, atol=2e-4):
    """The engine's log-probs of its own greedy tokens against the
    reference's full forward over prompt + answer (no cache, no batch)."""
    for prompt, res in zip(prompts, results):
        tokens = res.output_token_ids
        lp = tiny["ref_logprobs"](tiny["params"], jnp.asarray(prompt + tokens))
        rows = np.asarray(lp[len(prompt) - 1:len(prompt) - 1 + len(tokens)])
        np.testing.assert_allclose(
            res.output_logprobs, rows[np.arange(len(tokens)), tokens],
            atol=atol)
        assert (rows.max(-1) - rows[np.arange(len(tokens)), tokens]
                <= atol).all()


SCENARIOS = {
    # one request, inside the window: no block is released
    "lone_inside_the_window": dict(lengths=[11], engine={}),
    # prompts past the window, alone: the window group releases behind it
    "lone_past_the_window": dict(lengths=[45], engine={}),
    # a full batch of unequal rows, and one request more than the slots
    "into_a_full_batch": dict(lengths=[45, 7, 21, 70, 33], engine={}),
    # prompts fed 16 tokens a step: state, window table and shared pool
    # cross the chunks
    "chunked_prefill": dict(
        lengths=[45, 23], engine=dict(max_prefill_tokens_per_step=16)),
    # the decode kernel (interpreted) over fused pools, both groups
    "decode_kernel": dict(lengths=[37, 9], engine={}, kernel=True),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_engine_prefill_then_decode_agrees_with_full_forward(tiny, name):
    case = SCENARIOS[name]
    if case.get("kernel"):
        tiny = {**tiny, "cfg": dataclasses.replace(
            tiny["cfg"], paged_attention_impl="kernel")}
    eng = _engine(tiny, **case["engine"])
    prompts = _prompts(case["lengths"], seed=len(name))
    results = eng.generate(prompts,
                           SamplingParams(max_tokens=9, temperature=0.0))
    _hold_to_reference(tiny, prompts, results)
    st = eng.stats
    assert st["recurrent_state_resets"] == len(prompts)
    assert st["recurrent_prefill_tokens"] == sum(case["lengths"])
    assert st["cross_decoder_prefill_tokens"] == sum(case["lengths"])
    assert st["cross_decoder_prefill_tokens_decode"] == 0
    assert (eng._state_slots == eng.cfg.max_seqs).all()  # all released
    if max(case["lengths"]) > 2 * WINDOW:
        assert eng.kv_freed["window", "window"] > 0
    assert eng.window_manager.num_free == eng.window_manager.num_blocks - 1 \
        or eng.window_manager.num_free == eng.window_manager.num_blocks


def test_a_prompt_goes_as_two_prefill_calls(tiny, monkeypatch):
    """A prompt longer than ``prefill_call_tokens`` goes as several calls:
    the recurrent state, the window group's table (released behind the
    window between the calls) and layer 7's pool, which the cross layers of
    the second call read with the first call's keys in it, are carried."""
    assert SambaYForCausalLM.prefill_call_tokens == 2048
    monkeypatch.setattr(SambaYForCausalLM, "prefill_call_tokens", 32)
    eng = _engine(tiny)
    prompts = _prompts([57, 20], seed=8)
    results = eng.generate(prompts,
                           SamplingParams(max_tokens=6, temperature=0.0))
    _hold_to_reference(tiny, prompts, results)
    assert eng.stats["prefill_batches"] == 3
    assert eng.stats["recurrent_state_resets"] == 2


def test_a_kinds_block_is_traced_once_a_program(tiny):
    """Twelve layers, five traces: Mamba-1, attention under the window,
    attention over every key, memory unit, cross-attention."""
    ids = jnp.asarray(_prompts([16])[0])[None]
    jaxpr = jax.make_jaxpr(lambda p: tiny["model"].apply(
        {"params": p}, ids)[0])(tiny["params"])
    blocks = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in (
        "pjit", "jit") and e.params["name"] == "<lambda>"]
    assert len(blocks) == 12
    assert len({id(e.params["jaxpr"]) for e in blocks}) == 5


# -- what cannot serve this family refuses ------------------------------------

REFUSED = {
    "prefix_caching": (dict(enable_prefix_caching=True), "prefix caching"),
    "prefix_tiers": (dict(enable_prefix_caching=True, prefix_host_blocks=8),
                     "prefix caching"),
    "speculative": (dict(speculative="ngram"), "speculative"),
    "int8_weights": (dict(quantization="int8"), "int8"),
    "int8_cache": (dict(cache_dtype="int8"), "int8 scale"),
    "adapter_pool": (dict(adapter_slots=2), "adapter"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_engine_refuses_at_start_up(tiny, name):
    over, said = REFUSED[name]
    with pytest.raises(ValueError, match=said):
        _engine(tiny, **over)


def test_hand_off_disaggregation_and_a_mesh_refuse(tiny):
    from jax.sharding import Mesh

    from dlti_tpu.serving.disagg import DisaggController

    with pytest.raises(ValueError, match="--disagg"):
        DisaggController(tiny["cfg"], tiny["params"], EngineConfig())
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("tensor",))
    with pytest.raises(ValueError, match="tensor-parallel"):
        InferenceEngine(tiny["cfg"], tiny["params"], EngineConfig(),
                        mesh=mesh)
    with pytest.raises(NotImplementedError, match="packed rows"):
        tiny["model"].apply({"params": tiny["params"]},
                            jnp.zeros((1, 8), jnp.int32),
                            segment_ids=jnp.ones((1, 8), jnp.int32))


def test_the_published_size_counts_its_parameters():
    """3,852,562,944: embedding 512,163,840; 32 MLPs of 78,643,200; 9
    Mamba-1 of 41,241,600; 9 attention layers with their own keys of
    19,668,864; 7 memory units of 26,214,400; 7 cross-attention layers of
    13,112,704; 65 LayerNorms of 5,120. The issue's count (3,852.6 M) to
    the last digit it gives; the published "3.8B" rounds it."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4_mini_flash.json")) as f:
        cfg = ModelConfig(**model_fields(json.load(f)))
    by_hand = (200064 * 2560 + 32 * 78_643_200 + 9 * 41_241_600
               + 9 * 19_668_864 + 7 * 26_214_400 + 7 * 13_112_704
               + 65 * 5_120)
    assert cfg.num_params() == by_hand == 3_852_562_944
    assert abs(cfg.num_params() / 1e6 - 3852.6) < 0.05
    assert cfg.num_active_params() == cfg.num_params()


def test_memory_plan_counts_the_new_layers_and_pools():
    """``scripts/memory_plan.py`` at the cell's arguments: 7.71 GB of
    weights, the shared pool, the eight window pools and the nine layers'
    recurrent state, as the engine makes them."""
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import memory_plan

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4_mini_flash.json")) as f:
        cfg = ModelConfig(**model_fields(json.load(f)))
    plan = memory_plan.plan_serving(cfg, num_blocks=4096, block_size=16,
                                    max_model_len=1536, max_seqs=32)
    assert plan["kv_bytes_per_token"] == 5120        # one pool a token
    assert plan["owners"] == {
        "params": 2 * 3_852_562_944,
        "kv_block_pool": 4096 * 16 * 5120,
        "window_block_pools": 8 * 1257 * 16 * 5120,
        "recurrent_state_pool": 32 * 9 * (5120 * 16 * 4 + 3 * 5120 * 2)}
    assert 8.9e9 < plan["total_bytes"] < 9.1e9       # 56 % of the chip
    # the families that were there: a pool a layer with keys of its own
    nemotron = MODEL_PRESETS["nemotron_h_tiny"]
    assert memory_plan.kv_bytes_per_token(nemotron, "float32") == \
        1 * 2 * nemotron.num_kv_heads * 16 * 4
    llama = MODEL_PRESETS["llama_tiny"]
    assert memory_plan.kv_bytes_per_token(llama) == \
        2 * llama.num_layers * llama.num_kv_heads * llama.resolved_head_dim * 2
