"""The nemotron_h family (Mamba-2, held routed experts, rope-less attention)
against its plain reference, at tiny sizes on the CPU with seeded weights.

Both sides take their sizes from the benchmark's configuration file laid over
with the cell's rehearsal stand-ins, as the harness does: the program through
``chip_child.model_fields`` -> ``ModelConfig``, the reference through its own
``sizes(config)``.
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

import dlti_tpu.ops.pallas.grouped_experts as grouped_experts  # noqa: E402
from dlti_tpu.config import MODEL_PRESETS, ModelConfig  # noqa: E402
from dlti_tpu.models import LlamaForCausalLM, build_model  # noqa: E402
from dlti_tpu.models.mamba2 import Mamba2Mixer  # noqa: E402
from dlti_tpu.models.moe import HeldExpertsMLP  # noqa: E402
from dlti_tpu.models.nemotron_h import NemotronHForCausalLM  # noqa: E402
from dlti_tpu.serving.engine import EngineConfig, InferenceEngine  # noqa: E402
from dlti_tpu.serving.sampling import SamplingParams  # noqa: E402

CELL = "serve.nemotron3_nano_30b.tool_turns"


def tiny_config(**model_over) -> dict:
    """The configuration file as a rehearsal runs it (tiny stand-ins)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3_nano_30b.json")) as f:
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
    assert isinstance(tiny["model"], NemotronHForCausalLM)
    assert isinstance(build_model(MODEL_PRESETS["llama_tiny"]),
                      LlamaForCausalLM)
    assert tiny["cfg"].layer_pattern == "MEM*E" and not tiny["cfg"].rope


def test_forward_agrees_with_the_reference(tiny):
    ids = jnp.asarray(_prompts([37])[0])
    logits, _ = tiny["model"].apply({"params": tiny["params"]}, ids[None])
    want = tiny["reference"].forward(tiny["params"], tiny["sizes"], ids)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_param_count_of_a_patterned_model_is_the_tree(tiny):
    leaves = jax.tree_util.tree_leaves(tiny["params"])
    assert tiny["cfg"].num_params() == sum(x.size for x in leaves)
    cfg = tiny["cfg"]
    fewer = cfg.num_params() - cfg.num_active_params()
    f, h = cfg.moe_intermediate_size, cfg.hidden_size
    # held experts less the top-k's expected share of them, per E layer
    per_layer = 2 * h * f * (cfg.moe_held - cfg.num_experts_per_tok
                             * cfg.moe_held / cfg.moe_num_experts)
    assert fewer == pytest.approx(
        cfg.layer_pattern.count("E") * per_layer, abs=4)


def test_pattern_must_name_every_layer():
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(MODEL_PRESETS["nemotron_h_tiny"], num_layers=5)
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(MODEL_PRESETS["nemotron_h_tiny"],
                            layer_pattern="MEMXEM")


# -- Mamba-2: the scan, the step, padding and chunks -------------------------

@pytest.fixture(scope="module")
def mamba():
    cfg = MODEL_PRESETS["nemotron_h_tiny"]
    layer = Mamba2Mixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 21, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(21), (2, 21))
    params = layer.init(jax.random.PRNGKey(4), x, pos)["params"]
    return cfg, layer, params, x, pos


def _state(cfg, slots=4):
    from dlti_tpu.ops.kv_cache import init_recurrent_state

    return init_recurrent_state(
        slots, cfg.mamba_conv_kernel, cfg.mamba_conv_dim,
        (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_state_size),
        jnp.float32, jnp.float32)


def test_mamba_scan_equals_one_token_after_another(mamba):
    cfg, layer, params, x, pos = mamba
    want, _ = layer.apply({"params": params}, x, pos)
    cache = {**_state(cfg, 2), "state_slots": jnp.arange(2),
             "own_rows": True}
    got = []
    for t in range(x.shape[1]):
        y, new = layer.apply({"params": params}, x[:, t:t + 1],
                             pos[:, t:t + 1], cache)
        cache = {**cache, **new}
        got.append(y)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               np.asarray(want), atol=2e-5)


def test_mamba_padding_advances_nothing_and_chunks_continue(mamba):
    cfg, layer, params, x, pos = mamba
    n = 13  # real tokens of row 0; row 1 is all padding
    padded_pos = jnp.stack([jnp.where(jnp.arange(21) < n, jnp.arange(21), -1),
                            jnp.full((21,), -1)])
    cache = {**_state(cfg), "state_slots": jnp.asarray([2, 4]),
             "own_rows": False}
    y, whole = layer.apply({"params": params}, x, padded_pos, cache)
    exact = {**_state(cfg), "state_slots": jnp.asarray([2]),
             "own_rows": False}
    y_exact, want = layer.apply({"params": params}, x[:1, :n], pos[:1, :n],
                                exact)
    np.testing.assert_allclose(np.asarray(y[0, :n]), np.asarray(y_exact[0]),
                               atol=2e-5)
    for key in ("conv", "ssm"):
        np.testing.assert_allclose(np.asarray(whole[key][2]),
                                   np.asarray(want[key][2]), atol=2e-5)
        # the padding row (slot out of range) and the other slots: untouched
        assert not np.asarray(whole[key])[[0, 1, 3]].any()
    # the same prompt in two chunks, the second padded, into the same slot
    first = {**_state(cfg), "state_slots": jnp.asarray([2]),
             "own_rows": False}
    _, mid = layer.apply({"params": params}, x[:1, :8], pos[:1, :8], first)
    pos2 = jnp.where(jnp.arange(8) < n - 8, jnp.arange(8, 16), -1)[None]
    y2, end = layer.apply({"params": params}, x[:1, 8:16], pos2,
                          {**mid, "state_slots": jnp.asarray([2]),
                           "own_rows": False})
    np.testing.assert_allclose(np.asarray(y2[0, :n - 8]),
                               np.asarray(y_exact[0, 8:]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(end["ssm"][2]),
                               np.asarray(want["ssm"][2]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(end["conv"][2]),
                               np.asarray(want["conv"][2]), atol=2e-5)


def test_idle_decode_row_keeps_its_state(mamba):
    cfg, layer, params, x, pos = mamba
    state = jax.tree_util.tree_map(lambda v: v + 1.0, _state(cfg, 2))
    cache = {**state, "state_slots": jnp.asarray([0, 2]), "own_rows": True}
    _, new = layer.apply({"params": params}, x[:, :1], pos[:, 5:6], cache)
    assert np.asarray(new["ssm"][1] == state["ssm"][1]).all()
    assert np.asarray(new["conv"][1] == state["conv"][1]).all()
    assert np.asarray(new["ssm"][0] != state["ssm"][0]).any()


# -- the expert layer: shares and droplessness --------------------------------

@pytest.fixture(params=["masked", "grouped"])
def routing(request, monkeypatch):
    """HeldExpertsMLP computes a call's routed sum one of two ways by the
    call's token count: every held expert over every token under a mask, or
    the held assignments laid out by expert in tiles of rows (here of 8, so
    that an expert has whole, part-filled and no tiles) through the kernel,
    interpreted."""
    import dlti_tpu.models.moe as moe

    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS",
                        1 << 30 if request.param == "masked" else 1)
    monkeypatch.setattr(moe, "GROUPED_TILE_ROWS", 8)
    monkeypatch.setattr(grouped_experts, "WIDTH_CHUNK", 8)
    return request.param


def _expert_layer(cfg, x, seed=5):
    layer = HeldExpertsMLP(cfg)
    return layer, layer.init(jax.random.PRNGKey(seed), x)["params"]


def test_two_shares_and_the_shared_expert_once_sum_to_the_whole(tiny, routing):
    """What every chip of a layer computes, with what they all compute alike
    (the shared expert) counted once, adds up to the uncut reference."""
    config = tiny_config(n_routed_experts=8)
    config["published"]["n_routed_experts"] = 8
    whole = dataclasses.replace(
        tiny["cfg"], moe_num_experts=8, moe_held_start=0, moe_held_count=8)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 11, whole.hidden_size))
    layer, params = _expert_layer(whole, x)
    reference = tiny["reference"]
    sizes = reference.sizes(config)
    assert (sizes["experts"], sizes["held"], sizes["held_start"]) == (8, 8, 0)
    want = reference.experts(params, sizes, x.reshape(33, -1))
    shared = reference._mm(reference._relu2(reference._mm(
        x.reshape(33, -1), params["shared_up"]["kernel"])),
        params["shared_down"]["kernel"])
    total = -shared  # two shares hold the shared expert twice
    counted = 0
    for lo in (0, 4):
        half = dataclasses.replace(whole, moe_held_start=lo, moe_held_count=4)
        mine = {**params, "w_up": params["w_up"][lo:lo + 4],
                "w_down": params["w_down"][lo:lo + 4]}
        y, counters = HeldExpertsMLP(half).apply({"params": mine}, x)
        total = total + y.reshape(33, -1)
        counted += int(counters[1])
        # the reference given the same share agrees with the layer
        ref_half = reference.experts(
            mine, {**sizes, "held": 4, "held_start": lo}, x.reshape(33, -1))
        np.testing.assert_allclose(np.asarray(y.reshape(33, -1)),
                                   np.asarray(ref_half), atol=3e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=5e-5)
    assert counted == 33 * whole.num_experts_per_tok  # every assignment once


def test_no_token_is_dropped_under_a_skewed_router(tiny, routing):
    cfg = dataclasses.replace(tiny["cfg"], moe_num_experts=8,
                              moe_held_start=0, moe_held_count=8)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, cfg.hidden_size))
    layer, params = _expert_layer(cfg, x)
    # every token's first choice is expert 3: 80 tokens on one expert
    params = {**params, "e_score_correction_bias":
              jnp.zeros((8,)).at[3].set(10.0)}
    y, counters = layer.apply({"params": params}, x)
    assignments, held, touched, load_max, grouped_rows, tile_rows = (
        int(c) for c in counters)
    # 80 rows on expert 3 are ten whole tiles of 8; the other 160 spread
    assert (grouped_rows, tile_rows % 8) == (
        (held, 0) if routing == "grouped" else (0, 0))
    assert tile_rows == 0 or held <= tile_rows <= held + 7 * 7
    assert assignments == held == 80 * cfg.num_experts_per_tok
    assert load_max == 80  # capacity 1.25 x 80 x 3 / 8 = 37 would drop 43
    config = tiny_config(n_routed_experts=8)
    config["published"]["n_routed_experts"] = 8
    want = tiny["reference"].experts(
        params, tiny["reference"].sizes(config), x.reshape(80, -1))
    np.testing.assert_allclose(np.asarray(y.reshape(80, -1)),
                               np.asarray(want), atol=5e-5)
    # padding is not routed
    mask = jnp.ones((2, 40), bool).at[1, 10:].set(False)
    _, masked = layer.apply({"params": params}, x, mask)
    assert int(masked[0]) == 50 * cfg.num_experts_per_tok
    assert int(masked[3]) == 50


# -- through the engine: prefill then decode against the full forward --------

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
        lp = tiny["ref_logprobs"](jnp.asarray(prompt + tokens))
        rows = np.asarray(lp[len(prompt) - 1:len(prompt) - 1 + len(tokens)])
        np.testing.assert_allclose(
            res.output_logprobs, rows[np.arange(len(tokens)), tokens],
            atol=atol)
        # greedy: the reference's best token, up to a tie within atol
        assert (rows.max(-1) - rows[np.arange(len(tokens)), tokens]
                <= atol).all()


SCENARIOS = {
    # one request whose length is its bucket exactly: no padding at all
    "lone": dict(lengths=[16], engine={}),
    # a prompt well inside its bucket: 11 padded positions after 21 real
    "padded_bucket": dict(lengths=[21], engine={}),
    # one program call over rows of unequal lengths (and a padding row)
    "unequal_batch": dict(lengths=[33, 5, 19], engine={}),
    # prompts fed 16 tokens a step: the state crosses chunk boundaries
    "chunked_prefill": dict(
        lengths=[45, 23], engine=dict(max_prefill_tokens_per_step=16)),
}


def test_engine_agrees_with_full_forward_under_sorted_routing(tiny, routing):
    eng = _engine(tiny)
    prompts = _prompts([27, 14], seed=2)
    results = eng.generate(prompts,
                           SamplingParams(max_tokens=6, temperature=0.0))
    _hold_to_reference(tiny, prompts, results)


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_engine_prefill_then_decode_agrees_with_full_forward(tiny, name):
    case = SCENARIOS[name]
    eng = _engine(tiny, **case["engine"])
    prompts = _prompts(case["lengths"], seed=len(name))
    results = eng.generate(prompts,
                           SamplingParams(max_tokens=9, temperature=0.0))
    _hold_to_reference(tiny, prompts, results)
    st = eng.stats
    assert st["recurrent_state_resets"] == len(prompts)
    assert st["recurrent_prefill_tokens"] == sum(case["lengths"])
    experts = tiny["cfg"].layer_pattern.count("E")
    assert st["moe_assignments"] == experts * tiny["cfg"].num_experts_per_tok \
        * (sum(case["lengths"]) + 8 * len(prompts))
    assert 0 < st["moe_held_assignments_decode"] < st["moe_held_assignments"] \
        <= st["moe_assignments"]
    assert st["moe_expert_load_max_decode"] >= st["decode_steps"] > 0
    assert (eng._state_slots == eng.cfg.max_seqs).all()  # all released


# "chunked": the victim may be dropped with its state half built, and the
# re-admission feeds prompt + answer so far 16 tokens a step.
@pytest.mark.parametrize("over", [{}, dict(max_prefill_tokens_per_step=16)],
                         ids=["throughput", "chunked"])
def test_preemption_drops_the_state_and_readmission_rebuilds_it(tiny, over):
    prompts = _prompts([30, 28, 26], seed=9)
    sp = SamplingParams(max_tokens=30, temperature=0.0)
    roomy = _engine(tiny, max_model_len=64).generate(prompts, sp)
    # 11 allocatable blocks of 8 for three sequences that grow to 8 each
    tight = _engine(tiny, max_model_len=64, num_blocks=12, **over)
    squeezed = tight.generate(prompts, sp)
    assert tight.stats["preemptions"] > 0
    assert tight.stats["recurrent_state_resets"] > len(prompts)
    assert [r.output_token_ids for r in squeezed] == \
        [r.output_token_ids for r in roomy]
    _hold_to_reference(tiny, prompts, squeezed)


def test_a_slot_is_reused_from_a_zero_state(tiny):
    eng = _engine(tiny, max_seqs=1)
    prompts = _prompts([20, 9, 31], seed=4)
    results = [eng.generate([p], SamplingParams(max_tokens=5,
                                                temperature=0.0))[0]
               for p in prompts]
    _hold_to_reference(tiny, prompts, results)


def test_a_round_launched_ahead_leaves_the_recurrent_state_as_it_should_be(
        tiny, fetch_first_engine):
    """The loop a round ahead of the host, over per-slot recurrent state: a
    request that ends on a stop token nobody foresaw has a row in the round
    behind, which advances the dead slot's state once more; the next request
    in that slot starts from zero all the same, and every stream equals, to
    the bit, that of the engine fetching every round first, and the
    reference's."""
    prompts = _prompts([20, 9, 31, 14, 26], seed=6)
    cfg = EngineConfig(max_seqs=2, block_size=8, num_blocks=64,
                       max_model_len=128, cache_dtype="float32")

    def run(cls, stop=()):
        eng = cls(tiny["cfg"], tiny["params"], cfg)
        results = eng.generate(prompts, SamplingParams(
            max_tokens=7, temperature=0.0, stop_token_ids=tuple(stop)))
        return eng, results

    _, plain = run(fetch_first_engine)
    stop = [plain[0].output_token_ids[3], plain[2].output_token_ids[4]]
    _, want = run(fetch_first_engine, stop)
    eng, got = run(InferenceEngine, stop)
    assert [(r.output_token_ids, r.output_logprobs, r.finish_reason)
            for r in got] == \
        [(r.output_token_ids, r.output_logprobs, r.finish_reason)
         for r in want]
    assert {r.finish_reason for r in got} == {"stop", "length"}
    assert eng.stats["decode_rows_discarded"] >= 2
    assert eng.stats["recurrent_state_resets"] == len(prompts)
    _hold_to_reference(tiny, prompts, got)


def test_memory_ledger_names_the_recurrent_pool(tiny):
    eng = _engine(tiny)
    owners = eng.memledger.snapshot()["owners"]
    cfg = tiny["cfg"]
    per_slot = 4 * (cfg.mamba_num_heads * cfg.mamba_head_dim
                    * cfg.mamba_state_size
                    + (cfg.mamba_conv_kernel - 1) * cfg.mamba_conv_dim)
    want = cfg.layer_pattern.count("M") * 4 * per_slot
    assert owners["recurrent_state_pool"]["bytes"] == want
    assert eng.recurrent_state_pool_bytes == want
    assert owners["kv_block_pool"]["bytes"] == \
        2 * 64 * 8 * cfg.num_kv_heads * cfg.resolved_head_dim * 4


# -- what cannot serve a recurrent layer refuses ------------------------------

REFUSED = {
    "prefix_caching": (dict(enable_prefix_caching=True), "prefix caching"),
    "prefix_tiers": (dict(enable_prefix_caching=True, prefix_host_blocks=8),
                     "prefix caching"),
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

    with pytest.raises(ValueError, match="recurrent state"):
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


def test_llama_programs_take_no_new_argument():
    """A configuration without a pattern: the same programs as before (no
    slot argument, no counters riding the tokens)."""
    cfg = MODEL_PRESETS["llama_tiny"]
    params = build_model(cfg).init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_seqs=2, block_size=8, num_blocks=32, max_model_len=64,
        cache_dtype="float32"))
    assert eng.executor.counter_names == () and not eng.executor._recurrent
    assert eng._prefill_rows(2048) == 8  # no model limit: as before
    assert eng.executor.round_packing.extra_field is None
    assert list(eng.executor.round_packing.columns)[-1] == "top_p"
    res = eng.generate(_prompts([9]), SamplingParams(max_tokens=4,
                                                     temperature=0.0))
    assert len(res[0].output_token_ids) == 4
    assert all("ssm" not in layer for layer in eng.executor.cache)
    assert "moe_assignments" not in eng.stats
    assert eng.recurrent_state_pool_bytes == 0


def test_a_wide_prefill_call_runs_the_experts_grouped(tiny, monkeypatch):
    """Four admissions of one bucket are one call of 4 x 32 padded tokens:
    at the boundary and over it, so the expert layers lay its held
    assignments out by expert (tiles of 16 rows) and the decode rounds, of 4
    tokens, stay under the mask."""
    import dlti_tpu.models.moe as moe

    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", 128)
    monkeypatch.setattr(moe, "GROUPED_TILE_ROWS", 16)
    monkeypatch.setattr(grouped_experts, "WIDTH_CHUNK", 8)
    prompts = _prompts([30, 29, 31, 28], seed=6)
    eng = _engine(tiny)
    got = eng.generate(prompts, SamplingParams(max_tokens=4, temperature=0.0))
    assert eng.stats["prefill_batches"] == 1
    # every held assignment of the call's real tokens went grouped, and no
    # decode round's did
    assert eng.stats["moe_grouped_rows"] == (
        eng.stats["moe_held_assignments"]
        - eng.stats["moe_held_assignments_decode"]) > 0
    assert eng.stats["moe_grouped_rows_decode"] == 0
    assert eng.stats["moe_grouped_tile_rows"] % 16 == 0
    assert eng.stats["moe_grouped_tile_rows"] >= eng.stats["moe_grouped_rows"]
    _hold_to_reference(tiny, prompts, got)


def test_seeded_down_projections_are_centred(tiny):
    """relu² is positive: an uncentred down projection would put the same
    vector on every token (models.moe.centred_out_init)."""
    import dlti_tpu.models.moe as moe

    w = moe.centred_out_init(0.5, batch_axis=(0,))(
        jax.random.PRNGKey(0), (3, 400, 16), jnp.float32)
    np.testing.assert_allclose(np.asarray(w.sum(axis=1)), 0, atol=1e-5)
    assert 0.4 < float(w.std()) * 400 ** 0.5 < 0.6
    for i, kind in enumerate(tiny["cfg"].layer_pattern):
        if kind == "E":
            mixer = tiny["params"][f"layers_{i}"]["mixer"]
            assert abs(np.asarray(mixer["w_down"]).sum(axis=1)).max() < 1e-4
            assert abs(np.asarray(
                mixer["shared_down"]["kernel"]).sum(axis=0)).max() < 1e-4


def test_cache_entries_for_one_call_and_back(tiny):
    from dlti_tpu.ops.kv_cache import bind_call, init_cache, unbind_call

    cache = init_cache(tiny["cfg"], 8, 8, 4, jnp.float32)
    tables, slots = jnp.zeros((2, 3), jnp.int32), jnp.asarray([1, 4])
    bound = bind_call(cache, tables, slots, own_rows=False)
    for kind, entry in zip(tiny["cfg"].layer_pattern, bound):
        assert entry["block_tables"] is tables
        assert ("state_slots" in entry) == (kind == "M")
    back = unbind_call(bound)
    assert [sorted(c) for c in back] == [sorted(c) for c in cache]


def test_a_prefill_call_is_held_to_the_models_padded_tokens(tiny, monkeypatch):
    """The family holds one prefill call to ``prefill_call_tokens`` padded
    tokens (models.nemotron_h.PREFILL_CALL_TOKENS: the 13-layer program at
    2 x 2,048 never returns on the v5e); the engine knows no flag for it."""
    from dlti_tpu.models.nemotron_h import (
        PREFILL_CALL_TOKENS, NemotronHForCausalLM)

    eng = _engine(tiny)
    assert [eng._prefill_rows(b) for b in (128, 256, 512, 1024, 2048, 4096)] \
        == [8, 8, 4, 2, 1, 1]
    assert PREFILL_CALL_TOKENS == 2048
    assert not hasattr(EngineConfig(), "max_prefill_batch_tokens")
    # four admissions of one bucket go out two rows a call, same results
    prompts = _prompts([30, 29, 31, 28], seed=6)
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    want = eng.generate(prompts, sp)
    monkeypatch.setattr(NemotronHForCausalLM, "prefill_call_tokens", 64)
    bounded = _engine(tiny)
    got = bounded.generate(prompts, sp)
    assert (eng.stats["prefill_batches"],
            bounded.stats["prefill_batches"]) == (1, 2)
    assert [r.output_token_ids for r in got] == \
        [r.output_token_ids for r in want]
    _hold_to_reference(tiny, prompts, got)


# -- experts held wider than published ---------------------------------------

PUBLISHED, HELD = 232, 256  # a pad of 24 columns: a tenth of the width


@pytest.fixture(scope="module")
def padded(tiny):
    """The tiny model with experts published 232 wide, which the rule holds
    at 256 at the constants as they stand; the reference is given the same
    weights cut to the published width (it takes shapes from the arrays)."""
    assert grouped_experts.held_width(PUBLISHED) == HELD
    cfg = dataclasses.replace(tiny["cfg"], moe_intermediate_size=PUBLISHED)
    params = build_model(cfg).init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 8), jnp.int32))["params"]
    cut = dict(params)
    for i, kind in enumerate(cfg.layer_pattern):
        if kind == "E":
            mixer = params[f"layers_{i}"]["mixer"]
            cut[f"layers_{i}"] = {**params[f"layers_{i}"], "mixer": {
                **mixer, "w_up": mixer["w_up"][:, :, :PUBLISHED],
                "w_down": mixer["w_down"][:, :PUBLISHED]}}
    ref_logprobs = jax.jit(lambda ids: jax.nn.log_softmax(
        tiny["reference"].forward(cut, tiny["sizes"], ids), -1))
    return {**tiny, "cfg": cfg, "params": params,
            "ref_logprobs": ref_logprobs}


def test_a_padded_models_prefill_of_512_tokens_holds_the_kernel(padded):
    """The path is read from the held width: a prefill call of 4 x 128
    tokens lowers with the grouped kernel in every expert layer, one of
    2 x 128 without it, at the constants as they stand."""
    mixer = padded["params"]["layers_1"]["mixer"]
    assert mixer["w_up"].shape[-1] == mixer["w_down"].shape[1] == HELD
    assert padded["cfg"].num_params() == sum(
        x.size for x in jax.tree_util.tree_leaves(padded["params"])) \
        - padded["cfg"].held_pad_params
    ex = _engine(padded, max_seqs=8, max_model_len=256).executor

    def text(rows):
        ids = jnp.zeros((rows, 128), jnp.int32)
        row = jnp.zeros((rows,), jnp.int32)
        return ex._prefill_fn(128).lower(
            ex.params, ex.cache, ids, ids, jnp.zeros((rows, 16), jnp.int32),
            row, row).as_text(debug_info=True)  # the scopes' names

    assert text(4).count("dlti_grouped_experts") >= \
        padded["cfg"].layer_pattern.count("E")
    assert "dlti_grouped_experts" not in text(2)


def test_a_padded_engine_agrees_with_the_published_widths_forward(
        padded, monkeypatch):
    """Prefill (one call of 4 x 32 tokens, grouped at the boundary set here,
    the kernel at its own chunk) then decode (masked) over the held 256
    columns, against the float32 reference over the 232 published ones."""
    import dlti_tpu.models.moe as moe

    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", 128)
    monkeypatch.setattr(moe, "GROUPED_TILE_ROWS", 16)
    prompts = _prompts([30, 29, 31, 28], seed=6)
    eng = _engine(padded)
    got = eng.generate(prompts, SamplingParams(max_tokens=6, temperature=0.0))
    assert eng.stats["moe_grouped_rows"] == (
        eng.stats["moe_held_assignments"]
        - eng.stats["moe_held_assignments_decode"]) > 0
    assert eng.stats["moe_grouped_rows_decode"] == 0
    _hold_to_reference(padded, prompts, got)


def test_a_kinds_block_is_traced_once_a_program(tiny):
    """Outside ``init`` the layers of one kind are calls of ONE jitted
    function of a layer's weights and cache entry (the pattern here is
    MEM*E: two Mamba-2 layers, two expert layers, one attention layer):
    five calls, three traces, and the same logits as ever."""
    ids = jnp.asarray(_prompts([16])[0])[None]
    jaxpr = jax.make_jaxpr(lambda p: tiny["model"].apply(
        {"params": p}, ids)[0])(tiny["params"])
    blocks = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in (
        "pjit", "jit") and e.params["name"] == "<lambda>"]
    assert len(blocks) == len(tiny["cfg"].layer_pattern) == 5
    assert len({id(e.params["jaxpr"]) for e in blocks}) == 3
    want = tiny["reference"].forward(tiny["params"], tiny["sizes"], ids[0])
    got, _ = jax.jit(lambda p: tiny["model"].apply({"params": p}, ids))(
        tiny["params"])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-5, rtol=1e-4)
