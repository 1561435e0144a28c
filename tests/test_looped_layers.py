"""A Llama-family model whose layers run several times over one set of
weights (``ut_steps``), four norms a block (``sandwich_norm``), the final
norm inside the loop, a cache entry a (pass, layer) and the exit gate: the
program against the plain reference (``benchmark/references/ouro_2_6b.py``,
which imports nothing of the program) in a full forward and through every
cache path, what the tree and the cache hold, what is counted, and what is
refused. Float32 at tiny sizes, seeded weights."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import LoRAConfig, ModelConfig
from dlti_tpu.models import build_model
from dlti_tpu.models.llama import LOOP_COUNTERS, LlamaForCausalLM
from dlti_tpu.ops.kv_cache import init_cache
from dlti_tpu.serving.engine import EngineConfig, InferenceEngine
from dlti_tpu.serving.sampling import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = 3
TINY = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=LAYERS,
    num_heads=4, num_kv_heads=4, head_dim=16, max_seq_len=512,
    rope_theta=1e6, rms_norm_eps=1e-6, remat=False, dtype="float32",
    param_dtype="float32", sandwich_norm=True, ut_steps=4)


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "ouro_reference",
        os.path.join(ROOT, "benchmark", "references", "ouro_2_6b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()


def file_of(cfg: ModelConfig) -> dict:
    """The configuration file's ``model`` object that says what ``cfg``
    says, in the published keys: what the reference reads its sizes from."""
    return {"model": {
        "num_hidden_layers": cfg.num_layers, "total_ut_steps": cfg.ut_steps,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "hidden_size": cfg.hidden_size, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_theta": cfg.rope_theta, "early_exit_threshold": 1,
        "layer_types": ["full_attention"] * cfg.num_layers,
        "use_sliding_window": False, "tie_word_embeddings": False}}


def init(cfg, seed=0):
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed),
                             jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module")
def tiny():
    model, params = init(TINY)
    return {"model": model, "params": params,
            "sizes": REF.sizes(file_of(TINY))}


def prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(3, 512, n)] for n in lengths]


def against_reference(tiny, prompt, result, sizes=None):
    """(largest |log-prob difference|, largest gap to the reference's best)
    of an engine's greedy answer against the reference's full forward over
    prompt and answer: every position the engine sampled at."""
    ids = jnp.asarray(prompt + result.output_token_ids)
    lp = jax.nn.log_softmax(
        REF.forward(tiny["params"], sizes or tiny["sizes"], ids), -1)
    n, k = len(prompt), len(result.output_token_ids)
    rows = lp[n - 1:n - 1 + k]
    theirs = np.asarray(rows[np.arange(k),
                             np.asarray(result.output_token_ids)])
    return (float(np.abs(theirs - np.asarray(result.output_logprobs)).max()),
            float((np.asarray(rows.max(-1)) - theirs).max()))


# Float32 on both sides, the program's products at the backend's default
# precision and the reference's at "highest": 12 block applications and a
# head apart the logits (of size ~0.7) differ by ~1e-6; through the paged
# cache the order of the sums differs too (keys in blocks). Log-probs are
# held to 2e-4, as the other families' engine tests are, a hundred times
# what was read and a twentieth or less of what a wrong program below reads.
FORWARD_ATOL = 2e-5
ENGINE_ATOL = 2e-4


# -- the full forward ----------------------------------------------------------

@pytest.mark.parametrize("ut_steps", [1, 2, 4])
def test_the_full_forward_agrees_with_the_reference(ut_steps):
    cfg = dataclasses.replace(TINY, ut_steps=ut_steps)
    model, params = init(cfg, seed=ut_steps)
    ids = jnp.asarray(prompts([53], seed=ut_steps)[0])
    ours = model.apply({"params": params}, ids[None])[0][0]
    theirs = REF.forward(params, REF.sizes(file_of(cfg)), ids)
    assert float(jnp.abs(ours - theirs).max()) < FORWARD_ATOL
    assert float(jnp.abs(theirs).max()) > 0.3      # not all zeros


@pytest.mark.parametrize("ut_steps", [1, 4])
def test_the_tree_holds_each_layer_once_and_num_params_is_its_size(ut_steps):
    cfg = dataclasses.replace(TINY, ut_steps=ut_steps)
    model, params = init(cfg)
    assert isinstance(model, LlamaForCausalLM)
    # a looped stack keeps blocks, final norm and gate under ``loop``, the
    # body it scans over the passes: each layer once whatever the passes
    assert sorted(params["model"]) == (
        ["embed_tokens", "loop"] if ut_steps > 1 else
        ["embed_tokens", "final_norm", "layers_0", "layers_1", "layers_2"])
    body = params["model"].get("loop", params["model"])
    assert sorted(k for k in body if k.startswith("layers_")) == [
        f"layers_{i}" for i in range(LAYERS)]
    assert sorted(k for k in body["layers_0"] if k.endswith("norm")) == [
        "attn_out_norm", "input_norm", "mlp_out_norm", "post_attn_norm"]
    assert ("exit_gate_kernel" in body) == (ut_steps > 1)
    if ut_steps > 1:
        assert body["exit_gate_kernel"].shape == (64, 1)
        assert body["exit_gate_bias"].shape == (1,)
    size = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert cfg.num_params() == cfg.num_active_params() == size
    # by hand: a layer 4 x 64 x 64 + 3 x 64 x 96 + 4 x 64 = 35,072
    assert size == 3 * 35072 + 2 * 512 * 64 + 64 + (65 if ut_steps > 1 else 0)
    assert model.counter_names == (LOOP_COUNTERS if ut_steps > 1 else ())


def test_the_norms_and_the_gate_are_seeded_away_from_their_neutral_values(tiny):
    from dlti_tpu.models.llama import SANDWICH_OUT_NORM_MEAN as out_mean

    layer = tiny["params"]["model"]["loop"]["layers_1"]
    for name, mean in (("input_norm", 1.0), ("attn_out_norm", out_mean),
                       ("post_attn_norm", 1.0), ("mlp_out_norm", out_mean)):
        scale = layer[name]["scale"]
        assert abs(float(jnp.mean(scale)) - mean) < 0.15 * mean
        assert 0.1 * mean < float(jnp.std(scale)) < 0.4 * mean
    # the stream carries the token: an embedding of unit scale
    assert 0.9 < float(jnp.std(
        tiny["params"]["model"]["embed_tokens"])) < 1.1
    assert not np.allclose(layer["input_norm"]["scale"],
                           layer["attn_out_norm"]["scale"])
    assert abs(float(
        tiny["params"]["model"]["loop"]["exit_gate_bias"][0])) > 1e-3


def without_second_norms(params):
    return {**params, "model": {**params["model"], "loop": {
        name: ({k: v for k, v in layer.items()
                if k not in ("attn_out_norm", "mlp_out_norm")}
               if name.startswith("layers_") else layer)
        for name, layer in params["model"]["loop"].items()}}}


@pytest.mark.parametrize("wrong", ["three_passes", "no_second_norm",
                                   "two_norms_after"])
def test_each_size_and_convention_is_a_value_that_changes_the_logits(
        tiny, wrong):
    cfg, params = {
        "three_passes": (dataclasses.replace(TINY, ut_steps=3),
                         tiny["params"]),
        "no_second_norm": (dataclasses.replace(TINY, sandwich_norm=False),
                           without_second_norms(tiny["params"])),
        "two_norms_after": (dataclasses.replace(
            TINY, sandwich_norm=False, post_sublayer_norm=True),
            without_second_norms(tiny["params"]))}[wrong]
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(init(cfg)[1])
    ids = jnp.asarray(prompts([40])[0])[None]
    ours = tiny["model"].apply({"params": tiny["params"]}, ids)[0]
    theirs = build_model(cfg).apply({"params": params}, ids)[0]
    # (sublayers are seeded as increments of 0.02 on the stream, three
    # layers deep here: a pass less moves a logit by 0.03, a thousand times
    # FORWARD_ATOL)
    assert float(jnp.abs(ours - theirs).max()) > 0.02


def test_the_two_placements_of_norms_cannot_both_be_stated():
    with pytest.raises(ValueError, match="state one of the two"):
        dataclasses.replace(TINY, post_sublayer_norm=True)
    with pytest.raises(ValueError, match="at least one pass"):
        dataclasses.replace(TINY, ut_steps=0)
    with pytest.raises(ValueError, match="every pass"):
        dataclasses.replace(TINY, early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="dense Llama family"):
        dataclasses.replace(TINY, layer_pattern="M*E", sandwich_norm=False)
    with pytest.raises(ValueError, match="dense Llama family"):
        dataclasses.replace(TINY, layer_windows=(8, 0, 8))
    with pytest.raises(NotImplementedError, match="paged cache alone"):
        model, params = init(TINY)
        model.apply({"params": params}, jnp.zeros((1, 4), jnp.int32),
                    cache=model.init_cache(1, 16))


# -- the exit gate and the counters --------------------------------------------

def test_the_exit_distribution_sums_to_one_and_is_the_references(tiny):
    ids = jnp.asarray(prompts([29], seed=4)[0])
    (_, _, counted), sown = tiny["model"].apply(
        {"params": tiny["params"]}, ids[None], return_counters=True,
        mutable=["intermediates"])
    from dlti_tpu.models.llama import exit_distribution, expected_exit_pass

    rates = sown["intermediates"]["model"]["exit_rates"][0][0]     # (s, 4)
    ours = exit_distribution(rates)
    theirs = REF.exit_distribution(tiny["params"], tiny["sizes"], ids)
    assert ours.shape == theirs.shape == (29, 4)
    np.testing.assert_allclose(np.asarray(theirs.sum(-1)), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               atol=1e-5)
    # not 0.5 everywhere, and not one pass for every token
    assert float(jnp.abs(rates - 0.5).max()) > 0.1
    assert float(jnp.std(expected_exit_pass(theirs))) > 0.01
    assert int(counted["loop_passes"]) == 4
    assert abs(int(counted["loop_exit_pass_e3"])
               - 1000 * float(expected_exit_pass(theirs).sum())) < 29


def test_the_engine_counts_passes_and_the_expected_exit_pass(tiny):
    eng = InferenceEngine(TINY, tiny["params"], EngineConfig(
        max_seqs=4, block_size=4, num_blocks=128, max_model_len=128,
        cache_dtype="float32", eos_token_id=-1))
    asked = prompts([9, 30, 17], seed=5)
    out = eng.generate(asked, SamplingParams(temperature=0.0, max_tokens=6))
    st = eng.stats
    assert st["loop_passes_decode"] == 4 * st["decode_steps"] > 0
    assert st["loop_passes_prefill"] == 4 * st["prefill_batches"] > 0
    assert st["loop_passes"] == st["loop_passes_decode"] \
        + st["loop_passes_prefill"]
    # decode rows of the three live slots alone are counted (the fourth
    # slot is free): the reference's expected exit pass at the positions
    # the decode steps ran at (each answer's tokens but the last, whose
    # successor was never computed)
    want = 0.0
    for prompt, res in zip(asked, out):
        ids = jnp.asarray(prompt + res.output_token_ids)
        p = REF.exit_distribution(tiny["params"], tiny["sizes"], ids)
        n = len(prompt)
        want += float((p * jnp.arange(1, 5)).sum(-1)[n:n + 5].sum())
    assert st["decode_slot_steps"] == 15
    assert abs(st["loop_exit_pass_e3_decode"] - 1000 * want) < 15
    assert 1.0 < st["loop_exit_pass_e3_decode"] / 15 / 1000 < 4.0
    names = {m.name for m in eng.kv_metrics()}
    assert {"dlti_kv_cache_entries",
            "dlti_kv_bytes_per_context_token"} <= names
    assert eng.executor.kv_bytes_per_context_token == 12 * 2 * 4 * 16 * 4
    assert eng.executor.pool_bytes == 128 * 4 * 12 * 2 * 4 * 16 * 4


# -- the cache -------------------------------------------------------------------

def test_the_cache_has_an_entry_a_pass_of_each_layer(tiny):
    """``ut_steps x num_layers`` entries: each layer's pool holds a run of
    ``num_blocks`` blocks a pass, pass u's block b at ``u x num_blocks +
    b``, under one block table."""
    from dlti_tpu.models.llama import entry_of_pass

    assert TINY.cache_entries == 12
    cache = init_cache(TINY, 8, 4, 2, jnp.float32)
    assert len(cache) == 3
    assert all(c["k"].shape == (4 * 8, 4, 4, 16) for c in cache)
    once = dataclasses.replace(TINY, ut_steps=1)
    assert once.cache_entries == 3 == len(init_cache(once, 8, 4, 2))
    assert init_cache(once, 8, 4, 2)[0]["k"].shape[0] == 8
    tables = jnp.asarray([[3, 5, 0], [1, 0, 0]], jnp.int32)
    third = entry_of_pass({**cache[0], "block_tables": tables}, 2, 4)
    np.testing.assert_array_equal(third["block_tables"], tables + 16)
    # a sequence's keys differ from pass to pass and lie where the table
    # says, a run of blocks on: the entries are not copies, and nothing is
    # written outside the blocks the allocator gave (and each pass's trash)
    eng = InferenceEngine(TINY, tiny["params"], EngineConfig(
        max_seqs=2, block_size=4, num_blocks=32, max_model_len=64,
        cache_dtype="float32", eos_token_id=-1))
    req = eng.submit(prompts([11])[0],
                     SamplingParams(temperature=0.0, max_tokens=3))
    while not req.output_token_ids:
        eng.step()
    slot = next(s for s in eng.slots if s.request is req)
    held = list(slot.blocks)
    pool = np.asarray(eng.executor.cache[0]["k"])         # layer 0
    assert pool.shape[0] == 4 * 32
    runs = [pool[u * 32:(u + 1) * 32] for u in range(4)]
    for u in range(4):
        written = {b for b in range(32) if np.abs(runs[u][b]).max() > 0}
        assert written - {0} == set(held[:3]), (u, written, held)
    assert np.abs(runs[0][held[0]] - runs[1][held[0]]).max() > 1e-2
    payload = eng.executor.fetch_block_kv(held[0])
    assert len(payload) == 3 and payload["l00000"]["k"].shape == (4, 4, 4, 16)
    np.testing.assert_array_equal(payload["l00000"]["k"][2],
                                  runs[2][held[0]])


@pytest.mark.parametrize("mode,ec", [
    ("plain", {}),
    ("kernel", {}),                                  # the decode kernel
    ("two_prefill_calls", {"max_prefill_tokens_per_step": 48}),
    ("int8_cache", {"cache_dtype": "int8"}),
])
def test_prefill_then_decode_through_the_cache_agrees_with_the_reference(
        tiny, mode, ec):
    """Rows of unequal length in one batch, a prompt split over two prefill
    calls (the later over what the earlier wrote, in every pass's entry),
    the interpreted kernel, int8 keys and values: the engine's log-probs against the reference's full forward at
    every position it sampled at."""
    cfg = dataclasses.replace(
        TINY, paged_attention_impl="kernel" if mode == "kernel" else "gather")
    eng = InferenceEngine(cfg, tiny["params"], EngineConfig(**{
        **dict(max_seqs=4, block_size=4, num_blocks=128, max_model_len=128,
               cache_dtype="float32", eos_token_id=-1), **ec}))
    asked = prompts([5, 70, 37, 21, 60, 12], seed=2)
    out = eng.generate(asked, SamplingParams(temperature=0.0, max_tokens=9))
    # int8 keys and values: a rounding of 1/254 a value, 12 times a token
    limit = 0.05 if mode == "int8_cache" else ENGINE_ATOL
    for prompt, result in zip(asked, out):
        diff, gap = against_reference(tiny, prompt, result)
        assert diff < limit and gap < limit, (mode, len(prompt), diff, gap)
    if mode == "two_prefill_calls":
        assert eng.stats["prefill_batches"] > 6
    assert eng.block_manager.num_free == eng.block_manager.num_blocks - 1


def test_a_preempted_and_recomputed_sequence_agrees_with_the_reference(tiny):
    eng = InferenceEngine(TINY, tiny["params"], EngineConfig(
        max_seqs=3, block_size=8, num_blocks=8, max_model_len=48,
        cache_dtype="float32", eos_token_id=-1))
    asked = prompts([7, 6, 5], seed=6)
    out = eng.generate(asked, SamplingParams(temperature=0.0, max_tokens=12))
    assert eng.stats["preemptions"] >= 1
    for prompt, result in zip(asked, out):
        assert len(result.output_token_ids) == 12
        diff, gap = against_reference(tiny, prompt, result)
        assert diff < ENGINE_ATOL and gap < ENGINE_ATOL, (diff, gap)
    assert eng.block_manager.num_free == 7


def test_a_program_that_gives_every_pass_the_first_passs_entry_disagrees(
        tiny, monkeypatch):
    """A program that is wrong on purpose: one entry a layer for all the
    passes (no pass's run of blocks: every pass writes and reads the first
    pass's entry, a cache of ``num_layers`` entries under a looped stack).
    Within one call a pass still sees the keys it computes itself; what it
    reads of the context is what the last pass left there."""
    from dlti_tpu.models import llama as llama_mod

    monkeypatch.setattr(llama_mod, "entry_of_pass",
                        lambda layer_cache, u, ut_steps: layer_cache)
    eng = InferenceEngine(TINY, tiny["params"], EngineConfig(
        max_seqs=2, block_size=4, num_blocks=64, max_model_len=128,
        cache_dtype="float32", eos_token_id=-1))
    asked = prompts([37, 21], seed=2)
    out = eng.generate(asked, SamplingParams(temperature=0.0, max_tokens=9))
    diffs = [against_reference(tiny, p, r)[0] for p, r in zip(asked, out)]
    # three layers of 0.02-increments deep: 0.006-0.017, thirty times and
    # more what the stated program reads
    assert min(diffs) > 20 * ENGINE_ATOL, diffs


# -- what takes a sequence's state to be its entries ---------------------------

def test_a_prefix_hit_equals_a_cold_prefill_and_the_reference(tiny):
    eng = InferenceEngine(TINY, tiny["params"], EngineConfig(
        max_seqs=4, block_size=8, num_blocks=64, max_model_len=128,
        cache_dtype="float32", eos_token_id=-1, enable_prefix_caching=True))
    asked = prompts([70, 41, 16], seed=11)
    sp = SamplingParams(temperature=0.0, max_tokens=7)
    cold = eng.generate(asked, sp)
    assert eng.stats["prefix_cached_tokens"] == 0
    warm = eng.generate(asked, sp)
    assert eng.stats["prefix_cached_tokens"] == 64 + 40 + 8
    for prompt, a, b in zip(asked, cold, warm):
        assert a.output_token_ids == b.output_token_ids
        diff, gap = against_reference(tiny, prompt, b)
        assert diff < ENGINE_ATOL and gap < ENGINE_ATOL


def test_the_host_tier_brings_back_every_entry_of_a_block(tiny):
    """A pool too small for four sessions: blocks go to the host tier with
    every pass's entry of every layer and come back; the answers are those of an engine
    that never evicted, and the reference's."""
    def engine(**kw):
        return InferenceEngine(TINY, tiny["params"], EngineConfig(
            max_seqs=1, block_size=8, num_blocks=7, max_model_len=40,
            cache_dtype="float32", eos_token_id=-1,
            enable_prefix_caching=True, **kw))

    eng = engine(prefix_host_blocks=8)
    sessions = [[i + 3] * 8 + [7] * 8 + [1, 2, 3] for i in range(4)]
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    first = {tuple(p): eng.generate([p], sp)[0] for p in sessions}
    for p in sessions:
        [again] = eng.generate([p], sp)
        assert again.output_token_ids == first[tuple(p)].output_token_ids
        diff, gap = against_reference(tiny, p, again)
        assert diff < ENGINE_ATOL and gap < ENGINE_ATOL
    assert eng.prefix_cache.tier_store.stats["host_hits"] > 0
    assert eng.stats["prefix_restored_tokens"] > 0


def test_a_hand_off_carries_every_entry_and_decodes_on(tiny):
    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=32,
                      max_model_len=128, cache_dtype="float32",
                      eos_token_id=-1)
    src = InferenceEngine(TINY, tiny["params"], ec)
    dst = InferenceEngine(TINY, tiny["params"], ec)
    src.prefill_only = True
    [prompt] = prompts([21], seed=8)
    req = src.submit(prompt, SamplingParams(temperature=0.0, max_tokens=6))
    for _ in range(50):
        src.step()
        slot = next((s for s in src.slots if s.request is req), None)
        if slot is not None and not slot.prefilling \
                and slot.last_token is not None:
            break
    snap = src.export_handoff(slot)
    assert len(snap["payloads"]) == 3
    assert all(len(block) == 3 and block["l00001"]["k"].shape[0] == 4
               for block in snap["payloads"])
    assert dst.adopt_handoff(snap)
    while req.finish_reason is None:
        dst.step()
    assert len(req.output_token_ids) == 6

    class Result:
        output_token_ids = req.output_token_ids
        output_logprobs = req.output_logprobs

    diff, gap = against_reference(tiny, prompt, Result)
    assert diff < ENGINE_ATOL and gap < ENGINE_ATOL


@pytest.mark.parametrize("name,ec,match", [
    ("speculative", dict(speculative="ngram"), "speculative"),
    ("int8_weights", dict(quantization="int8"), "weight-only int8"),
    ("multi_lora", dict(adapter_slots=2), "multi-LoRA"),
])
def test_what_is_not_implemented_is_refused_at_start_up(tiny, name, ec, match):
    with pytest.raises(ValueError, match=match):
        InferenceEngine(TINY, tiny["params"], EngineConfig(
            max_seqs=2, block_size=4, num_blocks=32, max_model_len=64, **ec))


def test_a_tensor_mesh_is_refused(tiny):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("tensor",))
    with pytest.raises(ValueError, match="tensor-parallel"):
        InferenceEngine(TINY, tiny["params"], EngineConfig(
            max_seqs=2, block_size=4, num_blocks=32, max_model_len=64),
            mesh=mesh)


def test_training_through_shared_weights_is_refused():
    from dlti_tpu.config import Config
    from dlti_tpu.training.trainer import Trainer

    with pytest.raises(NotImplementedError, match="ut_steps"):
        Trainer(Config(model=TINY, lora=LoRAConfig(enabled=True, r=4)))
