"""The serving cache of a model whose layers differ in their window: a pool,
a table and an allocator a group of layers; the window group's blocks
released behind the window; what is refused for such a model; the books."""

import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import MODEL_PRESETS, ModelConfig
from dlti_tpu.models import build_model
from dlti_tpu.ops import kv_cache
from dlti_tpu.ops.kv_cache import (
    bind_call, init_cache, init_paged_cache, unbind_call, window_blocks,
    window_group_blocks,
)
from dlti_tpu.serving import block_manager as bmod
from dlti_tpu.serving import engine as engine_mod
from dlti_tpu.serving.decode_state import RoundPacking
from dlti_tpu.serving.engine import (
    EngineConfig, InferenceEngine, refuse_state_handoff,
)
from dlti_tpu.serving.sampling import SamplingParams

TINY = ModelConfig(
    vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=5,
    num_heads=8, num_kv_heads=2, head_dim=16, max_seq_len=512,
    rope_theta=1e6, remat=False, dtype="float32", param_dtype="float32",
    layer_windows=(8, 8, 8, 0, 8), qk_norm=True, rope_on_full_layers=False,
    first_k_dense=1, moe_num_experts=8, moe_held_count=4,
    num_experts_per_tok=3, moe_intermediate_size=32,
    moe_shared_intermediate_size=32, moe_routed_scaling=2.5)
EC = EngineConfig(max_seqs=4, block_size=4, num_blocks=320, max_model_len=256,
                  cache_dtype="float32")


@pytest.fixture(scope="module")
def params():
    return build_model(TINY).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(3, 512, n)] for n in lengths]


# -- pools, tables, packing ----------------------------------------------------

def test_a_window_group_has_a_pool_of_its_own_size_from_shapes_alone():
    cache = init_cache(TINY, 320, 4, 4, jnp.float32, call_tokens=64)
    want = window_group_blocks(8, 4, 4, 64)
    assert want == 4 * (3 + 2) + 16 + 8 + 1
    assert [c["k"].shape[0] for c in cache] == [want, want, want, 320, want]
    assert all(c["k"].shape[1:] == (4, 2, 16) for c in cache)
    assert window_blocks(128, 16, 1) == 11 and window_blocks(128, 16, 2048) == 138
    # the cell's: 32 slots, a window of 128 over blocks of 16, calls of 2,048
    assert window_group_blocks(128, 16, 32, 2048) == 489


@pytest.mark.parametrize("name", ["llama_tiny", "mistral_7b"])
def test_a_model_of_one_group_keeps_its_cache_to_the_byte(name):
    cfg = dataclasses.replace(MODEL_PRESETS[name], num_layers=2,
                              hidden_size=64, num_heads=4, num_kv_heads=2,
                              head_dim=None)
    cache = init_cache(cfg, 32, 4, 4, jnp.bfloat16, call_tokens=2048)
    plain = init_paged_cache(2, 32, 4, 2, 16, jnp.bfloat16)
    assert jax.tree_util.tree_structure(cache) == \
        jax.tree_util.tree_structure(plain)
    assert [(v.shape, v.dtype) for v in jax.tree_util.tree_leaves(cache)] == \
        [(v.shape, v.dtype) for v in jax.tree_util.tree_leaves(plain)]


def test_bind_call_hands_each_layer_its_groups_table():
    cache = init_cache(TINY, 32, 4, 4, jnp.float32, call_tokens=16)
    full = {"block_tables": jnp.ones((4, 64), jnp.int32)}
    win = {"block_tables": jnp.ones((4, 5), jnp.int32),
           "table_base": jnp.zeros((4,), jnp.int32)}
    bound = bind_call(cache, (full, win), groups=[1, 1, 1, 0, 1])
    assert [c["block_tables"].shape[1] for c in bound] == [5, 5, 5, 64, 5]
    assert ["table_base" in c for c in bound] == [True] * 3 + [False, True]
    assert all(set(c) == {"k", "v"} for c in unbind_call(bound))


def test_the_packed_round_carries_the_window_table_as_wide_as_it_needs():
    plain = RoundPacking(4, 64)
    packing = RoundPacking(4, 64, window_blocks=5)
    assert packing.width == plain.width + 5 + 1       # table + base
    mirrors = {
        "block_tables": np.arange(4 * 64, dtype=np.int32).reshape(4, 64),
        "window_tables": 7 + np.arange(20, dtype=np.int32).reshape(4, 5),
        "window_base": np.asarray([0, 16, 32, 48], np.int32),
        "slot_keys": np.ones((4, 2), np.uint32),
        "gen_counts": np.arange(4, dtype=np.int32),
        "temperature": np.full((4,), 0.5, np.float32),
        "top_k": np.zeros((4,), np.int32),
        "top_p": np.ones((4,), np.float32)}
    ids = np.full((4, 1), 9, np.int32)
    packed = packing.pack(ids, ids + 1, mirrors, masked_rows=[2])
    out = packing.unpack(jnp.asarray(packed))
    assert len(out) == 8                    # the programs' argument order
    full, win = out[2]
    np.testing.assert_array_equal(np.asarray(full["block_tables"])[[0, 1, 3]],
                                  mirrors["block_tables"][[0, 1, 3]])
    np.testing.assert_array_equal(np.asarray(win["block_tables"])[[0, 1, 3]],
                                  mirrors["window_tables"][[0, 1, 3]])
    np.testing.assert_array_equal(np.asarray(win["table_base"]),
                                  [0, 16, 0, 48])
    # a slot still prefilling reads as the trash block in both groups
    assert not np.asarray(full["block_tables"])[2].any()
    assert not np.asarray(win["block_tables"])[2].any()
    np.testing.assert_array_equal(np.asarray(out[5]), mirrors["temperature"])
    # a model of one group packs what it packed
    assert isinstance(plain.unpack(jnp.zeros((4, plain.width), jnp.int32))[2],
                      jax.Array)


# -- the allocator's invariants --------------------------------------------------

def check_invariants(eng):
    bs, w = eng.cfg.block_size, eng.window
    held = [b for s in eng.slots for b in s.window_blocks]
    assert len(held) == len(set(held)), "a window block held twice"
    assert 0 not in held
    mgr = eng.window_manager
    assert mgr.num_blocks - 1 - mgr.num_free == len(held)
    full = [b for s in eng.slots for b in s.blocks]
    assert len(full) == len(set(full)) and 0 not in full
    assert eng.block_manager.num_blocks - 1 - eng.block_manager.num_free \
        == len(full)
    for s in eng.slots:
        if s.free:
            assert not s.window_blocks and not s.blocks
            assert not eng._window_tables[s.slot_id].any()
            continue
        written = s.next_pos if s.prefilling else s.seq_len
        # nothing a later token's window can reach has been released ...
        assert s.window_first * bs <= max(0, written - w + 1)
        # ... and what is written and inside the window is held
        if written:
            assert (s.window_first + len(s.window_blocks)) * bs >= written
        if not s.prefilling:
            # the row that rides a round: the held blocks, from the first
            row = eng._window_tables[s.slot_id]
            n = len(s.window_blocks)
            assert n <= len(row)
            assert list(row[:n]) == s.window_blocks and not row[n:].any()
            assert eng._window_base[s.slot_id] == s.window_first * bs
            # between calls a sequence holds a window's blocks and the
            # round's one token's alone
            assert n <= window_blocks(w, bs, 1)


@pytest.mark.parametrize("native", [True, False])
@pytest.mark.parametrize("mode", ["plain", "chunked", "stop_tokens"])
def test_random_traffic_keeps_the_allocators_invariants(params, monkeypatch,
                                                        native, mode):
    """Random admissions, prefill calls (whole or in chunks), decode rounds
    and ends over the two groups: no block held twice, the window group
    within its bound, every table entry a live tile can touch a held block,
    everything free at the end; the native core and the fallback alike.
    ``stop_tokens``: ends the plan cannot foresee (an end by length it
    can), so rows of sequences that have ended, their blocks of both groups
    released, are in flight and thrown away."""
    if not native:
        monkeypatch.setattr(bmod, "load_native_runtime", lambda: None)
    elif bmod.load_native_runtime() is None:
        pytest.skip("no native runtime here")
    ec = dataclasses.replace(EC, **(
        {"max_prefill_tokens_per_step": 40} if mode == "chunked" else {}))
    # (an eighth of the vocabulary: an answer seldom reaches its length)
    stops = tuple(range(3, 512, 8)) if mode == "stop_tokens" else ()
    eng = InferenceEngine(TINY, params, ec)
    assert (eng.window_manager._native is not None) == native
    assert (eng.block_manager._native is not None) == native
    rng = random.Random(7)
    lengths = [rng.choice([3, 9, 17, 30, 41, 66, 120, 200]) for _ in range(14)]
    reqs = []
    for i, prompt in enumerate(prompts(lengths, seed=1)):
        reqs.append((prompt, SamplingParams(
            temperature=rng.choice([0.0, 1.0]), seed=100 + i,
            stop_token_ids=stops,
            max_tokens=min(rng.randint(1, 24), 255 - len(prompt)))))
    pending, steps = list(reqs), 0
    while pending or eng.has_work:
        for _ in range(rng.randint(0, 2)):
            if pending:
                eng.submit(*pending.pop())
        eng.step()
        check_invariants(eng)
        steps += 1
        assert steps < 2000
    assert eng.window_manager.num_free == eng.window_manager.num_blocks - 1
    assert eng.block_manager.num_free == eng.block_manager.num_blocks - 1
    assert eng.kv_freed["window", "window"] > 0 < eng.kv_freed["window", "end"]
    assert (eng.stats["decode_rows_discarded"] >= 4) == (mode == "stop_tokens")
    assert eng.stats["decode_window_context_tokens"] \
        < eng.stats["decode_context_tokens"]
    assert 0 < eng.stats["prefill_window_attention_pairs"] \
        < eng.stats["prefill_attention_pairs"]


def test_release_on_equals_every_block_kept_to_the_bit(params, monkeypatch):
    """The same requests through an engine whose released blocks go back to
    the pool and are written by other sequences at once, and through one
    that hands no released block out again (every block kept as written,
    in a pool with room for that): tokens and log-probs to the bit."""
    asked = prompts([150, 12, 70, 33, 101, 6, 58, 90], seed=5)
    sampling = [SamplingParams(temperature=t, seed=s, max_tokens=14)
                for t, s in zip([0.0, 1.0] * 4, range(40, 48))]

    def run(eng):
        reqs = [eng.submit(p, sp) for p, sp in zip(asked, sampling)]
        while eng.has_work:
            eng.step()
        return [(r.output_token_ids, r.output_logprobs) for r in reqs]

    released = run(InferenceEngine(TINY, params, EC))
    monkeypatch.setattr(kv_cache, "window_group_blocks", lambda *a: 2048)
    monkeypatch.setattr(engine_mod, "window_group_blocks", lambda *a: 2048)
    keeper = InferenceEngine(TINY, params, EC)
    assert keeper.window_manager.num_blocks == 2048
    kept = []
    monkeypatch.setattr(keeper.window_manager, "free", kept.extend)
    assert run(keeper) == released
    assert len(kept) > 100 and len(set(kept)) == len(kept)   # never reused


def test_preemption_recomputes_through_both_groups(params):
    """A full group too small for the batch: the youngest sequence goes back
    to the queue, its blocks of both groups with it, and is computed again."""
    model = build_model(TINY)
    ec = dataclasses.replace(EC, num_blocks=66)
    eng = InferenceEngine(TINY, params, ec)
    asked = prompts([60, 60, 60, 60], seed=9)
    out = eng.generate(asked, SamplingParams(temperature=0.0, max_tokens=40))
    assert eng.stats["preemptions"] > 0
    for prompt, result in zip(asked, out):
        ids = jnp.asarray([prompt + result.output_token_ids])
        lp = jax.nn.log_softmax(model.apply({"params": params}, ids)[0][0], -1)
        rows = lp[len(prompt) - 1:len(prompt) - 1 + 40]
        theirs = np.asarray(rows[np.arange(40),
                                 np.asarray(result.output_token_ids)])
        assert np.abs(theirs - np.asarray(result.output_logprobs)).max() < 2e-4
    assert eng.window_manager.num_free == eng.window_manager.num_blocks - 1


# -- the books -------------------------------------------------------------------

def test_the_caches_books_by_group(params):
    eng = InferenceEngine(TINY, params, EC)
    eng.generate(prompts([40, 25]), SamplingParams(temperature=0.0,
                                                   max_tokens=6))
    series = {}
    for metric in eng.kv_metrics():
        for name, labels, child in metric.samples():
            series[name + labels] = (metric.kind, child.value)
    bound = window_group_blocks(8, 4, 4, 2048)
    assert series['dlti_kv_pool_blocks{group="full"}'] == ("gauge", 320)
    assert series['dlti_kv_pool_blocks{group="window"}'] == ("gauge", bound)
    assert series['dlti_kv_blocks_in_use{group="full"}'] == ("gauge", 0)
    assert series['dlti_kv_blocks_in_use{group="window"}'] == ("gauge", 0)
    assert series["dlti_kv_context_tokens"] == ("gauge", 0)
    freed = {k: v[1] for k, v in series.items() if "freed" in k}
    assert freed == {
        'dlti_kv_blocks_freed_total{group="full",why="end"}':
            eng.kv_freed["full", "end"],
        'dlti_kv_blocks_freed_total{group="window",why="end"}':
            eng.kv_freed["window", "end"],
        'dlti_kv_blocks_freed_total{group="window",why="window"}':
            eng.kv_freed["window", "window"]}
    assert freed['dlti_kv_blocks_freed_total{group="window",why="window"}'] > 0
    kind, seconds = series["dlti_kv_window_free_seconds_total"]
    assert kind == "counter" and seconds > 0
    # a model of one group reports the one it has
    cfg = MODEL_PRESETS["llama_tiny"]
    dense = build_model(cfg).init(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(cfg, dense, EngineConfig(max_seqs=2, num_blocks=16,
                                                   max_model_len=64))
    names = [name + labels for metric in eng.kv_metrics()
             for name, labels, _ in metric.samples()]
    assert 'dlti_kv_blocks_in_use{group="full"}' in names
    assert not [n for n in names if "window\"" in n]
    assert eng.window_manager is None and eng._window_tables.shape == (2, 0)


# -- what is refused -------------------------------------------------------------

@pytest.mark.parametrize("engine_kw,message", [
    ({"enable_prefix_caching": True}, "prefix's last window"),
    ({"prefix_host_blocks": 8}, "prefix's last window"),
    ({"speculative": "ngram"}, "rolls rejected drafts back"),
    ({"quantization": "int8"}, "not implemented for expert layers"),
    ({"adapter_slots": 2}, "no multi-LoRA adapter branch"),
])
def test_what_takes_one_list_of_blocks_is_refused_at_start_up(params,
                                                              engine_kw,
                                                              message):
    with pytest.raises(ValueError, match=message):
        InferenceEngine(TINY, params, dataclasses.replace(EC, **engine_kw))


def test_a_tensor_mesh_hand_off_and_a_prediction_module_are_refused(params):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("tensor",))
    with pytest.raises(ValueError, match="no tensor-parallel placement"):
        InferenceEngine(TINY, params, EC, mesh=mesh)
    with pytest.raises(ValueError, match="a list a group of layers"):
        refuse_state_handoff(TINY, "--disagg")
    eng = InferenceEngine(TINY, params, EC)
    eng.submit(prompts([9])[0], SamplingParams(max_tokens=4))
    eng.step()
    with pytest.raises(ValueError, match="export_handoff moves a sequence"):
        eng.export_handoff(eng.slots[0])
    with pytest.raises(ValueError, match="num_nextn_predict_layers 1"):
        InferenceEngine(dataclasses.replace(
            TINY, num_nextn_predict_layers=1), params, EC)
    # held experts alone (one group) keep prefix caching
    same = dataclasses.replace(TINY, layer_windows=())
    dense = build_model(same).init(jax.random.PRNGKey(0),
                                   jnp.zeros((1, 8), jnp.int32))["params"]
    InferenceEngine(same, dense, dataclasses.replace(
        EC, enable_prefix_caching=True))
    with pytest.raises(ValueError, match="held routed experts"):
        InferenceEngine(same, dense, dataclasses.replace(
            EC, speculative="ngram"))
