"""A prefill program applies the head to each row's last real position
alone: the rows it returns against the model's logits over every position,
the cache and the counters against the program that computed those, and no
array of every position by the vocabulary in its text. Every family the
executor serves, at test widths."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.models import build_model
from dlti_tpu.models.lora import merge_lora_params
from dlti_tpu.serving.adapters import (
    get_catalog, register_adapter, save_adapter,
)
from dlti_tpu.serving.engine import EngineConfig, InferenceEngine
from dlti_tpu.serving.sampling import SamplingParams
from test_adapters import ALPHA, _lora_params
from test_layer_windows import TINY as WINDOW_GROUPS

BUCKET = 32
# Three prompts of one bucket and different lengths: one call of four rows,
# the fourth padding (positions -1, last_idx 0).
LENGTHS = (20, 27, 31)

LLAMA = MODEL_PRESETS["llama_tiny"]
LATENT = dataclasses.replace(MODEL_PRESETS["latent_tiny"],
                             moe_scoring="sigmoid_bias")
# name: (model configuration, EngineConfig fields, adapters to serve through)
CASES = {
    "llama_dense": (LLAMA, {}, False),
    "llama_tied": (dataclasses.replace(LLAMA, tie_embeddings=True), {}, False),
    "llama_int8_head": (LLAMA, {"quantization": "int8"}, False),
    # (``test_adapters``' factors: rank 4 over ``llama_tiny``)
    "llama_lora_pool": (LLAMA, {"adapter_slots": 2, "adapter_rank": 4}, True),
    "llama_window_groups": (WINDOW_GROUPS, {"num_blocks": 320}, False),
    "nemotron_h": (MODEL_PRESETS["nemotron_h_tiny"], {}, False),
    "latent": (LATENT, {}, False),
    "latent_hyper": (dataclasses.replace(LATENT, hc_mult=4), {}, False),
}


def every_position_program(ex):
    """The prefill program as it was: float32 logits over every position
    of every row, one position a row kept. Not donated: the cache it is
    handed goes on to the program under test."""
    @jax.jit
    def prefill(params, cache_kv, input_ids, positions, block_table,
                last_idx, *lora):
        logits, new_kv, counters = ex._model_cache_call(
            params, cache_kv, block_table, input_ids, positions,
            **ex._named(lora))
        last = jnp.take_along_axis(
            logits, last_idx[:, None, None], axis=1)[:, 0]
        return (new_kv, last) if counters is None \
            else (new_kv, last, counters)

    return prefill


def f32_arrays(text):
    """The shape of every float32 array the program's text names."""
    return {tuple(map(int, dims.split("x")))
            for dims in re.findall(r"tensor<(\d+(?:x\d+)*)xf32>", text)}


def every_position_by_vocab(shapes, vocab):
    """The logits of every position among them: ``(bucket, vocab)`` under
    nothing or the rows, or the two flattened. (By rank: a window of 512
    keys under 32 queries is as wide as these vocabularies.)"""
    return sorted(s for s in shapes if s in (
        (BUCKET, vocab), (4, BUCKET, vocab), (4 * BUCKET, vocab)))


def lora_adapters(root):
    """Two adapters with real factors over ``llama_tiny``, saved and
    registered; their names and the base they share."""
    trees = {f"ad{seed}": _lora_params(seed) for seed in (1, 2)}
    for name, tree in trees.items():
        save_adapter(str(root / name), tree, alpha=ALPHA)
        register_adapter(name, str(root / name))
    return sorted(trees), merge_lora_params(trees["ad1"], scaling=0.0)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_prefill_program_heads_each_rows_last_state_alone(name, tmp_path):
    cfg, engine_fields, pooled = CASES[name]
    adapters = [""]
    if pooled:
        get_catalog().clear()
        adapters, params = lora_adapters(tmp_path)
    else:
        params = build_model(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(cfg, params, EngineConfig(**{
        "max_seqs": 4, "block_size": 4, "num_blocks": 128,
        "max_model_len": 128, "cache_dtype": "float32", "eos_token_id": -1,
        **engine_fields}))
    ex = eng.executor
    parent, program_of, calls = every_position_program(ex), ex._prefill_fn, []

    def watched(bucket):
        fn = program_of(bucket)

        def call(params, cache, *args):
            text = fn.lower(params, cache, *args).as_text()
            was = parent.lower(params, cache, *args).as_text()
            want = jax.device_get(parent(params, cache, *args))
            got = fn(params, cache, *args)  # (the cache is donated here)
            calls.append((bucket, jax.device_get(args), want,
                          jax.device_get(got), text, was))
            return got

        return call

    ex._prefill_fn = watched
    try:
        rng = np.random.RandomState(7)
        reqs = [eng.submit([int(t) for t in rng.randint(3, 500, n)],
                           SamplingParams(temperature=0.0, max_tokens=2),
                           adapter=adapters[i % len(adapters)])
                for i, n in enumerate(LENGTHS)]
        while eng.has_work:
            eng.step()
    finally:
        get_catalog().clear()
    assert all(len(eng._result(r).output_token_ids) == 2 for r in reqs)

    # The call the three prompts went out in: rows of different last
    # positions and a padding row.
    (bucket, args, want, got, text, was), = [
        c for c in calls if c[1][0].shape == (4, BUCKET)]
    positions, last_idx = args[1], args[3]
    assert bucket == BUCKET and sorted(last_idx) == [0, 19, 26, 30]
    assert (positions[np.argmin(last_idx)] == -1).all()

    # (a) The rows equal the logits over every position at last_idx. The
    # operands are the same (the head ``head_matrix`` hands out, the same
    # final states); the product of 4 rows may tile its accumulation over
    # the hidden width otherwise than the product of 128, so float32
    # rounding of a sum of ``hidden`` terms is allowed and no more.
    scale = np.abs(want[1]).max()
    assert got[1].shape == (4, cfg.vocab_size) and got[1].dtype == np.float32
    np.testing.assert_allclose(got[1], want[1], rtol=0,
                               atol=8 * np.finfo(np.float32).eps * scale)
    assert scale > 0.1 and np.ptp(want[1][np.argmax(last_idx)]) > 0.1

    # (b) The cache is the one the other program wrote, to the bit.
    for a, b in zip(jax.tree_util.tree_leaves(got[0]),
                    jax.tree_util.tree_leaves(want[0]), strict=True):
        assert a.dtype == b.dtype and np.array_equal(a, b)

    # (c) No float32 array of every position by the vocabulary in the
    # program, where the other program holds one.
    assert every_position_by_vocab(f32_arrays(was), cfg.vocab_size)
    assert not every_position_by_vocab(f32_arrays(text), cfg.vocab_size)
    assert (4, cfg.vocab_size) in f32_arrays(text)

    # (d) What the model counts comes back unchanged.
    assert len(got) == len(want) == (3 if ex.counter_names else 2)
    if ex.counter_names:
        assert np.array_equal(got[2], want[2]) and got[2].any()
