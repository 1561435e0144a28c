"""MoE (Mixtral-style) models through the serving engine.

The engine needs no MoE-specific decode path by construction: MoEMLP is a
drop-in for LlamaMLP inside LlamaBlock (static top-k dispatch, fixed
expert capacity — all static shapes), and KV paging only touches
attention. These tests pin that: greedy engine decode == repeated dense
argmax forward, through prefill + block-table growth + continuous
batching, in fp32 and with int8-quantized expert weights.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.serving.engine import EngineConfig, InferenceEngine, SamplingParams

pytestmark = pytest.mark.slow

# moe_capacity_factor = E/k makes dispatch drop-free at ANY token count:
# with finite capacity a *full* forward drops overflow tokens as a function
# of sequence length, so incremental (cached) decode and the full-sequence
# forward legitimately diverge once a prompt overflows an expert — a
# property of GShard-style static capacity, not a caching bug. Drop-free
# config isolates the invariant these tests pin: KV-cache correctness.
CFG = dataclasses.replace(
    MODEL_PRESETS["mixtral_tiny"], dtype="float32", param_dtype="float32")
CFG = dataclasses.replace(
    CFG, moe_capacity_factor=float(CFG.num_experts) / CFG.num_experts_per_tok)


@pytest.fixture(scope="module")
def moe_model_and_params():
    model = LlamaForCausalLM(CFG)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _dense_greedy(model, params, prompt, n_gen):
    toks = list(prompt)
    for _ in range(n_gen):
        logits, _ = model.apply({"params": params},
                                jnp.asarray([toks], jnp.int32),
                                deterministic=True)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_moe_engine_greedy_matches_dense_forward(moe_model_and_params):
    model, params = moe_model_and_params
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]  # crosses a block boundary (bs=8)
    n_gen = 10
    expected = _dense_greedy(model, params, prompt, n_gen)

    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=32,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    eng = InferenceEngine(CFG, params, ec)
    [res] = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                  max_tokens=n_gen))
    assert res.output_token_ids == expected


def test_moe_engine_continuous_batching(moe_model_and_params):
    """Interleaved MoE requests share expert buffers correctly: each
    request's greedy output is independent of its batch company."""
    model, params = moe_model_and_params
    prompts = [[1, 2, 3], [7, 8, 9, 10, 11], [4, 5]]
    n_gen = 6
    expected = [_dense_greedy(model, params, p, n_gen) for p in prompts]

    ec = EngineConfig(max_seqs=3, block_size=8, num_blocks=32,
                      max_model_len=64, cache_dtype="float32",
                      eos_token_id=-1)
    eng = InferenceEngine(CFG, params, ec)
    results = eng.generate(prompts, SamplingParams(temperature=0.0,
                                                   max_tokens=n_gen))
    for r, want in zip(results, expected):
        assert r.output_token_ids == want


def test_moe_engine_int8_weights_close_to_fp32(moe_model_and_params):
    """int8 weight-only quantization covers expert tensors (per-expert
    out-channel scales, MoEMLP's maybe_dequantize branch): the int8
    engine's greedy tokens track fp32 for most steps."""
    from dlti_tpu.models.quantization import quantize_params_int8

    model, params = moe_model_and_params
    prompt = [3, 1, 4, 1, 5, 9]
    n_gen = 8
    expected = _dense_greedy(model, params, prompt, n_gen)

    qparams = quantize_params_int8(params)
    w1 = qparams["model"]["layers_0"]["mlp"]["w1"]
    assert isinstance(w1, dict) and w1["q"].dtype == jnp.int8

    ec = EngineConfig(max_seqs=1, block_size=8, num_blocks=16,
                      max_model_len=32, cache_dtype="float32",
                      eos_token_id=-1)
    eng = InferenceEngine(CFG, qparams, ec)
    [res] = eng.generate([prompt], SamplingParams(temperature=0.0,
                                                  max_tokens=n_gen))
    agree = sum(a == b for a, b in zip(res.output_token_ids, expected))
    assert agree >= n_gen - 2, (res.output_token_ids, expected)
