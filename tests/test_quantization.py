"""Weight-only int8 serving: quantization round-trip + engine integration."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.models.quantization import (
    dequantize_params,
    quantization_error,
    quantize_params_int8,
)
from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams

# Quantization only touches leaves >= 64KiB; bump the tiny preset's sizes
# enough that the projections qualify.
CFG = dataclasses.replace(
    MODEL_PRESETS["llama_tiny"], hidden_size=128, intermediate_size=256,
    vocab_size=1024)


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(CFG, None)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def test_quantize_leaf_selection_and_error(model_and_params):
    _, params = model_and_params
    qp = quantize_params_int8(params)
    # Kernels became {"q","scale"} int8 nodes; norm scales stayed float.
    qk = qp["model"]["layers_0"]["attn"]["q_proj"]["kernel"]
    assert set(qk.keys()) == {"q", "scale"} and qk["q"].dtype == jnp.int8
    assert qp["model"]["layers_0"]["input_norm"]["scale"].dtype != jnp.int8
    # int8 symmetric absmax keeps per-leaf relative RMS error small.
    assert quantization_error(params, qp) < 0.01


def test_dequantize_roundtrip_close(model_and_params):
    _, params = model_and_params
    deq = dequantize_params(quantize_params_int8(params), jnp.float32)
    a = np.asarray(params["model"]["layers_0"]["mlp"]["gate_proj"]["kernel"])
    b = np.asarray(deq["model"]["layers_0"]["mlp"]["gate_proj"]["kernel"])
    scale = np.abs(a).max(axis=0)
    np.testing.assert_allclose(a, b, atol=float(scale.max()) / 127 + 1e-7)


@pytest.mark.slow
def test_int8_engine_logits_close_and_serves(model_and_params):
    model, params = model_and_params
    ec = dict(max_seqs=2, block_size=8, num_blocks=32, max_model_len=48,
              cache_dtype="float32", eos_token_id=-1)
    prompts = [[3, 1, 4, 1, 5, 9], [2, 7, 1, 8]]
    sp = SamplingParams(temperature=0.0, max_tokens=6)

    fp = InferenceEngine(CFG, params, EngineConfig(**ec))
    q8 = InferenceEngine(CFG, params, EngineConfig(quantization="int8", **ec))
    # Weights really rest as int8.
    assert (q8.params["model"]["layers_0"]["attn"]["q_proj"]["kernel"]["q"]
            .dtype == jnp.int8)

    want = fp.generate(prompts, sp)
    got = q8.generate(prompts, sp)
    # Random tiny weights leave tokens near-tied, so compare logprob
    # trajectories rather than exact argmax tokens.
    for g, w in zip(got, want):
        assert len(g.output_token_ids) == len(w.output_token_ids)
        np.testing.assert_allclose(g.output_logprobs, w.output_logprobs,
                                   atol=0.35)


@pytest.mark.slow
def test_int8_tp_engine_matches_unsharded_int8(model_and_params):
    """int8 weights compose with TP: quantized {"q","scale"} leaves shard
    like their fp ancestors (scales follow output channels, replicate for
    row-parallel kernels) and TP=2 generation matches the unsharded int8
    engine token-for-token."""
    from dlti_tpu.config import ParallelConfig
    from dlti_tpu.parallel import build_mesh

    _, params = model_and_params
    ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=32,
                      max_model_len=48, cache_dtype="float32",
                      eos_token_id=-1, quantization="int8")
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8]]
    sp = SamplingParams(temperature=0.0, max_tokens=5)

    want = InferenceEngine(CFG, params, ec).generate(prompts, sp)

    mesh = build_mesh(ParallelConfig(tensor=2), devices=jax.devices()[:2])
    tp_engine = InferenceEngine(CFG, params, ec, mesh=mesh)
    # Quantized kernels really are sharded: q_proj q-leaf over its out dim,
    # its scale alongside; down_proj (row-parallel) scale replicated.
    qp = tp_engine.executor.params["model"]["layers_0"]["attn"]["q_proj"]["kernel"]
    assert qp["q"].sharding.spec[1] == "tensor"
    assert qp["scale"].sharding.spec[1] == "tensor"
    dp = tp_engine.executor.params["model"]["layers_0"]["mlp"]["down_proj"]["kernel"]
    assert dp["q"].sharding.spec[0] == "tensor"
    assert all(s is None for s in dp["scale"].sharding.spec)
    got = tp_engine.generate(prompts, sp)
    for g, w in zip(got, want):
        assert g.output_token_ids == w.output_token_ids


@pytest.mark.slow
def test_int8_moe_engine_serves(model_and_params):
    """MoE int8 serving: experts quantize (per-expert scales), the router
    stays fp32, and generation runs."""
    moe_cfg = dataclasses.replace(
        MODEL_PRESETS["mixtral_tiny"], hidden_size=128, intermediate_size=256,
        vocab_size=1024)
    model = LlamaForCausalLM(moe_cfg, None)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    qp = quantize_params_int8(params)
    mlp = qp["model"]["layers_0"]["mlp"]
    assert mlp["w1"]["q"].dtype == jnp.int8
    assert mlp["w1"]["scale"].shape == (4, 1, 256)  # per-expert-channel
    assert mlp["router"].dtype != jnp.int8  # excluded

    engine = InferenceEngine(moe_cfg, params, EngineConfig(
        max_seqs=2, block_size=8, num_blocks=32, max_model_len=48,
        cache_dtype="float32", eos_token_id=-1, quantization="int8"))
    [r] = engine.generate([[3, 1, 4, 1, 5]],
                          SamplingParams(temperature=0.0, max_tokens=5))
    assert len(r.output_token_ids) == 5
