"""int8 frozen-base LoRA training (the QLoRA idea, TPU-style).

Grads flow only to the LoRA factors, so the frozen base may rest in HBM as
weight-only int8 (``TrainConfig.quantize_frozen_base``) — the lever that
frees ~half the base-weight HBM for activation saving at 7B. Contracts
under test:

* quant leaves partition into the frozen subset; only LoRA trains
* the int8-frozen loss trajectory tracks the bf16 trajectory closely
* merged export dequantizes back to a standard compute-dtype tree
* the sharded (ZeRO-3 x TP) int8 step matches the single-device int8 step
* the Trainer wires it end to end (train -> resume -> export)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import (
    CheckpointConfig,
    Config,
    DataConfig,
    LoRAConfig,
    MODEL_PRESETS,
    OptimizerConfig,
    ParallelConfig,
    TrainConfig,
    ZeROStage,
)
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.models.lora import merge_lora_params
from dlti_tpu.models.quantization import (
    is_quant_node,
    quantize_params_int8,
)
from dlti_tpu.training import build_optimizer, create_train_state, make_train_step
from dlti_tpu.training.state import partition_params

# Big enough that projections pass the >=64KiB quantization threshold.
CFG = dataclasses.replace(
    MODEL_PRESETS["llama_tiny"], hidden_size=128, intermediate_size=256,
    vocab_size=1024)
LORA = LoRAConfig(r=4, alpha=8, dropout=0.0)


def _state(rng, quantize: bool):
    model = LlamaForCausalLM(CFG, LORA)
    tx = build_optimizer(OptimizerConfig(warmup_steps=2))
    state = create_train_state(rng, model, tx, (4, 32), lora_enabled=True)
    if quantize:
        state = state.replace(params=quantize_params_int8(state.params))
    return model, state


def _batch(seed, accum=1, bs=4, seq=32):
    r = jax.random.PRNGKey(seed)
    return {
        "input_ids": jax.random.randint(r, (accum, bs, seq), 0, CFG.vocab_size),
        "loss_mask": jnp.ones((accum, bs, seq), jnp.int32),
    }


def _run(rng, quantize: bool, steps: int):
    model, state = _state(rng, quantize)
    step = jax.jit(make_train_step(model, accum_steps=1))
    losses = []
    batch = _batch(0)  # fixed batch: memorization must drive loss down
    for i in range(steps):
        state, m = step(state, batch, jax.random.fold_in(rng, i))
        losses.append(float(m["loss"]))
    return state, losses


def test_quant_leaves_partition_as_frozen(rng):
    _, state = _state(rng, quantize=True)
    trainable, frozen = partition_params(state.params, lora_enabled=True)
    assert trainable, "LoRA factors must stay trainable"
    for key in trainable:
        assert key[-1] in ("lora_a", "lora_b")
    # Every quantized kernel's q/scale pair landed in the frozen subset.
    q_keys = [k for k in frozen if k[-1] == "q"]
    assert q_keys, "expected int8 kernels in the frozen subset"
    for k in q_keys:
        assert frozen[k].dtype == jnp.int8
        assert k[:-1] + ("scale",) in frozen


@pytest.mark.slow
def test_int8_frozen_loss_tracks_bf16(rng):
    """Quantization noise on the frozen base must be benign: the int8 run's
    loss trajectory stays within a small band of the bf16 run's."""
    steps = 12
    _, ref = _run(rng, quantize=False, steps=steps)
    _, q = _run(rng, quantize=True, steps=steps)
    assert all(np.isfinite(q)), q
    # Same data, same init (B=0 start): per-step losses track closely.
    for i, (a, b) in enumerate(zip(ref, q)):
        assert abs(a - b) / a < 0.02, f"step {i}: bf16 {a} vs int8 {b}"
    # And training actually trains.
    assert q[-1] < q[0]


def test_merged_export_is_dequantized_and_close(rng):
    _, state = _state(rng, quantize=True)
    # Give LoRA a nonzero delta so the merge is exercised for real.
    trainable, frozen = partition_params(state.params, lora_enabled=True)
    trainable = {
        k: jax.random.normal(jax.random.fold_in(rng, i), v.shape, v.dtype) * 0.02
        for i, (k, v) in enumerate(sorted(trainable.items()))
    }
    from dlti_tpu.training.state import combine_params

    params = combine_params(trainable, frozen)
    merged = merge_lora_params(params, alpha=LORA.alpha)

    leaves = jax.tree_util.tree_leaves_with_path(merged)
    assert not any(is_quant_node(v) for _, v in leaves)
    for path, v in leaves:
        assert v.dtype != jnp.int8, path

    # Against the dequantized-then-merged reference.
    from dlti_tpu.models.quantization import dequantize_params

    ref = merge_lora_params(
        combine_params(trainable, dequantize_params(frozen)), alpha=LORA.alpha)
    k = "q_proj"
    a = np.asarray(
        merged["model"]["layers_0"]["attn"][k]["kernel"], np.float32)
    b = np.asarray(ref["model"]["layers_0"]["attn"][k]["kernel"], np.float32)
    np.testing.assert_allclose(a, b, atol=1e-2)


@pytest.mark.slow
def test_sharded_int8_matches_single_device(rng):
    from dlti_tpu.parallel import build_mesh, make_sharded_train_step, shard_train_state

    batch = _batch(7, accum=2, bs=8)
    # Single-device int8 ground truth.
    model, state = _state(rng, quantize=True)
    step = jax.jit(make_train_step(model, accum_steps=2))
    ref_metrics = None
    for i in range(2):
        state, ref_metrics = step(state, batch, jax.random.fold_in(rng, i))

    cfg = Config(
        model=CFG, lora=LORA, optimizer=OptimizerConfig(warmup_steps=2),
        parallel=ParallelConfig(zero_stage=ZeROStage.ZERO3, fsdp=4, tensor=2),
        train=TrainConfig(micro_batch_size=8, grad_accum_steps=2,
                          quantize_frozen_base="int8"),
    )
    model, sh_state = _state(rng, quantize=True)
    mesh = build_mesh(cfg.parallel)
    sh_state = shard_train_state(sh_state, cfg, mesh)
    sh_step = make_sharded_train_step(model, sh_state, cfg, mesh,
                                      accum_steps=2, donate=False)
    metrics = None
    for i in range(2):
        sh_state, metrics = sh_step(sh_state, batch, jax.random.fold_in(rng, i))
    np.testing.assert_allclose(float(metrics["loss"]),
                               float(ref_metrics["loss"]), rtol=2e-4)


def test_trainer_requires_lora_for_quantized_base(tmp_path):
    cfg = Config(
        model=CFG, lora=LoRAConfig(enabled=False),
        train=TrainConfig(quantize_frozen_base="int8"),
        checkpoint=CheckpointConfig(output_dir=str(tmp_path / "ckpt")),
    )
    from dlti_tpu.training.trainer import Trainer

    with pytest.raises(ValueError, match="requires LoRA"):
        Trainer(cfg).init_state()


@pytest.mark.slow
def test_trainer_int8_train_resume_export(tmp_path):
    """End to end through the Trainer: quantized base training runs,
    checkpoints, resumes, and exports a standard merged tree."""
    from dlti_tpu.checkpoint import export_merged_model, load_exported_model
    from dlti_tpu.data import ByteTokenizer, make_batches
    from dlti_tpu.training.trainer import Trainer

    cfg = Config(
        model=dataclasses.replace(CFG, vocab_size=258),
        lora=LORA,
        optimizer=OptimizerConfig(warmup_steps=2),
        parallel=ParallelConfig(zero_stage=ZeROStage.ZERO2, data=8),
        data=DataConfig(max_seq_len=32, tokenizer="byte"),
        checkpoint=CheckpointConfig(
            output_dir=str(tmp_path / "ckpt"), save_steps=2,
            save_total_limit=2, async_save=False),
        train=TrainConfig(num_epochs=1, micro_batch_size=8,
                          grad_accum_steps=1, max_steps=4,
                          logging_steps=100, quantize_frozen_base="int8",
                          metrics_csv=str(tmp_path / "metrics.csv")),
    )
    texts = [f"question {i}: the answer is {2 * i}." for i in range(200)]
    ds = make_batches(texts, ByteTokenizer(), seq_len=32,
                      micro_batch_size=8, shard_by_host=False)
    state, record = Trainer(cfg).train(dataset=ds)
    assert np.isfinite(record.final_loss)

    # Resume picks up the quantized tree from the checkpoint.
    cfg2 = cfg.replace(train=dataclasses.replace(cfg.train, max_steps=6))
    state2, _ = Trainer(cfg2).train(dataset=ds)
    assert int(state2.step) == 6

    out = export_merged_model(str(tmp_path / "export"), state2.params, cfg2)
    params, _ = load_exported_model(out)
    for path, v in jax.tree_util.tree_leaves_with_path(params):
        assert v.dtype != jnp.int8, path
