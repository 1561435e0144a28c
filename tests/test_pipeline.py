"""Pipeline parallelism: layout roundtrip, forward equivalence vs the
unpipelined model, and a pipelined train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.config import (
    Config, DataConfig, LoRAConfig, ModelConfig, OptimizerConfig,
    ParallelConfig, TrainConfig,
)
from dlti_tpu.models import LlamaForCausalLM
from dlti_tpu.parallel.mesh import build_mesh
from dlti_tpu.parallel.pipeline import (
    from_pipeline_params,
    make_pipeline_train_step,
    pipeline_forward,
    pipeline_param_shardings,
    to_pipeline_params,
)
from dlti_tpu.training import build_optimizer, create_train_state

# Heavy jit-compile tier: excluded from the fast pre-commit gate
# (`pytest -m 'not slow'`); the full suite runs them.
pytestmark = pytest.mark.slow

CFG = ModelConfig(
    vocab_size=128, hidden_size=32, intermediate_size=64, num_layers=4,
    num_heads=2, num_kv_heads=2, max_seq_len=32, remat=False,
    dtype="float32", param_dtype="float32", attention_impl="reference",
)


@pytest.fixture(scope="module")
def pipe_mesh():
    return build_mesh(ParallelConfig(pipe=4))


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(CFG, None)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def test_param_layout_roundtrip(model_and_params):
    _, params = model_and_params
    pp = to_pipeline_params(params, CFG.num_layers)
    assert pp["layers"]["attn"]["q_proj"]["kernel"].shape[0] == CFG.num_layers
    back = from_pipeline_params(pp, CFG.num_layers)
    a = jax.tree_util.tree_leaves_with_path(params)
    b = jax.tree_util.tree_leaves_with_path(back)
    assert [p for p, _ in a] == [p for p, _ in b]
    for (_, x), (_, y) in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_forward_matches_unpipelined(model_and_params, pipe_mesh):
    model, params = model_and_params
    ids = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, CFG.vocab_size)
    want, _ = model.apply({"params": params}, ids, deterministic=True)

    pp = to_pipeline_params(params, CFG.num_layers)
    sh = pipeline_param_shardings(pp, pipe_mesh)
    pp = jax.tree_util.tree_map(jax.device_put, pp, sh)
    got = pipeline_forward(pp, ids, CFG, pipe_mesh, num_microbatches=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_forward_microbatch_count_invariance(model_and_params, pipe_mesh):
    _, params = model_and_params
    ids = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, CFG.vocab_size)
    pp = to_pipeline_params(params, CFG.num_layers)
    a = pipeline_forward(pp, ids, CFG, pipe_mesh, num_microbatches=2)
    b = pipeline_forward(pp, ids, CFG, pipe_mesh, num_microbatches=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)


def test_rejects_bad_divisibility(model_and_params, pipe_mesh):
    _, params = model_and_params
    pp = to_pipeline_params(params, CFG.num_layers)
    ids = jnp.zeros((6, 8), jnp.int32)
    with pytest.raises(ValueError, match="divide"):
        pipeline_forward(pp, ids, CFG, pipe_mesh, num_microbatches=4)
    import dataclasses

    bad_cfg = dataclasses.replace(CFG, num_layers=3)
    with pytest.raises(ValueError, match="stages"):
        pipeline_forward(pp, jnp.zeros((4, 8), jnp.int32), bad_cfg, pipe_mesh)


def test_trainer_pipe_e2e_train_resume(tmp_path):
    """The production path: Trainer with
    parallel.pipe=2 trains, checkpoints the stacked layout, resumes, and
    evals — no direct make_pipeline_train_step calls."""
    from dlti_tpu.config import CheckpointConfig
    from dlti_tpu.data import ByteTokenizer, make_batches
    from dlti_tpu.training.trainer import Trainer

    cfg = Config(
        model=CFG,
        lora=LoRAConfig(r=2, alpha=4, dropout=0.0),
        optimizer=OptimizerConfig(warmup_steps=2),
        parallel=ParallelConfig(pipe=2),
        data=DataConfig(max_seq_len=32, tokenizer="byte"),
        checkpoint=CheckpointConfig(output_dir=str(tmp_path / "ckpt"),
                                    save_steps=2, save_total_limit=2,
                                    async_save=False),
        train=TrainConfig(num_epochs=1, micro_batch_size=4,
                          grad_accum_steps=2, max_steps=4,
                          logging_steps=100, eval_steps=4,
                          metrics_csv=str(tmp_path / "m.csv")),
    )
    texts = [f"sample {i} text {i * 7}" for i in range(160)]
    ds = make_batches(texts, ByteTokenizer(), seq_len=32, micro_batch_size=4,
                      grad_accum_steps=2, shard_by_host=False)
    state, record = Trainer(cfg).train(dataset=ds, eval_dataset=ds)
    assert np.isfinite(record.final_loss)
    assert np.isfinite(record.eval_loss)
    # Params really are in stacked pipeline layout.
    assert state.params["layers"]["attn"]["q_proj"]["kernel"].shape[0] == (
        CFG.num_layers)

    # Resume from the stacked checkpoint and take two more steps.
    cfg2 = cfg.replace(train=dataclasses_replace(cfg.train, max_steps=6))
    state2, _ = Trainer(cfg2).train(dataset=ds)
    assert int(state2.step) == 6


def dataclasses_replace(obj, **kw):
    import dataclasses

    return dataclasses.replace(obj, **kw)


def test_trainer_rejects_illegal_pipe_compositions():
    from dlti_tpu.config import ZeROStage
    from dlti_tpu.training.trainer import Trainer

    # SP composes with pipe, but not together with loss_chunk (the chunk
    # reshape regathers the sequence-sharded hidden — flat-path parity).
    bad = Config(
        model=CFG, lora=LoRAConfig(r=2, alpha=4),
        parallel=ParallelConfig(pipe=2, sequence=2),
        train=TrainConfig(loss_chunk=8),
    )
    with pytest.raises(ValueError, match="does not compose"):
        Trainer(bad)
    # fsdp axis without ZeRO-3 carries nothing — rejected loudly.
    bad2 = Config(
        model=CFG, lora=LoRAConfig(r=2, alpha=4),
        parallel=ParallelConfig(pipe=2, fsdp=2),
    )
    with pytest.raises(ValueError, match="does not compose"):
        Trainer(bad2)
    # Param offload needs LoRA (it offloads the frozen base; full
    # fine-tune has none) — rejected without it, legal with it.
    bad3 = Config(
        model=CFG, lora=LoRAConfig(enabled=False),
        parallel=ParallelConfig(pipe=2, data=2, offload_params=True),
    )
    with pytest.raises(ValueError, match="does not compose"):
        Trainer(bad3)


def test_pipeline_train_step_matches_single_device(pipe_mesh):
    """Loss and updated LoRA params from the pipelined step equal the plain
    single-device step on the same batch (GPipe == grad accumulation)."""
    from dlti_tpu.training.step import make_train_step

    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    model = LlamaForCausalLM(CFG, lora)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))
    state = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                               lora_enabled=True)
    batch_flat = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                        CFG.vocab_size),
        "loss_mask": jnp.ones((8, 16), jnp.int32),
    }

    # Reference: unpipelined step, accum dim of 1.
    ref_step = jax.jit(make_train_step(model, accum_steps=1))
    ref_batch = {k: v[None] for k, v in batch_flat.items()}
    rng = jax.random.PRNGKey(4)
    ref_state, ref_m = ref_step(state, ref_batch, rng)

    # Pipelined: same params in pipeline layout. Dropout is 0 so the rng
    # path difference does not matter.
    cfg = Config(model=CFG, lora=lora, optimizer=OptimizerConfig(warmup_steps=0),
                 parallel=ParallelConfig(pipe=4), data=DataConfig(max_seq_len=16),
                 train=TrainConfig(micro_batch_size=8, grad_accum_steps=1))
    from dlti_tpu.parallel.pipeline import to_pipeline_state

    pstate = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                                lora_enabled=True)
    pstate = to_pipeline_state(pstate, CFG.num_layers)
    pstep = make_pipeline_train_step(cfg, tx, pipe_mesh, num_microbatches=4)
    pstate, pm = pstep(pstate, batch_flat, rng)

    np.testing.assert_allclose(float(pm["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    back = from_pipeline_params(pstate.params, CFG.num_layers)
    got = np.asarray(back["model"]["layers_0"]["attn"]["q_proj"]["lora_b"])
    want = np.asarray(
        ref_state.params["model"]["layers_0"]["attn"]["q_proj"]["lora_b"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_pipeline_steps_per_sync_matches(tmp_path):
    """steps_per_sync composes with the GPipe Trainer path: a scanned
    2-step window reproduces the per-step pipelined trajectory."""
    from dlti_tpu.config import CheckpointConfig, MODEL_PRESETS
    from dlti_tpu.training.trainer import Trainer

    rng = jax.random.PRNGKey(0)

    def run(k):
        cfg = Config(
            model=MODEL_PRESETS["llama_tiny"],
            lora=LoRAConfig(r=2, alpha=4, dropout=0.0),
            optimizer=OptimizerConfig(warmup_steps=1),
            parallel=ParallelConfig(pipe=2),
            data=DataConfig(max_seq_len=16),
            train=TrainConfig(num_epochs=1, micro_batch_size=2,
                              grad_accum_steps=8, logging_steps=100,
                              steps_per_sync=k,
                              metrics_csv=str(tmp_path / f"mp{k}.csv")),
            checkpoint=CheckpointConfig(save_strategy="no"),
        )
        batches = [
            {"input_ids": np.asarray(jax.random.randint(
                jax.random.fold_in(rng, i), (8, 2, 16), 0,
                cfg.model.vocab_size)),
             "loss_mask": np.ones((8, 2, 16), np.int32)}
            for i in range(4)]
        t = Trainer(cfg)
        state, rec = t.train(batches_per_epoch=batches,
                             state=t.init_state(jax.random.fold_in(rng, 99)))
        return state, rec

    s1, r1 = run(1)
    s2, r2 = run(2)
    assert int(jax.device_get(s1.step)) == int(jax.device_get(s2.step)) == 4
    np.testing.assert_allclose(r1.final_loss, r2.final_loss, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                    jax.tree_util.tree_leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-5)


def _run_pipe_vs_single_device(par, extra_checks=None):
    """Shared harness for the PP-composition equivalence family: run the
    single-device reference step and the pipelined step on ``par``'s
    mesh with identical init/batch/rng, assert equal loss and updated
    LoRA params. ``extra_checks(sh, pstate)`` runs after placement (for
    spec and physical-shard assertions). Sharded optimizer state goes
    through the production ``opt_state_shardings`` whenever ``par`` has
    a ZeRO stage, so the composition exercises the real opt layout."""
    from dlti_tpu.parallel.pipeline import to_pipeline_state
    from dlti_tpu.parallel.sharding import opt_state_shardings
    from dlti_tpu.training.step import make_train_step

    mesh = build_mesh(par)
    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    model = LlamaForCausalLM(CFG, lora)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))
    state = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                               lora_enabled=True)
    batch_flat = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                        CFG.vocab_size),
        "loss_mask": jnp.ones((8, 16), jnp.int32),
    }
    ref_step = jax.jit(make_train_step(model, accum_steps=1))
    ref_batch = {k: v[None] for k, v in batch_flat.items()}
    rng = jax.random.PRNGKey(4)
    ref_state, ref_m = ref_step(state, ref_batch, rng)

    cfg = Config(model=CFG, lora=lora,
                 optimizer=OptimizerConfig(warmup_steps=0),
                 parallel=par,
                 data=DataConfig(max_seq_len=16),
                 train=TrainConfig(micro_batch_size=8, grad_accum_steps=1))
    pstate = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                                lora_enabled=True)
    pstate = to_pipeline_state(pstate, CFG.num_layers)
    sh = pipeline_param_shardings(pstate.params, mesh)
    replace = {"params": jax.tree_util.tree_map(
        jax.device_put, pstate.params, sh)}
    if int(par.zero_stage):
        replace["opt_state"] = jax.device_put(
            pstate.opt_state, opt_state_shardings(pstate.opt_state, cfg,
                                                  mesh))
    pstate = pstate.replace(**replace)
    if extra_checks is not None:
        extra_checks(sh, pstate)
    pstep = make_pipeline_train_step(cfg, tx, mesh, num_microbatches=4)
    pstate, pm = pstep(pstate, batch_flat, rng)

    np.testing.assert_allclose(float(pm["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    back = from_pipeline_params(pstate.params, CFG.num_layers)
    for layer in (0, CFG.num_layers - 1):
        got = np.asarray(
            back["model"][f"layers_{layer}"]["attn"]["q_proj"]["lora_b"])
        want = np.asarray(
            ref_state.params["model"][f"layers_{layer}"]["attn"]["q_proj"]["lora_b"])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def _assert_physically_sharded(leaf, spec, axis, factor=2):
    """The dim carrying ``axis`` in ``spec`` is really split ``factor``
    ways across the leaf's addressable shards."""
    d = spec.index(axis)
    assert all(s.data.shape[d] == leaf.shape[d] // factor
               for s in leaf.addressable_shards), (
        axis, [s.data.shape for s in leaf.addressable_shards])


def test_pipe_x_tensor_matches_single_device():
    """PP x TP: pipe=2 x tensor=2 — stage-internal tensor
    sharding over a ('pipe','tensor') mesh, 'tensor' riding GSPMD inside
    the pipeline's shard_map — reproduces the single-device step: same
    loss, same updated LoRA params."""
    def checks(sh, pstate):
        # TP placement really happened: a q_proj kernel leaf must be
        # sharded over 'tensor' on its out dim (dim 2 with the leading
        # layer dim), and physically split.
        q_spec = sh["layers"]["attn"]["q_proj"]["kernel"].spec
        assert q_spec == jax.sharding.PartitionSpec("pipe", None, "tensor"), \
            q_spec
        _assert_physically_sharded(
            pstate.params["layers"]["attn"]["q_proj"]["kernel"], q_spec,
            "tensor")

    _run_pipe_vs_single_device(ParallelConfig(pipe=2, tensor=2), checks)


def test_pipe_x_zero3_matches_single_device(monkeypatch):
    """PP x ZeRO-3: pipe=2 x fsdp=2 — stacked leaves
    shard over 'fsdp' on a non-layer dim, 'fsdp' riding GSPMD as an auto
    axis inside the pipe shard_map (per-tick all-gather at use,
    reduce-scatter grads) — reproduces the single-device step: same
    loss, same updated LoRA params. The fsdp placement is asserted real
    (the fsdp-sharded dim physically halved)."""
    import dlti_tpu.parallel.sharding as sh_mod
    from dlti_tpu.config import ZeROStage

    # llama_tiny-scale dims sit under the production FSDP size floor;
    # lower it so placement actually happens in this test.
    monkeypatch.setattr(sh_mod, "_MIN_FSDP_DIM", 8)

    def checks(sh, pstate):
        q_spec = sh["layers"]["attn"]["q_proj"]["kernel"].spec
        assert q_spec[0] == "pipe" and "fsdp" in q_spec, q_spec
        _assert_physically_sharded(
            pstate.params["layers"]["attn"]["q_proj"]["kernel"], q_spec,
            "fsdp")

    _run_pipe_vs_single_device(
        ParallelConfig(pipe=2, fsdp=2, zero_stage=ZeROStage.ZERO3), checks)


@pytest.mark.parametrize("family,overrides", [
    ("mistral", dict(sliding_window=6)),
    ("qwen2", dict(attention_bias=True)),
    ("gemma", dict(tie_embeddings=True, mlp_activation="gelu_tanh",
                   rmsnorm_offset=True, embedding_scale=True)),
])
def test_pipeline_forward_model_families(pipe_mesh, family, overrides):
    """Every family switch rides the pipelined stage body unchanged:
    Mistral's sliding window, Qwen2's qkv bias, Gemma's (1+w) RMSNorm +
    scaled/tied embeddings + gelu MLP — pipelined logits equal the
    unpipelined model's."""
    import dataclasses

    fam_cfg = dataclasses.replace(CFG, **overrides)
    model = LlamaForCausalLM(fam_cfg, None)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    ids = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                             fam_cfg.vocab_size)
    want, _ = model.apply({"params": params}, ids, deterministic=True)
    pp = to_pipeline_params(params, fam_cfg.num_layers)
    got = pipeline_forward(pp, ids, fam_cfg, pipe_mesh, num_microbatches=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5,
                               err_msg=f"{family} pipelined forward diverged")


def test_pipeline_flash_attention_matches_unpipelined(pipe_mesh):
    """The Pallas flash path runs INSIDE pipe stages (production config
    on chip: attention_impl auto -> flash): the kernels' out_shape now
    carries the enclosing shard_map's varying-manual-axes, without which
    tracing fails ("vma must not be None") — a latent chip bug for any
    PP run with flash. Interpret mode on CPU; logits equal the
    unpipelined flash model."""
    import dataclasses

    flash_cfg = dataclasses.replace(CFG, attention_impl="flash",
                                    flash_block_q=16, flash_block_kv=16)
    model = LlamaForCausalLM(flash_cfg, None)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 16), jnp.int32))["params"]
    ids = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 0,
                             flash_cfg.vocab_size)
    want, _ = model.apply({"params": params}, ids, deterministic=True)
    pp = to_pipeline_params(params, flash_cfg.num_layers)
    got = pipeline_forward(pp, ids, flash_cfg, pipe_mesh,
                           num_microbatches=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_packed_matches_unpipelined(pipe_mesh):
    """Packed batches under PP: segment ids and per-doc positions ride
    each microbatch through the stages, so the pipelined step reproduces
    the unpipelined packed step exactly."""
    from conftest import make_packed_segments
    from dlti_tpu.data.pipeline import packed_loss_mask, packed_positions
    from dlti_tpu.parallel.pipeline import to_pipeline_state
    from dlti_tpu.training.step import make_train_step

    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    model = LlamaForCausalLM(CFG, lora)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))
    state = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                               lora_enabled=True)
    segs = make_packed_segments(8, 16)
    batch_flat = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                        CFG.vocab_size),
        "segment_ids": segs,
        "positions": packed_positions(segs),
        "loss_mask": packed_loss_mask(segs),
    }
    ref_step = jax.jit(make_train_step(model, accum_steps=1))
    ref_batch = {k: v[None] for k, v in batch_flat.items()}
    rng = jax.random.PRNGKey(4)
    ref_state, ref_m = ref_step(state, ref_batch, rng)

    cfg = Config(model=CFG, lora=lora,
                 optimizer=OptimizerConfig(warmup_steps=0),
                 parallel=ParallelConfig(pipe=4),
                 data=DataConfig(max_seq_len=16),
                 train=TrainConfig(micro_batch_size=8, grad_accum_steps=1))
    pstate = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                                lora_enabled=True)
    pstate = to_pipeline_state(pstate, CFG.num_layers)
    pstep = make_pipeline_train_step(cfg, tx, pipe_mesh, num_microbatches=4)
    pstate, pm = pstep(pstate, batch_flat, rng)

    np.testing.assert_allclose(float(pm["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    back = from_pipeline_params(pstate.params, CFG.num_layers)
    got = np.asarray(back["model"]["layers_0"]["attn"]["q_proj"]["lora_b"])
    want = np.asarray(
        ref_state.params["model"]["layers_0"]["attn"]["q_proj"]["lora_b"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_pipeline_int8_frozen_base_matches_unpipelined(pipe_mesh, monkeypatch):
    """int8 frozen base under PP: the stage body dequantizes stacked
    {q, scale} leaves like the unpipelined block, and embed/head
    dequantize on the fly — the pipelined step reproduces the
    unpipelined int8 step."""
    import dlti_tpu.models.quantization as qmod
    from dlti_tpu.models.quantization import quantize_params_int8
    from dlti_tpu.parallel.pipeline import to_pipeline_state
    from dlti_tpu.training.step import make_train_step

    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    model = LlamaForCausalLM(CFG, lora)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))

    # llama_tiny block kernels (64x64) sit under the production size
    # floor; lower it so the scanned stage body sees stacked int8 leaves.
    monkeypatch.setattr(qmod, "_MIN_QUANT_SIZE", 1 << 6)

    def fresh_state():
        st = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                                lora_enabled=True)
        return st.replace(params=quantize_params_int8(st.params))

    batch_flat = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                        CFG.vocab_size),
        "loss_mask": jnp.ones((8, 16), jnp.int32),
    }
    state = fresh_state()
    # The stage body must see int8 leaves: assert a block kernel was
    # actually quantized (size floor lowered above).
    from dlti_tpu.models.quantization import is_quant_node
    assert is_quant_node(
        state.params["model"]["layers_0"]["attn"]["q_proj"]["kernel"])
    assert is_quant_node(state.params["model"]["embed_tokens"])
    ref_step = jax.jit(make_train_step(model, accum_steps=1))
    ref_batch = {k: v[None] for k, v in batch_flat.items()}
    rng = jax.random.PRNGKey(4)
    ref_state, ref_m = ref_step(state, ref_batch, rng)

    cfg = Config(model=CFG, lora=lora,
                 optimizer=OptimizerConfig(warmup_steps=0),
                 parallel=ParallelConfig(pipe=4),
                 data=DataConfig(max_seq_len=16),
                 train=TrainConfig(micro_batch_size=8, grad_accum_steps=1,
                                   quantize_frozen_base="int8"))
    pstate = to_pipeline_state(fresh_state(), CFG.num_layers)
    pstep = make_pipeline_train_step(cfg, tx, pipe_mesh, num_microbatches=4)
    pstate, pm = pstep(pstate, batch_flat, rng)

    np.testing.assert_allclose(float(pm["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    back = from_pipeline_params(pstate.params, CFG.num_layers)
    got = np.asarray(back["model"]["layers_0"]["attn"]["q_proj"]["lora_b"])
    want = np.asarray(
        ref_state.params["model"]["layers_0"]["attn"]["q_proj"]["lora_b"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_pipe_x_data_x_tensor_3d_matches_single_device():
    """Full 3D parallelism: pipe=2 x data=2 x tensor=2 over the 8-device
    mesh — GPipe stages manual over 'pipe', stage-internal TP and
    batch-row DP riding GSPMD as auto axes — reproduces the single-device
    step: same loss, same updated params."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlti_tpu.parallel.pipeline import to_pipeline_state
    from dlti_tpu.training.step import make_train_step

    mesh = build_mesh(ParallelConfig(pipe=2, data=2, tensor=2))
    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    model = LlamaForCausalLM(CFG, lora)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))
    state = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                               lora_enabled=True)
    batch_flat = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                        CFG.vocab_size),
        "loss_mask": jnp.ones((8, 16), jnp.int32),
    }
    ref_step = jax.jit(make_train_step(model, accum_steps=1))
    ref_batch = {k: v[None] for k, v in batch_flat.items()}
    rng = jax.random.PRNGKey(4)
    ref_state, ref_m = ref_step(state, ref_batch, rng)

    cfg = Config(model=CFG, lora=lora,
                 optimizer=OptimizerConfig(warmup_steps=0),
                 parallel=ParallelConfig(pipe=2, data=2, tensor=2),
                 data=DataConfig(max_seq_len=16),
                 train=TrainConfig(micro_batch_size=8, grad_accum_steps=1))
    pstate = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                                lora_enabled=True)
    pstate = to_pipeline_state(pstate, CFG.num_layers)
    sh = pipeline_param_shardings(pstate.params, mesh)
    pstate = pstate.replace(
        params=jax.tree_util.tree_map(jax.device_put, pstate.params, sh))
    sharded_batch = {
        k: jax.device_put(v, NamedSharding(mesh, P("data", None)))
        for k, v in batch_flat.items()}
    pstep = make_pipeline_train_step(cfg, tx, mesh, num_microbatches=4)
    pstate, pm = pstep(pstate, sharded_batch, rng)

    np.testing.assert_allclose(float(pm["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    back = from_pipeline_params(pstate.params, CFG.num_layers)
    for layer in (0, CFG.num_layers - 1):
        got = np.asarray(
            back["model"][f"layers_{layer}"]["attn"]["q_proj"]["lora_b"])
        want = np.asarray(
            ref_state.params["model"][f"layers_{layer}"]["attn"]["q_proj"]["lora_b"])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_pipeline_fp16_scaler_matches_flat_step(pipe_mesh):
    """fp16 dynamic loss scaling under PP: the pipelined step scales the
    loss, unscales grads, and evolves the scaler exactly like the flat
    step (same loss, same updated params, same scale metrics); a forced
    overflow skips the update and burns hysteresis identically."""
    import dataclasses

    from dlti_tpu.parallel.pipeline import to_pipeline_state
    from dlti_tpu.training.step import make_train_step

    cfg16 = dataclasses.replace(CFG)  # fp32 compute keeps parity exact
    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    model = LlamaForCausalLM(cfg16, lora)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))

    def fresh(scale):
        return create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                                  lora_enabled=True,
                                  fp16_initial_scale=scale)

    batch_flat = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                        cfg16.vocab_size),
        "loss_mask": jnp.ones((8, 16), jnp.int32),
    }
    rng = jax.random.PRNGKey(4)
    cfg = Config(model=cfg16, lora=lora,
                 optimizer=OptimizerConfig(warmup_steps=0),
                 parallel=ParallelConfig(pipe=4),
                 data=DataConfig(max_seq_len=16),
                 train=TrainConfig(micro_batch_size=8, grad_accum_steps=1,
                                   fp16=True))
    pstep = make_pipeline_train_step(cfg, tx, pipe_mesh, num_microbatches=4)

    # Normal step: parity with the flat fp16 step.
    ref_step = jax.jit(make_train_step(model, accum_steps=1))
    ref_state, ref_m = ref_step(fresh(2.0 ** 4),
                                {k: v[None] for k, v in batch_flat.items()},
                                rng)
    pstate = to_pipeline_state(fresh(2.0 ** 4), cfg16.num_layers)
    pstate, pm = pstep(pstate, batch_flat, rng)
    np.testing.assert_allclose(float(pm["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    assert float(pm["loss_scale"]) == float(ref_m["loss_scale"]) == 16.0
    assert float(pm["overflow"]) == 0.0
    back = from_pipeline_params(pstate.params, cfg16.num_layers)
    np.testing.assert_allclose(
        np.asarray(back["model"]["layers_0"]["attn"]["q_proj"]["lora_b"]),
        np.asarray(
            ref_state.params["model"]["layers_0"]["attn"]["q_proj"]["lora_b"]),
        rtol=1e-4, atol=1e-6)

    # Forced overflow (NaN-poisoned LoRA factor, the flat fp16 test's
    # trigger): update skipped, hysteresis burned, params unchanged.
    st2 = fresh(2.0 ** 8)
    params = st2.params
    params["model"]["layers_0"]["attn"]["q_proj"]["lora_a"] = (
        params["model"]["layers_0"]["attn"]["q_proj"]["lora_a"]
        .at[0, 0].set(jnp.nan))
    pstate2 = to_pipeline_state(st2.replace(params=params), cfg16.num_layers)
    before = np.asarray(jax.device_get(
        pstate2.params["layers"]["attn"]["q_proj"]["lora_b"]))
    pstate2, pm2 = pstep(pstate2, batch_flat, rng)
    assert float(pm2["overflow"]) == 1.0
    assert int(pstate2.scaler["hysteresis_left"]) == 1
    assert float(pstate2.scaler["scale"]) == 256.0  # hysteresis absorbed it
    after = np.asarray(jax.device_get(
        pstate2.params["layers"]["attn"]["q_proj"]["lora_b"]))
    np.testing.assert_array_equal(before, after)
    # Second overflow exhausts hysteresis -> the scale actually halves
    # (catches transposed scale_window/hysteresis plumbing at the
    # pipeline call site).
    pstate2, pm3 = pstep(pstate2, batch_flat, rng)
    assert float(pm3["overflow"]) == 1.0
    assert float(pstate2.scaler["scale"]) == 128.0


def test_pipeline_loss_chunk_matches_unchunked(pipe_mesh):
    """Sequence-chunked CE under PP: the pipelined chunked step (hidden
    states + per-chunk head) reproduces the pipelined full-logits step."""
    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    model = LlamaForCausalLM(CFG, lora)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))

    def fresh():
        from dlti_tpu.parallel.pipeline import to_pipeline_state

        st = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                                lora_enabled=True)
        return to_pipeline_state(st, CFG.num_layers)

    batch_flat = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                        CFG.vocab_size),
        "loss_mask": jnp.ones((8, 16), jnp.int32),
    }
    rng = jax.random.PRNGKey(4)

    def run(chunk):
        cfg = Config(model=CFG, lora=lora,
                     optimizer=OptimizerConfig(warmup_steps=0),
                     parallel=ParallelConfig(pipe=4),
                     data=DataConfig(max_seq_len=16),
                     train=TrainConfig(micro_batch_size=8,
                                       grad_accum_steps=1,
                                       loss_chunk=chunk))
        step = make_pipeline_train_step(cfg, tx, pipe_mesh,
                                        num_microbatches=4)
        return step(fresh(), batch_flat, rng)

    full_state, full_m = run(0)
    chunk_state, chunk_m = run(7)  # ragged chunk: exercises the padding

    np.testing.assert_allclose(float(chunk_m["loss"]), float(full_m["loss"]),
                               rtol=2e-6)
    a = jax.tree_util.tree_leaves(
        from_pipeline_params(chunk_state.params, CFG.num_layers))
    b = jax.tree_util.tree_leaves(
        from_pipeline_params(full_state.params, CFG.num_layers))
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-7)


def test_pipeline_zero1_shards_opt_state_same_losses(tmp_path):
    """ZeRO-1/2 x PP x DP: Adam moments shard over 'data' (ZeRO-2 adds
    the grad reduce-scatter pin) while the trajectory matches the
    replicated-optimizer pipe run exactly."""
    from dlti_tpu.config import CheckpointConfig, ZeROStage
    from dlti_tpu.data import ByteTokenizer, make_batches
    from dlti_tpu.training.trainer import Trainer

    def run(zero_stage, tag, offload=False, offload_p=False):
        cfg = Config(
            model=CFG,
            lora=LoRAConfig(r=2, alpha=4, dropout=0.0),
            optimizer=OptimizerConfig(warmup_steps=2),
            parallel=ParallelConfig(pipe=2, data=2, zero_stage=zero_stage,
                                    offload_optimizer=offload,
                                    offload_params=offload_p),
            data=DataConfig(max_seq_len=32, tokenizer="byte"),
            checkpoint=CheckpointConfig(output_dir=str(tmp_path / tag),
                                        save_strategy="no"),
            train=TrainConfig(num_epochs=1, micro_batch_size=4,
                              grad_accum_steps=2, max_steps=4,
                              logging_steps=100,
                              # Offload runs also exercise the PP eval
                              # path (host params must be shimmed
                              # HBM-ward before the eval shard_map).
                              eval_steps=2 if offload_p else 0,
                              metrics_csv=str(tmp_path / f"{tag}.csv")),
        )
        texts = [f"sample {i} text {i * 7}" for i in range(160)]
        ds = make_batches(texts, ByteTokenizer(), seq_len=32,
                          micro_batch_size=4, grad_accum_steps=2,
                          shard_by_host=False)
        trainer = Trainer(cfg)
        state = trainer.init_state()
        sharded = 0
        on_host = 0
        for leaf in jax.tree_util.tree_leaves(state.opt_state):
            if hasattr(leaf, "addressable_shards") and leaf.ndim >= 1:
                if any(s.data.shape != leaf.shape
                       for s in leaf.addressable_shards):
                    sharded += 1
                if getattr(leaf.sharding, "memory_kind", None) == \
                        "pinned_host":
                    on_host += 1
        p_host = sum(
            1 for leaf in jax.tree_util.tree_leaves(state.params)
            if getattr(leaf.sharding, "memory_kind", None) == "pinned_host")
        state, record = trainer.train(
            dataset=ds, eval_dataset=ds if offload_p else None)
        return sharded, on_host, p_host, record.final_loss

    sharded0, host0, phost0, loss0 = run(ZeROStage.NONE, "base")
    sharded1, host1, phost1, loss1 = run(ZeROStage.ZERO1, "zero1")
    sharded2, host2, phost2, loss2 = run(ZeROStage.ZERO2, "zero2")
    assert sharded0 == 0, "baseline pipe run must replicate opt state"
    assert sharded1 > 0, "ZeRO-1 x PP must shard optimizer moments"
    assert sharded2 > 0, "ZeRO-2 x PP must shard optimizer moments"
    assert host0 == host1 == host2 == 0
    assert phost0 == phost1 == phost2 == 0
    np.testing.assert_allclose(loss1, loss0, rtol=1e-6)
    np.testing.assert_allclose(loss2, loss0, rtol=1e-6)
    # PP x host offload (r05, boundary-transfer mode): optimizer moments
    # AND the frozen base REST in pinned host memory (asserted
    # SEPARATELY so neither placement can silently regress), cross at
    # step boundaries, trajectory unchanged — with the eval pass
    # exercising the one-transfer-per-pass shim.
    shardedo, hosto, phosto, losso = run(ZeROStage.ZERO1, "zero1_offload",
                                         offload=True, offload_p=True)
    assert shardedo > 0
    assert hosto > 0, "offload_optimizer x PP must place moments on host"
    assert phosto > 0, "offload_params x PP must place frozen base on host"
    np.testing.assert_allclose(losso, loss0, rtol=1e-6)


@pytest.mark.parametrize("policy", ["nothing_saveable", "dots_saveable",
                                    "dots_with_no_batch_dims_saveable",
                                    "save_attn_out"])
def test_pipeline_remat_policy_matches_no_remat(pipe_mesh, policy):
    """Named remat policies under PP (r05): the scanned stage body
    passes cfg.remat_policy through the flat path's policy table —
    numerics identical to the no-remat pipelined step (remat never
    changes values, only what the backward recomputes)."""
    import dataclasses

    from dlti_tpu.parallel.pipeline import to_pipeline_state

    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))
    batch_flat = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                        CFG.vocab_size),
        "loss_mask": jnp.ones((8, 16), jnp.int32),
    }
    rng = jax.random.PRNGKey(4)

    def run(mc):
        model = LlamaForCausalLM(mc, lora)
        state = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                                   lora_enabled=True)
        cfg = Config(model=mc, lora=lora,
                     optimizer=OptimizerConfig(warmup_steps=0),
                     parallel=ParallelConfig(pipe=4),
                     data=DataConfig(max_seq_len=16),
                     train=TrainConfig(micro_batch_size=8,
                                       grad_accum_steps=1))
        pstate = to_pipeline_state(state, mc.num_layers)
        pstep = make_pipeline_train_step(cfg, tx, pipe_mesh,
                                         num_microbatches=4)
        pstate, pm = pstep(pstate, batch_flat, rng)
        back = from_pipeline_params(pstate.params, mc.num_layers)
        return float(pm["loss"]), np.asarray(
            back["model"]["layers_0"]["attn"]["q_proj"]["lora_b"])

    base_loss, base_w = run(CFG)
    remat_loss, remat_w = run(
        dataclasses.replace(CFG, remat=True, remat_policy=policy))
    np.testing.assert_allclose(remat_loss, base_loss, rtol=1e-6)
    np.testing.assert_allclose(remat_w, base_w, rtol=1e-6, atol=1e-7)


def test_pipe_x_tensor_x_zero3_matches_single_device(monkeypatch):
    """The big three together — pipe=2 x tensor=2 x fsdp=2 (GPipe +
    stage-internal TP + ZeRO-3 param sharding, all 8 devices): stacked
    leaves carry P('pipe', 'fsdp', 'tensor'), BOTH inner axes physically
    split, optimizer state through the production ZeRO-3 layout, and the
    step reproduces the single-device step."""
    import dlti_tpu.parallel.sharding as sh_mod
    from dlti_tpu.config import ZeROStage

    monkeypatch.setattr(sh_mod, "_MIN_FSDP_DIM", 8)

    def checks(sh, pstate):
        q_spec = sh["layers"]["attn"]["q_proj"]["kernel"].spec
        assert (q_spec[0] == "pipe" and "tensor" in q_spec
                and "fsdp" in q_spec), q_spec
        leaf = pstate.params["layers"]["attn"]["q_proj"]["kernel"]
        _assert_physically_sharded(leaf, q_spec, "tensor")
        _assert_physically_sharded(leaf, q_spec, "fsdp")

    _run_pipe_vs_single_device(
        ParallelConfig(pipe=2, tensor=2, fsdp=2,
                       zero_stage=ZeROStage.ZERO3), checks)


def test_pipe_x_sequence_matches_single_device():
    """PP x SP (the last mesh axis): under the pipe shard_map, sequence
    parallelism delegates attention to GSPMD over the AUTO 'sequence'
    axis (all-gather-style SP; a nested manual ring either computes
    wrong gradients with check_vma=False or fails verification on this
    jax — see ring_attention's nested-delegation comment). Activations
    stay sequence-sharded via the batch pins; the pipelined train step
    reproduces the single-device step: same loss, same updated params.

    SGD, not Adam: partitioned-reduction grads differ from the flat step
    at epsilon scale, and Adam's first step (~ +/- lr * sign) amplifies
    that into sign flips on near-zero grads — a property of the
    optimizer, not an error. With SGD the param delta IS the grad
    (scaled), so the comparison is smooth."""
    import optax

    from dlti_tpu.parallel.pipeline import to_pipeline_state
    from dlti_tpu.training.step import make_train_step

    par = ParallelConfig(pipe=2, sequence=2)
    mesh = build_mesh(par)
    assert mesh.shape["pipe"] == 2 and mesh.shape["sequence"] == 2

    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    tx = optax.sgd(0.1)
    model = LlamaForCausalLM(CFG, lora)  # ref: plain attention, no mesh
    batch_flat = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                        CFG.vocab_size),
        "loss_mask": jnp.ones((8, 16), jnp.int32),
    }
    ref_batch = {k: v[None] for k, v in batch_flat.items()}
    rng = jax.random.PRNGKey(4)
    state = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                               lora_enabled=True)
    ref_step = jax.jit(make_train_step(model, accum_steps=1))
    ref_state, ref_m = ref_step(state, ref_batch, rng)

    cfg = Config(model=CFG, lora=lora,
                 optimizer=OptimizerConfig(warmup_steps=0),
                 parallel=par,
                 data=DataConfig(max_seq_len=16),
                 train=TrainConfig(micro_batch_size=8, grad_accum_steps=1))
    pstate = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                                lora_enabled=True)
    pstate = to_pipeline_state(pstate, CFG.num_layers)
    pstate = pstate.replace(params=jax.tree_util.tree_map(
        jax.device_put, pstate.params,
        pipeline_param_shardings(pstate.params, mesh)))
    pstep = make_pipeline_train_step(cfg, tx, mesh, num_microbatches=4)
    pstate, pm = pstep(pstate, batch_flat, rng)

    np.testing.assert_allclose(float(pm["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    back = from_pipeline_params(pstate.params, CFG.num_layers)
    for layer in (0, CFG.num_layers - 1):
        got = np.asarray(
            back["model"][f"layers_{layer}"]["attn"]["q_proj"]["lora_b"])
        want = np.asarray(
            ref_state.params["model"][f"layers_{layer}"]["attn"]["q_proj"]["lora_b"])
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_pipeline_remat_stride_matches_no_remat():
    """Selective remat under PP (r05): layers scan in groups of `stride`
    with every stride-th block keeping its activations — numerics equal
    the no-remat pipelined step (pipe=2 so layers_per_stage=2 divides
    stride=2)."""
    import dataclasses

    from dlti_tpu.parallel.pipeline import to_pipeline_state

    mesh = build_mesh(ParallelConfig(pipe=2))
    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))
    batch_flat = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0,
                                        CFG.vocab_size),
        "loss_mask": jnp.ones((8, 16), jnp.int32),
    }
    rng = jax.random.PRNGKey(4)

    def run(mc):
        model = LlamaForCausalLM(mc, lora)
        state = create_train_state(jax.random.PRNGKey(0), model, tx, (4, 16),
                                   lora_enabled=True)
        cfg = Config(model=mc, lora=lora,
                     optimizer=OptimizerConfig(warmup_steps=0),
                     parallel=ParallelConfig(pipe=2),
                     data=DataConfig(max_seq_len=16),
                     train=TrainConfig(micro_batch_size=8,
                                       grad_accum_steps=1))
        pstate = to_pipeline_state(state, mc.num_layers)
        pstep = make_pipeline_train_step(cfg, tx, mesh, num_microbatches=4)
        pstate, pm = pstep(pstate, batch_flat, rng)
        back = from_pipeline_params(pstate.params, mc.num_layers)
        return float(pm["loss"]), np.asarray(
            back["model"]["layers_0"]["attn"]["q_proj"]["lora_b"])

    base_loss, base_w = run(CFG)
    strided_loss, strided_w = run(dataclasses.replace(
        CFG, remat=True, remat_policy="dots_saveable", remat_stride=2))
    np.testing.assert_allclose(strided_loss, base_loss, rtol=1e-6)
    np.testing.assert_allclose(strided_w, base_w, rtol=1e-6, atol=1e-7)


def test_pipe_x_expert_matches_flat():
    """PP x EP: stacked MoE expert weights shard over 'expert' on the
    expert dim inside the pipe shard_map (dispatch all-to-all via GSPMD
    auto axes) — reproduces the flat grad-accumulation MoE step: same
    CE, same aux, same updated params. FULL fine-tune (no LoRA), so the
    expert-sharded w1/w2/w3 actually receive gradients and optimizer
    updates through the sharded path, and the UPDATED expert weights are
    compared. Physical expert placement asserted (expert dim halved
    across shards)."""
    import dataclasses

    from dlti_tpu.config import MODEL_PRESETS
    from dlti_tpu.parallel.pipeline import to_pipeline_state
    from dlti_tpu.training.step import make_train_step

    moe_cfg = dataclasses.replace(
        MODEL_PRESETS["mixtral_tiny"], num_layers=4, remat=False,
        dtype="float32", param_dtype="float32",
        attention_impl="reference", max_seq_len=32)
    model = LlamaForCausalLM(moe_cfg, None)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))

    def fresh():
        return create_train_state(jax.random.PRNGKey(0), model, tx, (2, 16),
                                  lora_enabled=False)

    batch = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (4, 2, 16),
                                        0, moe_cfg.vocab_size),
        "loss_mask": jnp.ones((4, 2, 16), jnp.int32),
    }
    rng = jax.random.PRNGKey(4)
    ref_step = jax.jit(make_train_step(model, accum_steps=4))
    ref_state, ref_m = ref_step(fresh(), batch, rng)

    par = ParallelConfig(pipe=2, expert=2)
    mesh = build_mesh(par)
    cfg = Config(model=moe_cfg, lora=LoRAConfig(enabled=False),
                 optimizer=OptimizerConfig(warmup_steps=0),
                 parallel=par,
                 data=DataConfig(max_seq_len=16),
                 train=TrainConfig(micro_batch_size=2, grad_accum_steps=4))
    pstate = to_pipeline_state(fresh(), moe_cfg.num_layers)
    sh = pipeline_param_shardings(pstate.params, mesh)
    w1_spec = sh["layers"]["mlp"]["w1"].spec
    assert w1_spec[0] == "pipe" and w1_spec[1] == "expert", w1_spec
    pstate = pstate.replace(
        params=jax.tree_util.tree_map(jax.device_put, pstate.params, sh))
    w1 = pstate.params["layers"]["mlp"]["w1"]
    assert all(s.data.shape[1] == w1.shape[1] // 2
               for s in w1.addressable_shards), (
        f"expert sharding not physically placed: "
        f"{[s.data.shape for s in w1.addressable_shards]}")
    pstep = make_pipeline_train_step(cfg, tx, mesh, num_microbatches=4)
    batch_flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in batch.items()}
    pstate, pm = pstep(pstate, batch_flat, rng)

    np.testing.assert_allclose(float(pm["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(pm["aux_loss"]), float(ref_m["aux_loss"]),
                               rtol=1e-5)
    back = from_pipeline_params(pstate.params, moe_cfg.num_layers)
    # The expert weights themselves must have been UPDATED identically
    # through the expert-sharded pipe path (full FT: they are trainable).
    for layer in (0, moe_cfg.num_layers - 1):
        got = np.asarray(back["model"][f"layers_{layer}"]["mlp"]["w1"])
        want = np.asarray(
            ref_state.params["model"][f"layers_{layer}"]["mlp"]["w1"])
        assert not np.allclose(
            want, np.asarray(
                fresh().params["model"][f"layers_{layer}"]["mlp"]["w1"])), \
            "flat step did not update expert weights (test is vacuous)"
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)


def test_pipeline_moe_matches_flat_grad_accum():
    """MoE under PP: the pipelined step's aux-loss collection (per-layer
    sown losses, edge-tick masked, psum over pipe) reproduces the flat
    grad-accumulation step with identical microbatching — same CE, same
    aux, same updated params."""
    import dataclasses

    from dlti_tpu.config import MODEL_PRESETS
    from dlti_tpu.parallel.pipeline import to_pipeline_state
    from dlti_tpu.training.step import make_train_step

    moe_cfg = dataclasses.replace(
        MODEL_PRESETS["mixtral_tiny"], num_layers=4, remat=False,
        dtype="float32", param_dtype="float32",
        attention_impl="reference", max_seq_len=32)
    lora = LoRAConfig(r=2, alpha=4, dropout=0.0)
    model = LlamaForCausalLM(moe_cfg, lora)
    tx = build_optimizer(OptimizerConfig(warmup_steps=0))

    def fresh():
        return create_train_state(jax.random.PRNGKey(0), model, tx, (2, 16),
                                  lora_enabled=True)

    # (accum=4, mb=2, seq=16): the flat step's microbatches == the
    # pipeline's microbatches, so even capacity DROPS match exactly.
    batch = {
        "input_ids": jax.random.randint(jax.random.PRNGKey(3), (4, 2, 16),
                                        0, moe_cfg.vocab_size),
        "loss_mask": jnp.ones((4, 2, 16), jnp.int32),
    }
    rng = jax.random.PRNGKey(4)
    ref_step = jax.jit(make_train_step(model, accum_steps=4))
    ref_state, ref_m = ref_step(fresh(), batch, rng)
    assert "aux_loss" in ref_m

    cfg = Config(model=moe_cfg, lora=lora,
                 optimizer=OptimizerConfig(warmup_steps=0),
                 parallel=ParallelConfig(pipe=4),
                 data=DataConfig(max_seq_len=16),
                 train=TrainConfig(micro_batch_size=2, grad_accum_steps=4))
    mesh = build_mesh(ParallelConfig(pipe=4))
    pstate = to_pipeline_state(fresh(), moe_cfg.num_layers)
    pstep = make_pipeline_train_step(cfg, tx, mesh, num_microbatches=4)
    batch_flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in batch.items()}
    pstate, pm = pstep(pstate, batch_flat, rng)

    np.testing.assert_allclose(float(pm["loss"]), float(ref_m["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(pm["aux_loss"]), float(ref_m["aux_loss"]),
                               rtol=1e-5)
    back = from_pipeline_params(pstate.params, moe_cfg.num_layers)
    got = np.asarray(back["model"]["layers_0"]["attn"]["q_proj"]["lora_b"])
    want = np.asarray(
        ref_state.params["model"]["layers_0"]["attn"]["q_proj"]["lora_b"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)
