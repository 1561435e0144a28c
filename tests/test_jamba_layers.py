"""The jamba family (AI21-Jamba2: Mamba-1 layers with inner norms, plain
attention with one key-value head and no positions, dense MLPs) against its
plain reference, at tiny sizes on the CPU in float32 with seeded weights:
trained over packed rows with LoRA, and served through the cache.

Both sides take their sizes from the benchmark's configuration file laid
over with the cell's rehearsal stand-ins, as the harness does: the program
through ``chip_child.model_fields`` -> ``ModelConfig``, the reference
through its own ``sizes(config)``. The stand-in has four layers,
``attn_layer_period`` 4 and ``attn_layer_offset`` 2 (``SSAS``).
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

import check  # noqa: E402
import spec as spec_lib  # noqa: E402
from chip_child import model_fields  # noqa: E402

from dlti_tpu.config import (  # noqa: E402
    CheckpointConfig, Config, DataConfig, LoRAConfig, MODEL_PRESETS,
    ModelConfig, OptimizerConfig, TelemetryConfig, TrainConfig,
)
from dlti_tpu.data import ByteTokenizer, make_batches  # noqa: E402
from dlti_tpu.models import build_model, mamba1  # noqa: E402
from dlti_tpu.models.jamba import JambaForCausalLM  # noqa: E402
from dlti_tpu.serving.engine import EngineConfig, InferenceEngine  # noqa: E402
from dlti_tpu.serving.sampling import SamplingParams  # noqa: E402

CELL = "train.jamba2_3b.long_doc_sft"
LORA = LoRAConfig(enabled=True, r=4, alpha=8, dropout=0.0)


def tiny_config() -> dict:
    """The configuration file as a rehearsal runs it (tiny stand-ins)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2_3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "cells", CELL + ".json")) as f:
        rehearsal = json.load(f)["rehearsal"]
    config["model"] = {**config["model"], **rehearsal["model_overrides"]}
    config["program"] = {**config["program"],
                         **rehearsal["program_overrides"]}
    return config


def _seeded_b(params, key):
    """LoRA's B away from zero, or A's gradient is zero."""
    def perturb(path, v):
        if getattr(path[-1], "key", None) != "lora_b":
            return v
        k = jax.random.fold_in(key, hash(str(path)) % (2 ** 31))
        return 0.05 * jax.random.normal(k, v.shape, v.dtype)
    return jax.tree_util.tree_map_with_path(perturb, params)


@pytest.fixture(scope="module")
def tiny():
    config = tiny_config()
    cfg = ModelConfig(**model_fields(config))
    model = build_model(cfg, LORA)
    key = jax.random.PRNGKey(0)
    params = _seeded_b(model.init(key, jnp.zeros((1, 8), jnp.int32))[
        "params"], key)
    reference = spec_lib.load_reference(config, "train")
    return {"config": config, "cfg": cfg, "model": model, "params": params,
            "reference": reference, "sizes": reference.sizes(config)}


def _packed(lengths, seq, seed=0, vocab=512):
    """One packed row: documents of ``lengths`` back to back, then padding."""
    rng = np.random.default_rng(seed)
    ids, seg, pos = (np.zeros((1, seq), np.int32) for _ in range(3))
    at = 0
    for d, n in enumerate(lengths, 1):
        ids[0, at:at + n] = rng.integers(3, vocab, n)
        seg[0, at:at + n] = d
        pos[0, at:at + n] = np.arange(n)
        at += n
    return {"input_ids": jnp.asarray(ids), "segment_ids": jnp.asarray(seg),
            "positions": jnp.asarray(pos),
            "loss_mask": jnp.asarray((seg > 0).astype(np.int32))}


def _cosine(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    dot = sum(float((x * y).sum()) for x, y in zip(la, lb))
    return dot / (sum(float((x * x).sum()) for x in la)
                  * sum(float((y * y).sum()) for y in lb)) ** 0.5


# -- the family, its file and its count ---------------------------------------

def test_factory_picks_the_family_and_the_file_states_the_order(tiny):
    assert isinstance(tiny["model"], JambaForCausalLM)
    assert tiny["cfg"].layer_pattern == "SSAS"
    assert tiny["sizes"]["kinds"] == ["mamba", "mamba", "attention", "mamba"]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2_3b.json")) as f:
        config = json.load(f)
    cfg = ModelConfig(**model_fields(config))
    assert cfg.is_jamba and not cfg.is_sambay
    kinds = spec_lib.load_reference(config, "train").sizes(config)["kinds"]
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert cfg.layer_pattern == "".join(
        "A" if k == "attention" else "S" for k in kinds)
    assert cfg.num_params() == 3_029_337_472
    assert cfg.resolved_head_dim == 128 and cfg.mamba_inner_size == 5120


def test_param_count_of_the_family_is_the_tree(tiny):
    leaves = jax.tree_util.tree_flatten_with_path(tiny["params"])[0]
    base = sum(v.size for p, v in leaves if not check.is_lora(p))
    assert base == tiny["cfg"].num_params()
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import memory_plan

    adapters = sum(v.size for p, v in leaves if check.is_lora(p))
    assert adapters == memory_plan.lora_trainable_params(tiny["cfg"], r=4)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2_3b.json")) as f:
        cfg = ModelConfig(**model_fields(json.load(f)))
    assert memory_plan.lora_trainable_params(cfg, r=16) == 11_229_184
    # the two attention layers alone keep a pool
    assert memory_plan.kv_bytes_per_token(cfg) == 2 * 2 * 1 * 128 * 2
    assert memory_plan.recurrent_state_bytes_per_slot(cfg) == 26 * (
        3 * 5120 * 2 + 5120 * 16 * 4)


PATTERN_REFUSED = {
    "mixed_with_sambay": dict(layer_pattern="SDAS"),
    "rotation": dict(rope=True),
    "a_window": dict(sliding_window=16),
}


@pytest.mark.parametrize("name", sorted(PATTERN_REFUSED))
def test_pattern_is_checked(name):
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(MODEL_PRESETS["jamba_tiny"],
                            **PATTERN_REFUSED[name])


def test_other_families_adapters_are_the_lora_configs():
    """``lora_targets`` empty: the tree every other family had."""
    cfg = MODEL_PRESETS["llama_tiny"]
    assert cfg.lora_targets == ()
    params = build_model(cfg, LORA).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    names = {str(getattr(p[-2], "key", "")) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]
             if check.is_lora(p)}
    assert names == {"q_proj", "k_proj", "v_proj", "o_proj"}


# -- the mixer and the whole model against the reference ------------------------

def test_mixer_with_inner_norms_agrees_with_the_reference(tiny):
    cfg = tiny["cfg"]
    mixer = mamba1.Mamba1Mixer(cfg, LORA)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 37, cfg.hidden_size))
    pos = jnp.arange(37)[None]
    p = _seeded_b(mixer.init(jax.random.PRNGKey(2), x, pos)["params"],
                  jax.random.PRNGKey(3))
    assert {"dt_layernorm", "b_layernorm", "c_layernorm"} <= set(p)
    out, _, _ = mixer.apply({"params": p}, x, pos)
    want = tiny["reference"].mamba1(p, tiny["sizes"], x[0], LORA.scaling)
    np.testing.assert_allclose(out[0], want, atol=2e-5)
    # the norms' weights are seeded away from 1: without them it differs
    ones = {**p, **{k: {"scale": jnp.ones_like(p[k]["scale"])}
                    for k in ("dt_layernorm", "b_layernorm", "c_layernorm")}}
    other, _, _ = mixer.apply({"params": ones}, x, pos)
    assert float(jnp.abs(other - out).max()) > 1e-3


def test_forward_agrees_with_the_reference(tiny):
    ids = _packed([41], 41, seed=4)["input_ids"]
    logits, _ = tiny["model"].apply({"params": tiny["params"]}, ids)
    want = tiny["reference"].forward(tiny["params"], tiny["sizes"], ids[0],
                                     LORA.scaling)
    np.testing.assert_allclose(logits[0], want, atol=2e-4)


DOCUMENTS = {
    # SCAN_CHUNK is 128: starts inside chunks, on a chunk's edge, a row that
    # is whole chunks and one that is not, with and without padding
    "inside_chunks": ([50, 100, 90], 256),
    "on_an_edge": ([128, 60, 68], 256),
    "not_whole_chunks": ([33, 70, 45], 150),
    "padded": ([20, 61], 100),
}


@pytest.mark.parametrize("name", sorted(DOCUMENTS))
def test_a_packed_row_equals_its_documents_run_alone(tiny, name):
    """The check's comparison at float32: loss within 1e-3, per-token
    log-probs, adapter-gradient cosine at least 0.999 against the reference,
    which runs every document by itself."""
    lengths, seq = DOCUMENTS[name]
    batch = _packed(lengths, seq, seed=len(name))
    loss, grads, picked = tiny["reference"].grad(
        tiny["params"], tiny["sizes"], batch, LORA.scaling, check.is_lora)
    p_loss, p_picked, p_grads = check.program_side(
        tiny["model"], tiny["params"], batch)
    assert abs(p_loss - loss) < 1e-3
    w = np.asarray(batch["loss_mask"])[:, 1:]
    np.testing.assert_allclose(np.asarray(p_picked) * w,
                               np.asarray(picked) * w, atol=2e-4)
    assert _cosine(p_grads, grads) >= 0.999
    # and against the program itself, a document at a time
    at = 0
    row, _ = tiny["model"].apply(
        {"params": tiny["params"]}, batch["input_ids"],
        positions=batch["positions"], segment_ids=batch["segment_ids"])
    for n in lengths:
        alone, _ = tiny["model"].apply(
            {"params": tiny["params"]}, batch["input_ids"][:, at:at + n])
        np.testing.assert_allclose(row[:, at:at + n], alone, atol=2e-4)
        at += n


def test_a_dropped_reset_fails_the_comparison(tiny):
    """A program that lets a document read its neighbour's state (the
    segments hidden from the Mamba layers) is not the stated one."""
    batch = _packed([50, 100, 90], 256, seed=1)
    stated, _ = tiny["model"].apply(
        {"params": tiny["params"]}, batch["input_ids"],
        positions=batch["positions"], segment_ids=batch["segment_ids"])
    carried, _ = tiny["model"].apply(
        {"params": tiny["params"]}, batch["input_ids"],
        positions=batch["positions"])
    lp = jax.nn.log_softmax(stated, -1) - jax.nn.log_softmax(carried, -1)
    assert float(jnp.abs(lp[:, :50]).max()) < 1e-4     # the first document
    assert float(jnp.abs(lp[:, 50:240]).max()) > 1e-2  # the others


# -- the chunked scan's own backward pass ---------------------------------------

def _token_a_trip(u, dt, a, b_in, c_in, keep):
    def step(s, x):
        u_t, dt_t, b_t, c_t, keep_t = x
        s = jnp.exp(dt_t[..., None] * a) * keep_t[:, None, None] * s \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], -1)

    s0 = jnp.zeros((u.shape[0], u.shape[2], a.shape[1]))
    _, y = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (u, dt, b_in, c_in, keep)))
    return jnp.moveaxis(y, 0, 1)


SCAN_CASES = {
    # (length, document starts); SCAN_CHUNK is 128
    "divides_start_inside": (256, [70, 200]),
    "divides_start_on_edge": (256, [128]),
    "does_not_divide": (300, [131, 256]),
    "shorter_than_a_chunk": (19, [4]),
    "no_start": (130, []),
}


@pytest.mark.parametrize("name", sorted(SCAN_CASES))
def test_chunked_scan_and_its_vjp_equal_the_token_a_trip_scan(name):
    length, starts = SCAN_CASES[name]
    k = jax.random.split(jax.random.PRNGKey(length), 6)
    rows, d, n = 2, 6, 4
    u = jax.random.normal(k[0], (rows, length, d))
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, length, d)) - 1.0)
    a = -jnp.exp(jax.random.normal(k[2], (d, n)))
    b_in = jax.random.normal(k[3], (rows, length, n))
    c_in = jax.random.normal(k[4], (rows, length, n))
    w = jax.random.normal(k[5], (rows, length, d))
    keep = jnp.ones((rows, length)).at[0, jnp.asarray(starts, int)].set(0.0)
    args = (u, dt, a, b_in, c_in)
    np.testing.assert_allclose(
        mamba1.chunked_selective_scan(*args, keep),
        _token_a_trip(*args, keep), atol=1e-5)
    ours = jax.grad(lambda *xs: jnp.sum(
        mamba1.chunked_selective_scan(*xs, keep) * w), range(5))(*args)
    want = jax.grad(lambda *xs: jnp.sum(
        _token_a_trip(*xs, keep) * w), range(5))(*args)
    for got, ref in zip(ours, want):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_scan_kernels_interpreted_equal_the_xla_chunks(monkeypatch):
    """The TPU's kernels (``ops.pallas.selective_scan``), interpreted: the
    same outputs, kept states and gradients as the XLA loops, with a
    document start inside a chunk and one on a chunk's edge. (Chunks of 16
    tokens here: a kernel call is unrolled over its chunk, and interpreting
    128 tokens takes minutes.)"""
    from dlti_tpu.ops.pallas import selective_scan as scan

    monkeypatch.setattr(scan, "CHUNK", 16)
    monkeypatch.setattr(mamba1, "SCAN_CHUNK", 16)
    rows, length, d, n = 2, 48, 512, 16
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    u = jax.random.normal(k[0], (rows, length, d))
    dt = jax.nn.softplus(jax.random.normal(k[1], (rows, length, d)) - 1.0)
    a = -jnp.exp(jax.random.normal(k[2], (n, d)))
    b_in = jax.random.normal(k[3], (rows, length, n))
    c_in = jax.random.normal(k[4], (rows, length, n))
    dy = jax.random.normal(k[5], (rows, length, d))
    keep = jnp.ones((rows, length)).at[0, 21].set(0.0).at[1, 32].set(0.0)
    y, kept = scan.selective_scan_fwd(u, dt, a, b_in, c_in, keep,
                                      interpret=True)
    want_y, want_kept = mamba1._chunks_forward(u, dt, a, b_in, c_in, keep)
    np.testing.assert_allclose(y, want_y, atol=1e-4)
    np.testing.assert_allclose(kept, jnp.moveaxis(want_kept, 0, 1),
                               atol=1e-5)
    got = scan.selective_scan_bwd(u, dt, a, b_in, c_in, keep, kept, dy,
                                  interpret=True)
    du, ddt, da, db, dc, _ = mamba1._chunked_bwd(
        (u, dt, a.T, b_in, c_in, keep, want_kept), dy)
    for ours, ref in zip(got, (du, ddt, da.T, db, dc)):
        np.testing.assert_allclose(ours, ref, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(ref).max()))


def test_scan_keeps_a_state_a_chunk_not_a_token():
    """What the backward pass is handed: the inputs and a state every
    ``SCAN_CHUNK`` tokens."""
    rows, length, d, n = 1, 300, 6, 4
    z = jnp.zeros
    _, saved = mamba1._chunked_fwd(
        z((rows, length, d)), z((rows, length, d)), -jnp.ones((d, n)),
        z((rows, length, n)), z((rows, length, n)), jnp.ones((rows, length)))
    assert saved[-1].shape == (-(-length // mamba1.SCAN_CHUNK), rows, n, d)


def test_padding_advances_nothing(tiny):
    """Trailing padding (segment 0) changes nothing before it, in the
    logits and in the adapters' gradients."""
    short = _packed([40, 25], 65, seed=5)
    long = _packed([40, 25], 140, seed=5)
    assert (long["input_ids"][:, :65] == short["input_ids"]).all()

    def run(batch):
        return check.program_side(tiny["model"], tiny["params"], batch)

    (l1, p1, g1), (l2, p2, g2) = run(short), run(long)
    assert abs(l1 - l2) < 1e-5
    np.testing.assert_allclose(p1[:, :64], p2[:, :64], atol=1e-5)
    assert _cosine(g1, g2) > 0.99999


# -- the trainer ------------------------------------------------------------------

def test_trainer_and_check_build_the_same_adapter_leaves(tiny):
    """``scripts/train.py`` and ``benchmark/lib/check.py`` both build
    ``LoRAConfig(enabled, r, alpha)`` with the default targets; the
    family's targets reach both through the configuration's ``program``."""
    from dlti_tpu.training.trainer import Trainer

    config = tiny["config"]
    _, check_params, _ = check.train_inputs(
        config, {"lora_r": 4, "seed": 1, "rows": 1, "seq_len": 16,
                 "doc_median": 8})
    trainer = Trainer(Config(
        model=ModelConfig(**model_fields(config)),
        lora=LoRAConfig(enabled=True, r=4, alpha=8)))
    theirs = trainer.model.init(jax.random.PRNGKey(0),
                                jnp.zeros((1, 8), jnp.int32))["params"]

    def adapters(params):
        return {"/".join(str(getattr(k, "key", k)) for k in p): v.shape
                for p, v in jax.tree_util.tree_flatten_with_path(params)[0]
                if check.is_lora(p)}

    assert adapters(check_params) == adapters(theirs)
    per_layer = {"S": {"in_proj", "x_proj", "out_proj"},
                 "A": {"q_proj", "k_proj", "v_proj", "o_proj"}}
    for i, kind in enumerate("SSAS"):
        found = {name.split("/")[2] for name in adapters(theirs)
                 if name.startswith(f"layers_{i}/mixer/")}
        assert found == per_layer[kind]


def test_two_optimizer_steps_through_the_trainer_lower_the_loss(tmp_path):
    from dlti_tpu.training.trainer import Trainer

    steps = str(tmp_path / "steps.jsonl")
    cfg = Config(
        model=MODEL_PRESETS["jamba_tiny"],
        lora=LoRAConfig(r=4, alpha=8, dropout=0.05),
        optimizer=OptimizerConfig(warmup_steps=1, learning_rate=1e-2),
        data=DataConfig(max_seq_len=64, tokenizer="byte",
                        pack_sequences=True),
        checkpoint=CheckpointConfig(output_dir=str(tmp_path / "ckpt"),
                                    save_strategy="no"),
        train=TrainConfig(num_epochs=1, micro_batch_size=2,
                          grad_accum_steps=2, logging_steps=100, max_steps=3,
                          loss_chunk=16,
                          metrics_csv=str(tmp_path / "metrics.csv")),
        telemetry=TelemetryConfig(step_log_path=steps))
    texts = [f"doc {i} " + "abc " * (i % 9) for i in range(200)]
    dataset = make_batches(texts, ByteTokenizer(), seq_len=64,
                           micro_batch_size=2, grad_accum_steps=2,
                           shard_by_host=False, pack=True)
    trainer = Trainer(cfg)
    trainer.train(dataset=dataset)
    with open(steps) as f:
        rows = [r for r in map(json.loads, f) if r.get("type") == "step"]
    assert len(rows) == 3 and rows[2]["loss"] < rows[0]["loss"]
    # the counter: documents that started in the step's four rows
    batch = next(dataset.epoch(0))
    seg = np.asarray(batch["segment_ids"]).reshape(-1, 64)
    before = np.pad(seg, ((0, 0), (1, 0)))[:, :-1]
    assert rows[0]["recurrent_state_resets"] == int(
        ((seg != before) & (seg != 0)).sum()) > 4
    assert trainer._live["train_recurrent_state_resets"] \
        == rows[-1]["recurrent_state_resets"]
    assert "state-space" in trainer.remat_plan.why_not


def test_a_kinds_block_is_traced_once_and_rematerialised(tiny):
    """Four layers, two traces; without a cache each is under
    ``jax.checkpoint``."""
    batch = _packed([10, 6], 16)
    jaxpr = jax.make_jaxpr(lambda p: tiny["model"].apply(
        {"params": p}, batch["input_ids"],
        segment_ids=batch["segment_ids"])[0])(tiny["params"])
    blocks = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in (
        "pjit", "jit") and e.params["name"] == "apply"]
    assert len(blocks) == 4
    assert len({id(e.params["jaxpr"]) for e in blocks}) == 2
    assert all(any(q.primitive.name in ("remat", "checkpoint", "remat2")
                   for q in e.params["jaxpr"].jaxpr.eqns) for e in blocks)


def test_exported_adapter_names_the_state_space_projections(tiny, tmp_path):
    from safetensors import safe_open

    from dlti_tpu.models import load_peft_adapter, save_peft_adapter

    save_peft_adapter(str(tmp_path), tiny["params"], LORA)
    with safe_open(str(tmp_path / "adapter_model.safetensors"),
                   framework="flax") as f:
        keys = set(f.keys())
    assert "base_model.model.model.layers.0.mamba.in_proj.lora_A.weight" \
        in keys
    assert "base_model.model.model.layers.2.self_attn.q_proj.lora_B.weight" \
        in keys
    assert {k.split(".")[5] for k in keys} == {"mamba", "self_attn"}
    with open(tmp_path / "adapter_config.json") as f:
        assert json.load(f)["target_modules"] == [
            "q_proj", "k_proj", "v_proj", "o_proj",
            "in_proj", "out_proj", "x_proj"]
    zeroed = jax.tree_util.tree_map_with_path(
        lambda p, v: jnp.zeros_like(v) if check.is_lora(p) else v,
        tiny["params"])
    back = load_peft_adapter(str(tmp_path), jax.tree_util.tree_map(
        np.asarray, zeroed))
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(tiny["params"])):
        np.testing.assert_array_equal(a, b)


# -- serving: prefill, then decode through the cache ----------------------------

@pytest.fixture(scope="module")
def served(tiny):
    """The model as ``serve.py --random-init`` builds it: no adapters."""
    model = build_model(tiny["cfg"])
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    logprobs = jax.jit(lambda p, ids: jax.nn.log_softmax(
        tiny["reference"].forward(p, tiny["sizes"], ids), -1))
    return {"cfg": tiny["cfg"], "params": params, "logprobs": logprobs}


def _engine(served, **over):
    kw = dict(max_seqs=4, block_size=8, num_blocks=64, max_model_len=128,
              cache_dtype="float32")
    kw.update(over)
    return InferenceEngine(served["cfg"], served["params"],
                           EngineConfig(**kw))


SCENARIOS = {
    "lone": dict(lengths=[11], engine={}),
    "into_a_full_batch": dict(lengths=[45, 7, 21, 70, 33], engine={}),
    "chunked_prefill": dict(
        lengths=[45, 23], engine=dict(max_prefill_tokens_per_step=16)),
    "decode_kernel": dict(lengths=[37, 9], engine={}, kernel=True),
}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_engine_prefill_then_decode_agrees_with_full_forward(served, name):
    case = SCENARIOS[name]
    if case.get("kernel"):
        served = {**served, "cfg": dataclasses.replace(
            served["cfg"], paged_attention_impl="kernel")}
    eng = _engine(served, **case["engine"])
    rng = np.random.default_rng(len(name))
    prompts = [[int(t) for t in rng.integers(3, 512, n)]
               for n in case["lengths"]]
    results = eng.generate(prompts,
                           SamplingParams(max_tokens=9, temperature=0.0))
    for prompt, res in zip(prompts, results):
        tokens = res.output_token_ids
        lp = served["logprobs"](served["params"],
                                jnp.asarray(prompt + tokens))
        rows = np.asarray(lp[len(prompt) - 1:len(prompt) - 1 + len(tokens)])
        np.testing.assert_allclose(
            res.output_logprobs, rows[np.arange(len(tokens)), tokens],
            atol=2e-4)
    assert eng.stats["recurrent_state_resets"] == len(prompts)
    assert eng.stats["recurrent_prefill_tokens"] == sum(case["lengths"])
    # the cache: a state a Mamba layer by slot, a pool an attention layer
    kinds = ["ssm" in e for e in eng.executor.cache]
    assert kinds == [True, True, False, True]
    # ... fused: a token's row is the one key-value head's 16 values
    assert eng.executor.cache[2]["k"].shape == (64, 8, 16)


REFUSED = {
    "prefix_caching": (dict(enable_prefix_caching=True), "prefix caching"),
    "speculative": (dict(speculative="ngram"), "speculative"),
    "int8_weights": (dict(quantization="int8"), "int8"),
    "int8_cache": (dict(cache_dtype="int8"), "int8 scale"),
    "adapter_pool": (dict(adapter_slots=2), "adapter"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_engine_refuses_at_start_up(served, name):
    over, said = REFUSED[name]
    with pytest.raises(ValueError, match=said) as e:
        _engine(served, **over)
    assert "inner norms" in str(e.value)
