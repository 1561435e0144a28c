"""Pallas flash attention vs XLA reference (interpret mode on CPU)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_packed_segments
from dlti_tpu.ops.attention import reference_attention
from dlti_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(rng, b=2, s=256, h=4, hkv=4, d=64):
    q = jax.random.normal(rng, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, s, hkv, d))
    return q, k, v


@pytest.mark.parametrize("block_q,block_kv", [
    (128, 128),
    (64, 128),   # block_kv > block_q: rows with fully-masked blocks
    (128, 64),
    (256, 256),  # single block
])
def test_flash_matches_reference(rng, block_q, block_kv):
    q, k, v = _qkv(rng)
    out_ref = reference_attention(q, k, v, causal=True)
    out_fa = flash_attention(q, k, v, causal=True, block_q=block_q,
                             block_kv=block_kv, interpret=True)
    np.testing.assert_allclose(np.asarray(out_fa), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-3)


def test_flash_gqa(rng):
    q, k, v = _qkv(rng, h=8, hkv=2)
    out_ref = reference_attention(q, k, v, causal=True)
    out_fa = flash_attention(q, k, v, causal=True, block_q=128, block_kv=128,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out_fa), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-3)


def test_flash_grads_match_reference(rng):
    q, k, v = _qkv(rng, b=1, s=128, h=2, hkv=2, d=64)

    def loss_fa(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block_q=64,
                                       block_kv=64, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_noncausal(rng):
    q, k, v = _qkv(rng, s=128)
    out_ref = reference_attention(q, k, v, causal=False)
    out_fa = flash_attention(q, k, v, causal=False, block_q=64, block_kv=64,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out_fa), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-3)


@pytest.mark.parametrize("h,hkv", [(4, 4), (8, 2)])
def test_flash_segments_match_reference(rng, h, hkv):
    q, k, v = _qkv(rng, b=2, s=256, h=h, hkv=hkv)
    segs = make_packed_segments(2, 256)
    out_ref = reference_attention(q, k, v, causal=True, segment_ids=segs)
    out_fa = flash_attention(q, k, v, causal=True, segment_ids=segs,
                             block_q=64, block_kv=64, interpret=True)
    # Padding rows (seg 0) diverge by design: reference yields a uniform
    # softmax over all-masked scores, flash yields exact zeros. Both are
    # garbage excluded from the loss — compare real tokens only.
    valid = np.asarray(segs != 0)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(out_fa) * valid,
                               np.asarray(out_ref) * valid,
                               atol=2e-5, rtol=1e-3)


@pytest.mark.slow
def test_flash_segments_grads_match_reference(rng):
    q, k, v = _qkv(rng, b=1, s=128, h=4, hkv=2, d=64)
    segs = make_packed_segments(1, 128, n_docs=2)
    valid = (segs != 0).astype(q.dtype)[:, :, None, None]

    def loss_fa(q, k, v):
        out = flash_attention(q, k, v, causal=True, segment_ids=segs,
                              block_q=64, block_kv=64, interpret=True)
        return jnp.sum((out * valid) ** 2)

    def loss_ref(q, k, v):
        out = reference_attention(q, k, v, causal=True, segment_ids=segs)
        return jnp.sum((out * valid) ** 2)

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_window(rng):
    q, k, v = _qkv(rng, b=1, s=256, h=2, hkv=2)
    out_ref = reference_attention(q, k, v, causal=True, window=96)
    out_fa = flash_attention(q, k, v, causal=True, window=96,
                             block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out_fa), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-3)


def test_flash_window_plus_segments(rng):
    q, k, v = _qkv(rng, b=1, s=256, h=2, hkv=2)
    segs = make_packed_segments(1, 256)
    valid = np.asarray(segs != 0)[:, :, None, None]
    out_ref = reference_attention(q, k, v, causal=True, window=64,
                                  segment_ids=segs)
    out_fa = flash_attention(q, k, v, causal=True, window=64,
                             segment_ids=segs, block_q=64, block_kv=64,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(out_fa) * valid,
                               np.asarray(out_ref) * valid,
                               atol=2e-5, rtol=1e-3)


def test_flash_segments_unaligned_seq(rng):
    """seq not a multiple of the block: bounds masking composes with segs."""
    q, k, v = _qkv(rng, b=1, s=192, h=2, hkv=2)
    segs = make_packed_segments(1, 192, n_docs=2)
    valid = np.asarray(segs != 0)[:, :, None, None]
    out_ref = reference_attention(q, k, v, causal=True, segment_ids=segs)
    out_fa = flash_attention(q, k, v, causal=True, segment_ids=segs,
                             block_q=128, block_kv=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out_fa) * valid,
                               np.asarray(out_ref) * valid,
                               atol=2e-5, rtol=1e-3)


@pytest.mark.parametrize("seq,window,block", [
    (512, 96, 64),    # windowed grid engaged (3-4 visits of 8 blocks)
    (512, 100, 64),   # window not a multiple of the block
    (448, 96, 64),    # unaligned seq + windowed grid
    (512, 64, 128),   # window smaller than one block
])
def test_flash_windowed_grid_matches_reference(rng, seq, window, block):
    """The restricted kv sweep (only blocks inside the band are visited —
    or DMA'd) must be exact for every window/block alignment."""
    q, k, v = _qkv(rng, b=1, s=seq, h=2, hkv=2)
    out_ref = reference_attention(q, k, v, causal=True, window=window)
    out_fa = flash_attention(q, k, v, causal=True, window=window,
                             block_q=block, block_kv=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out_fa), np.asarray(out_ref),
                               atol=2e-5, rtol=1e-3)


def test_flash_windowed_grid_grads_match_reference(rng):
    q, k, v = _qkv(rng, b=1, s=256, h=2, hkv=2, d=64)

    def loss_fa(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window=96,
                                       block_q=64, block_kv=64,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True,
                                           window=96) ** 2)

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.slow
def test_flash_windowed_grid_with_segments_and_gqa(rng):
    """window + packing + GQA on the restricted sweep."""
    q, k, v = _qkv(rng, b=2, s=256, h=8, hkv=2)
    segs = make_packed_segments(2, 256)
    valid = np.asarray(segs != 0)[:, :, None, None]

    def loss_fa(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=80,
                              segment_ids=segs, block_q=64, block_kv=64,
                              interpret=True)
        return jnp.sum((out * valid) ** 2)

    def loss_ref(q, k, v):
        out = reference_attention(q, k, v, causal=True, window=80,
                                  segment_ids=segs)
        return jnp.sum((out * valid) ** 2)

    np.testing.assert_allclose(float(loss_fa(q, k, v)),
                               float(loss_ref(q, k, v)), rtol=1e-4)
    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("axes", [dict(fsdp=4), dict(data=2, tensor=2),
                                  dict(fsdp=2, tensor=2, expert=2)])
def test_flash_under_a_mesh_runs_per_shard_and_matches_reference(axes):
    """A Mosaic kernel cannot be partitioned by GSPMD (on the chip the
    sharded train step failed to lower), so under a mesh the model runs
    it per shard (``per_shard_attention``): batch rows over data/fsdp, heads
    over tensor. Loss and every gradient — including that of a REPLICATED weight
    upstream of the kernel, the case a wrong shard_map transpose corrupts —
    must equal the unsharded reference."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dlti_tpu.config import ParallelConfig
    from dlti_tpu.ops.attention import multi_head_attention
    from dlti_tpu.parallel.mesh import build_mesh
    from dlti_tpu.parallel.ring_attention import per_shard_attention

    mesh = build_mesh(ParallelConfig(**axes))
    b, s, h, kv, d = 4, 256, 8, 4, 128
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kv, d))
    w = jax.random.normal(jax.random.fold_in(key, 3), (d, d)) * 0.1

    def loss(attn):
        return lambda q, k, v, w: jnp.sum(jnp.tanh(attn(q @ w, k, v)) ** 2)

    sharded = loss(lambda q, k, v: per_shard_attention(
        functools.partial(multi_head_attention, causal=True, impl="flash",
                          block_q=128, block_kv=128, window=96),
        q, k, v, mesh))
    plain = loss(lambda q, k, v: reference_attention(
        q, k, v, causal=True, window=96))
    rows = NamedSharding(mesh, P(("data", "fsdp"), None, None, None))
    got_l, got_g = jax.jit(jax.value_and_grad(sharded, argnums=(0, 1, 2, 3)))(
        *(jax.device_put(x, rows) for x in (q, k, v)),
        jax.device_put(w, NamedSharding(mesh, P())))
    want_l, want_g = jax.jit(jax.value_and_grad(plain, argnums=(0, 1, 2, 3)))(
        q, k, v, w)
    np.testing.assert_allclose(got_l, want_l, rtol=1e-4)
    for got, want in zip(got_g, want_g):
        np.testing.assert_allclose(got, want, rtol=1e-3,
                                   atol=1e-4 * float(jnp.max(jnp.abs(want))))


def test_gqa_tile_is_a_power_of_two_share_of_block_q(monkeypatch):
    """block_q counts rows across the GQA group; each head's share is
    floored to a power of two so it divides any 128-aligned sequence
    (group 7 -> 64 positions, never 72 with a padded last tile)."""
    import importlib

    fa = importlib.import_module("dlti_tpu.ops.pallas.flash_attention")
    seen = []
    monkeypatch.setattr(
        fa, "_flash_attention_core",
        lambda q, k, v, seg, causal, block_q, *rest: seen.append(block_q))
    for heads, kv_heads in ((8, 8), (32, 8), (28, 4), (64, 1)):
        fa.flash_attention(jnp.zeros((1, 128, heads, 128)),
                           jnp.zeros((1, 128, kv_heads, 128)),
                           jnp.zeros((1, 128, kv_heads, 128)), block_q=512)
    assert seen == [512, 128, 64, 8]


# -- the forward alone: its own scale and value width, the lse beside it -----

def _masked_scores(q, k, seg, scale):
    """float32 scores with what a causal, segmented call hides at -inf."""
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    seen = (jnp.arange(s)[:, None] >= jnp.arange(s)[None])[None, None] \
        & (seg[:, None, :, None] == seg[:, None, None, :]) \
        & (seg[:, None, None, :] != 0)
    return jnp.where(seen, scores, -jnp.inf)


@pytest.mark.parametrize("case", ["width_192", "width_192_padded_to_256",
                                  "bf16", "gqa"])
def test_forward_alone_gives_the_output_and_the_lse(rng, case):
    """Queries and keys 192 wide (or their zero-padded 256: zeros add
    nothing to a score and the scale is the caller's) with values 128 wide,
    against ``reference_attention``; the returned lse against ``logsumexp``
    of the masked scores, +1e30 for a padding query that saw no key."""
    from dlti_tpu.ops.pallas.flash_attention import flash_attention_fwd

    hkv = 2 if case == "gqa" else 4
    q, k, _ = _qkv(rng, d=192, hkv=hkv)
    v = jax.random.normal(jax.random.fold_in(rng, 3), (2, 256, hkv, 128))
    seg = jnp.ones((2, 256), jnp.int32).at[1, 100:].set(0)
    scale = 192 ** -0.5
    want = reference_attention(q, k, v, causal=True, segment_ids=seg)
    lse_want = jax.nn.logsumexp(_masked_scores(
        q, jnp.repeat(k, 4 // hkv, axis=2), seg, scale), -1)
    given, tol = (q, k, v), 2e-5
    if case == "width_192_padded_to_256":
        pad = ((0, 0), (0, 0), (0, 0), (0, 64))
        given = (jnp.pad(q, pad), jnp.pad(k, pad), v)
    if case == "bf16":
        given, tol = [t.astype(jnp.bfloat16) for t in given], 4e-2
    out, lse = flash_attention_fwd(
        *given, scale=scale, segment_ids=seg, block_q=128, block_kv=128,
        interpret=True)
    assert out.shape == (2, 256, 4, 128) and out.dtype == given[0].dtype
    assert lse.shape == (2, 4, 256) and lse.dtype == jnp.float32
    real = np.asarray(seg != 0)
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32))[real], np.asarray(want)[real],
        atol=tol, rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(lse)[:, :, :100], np.asarray(lse_want)[:, :, :100],
        atol=tol)
    # a padding query: no key seen, a zero row and the backward's +BIG
    assert float(jnp.abs(out[1, 100:].astype(jnp.float32)).max()) == 0.0
    assert float(lse[1, :, 100:].min()) >= 1e29


# The three kernels as Mosaic is handed them at training's shape
# (train.mistral_7b.lora_sft: 4 x 2,048 tokens, 32/8 heads of 128, bf16,
# segment ids, window 4,096), hashed at the commit before the forward learnt
# a value width of its own and a caller's scale (PR 51): with v as wide as
# q, the text is that commit's.
TRAINING_KERNEL_TEXTS = {
    "dlti_flash_attention_fwd":
        "80cbd0cbc2445330",
    "dlti_flash_attention_bwd_dq":
        "333749f11ae79606",
    "dlti_flash_attention_bwd_dkv":
        "47ca84a5518d2346",
}


def _mosaic_texts(hlo: str) -> dict:
    """``{kernel name: its Mosaic module without locations + its cost
    estimate}`` of a program lowered for the TPU (a location names this
    file's lines and every caller's, which any edit moves)."""
    import base64
    import json
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    found = {}
    for config in re.findall(r'backend_config = "((?:[^"\\]|\\.)*)"', hlo):
        config = json.loads(re.sub(
            r"\\([0-9A-F]{2})", lambda m: chr(int(m.group(1), 16)),
            config)).get("custom_call_config", {})
        if "body" not in config:
            continue
        ctx = jax_mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(config["body"]))
            text = module.operation.get_asm(enable_debug_info=False)
        name = re.search(r"module @(\w+)", text).group(1)
        found[name] = text + json.dumps(config.get("cost_estimate"),
                                        sort_keys=True)
    return found


def test_training_kernels_lower_to_the_text_they_had():
    import hashlib

    q = jax.ShapeDtypeStruct((4, 2048, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((4, 2048, 8, 128), jnp.bfloat16)
    seg = jax.ShapeDtypeStruct((4, 2048), jnp.int32)

    def grads(q, k, v, seg):
        return jax.grad(lambda *a: flash_attention(
            *a, segment_ids=seg, window=4096).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    # (the suite's process-wide "highest" is not what a trainer runs under)
    with jax.default_matmul_precision("default"):
        hlo = jax.jit(grads).trace(q, kv, kv, seg).lower(
            lowering_platforms=("tpu",)).as_text()
    assert {name: hashlib.sha256(text.encode()).hexdigest()[:16]
            for name, text in _mosaic_texts(hlo).items()} \
        == TRAINING_KERNEL_TEXTS
