"""The serving cache — device-side ops: paged keys and values, paged
latents, and the recurrent state of state-space layers by decode slot.

One cache, one entry a layer (a looped stack, ``ut_steps`` > 1, keeps an
entry a (pass, layer), ``ModelConfig.cache_entries`` of them: pass u of
layer l attends over what pass u of layer l wrote. A layer's pool then
holds ``ut_steps`` runs of ``num_blocks`` blocks, pass u's block b at ``u x
num_blocks + b`` (``models.llama.entry_of_pass``), so that one block table
and one allocator serve every pass), three kinds of state
(:func:`init_cache`):
``{"k", "v"}`` block pools addressed by block tables for attention layers
(below; where a model's layers differ in their attention window they form
GROUPS, a pool size, a table and an allocator a group:
:func:`window_group_blocks`, :func:`bind_call`), ``{"latent"}`` — ONE block pool ``(num_blocks, block_size,
kv_lora_rank + qk_rope_head_dim`` rounded up to whole 128-value lanes``)``
— for latent-attention layers (:func:`init_latent_cache`: a token's row is
its normed latent and its rotated shared key, written by one scatter and
addressed by the same block tables, so the allocator and the prefix cache
see a block id like any other), ``{"conv", "ssm"}`` of shape ``(slots, ...)`` for state-space layers
(Mamba-2 and Mamba-1; :func:`init_recurrent_state`; a sequence's state has
a fixed size, so it is addressed by the decode slot that serves it and
needs no allocator), and ``{}`` for layers that keep nothing (routed
experts, gated memory units, and cross-attention layers, which read
another layer's pool as the model hands it to them inside a call). The
three may stand side by side in one model (``models.sambay``: recurrent
entries, a window group, a full group and empty entries), and a pool may be
FUSED (:func:`init_paged_cache`).

## Paged keys and values


The reference claims a vLLM serving leg ("PagedAttention, continuous
batching", ``README.md:10``; ``requirements.txt:18``) but ships no code.
This is the TPU-native equivalent of vLLM's block-based KV cache, designed
for XLA's static-shape model:

* One physical pool per layer: ``(num_blocks, block_size, kv_heads, head_dim)``
  living in HBM for the whole engine lifetime (no per-request allocation).
* A ``block_tables`` int32 array ``(batch, max_blocks_per_seq)`` maps each
  sequence's *logical* block ``i`` to a physical block id. Logical token
  position ``p`` lives at physical row ``block_tables[b, p // bs]`` offset
  ``p % bs``.
* Writes are flat scatters (``.at[...].set(mode="drop")``) — out-of-range
  slot ids (padding tokens) are dropped, so prefill and decode share one
  compiled update path.
* The XLA reference read path gathers a sequence's blocks back into a
  contiguous ``(batch, max_kv, kv_heads, head_dim)`` window; causal masking
  against explicit positions hides stale/unallocated slots (unwritten
  logical positions are always > the query position). The Pallas kernel
  (``dlti_tpu.ops.pallas.paged_attention``) reads blocks in place instead.

All functions are pure; the host-side block allocator lives in
``dlti_tpu.serving.block_manager``.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp

# int8 KV: pools store symmetric per-token-per-kv-head int8 (absmax over
# head_dim -> one fp32 scale per written row), halving KV HBM vs bf16 —
# the pool is the serving engine's biggest allocation after the weights,
# so the freed memory goes straight into more decode slots. Quantization
# happens once at write (paged_update); consumers either dequantize after
# gather (XLA fallback / prefill / TP path) or fold the scales into the
# attention math in place (the Pallas decode kernel).


def init_paged_cache(
    num_layers: int,
    num_blocks: int,
    block_size: int,
    num_kv_heads: int,
    head_dim: int,
    dtype=jnp.bfloat16,
    fused: bool = False,
) -> List[dict]:
    """Allocate the physical block pools, one ``{"k", "v"}`` dict per layer.

    ``fused``: pools of ``(num_blocks, block_size, kv_heads * head_dim)``, a
    token's kv heads side by side in one row. The TPU tiles an array's last
    two dimensions (16 x 128 for bfloat16), so a 4-D pool of 10 kv heads
    lies padded to 16 in HBM, and the decode kernel cannot copy a block of
    it by hand; a fused row of 10 x 128 values is whole lanes and
    ``block_size`` whole sublanes. :func:`paged_update`, :func:`paged_gather` and the
    decode kernel tell the layout by the pool's rank.

    ``dtype="int8"`` (the string, or ``jnp.int8``) selects the quantized
    pool layout: int8 payloads plus ``{"k_scale", "v_scale"}`` fp32 arrays
    of shape ``(num_blocks, block_size, kv_heads)``.
    """
    shape = (num_blocks, block_size, num_kv_heads, head_dim)
    if fused:
        if dtype == "int8" or dtype == jnp.int8:
            raise ValueError("a fused pool has no int8 layout (a scale a "
                             "(token, kv head) has no place in a fused row)")
        shape = (num_blocks, block_size, num_kv_heads * head_dim)
    if dtype == "int8" or dtype == jnp.int8:
        sshape = (num_blocks, block_size, num_kv_heads)
        return [
            {"k": jnp.zeros(shape, jnp.int8),
             "v": jnp.zeros(shape, jnp.int8),
             "k_scale": jnp.zeros(sshape, jnp.float32),
             "v_scale": jnp.zeros(sshape, jnp.float32)}
            for _ in range(num_layers)
        ]
    return [
        {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for _ in range(num_layers)
    ]


def init_recurrent_state(num_slots: int, conv_kernel: int, conv_dim: int,
                         state_shape: tuple, conv_dtype=jnp.bfloat16,
                         state_dtype=jnp.float32) -> dict:
    """One state-space layer's state for every decode slot: the last
    ``conv_kernel - 1`` inputs of the convolution and the SSM state, a
    sequence's ``state_shape`` (Mamba-2: heads, head_dim, state size;
    Mamba-1: channels, state size)."""
    return {"conv": jnp.zeros((num_slots, conv_kernel - 1, conv_dim),
                              conv_dtype),
            "ssm": jnp.zeros((num_slots, *state_shape), state_dtype)}


# Values in one lane row of the TPU's tiled layouts.
LANES = 128


def init_latent_cache(num_blocks: int, block_size: int, latent_dim: int,
                      dtype=jnp.bfloat16) -> dict:
    """One latent-attention layer's pool: a row a token, ``[normed latent ;
    rotated shared key ; zeros]``, under one key. The row is ``latent_dim``
    values rounded up to whole lanes of 128: the TPU lays a 576-wide array
    out in 640-wide rows whatever its shape says, and a block can be copied
    by hand (the decode kernel) only at the width it lies in."""
    if dtype == "int8" or dtype == jnp.int8:
        raise ValueError(
            "a latent cache has no int8 layout: a row is a normed latent "
            "and a rotated key with one scale between them; serve it with "
            "--kv-cache-dtype bfloat16")
    return {"latent": jnp.zeros(
        (num_blocks, block_size, -(-latent_dim // LANES) * LANES), dtype)}


def latent_update(layer_cache: dict, rows: jnp.ndarray,
                  slots: jnp.ndarray) -> dict:
    """Scatter new latent rows ``(batch, s, latent_dim)`` into the pool at
    the flat ``slots`` of :func:`slot_mapping`: one scatter a layer (the
    lanes past ``latent_dim`` are written as zeros)."""
    pool = layer_cache["latent"]
    nb, bs, d = pool.shape
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, d - rows.shape[-1])))
    flat = pool.reshape(nb * bs, d).at[slots.reshape(-1)].set(
        rows.reshape(-1, d).astype(pool.dtype), mode="drop")
    return {**layer_cache, "latent": flat.reshape(nb, bs, d)}


def latent_gather(layer_cache: dict, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Each sequence's logical window of latent rows, ``(batch, max_blocks *
    block_size, pool width)``; what lies past a sequence's written length is
    masked by the caller's positions."""
    pool = layer_cache["latent"]
    b, max_blk = block_tables.shape
    return pool[block_tables].reshape(b, max_blk * pool.shape[1],
                                      pool.shape[2])


def window_blocks(window: int, block_size: int, tokens: int) -> int:
    """Blocks a sequence holds of a window group while it writes ``tokens``
    tokens more: those of the ``window`` keys before them, theirs, and one
    for each end that falls inside a block."""
    return -(-(window + tokens) // block_size) + 2


def window_group_blocks(window: int, block_size: int, num_slots: int,
                        call_tokens: int) -> int:
    """Blocks of a window group's pool, from shapes alone: what every slot
    holds between two calls and through a decode round (one token), the
    tokens of the widest prefill call (``call_tokens``, over at most 8
    rows, an edge block each), and the trash block. The allocator
    (``serving.engine``) releases a block as soon as it lies wholly before
    its sequence's window, so this pool never runs out while the engine
    holds to ``call_tokens``: the full group's pool (``--num-blocks``) is
    what admission waits for."""
    return (num_slots * window_blocks(window, block_size, 1)
            + -(-call_tokens // block_size) + 8 + 1)


def init_cache(model_cfg, num_blocks: int, block_size: int, num_slots: int,
               dtype=jnp.bfloat16, call_tokens: int = 0) -> List[dict]:
    """The serving cache of ``model_cfg``, one entry a layer by its kind
    (a looped stack: ``ut_steps`` runs of ``num_blocks`` blocks in each
    layer's pool, an entry a pass). A model without a ``layer_pattern`` is
    attention in every layer, over latents where the configuration has a
    ``kv_lora_rank``. Attention
    layers of one window form a group (``ModelConfig.kv_group_windows``):
    the group that sees every key, and every model's only group, has pools
    of ``num_blocks``; a window group's are sized by
    :func:`window_group_blocks` (``call_tokens``: the most tokens of a
    prefill call)."""
    from dlti_tpu.utils.dtypes import resolve_dtype

    if model_cfg.latent_dim:
        return [init_latent_cache(num_blocks, block_size,
                                  model_cfg.latent_dim, dtype)
                for _ in range(model_cfg.num_layers)]

    # Pools of attention layers, by group: the group that sees every key
    # (and every model's only group) ``num_blocks`` a pass, a window
    # group's by its shapes.
    blocks = [model_cfg.ut_steps * num_blocks if not w or i == 0
              else window_group_blocks(w, block_size, num_slots, call_tokens)
              for i, w in enumerate(model_cfg.kv_group_windows)]
    sambay = model_cfg.is_sambay
    kinds = model_cfg.layer_pattern or "*" * model_cfg.num_layers
    # (the decoder-hybrid-decoder family pairs its heads: half as many
    # key-value heads, twice as wide, models.sambay; 10 of them at the
    # published sizes, so its pools are fused)
    kv_heads, head_dim = (
        (model_cfg.num_kv_heads // 2, 2 * model_cfg.resolved_head_dim)
        if sambay else (model_cfg.num_kv_heads, model_cfg.resolved_head_dim))

    # (... and the jamba family's ONE key-value head: a 4-D pool of one
    # head lies padded to two and the chip's compiler refuses the decode
    # kernel's block copy, "must be aligned to tiling (2), but is 1")
    fused = sambay or model_cfg.is_jamba

    def paged(layer):
        return init_paged_cache(
            1, blocks[model_cfg.kv_group_of_layer(layer)], block_size,
            kv_heads, head_dim, dtype, fused=fused)[0]

    def recurrent(layer):
        conv_dim, state = (
            (model_cfg.mamba_inner_size,
             (model_cfg.mamba_inner_size, model_cfg.mamba_state_size))
            if kinds[layer] == "S" else
            (model_cfg.mamba_conv_dim,
             (model_cfg.mamba_num_heads, model_cfg.mamba_head_dim,
              model_cfg.mamba_state_size)))
        return init_recurrent_state(
            num_slots, model_cfg.mamba_conv_kernel, conv_dim, state,
            resolve_dtype(model_cfg.dtype),
            resolve_dtype(model_cfg.mamba_state_dtype))

    def nothing(layer):
        return {}

    make = {"*": paged, "D": paged, "A": paged, "M": recurrent,
            "S": recurrent, "E": nothing, "G": nothing, "X": nothing}
    return [make[k](i) for i, k in enumerate(kinds)]


# What one program call adds to the layers' entries (:func:`bind_call`).
_CALL_KEYS = ("block_tables", "table_base", "state_slots", "own_rows")


def bind_call(cache: List[dict], block_tables, state_slots=None,
              own_rows: bool = False, groups=None) -> List[dict]:
    """The cache as one program call hands it to the model: every layer's
    entry with the rows' block tables, and a recurrent layer's with each
    row's decode slot (``state_slots`` (rows,): out of range for a row that
    must write no state; ``own_rows``: a decode call, row i is slot i).

    A model with several groups of attention layers (``groups``: each
    layer's group) gives ``block_tables`` as a tuple, a dict a group of
    what its layers' entries get: ``{"block_tables"}`` for the group that
    sees every key, ``{"block_tables", "table_base"}`` for a window group,
    whose table starts at the block that holds token ``table_base`` (rows,)
    of each row (what lies before has been released)."""
    recurrent = {"state_slots": state_slots, "own_rows": own_rows}
    return [{**c,
             **({"block_tables": block_tables} if groups is None
                else block_tables[groups[i]]),
             **(recurrent if "ssm" in c else {})}
            for i, c in enumerate(cache)]


def unbind_call(cache: List[dict]) -> List[dict]:
    """The pools alone again, as the model returned them."""
    return [{k: v for k, v in c.items() if k not in _CALL_KEYS}
            for c in cache]


def _quantize_rows(x: jnp.ndarray):
    """Per-(token, kv_head) symmetric int8 over the trailing head_dim."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def slot_mapping(block_tables: jnp.ndarray, positions: jnp.ndarray,
                 block_size: int, num_blocks: int) -> jnp.ndarray:
    """Flat physical slot index for each (batch, seq) token.

    ``positions`` are logical token positions; negative positions (padding)
    map to an out-of-range slot so the scatter drops them.
    """
    blk = jnp.maximum(positions, 0) // block_size
    off = jnp.maximum(positions, 0) % block_size
    phys = jnp.take_along_axis(block_tables, blk, axis=1)
    slots = phys * block_size + off
    oob = num_blocks * block_size  # one past the end -> dropped by mode="drop"
    return jnp.where(positions >= 0, slots, oob)


def paged_update(layer_cache: dict, k_new: jnp.ndarray, v_new: jnp.ndarray,
                 slots: jnp.ndarray) -> dict:
    """Scatter new K/V rows into the physical pool.

    ``k_new``/``v_new``: (batch, s, kv_heads, head_dim); ``slots``: (batch, s)
    flat physical slot ids from :func:`slot_mapping`.
    """
    k_pool, v_pool = layer_cache["k"], layer_cache["v"]
    flat = slots.reshape(-1)
    out = dict(layer_cache)
    if k_pool.ndim == 3:  # fused: a token's row is its kv heads side by side
        nb, bs, width = k_pool.shape
        for name, pool, new in (("k", k_pool, k_new), ("v", v_pool, v_new)):
            out[name] = pool.reshape(nb * bs, width).at[flat].set(
                new.reshape(-1, width).astype(pool.dtype),
                mode="drop").reshape(nb, bs, width)
        return out
    nb, bs, kvh, hd = k_pool.shape
    if k_pool.dtype == jnp.int8:
        kq, ks = _quantize_rows(k_new)
        vq, vs = _quantize_rows(v_new)
        out["k_scale"] = (layer_cache["k_scale"].reshape(nb * bs, kvh)
                          .at[flat].set(ks.reshape(-1, kvh), mode="drop")
                          .reshape(nb, bs, kvh))
        out["v_scale"] = (layer_cache["v_scale"].reshape(nb * bs, kvh)
                          .at[flat].set(vs.reshape(-1, kvh), mode="drop")
                          .reshape(nb, bs, kvh))
        k_new, v_new = kq, vq
    k_flat = k_pool.reshape(nb * bs, kvh, hd)
    v_flat = v_pool.reshape(nb * bs, kvh, hd)
    out["k"] = k_flat.at[flat].set(
        k_new.reshape(-1, kvh, hd).astype(k_pool.dtype),
        mode="drop").reshape(nb, bs, kvh, hd)
    out["v"] = v_flat.at[flat].set(
        v_new.reshape(-1, kvh, hd).astype(v_pool.dtype),
        mode="drop").reshape(nb, bs, kvh, hd)
    return out


def pool_kv_heads(layer_cache: dict, head_dim: int) -> int:
    """Key-value heads of a layer's pool, fused or not."""
    pool = layer_cache["k"]
    return pool.shape[2] // head_dim if pool.ndim == 3 else pool.shape[2]


def paged_gather(layer_cache: dict, block_tables: jnp.ndarray,
                 head_dim: int = 0):
    """Gather each sequence's logical KV window from the pool
    (``head_dim``: the heads' width, for a fused pool to be read by).

    Returns (k, v) of shape (batch, max_blocks*block_size, kv_heads, head_dim)
    in logical order; garbage beyond a sequence's written length is masked by
    the caller's causal/position mask. int8 pools dequantize to the fp32
    product (scales are fp32) — callers cast to their compute dtype, so
    fp32 paths don't pay an extra bf16 rounding step on the way through.
    """
    k_pool, v_pool = layer_cache["k"], layer_cache["v"]
    b, max_blk = block_tables.shape
    if k_pool.ndim == 3:
        return tuple(pool[block_tables].reshape(
            b, max_blk * pool.shape[1], -1, head_dim)
            for pool in (k_pool, v_pool))
    nb, bs, kvh, hd = k_pool.shape
    k = k_pool[block_tables].reshape(b, max_blk * bs, kvh, hd)
    v = v_pool[block_tables].reshape(b, max_blk * bs, kvh, hd)
    if k_pool.dtype == jnp.int8:
        # Dequantize the gathered window (gather moves 1/2 the bytes of a
        # bf16 pool; the expansion happens on the small window).
        ks = layer_cache["k_scale"][block_tables].reshape(b, max_blk * bs, kvh, 1)
        vs = layer_cache["v_scale"][block_tables].reshape(b, max_blk * bs, kvh, 1)
        k = k.astype(jnp.float32) * ks
        v = v.astype(jnp.float32) * vs
    return k, v
