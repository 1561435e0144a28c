"""Paged decode attention for TPU, in Pallas.

The serving engine's decode hot op. The XLA fallback path
(``dlti_tpu.ops.kv_cache.paged_gather`` + ``reference_attention``)
materializes each sequence's whole logical KV window in HBM every step —
O(batch * max_len) extra traffic. This kernel instead walks the block table
and reads K/V blocks *in place* from the physical pool, one VMEM tile at a
time, with an online softmax — the TPU analog of vLLM's PagedAttention
CUDA kernel (the reference claims that engine via ``requirements.txt:18``
but ships no code; SURVEY.md §2b).

Design:

* Grid ``(batch, max_blocks_per_seq)``; TPU grids run sequentially
  minor-most-first, so the online-softmax running state ``(m, l, acc)``
  for one sequence lives in VMEM scratch across the block sweep.
* ``block_tables`` and ``seq_lens`` ride scalar prefetch
  (:class:`~jax.experimental.pallas.tpu.PrefetchScalarGridSpec`), so the
  K/V ``BlockSpec`` index maps can pick the *physical* block
  ``block_tables[b, j]`` for logical block ``j`` — the indirection happens
  in the pipeline, not as a gather. Each live block is fetched exactly
  once per sequence per step, with every KV head in the tile (full-dim
  trailing axes keep Mosaic's (8, 128) tiling rules satisfied).
* GQA for free: q arrives as ``(batch, kv_heads, heads_per_group, d)``
  and the per-block matmuls are batched over ``kv_heads``, so KV heads are
  never repeated.
* Blocks at or past ``seq_lens[b]`` are skipped (``pl.when``), and the
  tail block is masked by token position, so stale pool rows never
  contribute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlti_tpu.ops.pallas.flash_attention import out_struct

NEG_INF = -1e30


def _decode_kernel(seq_lens_ref, block_tables_ref, q_ref, k_ref, v_ref, *rest,
                   scale: float, block_size: int, window: int,
                   quantized: bool):
    if quantized:
        # int8 pools travel with (1, block_size, kv_heads) fp32 scale
        # tiles; the scales fold into the attention math per kv position
        # (s *= k_scale, p *= v_scale) — no dequantized K/V tile is ever
        # materialized. The scale tile's minor dim is kv_heads (< the
        # 128-lane Mosaic tile): Mosaic pads it, costing a few KB of
        # VMEM per block against the 64+ KB int8 payload.
        ks_ref, vs_ref, o_ref, m_scratch, l_scratch, acc_scratch = rest
    else:
        (o_ref, m_scratch, l_scratch, acc_scratch), ks_ref, vs_ref = rest, None, None
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    seq_len = seq_lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    run = j * block_size < seq_len
    if window:
        # Sliding window: skip blocks wholly below [seq_len - window, seq_len).
        run = jnp.logical_and(run, (j + 1) * block_size > seq_len - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)                   # (kvh, hpg, d)
        k = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)  # (kvh, bs, d)
        v = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)  # (kvh, bs, d)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (kvh, hpg, bs)
        if ks_ref is not None:
            ks = jnp.swapaxes(ks_ref[0].astype(jnp.float32), 0, 1)
            s = s * ks[:, None, :]                         # (kvh, 1, bs)

        k_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        valid = k_pos < seq_len
        if window:
            valid &= k_pos >= seq_len - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scratch[:]                              # (kvh, hpg, 1)
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[:] = alpha * l_scratch[:] + jnp.sum(p, axis=2, keepdims=True)
        pv = p
        if vs_ref is not None:
            vs = jnp.swapaxes(vs_ref[0].astype(jnp.float32), 0, 1)
            pv = p * vs[:, None, :]                        # (kvh, 1, bs)
        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            pv, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scratch[:] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_scratch[:]
        l = jnp.where(l == 0.0, 1.0, l)  # seq_len == 0 -> zero output
        o_ref[0] = (acc_scratch[:] / l).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    window: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """One-token-per-sequence attention over the paged KV pool.

    Args:
      q: ``(batch, 1, num_heads, head_dim)`` current-step queries.
      k_pool / v_pool: ``(num_blocks, block_size, kv_heads, head_dim)``.
      block_tables: ``(batch, max_blocks_per_seq)`` int32; entries for
        unallocated logical blocks may be any value (they are clamped and
        masked, never read into the result).
      seq_lens: ``(batch,)`` int32 — tokens valid per sequence *including*
        the current one (i.e. query position + 1).
      k_scale / v_scale: for int8 pools, the ``(num_blocks, block_size,
        kv_heads)`` fp32 per-row scales (``ops.kv_cache`` int8 layout);
        folded into the attention math in place — required iff the pools
        are int8.
      window: Mistral-style sliding window — only the last ``window``
        positions stay visible; whole blocks outside the band are skipped.

    Returns ``(batch, 1, num_heads, head_dim)``.
    """
    batch, s1, num_heads, head_dim = q.shape
    assert s1 == 1, f"decode kernel takes single-token queries, got s={s1}"
    num_blocks, block_size, kv_heads, _ = k_pool.shape
    hpg = num_heads // kv_heads
    max_blocks = block_tables.shape[1]
    scale = head_dim ** -0.5

    # (batch, kv_heads, hpg, d): group query heads with their KV head.
    qg = q[:, 0].reshape(batch, kv_heads, hpg, head_dim)
    # Physical ids must be in-range even for never-run grid steps: the
    # pipeline prefetches by index map before the kernel's pl.when gate.
    bt = jnp.clip(block_tables, 0, num_blocks - 1).astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)

    grid = (batch, max_blocks)

    quantized = k_pool.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 KV pools require k_scale/v_scale")

    def q_map(b, j, seq_lens_ref, bt_ref):
        return (b, 0, 0, 0)

    def kv_map(b, j, seq_lens_ref, bt_ref):
        return (bt_ref[b, j], 0, 0, 0)

    def scale_map(b, j, seq_lens_ref, bt_ref):
        return (bt_ref[b, j], 0, 0)

    in_specs = [
        pl.BlockSpec((1, kv_heads, hpg, head_dim), q_map),
        pl.BlockSpec((1, block_size, kv_heads, head_dim), kv_map),
        pl.BlockSpec((1, block_size, kv_heads, head_dim), kv_map),
    ]
    operands = [qg, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, block_size, kv_heads), scale_map),
                     pl.BlockSpec((1, block_size, kv_heads), scale_map)]
        operands += [k_scale, v_scale]

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_size=block_size, window=window or 0,
                               quantized=quantized)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, kv_heads, hpg, head_dim), q_map),
            scratch_shapes=[
                pltpu.VMEM((kv_heads, hpg, 1), jnp.float32),
                pltpu.VMEM((kv_heads, hpg, 1), jnp.float32),
                pltpu.VMEM((kv_heads, hpg, head_dim), jnp.float32),
            ],
        ),
        out_shape=out_struct((batch, kv_heads, hpg, head_dim), q.dtype, q),
        interpret=interpret,
        # The name under which a device trace shows this kernel
        # (benchmark/lib/span_rules.json finds it by it); the scope round
        # the call keeps the model's own scopes out of that name.
        name="dlti_paged_attention_decode",
        cost_estimate=pl.CostEstimate(
            flops=int(2 * 2 * batch * num_heads * max_blocks * block_size
                      * head_dim),
            bytes_accessed=int(
                (batch * max_blocks * block_size * kv_heads * head_dim * 2)
                * k_pool.dtype.itemsize + 2 * q.size * q.dtype.itemsize),
            transcendentals=batch * num_heads * max_blocks * block_size,
        ),
    )
    with jax.named_scope("dlti_paged_attention_decode"):
        out = call(seq_lens, bt, *operands)

    return out.reshape(batch, 1, num_heads, head_dim)
