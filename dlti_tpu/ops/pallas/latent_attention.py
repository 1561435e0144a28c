"""Latent (MLA) decode attention over the paged latent pool, in Pallas.

The absorbed form of latent attention meets ONE shared row a key: the
queries of all heads, already taken through the key up-projection
(``q~_h = q_nope,h W_kvb,h[:, :nope]^T``) and laid beside their rotary
part, score against the whole row ``[latent ; rotated key]``, and the
values are the first ``value_dim`` entries of the same row (the latent;
the value up-projection follows the kernel, in XLA). So a live tile is
copied once and used twice.

The structure is the paged decode kernel's (``ops.pallas.paged_attention``,
PERF.md section 6, PR 34), as it is: the pool stays in HBM
(``memory_space=ANY``); ``live_tiles`` lists the batch's live tiles once a
decode step in XLA (every layer's call shares it); one ``fori_loop`` over
that list copies a tile's blocks by hand (``make_async_copy``, physical
block from the table in SMEM) into the other of two VMEM slots while the
body works on its own; the online-softmax state lives in VMEM scratch
across a sequence's tiles. What differs: one pool, so T copies a tile, not
2T; no kv heads, so no mask by head and no relayout at all (a tile is a
``(keys, latent_dim)`` matrix as it lies); operands stay in the pool's
type with float32 accumulation — per cached token a layer does 2 x heads
x (latent_dim + value_dim) FLOP for ``latent_dim`` x 2 bytes (60 FLOP a
byte at 32 heads, 576 / 512 wide), where float32 operands on the MXU would
be the bound before the bytes are.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlti_tpu.ops.pallas.flash_attention import out_struct
from dlti_tpu.ops.pallas.paged_attention import (
    NEG_INF, live_tiles, tile_blocks,
)


def _decode_kernel(seq_lens_ref, block_tables_ref, row_ref, tile_ref,
                   total_ref, q_ref, pool_hbm, o_ref, buf, sem, m_scratch,
                   l_scratch, acc_scratch, *, scale: float, block_size: int,
                   tile: int, value_dim: int):
    T = tile
    keys = T * block_size
    num_heads = q_ref.shape[1]
    max_blocks = block_tables_ref.shape[1]
    total = total_ref[0]

    # Rows no tile visits (seq_len == 0) read zero.
    o_ref[...] = jnp.zeros_like(o_ref)

    def tile_copies(i, slot):
        """The T block copies of schedule entry ``i`` into ``slot``. A block
        past the row's context names its last live block instead (masked by
        position below), so a tile holds pool data alone."""
        row, j = row_ref[i], tile_ref[i]
        last = jnp.minimum((seq_lens_ref[row] - 1) // block_size,
                           max_blocks - 1)
        for t in range(T):
            phys = block_tables_ref[row, jnp.minimum(j * T + t, last)]
            yield pltpu.make_async_copy(
                pool_hbm.at[phys],
                buf.at[slot, pl.ds(t * block_size, block_size)],
                sem.at[slot])

    def start(i, slot):
        for copy in tile_copies(i, slot):
            copy.start()

    @pl.when(total > 0)
    def _first():
        start(0, 0)

    k_in_tile = jax.lax.broadcasted_iota(jnp.int32, (num_heads, keys), 1)

    def step(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < total)
        def _next():
            start(i + 1, 1 - slot)

        for copy in tile_copies(i, slot):
            copy.wait()
        row, j = row_ref[i], tile_ref[i]
        seq_len = seq_lens_ref[row]

        @pl.when(j == 0)
        def _init():
            m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
            l_scratch[...] = jnp.zeros_like(l_scratch)
            acc_scratch[...] = jnp.zeros_like(acc_scratch)

        rows = buf[slot]                                   # (keys, latent)
        q = q_ref[row].astype(rows.dtype)                  # (heads, latent)
        # Stated, not left to the process's default: bf16 operands take one
        # MXU pass whatever ``jax_default_matmul_precision`` says.
        precision = jax.lax.Precision.HIGHEST \
            if rows.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        ) * scale                                          # (heads, keys)
        s = jnp.where(j * keys + k_in_tile < seq_len, s, NEG_INF)

        m_prev = m_scratch[...]                            # (heads, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[...] = alpha * l_scratch[...] \
            + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), buf[slot, :, :value_dim],
            (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        )
        m_scratch[...] = m_new

        @pl.when(j == (seq_len - 1) // keys)
        def _finalize():
            o_ref[row] = (acc_scratch[...] / l_scratch[...]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, total, step, 0)


# Jitted on its own, as the paged kernel is: every layer of a decode program
# shares one trace and one lowering of a body that unrolls 2T copies.
@functools.partial(jax.jit,
                   static_argnames=("value_dim", "scale", "interpret"))
def latent_decode_attention(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    *,
    value_dim: int,
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """One-token-per-sequence absorbed latent attention over the pool.

    Args:
      q: ``(batch, num_heads, latent_dim)`` absorbed queries ``[q~ ; q_rope]``.
      pool: ``(num_blocks, block_size, width)`` latent rows, ``width`` the
        latent_dim rounded up to whole lanes, zeros past it
        (``ops.kv_cache.init_latent_cache``); ``q`` is padded to match.
      block_tables: ``(batch, max_blocks_per_seq)`` int32; entries of
        unallocated logical blocks may be anything (never read).
      seq_lens: ``(batch,)`` int32, tokens valid a sequence including the
        current one (query position + 1).
      value_dim: the leading entries of a row that are its values (the
        latent, ``kv_lora_rank``).
      scale: the softmax scale (``(qk_nope_head_dim + qk_rope_head_dim)
        ** -0.5``: of the expanded head, not of the row).

    Returns ``(batch, num_heads, value_dim)``: the attended latents, for
    the value up-projection.
    """
    batch, num_heads, _ = q.shape
    num_blocks, block_size, latent_dim = pool.shape
    q = jnp.pad(q, ((0, 0), (0, 0), (0, latent_dim - q.shape[-1])))
    max_blocks = block_tables.shape[1]
    T = tile_blocks(block_size, max_blocks, latent_dim * pool.dtype.itemsize)
    keys = T * block_size
    steps = batch * pl.cdiv(max_blocks, T)

    # Physical ids must be in range whatever the table holds.
    bt = jnp.clip(block_tables, 0, num_blocks - 1).astype(jnp.int32)
    seq_lens = jnp.minimum(seq_lens.astype(jnp.int32), max_blocks * block_size)
    row, tile, total = live_tiles(seq_lens, keys, 0, steps)

    def whole(shape):
        return pl.BlockSpec(shape, lambda g, *_: (0,) * len(shape))

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_size=block_size, tile=T,
                               value_dim=value_dim)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[whole(q.shape),
                      pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=whole((batch, num_heads, value_dim)),
            scratch_shapes=[
                pltpu.VMEM((2, keys, latent_dim), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((num_heads, 1), jnp.float32),
                pltpu.VMEM((num_heads, 1), jnp.float32),
                pltpu.VMEM((num_heads, value_dim), jnp.float32),
            ],
        ),
        out_shape=out_struct((batch, num_heads, value_dim), q.dtype, q),
        interpret=interpret,
        # The name under which a device trace shows this kernel
        # (benchmark/rules/latent_attention.json finds it by it).
        name="dlti_latent_attention_decode",
        # Every tile of every row, as if all were live.
        cost_estimate=pl.CostEstimate(
            flops=int(2 * num_heads * steps * keys * (latent_dim + value_dim)),
            bytes_accessed=int(steps * keys * latent_dim * pool.dtype.itemsize
                               + 2 * q.size * q.dtype.itemsize),
            transcendentals=num_heads * steps * keys,
        ),
    )
    with jax.named_scope("dlti_latent_attention_decode"):
        return call(seq_lens, bt, row, tile, total, q, pool)
