"""Latent (MLA) decode attention over the paged latent pool, in Pallas.

The absorbed form of latent attention meets ONE shared row a key: the
queries of all heads, already taken through the key up-projection
(``q~_h = q_nope,h W_kvb,h[:, :nope]^T``) and laid beside their rotary
part, score against the whole row ``[latent ; rotated key]``, and the
values are the first ``value_dim`` entries of the same row (the latent;
the value up-projection follows the kernel, in XLA). So a live tile is
copied once and used twice.

The structure began as the paged decode kernel's (``ops.pallas.
paged_attention``, PERF.md section 6, PR 34): the pool stays in HBM
(``memory_space=ANY``); ``live_tiles`` lists the batch's live tiles once a
decode step in XLA (every layer's call shares it, and the table laid out
in whole tiles of live blocks beside it); one ``fori_loop`` over that list
copies a tile's blocks by hand (``make_async_copy``) and the online-softmax
state lives in VMEM scratch across a sequence's tiles. What
differs: one pool, so T copies a tile, not 2T; no kv heads, so no mask by
head and no relayout at all (a tile is a ``(keys, latent_dim)`` matrix as it
lies); operands stay in the pool's type with float32 accumulation — per
cached token a layer does 2 x heads x (latent_dim + value_dim) FLOP for
``latent_dim`` x 2 bytes (60 FLOP a byte at 32 heads, 576 / 512 wide), where
float32 operands on the MXU would be the bound before the bytes are.

**What the loop keeps in flight** (PR 55). A ring of ``depth`` VMEM slots
and as many DMA semaphores: tiles ``0 .. depth - 2`` are sent before the
loop; step ``i`` sends tile ``i + depth - 1`` into the slot step ``i - 1``
took its values from, waits (one wait a slot, for the tile's whole byte
count) for tile ``i + 1``, sent ``depth - 2`` steps before, and scores it
while tile ``i``'s softmax and values run on the scores step ``i - 1`` left:
``depth - 2`` whole tiles of copies are in flight under every body, across
sequence boundaries, and the MXU's fill and drain hide under the other
tile's reductions. The loop's body holds no branch but a row's write-out:
a tile past the schedule's end copies the last tile again (waited for after
the loop), and the state is reset where a row's last tile writes out. That
is what made the ring pay: with the copies under a branch of their own the
compiler schedules their address arithmetic, the scores and the softmax one
after the other, and the kernel read the same 0.96-0.98 us a 256-key tile
at 2, 3, 4 and 6 slots (copies alone 0.51-0.57, the body alone 0.67).
``ring_shape`` picks T and the depth from shapes alone (``block_size``, the
table's width, one row's bytes against ``VMEM_RING_BUDGET``), never from a
flag or a model's name: at the two cells' shapes 24 blocks (384 keys) and 4
slots, 1.9 MiB. Measured on one v5e (``benchmarks_dev/
latent_kernel_sweep.py``, ``results/latent_kernel_sweep_v5e.jsonl``; 32
rows, 32 heads, bf16): ~6.6k-token contexts 815 -> 446 us a call, 512-4,224
362 -> 221; the copies alone take as long, so what is left above the
roofline is the copies' own pace (~640 GB/s in 20 KB pieces), the 1,280 B a
row lies in for 1,152 counted, and the dead rows of each context's last
tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlti_tpu.ops.pallas.flash_attention import out_struct
from dlti_tpu.ops.pallas.paged_attention import (
    NEG_INF, live_tiles,
)


# Keys one step of the loop covers, at most (a tile is a whole number of
# blocks), and slots of the ring, at most: where the kernel alone stopped
# gaining on one v5e at both cells' shapes (PERF.md section 6, PR 55;
# benchmarks_dev/latent_kernel_sweep.py). 256 keys leave the body's fixed
# latency a larger share of a step, 512 copy more dead rows of short
# contexts; a fifth slot buys nothing.
TILE_KEYS = 384
RING_DEPTH = 4
# What the ring's slots may take of VMEM, beside the float32 scores and
# probabilities of two tiles inside the 16 MiB a kernel gets by default.
VMEM_RING_BUDGET = 4 * 1024 * 1024


def ring_shape(block_size: int, max_blocks: int, row_bytes: int):
    """``(T, depth)``: the blocks of a tile and the slots of the ring, from
    shapes alone. T: as many blocks as hold ``TILE_KEYS`` keys, no more than
    a row of the table has; the depth: as many slots as fit
    ``VMEM_RING_BUDGET`` at the pool's ``row_bytes`` (one token's row as it
    lies), ``RING_DEPTH`` at most; T halved until there are the three the
    loop needs (one a step's values come from, one its next scores come
    from, one being filled)."""
    T = max(1, min(TILE_KEYS // block_size, max_blocks))
    while T > 1 and 3 * T * block_size * row_bytes > VMEM_RING_BUDGET:
        T //= 2
    depth = VMEM_RING_BUDGET // (T * block_size * row_bytes)
    return T, max(3, min(RING_DEPTH, depth))


def tile_tokens(block_size: int, max_blocks: int, row_bytes: int) -> int:
    """Keys one step of the kernel holds (the engine's
    ``decode_kernel_tile_tokens`` counts in it)."""
    return ring_shape(block_size, max_blocks, row_bytes)[0] * block_size


def _decode_kernel(seq_lens_ref, blocks_ref, row_ref, tile_ref, total_ref,
                   q_ref, pool_hbm, o_ref, buf, sem, m_scratch, l_scratch,
                   acc_scratch, *, scale: float, block_size: int, tile: int,
                   depth: int, value_dim: int):
    T = tile
    keys = T * block_size
    batch, num_heads, _ = q_ref.shape
    table_width = blocks_ref.shape[0] // batch  # whole tiles a row, flat
    total = total_ref[0]

    # Rows no tile visits (seq_len == 0) read zero.
    o_ref[...] = jnp.zeros_like(o_ref)

    # Ring entry k is schedule entry min(k, total - 1) in slot k mod depth:
    # the entries past the schedule's end copy its last tile again, so that
    # the loop's body holds no branch (a branch ends the stretch of code the
    # compiler schedules as one: copies' address arithmetic, the MXU and the
    # softmax then run one after the other, not under each other).
    def entry(k):
        return jnp.minimum(k, total - 1)

    def start(k):
        """Ring entry k's T block copies, the physical blocks as the wrapper
        laid the table out: whole tiles, live blocks alone."""
        i, slot = entry(k), jax.lax.rem(k, depth)
        first = row_ref[i] * table_width + tile_ref[i] * T
        for t in range(T):
            pltpu.make_async_copy(
                pool_hbm.at[blocks_ref[first + t]],
                buf.at[slot, pl.ds(t * block_size, block_size)],
                sem.at[slot]).start()

    def wait(k):
        """One wait for the slot's whole byte count: its T copies signal
        one semaphore."""
        slot = jax.lax.rem(k, depth)
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()

    k_in_tile = jax.lax.broadcasted_iota(jnp.int32, (num_heads, keys), 1)
    # Stated, not left to the process's default: bf16 operands take one
    # MXU pass whatever ``jax_default_matmul_precision`` says.
    precision = jax.lax.Precision.HIGHEST \
        if buf.dtype == jnp.float32 else jax.lax.Precision.DEFAULT

    def scores(k):
        """Ring entry k's masked scores, (heads, keys) float32."""
        i, slot = entry(k), jax.lax.rem(k, depth)
        row, j = row_ref[i], tile_ref[i]
        rows = buf[slot]                                   # (keys, latent)
        q = q_ref[row].astype(rows.dtype)                  # (heads, latent)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        ) * scale
        return jnp.where(j * keys + k_in_tile < seq_lens_ref[row], s, NEG_INF)

    def reset():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    def step(i, s):
        # Tile i + depth - 1 goes into the slot step i - 1 took its values
        # from; tile i + 1 (sent depth - 2 steps ago) is scored while this
        # tile's softmax and values run: the two share no operand but the
        # slots, so the MXU's and the reductions' latencies hide under each
        # other.
        start(i + depth - 1)
        wait(i + 1)
        s_next = scores(i + 1)
        slot = jax.lax.rem(i, depth)
        row, j = row_ref[i], tile_ref[i]

        m_prev = m_scratch[...]                            # (heads, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scratch[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc_scratch[...] * alpha + jax.lax.dot_general(
            p.astype(buf.dtype), buf[slot, :, :value_dim],
            (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        )
        m_scratch[...] = m_new
        l_scratch[...] = l_new
        acc_scratch[...] = acc_new

        @pl.when(j == (seq_lens_ref[row] - 1) // keys)
        def _finalize():
            o_ref[row] = (acc_new / l_new).astype(o_ref.dtype)
            reset()                                        # the next row's

        return s_next

    @pl.when(total > 0)
    def _run():
        reset()
        for k in range(depth - 1):
            start(k)
        wait(0)
        jax.lax.fori_loop(0, total, step, scores(0))
        # What the last steps sent past the schedule's end.
        for k in range(1, depth - 1):
            wait(total + k)


# Jitted on its own, as the paged kernel is: every layer of a decode program
# shares one trace and one lowering of a body that unrolls depth x T copies.
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
    T, depth = ring_shape(block_size, max_blocks,
                          latent_dim * pool.dtype.itemsize)
    keys = T * block_size
    steps = batch * pl.cdiv(max_blocks, T)

    # Physical ids must be in range whatever the table holds.
    bt = jnp.clip(block_tables, 0, num_blocks - 1).astype(jnp.int32)
    seq_lens = jnp.minimum(seq_lens.astype(jnp.int32), max_blocks * block_size)
    row, tile, total = live_tiles(seq_lens, keys, 0, steps)
    # The table as the kernel reads it, once a decode step (every layer's
    # call shares it): flat, whole tiles a row, and every block past a row's
    # context names the row's last live block instead (masked by position in
    # the kernel), so a tile holds pool data alone and what the table holds
    # past a context never reaches the kernel.
    last = jnp.maximum(seq_lens - 1, 0) // block_size
    bt = jnp.pad(bt, ((0, 0), (0, -max_blocks % T)))
    bt = jnp.where(jnp.arange(bt.shape[1])[None, :] <= last[:, None], bt,
                   jnp.take_along_axis(bt, last[:, None], axis=1)).reshape(-1)

    def whole(shape):
        return pl.BlockSpec(shape, lambda g, *_: (0,) * len(shape))

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_size=block_size, tile=T, depth=depth,
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
                pltpu.VMEM((depth, keys, latent_dim), pool.dtype),
                pltpu.SemaphoreType.DMA((depth,)),
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
