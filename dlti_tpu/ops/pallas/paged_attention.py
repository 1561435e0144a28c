"""Paged decode attention for TPU, in Pallas.

The serving engine's decode hot op. The XLA fallback path
(``dlti_tpu.ops.kv_cache.paged_gather`` + ``reference_attention``)
materializes each sequence's whole logical KV window in HBM every step —
O(batch * max_len) extra traffic. This kernel instead walks the block table
and reads K/V blocks *in place* from the physical pool, a tile of many
blocks at a time, with an online softmax — the TPU analog of vLLM's
PagedAttention CUDA kernel (the reference claims that engine via
``requirements.txt:18`` but ships no code; SURVEY.md §2b).

Design:

* **One software pipeline over the live tiles of the whole batch.** A tile
  is T consecutive logical blocks of one sequence (``tile_blocks``: T x
  ``block_size`` = ``TILE_KEYS`` = 256 keys at the serving cells' shapes).
  ``live_tiles`` lists, in XLA and once a decode step for each (table,
  window) the layers' calls are given (the layers of one window share it: one
  schedule for most models, two where window and full layers are mixed),
  each sequence's tiles inside ``[seq_len - window, seq_len)``;
  the kernel has a grid of one step and a ``fori_loop`` over that list, so
  a tile past a context, or before a window, is never a step at all.
* **The pools stay in HBM** (``memory_space=ANY``). A step starts the next
  tile's 2T block copies (``make_async_copy``, physical block
  ``block_tables[row, j * T + t]`` read from SMEM) into the other of two
  VMEM slots, then waits for its own. A block past the context inside the
  last live tile copies the row's last live block again and is masked by
  token position: a tile is always 2T copies and holds pool data alone.
* ``seq_lens``, ``block_tables`` and the schedule (row, tile, count) ride
  scalar prefetch; queries and outputs sit whole in VMEM, indexed by row.
* **No relayout of a tile.** The pool's rows are (token, kv head) pairs, so
  a tile reads as a ``(keys * kv_heads, head_dim)`` matrix as it lies. All
  query heads meet all of its rows in one 2-D product each way; a query
  head keeps the rows of its own kv head by the mask (the others get
  probability 0), which costs the MXU rows it has to spare and saves the
  ``swapaxes`` of every tile. GQA for free as before: KV heads are never
  repeated. Operands are float32 after the cast, as they always were.
* The online-softmax state ``(m, l, acc)`` lives in VMEM scratch across a
  sequence's tiles: reset at its first live tile, written out at its last.
* ``tile_blocks`` picks T from shapes alone (``block_size``, the table's
  width, one token's key bytes against ``VMEM_TILE_BUDGET`` for the 2 pools
  x 2 slots): never from a flag or a model's name.
* int8 pools: the wrapper gathers the scales by the table into one
  ``(1, keys * kv_heads)`` row a tile (a ``(block_size, kv_heads)`` block of
  scales is padded to 128 lanes in HBM and cannot be copied by hand); one
  more copy a tile and scale pool, folded in as ``s *= k_scale``,
  ``p *= v_scale``.

Measured on one v5e (PERF.md section 6, PR 34; the kernel alone, 32 rows,
256 blocks a row of 16, bf16; old = one block a grid step):

=====================  ======  =======================  =========
geometry, mean context  old     this kernel by keys      copies
                                128 / 256 / 512          alone
=====================  ======  =======================  =========
28 q / 4 kv, 934        1278    286 / 249 / 254 us       152 us
32 q / 8 kv, 467, w4096 1142    172 / 154 / 195 us       135 us
32 q / 2 kv, 861        990     274 / 252 / 249 us       151 us
=====================  ======  =======================  =========

What the old form's time was: BlockSpec pipelining copies a block every grid
step whether or not its index changed, so 8,192 steps x 2 pools were 16,384
copies a call at any context (618 us at 62-token contexts, where the dead
steps repeat one block). Passing the pool T times with T index maps (ISSUE
34's first form) keeps that count and gains nothing (1,100 / 1,820 / 579 us;
1,008 at 62-token contexts). Also tried: keeping the ``swapaxes`` and per-head batched products
(288 / 190 / 265 us at 256 keys), one kv head at a time through strided
loads (2,060 us in the first form), bf16 operands (no faster: the MXU is not
the bound), skipping dead blocks' copies instead of repeating the last
block (270 / 158 / 269 us), one wait a slot instead of one a copy (242 /
155 / 238 us: not worth leaning on how a DMA semaphore counts).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlti_tpu.ops.pallas.flash_attention import out_struct

NEG_INF = -1e30


# Keys one step of the kernel's loop covers, at most (a tile is a whole
# number of blocks).
TILE_KEYS = 256
# What the two-slot buffers of keys and values may take of VMEM (2 pools x 2
# slots x a tile), with the float32 working copies of one tile the body makes
# beside them inside the 16 MiB a kernel gets by default.
VMEM_TILE_BUDGET = 4 * 1024 * 1024


def tile_blocks(block_size: int, max_blocks: int, token_bytes: int = 0) -> int:
    """T: the logical blocks of one sequence a step of the kernel covers.

    From shapes alone: as many blocks as hold ``TILE_KEYS`` keys, no more
    than a row of the table has, halved until the buffers fit
    ``VMEM_TILE_BUDGET`` (``token_bytes``: one token's keys in the pool, all
    kv heads; 0 leaves the budget out)."""
    t = max(1, min(TILE_KEYS // block_size, max_blocks))
    while t > 1 and 4 * t * block_size * token_bytes > VMEM_TILE_BUDGET:
        t //= 2
    return t


def tile_tokens(block_size: int, max_blocks: int, token_bytes: int = 0) -> int:
    """Keys one step of the kernel holds: ``T * block_size``. A live context
    of n tokens costs ``ceil(n / tile_tokens) * tile_tokens`` keys of tiles
    (the engine's ``decode_kernel_tile_tokens``)."""
    return tile_blocks(block_size, max_blocks, token_bytes) * block_size


def live_tiles(seq_lens, keys: int, window: int, steps: int):
    """The kernel's schedule: every live tile of the batch, row by row.
    (A window group's call gives lengths counted from its table's first
    block, ``ops.kv_cache.bind_call``: the band is the same keys.)

    Returns ``(row, tile, total)``: entries ``[0, total)`` of ``row`` and
    ``tile`` (each ``(steps,)`` int32) name a sequence and one of its tiles
    of ``keys`` keys inside ``[seq_len - window, seq_len)``; a sequence of
    length 0 has none."""
    first = jnp.maximum(seq_lens - window, 0) // keys if window \
        else jnp.zeros_like(seq_lens)
    n = jnp.where(seq_lens > 0, (seq_lens - 1) // keys - first + 1, 0)
    ends = jnp.cumsum(n)
    i = jnp.arange(steps, dtype=jnp.int32)
    row = jnp.minimum((i[:, None] >= ends[None, :]).sum(axis=1),
                      seq_lens.shape[0] - 1)
    tile = first[row] + i - (ends[row] - n[row])
    return row.astype(jnp.int32), tile.astype(jnp.int32), \
        ends[-1:].astype(jnp.int32)


def _decode_kernel(seq_lens_ref, block_tables_ref, row_ref, tile_ref,
                   total_ref, q_ref, k_hbm, v_hbm, *rest,
                   scale: float, block_size: int, tile: int, window: int,
                   kv_heads: int, quantized: bool, fused: bool = False):
    if quantized:
        # int8 pools travel with their fp32 scales, already gathered by the
        # table and laid flat: one (1, keys * kv_heads) row a tile, in the
        # order of the tile's rows, so that the scales fold into the
        # attention math per kv position (s *= k_scale, p *= v_scale) and no
        # dequantized K/V tile is ever materialized.
        ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem, \
            m_scratch, l_scratch, acc_scratch = rest
    else:
        o_ref, kbuf, vbuf, sem, m_scratch, l_scratch, acc_scratch = rest
    T = tile
    keys = T * block_size
    num_heads = q_ref.shape[1]
    hpg = num_heads // kv_heads
    max_blocks = block_tables_ref.shape[1]
    total = total_ref[0]

    # Rows no tile visits (seq_len == 0) read zero.
    o_ref[...] = jnp.zeros_like(o_ref)

    def band(row):
        seq_len = seq_lens_ref[row]
        return seq_len, (jnp.maximum(seq_len - window, 0) if window else 0)

    def tile_copies(i, slot):
        """The copies of schedule entry ``i`` into ``slot``: one a pool and
        block of the tile. A block outside the row's band names the band's
        nearest block instead (masked by position below), so a tile is
        always T copies a pool and holds pool data alone."""
        row, j = row_ref[i], tile_ref[i]
        seq_len, lo = band(row)
        first = lo // block_size
        last = jnp.minimum((seq_len - 1) // block_size, max_blocks - 1)
        for t in range(T):
            phys = block_tables_ref[row, jnp.clip(j * T + t, first, last)]
            for p, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                yield pltpu.make_async_copy(
                    hbm.at[phys],
                    buf.at[slot, pl.ds(t * block_size, block_size)],
                    sem.at[p, slot])
        if quantized:
            for p, (hbm, buf) in enumerate(((ks_hbm, ksbuf), (vs_hbm, vsbuf))):
                yield pltpu.make_async_copy(hbm.at[row, j], buf.at[slot],
                                            sem.at[2 + p, slot])

    def start(i, slot):
        for copy in tile_copies(i, slot):
            copy.start()

    @pl.when(total > 0)
    def _first():
        start(0, 0)

    # Rows of a tile are (token, kv head) pairs, as the pool lays them out:
    # query head h meets the rows of its own kv head alone. (A fused pool
    # lays a token's kv heads side by side in one row: its tile is read a
    # head's lanes at a time, one below the other, rows (kv head, token).)
    shape = (num_heads, keys * kv_heads)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    group = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // hpg
    own_head = (col // keys if fused else col % kv_heads) == group
    token = col % keys if fused else col // kv_heads

    def flat(buf, slot):
        x = buf[slot].astype(jnp.float32)        # (keys, kvh, d) or fused
        if fused:
            d = x.shape[-1] // kv_heads
            return jnp.concatenate(
                [x[:, h * d:(h + 1) * d] for h in range(kv_heads)], axis=0)
        return x.reshape(keys * kv_heads, x.shape[-1])

    def step(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < total)
        def _next():
            start(i + 1, 1 - slot)

        for copy in tile_copies(i, slot):
            copy.wait()
        row, j = row_ref[i], tile_ref[i]
        seq_len, lo = band(row)

        @pl.when(j == lo // keys)
        def _init():
            m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
            l_scratch[...] = jnp.zeros_like(l_scratch)
            acc_scratch[...] = jnp.zeros_like(acc_scratch)

        q = q_ref[row].astype(jnp.float32)                 # (heads, d)
        k = flat(kbuf, slot)                               # (keys*kvh, d)
        v = flat(vbuf, slot)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (heads, keys*kvh)
        if quantized:
            s = s * ksbuf[slot]                            # (1, keys*kvh)
        k_pos = j * keys + token
        s = jnp.where(own_head & (k_pos >= lo) & (k_pos < seq_len), s, NEG_INF)

        m_prev = m_scratch[...]                            # (heads, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
        alpha = jnp.exp(m_prev - m_new)
        l_scratch[...] = alpha * l_scratch[...] \
            + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * vsbuf[slot]                            # (1, keys*kvh)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scratch[...] = m_new

        @pl.when(j == (seq_len - 1) // keys)
        def _finalize():
            o_ref[row] = (acc_scratch[...] / l_scratch[...]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, total, step, 0)


# Jitted on its own: every layer of a decode program (and every program of the
# engine's ladder) then shares one trace, and one lowering a program, of a
# kernel whose body unrolls 4T copies.
@functools.partial(jax.jit, static_argnames=("window", "interpret"))
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
      k_pool / v_pool: ``(num_blocks, block_size, kv_heads, head_dim)``, or
        FUSED ``(num_blocks, block_size, kv_heads * head_dim)``: a token's
        kv heads side by side in one row (``ops.kv_cache.init_paged_cache``:
        the layout of a model whose count of kv heads the 4-D tiling would
        pad; ``head_dim`` is the queries').
      block_tables: ``(batch, max_blocks_per_seq)`` int32; entries for
        unallocated logical blocks may be any value (they are never read:
        only live blocks of a row are copied).
      seq_lens: ``(batch,)`` int32 — tokens valid per sequence *including*
        the current one (i.e. query position + 1).
      k_scale / v_scale: for int8 pools, the ``(num_blocks, block_size,
        kv_heads)`` fp32 per-row scales (``ops.kv_cache`` int8 layout);
        folded into the attention math in place — required iff the pools
        are int8.
      window: Mistral-style sliding window — only the last ``window``
        positions stay visible; whole tiles outside the band are skipped.

    Returns ``(batch, 1, num_heads, head_dim)``.
    """
    batch, s1, num_heads, head_dim = q.shape
    assert s1 == 1, f"decode kernel takes single-token queries, got s={s1}"
    num_blocks, block_size = k_pool.shape[:2]
    fused = k_pool.ndim == 3
    kv_heads = k_pool.shape[2] // head_dim if fused else k_pool.shape[2]
    max_blocks = block_tables.shape[1]
    window = window or 0
    T = tile_blocks(block_size, max_blocks,
                    kv_heads * head_dim * k_pool.dtype.itemsize)
    keys = T * block_size
    n_tiles = pl.cdiv(max_blocks, T)
    steps = batch * n_tiles

    quantized = k_pool.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 KV pools require k_scale/v_scale")

    # Physical ids must be in range whatever the table holds.
    bt = jnp.clip(block_tables, 0, num_blocks - 1).astype(jnp.int32)
    seq_lens = jnp.minimum(seq_lens.astype(jnp.int32), max_blocks * block_size)
    row, tile, total = live_tiles(seq_lens, keys, window, steps)

    def whole(shape):
        return pl.BlockSpec(shape, lambda g, *_: (0,) * len(shape))

    in_hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    qo = (batch, num_heads, head_dim)
    operands = [q[:, 0], k_pool, v_pool]
    buffers = [pltpu.VMEM((2, keys, *k_pool.shape[2:]), k_pool.dtype),
               pltpu.VMEM((2, keys, *v_pool.shape[2:]), v_pool.dtype)]
    if quantized:
        if fused:
            raise ValueError("a fused pool has no int8 layout")
        # The scales of every row's tiles, gathered by the table here: a
        # (block_size, kv_heads) block of them is no shape to copy by hand
        # (its minor dimension is padded to 128 lanes in HBM).
        tiles = jnp.pad(bt, ((0, 0), (0, n_tiles * T - max_blocks)))
        for scales in (k_scale, v_scale):
            operands.append(scales[tiles].reshape(
                batch, n_tiles, 1, keys * kv_heads))
            buffers.append(pltpu.VMEM((2, 1, keys * kv_heads), scales.dtype))

    kernel = functools.partial(_decode_kernel, scale=head_dim ** -0.5,
                               block_size=block_size, tile=T, window=window,
                               kv_heads=kv_heads, quantized=quantized,
                               fused=fused)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[whole(qo)] + [in_hbm] * (len(operands) - 1),
            out_specs=whole(qo),
            scratch_shapes=buffers + [
                pltpu.SemaphoreType.DMA((len(buffers), 2)),
                pltpu.VMEM((num_heads, 1), jnp.float32),
                pltpu.VMEM((num_heads, 1), jnp.float32),
                pltpu.VMEM((num_heads, head_dim), jnp.float32),
            ],
        ),
        out_shape=out_struct(qo, q.dtype, q),
        interpret=interpret,
        # The name under which a device trace shows this kernel
        # (benchmark/lib/span_rules.json finds it by it); the scope round
        # the call keeps the model's own scopes out of that name.
        name="dlti_paged_attention_decode",
        # Every tile of every row, as if all were live.
        cost_estimate=pl.CostEstimate(
            flops=int(2 * 2 * num_heads * steps * keys * kv_heads * head_dim),
            bytes_accessed=int(
                2 * steps * keys * kv_heads * head_dim * k_pool.dtype.itemsize
                + 2 * q.size * q.dtype.itemsize),
            transcendentals=num_heads * steps * keys * kv_heads,
        ),
    )
    with jax.named_scope("dlti_paged_attention_decode"):
        out = call(seq_lens, bt, row, tile, total, *operands)

    return out[:, None]
