"""Attention ops.

The reference delegates attention to HF ``LlamaAttention`` CUDA paths; here
we provide:

* :func:`multi_head_attention` — XLA reference implementation (einsum-based,
  GQA-capable, causal + padding masks). XLA fuses this well on TPU and it is
  the numerically-trusted baseline for kernel tests.
* A Pallas flash-attention path (``dlti_tpu.ops.pallas.flash_attention``)
  selected via ``ModelConfig.attention_impl`` — blockwise, never materializes
  the (seq, seq) score matrix, keeps the MXU fed at long sequence lengths.

Which implementation a call site gets is decided here and nowhere else:
:func:`resolve_flash` for training/prefill-shaped self-attention,
:func:`resolve_paged_decode` for the serving engine's one-token decode over
the paged pool. Both answer ``(path, reason)`` with path one of
``"pallas"`` (the kernel, compiled for the TPU), ``"pallas-interpret"``
(the kernel, interpreted — the CPU backend only, for tests) or ``"xla"``
(the reference implementation). The trainer and the engine log the answer
once at build time. There is no fallback from a kernel to the reference: a
kernel that fails to compile fails the run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(b, s, kv_heads, d) -> (b, s, kv_heads * n_rep, d) for GQA."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def make_causal_mask(q_len: int, kv_len: int, dtype=jnp.float32,
                     window: int | None = None) -> jnp.ndarray:
    """Additive causal mask of shape (1, 1, q_len, kv_len).

    Supports q_len < kv_len (decode with cache): query i attends to
    kv positions <= (kv_len - q_len + i). ``window`` adds Mistral-style
    sliding-window locality: only the last ``window`` positions (query
    included) stay visible.
    """
    q_pos = jnp.arange(q_len)[:, None] + (kv_len - q_len)
    kv_pos = jnp.arange(kv_len)[None, :]
    allowed = kv_pos <= q_pos
    if window is not None:
        allowed &= kv_pos > q_pos - window
    return jnp.where(allowed, 0.0, jnp.finfo(dtype).min)[None, None, :, :].astype(dtype)


def reference_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    segment_ids: jnp.ndarray | None = None,
    kv_segment_ids: jnp.ndarray | None = None,
    q_positions: jnp.ndarray | None = None,
    kv_positions: jnp.ndarray | None = None,
    window: int | None = None,
    softmax_dtype=jnp.float32,
) -> jnp.ndarray:
    """Plain XLA attention. q: (b, sq, h, d); k/v: (b, skv, h_kv, d).

    Softmax is computed in float32 (TPU-friendly: bf16 matmuls on the MXU,
    fp32 VPU reductions). ``segment_ids`` enables packed-sequence masking:
    tokens attend only within their own segment; id 0 = padding.
    ``q_positions``/``kv_positions`` (b, s) give explicit token positions for
    causal masking — required for KV-cached decode where the cache capacity
    exceeds the written region (slot index == position by construction).
    ``window`` is Mistral-style sliding-window locality (needs ``causal``).
    """
    b, sq, num_heads, head_dim = q.shape
    num_kv = k.shape[2]
    k = repeat_kv(k, num_heads // num_kv)
    v = repeat_kv(v, num_heads // num_kv)

    scale = head_dim ** -0.5
    # (b, h, sq, skv) scores on the MXU in compute dtype, accumulated fp32.
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=softmax_dtype)
    scores = scores.astype(softmax_dtype) * scale

    skv = k.shape[1]
    if causal:
        if q_positions is not None:
            kv_pos = (kv_positions if kv_positions is not None
                      else jnp.broadcast_to(jnp.arange(skv)[None, :], (b, skv)))
            allowed = kv_pos[:, None, :] <= q_positions[:, :, None]
            if window is not None:
                allowed &= kv_pos[:, None, :] > q_positions[:, :, None] - window
            scores = scores + jnp.where(
                allowed, 0.0, jnp.finfo(softmax_dtype).min
            )[:, None, :, :].astype(softmax_dtype)
        else:
            scores = scores + make_causal_mask(sq, skv, softmax_dtype,
                                               window=window)
    if segment_ids is not None:
        kv_seg = kv_segment_ids if kv_segment_ids is not None else segment_ids
        same = (segment_ids[:, :, None] == kv_seg[:, None, :]) & (kv_seg[:, None, :] != 0)
        scores = jnp.where(same[:, None, :, :], scores, jnp.finfo(softmax_dtype).min)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=softmax_dtype)
    return out.astype(q.dtype)


# A prefill call over the paged cache either gathers each row's whole table
# and scores it at once (``kv_cache.paged_gather`` + ``reference_attention``)
# or walks the table a block of keys at a time (``attend_over_cache``). The
# gather reads and scores a table's every key whatever the row's context, so
# it stays where a table is short: up to this many keys a row (the dense
# family's serving cells hand a call the narrowest power of two of blocks
# that holds its rows, 4,096 keys at most, and keep the programs they had).
GATHER_MAX_KEYS = 8192
# Keys a step of the walk covers (whole blocks) and queries a walk takes at
# a time: the float32 scores of a step are (rows, heads, WALK_QUERIES,
# WALK_KEYS), 64 MiB a row at 64 heads.
WALK_KEYS = 512
WALK_QUERIES = 512
NEG_INF = -1e30


def walks_cache(queries: int, table_keys: int, released: bool) -> bool:
    """Whether a call of ``queries`` tokens a row over a table of
    ``table_keys`` keys a row walks the cache: from shapes alone. A decode
    step off the kernel (one query) gathers; a table longer than
    ``GATHER_MAX_KEYS`` is walked, and so is one whose head has been
    ``released`` (a window group's: the walk skips what a window hides)."""
    return queries > 1 and (released or table_keys > GATHER_MAX_KEYS)


def attend_over_cache(q, layer_cache, block_tables, positions,
                      window: int | None = None):
    """This call's queries against each row's cached keys and values (its
    own just written among them), ``WALK_KEYS`` keys at a time with an
    online softmax: never a (heads, queries, context) array.

    ``q`` (b, s, heads, d); ``positions`` (b, s), a key's index in its
    row's table being its position (-1: a padding token, which sees
    nothing and reads 0). Queries go ``WALK_QUERIES`` at a time, and a
    walk runs from the block that holds the lowest key its queries can see
    (their lowest position less the window; block 0 without one) to their
    highest position: the trip count is data, so one program serves a
    (rows, tokens) shape whatever the cached context."""
    from dlti_tpu.ops.kv_cache import paged_gather, pool_kv_heads

    b, s, num_heads, d = q.shape
    block_size = layer_cache["k"].shape[1]
    kv_heads = pool_kv_heads(layer_cache, d)
    blocks = max(1, WALK_KEYS // block_size)
    keys = blocks * block_size
    tables = jnp.pad(block_tables,
                     ((0, 0), (0, -block_tables.shape[1] % blocks)))
    group = num_heads // kv_heads
    scale = d ** -0.5
    far = tables.shape[1] * block_size  # past every position

    def walk(qb, pos):
        """qb (b, n, kv_heads, group, d), pos (b, n) -> (b, n, heads, d)."""
        n = pos.shape[1]
        last = jnp.max(pos) // keys + 1
        lowest = jnp.min(jnp.where(pos >= 0, pos, far))
        first = jnp.maximum(lowest - window + 1, 0) // keys if window else 0

        def step(j, carry):
            m, l, acc = carry
            k, v = paged_gather(layer_cache, jax.lax.dynamic_slice_in_dim(
                tables, j * blocks, blocks, axis=1), d)
            k, v = k.astype(q.dtype), v.astype(q.dtype)
            scores = jnp.einsum("bngqd,bkgd->bgqnk", qb, k,
                                preferred_element_type=jnp.float32) * scale
            k_pos = (j * keys + jnp.arange(keys))[None, None, :]
            visible = k_pos <= pos[:, :, None]
            if window:
                visible &= k_pos > pos[:, :, None] - window
            visible = visible[:, None, None]              # (b, 1, 1, n, k)
            scores = jnp.where(visible, scores, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
            p = jnp.exp(scores - m_new) * visible
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, -1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bgqnk,bkgd->bgqnd", p.astype(q.dtype), v,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        shape = (b, kv_heads, group, n)
        m, l, acc = jax.lax.fori_loop(
            first, last, step,
            (jnp.full((*shape, 1), NEG_INF, jnp.float32),
             jnp.zeros((*shape, 1), jnp.float32),
             jnp.zeros((*shape, d), jnp.float32)))
        out = acc / jnp.maximum(l, 1e-30)   # a padding token saw no key
        return jnp.moveaxis(out, 3, 1).reshape(b, n, num_heads, d)

    with jax.named_scope("dlti_attn_over_cache"):
        qg = q.reshape(b, s, kv_heads, group, d)
        out = [walk(qg[:, at:at + WALK_QUERIES],
                    positions[:, at:at + WALK_QUERIES])
               for at in range(0, s, WALK_QUERIES)]
        return jnp.concatenate(out, axis=1).astype(q.dtype)


def _kernel_path() -> str:
    """``"pallas"`` on the TPU, ``"pallas-interpret"`` on the CPU backend;
    no other backend has a Pallas path in this repo."""
    backend = jax.default_backend()
    if backend == "tpu":
        return "pallas"
    if backend == "cpu":
        return "pallas-interpret"
    raise RuntimeError(
        f"the Pallas kernels target the TPU (interpreted on CPU for tests); "
        f"backend {backend!r} has neither — select the reference "
        f"implementation explicitly")


def resolve_flash(impl: str, *, seq_q: int, seq_kv: int, head_dim: int,
                  causal: bool = True) -> tuple:
    """``(path, reason)`` for self-attention under ``ModelConfig.attention_impl``.

    ``"auto"`` takes the kernel on TPU for causal self-attention whose
    sequence length and head_dim are multiples of 128 (the kernel's tile
    alignment), and the XLA reference otherwise (CPU tests, tiny shapes).
    """
    if impl == "reference":
        return "xla", "attention_impl=reference"
    if impl == "flash":
        return _kernel_path(), "attention_impl=flash"
    if impl != "auto":
        raise ValueError(f"unknown attention_impl {impl!r}")
    if jax.default_backend() != "tpu":
        return "xla", "auto takes the kernel on TPU only"
    if not causal:
        return "xla", "non-causal attention"
    if seq_q != seq_kv or seq_q % 128 or head_dim % 128:
        return "xla", (f"unaligned shape (seq {seq_q}x{seq_kv}, head_dim "
                       f"{head_dim}; the kernel tiles in multiples of 128)")
    return "pallas", "auto on TPU, tile-aligned"


def resolve_paged_decode(impl: str, *, tp_sharded: bool) -> tuple:
    """``(path, reason)`` for one-token decode over the paged KV pool under
    ``ModelConfig.paged_attention_impl`` ("auto" | "kernel" | "gather").

    Under a tensor-parallel mesh the pool is kv_head-sharded and
    ``pallas_call`` has no SPMD partitioning rule (GSPMD would all-gather
    the whole pool), so TP serving takes the sharded-einsum gather path
    whatever ``impl`` says.
    """
    if impl == "gather":
        return "xla", "paged_attention_impl=gather"
    if impl not in ("auto", "kernel"):
        raise ValueError(f"unknown paged_attention_impl {impl!r}")
    if tp_sharded:
        return "xla", "TP-sharded KV pool (pallas_call has no SPMD rule)"
    if impl == "kernel":
        return _kernel_path(), "paged_attention_impl=kernel"
    if jax.default_backend() != "tpu":
        return "xla", "auto takes the kernel on TPU only"
    return "pallas", "auto on TPU"


@functools.partial(
    jax.jit, static_argnames=("causal", "impl", "block_q", "block_kv",
                              "window")
)
def multi_head_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    segment_ids: jnp.ndarray | None = None,
    impl: str = "auto",
    block_q: int = 512,
    block_kv: int = 512,
    window: int | None = None,
) -> jnp.ndarray:
    """Dispatching attention entry point used by the model.

    impl: "reference" | "flash" | "auto" (see :func:`resolve_flash`).
    Packed batches run on either path (segment masking runs inside the
    kernel), and so does a sliding ``window`` (flash skips whole blocks
    outside the band).

    A Mosaic kernel has no GSPMD partitioning rule: under a mesh the caller
    runs this per shard (``parallel.ring_attention.per_shard_attention``).
    """
    path, _ = resolve_flash(impl, seq_q=q.shape[1], seq_kv=k.shape[1],
                            head_dim=q.shape[3], causal=causal)
    if path == "xla":
        return reference_attention(q, k, v, causal=causal,
                                   segment_ids=segment_ids, window=window)
    from dlti_tpu.ops.pallas.flash_attention import flash_attention

    return flash_attention(
        q, k, v, segment_ids=segment_ids, causal=causal, block_q=block_q,
        block_kv=block_kv, window=window,
        interpret=path == "pallas-interpret")
