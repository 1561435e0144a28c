"""Ring attention: exact attention over a sequence-sharded mesh axis.

The reference "scales sequence length" by truncating every sample to 512
tokens (``train_baseline.py:155``; SURVEY.md §5.7) and ships no sequence /
context parallelism of any kind. Here long-context is first-class: the
``sequence`` mesh axis shards the *length* dimension of activations, and
attention — the only op that mixes positions — is computed exactly with a
ring schedule (Liu et al., "Ring Attention with Blockwise Transformers"):

* Each device holds one contiguous sequence chunk of Q, K, V.
* For ``sequence`` axis size N, the ring runs N steps. At step t a device
  computes blockwise attention of its local Q chunk against the K/V chunk
  it currently holds, folding the result into an online-softmax
  accumulator (the same m/l/acc recurrence as flash attention), then
  passes K/V to its ring neighbor with ``jax.lax.ppermute``.
* ``ppermute`` is a neighbor-exchange, so on TPU the transfer rides a
  single ICI hop per step and XLA overlaps it with the block matmuls —
  communication is hidden behind compute for all but tiny chunk sizes.
* Causal masking is driven by explicit *token positions* that travel the
  ring alongside K/V, so the mask always agrees with the RoPE positions
  the caller embedded — including shifted/custom position schemes. Chunks
  that are entirely in the future (``min(kv_pos) > max(q_pos)``) skip
  their matmuls via ``lax.cond``, so a causal ring does ~half the FLOPs
  of a full one, like any flash-attention kernel.

K/V travel in *unexpanded* GQA form (``num_kv_heads``) and are repeated to
``num_heads`` only inside the local block product, so ring traffic is
proportional to the KV width, not the Q width.

Composition with the other axes: batch dims stay sharded over
``('data','fsdp')`` and the head dim over ``'tensor'`` (when divisible) —
the ring only communicates along ``'sequence'``, so TP×SP×DP all compose
inside one ``shard_map``. The wrapper is differentiable (``ppermute``
transposes to the reverse ring), so the same code path serves training.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dlti_tpu.ops.attention import reference_attention, repeat_kv
from dlti_tpu.parallel.mesh import BATCH_AXES, in_manual_region

# Finite stand-in for -inf. Keeps every exp()/max() total (no inf-inf=NaN
# corner) while exp(NEG_INF - anything_finite) underflows to exactly 0.
NEG_INF = -1e30


def _block_accumulate(carry, q, k, v, q_pos, kv_pos, q_seg, kv_seg, scale,
                      causal, window):
    """Fold one K/V chunk into the online-softmax state.

    carry: (m, l, acc) with m,l (b, h, sq) fp32 and acc (b, sq, h, d) fp32.
    q: (b, sq, h, d); k/v: (b, sk, hk, d); q_pos/kv_pos: (b, sq)/(b, sk)
    global token positions driving the causal (and sliding-window) mask;
    q_seg/kv_seg: optional (b, sq)/(b, sk) segment ids for packed batches
    (id 0 = padding, matching ``reference_attention``).
    """
    m, l, acc = carry
    kr = repeat_kv(k, q.shape[2] // k.shape[2])
    vr = repeat_kv(v, q.shape[2] // v.shape[2])

    # (b, h, sq, sk) scores, fp32 accumulation on the MXU.
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr, preferred_element_type=jnp.float32)
    s = s.astype(jnp.float32) * scale
    allowed = None
    if causal:
        # (b, 1, sq, sk): kv token visible iff its position <= the query's.
        allowed = kv_pos[:, None, None, :] <= q_pos[:, None, :, None]
        if window:
            allowed &= kv_pos[:, None, None, :] > (q_pos[:, None, :, None]
                                                   - window)
    if q_seg is not None:
        same = ((q_seg[:, None, :, None] == kv_seg[:, None, None, :])
                & (kv_seg[:, None, None, :] != 0))
        allowed = same if allowed is None else (allowed & same)
    if allowed is not None:
        s = jnp.where(allowed, s, NEG_INF)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if allowed is not None:
        # Fully-masked rows have m_new == NEG_INF, making exp(s - m_new)
        # == 1 at every masked entry — zero them explicitly.
        p = jnp.where(allowed, p, 0.0)
    alpha = jnp.exp(m - m_new)  # (b, h, sq)

    l_new = alpha * l + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vr.dtype), vr,
                    preferred_element_type=jnp.float32)
    acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv.astype(jnp.float32)
    return m_new, l_new, acc_new


def ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    q_seg: Optional[jnp.ndarray] = None,
    *,
    axis_name: str,
    axis_size: int,
    causal: bool = True,
    window: int = 0,
) -> jnp.ndarray:
    """Per-shard ring attention body. Must run under ``shard_map`` with
    ``axis_name`` bound; each call sees the local (b, s_local, h|hk, d)
    chunks of globally (b, s, h|hk, d) arrays sharded on dim 1, and the
    matching local slices of token positions ``q_pos`` (b, s_local) and
    (for packed batches) segment ids ``q_seg`` (b, s_local).
    """
    b, sq, h, d = q.shape
    scale = d ** -0.5

    m = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    acc = jnp.zeros((b, sq, h, d), jnp.float32)

    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
    kv_pos = q_pos
    kv_seg = q_seg
    carry = (m, l, acc)
    for t in range(axis_size):
        # Runtime whole-chunk skips (the ring analog of flash's block
        # skipping). All are conservative: a skipped chunk provably
        # contributes nothing to any row.
        skip = None
        if causal:
            # Chunk entirely in the future for every row. With default
            # contiguous positions this reduces to the classic "source
            # shard index > mine" skip (~half the ring FLOPs).
            skip = jnp.min(kv_pos) > jnp.max(q_pos)
            if window:
                # Chunk entirely behind every row's sliding window.
                skip |= jnp.max(kv_pos) <= jnp.min(q_pos) - window
        if q_seg is not None:
            # Segment-id intervals disjoint -> no equal pair can exist.
            seg_disjoint = jnp.logical_or(
                jnp.min(q_seg) > jnp.max(kv_seg),
                jnp.max(q_seg) < jnp.min(kv_seg))
            skip = seg_disjoint if skip is None else (skip | seg_disjoint)

        if skip is not None:
            carry = jax.lax.cond(
                skip,
                lambda op: op[0],
                lambda op: _block_accumulate(op[0], q, op[1], op[2],
                                             q_pos, op[3], q_seg, op[4],
                                             scale, causal, window),
                (carry, k, v, kv_pos,
                 kv_seg if kv_seg is not None else kv_pos),
            )
        else:
            carry = _block_accumulate(carry, q, k, v, q_pos, kv_pos, q_seg,
                                      kv_seg, scale, causal, window)

        if t != axis_size - 1:
            k = jax.lax.ppermute(k, axis_name, perm)
            v = jax.lax.ppermute(v, axis_name, perm)
            kv_pos = jax.lax.ppermute(kv_pos, axis_name, perm)
            if kv_seg is not None:
                kv_seg = jax.lax.ppermute(kv_seg, axis_name, perm)

    _, l, acc = carry
    # Fully-masked rows (padding tokens in packed batches) have l == 0 and
    # acc == 0: the max() guard makes their output exactly zero.
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _qkv_spec(mesh: Mesh, q, k, seq_axis=None, batch_axes: tuple = BATCH_AXES,
              head_axis: str = "tensor") -> P:
    """Layout of (b, s, heads, d) attention operands over the mesh: rows
    over the batch axes, heads over ``head_axis`` only when both head
    counts divide (each shard keeps whole GQA groups)."""
    tp = mesh.shape.get(head_axis, 1)
    h_ax = head_axis if (tp > 1 and q.shape[2] % tp == 0
                         and k.shape[2] % tp == 0) else None
    return P(batch_axes, seq_axis, h_ax, None)


def per_shard_attention(attend, q, k, v, mesh: Optional[Mesh],
                        segment_ids=None) -> jnp.ndarray:
    """Run ``attend(q, k, v, segment_ids=)`` on each device's own rows and
    heads: the way a Pallas kernel runs under a mesh.

    A Mosaic kernel cannot be partitioned by GSPMD ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map" —
    what sharded training answered on the v5e), and self-attention never
    mixes batch rows or heads, so each shard computes its own: rows over
    the batch axes, heads over 'tensor' (:func:`_qkv_spec`); the sequence
    stays whole. Inside an enclosing manual region (a pipeline stage) the
    call is left as it is — a nested shard_map is untrainable on this jax —
    and the trainer refuses that combination on a TPU
    (``training.trainer._validate_pipeline_config``).
    """
    if mesh is None or mesh.size == 1 or in_manual_region():
        return attend(q, k, v, segment_ids=segment_ids)
    spec = _qkv_spec(mesh, q, k)
    packed = () if segment_ids is None else (segment_ids,)
    return jax.shard_map(
        lambda q, k, v, *seg: attend(q, k, v,
                                     segment_ids=seg[0] if seg else None),
        mesh=mesh, in_specs=(spec, spec, spec) + (P(BATCH_AXES, None),) * len(packed),
        out_specs=spec, check_vma=False)(q, k, v, *packed)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    positions: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    causal: bool = True,
    window: Optional[int] = None,
    seq_axis: str = "sequence",
    batch_axes: tuple = BATCH_AXES,
    head_axis: str = "tensor",
) -> jnp.ndarray:
    """Global-view ring attention entry point (callable inside ``jit``).

    q: (b, s, h, d); k/v: (b, s, hk, d) — *global* shapes; the wrapper
    shard_maps them as P(batch_axes, seq_axis, head_axis?, None).
    ``positions`` (b, s) are the token positions RoPE was applied at; the
    causal mask is computed from them so the two can never disagree
    (default: contiguous 0..s-1). ``segment_ids`` (b, s) enables packed
    batches (tokens attend within their own segment; id 0 = padding,
    producing zero output rows); the ids travel the ring with K/V and
    segment-disjoint chunks skip their matmuls. ``window`` is
    Mistral-style sliding-window locality (requires ``causal``); chunks
    entirely behind every query's window are skipped, so a long ring
    does O(window) work per query, not O(seq). The head dim is sharded
    over ``head_axis`` (TP) only when both h and hk divide; otherwise
    heads stay replicated and GSPMD reconciles with the surrounding
    layout.
    """
    n = mesh.shape[seq_axis]
    if n == 1:
        return reference_attention(
            q, k, v, causal=causal, segment_ids=segment_ids,
            q_positions=positions, kv_positions=positions, window=window,
        )
    b, s = q.shape[0], q.shape[1]
    if s % n != 0:
        raise ValueError(
            f"ring attention: seq len {s} not divisible by "
            f"{seq_axis} axis size {n}"
        )
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :],
                                     (b, s))
    else:
        positions = jnp.broadcast_to(positions.astype(jnp.int32), (b, s))

    spec = _qkv_spec(mesh, q, k, seq_axis, batch_axes, head_axis)
    pos_spec = P(batch_axes, seq_axis)

    # Inside an enclosing shard_map (PP x SP: the pipe schedule is manual
    # over 'pipe'), a NESTED manual ring is off the table on this jax: a
    # nested shard_map either computes silently wrong gradients
    # (check_vma=False skips the transpose's replication psums — measured
    # embed grads off by 17-370x) or fails verification/lowering
    # (check_vma=True: cond-branch vma mismatches in the skip cond's
    # transpose, then an sdy.manual_computation local-shape error).
    # Delegate to reference_attention instead and let GSPMD partition it
    # over the AUTO 'sequence' axis — all-gather-style sequence
    # parallelism: activations stay sequence-sharded outside attention,
    # XLA inserts the k/v gathers, numerics and gradients are exact by
    # construction (no nested manual region at all). The flat path below
    # keeps the true ring schedule.
    if in_manual_region():
        return reference_attention(
            q, k, v, causal=causal, segment_ids=segment_ids,
            q_positions=positions, kv_positions=positions, window=window,
        )

    body = functools.partial(
        ring_attention_local, axis_name=seq_axis, axis_size=n, causal=causal,
        window=int(window or 0),
    )
    if segment_ids is None:
        f = jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec, pos_spec),
            out_specs=spec, check_vma=False,
        )
        return f(q, k, v, positions)
    segment_ids = jnp.broadcast_to(segment_ids.astype(jnp.int32), (b, s))
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec, spec, spec, pos_spec, pos_spec),
        out_specs=spec, check_vma=False,
    )
    return f(q, k, v, positions, segment_ids)
