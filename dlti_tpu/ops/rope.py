"""Rotary position embeddings (RoPE), Llama-style.

The reference gets RoPE implicitly through HF ``LlamaModel``
(``training/train_baseline.py:122-126`` loads ``meta-llama/Llama-2-7b-hf``);
here it is implemented directly. Uses the split-half rotation convention
(matching HF Llama), computed in float32 for numerical parity and cast back
to the compute dtype. ``interleaved`` rotates the pairs ``(2i, 2i + 1)``
instead (``rope_interleave`` of the deepseek_v3 family), each pair left
where it lies.
"""

from __future__ import annotations

import jax.numpy as jnp


def assert_rope_table_covers(table_len: int, needed_len: int,
                             context: str = "") -> None:
    """Trace-time guard for the table-sizing invariant.

    :func:`apply_rope` gathers with ``mode="clip"`` (no per-gather bounds
    check — see the comment there), so an under-sized cos/sin table no
    longer NaNs loudly: it silently clamps rotary angles (the r03 bug
    class, seq 512 > table 128). Call this wherever the maximum position
    is STATICALLY known (both arguments are Python ints at trace time —
    sequence lengths and table sizes are static under jit), so a future
    mis-sized caller fails at trace time instead of training on wrong
    rotations.
    """
    if table_len < needed_len:
        raise ValueError(
            f"RoPE table of length {table_len} cannot cover positions up "
            f"to {needed_len - 1}{' (' + context + ')' if context else ''}; "
            "apply_rope gathers with mode='clip' and would silently clamp "
            "rotary angles — size the table to >= max position + 1")


def rope_frequencies(head_dim: int, max_seq_len: int, theta: float = 10000.0) -> tuple:
    """Precompute cos/sin tables of shape ``(max_seq_len, head_dim // 2)``."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # (seq, head_dim//2)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               positions: jnp.ndarray,
               interleaved: bool = False) -> jnp.ndarray:
    """Rotate ``x`` of shape (batch, seq, heads, head_dim) by position.

    ``positions`` is (batch, seq) int32 — explicit so the same op serves
    packed sequences and KV-cached decode (where position != index).
    Frequency ``i`` turns the pair ``(i, i + head_dim / 2)``, or with
    ``interleaved`` the pair ``(2i, 2i + 1)``.
    """
    orig_dtype = x.dtype
    half = x.shape[-1] // 2
    # Gather per-token tables: (batch, seq, half) -> broadcast over heads.
    # mode="clip", not the default "fill": positions are in-range by
    # construction (callers size the table to cover the actual sequence —
    # models/llama.py sizes it past max_seq_len), the NaN-fill bounds
    # check costs a lax.cond per gather, and that cond's branches type
    # differently under nested shard_map vma checking (PP x SP: the fill
    # branch is device-invariant while the gather branch varies over
    # 'pipe') — clip has no cond at all.
    cos_p = jnp.take(cos, positions, axis=0,
                     mode="clip")[:, :, None, :].astype(jnp.float32)
    sin_p = jnp.take(sin, positions, axis=0,
                     mode="clip")[:, :, None, :].astype(jnp.float32)
    x = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        rotated = jnp.stack(
            [x1 * cos_p - x2 * sin_p, x2 * cos_p + x1 * sin_p], axis=-1
        ).reshape(x.shape)
        return rotated.astype(orig_dtype)
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos_p - x2 * sin_p, x2 * cos_p + x1 * sin_p], axis=-1
    )
    return rotated.astype(orig_dtype)
