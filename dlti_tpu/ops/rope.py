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

import math
from typing import Optional

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


def yarn_correction_range(head_dim: int, theta: float, scaling: dict) -> tuple:
    """``(low, high)``: the rotary pairs below ``low`` turn more than
    ``beta_fast`` times over the original context and keep their frequency;
    those above ``high`` turn less than ``beta_slow`` times and are
    interpolated whole; between them the two are blended."""
    span = scaling["original_max_position_embeddings"]

    def pair_of(turns):
        return head_dim * math.log(span / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair_of(scaling.get("beta_slow", 1))), head_dim - 1)
    return low, high


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 m ln(factor) + 1`` (1 at factor <= 1)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _inv_freq(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_inv_freq(head_dim: int, theta: float, scaling: dict) -> jnp.ndarray:
    """YaRN's blended frequencies as the deepseek_v3 family writes them:
    pair ``i`` keeps ``theta^(-2i/d)`` by the share ``m_i`` and takes it
    divided by ``factor`` by the rest, ``m_i`` falling from 1 to 0 between
    :func:`yarn_correction_range`'s two pairs."""
    inv_freq = _inv_freq(head_dim, theta)
    low, high = yarn_correction_range(head_dim, theta, scaling)
    ramp = (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) \
        / max(high - low, 0.001)
    keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return inv_freq / scaling["factor"] * (1.0 - keep) + inv_freq * keep


def yarn_softmax_factor(scaling: Optional[dict]) -> float:
    """What the softmax scale is multiplied by under YaRN:
    ``yarn_mscale(factor, mscale_all_dim)^2`` (1 with no scaling or no
    ``mscale_all_dim``)."""
    if not scaling or not scaling.get("mscale_all_dim"):
        return 1.0
    return yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2


def rope_frequencies(head_dim: int, max_seq_len: int, theta: float = 10000.0,
                     scaling: Optional[dict] = None) -> tuple:
    """Precompute cos/sin tables of shape ``(max_seq_len, head_dim // 2)``.
    ``scaling`` (a public config's ``rope_scaling`` of type yarn): blended
    frequencies, and both tables times ``yarn_mscale(mscale) /
    yarn_mscale(mscale_all_dim)``."""
    inv_freq = yarn_inv_freq(head_dim, theta, scaling) if scaling \
        else _inv_freq(head_dim, theta)
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # (seq, head_dim//2)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    if scaling:
        amplitude = yarn_mscale(scaling["factor"], scaling.get("mscale", 1)) \
            / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0))
        if amplitude != 1.0:
            cos, sin = cos * amplitude, sin * amplitude
    return cos, sin


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
