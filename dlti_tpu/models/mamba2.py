"""Mamba-2 mixer (state-space duality layer), for prefill and for decode.

One layer of the ``nemotron_h`` family's "M" kind. Per head (H heads of P
channels, B and C shared by runs of H / G heads, state width N):

    xBC  = silu(causal depthwise conv1d(xBC, width K) + bias)
    dt   = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t  = exp(dt_t A) h_{t-1} + dt_t * x_t (outer) B_t        h in R^{P x N}
    y_t  = h_t C_t + D x_t
    out  = out_proj(RMSNorm_groups(y * silu(z)) * w)

Two programs compute the same recurrence:

* **a prompt** (``s > 1``): the chunked scan of the Mamba-2 paper (within a
  chunk of ``mamba_chunk_size`` tokens the recurrence is a masked matrix
  product; between chunks the state is carried by a short ``lax.scan``).
  The chunk size changes no result.
* **one token** (``s == 1``): the state update written out.

**The recurrent state** of a sequence is the last ``K - 1`` inputs of the
convolution and ``h``. In serving it lives in ``ops.kv_cache``'s pool,
addressed by decode slot. A prefill row that starts at position 0 starts
from a zero state; one that continues a chunk reads its slot's. Padding
(position -1, always trailing) advances nothing: ``dt`` is zeroed there, so
the state handed to the slot is the state after the last real token, and
the convolution tail is gathered at the last real inputs. A row whose slot
is out of range (a padding row, an idle decode row) writes nothing.
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlti_tpu.config import ModelConfig
from dlti_tpu.models.lora import LoRADense
from dlti_tpu.utils.dtypes import resolve_dtype as _dtype

# What shapes the seeded dt_bias (the published time_step_min / _max /
# _floor of Mamba-2 and of nemotron_h): softplus(dt_bias) is log-uniform in
# [min, max]. They appear nowhere in the forward pass.
TIME_STEP_MIN, TIME_STEP_MAX, TIME_STEP_FLOOR = 1e-3, 1e-1, 1e-4


def _dt_bias_init(key, shape, dtype=jnp.float32):
    u = jax.random.uniform(key, shape, jnp.float32)
    dt = jnp.exp(u * (math.log(TIME_STEP_MAX) - math.log(TIME_STEP_MIN))
                 + math.log(TIME_STEP_MIN))
    dt = jnp.maximum(dt, TIME_STEP_FLOOR)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)  # softplus^-1


# Seeded out_proj: a quarter of LeCun-normal, so that a layer's part of the
# residual stream stays below the embedding's (models.moe.centred_out_init).
_OUT_INIT = nn.initializers.variance_scaling(
    0.25 ** 2, "fan_in", "truncated_normal")


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def ssd_chunked(x, dt, a, b_in, c_in, h0, chunk: int):
    """The recurrence over a prompt, a chunk at a time, in float32.

    x (b, L, G, R, P); dt (b, L, G, R), zero at padding; a (G, R) negative;
    b_in, c_in (b, L, G, N); h0 (b, G, R, P, N). Returns
    ``(y (b, L, G, R, P), h after the last token)``.
    """
    bsz, L, G, R, P = x.shape
    N = b_in.shape[-1]
    Q = min(chunk, L)
    pad = (-L) % Q
    if pad:  # dt = 0 there: no decay, no input
        x, dt, b_in, c_in = (jnp.pad(t, [(0, 0), (0, pad)]
                                     + [(0, 0)] * (t.ndim - 2))
                             for t in (x, dt, b_in, c_in))
    c = (L + pad) // Q
    xc = x.reshape(bsz, c, Q, G, R, P)
    dtc = dt.reshape(bsz, c, Q, G, R)
    bc = b_in.reshape(bsz, c, Q, G, N)
    cc = c_in.reshape(bsz, c, Q, G, N)
    cum = jnp.cumsum(dtc * a, axis=2)                  # (b,c,Q,G,R), <= 0
    # Within a chunk: y_q += sum_{s<=q} C_q.B_s exp(cum_q - cum_s) dt_s x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcqsg", cc, bc)
    seg = cum[:, :, :, None] - cum[:, :, None, :]      # (b,c,q,s,G,R)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    w = (jnp.exp(jnp.where(causal, seg, -jnp.inf)) * cb[..., None]
         * dtc[:, :, None])
    y = jnp.einsum("bcqsgr,bcsgrp->bcqgrp", w, xc)
    # What each chunk adds to the state by its end, and how it decays it.
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dtc       # (b,c,Q,G,R)
    added = jnp.einsum("bcsgn,bcsgrp->bcgrpn", bc, to_end[..., None] * xc)
    decay = jnp.exp(cum[:, :, -1])                     # (b,c,G,R)

    def carry(h, chunk_in):
        add, dec = chunk_in
        return dec[..., None, None] * h + add, h

    h_last, h_in = jax.lax.scan(
        carry, h0, (jnp.moveaxis(added, 1, 0), jnp.moveaxis(decay, 1, 0)))
    # The state that entered the chunk, seen from each of its tokens.
    y = y + jnp.einsum("bcqgn,cbgrpn->bcqgrp", cc, h_in) \
        * jnp.exp(cum)[..., None]
    return y.reshape(bsz, L + pad, G, R, P)[:, :L], h_last


# A prefill call has a handful of rows (at most 8), each with a slot of its
# own: a slice read and a slice written per row, in place, instead of a
# gather and a scatter over the pool (which XLA runs as loops over it).


def _read_rows(pool, at):
    """``pool[at]`` for a few in-range row indices ``at`` (b,)."""
    return jnp.stack([jax.lax.dynamic_index_in_dim(pool, at[r], 0, False)
                      for r in range(at.shape[0])])


def _write_rows(pool, slots, rows):
    """``pool`` with ``rows[r]`` at ``slots[r]``; a slot out of range
    (a padding row) writes nothing."""
    n = pool.shape[0]
    for r in range(slots.shape[0]):
        ok = (slots[r] >= 0) & (slots[r] < n)
        at = jnp.clip(slots[r], 0, n - 1)
        old = jax.lax.dynamic_index_in_dim(pool, at, 0, True)
        pool = jax.lax.dynamic_update_index_in_dim(
            pool, jnp.where(ok, rows[r][None], old), at, 0)
    return pool


def _per_row(flags, like):
    """``flags`` (b,) shaped to broadcast over ``like`` (b, ...)."""
    return flags.reshape((-1,) + (1,) * (like.ndim - 1))


def read_state(cache: dict, positions):
    """Each row's ``(convolution tail, state)`` out of a recurrent layer's
    bound entry (``{"conv", "ssm", "state_slots", "own_rows"}``): the pool
    itself in a decode call (``own_rows``: row i is slot i), else the rows'
    slots, zero for a row that starts at position 0."""
    if cache["own_rows"]:
        return cache["conv"], cache["ssm"]
    fresh = positions[:, 0] == 0
    at = jnp.clip(cache["state_slots"], 0, cache["ssm"].shape[0] - 1)

    def rows(pool):
        got = _read_rows(pool, at)
        return jnp.where(_per_row(fresh, got), 0, got)

    return rows(cache["conv"]), rows(cache["ssm"])


def write_state(cache: dict, tail, state) -> dict:
    """The entry's pools with each row's new tail and state at its slot; a
    slot out of range writes nothing."""
    slots = cache["state_slots"]
    new = {"conv": tail.astype(cache["conv"].dtype),
           "ssm": state.astype(cache["ssm"].dtype)}
    if cache["own_rows"]:
        live = slots == jnp.arange(slots.shape[0])
        return {k: jnp.where(_per_row(live, v), v, cache[k])
                for k, v in new.items()}
    return {k: _write_rows(cache[k], slots, v) for k, v in new.items()}


def last_inputs(full, valid, k: int):
    """The last ``k`` real inputs of the convolution: rows of ``full``
    (b, k + s, channels: the old tail, then this call's inputs) from each
    row's count of real tokens on (padding trails)."""
    n_real = jnp.sum(valid, axis=1)
    return jnp.take_along_axis(
        full, (n_real[:, None] + jnp.arange(k))[:, :, None], axis=1)


class Mamba2Mixer(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray,
                 cache: Optional[dict] = None):
        """``x`` (b, s, hidden); ``positions`` (b, s), -1 at (trailing)
        padding. ``cache``: None (every row from a zero state, nothing
        kept), or ``{"conv": (slots, K-1, conv_dim), "ssm": (slots, H, P,
        N), "state_slots": (b,), "own_rows": bool}``; ``own_rows`` (decode)
        says that row i is slot i wherever ``state_slots[i]`` is in range.
        Returns ``(out, {"conv", "ssm"} or None)``."""
        cfg = self.cfg
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        f32 = jnp.float32
        b, s, _ = x.shape
        H, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                      cfg.mamba_n_groups, cfg.mamba_state_size)
        R, K = H // G, cfg.mamba_conv_kernel
        d_in, cd = cfg.mamba_inner_size, cfg.mamba_conv_dim

        def dense(name, features, **kw):
            return LoRADense(features=features, use_bias=False, dtype=dtype,
                             param_dtype=pdtype, name=name, **kw)

        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (K, cd), pdtype).astype(f32)
        conv_b = self.param("conv_bias", nn.initializers.normal(0.2),
                            (cd,), pdtype).astype(f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), f32)
        a = -jnp.exp(self.param("A_log", _a_log_init, (H,), f32))
        d_skip = self.param("D", nn.initializers.ones, (H,), f32)
        norm_w = self.param("norm_scale", nn.initializers.ones, (d_in,), f32)

        zxbcdt = dense("in_proj", 2 * d_in + 2 * G * N + H)(x)
        z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + cd], axis=-1)
        valid = positions >= 0
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias) * valid[..., None]
        dt = dt.reshape(b, s, G, R)

        if cache is not None:
            tail, h0 = read_state(cache, positions)
            h0 = h0.astype(f32).reshape(b, G, R, P, N)
        else:
            tail = jnp.zeros((b, K - 1, cd), dtype)
            h0 = jnp.zeros((b, G, R, P, N), f32)

        # Causal depthwise convolution over [tail | inputs].
        full = jnp.concatenate([tail.astype(dtype), xbc], axis=1)
        conv = sum(conv_w[k] * full[:, k:k + s].astype(f32)
                   for k in range(K)) + conv_b
        conv = jax.nn.silu(conv)
        xs, b_in, c_in = jnp.split(conv, [d_in, d_in + G * N], axis=-1)
        xs = xs.reshape(b, s, G, R, P)
        b_in = b_in.reshape(b, s, G, N)
        c_in = c_in.reshape(b, s, G, N)
        new_tail = last_inputs(full, valid, K - 1)

        if s == 1:
            dt1, x1 = dt[:, 0], xs[:, 0]                   # (b,G,R) (b,G,R,P)
            h = (jnp.exp(dt1 * a.reshape(G, R))[..., None, None] * h0
                 + (dt1[..., None] * x1)[..., None]
                 * b_in[:, 0][:, :, None, None, :])
            y = jnp.sum(h * c_in[:, 0][:, :, None, None, :], axis=-1)[:, None]
        else:
            y, h = ssd_chunked(xs, dt, a.reshape(G, R), b_in, c_in, h0,
                               cfg.mamba_chunk_size)
        y = y + d_skip.reshape(G, R)[..., None] * xs       # (b,s,G,R,P)

        # Gate, then normalise within each of the G groups of channels.
        y = y.reshape(b, s, G, R * P) * jax.nn.silu(
            z.astype(f32)).reshape(b, s, G, R * P)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + cfg.rms_norm_eps)
        y = (y.reshape(b, s, d_in) * norm_w).astype(dtype)
        out = dense("out_proj", cfg.hidden_size, kernel_init=_OUT_INIT)(y)

        new_cache = None if cache is None else write_state(
            cache, new_tail, h.reshape(b, H, P, N))
        return out, new_cache
