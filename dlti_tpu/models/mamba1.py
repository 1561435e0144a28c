"""Mamba-1 mixer (selective state-space layer), for prefill and for decode.

One layer of the ``models.sambay`` family's "S" kind. A state a CHANNEL
(``d_inner`` = ``mamba_expand`` x hidden channels of ``N`` values each),
where Mamba-2 (``models.mamba2``) has one scalar decay a head, and a time
step a channel, projected through ``mamba_dt_rank`` values:

    [u ; z]       = in_proj h
    u'            = silu(causal depthwise conv1d(u, width K) + bias)
    [dlt ; B ; C] = x_proj u'                    (dt_rank, N, N)
    Dt            = softplus(dt_proj dlt + bias) (d_inner)
    A             = -exp(A_log)                  (d_inner, N)
    s_t           = exp(Dt_t A) s_{t-1} + (Dt_t u'_t) (outer) B_t
    y_t           = s_t C_t + D u'_t
    out           = out_proj(y * silu(z))

The mixer also returns ``y`` (before the gate): the MEMORY that the family's
gated memory units read (``ModelConfig.shared_memory_layer``).

Two programs compute the same recurrence, in float32: a prompt (``s > 1``)
as a scan over its tokens (``lax.scan``, ``SCAN_UNROLL`` tokens a trip: the
unroll changes no result), one token (``s == 1``) as the update written out.
No Pallas kernel yet: under ``jax.named_scope("dlti_mamba1")`` (the caller's)
the layer is XLA's.

**The recurrent state** of a sequence is the last ``K - 1`` inputs of the
convolution and ``s``, kept by decode slot exactly as Mamba-2's
(``mamba2.read_state`` / ``write_state``): padding (position -1, trailing)
advances nothing, a row whose slot is out of range writes nothing.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlti_tpu.config import ModelConfig
from dlti_tpu.models.lora import LoRADense
from dlti_tpu.models.mamba2 import (
    _dt_bias_init, last_inputs, read_state, write_state,
)
from dlti_tpu.utils.dtypes import resolve_dtype as _dtype

# Tokens of a prompt's scan that one trip of the loop covers.
SCAN_UNROLL = 8
def _a_log_init(key, shape, dtype=jnp.float32):
    """The published S4D-real start: ``A[c, n] = -(n + 1)``."""
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)),
        shape).astype(dtype)


def selective_scan(u, dt, a, b_in, c_in, s0):
    """The recurrence over a prompt, in float32.

    u, dt (b, L, D), ``dt`` zero at padding; a (D, N) negative; b_in, c_in
    (b, L, N); s0 (b, D, N). Returns ``(y (b, L, D) without the skip term,
    the state after the last token)``."""

    def step(s, inputs):
        u_t, dt_t, b_t, c_t = inputs
        s = (jnp.exp(dt_t[..., None] * a) * s
             + (dt_t * u_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    s_last, y = jax.lax.scan(
        step, s0, tuple(jnp.moveaxis(t, 1, 0) for t in (u, dt, b_in, c_in)),
        unroll=min(SCAN_UNROLL, u.shape[1]))
    return jnp.moveaxis(y, 0, 1), s_last


class Mamba1Mixer(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray,
                 cache: Optional[dict] = None):
        """``x`` (b, s, hidden); ``positions`` (b, s), -1 at (trailing)
        padding. ``cache``: None (every row from a zero state, nothing
        kept), or ``{"conv": (slots, K-1, d_inner), "ssm": (slots, d_inner,
        N), "state_slots": (b,), "own_rows": bool}`` as Mamba-2's. Returns
        ``(out, y (b, s, d_inner) float32: the memory, {"conv", "ssm"} or
        None)``."""
        cfg = self.cfg
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        f32 = jnp.float32
        b, s, _ = x.shape
        D, N, K, R = (cfg.mamba_inner_size, cfg.mamba_state_size,
                      cfg.mamba_conv_kernel, cfg.mamba_dt_rank)

        def dense(name, features, **kw):
            return LoRADense(features=features, dtype=dtype,
                             param_dtype=pdtype, name=name, **kw)

        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (K, D), pdtype).astype(f32)
        conv_b = self.param("conv_bias", nn.initializers.normal(0.2),
                            (D,), pdtype).astype(f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (D,), f32)
        a = -jnp.exp(self.param("A_log", _a_log_init, (D, N), f32))
        d_skip = self.param("D", nn.initializers.ones, (D,), f32)

        u, z = jnp.split(dense("in_proj", 2 * D, use_bias=False)(x), 2,
                         axis=-1)
        valid = positions >= 0
        if cache is not None:
            tail, s0 = read_state(cache, positions)
            s0 = s0.astype(f32)
        else:
            tail = jnp.zeros((b, K - 1, D), dtype)
            s0 = jnp.zeros((b, D, N), f32)

        # Causal depthwise convolution over [tail | inputs].
        full = jnp.concatenate([tail.astype(dtype), u], axis=1)
        conv = sum(conv_w[k] * full[:, k:k + s].astype(f32)
                   for k in range(K)) + conv_b
        u = jax.nn.silu(conv)                                # (b, s, D) f32
        new_tail = last_inputs(full, valid, K - 1)

        dbc = dense("x_proj", R + 2 * N, use_bias=False)(u.astype(dtype))
        dlt, b_in, c_in = jnp.split(dbc, [R, R + N], axis=-1)
        # dt_proj's bias is ``dt_bias``, kept in float32 as Mamba-2's is:
        # softplus(bias) log-uniform in the published [1e-3, 1e-1].
        dt = dense("dt_proj", D, use_bias=False,
                   kernel_init=nn.initializers.variance_scaling(
                       1.0, "fan_in", "uniform"))(dlt)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias) * valid[..., None]
        b_in, c_in = b_in.astype(f32), c_in.astype(f32)

        if s == 1:
            state = (jnp.exp(dt[:, 0, :, None] * a) * s0
                     + (dt[:, 0] * u[:, 0])[..., None] * b_in[:, 0, None, :])
            y = jnp.sum(state * c_in[:, 0, None, :], axis=-1)[:, None]
        else:
            y, state = selective_scan(u, dt, a, b_in, c_in, s0)
        y = y + d_skip * u                                   # the memory
        gated = (y * jax.nn.silu(z.astype(f32))).astype(dtype)
        out = dense("out_proj", cfg.hidden_size, use_bias=False)(gated)
        new_cache = None if cache is None else write_state(
            cache, new_tail, state)
        return out, y, new_cache
