"""Mamba-1 mixer (selective state-space layer), for training, prefill and
decode.

The "S" kind of the ``models.sambay`` and ``models.jamba`` families. A
state a CHANNEL
(``d_inner`` = ``mamba_expand`` x hidden channels of ``N`` values each),
where Mamba-2 (``models.mamba2``) has one scalar decay a head, and a time
step a channel, projected through ``mamba_dt_rank`` values:

    [u ; z]       = in_proj h
    u'            = silu(causal depthwise conv1d(u, width K) + bias)
    [dlt ; B ; C] = x_proj u'                    (dt_rank, N, N)
    dlt, B, C     = RMSNorm(dlt), RMSNorm(B), RMSNorm(C)   (learned weights;
                    jamba's own, ``cfg.mamba_inner_norms``)
    Dt            = softplus(dt_proj dlt + bias) (d_inner)
    A             = -exp(A_log)                  (d_inner, N)
    s_t           = exp(Dt_t A) s_{t-1} + (Dt_t u'_t) (outer) B_t
    y_t           = s_t C_t + D u'_t
    out           = out_proj(y * silu(z))

The mixer also returns ``y`` (before the gate): the MEMORY that the family's
gated memory units read (``ModelConfig.shared_memory_layer``).

Three programs compute the same recurrence, in float32. One token
(``s == 1``, with a cache) is the update written out. A prompt over a
serving cache is a scan over its tokens from the slot's state
(:func:`selective_scan`: ``lax.scan``, ``SCAN_UNROLL`` tokens a trip; the
unroll changes no result). Rows without a cache (training, the check) go
through :func:`chunked_selective_scan`: the same loop a chunk of
``SCAN_CHUNK`` tokens at a time with a backward pass of its own
(``jax.custom_vjp``), which keeps the state at chunk boundaries alone and
recomputes the states inside a chunk when its gradient is due, where
autodiff of the plain loop keeps ``d_inner x N`` float32 a token. On the TPU
both passes are Pallas kernels (``ops.pallas.selective_scan``, the state in
the chip's registers), elsewhere XLA loops of the same chunks; either way
under ``jax.named_scope("dlti_mamba1")`` (the caller's) and inside it under
the names ``dlti_selective_scan_fwd`` / ``_bwd``. The serving programs'
scan and update are XLA's.

**Documents packed into one row** (``starts``: a row's positions where a
document begins): the state before a document's first token is zero and the
convolution reads zeros before it, so a packed row gives what each of its
documents gives alone. Padding advances nothing.

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

from dlti_tpu.config import LoRAConfig, ModelConfig
from dlti_tpu.models.llama import RMSNorm
from dlti_tpu.models.lora import LoRADense
from dlti_tpu.ops.pallas import selective_scan as scan_kernel
from dlti_tpu.models.mamba2 import (
    _dt_bias_init, last_inputs, read_state, write_state,
)
from dlti_tpu.utils.dtypes import resolve_dtype as _dtype

# Tokens of a scan that one trip of the loop covers.
SCAN_UNROLL = 8
# ... and of the training scan (forward and backward of 2 x 8,192 tokens at
# 5120 x 16 on the v5e: 31.5 ms a layer at 4, 39.3 at 8, 38.5 at 16, 64.3 at
# 32; the forward alone 11-12 ms at any: my chip run, PR 56).
CHUNK_UNROLL = 4
# Tokens between two kept states of the training scan. What is kept for the
# backward pass is L / SCAN_CHUNK states of d_inner x N float32 a row (21 MB
# a layer at 8,192 tokens and 5120 x 16), and what the backward pass holds
# while it recomputes one chunk is SCAN_CHUNK states a row (42 MB): both
# small beside a block's activations, and 64 chunks of 128 trips are few
# enough that the outer loop's own cost does not show. The kernels' chunk.
SCAN_CHUNK = scan_kernel.CHUNK
# Seeded spread of the inner norms' weights (``mamba_inner_norms``): away
# from 1, so that a program without them differs from one with them.
INNER_NORM_INIT_STD = 0.25


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


def _row_major(t):
    """(chunks, SCAN_CHUNK, b, ...) -> (b, L, ...)."""
    return jnp.moveaxis(t.reshape((-1,) + t.shape[2:]), 0, 1)


def _decay(dt_t, keep_t, a):
    """What a token multiplies the state before it by: (b, N, D)."""
    return jnp.exp(dt_t[:, None, :] * a) * keep_t[:, None, None]


def _whole_chunks(t, fill=0.0):
    """(b, L, ...) padded along L to whole chunks. A padded token has ``dt``
    0 and ``keep`` 1 (``fill``): it advances nothing."""
    pad = -t.shape[1] % SCAN_CHUNK
    return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2),
                   constant_values=fill)


def _by_chunk(t, fill=0.0):
    """(b, L, ...) -> (chunks, SCAN_CHUNK, b, ...), padded to whole chunks:
    what XLA's loops scan over."""
    t = jnp.moveaxis(_whole_chunks(t, fill), 1, 0)
    return t.reshape((-1, SCAN_CHUNK) + t.shape[1:])


def _scan_inputs(u, dt, b_in, c_in, keep, lay=_by_chunk):
    """The scan's five inputs under ``lay`` (by chunk for XLA's loops, whole
    chunks in place for the kernels)."""
    return lay(u), lay(dt), lay(b_in), lay(c_in), lay(keep, 1.0)


def _chunks_forward(u, dt, a, b_in, c_in, keep):
    """``(y (b, L, D), the state before each chunk (chunks, b, N, D))``;
    ``a`` (N, D)."""
    length = u.shape[1]
    xs = _scan_inputs(u, dt, b_in, c_in, keep)

    def step(s, x):
        u_t, dt_t, b_t, c_t, keep_t = x
        s = _decay(dt_t, keep_t, a) * s \
            + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    def chunk(s, x):
        s_next, y = jax.lax.scan(step, s, x, unroll=CHUNK_UNROLL)
        return s_next, (y, s)

    s0 = jnp.zeros((u.shape[0], a.shape[0], u.shape[2]), jnp.float32)
    with jax.named_scope("dlti_selective_scan_fwd"):
        _, (y, kept) = jax.lax.scan(chunk, s0, xs)
    return _row_major(y)[:, :length], kept


def _kernel_takes(u) -> bool:
    """Whether the Pallas kernels run this scan: on the TPU, at a channel
    count their blocks divide (the published 5120 does)."""
    return jax.default_backend() == "tpu" \
        and u.shape[2] % scan_kernel.CHANNELS == 0


@jax.custom_vjp
def chunked_selective_scan(u, dt, a, b_in, c_in, keep):
    """The recurrence over rows from a zero state, in float32, with a
    backward pass that keeps a state a chunk and not a token.

    u, dt (b, L, D), ``dt`` zero at padding; a (D, N) negative; b_in, c_in
    (b, L, N); keep (b, L): 0 where a document begins (the state before
    that token is dropped), 1 elsewhere. Returns y (b, L, D) without the
    skip term. ``keep`` gets no gradient."""
    return _chunked_fwd(u, dt, a, b_in, c_in, keep)[0]


def _chunked_fwd(u, dt, a, b_in, c_in, keep):
    if _kernel_takes(u):
        lu, ldt, lb, lc, lkeep = _scan_inputs(u, dt, b_in, c_in, keep,
                                              _whole_chunks)
        y, kept = scan_kernel.selective_scan_fwd(lu, ldt, a.T, lb, lc, lkeep)
        y = y[:, :u.shape[1]]
    else:
        y, kept = _chunks_forward(u, dt, a.T, b_in, c_in, keep)
    return y, (u, dt, a, b_in, c_in, keep, kept)


def _chunked_bwd(saved, dy):
    u, dt, a, b_in, c_in, keep, kept = saved
    a = a.T                                               # (N, D)
    length = u.shape[1]
    if _kernel_takes(u):
        lu, ldt, lb, lc, lkeep = _scan_inputs(u, dt, b_in, c_in, keep,
                                              _whole_chunks)
        du, ddt, da, db, dc = scan_kernel.selective_scan_bwd(
            lu, ldt, a, lb, lc, lkeep, kept, _whole_chunks(dy))
        return (du[:, :length], ddt[:, :length], da.T, db[:, :length],
                dc[:, :length], jnp.zeros_like(keep))
    xs = _scan_inputs(u, dt, b_in, c_in, keep)
    dy = _by_chunk(dy)

    def state_before(s, x):
        u_t, dt_t, b_t, _, keep_t = x
        return (_decay(dt_t, keep_t, a) * s
                + (dt_t * u_t)[:, None, :] * b_t[:, :, None]), s

    def step_back(carry, x):
        # lam: the gradient that later tokens send to this token's state
        lam, da = carry
        (u_t, dt_t, b_t, c_t, keep_t), dy_t, s_prev = x
        decay = _decay(dt_t, keep_t, a)
        dtu = dt_t * u_t
        s = decay * s_prev + dtu[:, None, :] * b_t[:, :, None]
        lam = lam + c_t[:, :, None] * dy_t[:, None, :]
        dc_t = jnp.sum(s * dy_t[:, None, :], axis=2)
        db_t = jnp.sum(lam * dtu[:, None, :], axis=2)
        through_b = jnp.sum(lam * b_t[:, :, None], axis=1)     # (b, D)
        through_decay = lam * decay * s_prev                   # (b, N, D)
        ddt_t = jnp.sum(through_decay * a, axis=1) + through_b * u_t
        da = da + jnp.sum(through_decay * dt_t[:, None, :], axis=0)
        return (decay * lam, da), (through_b * dt_t, ddt_t, db_t, dc_t)

    def chunk_back(carry, x):
        inputs, dy_c, s_start = x
        _, before = jax.lax.scan(state_before, s_start, inputs,
                                 unroll=CHUNK_UNROLL)
        return jax.lax.scan(step_back, carry, (inputs, dy_c, before),
                            reverse=True, unroll=CHUNK_UNROLL)

    zero = (jnp.zeros_like(kept[0]), jnp.zeros_like(a))
    with jax.named_scope("dlti_selective_scan_bwd"):
        (_, da), grads = jax.lax.scan(chunk_back, zero, (xs, dy, kept),
                                      reverse=True)
    du, ddt, db, dc = (_row_major(g)[:, :length] for g in grads)
    return du, ddt, da.T, db, dc, jnp.zeros_like(keep)


chunked_selective_scan.defvjp(_chunked_fwd, _chunked_bwd)


class Mamba1Mixer(nn.Module):
    cfg: ModelConfig
    lora: Optional[LoRAConfig] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, positions: jnp.ndarray,
                 cache: Optional[dict] = None,
                 segment_ids: Optional[jnp.ndarray] = None,
                 deterministic: bool = True):
        """``x`` (b, s, hidden); ``positions`` (b, s), -1 at (trailing)
        padding. ``cache``: None (every row from a zero state, nothing
        kept), or ``{"conv": (slots, K-1, d_inner), "ssm": (slots, d_inner,
        N), "state_slots": (b,), "own_rows": bool}`` as Mamba-2's.
        ``segment_ids`` (b, s), without a cache alone: the packed
        documents of each row, 1-based, 0 at padding; every document starts
        from a zero state. Returns ``(out, y (b, s, d_inner) float32: the
        memory, {"conv", "ssm"} or None)``."""
        cfg = self.cfg
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        f32 = jnp.float32
        b, s, _ = x.shape
        D, N, K, R = (cfg.mamba_inner_size, cfg.mamba_state_size,
                      cfg.mamba_conv_kernel, cfg.mamba_dt_rank)

        targets = () if self.lora is None or not self.lora.enabled \
            else cfg.lora_targets_of(self.lora)

        def dense(name, features, **kw):
            if name in targets:
                kw.update(lora_r=self.lora.r, lora_alpha=self.lora.alpha,
                          lora_dropout=self.lora.dropout)
            layer = LoRADense(features=features, dtype=dtype,
                              param_dtype=pdtype, name=name, **kw)
            return lambda t: layer(t, deterministic)

        def inner_norm(name, t):
            if not cfg.mamba_inner_norms:
                return t
            return RMSNorm(cfg.rms_norm_eps, init_std=INNER_NORM_INIT_STD,
                           name=name)(t)

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
        same_doc = None
        if segment_ids is not None:
            if cache is not None:
                raise ValueError("packed rows (segment_ids) go without a "
                                 "serving cache")
            valid = valid & (segment_ids != 0)
            # same_doc[k]: the token k places back is of this token's
            # document (k = 1 .. K-1); a row's first tokens have none
            same_doc = [
                jnp.pad(segment_ids, ((0, 0), (k, 0)))[:, :s] == segment_ids
                for k in range(max(K, 2))]
        if cache is not None:
            tail, s0 = read_state(cache, positions)
            s0 = s0.astype(f32)
        else:
            tail = jnp.zeros((b, K - 1, D), dtype)
            s0 = jnp.zeros((b, D, N), f32)

        # Causal depthwise convolution over [tail | inputs].
        full = jnp.concatenate([tail.astype(dtype), u], axis=1)

        def tap(k):
            # ``full[:, k:k + s]`` lies K - 1 - k tokens back
            t = full[:, k:k + s].astype(f32)
            if same_doc is None or k == K - 1:
                return t
            return jnp.where(same_doc[K - 1 - k][..., None], t, 0.0)

        conv = sum(conv_w[k] * tap(k) for k in range(K)) + conv_b
        u = jax.nn.silu(conv)                                # (b, s, D) f32
        new_tail = last_inputs(full, valid, K - 1)

        dbc = dense("x_proj", R + 2 * N, use_bias=False)(u.astype(dtype))
        dlt, b_in, c_in = jnp.split(dbc, [R, R + N], axis=-1)
        dlt = inner_norm("dt_layernorm", dlt)
        b_in = inner_norm("b_layernorm", b_in)
        c_in = inner_norm("c_layernorm", c_in)
        # dt_proj's bias is ``dt_bias``, kept in float32 as Mamba-2's is:
        # softplus(bias) log-uniform in the published [1e-3, 1e-1].
        dt = dense("dt_proj", D, use_bias=False,
                   kernel_init=nn.initializers.variance_scaling(
                       1.0, "fan_in", "uniform"))(dlt)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias) * valid[..., None]
        b_in, c_in = b_in.astype(f32), c_in.astype(f32)

        if cache is None:
            keep = jnp.ones((b, s), f32) if same_doc is None \
                else same_doc[1].astype(f32)
            y = chunked_selective_scan(u, dt, a, b_in, c_in, keep)
            state = None
        elif s == 1:
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
