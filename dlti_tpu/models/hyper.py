"""Hyper-connected residual streams (mHC: manifold-constrained
hyper-connections, DeepSeek-AI 2025), usable by any family.

The residual path is ``n = hc_mult`` streams wide: a token is
``X in R^(n x C)``. A sublayer ``F`` (with its own pre-norm) is no longer
``x + F(x)``; three maps computed from the token itself say what the
sublayer sees and where its result goes:

    x~     = RMSNorm(vec(X))                over all nC values, no weight
    H_pre  = sigmoid(a_pre x~ phi_pre + b_pre)                 (n,)
    H_post = 2 sigmoid(a_post x~ phi_post + b_post)            (n,)
    M0     = exp(clip(a_res mat(x~ phi_res) + b_res, lo, hi))  (n, n)
    H_res  = Sinkhorn(M0): ``hc_sinkhorn_iters`` times, each column divided
             by its sum + eps, then each row by its sum + eps
    u      = H_pre X                        what the sublayer sees, (C,)
    X'     = H_res X + H_post^T F(u)        (n, C)

``H_res`` is (nearly) doubly stochastic, so the residual mixing neither
grows nor shrinks the streams. :class:`HyperMaps` owns one sublayer's
``phi``, ``b`` and ``a`` (float32 parameters; the maps are computed in
float32) and :func:`mix_in` / :func:`mix_out` apply them to streams kept
in the model's dtype.

**Layout.** The streams are one array ``(n, batch, seq, C)``, the stream
axis first, so that a stream is a plain ``(batch, seq, C)`` activation and
no array has a minor dimension of 4 (the TPU would pad it to a tile). The
maps come back with the stream axes first too, ``(n, batch, seq)`` and
``(n, n, batch, seq)``: the Sinkhorn rounds are element-wise work over
tokens, unrolled, which XLA fuses into one pass.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlti_tpu.config import ModelConfig

# What a model with stream maps counts, after its other counters: the
# largest |row sum - 1| or |column sum - 1| of any H_res of the call, x 1e6,
# over routed tokens (combined by max); routed tokens x sublayers.
MHC_COUNTERS = ("mhc_sinkhorn_residual_e6", "mhc_maps")

# Seeded weights (a fresh model starts at a = 0.01 and b such that H_pre =
# 1/n, H_post = 1, H_res = I: maps that ignore the token). Here the dynamic
# part is of the size of the bias, so that a program that dropped x~ phi,
# cut the Sinkhorn rounds or kept the plain residual computes another
# function: x~ phi is N(0, 1) an entry under LeCun-normal phi and a unit-RMS
# x~, so ``A_*`` is the spread the token gives a map's logits and ``B_STD``
# the spread of its bias.
A_PRE, A_POST, A_RES = 0.5, 0.5, 0.7
B_STD, B_RES_STD = 0.5, 1.0


def sinkhorn(m, iters: int, eps: float):
    """``m`` (n, n, ...) positive: ``iters`` rounds of columns then rows,
    each divided by its sum + eps. Axis 0 indexes rows, axis 1 columns."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


class HyperMaps(nn.Module):
    """The three maps of one sublayer. ``streams`` (n, b, s, C) ->
    ``(h_pre (n, b, s), h_post (n, b, s), h_res (n, n, b, s))`` float32,
    and the two ``MHC_COUNTERS`` of the call over the tokens that
    ``token_mask`` (b, s) marks as real."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, streams, token_mask):
        cfg = self.cfg
        n, b, s, C = streams.shape
        f32 = jnp.float32

        def phi(name, width):       # (n, C, width): row j*C + c of vec(X)
            return self.param(name, nn.initializers.lecun_normal(
                in_axis=(0, 1), out_axis=2), (n, C, width), f32)

        def normal(name, shape, std):
            return self.param(name, nn.initializers.normal(std), shape, f32)

        def const(name, value):
            return self.param(name, nn.initializers.constant(value), (), f32)

        weights = jnp.concatenate(
            [phi("phi_pre", n), phi("phi_post", n), phi("phi_res", n * n)],
            axis=-1)
        b_pre, b_post = (normal(name, (n,), B_STD)
                         for name in ("b_pre", "b_post"))
        b_res = normal("b_res", (n, n), B_RES_STD)
        a_pre, a_post, a_res = (const(name, v) for name, v in (
            ("a_pre", A_PRE), ("a_post", A_POST), ("a_res", A_RES)))
        with jax.named_scope("dlti_mhc_map"):
            x = streams.astype(f32)
            # x~ phi = rsqrt(mean(X^2) + eps) (X phi): the norm has no
            # weight, so it scales the product and costs no pass of its own
            raw = sum(jnp.dot(x[j], weights[j],
                              precision=jax.lax.Precision.HIGHEST)
                      for j in range(n))                    # (b, s, n*n+2n)
            inv_rms = jax.lax.rsqrt(
                jnp.sum(jnp.square(x), axis=(0, 3)) / (n * C) + cfg.hc_eps)
            proj = jnp.moveaxis(raw * inv_rms[..., None], -1, 0)
            h_pre = jax.nn.sigmoid(
                a_pre * proj[:n] + b_pre[:, None, None])
            h_post = 2.0 * jax.nn.sigmoid(
                a_post * proj[n:2 * n] + b_post[:, None, None])
            logits = a_res * proj[2 * n:].reshape(n, n, b, s) \
                + b_res[:, :, None, None]
            h_res = sinkhorn(jnp.exp(jnp.clip(
                logits, cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max)),
                cfg.hc_sinkhorn_iters, cfg.hc_eps)
            off = jnp.maximum(
                jnp.max(jnp.abs(jnp.sum(h_res, axis=0) - 1.0), axis=0),
                jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0), axis=0))
            counters = jnp.stack([
                jnp.max(jnp.where(token_mask, off, 0.0)) * 1e6,
                jnp.sum(token_mask)]).astype(jnp.int32)
        return h_pre, h_post, h_res, counters


def mix_in(streams, h_pre):
    """``u = H_pre X``: what the sublayer sees, (b, s, C) in the streams'
    dtype."""
    with jax.named_scope("dlti_mhc_mix"):
        u = sum(h_pre[j][..., None] * streams[j].astype(jnp.float32)
                for j in range(streams.shape[0]))
        return u.astype(streams.dtype)


def mix_out(streams, out, h_post, h_res):
    """``X' = H_res X + H_post^T F(u)`` with ``out = F(u)`` (b, s, C)."""
    n = streams.shape[0]
    with jax.named_scope("dlti_mhc_mix"):
        x, y = streams.astype(jnp.float32), out.astype(jnp.float32)
        return jnp.stack([
            sum(h_res[i, j][..., None] * x[j] for j in range(n))
            + h_post[i][..., None] * y for i in range(n)
        ]).astype(streams.dtype)
