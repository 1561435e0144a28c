"""Plain reference of Phi-4-mini-flash-reasoning (``model_type`` phi4flash).

The architecture is SambaY ("Decoder-Hybrid-Decoder Architecture for
Efficient Reasoning with Long Generation", arXiv:2507.06607): a self-decoder
of Mamba-1 layers (arXiv:2312.00752) and differential attention
(arXiv:2410.05258) under a sliding window, ONE full-attention layer whose
keys and values every later attention layer reads, and gated memory units
that read one Mamba layer's scan output.

Straightforward ``jax.numpy`` in float32, every product at precision
``highest``, no cache, no batching, heads of 64 and four softmaxes a pair of
heads as the paper has them, the recurrence one token after another. It
imports nothing of ``dlti_tpu``; ``sizes`` reads the configuration file
alone (``config["model"]`` as run and ``assumed`` beside it), never the
program's ``ModelConfig``.

## The layer equations (d = hidden_size, layers l = 0 .. n - 1)

``x = embed[ids]`` (rows unscaled); for each layer
``x = x + Mixer_l(LN(x))``, then ``x = x + MLP_l(LN'(x))``; a final LN;
``logits = x embed^T`` (tied, no bias). **No positional embedding anywhere.**
``LN`` is LayerNorm with weight and bias, eps ``layer_norm_eps``.
``MLP(u) = fc2(silu(g) * y)`` with ``[g ; y] = fc1 u``: the gate is the FIRST
half; no biases (``mlp_bias`` false).

Which mixer (``mb_per_layer`` = 2, half = ``num_hidden_layers // 2``):

- l even, l <= half: **Mamba-1**. d_inner = ``mamba_expand`` d, state N,
  conv K, dt_rank R:

      [u ; z] = in_proj h
      u' = silu(causal depthwise conv1d(u, width K) + bias)
      [dlt ; B ; C] = x_proj u'                       (R, N, N)
      Dt = softplus(dt_proj dlt + dt_bias)            (d_inner)
      A = -exp(A_log)                                 (d_inner, N)
      s_t = exp(Dt_t A) s_{t-1} + (Dt_t u'_t) (outer) B_t
      y_t = s_t C_t + D u'_t
      out = out_proj(y * silu(z))

  Layer l = half also hands ``m_t = y_t`` (before the gate) to the memory
  units: THE MEMORY.
- l odd, l < half: **differential attention under a window** of
  ``sliding_window`` (a query sees itself and the window - 1 keys before
  it); l = half + 1: the same over every key; its keys and values are the
  shared ones. ``[q ; k ; v] = qkv_proj h + b``, ``num_attention_heads``
  query and ``num_key_value_heads`` key-value heads of dh = d / heads.
  ``q1_i = q[2i]``, ``q2_i = q[2i+1]``; ``k1_j = k[2j]``, ``k2_j = k[2j+1]``,
  ``V_j = [v[2j] ; v[2j+1]]``; head i reads j = i // (pairs / kv pairs).

      a1_i = softmax(q1_i k1_j^T / sqrt dh) V_j   (causal, windowed)
      a2_i = softmax(q2_i k2_j^T / sqrt dh) V_j
      lam  = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l)
      lam0(l) = 0.8 - 0.6 exp(-0.3 l)             l: the index in the n
      o_i  = (1 - lam0(l)) RMSNorm_{2 dh}(a1_i - lam a2_i)
      out  = o_proj [o_0 ; ... ] + b_o

- l even, l > half: **gated memory unit**,
  ``out_proj(silu(in_proj h) * m_t)``, no biases.
- l odd, l > half + 1: **differential cross-attention**: ``q = q_proj h +
  b_q`` alone; keys and values are layer half + 1's for the same sequence
  (every position <= the query's); the layer's own lambda vectors, RMSNorm
  weight and ``o_proj``; the arithmetic above.

## Departures, each on purpose

- The weights arrive in the program's storage precision (bf16) and are cast
  up; every activation and every product is float32.
- ``dt_proj``'s bias is the tree's ``dt_bias`` leaf (float32), not a
  ``bias`` under ``dt_proj``.
- The RMSNorm after the difference uses ``layer_norm_eps``; the published
  modelling code's own constant (1e-5) is the same number.
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _assumed(config, key):
    return config["assumed"][key]["value"]


def sizes(config):
    """Everything ``forward`` needs, from the configuration file alone."""
    m = config["model"]
    layers, period = int(m["num_hidden_layers"]), int(m["mb_per_layer"])
    half = layers // 2
    kinds = []
    for l in range(layers):
        if l % period == 0:
            kinds.append("mamba" if l <= half else "memory_unit")
        else:
            kinds.append("window" if l < half else
                         "full" if l == half + 1 else "cross")
    hidden, heads = int(m["hidden_size"]), int(m["num_attention_heads"])
    return {
        "kinds": kinds, "memory_layer": half, "shared_kv_layer": half + 1,
        "eps": float(m["layer_norm_eps"]), "hidden": hidden,
        "vocab": int(m["vocab_size"]), "window": int(m["sliding_window"]),
        "heads": heads, "kv_heads": int(m["num_key_value_heads"]),
        "head_dim": hidden // heads,
        "m_inner": int(_assumed(config, "mamba_expand")) * hidden,
        "m_state": int(_assumed(config, "mamba_d_state")),
        "m_conv": int(_assumed(config, "mamba_d_conv")),
        "m_dt_rank": int(_assumed(config, "mamba_dt_rank")),
    }


def _mm(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HIGHEST)


def _linear(p, x):
    y = _mm(x, p["kernel"])
    return y + p["bias"].astype(F32) if "bias" in p else y


def _layer_norm(p, x, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def mamba1(p, sz, x):
    """x (seq, hidden) -> (out (seq, hidden), y (seq, d_inner): the scan's
    output before the gate), one token after another."""
    D, N, K, R = sz["m_inner"], sz["m_state"], sz["m_conv"], sz["m_dt_rank"]
    seq = x.shape[0]
    u, z = jnp.split(_linear(p["in_proj"], x), 2, axis=-1)
    w, bias = p["conv_kernel"].astype(F32), p["conv_bias"].astype(F32)
    padded = jnp.concatenate([jnp.zeros((K - 1, D), F32), u])
    u = jax.nn.silu(sum(w[k] * padded[k:k + seq] for k in range(K)) + bias)
    dlt, b_in, c_in = jnp.split(_linear(p["x_proj"], u), [R, R + N], axis=-1)
    dt = jax.nn.softplus(_linear(p["dt_proj"], dlt)
                         + p["dt_bias"].astype(F32))          # (seq, D)
    a = -jnp.exp(p["A_log"].astype(F32))                      # (D, N)

    def step(s, t):
        u_t, dt_t, b_t, c_t = t
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((D, N), F32), (u, dt, b_in, c_in))
    y = y + p["D"].astype(F32) * u
    return _linear(p["out_proj"], y * jax.nn.silu(z)), y


def _softmax_av(q, k, v, visible, scale):
    """q (seq, kv, r, dh), k (seq, kv, dh), v (seq, kv, dv) ->
    (seq, kv, r, dv)."""
    s = jnp.einsum("qjrd,kjd->jrqk", q, k, precision=HIGHEST) * scale
    s = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
    return jnp.einsum("jrqk,kjd->qjrd", s, v, precision=HIGHEST)


def differential(p, sz, depth, q, k, v, window):
    """q (seq, heads, dh), k, v (seq, kv_heads, dh) of one sequence -> the
    layer's output (seq, hidden)."""
    dh, seq = sz["head_dim"], q.shape[0]
    pairs, kv_pairs = sz["heads"] // 2, sz["kv_heads"] // 2
    per = pairs // kv_pairs
    q1 = q[:, 0::2].reshape(seq, kv_pairs, per, dh)
    q2 = q[:, 1::2].reshape(seq, kv_pairs, per, dh)
    k1, k2 = k[:, 0::2], k[:, 1::2]
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)
    at = jnp.arange(seq)
    visible = at[None, :] <= at[:, None]
    if window:
        visible &= at[None, :] > at[:, None] - window
    a1 = _softmax_av(q1, k1, vv, visible, dh ** -0.5)
    a2 = _softmax_av(q2, k2, vv, visible, dh ** -0.5)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = jnp.exp(jnp.sum(p["lambda_q1"].astype(F32)
                          * p["lambda_k1"].astype(F32))) \
        - jnp.exp(jnp.sum(p["lambda_q2"].astype(F32)
                          * p["lambda_k2"].astype(F32))) + lam0
    diff = a1 - lam * a2
    diff = diff * jax.lax.rsqrt(jnp.mean(diff * diff, -1, keepdims=True)
                                + sz["eps"]) * p["subln"].astype(F32)
    return _linear(p["o_proj"], ((1.0 - lam0) * diff).reshape(seq, -1))


def _heads(x, n):
    return x.reshape(x.shape[0], n, -1)


def forward(params, sizes, ids):
    """float32 logits (seq, vocab) of one sequence ``ids``, a layer at a
    time."""
    sz = sizes
    nh, nkv, dh = sz["heads"], sz["kv_heads"], sz["head_dim"]
    x = params["embed_tokens"][ids].astype(F32)
    memory = shared = None
    for l, kind in enumerate(sz["kinds"]):
        layer = params[f"layers_{l}"]
        p = layer["mixer"]
        h = _layer_norm(layer["input_norm"], x, sz["eps"])
        if kind == "mamba":
            out, y = mamba1(p, sz, h)
            if l == sz["memory_layer"]:
                memory = y
        elif kind == "memory_unit":
            out = _linear(p["out_proj"],
                          jax.nn.silu(_linear(p["in_proj"], h)) * memory)
        elif kind == "cross":
            q = _heads(_linear(p["q_proj"], h), nh)
            out = differential(p, sz, l, q, *shared, window=0)
        else:
            q, k, v = jnp.split(_linear(p["qkv_proj"], h),
                                [nh * dh, (nh + nkv) * dh], axis=-1)
            q, k, v = _heads(q, nh), _heads(k, nkv), _heads(v, nkv)
            if l == sz["shared_kv_layer"]:
                shared = (k, v)
            out = differential(p, sz, l, q, k, v,
                               sz["window"] if kind == "window" else 0)
        x = x + out
        gate, up = jnp.split(
            _linear(layer["mlp"]["fc1"],
                    _layer_norm(layer["post_mixer_norm"], x, sz["eps"])),
            2, axis=-1)
        x = x + _linear(layer["mlp"]["fc2"], jax.nn.silu(gate) * up)
    x = _layer_norm(params["final_norm"], x, sz["eps"])
    return jnp.matmul(x, params["embed_tokens"].astype(F32).T,
                      precision=HIGHEST)
