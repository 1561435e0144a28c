"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type`` nemotron_h).

Straightforward ``jax.numpy`` in float32, every product at precision
``highest``, no cache, no batching, no chunked scan: the recurrence is run
one token after another. It imports nothing of ``dlti_tpu``; ``sizes`` reads
the configuration file alone (``config["model"]`` as run, ``published`` and
``share`` beside it), never the program's ``ModelConfig``.

## The layer equations

``x = embed[ids]``; for each layer ``i``,
``x = x + Mixer_i(RMSNorm(x, eps = layer_norm_epsilon))`` with the mixer
named by character ``i`` of ``hybrid_override_pattern``; then ``RMSNorm`` and
the untied head. RMSNorm: ``x * rsqrt(mean(x^2) + eps) * w``.

**M, Mamba-2** (H = ``mamba_num_heads`` heads of P = ``mamba_head_dim``
channels, d_inner = H P, not ``expand`` x hidden; G = ``n_groups``; N =
``ssm_state_size``; K = ``conv_kernel``; ``use_conv_bias`` true;
``mamba_proj_bias`` false):

    [z (d_inner) | xBC (d_inner + 2 G N) | dt (H)] = in_proj(x)
    xBC = silu(causal depthwise conv1d(xBC, width K) + bias)
    [x (H, P) | B (G, N) | C (G, N)] = xBC; head h uses group h // (H / G)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)                 per head
    h_t = exp(dt_t A) h_{t-1} + dt_t * x_t (outer) B_t      h in R^{P x N}
    y_t = h_t C_t + D x_t
    y = RMSNorm_groups(y * silu(z)) * w   within each of the G groups of
        d_inner / G channels, gate before norm, eps layer_norm_epsilon
    out = out_proj(y)

``time_step_min/max/floor`` shape the seeded ``dt_bias`` only; ``chunk_size``
is a block of the program's scan and changes no result.

**\\*, attention**: q hidden -> heads x head_dim, k and v hidden -> kv heads x
head_dim, no bias, causal softmax at scale head_dim^-1/2, o back to hidden.
**No rotary embedding**: the nemotron_h family applies none; ``rope_theta``
and ``partial_rotary_factor`` of the published config are unused (listed
under ``assumed`` in the configuration file).

**E, experts**: ``s = sigmoid(x W_r)`` over all published experts; chosen =
top-k of ``s + e_score_correction_bias`` (``n_group`` 1, ``topk_group`` 1: no
group limit); weights = ``s`` at the chosen, divided by their sum
(``norm_topk_prob``), times ``routed_scaling_factor``. Expert e:
``W_down,e relu(W_up,e x)^2`` (``mlp_hidden_act`` relu2: no gate, no bias).
Shared expert: the same form at ``moe_shared_expert_intermediate_size``, for
every token. Output: the weighted sum over the chosen experts **that are
held here**, plus the shared expert.

## The cut (benchmark/configs/nemotron3_nano_30b.json)

One v5e-8 host, four pipeline stages of 13 layers, each layer shared by two
chips: experts and vocabulary halved, mixers and the shared expert whole on
both. This is one chip of stage 1: layers 0-12 (``MEMEM*EMEMEM*``), experts
``share.experts`` = [0, 64) of 128 in each expert layer, rows [0, 65536) of
the vocabulary for embedding and head. The router keeps its 128 outputs and
top-6. What the absent experts would have added is left out here as in the
program, and that partial result goes on to the next layer. No width is
changed.

## Departures, each on purpose

- The weights arrive in the program's storage precision (bf16) and are cast
  up; every activation and every product is float32.
- The held experts are run one after another over all tokens with the
  routing weight as a mask (zero where the token did not choose the expert):
  the same sum, with nothing sorted or gathered.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def sizes(config):
    """Everything ``forward`` needs, from the configuration file alone."""
    m = config["model"]
    held = int(m["n_routed_experts"])
    experts = int(config.get("published", {}).get("n_routed_experts", held))
    start = int(config.get("share", {}).get("experts", [0, held])[0])
    pattern = m["hybrid_override_pattern"][:int(m["num_hidden_layers"])]
    heads, head_p = int(m["mamba_num_heads"]), int(m["mamba_head_dim"])
    return {
        "pattern": pattern, "eps": float(m["layer_norm_epsilon"]),
        "hidden": int(m["hidden_size"]), "vocab": int(m["vocab_size"]),
        "m_heads": heads, "m_head_dim": head_p, "m_inner": heads * head_p,
        "m_groups": int(m["n_groups"]), "m_state": int(m["ssm_state_size"]),
        "m_conv": int(m["conv_kernel"]),
        "heads": int(m["num_attention_heads"]),
        "kv_heads": int(m["num_key_value_heads"]),
        "head_dim": int(m["head_dim"]),
        "experts": experts, "held_start": start, "held": held,
        "top_k": int(m["num_experts_per_tok"]),
        "scaling": float(m["routed_scaling_factor"]),
        "norm_topk": bool(m["norm_topk_prob"]),
    }


def _mm(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mamba2(p, sz, x):
    """x (seq, hidden) -> (seq, hidden), one token after another."""
    H, P, G, N, K = (sz["m_heads"], sz["m_head_dim"], sz["m_groups"],
                     sz["m_state"], sz["m_conv"])
    d_in = sz["m_inner"]
    seq = x.shape[0]
    zxbcdt = _mm(x, p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * G * N], axis=-1)
    w, bias = p["conv_kernel"].astype(F32), p["conv_bias"].astype(F32)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(sum(w[k] * padded[k:k + seq] for k in range(K)) + bias)
    xs, b_in, c_in = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    xs = xs.reshape(seq, H, P)
    b_in = jnp.repeat(b_in.reshape(seq, G, N), H // G, axis=1)   # per head
    c_in = jnp.repeat(c_in.reshape(seq, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))           # (seq, H)
    a = -jnp.exp(p["A_log"].astype(F32))

    def step(h, t):
        x_t, b_t, c_t, dt_t = t
        h = jnp.exp(dt_t * a)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32),
                        (xs, b_in, c_in, dt))
    y = y + p["D"].astype(F32)[:, None] * xs
    y = (y.reshape(seq, d_in) * jax.nn.silu(z)).reshape(seq, G, d_in // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + sz["eps"])
    y = y.reshape(seq, d_in) * p["norm_scale"].astype(F32)
    return _mm(y, p["out_proj"]["kernel"])


def attention(p, sz, x):
    nh, nkv, hd = sz["heads"], sz["kv_heads"], sz["head_dim"]
    seq = x.shape[0]
    q = _mm(x, p["q_proj"]["kernel"]).reshape(seq, nkv, nh // nkv, hd)
    k = _mm(x, p["k_proj"]["kernel"]).reshape(seq, nkv, hd)
    v = _mm(x, p["v_proj"]["kernel"]).reshape(seq, nkv, hd)
    s = jnp.einsum("qgrd,kgd->grqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    s = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    out = jnp.einsum("grqk,kgd->qgrd", s, v, precision=HIGHEST)
    return _mm(out.reshape(seq, nh * hd), p["o_proj"]["kernel"])


def route(p, sz, x):
    """(seq, experts) routing weights: zero where not chosen."""
    scores = jax.nn.sigmoid(_mm(x, p["router"]))
    _, chosen = jax.lax.top_k(
        scores + p["e_score_correction_bias"].astype(F32), sz["top_k"])
    w = jnp.take_along_axis(scores, chosen, axis=1)
    if sz["norm_topk"]:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    w = w * sz["scaling"]
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(w)


def experts(p, sz, x):
    weights = route(p, sz, x)
    mine = jax.lax.dynamic_slice_in_dim(weights, sz["held_start"],
                                        sz["held"], axis=1)

    def one(y, e):
        w_up, w_down, w_e = e
        return y + w_e[:, None] * _mm(_relu2(_mm(x, w_up)), w_down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["w_up"], p["w_down"], mine.T))
    return y + _mm(_relu2(_mm(x, p["shared_up"]["kernel"])),
                   p["shared_down"]["kernel"])


MIXERS = {"M": mamba2, "*": attention, "E": experts}


def forward(params, sizes, ids):
    """float32 logits (seq, vocab of the slice) of one sequence ``ids``,
    a layer at a time."""
    x = params["embed_tokens"][ids].astype(F32)
    for i, kind in enumerate(sizes["pattern"]):
        layer = params[f"layers_{i}"]
        x = x + MIXERS[kind](layer["mixer"], sizes,
                             _rms(x, layer["norm"]["scale"], sizes["eps"]))
    x = _rms(x, params["final_norm"]["scale"], sizes["eps"])
    return _mm(x, params["lm_head"])
