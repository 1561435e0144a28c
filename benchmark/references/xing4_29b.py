"""Plain reference of Xing4.0-29B-A4B (``model_type`` xing4_0).

Straightforward ``jax.numpy`` in float32, every product at precision
``highest``, no cache, no kernel, no batching: the expanded form of latent
attention one head after another, the experts one after another, the stream
maps with a Python loop for Sinkhorn, the head a block of vocabulary columns
at a time, so that a 6k-token prompt fits beside 4.9 B parameters. It imports
nothing of ``dlti_tpu``; ``sizes`` reads the configuration file alone
(``config["model"]`` as run), never the program's ``ModelConfig``.

## The residual path (mHC: manifold-constrained hyper-connections)

No layer is ``x + F(norm(x))``. A token's residual state is ``n = hc_mult``
streams, ``X in R^(n x C)``. ``X_0`` = the embedding row repeated n times.
Every sublayer ``F`` (``F(u) = Attn(RMSNorm(u))`` or ``MLP(RMSNorm(u))``,
two a layer) owns ``phi_pre, phi_post in R^(nC x n)``, ``phi_res in
R^(nC x n^2)``, ``b_pre, b_post in R^n``, ``b_res in R^(n x n)`` and scalars
``a_pre, a_post, a_res``:

    x~     = vec(X) * rsqrt(mean(vec(X)^2) + hc_eps)       (nC values, no weight)
    H_pre  = sigmoid(a_pre  * x~ phi_pre  + b_pre)                     (n,)
    H_post = 2 sigmoid(a_post * x~ phi_post + b_post)                  (n,)
    M0     = exp(clip(a_res * mat(x~ phi_res) + b_res,
                      mhc_h_res_clamp_min, mhc_h_res_clamp_max))       (n, n)
    H_res  = hc_sinkhorn_iters times { each column of M divided by its sum
             + hc_eps, then each row by its sum + hc_eps }
    u      = H_pre X = sum_j H_pre[j] X[j]                             (C,)
    X'     = H_res X + H_post^T F(u):  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] F(u)

``vec`` and ``mat`` are row-major. After the last layer the n streams are
summed, then ``RMSNorm`` and the untied head. RMSNorm with a weight:
``x * rsqrt(mean(x^2) + rms_norm_eps) * w``.

## Latent attention with a query latent, under YaRN

H = ``num_attention_heads``, r = ``kv_lora_rank``, ``q_lora_rank`` the query
latent's width, ``qk_nope_head_dim`` + ``qk_rope_head_dim`` a query head,
``v_head_dim`` a value head (x the normed input):

    c_q      = RMSNorm(x W_qa) (q_a_layernorm);   q_h = c_q W_qb,h = [q_nope,h ; q_rope,h]
    [c ; k_r] = x W_kva;   c <- RMSNorm(c) (kv_a_layernorm)
    q_rope,h <- RoPE(q_rope,h);   k_r <- RoPE(k_r)      (one rotated key serves every head)
    [k_nope,h ; v_h] = c W_kvb,h
    s_h = (q_nope,h . k_nope,h + q_rope,h . k_r) * scale, causal
    y   = [softmax(s_1) v_1 .. softmax(s_H) v_H] W_o

YaRN as the deepseek_v3 family writes it (d = ``qk_rope_head_dim``, L0 =
``original_max_position_embeddings``):

    inv_freq_i = (1 - m_i) theta^(-2i/d) / factor + m_i theta^(-2i/d)
    m_i        = 1 - clip((i - low) / (high - low), 0, 1)
    low, high  = floor, ceil of  d ln(L0 / (2 pi beta)) / (2 ln theta)  at beta_fast, beta_slow
    g(m)       = 0.1 m ln(factor) + 1
    cos, sin  *= g(mscale) / g(mscale_all_dim);   scale = (nope + rope)^-0.5 g(mscale_all_dim)^2

``rope_interleave`` true: frequency i turns the pair of entries (2i, 2i+1).

## MLP

Layers ``[0, first_k_dense_replace)``: a dense gated MLP of
``intermediate_size``. Every later layer: ``s = sigmoid(x W_r)`` over the
``n_routed_experts``; chosen = top-k of ``s + e_score_correction_bias``
(``noaux_tc``, ``n_group`` 1: no group limit); weights = ``s`` at the chosen
over their sum (``norm_topk_prob``) times ``routed_scaling_factor``; expert e
is a gated MLP of ``moe_intermediate_size``; ``n_shared_experts`` shared
experts as one gated MLP of their summed width run for every token.

## The cut (benchmark/configs/xing4_29b.json)

Stage 1 of 8 of a v5e-8 host, each layer whole on one chip: layers 0-6 (both
leading dense layers and five expert layers), all 64 experts, the whole
vocabulary. No width changed. The multi-token-prediction module lies on
stage 8 and is not computed.

## Departures from the published description, each on purpose

- What the catalog's keys do not settle is under ``assumed`` in the file: the
  streams' start and end, the place of ``hc_eps``, columns before rows, the
  weightless norm, ``rope_interleave``.
- The weights arrive in the program's storage precision (bf16; the stream
  maps' float32) and are cast up; every activation and product is float32.
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
HEAD_COLUMNS = 8192     # vocabulary columns of the head a step


def sizes(config):
    """Everything ``forward`` needs, from the configuration file alone."""
    m = config["model"]
    scaling = m.get("rope_scaling") or None
    if scaling is not None and scaling.get("type") != "yarn":
        raise ValueError(f"this reference knows YaRN alone, not {scaling}")
    return {
        "layers": int(m["num_hidden_layers"]),
        "dense_layers": int(m["first_k_dense_replace"]),
        "eps": float(m["rms_norm_eps"]),
        "hidden": int(m["hidden_size"]), "vocab": int(m["vocab_size"]),
        "heads": int(m["num_attention_heads"]),
        "rank": int(m["kv_lora_rank"]), "q_rank": int(m["q_lora_rank"]),
        "nope": int(m["qk_nope_head_dim"]),
        "rope": int(m["qk_rope_head_dim"]), "v": int(m["v_head_dim"]),
        "theta": float(m["rope_theta"]), "yarn": scaling,
        "interleave": bool(m["rope_interleave"]),
        "experts": int(m["n_routed_experts"]),
        "top_k": int(m["num_experts_per_tok"]),
        "scaling": float(m["routed_scaling_factor"]),
        "norm_topk": bool(m["norm_topk_prob"]),
        "streams": int(m["hc_mult"]),
        "sinkhorn_iters": int(m["hc_sinkhorn_iters"]),
        "hc_eps": float(m["hc_eps"]),
        "clamp": (float(m["mhc_h_res_clamp_min"]),
                  float(m["mhc_h_res_clamp_max"])),
    }


def _mm(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


# -- the stream maps -----------------------------------------------------------

def stream_maps(p, sz, X):
    """X (seq, n, C) -> H_pre (seq, n), H_post (seq, n), H_res (seq, n, n)."""
    n, eps = sz["streams"], sz["hc_eps"]
    seq = X.shape[0]
    flat = X.reshape(seq, -1)
    xt = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)

    def proj(name):
        phi = p[name].astype(F32)
        return jnp.matmul(xt, phi.reshape(-1, phi.shape[-1]),
                          precision=HIGHEST)

    h_pre = jax.nn.sigmoid(p["a_pre"] * proj("phi_pre") + p["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(p["a_post"] * proj("phi_post") + p["b_post"])
    m = jnp.exp(jnp.clip(
        p["a_res"] * proj("phi_res").reshape(seq, n, n) + p["b_res"],
        *sz["clamp"]))
    for _ in range(sz["sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)    # each column
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)    # each row
    return h_pre, h_post, m


def through_streams(p, sz, X, sublayer):
    h_pre, h_post, h_res = stream_maps(p, sz, X)
    u = jnp.einsum("sj,sjc->sc", h_pre, X, precision=HIGHEST)
    return jnp.einsum("sij,sjc->sic", h_res, X, precision=HIGHEST) \
        + h_post[:, :, None] * sublayer(u)[:, None, :]


# -- attention ---------------------------------------------------------------------

def yarn(sz):
    """(inv_freq (d/2,), what cos and sin are multiplied by, what the softmax
    scale is multiplied by)."""
    d, theta, s = sz["rope"], sz["theta"], sz["yarn"]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    if s is None:
        return inv_freq, 1.0, 1.0

    def pair_of(beta):
        return d * math.log(s["original_max_position_embeddings"]
                            / (2 * math.pi * beta)) / (2 * math.log(theta))

    def g(mscale):
        return 0.1 * mscale * math.log(s["factor"]) + 1.0

    low = max(math.floor(pair_of(s["beta_fast"])), 0)
    high = min(math.ceil(pair_of(s["beta_slow"])), d - 1)
    m = 1.0 - jnp.clip((jnp.arange(d // 2, dtype=F32) - low) / (high - low),
                       0.0, 1.0)
    return ((1.0 - m) * inv_freq / s["factor"] + m * inv_freq,
            g(s["mscale"]) / g(s["mscale_all_dim"]),
            g(s["mscale_all_dim"]) ** 2)


def rope(x, sz):
    """x (seq, ..., rope) at positions 0 .. seq - 1."""
    d = sz["rope"]
    inv_freq, amplitude, _ = yarn(sz)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq
    angle = angle.reshape(x.shape[0], *(1,) * (x.ndim - 2), d // 2)
    cos, sin = jnp.cos(angle) * amplitude, jnp.sin(angle) * amplitude
    if sz["interleave"]:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, sz, x):
    """x (seq, hidden) -> (seq, hidden): the expanded form, a head at a time."""
    H, r, nope, rd, vd = (sz["heads"], sz["rank"], sz["nope"], sz["rope"],
                          sz["v"])
    seq = x.shape[0]
    c_q = _rms(_mm(x, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"],
               sz["eps"])
    q = _mm(c_q, p["q_b_proj"]["kernel"]).reshape(seq, H, nope + rd)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], sz)
    kv_a = _mm(x, p["kv_a_proj"]["kernel"])
    c = _rms(kv_a[:, :r], p["kv_a_norm"]["scale"], sz["eps"])
    k_rope = rope(kv_a[:, r:], sz)                                # (seq, rd)
    w_kvb = p["kv_b_proj"].astype(F32).reshape(r, H, nope + vd)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scale = (nope + rd) ** -0.5 * yarn(sz)[2]

    def head(_, h):
        qn, qr, w = h                        # (seq, nope), (seq, rd), (r, ..)
        kv = jnp.matmul(c, w, precision=HIGHEST)             # (seq, nope+vd)
        s = (jnp.matmul(qn, kv[:, :nope].T, precision=HIGHEST)
             + jnp.matmul(qr, k_rope.T, precision=HIGHEST)) * scale
        s = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return None, jnp.matmul(s, kv[:, nope:], precision=HIGHEST)

    _, out = jax.lax.scan(head, None, (jnp.moveaxis(q_nope, 1, 0),
                                       jnp.moveaxis(q_rope, 1, 0),
                                       jnp.moveaxis(w_kvb, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(seq, H * vd)
    return _mm(out, p["o_proj"]["kernel"])


# -- MLPs --------------------------------------------------------------------------

def gated(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


def dense_mlp(p, sz, x):
    return gated(x, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                 p["down_proj"]["kernel"])


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

    def one(y, e):
        w_gate, w_up, w_down, w_e = e
        return y + w_e[:, None] * gated(x, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["w_gate"], p["w_up"], p["w_down"], weights.T))
    return y + gated(x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                     p["shared_down"]["kernel"])


def head_logits(x, w):
    """x (seq, hidden) W (hidden, vocab), ``HEAD_COLUMNS`` columns a step:
    the float32 copy of a block of the head is small."""
    vocab = w.shape[1]
    cols = HEAD_COLUMNS if vocab % HEAD_COLUMNS == 0 else vocab

    def step(i, out):
        block = jax.lax.dynamic_slice_in_dim(w, i * cols, cols, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _mm(x, block), i * cols, axis=1)

    return jax.lax.fori_loop(0, vocab // cols, step,
                             jnp.zeros((x.shape[0], vocab), F32))


def forward(params, sizes, ids):
    """float32 logits (seq, vocab) of one sequence ``ids``, a layer at a
    time."""
    x = params["embed_tokens"][ids].astype(F32)
    X = jnp.repeat(x[:, None, :], sizes["streams"], axis=1)     # (seq, n, C)
    for i in range(sizes["layers"]):
        layer = params[f"layers_{i}"]
        X = through_streams(
            layer["attn_hc"], sizes, X, lambda u: attention(
                layer["attn"], sizes,
                _rms(u, layer["input_norm"]["scale"], sizes["eps"])))
        mlp = dense_mlp if i < sizes["dense_layers"] else experts
        X = through_streams(
            layer["mlp_hc"], sizes, X, lambda u: mlp(
                layer["mlp"], sizes,
                _rms(u, layer["post_attn_norm"]["scale"], sizes["eps"])))
    x = _rms(jnp.sum(X, axis=1), params["final_norm"]["scale"], sizes["eps"])
    return head_logits(x, params["lm_head"])
