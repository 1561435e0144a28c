"""Plain reference of kanana-2-30b-a3b-instruct-2601 (``model_type`` deepseek_v3).

Straightforward ``jax.numpy`` in float32, every product at precision
``highest``, no cache, no kernel, no batching, the expanded form of latent
attention only, one head after another so that a 6k-token prompt fits beside
4 B parameters. It imports nothing of ``dlti_tpu``; ``sizes`` reads the
configuration file alone (``config["model"]`` as run, ``published`` and
``share`` beside it), never the program's ``ModelConfig``.

## The layer equations

``x = embed[ids]``; for each layer, ``x = x + Attn(RMSNorm(x))`` then
``x = x + MLP(RMSNorm(x))`` (eps ``rms_norm_eps``); then ``RMSNorm`` and the
untied head. RMSNorm: ``x * rsqrt(mean(x^2) + eps) * w``.

**Latent attention** (H = ``num_attention_heads``, r = ``kv_lora_rank``,
``qk_nope_head_dim`` + ``qk_rope_head_dim`` = ``qk_head_dim`` a query head,
``v_head_dim`` a value head; ``q_lora_rank`` null: one query projection):

    q_h      = x W_q,h = [q_nope,h ; q_rope,h];   q_rope,h <- RoPE(q_rope,h)
    [c ; k_r] = x W_kva;   c <- RMSNorm_r(c) (kv_a_layernorm);  k_r <- RoPE(k_r)
    [k_nope,h ; v_h] = c W_kvb,h
    s_h = (q_nope,h . k_nope,h + q_rope,h . k_r) / sqrt(qk_head_dim), causal
    y   = [softmax(s_1) v_1 .. softmax(s_H) v_H] W_o

One rotated key ``k_r`` serves every head. RoPE (``rope_interleave`` true,
``rope_theta``, ``rope_scaling`` null so no scale correction): frequency i of
``qk_rope_head_dim`` / 2 turns the pair of entries (2i, 2i + 1) by
``position x theta^(-2i / qk_rope_head_dim)``. (HF's module gathers the even
and the odd entries into halves before turning them; queries and key get the
same permutation, so every score is the same.)

**MLP**: layers ``[0, first_k_dense_replace)`` a dense gated MLP of
``intermediate_size``, ``W_down (silu(W_gate x) * W_up x)``; every later
layer (``moe_layer_freq`` 1) routed experts: ``s = sigmoid(x W_r)`` over all
published experts; chosen = top-k of ``s + e_score_correction_bias``
(``topk_method`` noaux_tc with ``n_group`` 1, ``topk_group`` 1: no group
limit); weights = ``s`` at the chosen, divided by their sum
(``norm_topk_prob``), times ``routed_scaling_factor``. Expert e is the gated
MLP at ``moe_intermediate_size``; ``n_shared_experts`` shared experts of the
same width run for every token, here as one gated MLP of their summed width
(the sum of n gated MLPs of width f is one of width n f). Output: the
weighted sum over the chosen experts **that are held here**, plus the shared.

## The cut (benchmark/configs/kanana2_30b.json)

One v5e-8 host, four pipeline stages of 12 layers, each layer shared by two
chips: routed experts and vocabulary halved, attention and the shared
experts whole on both. This is one chip of stage 1: layers 0-11 (the dense
layer and 11 expert layers), experts ``share.experts`` = [0, 64) of 128,
rows [0, 64128) of the vocabulary for embedding and head. The router keeps
its 128 outputs and top-6. No width is changed.

## Departures from the published description, each on purpose

- **The held experts**: what experts 64-127 would add to a token is left
  out, here as in the program, and that partial result goes on.
- **The sliced vocabulary**: embedding and head have the slice's rows; the
  logits are over the slice.
- The weights arrive in the program's storage precision (bf16) and are cast
  up; every activation and every product is float32. The held experts run
  one after another over all tokens with the routing weight as a mask.
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
    if m.get("rope_scaling") or m.get("q_lora_rank"):
        raise ValueError("this reference knows neither rope scaling nor a "
                         "query latent")
    return {
        "layers": int(m["num_hidden_layers"]),
        "dense_layers": int(m["first_k_dense_replace"]),
        "eps": float(m["rms_norm_eps"]),
        "hidden": int(m["hidden_size"]), "vocab": int(m["vocab_size"]),
        "heads": int(m["num_attention_heads"]),
        "rank": int(m["kv_lora_rank"]), "nope": int(m["qk_nope_head_dim"]),
        "rope": int(m["qk_rope_head_dim"]), "v": int(m["v_head_dim"]),
        "theta": float(m["rope_theta"]),
        "interleave": bool(m["rope_interleave"]),
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


def rope(x, sz):
    """x (seq, ..., rope) at positions 0 .. seq - 1."""
    d = sz["rope"]
    freq = sz["theta"] ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq    # (seq, d/2)
    angle = angle.reshape(x.shape[0], *(1,) * (x.ndim - 2), d // 2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
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
    q = _mm(x, p["q_proj"]["kernel"]).reshape(seq, H, nope + rd)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], sz)
    kv_a = _mm(x, p["kv_a_proj"]["kernel"])
    c = _rms(kv_a[:, :r], p["kv_a_norm"]["scale"], sz["eps"])
    k_rope = rope(kv_a[:, r:], sz)                                # (seq, rd)
    w_kvb = p["kv_b_proj"].astype(F32).reshape(r, H, nope + vd)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scale = (nope + rd) ** -0.5

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


def shared_experts(p, x):
    return gated(x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                 p["shared_down"]["kernel"])


def experts(p, sz, x):
    weights = route(p, sz, x)
    mine = jax.lax.dynamic_slice_in_dim(weights, sz["held_start"],
                                        sz["held"], axis=1)

    def one(y, e):
        w_gate, w_up, w_down, w_e = e
        return y + w_e[:, None] * gated(x, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["w_gate"], p["w_up"], p["w_down"], mine.T))
    return y + shared_experts(p, x)


def forward(params, sizes, ids):
    """float32 logits (seq, vocab of the slice) of one sequence ``ids``,
    a layer at a time."""
    x = params["embed_tokens"][ids].astype(F32)
    for i in range(sizes["layers"]):
        layer = params[f"layers_{i}"]
        x = x + attention(layer["attn"], sizes, _rms(
            x, layer["input_norm"]["scale"], sizes["eps"]))
        mlp = dense_mlp if i < sizes["dense_layers"] else experts
        x = x + mlp(layer["mlp"], sizes, _rms(
            x, layer["post_attn_norm"]["scale"], sizes["eps"]))
    x = _rms(x, params["final_norm"]["scale"], sizes["eps"])
    return _mm(x, params["lm_head"])
