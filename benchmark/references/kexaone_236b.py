"""Plain reference of K-EXAONE-236B-A23B (``model_type`` exaone_moe).

Straightforward ``jax.numpy`` in float32, every product at precision
``highest``, no cache, no kernel, no batching: one sequence, a layer at a
time, attention a key-value head and a block of queries at a time under an
explicit (banded) mask, so that a 9,000-token prompt fits beside 3.7 B
parameters. It imports nothing of ``dlti_tpu``; ``sizes`` reads the
configuration file alone (``config["model"]`` as run, ``published``,
``share`` and ``assumed`` beside it), never the program's ``ModelConfig``.

## The layer equations

``x = embed[ids]``; layer l with window ``w_l`` (``sliding_window`` where
``layer_types[l]`` is ``sliding_attention``, none where ``full_attention``):

    a   = RMSNorm_in(x)                                  (eps rms_norm_eps)
    q_h = RMSNorm_q(a W_q)_h, k_g = RMSNorm_k(a W_k)_g, v_g = (a W_v)_g
          h < 64 query heads, g = h // 8 < 8 key-value heads, 128 wide; the
          two norms over the 128 values of a head, one weight of 128 each
    (q_h, k_g) = RoPE(q_h, k_g; position, rope_theta, halves)
          in window layers; in full layers unchanged (no position)
    s_hij = q_hi . k_gj / sqrt(128)   for j <= i and (no window or
          i - j < w_l); else -inf
    o   = concat_h(softmax_j(s_hi.) v_g) W_o
    x   = x + o
    m   = RMSNorm_post(x)
    l < first_k_dense_replace:  f = (silu(m W_gate) * (m W_up)) W_down
    else: p = sigmoid(m W_r) over all published experts; S = top-k of
          (p + e_score_correction_bias); g_e = routed_scaling_factor
          p_e / sum_{e' in S} p_e' (norm_topk_prob);
          f = sum_{e in S, held here} g_e E_e(m) + E_shared(m),
          E(m) = (silu(m W_g) * (m W_u)) W_d at moe_intermediate_size
          (``n_group`` 1, ``topk_group`` 1: no group limit)
    x   = x + f
    logits = RMSNorm_final(x) W_head

RMSNorm: ``x * rsqrt(mean(x^2) + eps) * w``. RoPE by halves: frequency i of
head_dim / 2 turns the pair of entries (i, i + head_dim / 2) by
``position x theta^(-2i / head_dim)``.

## Conventions the catalog's keys do not settle (``assumed`` in the file)

Three booleans, each read here from ``assumed.<name>.value`` and by the
program from its own ``program`` object, so that a disagreement shows:

- ``qk_norm`` true: the norms on queries and keys above (the ``exaone4``
  family's published attention). False: none.
- ``rope_on_full_layers`` false: the layers that see every key do not
  rotate (the same code: a hybrid model's global layers carry no position).
  True: every layer rotates.
- ``post_sublayer_norm`` false: the pre-norm block above (the
  deepseek_v3-style keys of this config come from pre-norm code). True:
  ``x = x + RMSNorm_in(Attn(x))``, ``x = x + RMSNorm_post(MLP(x))``, the
  ``exaone4`` family's own placement.

## The cut (benchmark/configs/kexaone_236b.json)

Each layer shared by eight chips (experts ``[16 r, 16 r + 16)`` on rank r,
attention whole on every rank, an eighth of the vocabulary's rows), the
layers in pipeline stages; this is rank 0 of stage 1: layers 0-4 (the dense
layer, then ``L L G L`` of the expert layers), experts ``share.experts`` =
[0, 16) of 128, rows [0, 19200) of the vocabulary for embedding and head.
The router keeps its 128 outputs and top-8. No width is changed.

## Departures from the published description, each on purpose

- **The held experts**: what experts 16-127 would add to a token is left
  out, here as in the program, and that partial result goes on.
- **The sliced vocabulary**: embedding and head have the slice's rows; the
  logits are over the slice.
- **No multi-token-prediction module** (``num_nextn_predict_layers`` 0 as
  run): it lies on the deployment's last stage.
- The weights arrive in the program's storage precision (bf16) and are cast
  up; every activation and every product is float32. The held experts run
  one after another over all tokens with the routing weight as a mask.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERY_BLOCK = 512


def sizes(config):
    """Everything ``forward`` needs, from the configuration file alone."""
    m = config["model"]
    layers = int(m["num_hidden_layers"])
    kinds = list(m["layer_types"])
    if len(kinds) != layers:
        raise ValueError("layer_types does not state num_hidden_layers kinds")
    held = int(m["num_experts"])
    experts = int(config.get("published", {}).get("num_experts", held))
    start = int(config.get("share", {}).get("experts", [0, held])[0])
    assumed = config.get("assumed", {})

    def convention(name):
        return bool(assumed[name]["value"])

    return {
        "layers": layers,
        "windows": [int(m["sliding_window"]) if k == "sliding_attention"
                    else 0 for k in kinds],
        "dense_layers": int(m["first_k_dense_replace"]),
        "eps": float(m["rms_norm_eps"]),
        "hidden": int(m["hidden_size"]), "vocab": int(m["vocab_size"]),
        "heads": int(m["num_attention_heads"]),
        "kv_heads": int(m["num_key_value_heads"]),
        "head_dim": int(m["head_dim"]),
        "theta": float(m["rope_parameters"]["rope_theta"]),
        "experts": experts, "held_start": start, "held": held,
        "top_k": int(m["num_experts_per_tok"]),
        "scaling": float(m["routed_scaling_factor"]),
        "norm_topk": bool(m["norm_topk_prob"]),
        "qk_norm": convention("qk_norm"),
        "rope_on_full_layers": convention("rope_on_full_layers"),
        "post_sublayer_norm": convention("post_sublayer_norm"),
    }


def _mm(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, sz):
    """x (seq, heads, head_dim) at positions 0 .. seq - 1, by halves."""
    d = sz["head_dim"]
    freq = sz["theta"] ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, sz, x, window):
    """x (seq, hidden) -> (seq, hidden); ``window`` 0: every earlier key."""
    H, G, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    seq = x.shape[0]
    q = _mm(x, p["q_proj"]["kernel"]).reshape(seq, H, d)
    k = _mm(x, p["k_proj"]["kernel"]).reshape(seq, G, d)
    v = _mm(x, p["v_proj"]["kernel"]).reshape(seq, G, d)
    if sz["qk_norm"]:
        q = _rms(q, p["q_norm"]["scale"], sz["eps"])
        k = _rms(k, p["k_norm"]["scale"], sz["eps"])
    if window or sz["rope_on_full_layers"]:
        q, k = rope(q, sz), rope(k, sz)
    block = min(QUERY_BLOCK, seq)
    pad = -seq % block
    # (group, query blocks, block, heads of the group, d)
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, G, H // G, d).transpose(2, 0, 1, 3, 4)
    j = jnp.arange(seq)[None, :]

    def group(_, g):
        q_blocks, kg, vg = g                   # (nb, block, hpg, d), (seq, d)

        def queries(_, qb):
            at, qs = qb                                    # (block, hpg, d)
            i = (at * block + jnp.arange(block))[:, None]
            seen = j <= i
            if window:
                seen &= i - j < window
            s = jnp.einsum("qhd,kd->hqk", qs, kg, precision=HIGHEST) \
                * d ** -0.5
            s = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return None, jnp.einsum("hqk,kd->qhd", s, vg, precision=HIGHEST)

        _, out = jax.lax.scan(queries, None,
                              (jnp.arange(q_blocks.shape[0]), q_blocks))
        return None, out                               # (nb, block, hpg, d)

    _, out = jax.lax.scan(group, None, (qg, jnp.moveaxis(k, 1, 0),
                                        jnp.moveaxis(v, 1, 0)))
    # (G, nb, block, hpg, d) -> (seq, H * d), head h = g * hpg + its place
    out = out.transpose(1, 2, 0, 3, 4).reshape(-1, H * d)[:seq]
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


def experts(p, sz, x):
    weights = route(p, sz, x)
    mine = jax.lax.dynamic_slice_in_dim(weights, sz["held_start"],
                                        sz["held"], axis=1)

    def one(y, e):
        w_gate, w_up, w_down, w_e = e
        return y + w_e[:, None] * gated(x, w_gate, w_up, w_down), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["w_gate"], p["w_up"], p["w_down"], mine.T))
    return y + gated(x, p["shared_gate"]["kernel"], p["shared_up"]["kernel"],
                     p["shared_down"]["kernel"])


def forward(params, sizes, ids):
    """float32 logits (seq, vocab of the slice) of one sequence ``ids``,
    a layer at a time."""
    body = params["model"]
    x = body["embed_tokens"][ids].astype(F32)
    after = sizes["post_sublayer_norm"]
    for i in range(sizes["layers"]):
        layer = body[f"layers_{i}"]

        def norm_in(u):
            return _rms(u, layer["input_norm"]["scale"], sizes["eps"])

        def norm_post(u):
            return _rms(u, layer["post_attn_norm"]["scale"], sizes["eps"])

        mlp = dense_mlp if i < sizes["dense_layers"] else experts
        window = sizes["windows"][i]
        if after:
            x = x + norm_in(attention(layer["attn"], sizes, x, window))
            x = x + norm_post(mlp(layer["mlp"], sizes, x))
        else:
            x = x + attention(layer["attn"], sizes, norm_in(x), window)
            x = x + mlp(layer["mlp"], sizes, norm_post(x))
    x = _rms(x, body["final_norm"]["scale"], sizes["eps"])
    return _mm(x, params["lm_head"])
