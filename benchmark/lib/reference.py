"""The plain reference: one decoder block family in straightforward float32.

RMSNorm, rotary embedding (split-half, as published for these families),
grouped-query attention with an optional sliding window and optional q/k/v
bias, SwiGLU, untied or tied head. No kernels, no cache, no batching tricks:
full attention scores with an explicit mask, everything in float32 at
precision "highest" (on a TPU a float32 matmul is otherwise done in
bfloat16 passes), named on every product and set as the default besides. Loss is next-token cross-entropy over a
mask; gradients come from ``jax.grad`` of it.

It reads the weights from the program's parameter tree, because agreement is
only meaningful on the same weights; ``layer_weights`` is the one place that
knows that tree's names. Weights stay in the dtype they are served in and
are cast to float32 a layer at a time, so that a 7B-wide model fits beside
them; ``jax.checkpoint`` around a layer only saves memory in the backward
pass and changes no value.

Departures from the published descriptions: none for Mistral-7B-v0.1 and
Qwen2-7B (sliding window: a query at position i sees keys j with
i - window < j <= i, as in the Mistral reference implementation).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
# Every product names its precision itself, so that the backward pass (whose
# matmuls jax.grad makes by transposing these, after forward() has returned)
# cannot fall back to the default wherever it is lowered.
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def sizes(config: dict) -> dict:
    """What ``forward`` and ``grad`` need, from the configuration file as
    run: ``config["model"]`` holds the keys of the model's public
    config.json. Never from the program's ``ModelConfig``: a key that the
    program drops or mistranslates has to show as a disagreement."""
    m = config["model"]
    heads = m["num_attention_heads"]
    window = m.get("sliding_window") \
        if m.get("use_sliding_window", True) else None
    return {"num_layers": m["num_hidden_layers"],
            "num_heads": heads,
            "num_kv_heads": m["num_key_value_heads"],
            "head_dim": m.get("head_dim") or m["hidden_size"] // heads,
            "rms_norm_eps": m["rms_norm_eps"],
            "rope_theta": m["rope_theta"],
            "sliding_window": int(window) if window else None,
            "tie_embeddings": bool(m.get("tie_word_embeddings", False))}


def layer_weights(params: dict, i: int) -> dict:
    """Layer ``i`` of the program's parameter tree, by this module's names.
    A projection is ``(kernel, bias or None, lora_a or None, lora_b)``."""
    layer = params["model"][f"layers_{i}"]

    def proj(node):
        return (node["kernel"], node.get("bias"), node.get("lora_a"),
                node.get("lora_b"))

    attn, mlp = layer["attn"], layer["mlp"]
    return {"input_norm": layer["input_norm"]["scale"],
            "post_attn_norm": layer["post_attn_norm"]["scale"],
            "q": proj(attn["q_proj"]), "k": proj(attn["k_proj"]),
            "v": proj(attn["v_proj"]), "o": proj(attn["o_proj"]),
            "gate": proj(mlp["gate_proj"]), "up": proj(mlp["up_proj"]),
            "down": proj(mlp["down_proj"])}


def _linear(x, proj, lora_scaling: float):
    kernel, bias, lora_a, lora_b = proj
    y = _mm(x, kernel.astype(F32))
    if bias is not None:
        y = y + bias.astype(F32)
    if lora_a is not None:
        y = y + lora_scaling * _mm(_mm(x, lora_a.astype(F32)),
                                   lora_b.astype(F32))
    return y


def _rms_norm(x, scale, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, positions, theta: float):
    """x: (seq, heads, head_dim); rotate halves by position."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) * 2.0
                                / x.shape[-1]))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, w, m: dict, positions, segments, lora_scaling: float):
    """One decoder layer on one sequence. x: (seq, hidden)."""
    n_q, n_kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    seq = x.shape[0]
    h = _rms_norm(x, w["input_norm"], m["rms_norm_eps"])
    q = _linear(h, w["q"], lora_scaling).reshape(seq, n_q, hd)
    k = _linear(h, w["k"], lora_scaling).reshape(seq, n_kv, hd)
    v = _linear(h, w["v"], lora_scaling).reshape(seq, n_kv, hd)
    q = _rope(q, positions, m["rope_theta"])
    k = _rope(k, positions, m["rope_theta"])
    group = n_q // n_kv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=HIGHEST) / jnp.sqrt(F32(hd))
    idx = jnp.arange(seq)
    allowed = idx[:, None] >= idx[None, :]
    if m.get("sliding_window"):
        allowed &= idx[:, None] - idx[None, :] < m["sliding_window"]
    if segments is not None:
        allowed &= segments[:, None] == segments[None, :]
    scores = jnp.where(allowed[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v,
                      precision=HIGHEST).reshape(seq, n_q * hd)
    x = x + _linear(attn, w["o"], lora_scaling)
    h = _rms_norm(x, w["post_attn_norm"], m["rms_norm_eps"])
    gated = jax.nn.silu(_linear(h, w["gate"], lora_scaling)) \
        * _linear(h, w["up"], lora_scaling)
    return x + _linear(gated, w["down"], lora_scaling)


def forward(params: dict, m: dict, ids, positions=None, segments=None,
            lora_scaling: float = 0.0):
    """Float32 logits (seq, vocab) of one sequence ``ids`` (seq,).
    ``m``: the sizes (``num_layers``, ``num_heads``, ``num_kv_heads``,
    ``head_dim``, ``rms_norm_eps``, ``rope_theta``, ``sliding_window``,
    ``tie_embeddings``)."""
    with jax.default_matmul_precision("highest"):
        if positions is None:
            positions = jnp.arange(ids.shape[0])
        x = params["model"]["embed_tokens"][ids].astype(F32)
        block = jax.checkpoint(_block, static_argnums=(2, 5))
        for i in range(m["num_layers"]):
            x = block(x, layer_weights(params, i), _Frozen(m), positions,
                      segments, lora_scaling)
        x = _rms_norm(x, params["model"]["final_norm"]["scale"],
                      m["rms_norm_eps"])
        if m.get("tie_embeddings"):
            return _mm(x, params["model"]["embed_tokens"].astype(F32).T)
        return _mm(x, params["lm_head"].astype(F32))


class _Frozen(dict):
    """A hashable view of the sizes, so they can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def token_logprobs(params: dict, m: dict, ids, positions=None,
                   segments=None, lora_scaling: float = 0.0):
    """Log-probability of each next token ``ids[1:]`` (seq - 1,)."""
    logits = forward(params, m, ids, positions, segments, lora_scaling)
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return jnp.take_along_axis(logp, ids[1:, None], axis=-1)[:, 0]


def loss(params: dict, m: dict, ids, mask, positions=None, segments=None,
         lora_scaling: float = 0.0):
    """(sum of next-token cross-entropy over ``mask[1:]``, token count)."""
    picked = token_logprobs(params, m, ids, positions, segments,
                            lora_scaling)
    w = mask[1:].astype(F32)
    return -(picked * w).sum(), w.sum()


def is_lora(path) -> bool:
    return any(getattr(k, "key", None) in ("lora_a", "lora_b") for k in path)


def split(params: dict, chosen) -> tuple:
    """(leaves whose path ``chosen`` accepts, everything else), as two
    trees of one shape with ``None`` where the other holds the leaf."""
    picked = jax.tree_util.tree_map_with_path(
        lambda p, v: v if chosen(p) else None, params)
    rest = jax.tree_util.tree_map_with_path(
        lambda p, v: None if chosen(p) else v, params)
    return picked, rest


def merge(picked: dict, rest: dict) -> dict:
    return jax.tree_util.tree_map(
        lambda a, b: b if a is None else a, picked, rest,
        is_leaf=lambda v: v is None)


def grad(params: dict, m: dict, batch: dict, lora_scaling: float, chosen):
    """(mean loss, gradient tree of the leaves ``chosen`` accepts, log-
    probabilities of the next tokens (rows, seq - 1)) over the rows of
    ``batch`` (``input_ids``, ``loss_mask``, ``positions``,
    ``segment_ids``; each (rows, seq)), one row at a time."""
    picked, rest = split(params, chosen)

    # ``rest`` is an argument, not a closure: closed-over weights would be
    # baked into the compiled program as constants.
    def row_loss(leaves, rest, ids, mask, pos, seg):
        logp = token_logprobs(merge(leaves, rest), m, ids, pos, seg,
                              lora_scaling)
        w = mask[1:].astype(F32)
        return -(logp * w).sum(), (w.sum(), logp)

    grad_fn = jax.jit(jax.value_and_grad(row_loss, has_aux=True))
    total, count, grads, logps = 0.0, 0.0, None, []
    for r in range(batch["input_ids"].shape[0]):
        (s, (n, logp)), g = grad_fn(
            picked, rest, batch["input_ids"][r], batch["loss_mask"][r],
            batch["positions"][r], batch["segment_ids"][r])
        total, count = total + float(s), count + float(n)
        logps.append(logp)
        grads = g if grads is None else jax.tree_util.tree_map(
            lambda a, b: a + b, grads, g)
    grads = jax.tree_util.tree_map(lambda g: g / count, grads)
    return total / count, grads, jnp.stack(logps)
