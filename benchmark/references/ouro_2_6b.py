"""Plain reference of Ouro-2.6B (``model_type`` ouro): a decoder whose
layers run several times over one set of weights.

Straightforward ``jax.numpy`` in float32, every product at precision
``highest``, no cache, no kernel, no batching: one sequence, a Python loop
over the passes and inside it over the layers. It imports nothing of
``dlti_tpu``; ``sizes`` reads the configuration file alone
(``config["model"]`` as run and ``assumed`` beside it), never the program's
``ModelConfig``.

## The equations

``N`` is RMSNorm with a weight: ``N(x) = x * rsqrt(mean(x^2) + eps) * w``
(eps ``rms_norm_eps``). Block l (``num_hidden_layers`` of them), on the
residual stream x of one sequence:

    q_h = (N1_l(x) W_q)_h, k_h = (N1_l(x) W_k)_h, v_h = (N1_l(x) W_v)_h
          ``num_attention_heads`` query heads over ``num_key_value_heads``
          key-value heads (16 and 16 as published: plain multi-head
          attention, group 1), ``head_dim`` wide, no biases
    (q_h, k_h) = RoPE(q_h, k_h; position, rope_theta, halves)
    s_hij = q_hi . k_hj / sqrt(head_dim) for j <= i, else -inf   (no window)
    a   = x + N2_l(concat_h(softmax_j(s_hi.) v_h) W_o)
    y   = a + N4_l((silu(N3_l(a) W_gate) * (N3_l(a) W_up)) W_down)

**four norms a block**, one before and one after each sublayer (the
published modelling code's ``input_layernorm``, ``input_layernorm_2``,
``post_attention_layernorm``, ``post_attention_layernorm_2``; the paper,
"Scaling Latent Reasoning via Looped Language Models", arXiv 2510.25741,
calls it sandwich normalisation). The model:

    h_0 = embed[ids]
    for pass u = 1 .. total_ut_steps:
        h_u = Nf(block_L(... block_1(h_{u-1})))

the **same blocks' weights every pass**, the **final norm inside the loop**
(its output is the next pass's input), positions and rotation the same in
every pass. With no cache there is nothing to index; what the published
code's cache index ``current_ut * num_hidden_layers + layer_idx`` says is
that pass u of layer l attends over the keys and values that pass u of
layer l computed, which is what a full forward does. The exit gate, one
``Linear(hidden, 1)`` with bias on the normed state of each pass:

    lambda_u = sigmoid(w_g . h_u + b_g)
    p_u = lambda_u prod_{v<u} (1 - lambda_v)   for u < total_ut_steps,
    p_last = prod_{v<last} (1 - lambda_v)       (the rest: sums to 1)

A token leaves at the first pass whose cumulated p reaches
``early_exit_threshold``; at the published 1.0 every token runs every pass
and ``logits = h_last W_head``.

RoPE by halves: frequency i of head_dim / 2 turns the pair of entries
(i, i + head_dim / 2) by ``position x theta^(-2i / head_dim)``.

## Conventions the catalog's keys do not settle (``assumed`` in the file)

The four norms and their order, the final norm inside the loop, the cache
entry a (pass, layer), rotation by halves, the gate's form. Each is stated
there with its source, read here from the equations above and by the
program from its own ``program`` object (``sandwich_norm``, ``ut_steps``),
so that a disagreement shows.

## Departures from the published description

None in what is computed. Early exit below threshold 1 is absent
(``forward`` runs every pass for every token, which is what the published
threshold says); ``exit_distribution`` gives the ``p_u`` all the same. A
file with ``total_ut_steps`` 1 (the CPU tests) has no gate and ``p`` is 1.
The weights arrive in the program's storage precision (bf16) and are cast
up a layer at a time; every activation and every product is float32.
"""

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HIGHEST)


def sizes(config: dict) -> dict:
    """What ``forward`` needs, from the configuration file as run."""
    m = config["model"]
    heads = m["num_attention_heads"]
    if m.get("use_sliding_window") or m.get("rope_scaling") \
            or set(m["layer_types"]) != {"full_attention"}:
        raise ValueError("this reference states full attention in every "
                         "layer and plain rotation")
    if float(m.get("early_exit_threshold", 1)) != 1.0:
        raise ValueError("this reference runs every pass for every token "
                         "(early_exit_threshold 1)")
    return {"num_layers": m["num_hidden_layers"],
            "ut_steps": int(m["total_ut_steps"]),
            "num_heads": heads,
            "num_kv_heads": m["num_key_value_heads"],
            "head_dim": m.get("head_dim") or m["hidden_size"] // heads,
            "rms_norm_eps": m["rms_norm_eps"],
            "rope_theta": float(m["rope_theta"]),
            "tie_embeddings": bool(m.get("tie_word_embeddings", False))}


def layer_weights(params: dict, i: int) -> dict:
    """Layer ``i`` of the program's parameter tree, by this module's names:
    the one place that knows the tree (``N1`` .. ``N4`` in the order of the
    equations)."""
    layer = _stack(params)[f"layers_{i}"]
    attn, mlp = layer["attn"], layer["mlp"]
    return {"n1": layer["input_norm"]["scale"],
            "n2": layer["attn_out_norm"]["scale"],
            "n3": layer["post_attn_norm"]["scale"],
            "n4": layer["mlp_out_norm"]["scale"],
            **{k: attn[f"{k}_proj"]["kernel"] for k in "qkvo"},
            **{k: mlp[f"{k}_proj"]["kernel"] for k in ("gate", "up", "down")}}


def _stack(params: dict) -> dict:
    """Where the tree keeps the blocks, the final norm and the gate: under
    ``loop`` (the body the program scans over the passes); a model of one
    pass keeps them beside the embedding."""
    return params["model"].get("loop", params["model"])


def _norm(x, w, eps: float):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, positions, theta: float):
    """x: (seq, heads, head_dim); rotate halves by position."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) * 2.0
                                / x.shape[-1]))
    angles = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _block(x, w: dict, m: dict, positions):
    """One block on one sequence. x: (seq, hidden)."""
    n_q, n_kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    seq, eps = x.shape[0], m["rms_norm_eps"]
    h = _norm(x, w["n1"], eps)
    q = _rope(_mm(h, w["q"]).reshape(seq, n_q, hd), positions,
              m["rope_theta"])
    k = _rope(_mm(h, w["k"]).reshape(seq, n_kv, hd), positions,
              m["rope_theta"])
    v = _mm(h, w["v"]).reshape(seq, n_kv, hd)
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=HIGHEST) / jnp.sqrt(F32(hd))
    idx = jnp.arange(seq)
    scores = jnp.where((idx[:, None] >= idx[None, :])[None], scores,
                       -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v,
                      precision=HIGHEST).reshape(seq, n_q * hd)
    a = x + _norm(_mm(attn, w["o"]), w["n2"], eps)
    h = _norm(a, w["n3"], eps)
    mlp = _mm(jax.nn.silu(_mm(h, w["gate"])) * _mm(h, w["up"]), w["down"])
    return a + _norm(mlp, w["n4"], eps)


def pass_states(params: dict, m: dict, ids) -> list:
    """``[h_1, ..., h_last]``, the normed state (seq, hidden) after each
    pass of one sequence ``ids`` (seq,)."""
    with jax.default_matmul_precision("highest"):
        positions = jnp.arange(ids.shape[0])
        x = params["model"]["embed_tokens"][ids].astype(F32)
        block = jax.jit(_block, static_argnums=(2,))
        states = []
        for _ in range(m["ut_steps"]):
            for i in range(m["num_layers"]):
                x = block(x, layer_weights(params, i), _Frozen(m), positions)
            x = _norm(x, _stack(params)["final_norm"]["scale"],
                      m["rms_norm_eps"])
            states.append(x)
        return states


def forward(params: dict, m: dict, ids):
    """Float32 logits (seq, vocab) of one sequence ``ids`` (seq,): the head
    on the last pass's state."""
    x = pass_states(params, m, ids)[-1]
    if m.get("tie_embeddings"):
        return _mm(x, params["model"]["embed_tokens"].T)
    return _mm(x, params["lm_head"])


def exit_distribution(params: dict, m: dict, ids):
    """The exit gate's ``p_u``, (seq, passes), each row summing to 1."""
    states = pass_states(params, m, ids)
    if len(states) == 1:
        return jnp.ones((ids.shape[0], 1), F32)
    w = _stack(params)["exit_gate_kernel"].astype(F32)[:, 0]
    b = _stack(params)["exit_gate_bias"].astype(F32)[0]
    stay, ps = jnp.ones((ids.shape[0],), F32), []
    for h in states[:-1]:
        rate = jax.nn.sigmoid(jnp.sum(h * w, axis=-1) + b)
        ps.append(rate * stay)
        stay = stay * (1.0 - rate)
    return jnp.stack([*ps, stay], axis=-1)


class _Frozen(dict):
    """A hashable view of the sizes, so they can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
