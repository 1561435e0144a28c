"""Plain reference of AI21-Jamba2-3B (``model_type`` jamba).

The architecture is "Jamba: A Hybrid Transformer-Mamba Language Model"
(arXiv:2403.19887) as the family's published modelling code
(``modeling_jamba.py``) has it: Mamba-1 layers (arXiv:2312.00752) with an
attention layer among every ``attn_layer_period``, a gate/up/down MLP in
every layer (``num_experts`` 1: no layer is an expert layer), RMSNorm, a
tied head, **no positional encoding of any kind**.

Straightforward ``jax.numpy`` in float32, every product at precision
``highest``, no cache, no kernel, no chunking of the mathematics: the
recurrence one token after another, attention as full scores under an
explicit mask, and **each document of a packed row run by itself** from a
zero state (``grad`` cuts the row at its document boundaries and runs every
piece as a sequence of its own; nothing of one piece is visible to another).
It imports nothing of ``dlti_tpu``; ``sizes`` reads the configuration file
alone (``config["model"]`` as run, with ``assumed`` beside it), never the
program's ``ModelConfig``.

## The layer equations (d = hidden_size, layers l = 0 .. n - 1)

``x = embed[ids]`` (rows unscaled); for each layer

    x = x + Mixer_l(RMSNorm_in(x))
    x = x + down(silu(gate g) * up g),   g = RMSNorm_ff(x)     (no bias)

a final RMSNorm; ``logits = x embed^T`` (tied). Layer l is an **attention**
layer where ``l % attn_layer_period == attn_layer_offset`` and a **Mamba**
layer otherwise (the family's rule; the order is derived here from those two
keys alone).

- **Attention**: ``q = Wq h`` (``num_attention_heads`` of dh = d / heads),
  ``k = Wk h``, ``v = Wv h`` (``num_key_value_heads`` of dh), no bias, no
  rotation; ``softmax(q k^T / sqrt dh)`` over the keys at or before the
  query; ``out = Wo [o_0 ; ...]``.
- **Mamba** (d_inner = ``mamba_expand`` d, state N = ``mamba_d_state``, conv
  K = ``mamba_d_conv``, R = ``mamba_dt_rank``; conv bias on, projection
  biases off):

      [u ; z] = in_proj h
      u' = silu(causal depthwise conv1d(u, width K) + bias)
      [dlt ; B ; C] = x_proj u'                           (R, N, N)
      dlt, B, C = RMSNorm_R(dlt), RMSNorm_N(B), RMSNorm_N(C)   (learned weights)
      Dt = softplus(dt_proj dlt + dt_bias)                (d_inner)
      A = -exp(A_log)                                     (d_inner, N)
      s_t = exp(Dt_t A) s_{t-1} + (Dt_t u'_t) (outer) B_t
      y_t = s_t C_t + D u'_t
      out = out_proj(y * silu(z))

  with ``s`` zero and the convolution reading zeros before a sequence's
  first token.

A projection that carries a LoRA adapter computes
``W x + scaling * B (A x)`` (factors ``lora_a`` (in, r), ``lora_b`` (r, out)).

## Departures, each on purpose

- The weights arrive in the program's storage precision (bf16) and are cast
  up a layer at a time; every activation and every product is float32.
- ``dt_proj``'s bias is the tree's ``dt_bias`` leaf (float32), not a
  ``bias`` under ``dt_proj``.
- A document is run padded up to a whole number of ``PAD_TO`` tokens so that
  the documents of a row share a few compiled shapes: what follows a
  position is invisible to it in every layer here, so the padding changes
  no value that is read.
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
PAD_TO = 256


def sizes(config):
    """Everything ``forward`` and ``grad`` need, from the configuration
    file alone."""
    m = config["model"]
    layers = int(m["num_hidden_layers"])
    period, offset = int(m["attn_layer_period"]), int(m["attn_layer_offset"])
    hidden, heads = int(m["hidden_size"]), int(m["num_attention_heads"])
    return {
        "kinds": ["attention" if l % period == offset else "mamba"
                  for l in range(layers)],
        "eps": float(m["rms_norm_eps"]), "hidden": hidden,
        "vocab": int(m["vocab_size"]), "heads": heads,
        "kv_heads": int(m["num_key_value_heads"]),
        "head_dim": int(m.get("head_dim") or hidden // heads),
        "m_inner": int(m["mamba_expand"]) * hidden,
        "m_state": int(m["mamba_d_state"]),
        "m_conv": int(m["mamba_d_conv"]),
        "m_dt_rank": int(m["mamba_dt_rank"]),
    }


def _mm(a, b):
    return jnp.matmul(a, b.astype(F32), precision=HIGHEST)


def _linear(p, x, scaling):
    y = _mm(x, p["kernel"])
    if "bias" in p:
        y = y + p["bias"].astype(F32)
    if "lora_a" in p:
        y = y + scaling * _mm(_mm(x, p["lora_a"]), p["lora_b"])
    return y


def _rms_norm(p, x, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)


def mamba1(p, sz, x, scaling):
    """x (seq, hidden) -> (seq, hidden), one token after another."""
    D, N, K, R = sz["m_inner"], sz["m_state"], sz["m_conv"], sz["m_dt_rank"]
    seq = x.shape[0]
    u, z = jnp.split(_linear(p["in_proj"], x, scaling), 2, axis=-1)
    w, bias = p["conv_kernel"].astype(F32), p["conv_bias"].astype(F32)
    padded = jnp.concatenate([jnp.zeros((K - 1, D), F32), u])
    u = jax.nn.silu(sum(w[k] * padded[k:k + seq] for k in range(K)) + bias)
    dlt, b_in, c_in = jnp.split(_linear(p["x_proj"], u, scaling),
                                [R, R + N], axis=-1)
    dlt = _rms_norm(p["dt_layernorm"], dlt, sz["eps"])
    b_in = _rms_norm(p["b_layernorm"], b_in, sz["eps"])
    c_in = _rms_norm(p["c_layernorm"], c_in, sz["eps"])
    dt = jax.nn.softplus(_linear(p["dt_proj"], dlt, scaling)
                         + p["dt_bias"].astype(F32))          # (seq, D)
    a = -jnp.exp(p["A_log"].astype(F32))                      # (D, N)

    def step(s, t):
        u_t, dt_t, b_t, c_t = t
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=-1)

    # One token after another. Where the sequence is whole blocks of PAD_TO
    # tokens the loop is written as a loop over blocks of a loop over
    # tokens, the inner one under jax.checkpoint: the backward pass then
    # keeps a state a block and not a token, and no value changes.
    inputs = (u, dt, b_in, c_in)
    s0 = jnp.zeros((D, N), F32)
    if seq % PAD_TO == 0 and seq > PAD_TO:
        block = jax.checkpoint(lambda s, x: jax.lax.scan(step, s, x))
        _, y = jax.lax.scan(block, s0, tuple(
            t.reshape((seq // PAD_TO, PAD_TO) + t.shape[1:])
            for t in inputs))
        y = y.reshape(seq, D)
    else:
        _, y = jax.lax.scan(step, s0, inputs)
    y = y + p["D"].astype(F32) * u
    return _linear(p["out_proj"], y * jax.nn.silu(z), scaling)


def attention(p, sz, x, scaling):
    """x (seq, hidden) -> (seq, hidden): causal, every key at or before
    the query, no rotation."""
    seq, nh, nkv, dh = x.shape[0], sz["heads"], sz["kv_heads"], \
        sz["head_dim"]
    q = _linear(p["q_proj"], x, scaling).reshape(seq, nkv, nh // nkv, dh)
    k = _linear(p["k_proj"], x, scaling).reshape(seq, nkv, dh)
    v = _linear(p["v_proj"], x, scaling).reshape(seq, nkv, dh)
    at = jnp.arange(seq)

    def rows(q_rows, at_rows):
        """Full scores of some query rows against every key."""
        scores = jnp.einsum("qjrd,kjd->jrqk", q_rows, k,
                            precision=HIGHEST) * dh ** -0.5
        scores = jnp.where(at[None, :] <= at_rows[:, None], scores, -jnp.inf)
        return jnp.einsum("jrqk,kjd->qjrd", jax.nn.softmax(scores, axis=-1),
                          v, precision=HIGHEST)

    # (a long sequence PAD_TO query rows at a time, so that the scores of
    # 8,192 x 8,192 x heads never stand in memory at once; no value changes)
    if seq % PAD_TO == 0 and seq > PAD_TO:
        o = jax.lax.map(
            jax.checkpoint(lambda x: rows(*x)),
            (q.reshape(seq // PAD_TO, PAD_TO, nkv, nh // nkv, dh),
             at.reshape(seq // PAD_TO, PAD_TO)))
    else:
        o = rows(q, at)
    return _linear(p["o_proj"], o.reshape(seq, nh * dh), scaling)


def _layer(layer, kind, sz, x, scaling):
    h = _rms_norm(layer["input_norm"], x, sz["eps"])
    mixer = mamba1 if kind == "mamba" else attention
    x = x + mixer(layer["mixer"], sz, h, scaling)
    g = _rms_norm(layer["post_mixer_norm"], x, sz["eps"])
    mlp = layer["mlp"]
    gated = jax.nn.silu(_linear(mlp["gate_proj"], g, scaling)) \
        * _linear(mlp["up_proj"], g, scaling)
    return x + _linear(mlp["down_proj"], gated, scaling)


def forward(params, sizes, ids, lora_scaling=0.0):
    """float32 logits (seq, vocab) of ONE sequence ``ids`` from a zero
    state, a layer at a time."""
    sz = sizes
    x = params["embed_tokens"][ids].astype(F32)
    # (the checkpoint saves memory in the backward pass and changes no value)
    layer_fn = jax.checkpoint(_layer, static_argnums=(1, 2, 4))
    for l, kind in enumerate(sz["kinds"]):
        x = layer_fn(params[f"layers_{l}"], kind, _Frozen(sz), x,
                     lora_scaling)
    x = _rms_norm(params["final_norm"], x, sz["eps"])
    return jnp.matmul(x, params["embed_tokens"].astype(F32).T,
                      precision=HIGHEST)


class _Frozen(dict):
    """A hashable view of the sizes, so they can be a static argument."""

    def __hash__(self):
        return hash(tuple((k, tuple(v) if isinstance(v, list) else v)
                          for k, v in sorted(self.items())))


def _split(params, chosen):
    picked = jax.tree_util.tree_map_with_path(
        lambda p, v: v if chosen(p) else None, params)
    rest = jax.tree_util.tree_map_with_path(
        lambda p, v: None if chosen(p) else v, params)
    return picked, rest


def _merge(picked, rest):
    return jax.tree_util.tree_map(
        lambda a, b: b if a is None else a, picked, rest,
        is_leaf=lambda v: v is None)


def documents(segment_ids):
    """``[(start, stop)]`` of the documents of one row (``segment_ids``
    (seq,), 1-based, 0 at padding), in their order."""
    seg = [int(s) for s in segment_ids]
    out, start = [], 0
    for i in range(1, len(seg) + 1):
        if i == len(seg) or seg[i] != seg[start]:
            if seg[start] != 0:
                out.append((start, i))
            start = i
    return out


def grad(params, sizes, batch, lora_scaling, chosen):
    """(mean loss, gradient tree of the leaves ``chosen`` accepts, log-
    probabilities of the next tokens (rows, seq - 1)) over the rows of
    ``batch`` (``input_ids``, ``loss_mask``, ``positions``,
    ``segment_ids``; each (rows, seq)). Every document of every row is run
    by itself; each of its positions is scored on the row's next token
    under the row's ``loss_mask`` (a document's last position on the first
    token of the next document, as the row's shifted labels have it)."""
    picked, rest = _split(params, chosen)

    # ``rest`` is an argument, not a closure: closed-over weights would be
    # baked into the compiled program as constants.
    def doc_loss(leaves, rest, ids, targets, weights):
        logits = forward(_merge(leaves, rest), sizes, ids, lora_scaling)
        logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                   targets[:, None], axis=-1)[:, 0]
        return -(logp * weights).sum(), (weights.sum(), logp)

    grad_fn = jax.jit(jax.value_and_grad(doc_loss, has_aux=True))
    rows, seq = batch["input_ids"].shape
    total, count, grads = 0.0, 0.0, None
    logps = jnp.zeros((rows, seq - 1), F32)
    for r in range(rows):
        # the row's labels: position i is scored on token i + 1
        labels = jnp.pad(batch["input_ids"][r, 1:], (0, 1))
        weights = jnp.pad(batch["loss_mask"][r, 1:].astype(F32), (0, 1))
        for start, stop in documents(batch["segment_ids"][r]):
            n = stop - start
            pad = (0, -n % PAD_TO)
            (s, (c, logp)), g = grad_fn(
                picked, rest, jnp.pad(batch["input_ids"][r, start:stop], pad),
                jnp.pad(labels[start:stop], pad),
                jnp.pad(weights[start:stop], pad))
            total, count = total + float(s), count + float(c)
            stop = min(stop, seq - 1)
            logps = logps.at[r, start:stop].set(logp[:stop - start])
            grads = g if grads is None else jax.tree_util.tree_map(
                lambda a, b: a + b, grads, g)
    grads = jax.tree_util.tree_map(lambda g: g / count, grads)
    return total / count, grads, logps
