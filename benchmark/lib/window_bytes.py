"""Parameters, bytes and operations of a GQA decoder whose layers mix a
sliding window with full attention and whose MLPs are held routed experts
after leading dense layers (``model_type`` exaone_moe), from the
configuration file's shapes and the engine's counters.

Each count is the least any program has to do (a floor must not overstate,
so that a share of a peak computed from it cannot pass 100 %), and none
depends on what implements a kernel:

- ``parameters``: the model as run, by part; what the reference's ``sizes``
  and the program's ``ModelConfig.num_params`` must agree with.
- ``cache_bytes_a_token``: keys and values of one token in one layer.
- ``live_cache_bytes``: what a decode step's attention must read: the full
  layers' live keys and values and, of the window layers, those inside the
  window alone (the engine's ``decode_context_tokens`` and
  ``decode_window_context_tokens``, each a sum over the live slots).
- ``decode_step_bytes``: that, and the weights a step must read: attention
  and norms of every layer, the leading dense MLPs, shared experts and
  routers, the held routed experts that the step's tokens *touched* (the
  engine's counter), the head. Not counted: embedding rows, activations,
  logits, experts no token chose.
- ``prefill_flops``: what the prompt tokens of prefill calls need: 2 FLOP a
  parameter a token *uses* (of the routed experts the held share of the
  top-k: ``num_experts_per_tok x held / published`` in the mean) plus
  attention's products over the (query, key) pairs the tokens could see
  **under each layer's own window** (the engine's
  ``prefill_attention_pairs`` for the full layers,
  ``prefill_window_attention_pairs`` for the window layers). Not counted:
  the head (one row a prompt), padding, masked experts, keys a walk over
  the cache scores and the mask hides.

Standard library only; sizes come from ``config["model"]`` (the published
keys as run) and the stated share, never from the program.
"""

from __future__ import annotations

# Bytes of one value by the name of its type (weights, cache rows).
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def layer_kinds(model: dict) -> tuple:
    """``(full layers, window layers, window)`` of the model as run."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    window = kinds.count("sliding_attention")
    return len(kinds) - window, window, int(model["sliding_window"])


def layer_counts(model: dict) -> tuple:
    layers = model["num_hidden_layers"]
    dense = min(model["first_k_dense_replace"], layers)
    return layers, dense, layers - dense


def attention_parameters(model: dict) -> int:
    """One layer's attention matrices and the two norms of a head."""
    h, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return 2 * h * heads * d + 2 * h * kv * d + 2 * d


def published_experts(config: dict) -> int:
    return int(config.get("published", {}).get(
        "num_experts", config["model"]["num_experts"]))


def parameters(config: dict) -> dict:
    """Parameters of the model as run, by part, and their ``total``."""
    model = config["model"]
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    layers, dense, expert_layers = layer_counts(model)
    routed = published_experts(config)
    parts = {
        "attention": layers * attention_parameters(model),
        "layer_norms": layers * 2 * h + h,
        "dense_mlp": dense * 3 * h * model["intermediate_size"],
        "shared_experts": expert_layers * 3 * h * f
        * model["num_shared_experts"],
        "routers": expert_layers * (h * routed + routed),
        "routed_experts": expert_layers * model["num_experts"] * 3 * h * f,
        "embedding_and_head": 2 * model["vocab_size"] * h,
    }
    parts["total"] = sum(parts.values())
    return parts


def cache_bytes_a_token(model: dict, cache_itemsize: int) -> int:
    """Keys and values of one token in one layer."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] \
        * cache_itemsize


def live_cache_bytes(model: dict, cache_itemsize: int,
                     context_tokens: float,
                     window_context_tokens: float) -> float:
    """Bytes of the keys and values a decode step attends over, each layer
    under its own window. ``context_tokens``: the live slots' contexts
    summed; ``window_context_tokens``: the same, each cut to the window."""
    full, window, _ = layer_kinds(model)
    return cache_bytes_a_token(model, cache_itemsize) * (
        full * context_tokens + window * window_context_tokens)


def decode_step_bytes(config: dict, cache_itemsize: int,
                      context_tokens: float, window_context_tokens: float,
                      experts_touched: float) -> dict:
    """Bytes one decode step must move, by part. ``experts_touched``: mean
    held routed experts with at least one token a step, summed over the
    expert layers."""
    model = config["model"]
    w = ITEMSIZE[model.get("torch_dtype", "bfloat16")]
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    layers, dense, expert_layers = layer_counts(model)
    routed = published_experts(config)
    parts = {
        "attention_weights": layers * (
            w * (attention_parameters(model) - 2 * model["head_dim"])
            + 4 * (2 * model["head_dim"] + 2 * h)),
        "dense_mlp": dense * w * 3 * h * model["intermediate_size"],
        "shared_experts_and_routers": expert_layers * (
            w * 3 * h * f * model["num_shared_experts"]
            + 4 * h * routed + 4 * routed),
        "experts_touched": experts_touched * w * 3 * h * f,
        "head": w * h * model["vocab_size"] + 4 * h,
        "keys_and_values": live_cache_bytes(
            model, cache_itemsize, context_tokens, window_context_tokens),
    }
    parts["total"] = sum(parts.values())
    return parts


def prefill_flops(config: dict, tokens: float, attention_pairs: float,
                  window_attention_pairs: float) -> dict:
    """FLOP that ``tokens`` prompt tokens need in prefill, by part.
    ``attention_pairs``: (query, key) pairs those tokens could see with no
    window, their own among them; ``window_attention_pairs``: under the
    window."""
    model = config["model"]
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    layers, dense, expert_layers = layer_counts(model)
    full, window, _ = layer_kinds(model)
    routed = published_experts(config)
    held_of_top_k = model["num_experts_per_tok"] * model["num_experts"] \
        / routed
    per_pair = 2 * 2 * model["num_attention_heads"] * model["head_dim"]
    parts = {
        "attention_weights": tokens * layers * 2 * (
            attention_parameters(model) - 2 * model["head_dim"]),
        "dense_mlp": tokens * dense * 2 * 3 * h * model["intermediate_size"],
        "experts": tokens * expert_layers * 2 * (
            3 * h * f * (held_of_top_k + model["num_shared_experts"])
            + h * routed),
        "attention_products": per_pair * (
            full * attention_pairs + window * window_attention_pairs),
    }
    parts["total"] = sum(parts.values())
    return parts
