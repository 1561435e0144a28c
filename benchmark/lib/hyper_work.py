"""Parameters, bytes and operations of a latent-attention configuration with
a query latent and hyper-connected residual streams (``model_type`` xing4_0),
from the configuration file's shapes and the engine's counters.

Three counts, each the least any program has to do (a floor must not
overstate, so that a share of a peak computed from it cannot pass 100 %):

- ``parameters``: the model as run, by part; what ``sizes`` of the reference
  and the program's ``ModelConfig.num_params`` must agree with.
- ``decode_step_bytes``: what one decode step must read at the
  configuration's precisions: attention weights with the query latent
  (``q_a``, ``q_b`` in place of one query projection), the stream maps'
  float32 weights, norms, the leading dense MLPs, shared experts and routers,
  the routed experts that the step's tokens *touched* (the engine's counter),
  the head, the live latent rows once. Not counted: embedding rows,
  activations, logits, experts no token chose.
- ``prefill_flops``: what the prompt tokens of prefill calls need: 2 FLOP a
  parameter a token *uses* (``num_experts_per_tok`` routed experts, not all
  that are held; the up-projection ``kv_b`` once a token, whatever a later
  call expands again; the maps' projections) plus attention's products over
  the (query, key) pairs the tokens could see (scores over nope + rope,
  values over v, every head). Not counted: the head (one row a prompt, 0.03 %
  of its prefill), the Sinkhorn rounds and the mixes (element-wise), padding,
  masked experts, recomputed expansions.

Standard library only; sizes come from ``config["model"]`` (the published
keys as run), never from the program.
"""

from __future__ import annotations

# Bytes of one value by the name of its type (weights, cache rows).
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def attention_matrices(model: dict) -> dict:
    """Parameters of one layer's attention matrices, by name."""
    h, heads = model["hidden_size"], model["num_attention_heads"]
    r, q = model["kv_lora_rank"], model["q_lora_rank"]
    nope, rope, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
    return {"q_a": h * q, "q_b": q * heads * (nope + rope),
            "kv_a": h * (r + rope), "kv_b": r * heads * (nope + vd),
            "o": heads * vd * h}


def map_parameters(model: dict) -> int:
    """float32 parameters of one sublayer's stream maps: phi_pre, phi_post
    (nC x n each), phi_res (nC x n^2), their biases, three scalars."""
    n = model["hc_mult"]
    return (n * model["hidden_size"] + 1) * (n * n + 2 * n) + 3


def layer_counts(model: dict) -> tuple:
    layers = model["num_hidden_layers"]
    dense = min(model["first_k_dense_replace"], layers)
    return layers, dense, layers - dense


def parameters(config: dict) -> dict:
    """Parameters of the model as run, by part, and their ``total``."""
    model = config["model"]
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    layers, dense, expert_layers = layer_counts(model)
    experts = model["n_routed_experts"]
    parts = {
        "attention": layers * (
            sum(attention_matrices(model).values())
            + model["q_lora_rank"] + model["kv_lora_rank"]),    # two norms
        "stream_maps": layers * 2 * map_parameters(model),
        "layer_norms": layers * 2 * h + h,
        "dense_mlp": dense * 3 * h * model["intermediate_size"],
        "shared_experts": expert_layers * 3 * h * f
        * model["n_shared_experts"],
        "routers": expert_layers * (h * experts + experts),
        "routed_experts": expert_layers * experts * 3 * h * f,
        "embedding_and_head": 2 * model["vocab_size"] * h,
    }
    parts["total"] = sum(parts.values())
    return parts


def decode_step_bytes(config: dict, cache_itemsize: int,
                      context_tokens: float, experts_touched: float) -> dict:
    """Bytes one decode step must move, by part. ``context_tokens``: mean
    rows of context a step attends over (all live slots together);
    ``experts_touched``: mean routed experts with at least one token a
    step, summed over the expert layers."""
    model = config["model"]
    w = ITEMSIZE[model.get("torch_dtype", "bfloat16")]
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    layers, dense, expert_layers = layer_counts(model)
    experts = model["n_routed_experts"]
    row = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    parts = {
        "attention_weights": layers * (
            w * sum(attention_matrices(model).values())
            + 4 * (model["q_lora_rank"] + model["kv_lora_rank"] + 2 * h)),
        "stream_maps": layers * 2 * 4 * map_parameters(model),
        "dense_mlp": dense * w * 3 * h * model["intermediate_size"],
        "shared_experts_and_routers": expert_layers * (
            w * 3 * h * f * model["n_shared_experts"]
            + 4 * h * experts + 4 * experts),
        "experts_touched": experts_touched * w * 3 * h * f,
        "head": w * h * model["vocab_size"] + 4 * h,
        "latents": context_tokens * layers * row * cache_itemsize,
    }
    parts["total"] = sum(parts.values())
    return parts


def prefill_flops(model: dict, tokens: float, attention_pairs: float) -> dict:
    """FLOP that ``tokens`` prompt tokens need in prefill, by part.
    ``attention_pairs``: (query, key) pairs those tokens could see, their
    own among them (the engine's ``prefill_attention_pairs``)."""
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    layers, dense, expert_layers = layer_counts(model)
    heads = model["num_attention_heads"]
    n = model["hc_mult"]
    parts = {
        "attention_weights": tokens * layers * 2
        * sum(attention_matrices(model).values()),
        "stream_maps": tokens * layers * 2 * 2 * n * h * (n * n + 2 * n),
        "dense_mlp": tokens * dense * 2 * 3 * h * model["intermediate_size"],
        "experts": tokens * expert_layers * 2 * (
            3 * h * f * (model["num_experts_per_tok"]
                         + model["n_shared_experts"])
            + h * model["n_routed_experts"]),
        "attention_products": attention_pairs * layers * 2 * heads * (
            model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
            + model["v_head_dim"]),
    }
    parts["total"] = sum(parts.values())
    return parts
