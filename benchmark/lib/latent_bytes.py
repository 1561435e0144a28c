"""Operations and bytes of a latent-attention (deepseek_v3) configuration's
decode step, from the configuration file's shapes and the engine's counters.

Two counts. ``kernel_work``: what the absorbed decode kernel
(``dlti_latent_attention_decode``) has to do for the live context: every
live latent row read ONCE a layer (``kv_lora_rank + qk_rope_head_dim``
values at the cache's item size; the 64 lanes of padding a row lies in and
anything a kernel reads twice are not the work), and 2 x heads x (row +
latent) FLOP a row (scores against the whole row, values from its latent
part). ``decode_step_bytes``: a floor of what any program has to read for
one decode step at the configuration's precisions: attention and
shared-expert weights, routers, norms, the leading dense MLP, the held
routed experts that the step's tokens *touched* (by the engine's counter),
the head, the live latents once. Not counted: the embedding rows, activations,
logits, experts no token chose.

Standard library only; sizes come from ``config["model"]`` (the published
keys as run), never from the program.
"""

from __future__ import annotations

# Bytes of one value by the name of its type (weights, cache rows).
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def row_values(model: dict) -> int:
    """Values of one token's cache row in one layer."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def kernel_work(model: dict, cache_itemsize: int,
                context_tokens: float) -> dict:
    """Bytes and FLOP of one decode step's kernel calls (all layers) over
    ``context_tokens`` live rows (all live slots together)."""
    layers, heads = model["num_hidden_layers"], model["num_attention_heads"]
    row = row_values(model)
    return {
        "bytes": context_tokens * layers * row * cache_itemsize,
        "flops": context_tokens * layers * 2 * heads
        * (row + model["kv_lora_rank"]),
    }


def decode_step_bytes(config: dict, cache_itemsize: int,
                      context_tokens: float, experts_touched: float) -> dict:
    """Bytes one decode step must move, by part. ``context_tokens``: mean
    rows of context a step attends over (all live slots together);
    ``experts_touched``: mean held experts with at least one token a step,
    summed over the expert layers."""
    model = config["model"]
    w = ITEMSIZE[model.get("torch_dtype", "bfloat16")]
    h, heads = model["hidden_size"], model["num_attention_heads"]
    r, nope, rope, vd = (model["kv_lora_rank"], model["qk_nope_head_dim"],
                         model["qk_rope_head_dim"], model["v_head_dim"])
    layers = model["num_hidden_layers"]
    dense = min(model["first_k_dense_replace"], layers)
    f = model["moe_intermediate_size"]
    router_experts = config.get("published", {}).get(
        "n_routed_experts", model["n_routed_experts"])
    attention = w * (h * heads * (nope + rope) + h * (r + rope)
                     + r * heads * (nope + vd) + heads * vd * h) + 4 * r
    parts = {
        "attention_weights": layers * (attention + 2 * 4 * h),
        "dense_mlp": dense * w * 3 * h * model["intermediate_size"],
        "shared_experts_and_routers": (layers - dense) * (
            w * 3 * h * f * model["n_shared_experts"]
            + 4 * h * router_experts + 4 * router_experts),
        "experts_touched": experts_touched * w * 3 * h * f,
        "head": w * h * model["vocab_size"] + 4 * h,
        "latents": kernel_work(model, cache_itemsize,
                               context_tokens)["bytes"],
    }
    parts["total"] = sum(parts.values())
    return parts
