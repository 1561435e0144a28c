"""Bytes a decode step of a patterned (nemotron_h) configuration must move,
from the configuration file's shapes and the engine's counters.

A floor: what any program has to read and write for one step at the
configuration's precisions, and nothing that a program may skip. Counted:
the weights of every mixer once a step (a Mamba-2 layer's projections,
convolution and per-head vectors; an attention layer's four projections; an
expert layer's router, its shared expert, and the held routed experts that
the step's tokens *touched*, by the engine's counter); the norms; the output
head; the recurrent state of the live slots, read and written; keys and
values of the live context, read. Not counted: the embedding rows (32 of
65,536), activations, logits, experts no token chose, the state of idle
slots.

Standard library only; sizes come from ``config["model"]`` (the published
keys as run), never from the program.
"""

from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_weight_bytes(model: dict) -> dict:
    """Bytes of the weights one layer of each kind reads a step, the routed
    experts apart (``expert``: one routed expert's two matrices)."""
    w = _ITEM[model.get("torch_dtype", "bfloat16")]
    h = model["hidden_size"]
    heads, hp = model["mamba_num_heads"], model["mamba_head_dim"]
    d_in = heads * hp
    conv_dim = d_in + 2 * model["n_groups"] * model["ssm_state_size"]
    mamba = (w * (h * (d_in + conv_dim + heads) + d_in * h
                  + conv_dim * (model["conv_kernel"] + 1))
             + 4 * (3 * heads + d_in))
    nq, nkv, hd = (model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"])
    attention = w * (h * nq * hd + 2 * h * nkv * hd + nq * hd * h)
    shared = w * 2 * h * model["moe_shared_expert_intermediate_size"] \
        * model["n_shared_experts"]
    return {"M": mamba + w * h, "*": attention + w * h,
            "E": shared + w * h,  # + router, by the published expert count
            "expert": w * 2 * h * model["moe_intermediate_size"]}


def decode_step_bytes(config: dict, kv_itemsize: int, live_slots: float,
                      context_tokens: float, experts_touched: float) -> dict:
    """Bytes one decode step must move, by part. ``live_slots``: mean slots
    decoding; ``context_tokens``: mean tokens of context a step attends over
    (all live slots together); ``experts_touched``: mean held experts with
    at least one token a step, summed over the expert layers."""
    model = config["model"]
    pattern = model["hybrid_override_pattern"][:model["num_hidden_layers"]]
    per = layer_weight_bytes(model)
    w = _ITEM[model.get("torch_dtype", "bfloat16")]
    h = model["hidden_size"]
    router_experts = config.get("published", {}).get(
        "n_routed_experts", model["n_routed_experts"])
    n = {k: pattern.count(k) for k in "M*E"}
    heads, hp, state = (model["mamba_num_heads"], model["mamba_head_dim"],
                        model["ssm_state_size"])
    conv_dim = heads * hp + 2 * model["n_groups"] * state
    # The configuration states float32 for the SSM state (assumed); the
    # convolution tail is kept as the projection made it (the weights' type).
    slot_state = heads * hp * state * 4 + (model["conv_kernel"] - 1) \
        * conv_dim * w
    parts = {
        "mixer_weights": n["M"] * per["M"] + n["*"] * per["*"]
        + n["E"] * (per["E"] + 4 * h * router_experts + 4 * router_experts),
        "experts_touched": experts_touched * per["expert"],
        "head": w * h * model["vocab_size"] + w * h,
        "recurrent_state": 2 * live_slots * n["M"] * slot_state,
        "keys_values": context_tokens * n["*"] * 2
        * model["num_key_value_heads"] * model["head_dim"] * kv_itemsize,
    }
    parts["total"] = sum(parts.values())
    return parts
