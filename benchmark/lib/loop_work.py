"""Parameters, bytes and operations of a dense decoder whose layers run
several times over one set of weights (``model_type`` ouro: ``total_ut_steps``
passes over ``num_hidden_layers`` blocks of four norms, plain multi-head
attention, SwiGLU, the final norm after every pass, an exit gate), from the
configuration file's shapes and the engine's counters.

Each count is the least any program has to do (a floor must not overstate,
so that a share of a peak computed from it cannot pass 100 %), none depends
on what implements a kernel, and **each has the passes in it**:

- ``parameters``: the model, by part, each layer once; what the program's
  ``ModelConfig.num_params`` must agree with.
- ``cache_entries``: passes x layers, the entries of a sequence's cache.
- ``cache_bytes_a_token``: keys and values of one token over every entry.
- ``decode_step_bytes``: what one decode step must move: the layers'
  weights once a pass (the chip's 128 MiB of fast memory cannot keep
  4.9 GB from one pass to the next), the final norm and the gate a pass,
  the head once, the live keys and values of every entry. Not counted:
  embedding rows, activations, logits.
- ``prefill_flops``: what prompt tokens need: 2 FLOP a layer parameter a
  token **a pass**, plus attention's two products over the (query, key)
  pairs the tokens could see, in every layer of every pass. Not counted:
  the head (one row a prompt), the gate, padding.

Standard library only; sizes come from ``config["model"]`` (the published
keys as run), never from the program.
"""

from __future__ import annotations

# Bytes of one value by the name of its type (weights, cache rows).
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def passes(model: dict) -> int:
    return int(model["total_ut_steps"])


def cache_entries(model: dict) -> int:
    return passes(model) * int(model["num_hidden_layers"])


def layer_parameters(model: dict) -> dict:
    """One block's parameters, by part: q, k, v and o, the gated MLP, the
    four norms."""
    h, d = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return {"attention": 2 * h * heads * d + 2 * h * kv * d,
            "mlp": 3 * h * model["intermediate_size"],
            "norms": 4 * h}


def parameters(config: dict) -> dict:
    """Parameters of the model as run, by part, and their ``total``."""
    model = config["model"]
    h, layers = model["hidden_size"], model["num_hidden_layers"]
    layer = layer_parameters(model)
    parts = {
        "attention": layers * layer["attention"],
        "mlp": layers * layer["mlp"],
        "layer_norms": layers * layer["norms"],
        "final_norm": h,
        "exit_gate": h + 1 if passes(model) > 1 else 0,
        "embedding_and_head": model["vocab_size"] * h * (
            1 if model.get("tie_word_embeddings") else 2),
    }
    parts["total"] = sum(parts.values())
    return parts


def cache_bytes_a_token(model: dict, cache_itemsize: int) -> int:
    """Keys and values of one token of context over every entry."""
    return cache_entries(model) * 2 * model["num_key_value_heads"] \
        * model["head_dim"] * cache_itemsize


def decode_step_bytes(config: dict, cache_itemsize: int,
                      context_tokens: float) -> dict:
    """Bytes one decode step must move, by part. ``context_tokens``: the
    live slots' contexts summed (the engine's ``decode_context_tokens``
    over ``decode_steps``)."""
    model = config["model"]
    w = ITEMSIZE[model.get("torch_dtype", "bfloat16")]
    h, layers, u = model["hidden_size"], model["num_hidden_layers"], \
        passes(model)
    layer = layer_parameters(model)
    parts = {
        "layer_weights": u * layers * (
            w * (layer["attention"] + layer["mlp"]) + 4 * layer["norms"]),
        "final_norm_and_gate": u * 4 * (2 * h + 1),
        "head": w * h * model["vocab_size"],
        "keys_and_values": cache_bytes_a_token(model, cache_itemsize)
        * context_tokens,
    }
    parts["total"] = sum(parts.values())
    return parts


def prefill_flops(config: dict, tokens: float,
                  attention_pairs: float) -> dict:
    """FLOP that ``tokens`` prompt tokens need in prefill, by part.
    ``attention_pairs``: (query, key) pairs those tokens could see in one
    layer of one pass, their own among them."""
    model = config["model"]
    layers, u = model["num_hidden_layers"], passes(model)
    layer = layer_parameters(model)
    per_pair = 2 * 2 * model["num_attention_heads"] * model["head_dim"]
    parts = {
        "layer_weights": tokens * u * layers * 2 * (
            layer["attention"] + layer["mlp"]),
        "attention_products": per_pair * u * layers * attention_pairs,
    }
    parts["total"] = sum(parts.values())
    return parts
