"""Parameters, bytes and operations of the decoder-hybrid-decoder family
(``model_type`` phi4flash, SambaY: Mamba-1 layers, differential attention
under a window, one full-attention layer whose keys and values every later
attention layer reads, gated memory units), from the configuration file's
shapes and the engine's counters.

Each count is the least any program has to do (a floor must not overstate,
so that a share of a peak computed from it cannot pass 100 %), and none
depends on what implements a kernel:

- ``kinds``: the mixer of each layer, from ``mb_per_layer`` and the split at
  ``num_hidden_layers // 2`` (the reference derives the same).
- ``parameters``: the model, by part; what the program's
  ``ModelConfig.num_params`` must agree with.
- ``kv_bytes_a_token``: keys and values of one token in ONE pool (the
  paired heads: ``num_key_value_heads x head size x 2`` values).
- ``state_bytes_a_slot``: one sequence's recurrent state over the Mamba-1
  layers (float32 state, the convolution tail in the weights' type).
- ``kv_read_bytes``: what a decode step's attention reads: the shared pool's
  live rows once a READER (the full layer and every cross layer), each
  window pool's rows inside the window.
- ``decode_step_bytes``: every weight once (the tied head is the embedding:
  once), the live slots' state in and out, ``kv_read_bytes``. Not counted:
  embedding rows, activations, logits, the rows written.
- ``ssm_step_bytes``: the Mamba-1 layers' part of that: their weights and
  the live slots' state in and out.
- ``prefill_flops``: what a prefill call MUST do: layers up to the memory
  layer, and the shared layer's key and value projection, over every prompt
  token; the shared layer's query, output projection and MLP and every later
  layer over ONE token a row (the program heads one position a row);
  attention by its band; the scan's updates. Today's program runs every
  layer over every token: it does more than this counts and reads low.

- ``decode_means``: what the readers divide by, from a run's two scrapes.

The standard library and ``stats`` alone; sizes come from
``config["model"]`` (the published keys as run) and ``config["assumed"]``
(the sizes the catalog's config does not state), never from the program.
"""

from __future__ import annotations

# Bytes of one value by the name of its type (weights, cache rows).
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}
# Operations of one state element's update a token, the exponential apart:
# dt A, x s, + (dt u) B (a product and a sum), x C, the sum over the state.
SCAN_FLOPS_A_STATE = 6


def is_family(config: dict) -> bool:
    return config["model"].get("model_type") == "phi4flash"


def sizes(config: dict) -> dict:
    m, a = config["model"], config["assumed"]
    h, heads = m["hidden_size"], m["num_attention_heads"]
    return {"h": h, "m": m["intermediate_size"], "vocab": m["vocab_size"],
            "layers": m["num_hidden_layers"], "heads": heads,
            "kv_heads": m["num_key_value_heads"], "dh": h // heads,
            "window": m["sliding_window"],
            "d_inner": a["mamba_expand"]["value"] * h,
            "n": a["mamba_d_state"]["value"],
            "k": a["mamba_d_conv"]["value"],
            "r": a["mamba_dt_rank"]["value"]}


def kinds(config: dict) -> list:
    """``mamba | window | full | memory_unit | cross`` a layer."""
    m = config["model"]
    layers, period = m["num_hidden_layers"], m["mb_per_layer"]
    half = layers // 2
    return [("mamba" if l <= half else "memory_unit") if l % period == 0
            else ("window" if l < half else
                  "full" if l == half + 1 else "cross")
            for l in range(layers)]


def mixer_parameters(config: dict) -> dict:
    """One mixer's parameters by kind, by part (``matmul``: what a token is
    multiplied by; ``other``: biases, vectors, the convolution)."""
    s = sizes(config)
    h, d, n, r = s["h"], s["d_inner"], s["n"], s["r"]
    q, kv = s["heads"] * s["dh"], s["kv_heads"] * s["dh"]
    diff = 4 * s["dh"] + 2 * s["dh"]      # four lambda vectors, the head norm
    return {
        "mamba": {"matmul": h * 2 * d + d * (r + 2 * n) + r * d + d * h,
                  "other": d * (s["k"] + 1) + d + d * n + d},
        "window": {"matmul": h * (q + 2 * kv) + q * h,
                   "other": q + 2 * kv + h + diff},
        "memory_unit": {"matmul": 2 * h * d, "other": 0},
        "cross": {"matmul": 2 * h * q, "other": q + h + diff},
    }


def parameters(config: dict) -> dict:
    """Parameters of the model as run, by part, and their ``total``."""
    s = sizes(config)
    per = mixer_parameters(config)
    per["full"] = per["window"]
    count = {k: kinds(config).count(k) for k in per}
    parts = {k: count[k] * (per[k]["matmul"] + per[k]["other"]) for k in per}
    parts["attention"] = parts.pop("window") + parts.pop("full")
    parts["mlp"] = s["layers"] * 3 * s["h"] * s["m"]
    parts["layer_norms"] = (2 * s["layers"] + 1) * 2 * s["h"]
    parts["embedding_and_head"] = s["vocab"] * s["h"] * (
        1 if config["model"].get("tie_word_embeddings") else 2)
    parts["total"] = sum(parts.values())
    return parts


def kv_bytes_a_token(config: dict, cache_itemsize: int) -> int:
    """Keys and values of one token in one pool."""
    s = sizes(config)
    return 2 * s["kv_heads"] * s["dh"] * cache_itemsize


def state_bytes_a_slot(config: dict) -> int:
    """One sequence's recurrent state over the Mamba-1 layers."""
    s = sizes(config)
    w = ITEMSIZE[config["model"].get("torch_dtype", "bfloat16")]
    return kinds(config).count("mamba") * (
        s["d_inner"] * s["n"] * 4 + (s["k"] - 1) * s["d_inner"] * w)


def kv_read_bytes(config: dict, cache_itemsize: int, context_tokens: float,
                  window_context_tokens: float) -> dict:
    """Keys and values one decode step's attention reads. ``context_tokens``:
    the live slots' contexts summed; ``window_context_tokens``: the same,
    each cut to the window."""
    of = kinds(config)
    row = kv_bytes_a_token(config, cache_itemsize)
    parts = {
        "shared_pool": (of.count("full") + of.count("cross")) * row
        * context_tokens,
        "window_pools": of.count("window") * row * window_context_tokens,
    }
    parts["total"] = sum(parts.values())
    return parts


def ssm_step_bytes(config: dict, live_slots: float) -> dict:
    """What the Mamba-1 layers of one decode step must move."""
    w = ITEMSIZE[config["model"].get("torch_dtype", "bfloat16")]
    per = mixer_parameters(config)["mamba"]
    n = kinds(config).count("mamba")
    parts = {"weights": n * w * (per["matmul"] + per["other"]),
             "state_in_and_out": 2 * live_slots * state_bytes_a_slot(config)}
    parts["total"] = sum(parts.values())
    return parts


def decode_step_bytes(config: dict, cache_itemsize: int, live_slots: float,
                      context_tokens: float,
                      window_context_tokens: float) -> dict:
    """Bytes one decode step must move, by part."""
    w = ITEMSIZE[config["model"].get("torch_dtype", "bfloat16")]
    parts = {
        "weights": w * parameters(config)["total"],
        "state_in_and_out": ssm_step_bytes(
            config, live_slots)["state_in_and_out"],
        "keys_and_values": kv_read_bytes(
            config, cache_itemsize, context_tokens,
            window_context_tokens)["total"],
    }
    parts["total"] = sum(parts.values())
    return parts


def prefill_flops(config: dict, tokens: float, rows: float,
                  window_pairs: float, context_tokens: float) -> dict:
    """FLOP a prefill call over ``tokens`` prompt tokens in ``rows`` rows
    must do, by part. ``window_pairs``: (query, key) pairs those tokens
    could see in ONE windowed layer; ``context_tokens``: keys the rows' last
    tokens see, summed over the rows (each row's whole context: what the
    full layer and each cross layer need for the one query a row that
    anyone reads)."""
    s = sizes(config)
    of, per = kinds(config), mixer_parameters(config)
    mlp = 3 * s["h"] * s["m"]
    kv_proj = s["h"] * 2 * s["kv_heads"] * s["dh"]
    early = (of.count("mamba") * per["mamba"]["matmul"]
             + of.count("window") * per["window"]["matmul"]
             + (of.index("full")) * mlp + kv_proj)
    late = (per["window"]["matmul"] - kv_proj + mlp
            + of.count("memory_unit") * per["memory_unit"]["matmul"]
            + of.count("cross") * per["cross"]["matmul"]
            + (s["layers"] - of.index("full") - 1) * mlp)
    # two softmaxes a pair of heads: two score products over dh, two value
    # products over 2 dh
    per_pair = 2 * (s["heads"] // 2) * 2 * (s["dh"] + 2 * s["dh"])
    parts = {
        "every_token": 2 * tokens * early,
        "one_token_a_row": 2 * rows * late,
        "window_attention": per_pair * of.count("window") * window_pairs,
        "last_query_attention": per_pair * (of.count("full")
                                            + of.count("cross"))
        * context_tokens,
        "scan": SCAN_FLOPS_A_STATE * of.count("mamba") * s["d_inner"]
        * s["n"] * tokens,
    }
    parts["total"] = sum(parts.values())
    return parts


def decode_means(ctx: dict):
    """``(live slots, context tokens, window context tokens)`` a decode step
    in the mean between the window's two scrapes; None for another
    configuration, without the family's counters (the parent's program), or
    on the CPU."""
    import stats

    if not is_family(ctx["config"]):
        return None
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    slots = stats.counter_delta(a, b, "dlti_decode_slot_steps")
    tokens = stats.counter_delta(a, b, "dlti_decode_context_tokens")
    inside = stats.counter_delta(a, b, "dlti_decode_window_context_tokens")
    resets = stats.counter_delta(a, b, "dlti_recurrent_state_resets")
    if not steps or not slots or not tokens or not inside or resets is None \
            or ctx["device"]["platform"] == "cpu":
        return None
    return slots / steps, tokens / steps, inside / steps
