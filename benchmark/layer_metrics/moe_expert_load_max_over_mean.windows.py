"""Per-layer metric ``moe_expert_load_max_over_mean.windows``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "moe_expert_load_max_over_mean.windows"
UNIT = "ratio"
BETTER = "lower"
LAYER = "model (models/llama.py, models/moe.py)"
MOVES = "output_tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    """``moe_expert_load_max_over_mean`` for a Llama-family configuration
    with held experts under its blocks (``layer_types`` beside
    ``first_k_dense_replace``; the held experts are the file's
    ``num_experts`` as run, which the latent family's reader looks for as
    ``n_routed_experts``): the mean over decode steps of the largest number
    of tokens on one held expert in one layer (d
    ``moe_expert_load_max_decode`` / d ``decode_steps``), over the mean
    number on a held expert (d ``moe_held_assignments_decode`` / (steps x
    the layers past the leading dense ones x experts held)). 1 = perfectly
    even. None without the counters or expert layers."""
    import stats

    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    worst = stats.counter_delta(a, b, "dlti_moe_expert_load_max_decode")
    held = stats.counter_delta(a, b, "dlti_moe_held_assignments_decode")
    model = ctx["config"]["model"]
    if not steps or not held or worst is None \
            or "layer_types" not in model or "num_experts" not in model:
        return None
    layers = model["num_hidden_layers"] - model.get("first_k_dense_replace", 0)
    if layers <= 0:
        return None
    mean = held / (steps * layers * model["num_experts"])
    return (worst / steps) / mean
