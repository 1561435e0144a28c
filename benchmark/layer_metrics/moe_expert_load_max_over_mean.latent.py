"""Per-layer metric ``moe_expert_load_max_over_mean.latent``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "moe_expert_load_max_over_mean.latent"
UNIT = "ratio"
BETTER = "lower"
LAYER = "model (models/latent.py, models/moe.py)"
MOVES = "output_tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    """``moe_expert_load_max_over_mean`` for a latent-attention
    configuration, whose expert layers are those after the leading dense
    ones (``num_hidden_layers - first_k_dense_replace``) and which has no
    ``hybrid_override_pattern`` for the other reader to count them by: the
    mean over decode steps of the largest number of tokens on one held
    expert in one layer (d ``moe_expert_load_max_decode`` / d
    ``decode_steps``), over the mean number on a held expert (d
    ``moe_held_assignments_decode`` / (steps x expert layers x experts
    held, the configuration's ``n_routed_experts`` as run)). 1 = perfectly
    even. None without the counters or expert layers."""
    import stats

    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    worst = stats.counter_delta(a, b, "dlti_moe_expert_load_max_decode")
    held = stats.counter_delta(a, b, "dlti_moe_held_assignments_decode")
    model = ctx["config"]["model"]
    if not steps or not held or worst is None \
            or "first_k_dense_replace" not in model:
        return None
    layers = model["num_hidden_layers"] - model["first_k_dense_replace"]
    if layers <= 0:
        return None
    mean = held / (steps * layers * model["n_routed_experts"])
    return (worst / steps) / mean
