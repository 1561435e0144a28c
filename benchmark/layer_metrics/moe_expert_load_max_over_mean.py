"""Per-layer metric ``moe_expert_load_max_over_mean``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "moe_expert_load_max_over_mean"
UNIT = "ratio"
BETTER = "lower"
LAYER = "model (models/nemotron_h.py, models/mamba2.py, models/moe.py)"
MOVES = "output_tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    """How uneven the routing of decode steps is over the window: the mean
    over decode steps of the largest number of tokens on one held expert in
    one layer (d ``moe_expert_load_max_decode`` / d ``decode_steps``), over the mean
    number on a held expert (d ``moe_held_assignments_decode`` / (steps x
    expert layers x experts held)). 1 = perfectly even. None without the
    counters or expert layers."""
    import stats

    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    worst = stats.counter_delta(a, b, "dlti_moe_expert_load_max_decode")
    held = stats.counter_delta(a, b, "dlti_moe_held_assignments_decode")
    model = ctx["config"]["model"]
    if not steps or not held or worst is None \
            or "hybrid_override_pattern" not in model:
        return None
    layers = model["hybrid_override_pattern"][
        :model["num_hidden_layers"]].count("E")
    mean = held / (steps * layers * model["n_routed_experts"])
    return (worst / steps) / mean
