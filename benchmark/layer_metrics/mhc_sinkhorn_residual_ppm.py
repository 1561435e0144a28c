"""Per-layer metric ``mhc_sinkhorn_residual_ppm``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "mhc_sinkhorn_residual_ppm"
UNIT = "ppm"
BETTER = "lower"
LAYER = "model (models/latent.py, models/hyper.py)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """How far the residual mixing matrices are from doubly stochastic: the
    mean over decode steps of the largest ``|row sum - 1|`` or ``|column sum
    - 1|`` of any ``H_res`` of the step (every live token, every sublayer),
    in parts per million (d ``mhc_sinkhorn_residual_e6_decode`` / d
    ``decode_steps``). What the published number of Sinkhorn rounds leaves
    on the seeded weights; a program that cut the rounds reads tens of
    times more here before the check's log-probs move. None without the
    counter."""
    import stats

    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    worst = stats.counter_delta(a, b, "dlti_mhc_sinkhorn_residual_e6_decode")
    if not steps or worst is None:
        return None
    return worst / steps
