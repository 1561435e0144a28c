"""Per-layer metric ``loop_exit_expected_pass``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "loop_exit_expected_pass"
UNIT = "passes"
BETTER = "lower"
LAYER = "model (models/llama.py)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """The pass at which the exit gate expects a decode token to leave, in
    the mean: d ``loop_exit_pass_e3_decode`` (the sum over kept decode
    tokens of 1000 x sum_u u p_u of the gate's distribution) / d
    ``decode_slot_steps`` / 1000, between 1 and ``total_ut_steps``. Every
    token runs every pass today (``early_exit_threshold`` 1): this is what
    an adaptive exit would have to work with, on seeded weights a property
    of the seeding. None without the counters."""
    import stats

    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    e3 = stats.counter_delta(a, b, "dlti_loop_exit_pass_e3_decode")
    rows = stats.counter_delta(a, b, "dlti_decode_slot_steps")
    if e3 is None or not rows:
        return None
    return e3 / rows / 1000.0
