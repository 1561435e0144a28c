"""Per-layer metric ``decode_slot_occupancy``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "decode_slot_occupancy"
UNIT = "%"
BETTER = "higher"
LAYER = "engine (serving/engine.py InferenceEngine)"
MOVES = "output_tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    """Slots that held a live sequence, over all slots, across the window's
    decode steps: d(decode_slot_steps) / (max_seqs x d(decode_steps))."""
    import stats

    a, b = ctx["metrics_before"], ctx["metrics_after"]
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    slots = stats.counter_delta(a, b, "dlti_decode_slot_steps")
    if not steps or slots is None:
        return None
    return 100.0 * slots / (int(ctx["spec"]["args"]["--max-seqs"]) * steps)
