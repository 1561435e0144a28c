"""Per-layer metric ``idle_ms_per_step.decode_emit``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "idle_ms_per_step.decode_emit"
UNIT = "ms/step"
BETTER = "lower"
LAYER = "engine (serving/engine.py InferenceEngine)"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def read(ctx):
    """Device idle time under ``engine/decode_emit`` (numeric guards and the
    walk that appends each slot's token), per execution of the decode
    program in the traced window."""
    return attribute_idle.idle_ms_per_step(ctx, "decode_emit")
