"""Per-layer metric ``idle_ms_per_step.decode_prep``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "idle_ms_per_step.decode_prep"
UNIT = "ms/step"
BETTER = "lower"
LAYER = "engine (serving/engine.py InferenceEngine)"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def read(ctx):
    """Device idle time under ``engine/decode_prep`` (everything a decode
    round does on the host before its program is called: round gating,
    block growth, batch assembly, the decode-state sync and its uploads),
    per execution of the decode program in the traced window (the count
    ``decode_step_device_ms`` divides by). With the other ``idle_ms_per_step.*``, the idle under
    any other span and the unattributed rest (both in
    ``idle_attribution.json`` beside the trace) it adds up to the device's
    idle time per step."""
    return attribute_idle.idle_ms_per_step(ctx, "decode_prep")
