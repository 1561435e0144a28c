"""Per-layer metric ``idle_ms_per_step.decode_wait``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "idle_ms_per_step.decode_wait"
UNIT = "ms/step"
BETTER = "lower"
LAYER = "executor, decode program (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "program_span"


def read(ctx):
    """Device idle time under ``engine/decode_launch`` (the call of the
    decode program) and ``engine/decode_wait`` (the blocking fetch of its
    results): the device idles there only between the call and the
    program's start, and between its end and the host's noticing, so
    this is the executor's own latency. Per execution of the decode
    program in the traced window."""
    return attribute_idle.idle_ms_per_step(ctx, "decode_wait")
