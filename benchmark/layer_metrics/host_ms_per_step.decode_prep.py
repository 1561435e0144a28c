"""Per-layer metric ``host_ms_per_step.decode_prep``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "host_ms_per_step.decode_prep"
UNIT = "ms"
BETTER = "lower"
LAYER = "engine (serving/engine.py InferenceEngine)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Of the host path, everything of a round before its program call:
    ``engine/decode_plan`` (speculation gate, block growth, who rides),
    ``engine/decode_assemble`` (ids, positions, mirrors),
    ``engine/decode_stage`` (the executor's upload of dirty rows) and what
    of ``engine/decode_prep`` is in none of them, over d ``decode_steps``."""
    return host_account.ms_per_step(ctx, (
        "engine/decode_prep", "engine/decode_plan",
        "engine/decode_assemble", "engine/decode_stage"))
