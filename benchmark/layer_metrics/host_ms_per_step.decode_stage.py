"""Per-layer metric ``host_ms_per_step.decode_stage``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "host_ms_per_step.decode_stage"
UNIT = "ms"
BETTER = "lower"
LAYER = "executor, decode program (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Of the host path, ``engine/decode_stage`` alone: the executor's
    ``stage_decode`` (the dirty rows' upload and the small programs
    ``jit_fold_in`` / ``jit__apply_rows`` the longest idle gaps are named
    after), over d ``decode_steps``."""
    return host_account.ms_per_step(ctx, ("engine/decode_stage",))
