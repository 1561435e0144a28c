"""Per-layer metric ``host_ms_per_step.admit``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "host_ms_per_step.admit"
UNIT = "ms"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Of the host path, admission and the host side of prefill:
    ``engine/admit`` (with what of a prefill group is neither launch nor
    wait), ``engine/prefill_chunks`` and ``engine/prefill_launch``, over
    d ``decode_steps``. The wait for a prefill's tokens is not host time."""
    return host_account.ms_per_step(ctx, (
        "engine/admit", "engine/prefill_chunks", "engine/prefill_launch"))
