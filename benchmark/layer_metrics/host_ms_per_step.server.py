"""Per-layer metric ``host_ms_per_step.server``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "host_ms_per_step.server"
UNIT = "ms"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Of the host path, the server's loop round the step: taking the
    lock the handlers' submits take too (``server/lock_wait``) and pushing
    the round's tokens to the handlers' queues (``server/drain_events``),
    over d ``decode_steps``."""
    return host_account.ms_per_step(ctx, (
        "server/lock_wait", "server/drain_events"))
