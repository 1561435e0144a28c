"""Per-layer metric ``idle_attributed_share.train``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "idle_attributed_share.train"
UNIT = "%"
BETTER = "higher"
LAYER = "device"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_span"


def read(ctx):
    """Device idle time of the traced steps that a ``train/*`` span of the
    trainer's loop thread covers, over all of it. None for a program that
    annotates no spans."""
    return attribute_idle.attributed_share(ctx, "train/step_dispatch")
