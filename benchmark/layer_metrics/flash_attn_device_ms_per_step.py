"""Per-layer metric ``flash_attn_device_ms_per_step``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "flash_attn_device_ms_per_step"
UNIT = "ms/step"
BETTER = "lower"
LAYER = "kernels (ops/pallas/flash_attention.py)"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(ctx):
    """Device time of the three flash-attention kernels (forward, and the
    backward's dq and dk/dv; found by their ``name=``) summed, per
    execution of the train step in the traced window. None for a program
    whose kernels have no name of their own."""
    return attribute_idle.kernel_ms_per_step(ctx, "flash_attention")
