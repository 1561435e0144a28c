"""Per-layer metric ``paged_attn_device_ms_per_step``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "paged_attn_device_ms_per_step"
UNIT = "ms/step"
BETTER = "lower"
LAYER = "kernels (ops/pallas/paged_attention.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """Device time of the paged-attention decode kernel (found by its
    ``name=``, see ``span_rules.json``) per execution of the decode
    program in the traced window. None for a program whose kernel has no
    name of its own."""
    return attribute_idle.kernel_ms_per_step(ctx, "paged_attention")
