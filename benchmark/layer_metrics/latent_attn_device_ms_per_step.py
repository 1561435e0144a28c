"""Per-layer metric ``latent_attn_device_ms_per_step``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "latent_attn_device_ms_per_step"
UNIT = "ms/step"
BETTER = "lower"
LAYER = "kernels (ops/pallas/latent_attention.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """Device time of the latent-attention decode kernel (found by its
    ``name=``, see ``benchmark/rules/latent_attention.json``) per execution
    of the decode program in the traced window. None for a program that
    runs no such kernel."""
    return attribute_idle.kernel_ms_per_step(ctx, "latent_attention")
