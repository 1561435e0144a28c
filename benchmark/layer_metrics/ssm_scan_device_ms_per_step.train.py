"""Per-layer metric ``ssm_scan_device_ms_per_step.train``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "ssm_scan_device_ms_per_step.train"
UNIT = "ms"
BETTER = "lower"
LAYER = "model (models/jamba.py, models/mamba1.py)"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(ctx):
    """Device time a train step spends in the selective scan of the Mamba-1
    layers, forward (twice where the block is recomputed) and backward: the
    operations of ``jit_train_step`` that the trace puts under the scopes
    ``dlti_selective_scan_fwd`` and ``dlti_selective_scan_bwd``
    (``scope_time``: the union of their intervals, a step in the mean).
    None where the trace names no such scope (another configuration, the
    parent's program)."""
    import scope_time

    per_call_s = scope_time.scope_s_per_call(ctx, "train_step",
                                             "dlti_selective_scan")
    return None if per_call_s is None else 1e3 * per_call_s
