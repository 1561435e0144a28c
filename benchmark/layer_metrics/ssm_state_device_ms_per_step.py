"""Per-layer metric ``ssm_state_device_ms_per_step``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "ssm_state_device_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "model (models/sambay.py, models/mamba1.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """Device time a decode step spends in the Mamba-1 layers: the
    operations of ``jit_decode`` that the trace puts under the scope
    ``dlti_mamba1`` (``scope_time``: the union of their intervals, a call in
    the mean): projections, convolution, the state's update, the gate. None
    where the trace's operations carry no such scope (another
    configuration, the parent's program)."""
    import scope_time

    per_call_s = scope_time.scope_s_per_call(ctx, "decode", "dlti_mamba1")
    return None if per_call_s is None else 1e3 * per_call_s
