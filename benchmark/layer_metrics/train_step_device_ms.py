"""Per-layer metric ``train_step_device_ms``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "train_step_device_ms"
UNIT = "ms"
BETTER = "lower"
LAYER = "train step (training/step.py)"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(ctx):
    """Median device time of one execution of the train-step program."""
    trace = ctx["trace"]
    if not trace or not trace["programs"]["train_step"]["count"]:
        return None
    return 1e3 * trace["programs"]["train_step"]["median_s"]
