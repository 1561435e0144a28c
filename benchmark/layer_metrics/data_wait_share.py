"""Per-layer metric ``data_wait_share``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "data_wait_share"
UNIT = "%"
BETTER = "lower"
LAYER = "data pipeline (data/pipeline.py, data/prefetch.py)"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(ctx):
    """Step log ``data_wait_s`` summed over the window's steps, over the
    window: the share of the window the step loop waited for a batch."""
    rows = ctx["rows"]
    waited = sum(r.get("data_wait_s", 0.0) for r in rows)
    return 100.0 * waited / ctx["window"]["seconds"]
