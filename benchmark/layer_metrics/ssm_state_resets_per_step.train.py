"""Per-layer metric ``ssm_state_resets_per_step.train``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "ssm_state_resets_per_step.train"
UNIT = "resets/step"
BETTER = "lower"
LAYER = "model (models/jamba.py, models/mamba1.py)"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "program_counter"


def read(ctx):
    """Documents that started inside a step's rows, so recurrent states
    that started from zero a layer: the mean of the counter
    ``recurrent_state_resets`` over the window's step rows (the model counts
    them from the packed segments, the trainer writes them into each row).
    What the window's documents predict is
    ``jamba_work.documents_per_step`` (the step's real tokens over the mean
    document). None where the rows carry no such counter (another family,
    the parent's program)."""
    rows = [r["recurrent_state_resets"] for r in ctx["rows"]
            if "recurrent_state_resets" in r]
    return sum(rows) / len(rows) if rows else None
