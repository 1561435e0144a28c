"""Per-layer metric ``stalled_ms_per_s``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "stalled_ms_per_s"
UNIT = "ms/s"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Milliseconds a second of the window the stepper spent in host phases
    that went 250 ms without a transition (each leaves a WARNING line and a
    ``server/stall`` instant): 1000 x d ``stepper_stall_seconds`` over the
    seconds between the two scrapes. 0 in a quiet run."""
    import stats

    a, b = host_account.scrapes(ctx)
    stalled = stats.counter_delta(a, b, "dlti_stepper_stall_seconds_total")
    window = host_account.window_seconds(ctx)
    if stalled is None or not window:
        return None
    return 1000.0 * stalled / window
