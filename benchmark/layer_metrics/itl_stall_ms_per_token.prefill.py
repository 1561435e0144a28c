"""Per-layer metric ``itl_stall_ms_per_token.prefill``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "itl_stall_ms_per_token.prefill"
UNIT = "ms"
BETTER = "lower"
LAYER = "executor, prefill programs (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Milliseconds of an average gap between two tokens that were a prefill
    call's: d ``decode_stream_stall_seconds_prefill`` (the wall of every
    prefill call, launch to fetch, times the slots that held a decoding
    stream when it began) over d ``decode_slot_steps`` (kept tokens)."""
    import stats

    a, b = host_account.scrapes(ctx)
    stall = stats.counter_delta(
        a, b, "dlti_decode_stream_stall_seconds_prefill")
    tokens = stats.counter_delta(a, b, "dlti_decode_slot_steps")
    if stall is None or not tokens:
        return None
    return 1000.0 * stall / tokens
