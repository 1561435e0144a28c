"""Per-layer metric ``setup_program_load_s``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "setup_program_load_s"
UNIT = "s"
BETTER = "lower"
LAYER = "executor start-up (compile and compile-cache fetch)"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    """Seconds the process spent compiling programs and fetching them from
    the persistent compilation cache, from its start to the opening of
    the window (``dlti_compile_seconds_total`` +
    ``dlti_compile_cache_fetch_seconds_total``; warm runs fetch, the cold
    first run compiles). None for a program without the counters."""
    return attribute_idle.startup_seconds(ctx, [
        "dlti_compile_seconds_total",
        "dlti_compile_cache_fetch_seconds_total"])
