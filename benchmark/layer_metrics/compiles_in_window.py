"""Per-layer metric ``compiles_in_window``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "compiles_in_window"
UNIT = "programs"
BETTER = "lower"
LAYER = "executor start-up (compile and compile-cache fetch)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Programs the server compiled or fetched from the persistent cache
    between the window's two scrapes: d ``dlti_compilations_total`` +
    d ``dlti_compile_cache_hits_total`` (``telemetry/startup.py``'s listener
    counts both). 0 in every run whose warm-up met every shape: a run that
    reads more compiled inside its window, reads ``correct`` false for it,
    and its server's log names each program in a ``compiled after ready``
    line. A family the program has not counted into yet stands at 0; None
    where a scrape has neither."""
    a, b = host_account.scrapes(ctx)
    names = ("dlti_compilations_total", "dlti_compile_cache_hits_total")
    if not any(n in b for n in names) or not a:
        return None
    return sum(b.get(n, 0.0) - a.get(n, 0.0) for n in names)
