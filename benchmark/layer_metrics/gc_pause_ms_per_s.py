"""Per-layer metric ``gc_pause_ms_per_s``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "gc_pause_ms_per_s"
UNIT = "ms/s"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Milliseconds a second of the window inside the cyclic collector, all
    generations and all threads: 1000 x d ``gc_pause_seconds`` (the
    ``gc.callbacks`` hook ``scripts/serve.py`` installs) over the seconds
    between the two scrapes. 0 in a window without a collection (the three
    generations stand on ``/metrics`` from the hook's installation); a
    generation the first scrape lacks began at 0."""
    a, b = host_account.scrapes(ctx)
    now = host_account.labelled(b, "dlti_gc_pause_seconds_total",
                                "generation")
    window = host_account.window_seconds(ctx)
    if not now or not window:
        return None
    was = host_account.labelled(a, "dlti_gc_pause_seconds_total",
                                "generation")
    paused = sum(v - was.get(g, 0.0) for g, v in now.items())
    return 1000.0 * paused / window
