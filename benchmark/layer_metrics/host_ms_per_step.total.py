"""Per-layer metric ``host_ms_per_step.total``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "host_ms_per_step.total"
UNIT = "ms"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Wall milliseconds of the stepper thread's host path a decode step:
    the window's growth of every phase of the stepper's always-on clock
    that the program labels ``kind="host"`` (all but the waits for work and
    for the device, ``server/wait_work``, ``engine/decode_wait``,
    ``engine/prefill_wait``, and what of the thread's time is outside every
    phase, ``server/loop``), over d ``decode_steps``. What the host takes
    between two fetches, whether or not a program hid it: with the decode
    loop a round ahead, the larger of this and the device's step is the pace."""
    return host_account.ms_per_step(ctx)
