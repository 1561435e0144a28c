"""Per-layer metric ``stepper_off_cpu_ms_per_step``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import host_account

NAME = "stepper_off_cpu_ms_per_step"
UNIT = "ms"
BETTER = "lower"
LAYER = "server and admission (serving/server.py, engine admit)"
MOVES = "itl_mean_ms"
SOURCE = "program_counter"


def read(ctx):
    """Wall milliseconds a decode step that the stepper thread was in a host
    phase and not on the CPU: runnable and not running, which is the
    interpreter lock (the handler threads hold it) or the OS. The thread's
    CPU clock is a slow system call and a coarse one on the chip's host
    (5.5 us a read, ticks of 10 ms), so the program reads it in one
    ``server/step`` in sixteen (a marked step). At that step's entry it
    takes three running totals as of one instant: the thread's CPU
    (``dlti_stepper_cpu_seconds_total``), the wall of its host phases
    (``..._marked_host_seconds_total``) and the engine's decode steps
    (``..._marked_decode_steps_total``): like with like, and exact to one
    tick over the window. The CPU inside the waits for the device, which no
    running total holds, is read round the marked step's waits and booked
    times sixteen (``..._device_wait_cpu_seconds_total``: an estimate).
    (d host wall - (d CPU - d device-wait CPU)) / d decode steps, given as
    it comes out, signed."""
    import stats

    a, b = host_account.scrapes(ctx)
    steps, wall, cpu, waits = (stats.counter_delta(a, b, name) for name in (
        "dlti_stepper_marked_decode_steps_total",
        "dlti_stepper_marked_host_seconds_total",
        "dlti_stepper_cpu_seconds_total",
        "dlti_stepper_device_wait_cpu_seconds_total"))
    if not steps or None in (wall, cpu, waits):
        return None
    return 1000.0 * (wall - (cpu - waits)) / steps
