"""Per-layer metric ``ssm_state_hbm_pct``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "ssm_state_hbm_pct"
UNIT = "%"
BETTER = "higher"
LAYER = "model (models/sambay.py, models/mamba1.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """The Mamba-1 layers' share of their roofline in a decode step, while
    the layer is XLA's: time the chip's memory would need to move their
    weights once and the live slots' convolution tail and state in and out
    (``sambay_work.ssm_step_bytes``, by d ``decode_slot_steps`` / d
    ``decode_steps``), over the device time under ``dlti_mamba1`` a step
    (``ssm_state_device_ms_per_step``). The weights are most of it (742 MB
    of 949 at 32 slots): a layer of matrix-vector products. None for another
    configuration, without the counters or the scope, or on the CPU."""
    import flops
    import sambay_work
    import scope_time

    got = sambay_work.decode_means(ctx)
    if got is None:
        return None
    per_call_s = scope_time.scope_s_per_call(ctx, "decode", "dlti_mamba1")
    if not per_call_s:
        return None
    need = sambay_work.ssm_step_bytes(ctx["config"], got[0])["total"]
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / per_call_s
