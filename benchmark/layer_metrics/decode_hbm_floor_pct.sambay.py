"""Per-layer metric ``decode_hbm_floor_pct.sambay``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "decode_hbm_floor_pct.sambay"
UNIT = "%"
BETTER = "higher"
LAYER = "executor, decode program (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """``decode_hbm_floor_pct`` for the decoder-hybrid-decoder family: time
    the chip's memory would need at its published bandwidth to move what a
    decode step must (``sambay_work.decode_step_bytes``: every weight once
    with the tied head once, the live slots' convolution tail and state in
    and out for the nine Mamba-1 layers by d ``decode_slot_steps``, the live
    rows of the shared pool once a reader, eight of them, by d
    ``decode_context_tokens``, the rows inside the window of each window
    pool by d ``decode_window_context_tokens``, all over d ``decode_steps``),
    over ``decode_step_device_ms``: the decode program's share of its
    roofline. The byte count is a floor, so the share cannot pass 100 %.
    None for another configuration, without the counters, without a decode
    program in the trace, or on the CPU."""
    import flops
    import sambay_work

    trace, got = ctx["trace"], sambay_work.decode_means(ctx)
    if got is None or not trace or not trace["programs"]["decode"]["count"]:
        return None
    decode = trace["programs"]["decode"]
    need = sambay_work.decode_step_bytes(
        ctx["config"],
        sambay_work.ITEMSIZE[ctx["spec"]["args"]["--kv-cache-dtype"]],
        *got)["total"]
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (decode["total_s"] / decode["count"])
