"""Per-layer metric ``decode_hbm_floor_pct.loop``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "decode_hbm_floor_pct.loop"
UNIT = "%"
BETTER = "higher"
LAYER = "executor, decode program (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """``decode_hbm_floor_pct`` for a configuration whose layers run
    several times over one set of weights: time the chip's memory would need
    at its published bandwidth to move what a decode step must
    (``loop_work.decode_step_bytes``: the layers' weights once **a pass**,
    the final norm and the gate a pass, the head once, the live keys and
    values of every (pass, layer) entry by d ``decode_context_tokens`` over
    d ``decode_steps``), over ``decode_step_device_ms``: the decode
    program's share of its roofline. The byte count is a floor, so the
    share cannot pass 100 %. None without ``total_ut_steps``, the counters,
    a decode program in the trace, or on the CPU."""
    import flops
    import loop_work
    import stats

    trace = ctx["trace"]
    model = ctx["config"]["model"]
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    tokens = stats.counter_delta(a, b, "dlti_decode_context_tokens")
    passes = stats.counter_delta(a, b, "dlti_loop_passes_decode")
    if ("total_ut_steps" not in model or not trace
            or not trace["programs"]["decode"]["count"] or not steps
            or tokens is None or not passes
            or ctx["device"]["platform"] == "cpu"):
        return None
    decode = trace["programs"]["decode"]
    step_s = decode["total_s"] / decode["count"]
    need = loop_work.decode_step_bytes(
        ctx["config"],
        loop_work.ITEMSIZE[ctx["spec"]["args"]["--kv-cache-dtype"]],
        tokens / steps)["total"]
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / step_s
