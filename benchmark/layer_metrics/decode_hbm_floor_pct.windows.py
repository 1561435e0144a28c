"""Per-layer metric ``decode_hbm_floor_pct.windows``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "decode_hbm_floor_pct.windows"
UNIT = "%"
BETTER = "higher"
LAYER = "executor, decode program (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """``decode_hbm_floor_pct`` for a configuration whose layers differ in
    their window and whose MLPs are held experts: time the chip's memory
    would need at its published bandwidth to move what a decode step must
    (``window_bytes.decode_step_bytes``: attention weights and norms, the
    leading dense MLP, shared experts and routers, the held routed experts
    the step's tokens touched by ``moe_experts_touched_decode``, the head,
    the live keys and values of each layer under its own window), over
    ``decode_step_device_ms``. The byte count is a floor, so the share
    cannot pass 100 %. None without ``layer_types``, the counters, a decode
    program in the trace, or on the CPU."""
    import flops
    import stats
    import window_bytes

    trace = ctx["trace"]
    model = ctx["config"]["model"]
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    touched = stats.counter_delta(a, b, "dlti_moe_experts_touched_decode")
    tokens = stats.counter_delta(a, b, "dlti_decode_context_tokens")
    inside = stats.counter_delta(a, b, "dlti_decode_window_context_tokens")
    if ("layer_types" not in model or not trace
            or not trace["programs"]["decode"]["count"] or not steps
            or touched is None or tokens is None or inside is None
            or ctx["device"]["platform"] == "cpu"):
        return None
    decode = trace["programs"]["decode"]
    step_s = decode["total_s"] / decode["count"]
    need = window_bytes.decode_step_bytes(
        ctx["config"],
        window_bytes.ITEMSIZE[ctx["spec"]["args"]["--kv-cache-dtype"]],
        tokens / steps, inside / steps, touched / steps)["total"]
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / step_s
