"""Per-layer metric ``decode_hbm_floor_pct``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "decode_hbm_floor_pct"
UNIT = "%"
BETTER = "higher"
LAYER = "executor, decode program (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """Time the chip's memory would need at its published bandwidth to move
    what a decode step of a patterned configuration must
    (``hybrid_bytes.decode_step_bytes``: mixer weights, the held experts the
    step's tokens touched by the counter ``moe_experts_touched_decode``, the
    head, the live slots' recurrent state in and out, keys and values of the
    live context), over ``decode_step_device_ms``. The byte count is a
    floor, so the share cannot pass 100 %. None without the counter (a
    program or a configuration without expert layers), without a decode
    program in the trace, or on the CPU."""
    import flops
    import hybrid_bytes
    import stats

    trace = ctx["trace"]
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    touched = stats.counter_delta(a, b, "dlti_moe_experts_touched_decode")
    slots = stats.counter_delta(a, b, "dlti_decode_slot_steps")
    tokens = stats.counter_delta(a, b, "dlti_decode_context_tokens")
    if (not trace or not trace["programs"]["decode"]["count"] or not steps
            or touched is None or slots is None or tokens is None
            or "hybrid_override_pattern" not in ctx["config"]["model"]
            or ctx["device"]["platform"] == "cpu"):
        return None
    decode = trace["programs"]["decode"]
    step_s = decode["total_s"] / decode["count"]
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[
        ctx["spec"]["args"]["--kv-cache-dtype"]]
    need = hybrid_bytes.decode_step_bytes(
        ctx["config"], itemsize, slots / steps, tokens / steps,
        touched / steps)["total"]
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / step_s
