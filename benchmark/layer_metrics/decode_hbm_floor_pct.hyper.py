"""Per-layer metric ``decode_hbm_floor_pct.hyper``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "decode_hbm_floor_pct.hyper"
UNIT = "%"
BETTER = "higher"
LAYER = "executor, decode program (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """``decode_hbm_floor_pct`` for a latent-attention configuration with a
    query latent and stream maps: time the chip's memory would need at its
    published bandwidth to move what a decode step must
    (``hyper_work.decode_step_bytes``: attention weights with ``q_a`` and
    ``q_b`` in place of one query projection, the maps' float32 weights,
    the leading dense MLPs, shared experts and routers, the routed experts
    the step's tokens touched by the counter ``moe_experts_touched_decode``,
    the head, the live latents once), over ``decode_step_device_ms``. The
    byte count is a floor, so the share cannot pass 100 %. None without the
    counters, without a decode program in the trace, without stream maps in
    the configuration, or on the CPU."""
    import flops
    import hyper_work
    import stats

    trace = ctx["trace"]
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    touched = stats.counter_delta(a, b, "dlti_moe_experts_touched_decode")
    tokens = stats.counter_delta(a, b, "dlti_decode_context_tokens")
    if (not trace or not trace["programs"]["decode"]["count"] or not steps
            or touched is None or tokens is None
            or not ctx["config"]["model"].get("hc_mult")
            or ctx["device"]["platform"] == "cpu"):
        return None
    decode = trace["programs"]["decode"]
    step_s = decode["total_s"] / decode["count"]
    itemsize = hyper_work.ITEMSIZE[ctx["spec"]["args"]["--kv-cache-dtype"]]
    need = hyper_work.decode_step_bytes(
        ctx["config"], itemsize, tokens / steps, touched / steps)["total"]
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / step_s
