"""Per-layer metric ``paged_attn_hbm_pct.loop``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "paged_attn_hbm_pct.loop"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels (ops/pallas/paged_attention.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """``paged_attn_hbm_pct`` for a configuration whose layers run several
    times: time the chip's memory would need to read the keys and values a
    decode step attends over, the bytes a token counted over every (pass,
    layer) entry (``loop_work.cache_bytes_a_token``: ``total_ut_steps x
    num_hidden_layers`` entries) x the mean context a step (d
    ``decode_context_tokens`` / d ``decode_steps``), over the time the
    paged-attention kernel takes a step (every entry's call: 192 of them at
    group 1 here). What a call costs beside its bytes is in the time and
    not in the work. None without ``total_ut_steps``, the looped stack's
    counter, the kernel's name, or on the CPU."""
    import flops
    import loop_work
    import stats

    model = ctx["config"]["model"]
    if "total_ut_steps" not in model:
        return None
    kernel_ms = attribute_idle.kernel_ms_per_step(ctx, "paged_attention")
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    tokens = stats.counter_delta(a, b, "dlti_decode_context_tokens")
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    passes = stats.counter_delta(a, b, "dlti_loop_passes_decode")
    if not kernel_ms or not tokens or not steps or not passes \
            or ctx["device"]["platform"] == "cpu":
        return None
    need = tokens / steps * loop_work.cache_bytes_a_token(
        model, loop_work.ITEMSIZE[ctx["spec"]["args"]["--kv-cache-dtype"]])
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (kernel_ms / 1e3)
