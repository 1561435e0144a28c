"""Per-layer metric ``paged_attn_hbm_pct.windows``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "paged_attn_hbm_pct.windows"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels (ops/pallas/paged_attention.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """``paged_attn_hbm_pct`` for a configuration whose layers differ in
    their window: time the chip's memory would need to read the keys and
    values a decode step attends over **under each layer's own window**
    (``window_bytes.live_cache_bytes``: the full layers' whole contexts by
    d ``decode_context_tokens``, the window layers' last ``sliding_window``
    keys by d ``decode_window_context_tokens``, each over d
    ``decode_steps``), over the time the paged-attention kernel takes a
    step (every layer's call). The count does not depend on what the kernel
    copies: a tile of 256 keys for 128 live ones is in the time and not in
    the bytes. None without ``layer_types``, the counters or the kernel's
    name."""
    import flops
    import stats
    import window_bytes

    model = ctx["config"]["model"]
    if "layer_types" not in model:
        return None
    kernel_ms = attribute_idle.kernel_ms_per_step(ctx, "paged_attention")
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    tokens = stats.counter_delta(a, b, "dlti_decode_context_tokens")
    inside = stats.counter_delta(a, b, "dlti_decode_window_context_tokens")
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    if not kernel_ms or not tokens or inside is None or not steps \
            or ctx["device"]["platform"] == "cpu":
        return None
    need = window_bytes.live_cache_bytes(
        model, window_bytes.ITEMSIZE[ctx["spec"]["args"]["--kv-cache-dtype"]],
        tokens / steps, inside / steps)
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (kernel_ms / 1e3)
