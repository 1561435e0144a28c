"""Per-layer metric ``paged_attn_hbm_pct``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "paged_attn_hbm_pct"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels (ops/pallas/paged_attention.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """Time the chip's memory would need at its published bandwidth to read
    the keys and values a decode step attends over (no weights, no
    output), over the time the paged-attention kernel takes a step:
    mean context a step (d ``decode_context_tokens`` / d ``decode_steps``
    over the window) x cache bytes a token of the configuration (keys and
    values of every layer) / HBM bytes a second, over
    ``paged_attn_device_ms_per_step``. None without the counter or the
    kernel's name."""
    import flops
    import stats

    kernel_ms = attribute_idle.kernel_ms_per_step(ctx, "paged_attention")
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    tokens = stats.counter_delta(a, b, "dlti_decode_context_tokens")
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    if not kernel_ms or not tokens or not steps \
            or ctx["device"]["platform"] == "cpu":
        return None
    model = ctx["config"]["model"]
    head_dim = model.get("head_dim") \
        or model["hidden_size"] // model["num_attention_heads"]
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[
        ctx["spec"]["args"]["--kv-cache-dtype"]]
    bytes_a_token = (2 * model["num_hidden_layers"]
                     * model["num_key_value_heads"] * head_dim * itemsize)
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (tokens / steps * bytes_a_token / peak) / (kernel_ms / 1e3)
