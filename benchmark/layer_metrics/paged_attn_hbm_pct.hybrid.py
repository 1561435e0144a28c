"""Per-layer metric ``paged_attn_hbm_pct.hybrid``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "paged_attn_hbm_pct.hybrid"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels (ops/pallas/paged_attention.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """``paged_attn_hbm_pct`` for a configuration with a pattern of mixers:
    only its attention layers (``*`` in ``hybrid_override_pattern``) keep
    keys and values, so the bytes a token are counted over those, not over
    ``num_hidden_layers``. Time the chip's memory would need to read the
    keys and values a decode step attends over, over the time the
    paged-attention kernel takes a step. None without a pattern, the
    counter or the kernel's name."""
    import flops
    import stats

    model = ctx["config"]["model"]
    if "hybrid_override_pattern" not in model:
        return None
    kernel_ms = attribute_idle.kernel_ms_per_step(ctx, "paged_attention")
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    tokens = stats.counter_delta(a, b, "dlti_decode_context_tokens")
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    if not kernel_ms or not tokens or not steps \
            or ctx["device"]["platform"] == "cpu":
        return None
    layers = model["hybrid_override_pattern"][
        :model["num_hidden_layers"]].count("*")
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[
        ctx["spec"]["args"]["--kv-cache-dtype"]]
    bytes_a_token = (2 * layers * model["num_key_value_heads"]
                     * model["head_dim"] * itemsize)
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (tokens / steps * bytes_a_token / peak) / (kernel_ms / 1e3)
