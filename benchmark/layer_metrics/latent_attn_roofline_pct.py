"""Per-layer metric ``latent_attn_roofline_pct``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

import attribute_idle

NAME = "latent_attn_roofline_pct"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels (ops/pallas/latent_attention.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """The latent-attention decode kernel's share of its roofline: the
    longer of the time the chip's memory needs for the live latents of a
    decode step, each read once a layer, and the time its MXU needs for the
    kernel's products over them (``latent_bytes.kernel_work``, from
    d ``decode_context_tokens`` / d ``decode_steps``), over the time the
    kernel takes a step. The work is the least any kernel must do, so the
    share cannot pass 100 %. None without the kernel's name, the counters
    or latent attention in the configuration."""
    import flops
    import latent_bytes
    import stats

    model = ctx["config"]["model"]
    if "kv_lora_rank" not in model:
        return None
    kernel_ms = attribute_idle.kernel_ms_per_step(ctx, "latent_attention")
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    tokens = stats.counter_delta(a, b, "dlti_decode_context_tokens")
    steps = stats.counter_delta(a, b, "dlti_decode_steps")
    if not kernel_ms or not tokens or not steps \
            or ctx["device"]["platform"] == "cpu":
        return None
    itemsize = latent_bytes.ITEMSIZE[ctx["spec"]["args"]["--kv-cache-dtype"]]
    work = latent_bytes.kernel_work(model, itemsize, tokens / steps)
    peak = flops.peaks(ctx["device"]["kind"])
    need_s = max(work["bytes"] / peak["hbm_bytes_per_s"],
                 work["flops"] / peak["bf16_flops_per_s"])
    return 100.0 * need_s / (kernel_ms / 1e3)
