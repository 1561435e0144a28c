"""Per-layer metric ``paged_attn_hbm_pct.sambay``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "paged_attn_hbm_pct.sambay"
UNIT = "%"
BETTER = "higher"
LAYER = "kernels (ops/pallas/paged_attention.py)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """``paged_attn_hbm_pct`` for the decoder-hybrid-decoder family: time the
    chip's memory would need to read the keys and values a decode step
    attends over (``sambay_work.kv_read_bytes``: the shared pool's live rows
    once a reader, the full layer and the seven cross layers, and each of
    the eight window pools' rows inside the window), over the time the
    paged-attention kernel takes a step (sixteen calls). What a call costs
    beside its bytes is in the time and not in the work. None for another
    configuration, without the counters or the kernel's name, or on the
    CPU."""
    import attribute_idle
    import flops
    import sambay_work

    got = sambay_work.decode_means(ctx)
    if got is None:
        return None
    kernel_ms = attribute_idle.kernel_ms_per_step(ctx, "paged_attention")
    if not kernel_ms:
        return None
    need = sambay_work.kv_read_bytes(
        ctx["config"],
        sambay_work.ITEMSIZE[ctx["spec"]["args"]["--kv-cache-dtype"]],
        got[1], got[2])["total"]
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (kernel_ms / 1e3)
