"""Per-layer metric ``prefill_mfu_pct.windows``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "prefill_mfu_pct.windows"
UNIT = "%"
BETTER = "higher"
LAYER = "executor, prefill programs (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """The prefill programs' share of the chip's arithmetic peak for a
    configuration whose layers differ in their window: the FLOP that the
    prompt tokens of a prefill call need in the mean
    (``window_bytes.prefill_flops``: 2 a parameter a token uses, of the
    routed experts the held share of the top-k, plus attention's products
    over the (query, key) pairs the tokens could see under each layer's own
    window, from d ``prefill_tokens``, d ``prefill_attention_pairs`` and d
    ``prefill_window_attention_pairs`` over d ``prefill_batches`` of the
    window), over the mean device time of a ``jit_prefill`` execution in
    the trace times the published bf16 peak. A share of the whole program:
    padding, the head over every position, masked experts and the keys a
    walk scores and the mask hides are in the time and not in the work, so
    it cannot pass 100 %. None without ``layer_types``, the counters, a
    prefill program in the trace, or on the CPU."""
    import flops
    import stats
    import window_bytes

    trace = ctx["trace"]
    model = ctx["config"]["model"]
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    tokens = stats.counter_delta(a, b, "dlti_prefill_tokens")
    pairs = stats.counter_delta(a, b, "dlti_prefill_attention_pairs")
    inside = stats.counter_delta(a, b, "dlti_prefill_window_attention_pairs")
    calls = stats.counter_delta(a, b, "dlti_prefill_batches")
    if ("layer_types" not in model or not trace
            or not trace["programs"]["prefill"]["count"] or not tokens
            or not calls or pairs is None or inside is None
            or ctx["device"]["platform"] == "cpu"):
        return None
    prefill = trace["programs"]["prefill"]
    per_call_s = prefill["total_s"] / prefill["count"]
    need = window_bytes.prefill_flops(
        ctx["config"], tokens / calls, pairs / calls, inside / calls)["total"]
    peak = flops.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (need / peak) / per_call_s
