"""Per-layer metric ``prefill_mfu_pct.loop``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "prefill_mfu_pct.loop"
UNIT = "%"
BETTER = "higher"
LAYER = "executor, prefill programs (serving/engine.py EngineExecutor)"
MOVES = "itl_mean_ms"
SOURCE = "device_trace"


def read(ctx):
    """The prefill programs' share of the chip's arithmetic peak for a
    configuration whose layers run several times: the FLOP that the prompt
    tokens of a prefill call need in the mean (``loop_work.prefill_flops``:
    2 a layer parameter a token **a pass** plus attention's products over
    the (query, key) pairs of every layer of every pass, from d
    ``prefill_tokens`` and d ``prefill_attention_pairs`` over d
    ``prefill_batches`` of the window), over the mean device time of a
    ``jit_prefill`` execution in the trace times the published bf16 peak. A
    share of the whole program: padding, the head, the gate and the keys
    the mask hides are in the time and not in the work, so it cannot pass
    100 %. None without ``total_ut_steps``, the counters, a prefill program
    in the trace, or on the CPU."""
    import flops
    import loop_work
    import stats

    trace = ctx["trace"]
    model = ctx["config"]["model"]
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    tokens = stats.counter_delta(a, b, "dlti_prefill_tokens")
    pairs = stats.counter_delta(a, b, "dlti_prefill_attention_pairs")
    calls = stats.counter_delta(a, b, "dlti_prefill_batches")
    passes = stats.counter_delta(a, b, "dlti_loop_passes_prefill")
    if ("total_ut_steps" not in model or not trace
            or not trace["programs"]["prefill"]["count"] or not tokens
            or not calls or pairs is None or not passes
            or ctx["device"]["platform"] == "cpu"):
        return None
    prefill = trace["programs"]["prefill"]
    per_call_s = prefill["total_s"] / prefill["count"]
    need = loop_work.prefill_flops(
        ctx["config"], tokens / calls, pairs / calls)["total"]
    peak = flops.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (need / peak) / per_call_s
