"""Per-layer metric ``prefill_mfu_pct.sambay``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "prefill_mfu_pct.sambay"
UNIT = "%"
BETTER = "higher"
LAYER = "executor, prefill programs (serving/engine.py EngineExecutor)"
MOVES = "output_tokens_per_s"
SOURCE = "device_trace"


def read(ctx):
    """The prefill programs' share of the chip's arithmetic peak for the
    decoder-hybrid-decoder family: the FLOP a prefill call MUST do in the
    mean (``sambay_work.prefill_flops``: layers 0-16 and layer 17's key and
    value projection over every prompt token; layer 17's query, output
    projection and MLP and layers 18-31 over one token a row; the windowed
    layers' attention by its band, the full and the cross layers' for each
    row's last query alone; the scan's updates; from d ``prefill_tokens``, d
    ``recurrent_state_resets`` (a row a prompt: no prompt of this cell goes
    as several calls), d ``prefill_window_attention_pairs`` and d
    ``prefill_batches`` of the window), over the mean device time of a
    ``jit_prefill`` execution in the trace times the published bf16 peak.
    Today's program runs all 32 layers over every prompt token (d
    ``cross_decoder_prefill_tokens`` counts them): it does more than this
    counts and reads low, and a program that skips what no later token needs
    cannot push it past 100 %. None for another configuration, without the
    counters, without a prefill program in the trace, or on the CPU."""
    import flops
    import sambay_work
    import stats

    trace = ctx["trace"]
    if not sambay_work.is_family(ctx["config"]):
        return None
    a, b = ctx.get("metrics_before") or {}, ctx.get("metrics_after") or {}
    tokens = stats.counter_delta(a, b, "dlti_prefill_tokens")
    rows = stats.counter_delta(a, b, "dlti_recurrent_state_resets")
    pairs = stats.counter_delta(a, b, "dlti_prefill_window_attention_pairs")
    calls = stats.counter_delta(a, b, "dlti_prefill_batches")
    if (not trace or not trace["programs"]["prefill"]["count"] or not tokens
            or not rows or not calls or pairs is None
            or ctx["device"]["platform"] == "cpu"):
        return None
    prefill = trace["programs"]["prefill"]
    need = sambay_work.prefill_flops(
        ctx["config"], tokens / calls, rows / calls, pairs / calls,
        tokens / calls)["total"]
    peak = flops.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (need / peak) / (prefill["total_s"] / prefill["count"])
