"""Per-layer metric ``mfu_pct.train``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "mfu_pct.train"
UNIT = "%"
BETTER = "higher"
LAYER = "model (models/llama.py, models/lora.py)"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(ctx):
    """Operations the forward and backward passes need per token (flops.py:
    matmul parameters only, 4N for LoRA and 6N for a full fine-tune,
    attention by the band the packed documents and the window leave, no
    recomputation) x tokens/s/chip over the chip's published bf16 peak."""
    import flops

    if ctx["device"]["platform"] == "cpu":
        return None  # a rehearsal: no peak to hold a CPU against
    model = ctx["config"]["model"]
    lengths = [min(len(t.encode("utf-8")) + 2, ctx["seq_len"])
               for t in ctx["texts"]]
    window = model.get("sliding_window") \
        if model.get("use_sliding_window", True) else None
    per_token = flops.train_flops_per_token(
        model, int(ctx["spec"]["check"]["lora_r"]),
        flops.mean_keys_seen(lengths, window))
    peak = flops.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * ctx["values"]["train_tokens_per_s_per_chip"] / peak
