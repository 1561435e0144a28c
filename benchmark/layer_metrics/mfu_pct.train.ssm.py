"""Per-layer metric ``mfu_pct.train.ssm``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "mfu_pct.train.ssm"
UNIT = "%"
BETTER = "higher"
LAYER = "model (models/jamba.py, models/mamba1.py)"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(ctx):
    """The jamba family's share of the chip's bf16 peak over a whole train
    step: operations the forward and backward passes of a LoRA step need a
    token (``jamba_work.train_flops_per_token``: 4 a matmul parameter, 6 an
    adapter parameter, attention in the attention layers alone by the band
    the packed documents leave; no recomputation) x tokens/s/chip over the
    peak. The scan's element-by-element work is the vector unit's, not the
    matrix units', and is left out: the time it takes lowers this share.
    None for another family or on the CPU."""
    import flops
    import jamba_work

    if ctx["device"]["platform"] == "cpu" \
            or not jamba_work.is_family(ctx["config"]):
        return None
    lengths = jamba_work.document_lengths(ctx["texts"], ctx["seq_len"])
    per_token = jamba_work.train_flops_per_token(
        ctx["config"], int(ctx["spec"]["check"]["lora_r"]),
        flops.mean_keys_seen(lengths, None))
    peak = flops.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_token \
        * ctx["values"]["train_tokens_per_s_per_chip"] / peak
