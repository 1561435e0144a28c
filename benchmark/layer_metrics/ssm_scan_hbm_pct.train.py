"""Per-layer metric ``ssm_scan_hbm_pct.train``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "ssm_scan_hbm_pct.train"
UNIT = "%"
BETTER = "higher"
LAYER = "model (models/jamba.py, models/mamba1.py)"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(ctx):
    """The selective scan's share of its roofline in a train step: time the
    chip's memory would need to move what the algorithm must move once
    forward and once backward (``jamba_work.scan_bytes_per_step``: u', Dt,
    z, B, C in and y out, then those and dy in and their gradients out, in
    float32, over the step's real tokens and the Mamba layers; kept and
    recomputed states are the implementation's and are not counted) over
    the device time under the scan's scopes
    (``ssm_scan_device_ms_per_step.train``). The same count whatever
    implements the scan; it reads low where the vector unit and not the
    memory sets the pace. None for another family, without the scope, or on
    the CPU."""
    import flops
    import jamba_work
    import scope_time

    if ctx["device"]["platform"] == "cpu" \
            or not jamba_work.is_family(ctx["config"]):
        return None
    per_call_s = scope_time.scope_s_per_call(ctx, "train_step",
                                             "dlti_selective_scan")
    if not per_call_s:
        return None
    need = jamba_work.scan_bytes_per_step(ctx["config"],
                                          ctx["tokens_per_step"])
    peak = flops.peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / per_call_s
