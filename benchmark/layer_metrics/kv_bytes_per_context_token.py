"""Per-layer metric ``kv_bytes_per_context_token``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "kv_bytes_per_context_token"
UNIT = "B/token"
BETTER = "lower"
LAYER = "cache allocator (serving/block_manager.py)"
MOVES = "output_tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    """Bytes of cache the allocator has handed out for a token of live
    context: blocks in use of each group of layers
    (``dlti_kv_blocks_in_use{group=}``) x a block's bytes x the layers of
    the group, over ``dlti_kv_context_tokens``, the mean over the window's
    two scrapes. One pool a model with nothing released reads the whole
    model's bytes a token (20,480 here); with the window group released
    behind the window it tends to the full layers' (4,096) plus the window
    layers' few blocks a sequence. None without the gauges (the parent's
    program) or with no live context at either scrape."""
    import window_bytes

    model = ctx["config"]["model"]
    if "layer_types" not in model:
        return None
    full, window, _ = window_bytes.layer_kinds(model)
    block = int(ctx["spec"]["args"]["--block-size"]) \
        * window_bytes.cache_bytes_a_token(model, window_bytes.ITEMSIZE[
            ctx["spec"]["args"]["--kv-cache-dtype"]])
    readings = []
    for scrape in (ctx.get("metrics_before") or {},
                   ctx.get("metrics_after") or {}):
        tokens = scrape.get("dlti_kv_context_tokens")
        in_full = scrape.get('dlti_kv_blocks_in_use{group="full"}')
        if not tokens or in_full is None:
            continue
        in_window = scrape.get('dlti_kv_blocks_in_use{group="window"}', 0.0)
        readings.append(
            block * (full * in_full + window * in_window) / tokens)
    return sum(readings) / len(readings) if readings else None
