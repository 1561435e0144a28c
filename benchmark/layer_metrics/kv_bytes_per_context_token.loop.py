"""Per-layer metric ``kv_bytes_per_context_token.loop``: its own small reader.

The harness finds this file by the metric's name in BENCHMARK.json and calls
``read(ctx)``; a reader that finds nothing to read returns None and the metric
is left out of the result line.
"""

NAME = "kv_bytes_per_context_token.loop"
UNIT = "B/token"
BETTER = "lower"
LAYER = "cache allocator (serving/block_manager.py)"
MOVES = "output_tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    """Bytes of cache the allocator has handed out for a token of live
    context, for a configuration whose layers run several times: blocks in
    use (``dlti_kv_blocks_in_use{group="full"}``) x a block's bytes over
    every (pass, layer) entry (``--block-size`` x
    ``loop_work.cache_bytes_a_token``) over ``dlti_kv_context_tokens``, the
    mean over the window's two scrapes: 1,572,864 here plus the rounding of
    each sequence's last block. None without ``total_ut_steps``, the gauge
    of the cache's entries (the parent's program has neither), or with no
    live context at either scrape."""
    import loop_work

    model = ctx["config"]["model"]
    if "total_ut_steps" not in model:
        return None
    block = int(ctx["spec"]["args"]["--block-size"]) \
        * loop_work.cache_bytes_a_token(model, loop_work.ITEMSIZE[
            ctx["spec"]["args"]["--kv-cache-dtype"]])
    readings = []
    for scrape in (ctx.get("metrics_before") or {},
                   ctx.get("metrics_after") or {}):
        tokens = scrape.get("dlti_kv_context_tokens")
        in_use = scrape.get('dlti_kv_blocks_in_use{group="full"}')
        entries = scrape.get("dlti_kv_cache_entries")
        if not tokens or in_use is None \
                or entries != loop_work.cache_entries(model):
            continue
        readings.append(block * in_use / tokens)
    return sum(readings) / len(readings) if readings else None
