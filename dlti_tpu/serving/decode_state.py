"""A plain decode round's host inputs as ONE buffer — the serving half of
the host-latency-hiding layer, owned by ``EngineExecutor``.

Everything a decode program takes per slot is small: the round's token and
position, the block-table row, the sampling key and count, temperature /
top-k / top-p, and one more where the programs take it (adapter ids or
recurrent state slots): 32 x (256 to 544 + 9) x 4 B = 34-71 KB a round
whole. What costs is the *number* of trips out of the interpreter: each
upload and each program call gives up the interpreter lock, and the stepper
then queues for it behind every streaming handler. So a round is packed
here into one ``(max_seqs, width)`` int32 array (:meth:`RoundPacking.pack`,
numpy, built fresh each round from the scheduler's mirrors as of the
round's launch), goes up as one transfer, and ``decode`` slices and
bitcasts it back as its first lines (:meth:`RoundPacking.unpack`, traced). uint32 keys and float32 values travel by their bits: nothing is
rounded, and a seeded request draws what it always drew.

Nothing per-slot stays resident on the device between rounds, so there is
nothing to mark stale, no row updater, no program a count of dirty rows,
and no count to advance on the device: the counts a round draws with are
the mirrors' (plus one for a row that rides behind the round in flight,
whose token the host has not seen: the engine adds it before it packs).
Slots still prefilling have their block-table row packed as the trash
block: a decode program can never scribble on KV a partially-prefilled
slot has written.

The speculative path ships its mirrors as separate arrays (it uploads the
full token history anyway; ``EngineExecutor.stage_spec``).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# The packed round's columns, in the decode programs' argument order:
# (name, columns (None: max_blocks), dtype, one value a slot -> (S,)).
_COLUMNS = (("input_ids", 1, np.int32, False),
            ("positions", 1, np.int32, False),
            ("block_tables", None, np.int32, False),
            ("slot_keys", 2, np.uint32, False),
            ("gen_counts", 1, np.int32, True),
            ("temperature", 1, np.float32, True),
            ("top_k", 1, np.int32, True),
            ("top_p", 1, np.float32, True))


class RoundPacking:
    """The layout of a packed decode round: ``[ids | positions |
    block_tables (max_blocks) | slot_keys (2) | gen_counts | temperature |
    top_k | top_p | extra]``, one row a slot, int32 by bits."""

    def __init__(self, num_slots: int, max_blocks: int,
                 extra_field: Optional[str] = None, window_blocks: int = 0):
        """``window_blocks`` > 0 (a model with a window group of layers):
        that group's table, as wide as a slot's live blocks need, and the
        token its first column starts at ride after ``top_p``:
        ``window_tables (window_blocks) | window_base``."""
        self.num_slots = num_slots
        self.extra_field = extra_field
        self.window_blocks = window_blocks
        cols = _COLUMNS
        if window_blocks:
            cols += (("window_tables", window_blocks, np.int32, False),
                     ("window_base", 1, np.int32, True))
        if extra_field is not None:
            cols += ((extra_field, 1, np.int32, True),)
        # name -> (first column, columns, dtype, one value a slot)
        self.columns: Dict[str, Tuple[int, int, type, bool]] = {}
        at = 0
        for name, n, dt, scalar in cols:
            n = max_blocks if n is None else n
            self.columns[name] = (at, n, dt, scalar)
            at += n
        self.width = at

    def pack(self, input_ids: np.ndarray, positions: np.ndarray,
             mirrors: Dict[str, np.ndarray],
             masked_rows: Sequence[int] = ()) -> np.ndarray:
        """The round as one fresh ``(num_slots, width)`` int32 array (a copy:
        the scheduler writes its mirrors while the round is in flight).
        ``masked_rows``: slots whose block-table row must read as the trash
        block (still prefilling)."""
        out = np.empty((self.num_slots, self.width), np.int32)
        given = dict(mirrors, input_ids=input_ids, positions=positions)
        for name, (at, n, dt, _) in self.columns.items():
            src = np.asarray(given[name])
            if src.dtype != dt:
                raise TypeError(f"{name} is {src.dtype}, packed as {dt}")
            out[:, at:at + n] = src.view(np.int32).reshape(self.num_slots, n)
        if len(masked_rows):
            for name in ("block_tables", "window_tables", "window_base"):
                if name in self.columns:
                    at, n, _, _ = self.columns[name]
                    out[list(masked_rows), at:at + n] = 0
        return out

    def unpack(self, packed: jax.Array) -> tuple:
        """``packed`` back in the decode programs' argument order:
        ``(input_ids (S, 1), positions (S, 1), block_tables, slot_keys,
        gen_counts, temperature, top_k, top_p[, extra])``. Traced: the
        programs' first lines. With a window group ``block_tables`` is the
        tuple ``ops.kv_cache.bind_call`` takes: a dict a group."""
        out = {}
        for name, (at, n, dt, scalar) in self.columns.items():
            x = packed[:, at] if scalar else packed[:, at:at + n]
            if dt is not np.int32:
                x = jax.lax.bitcast_convert_type(x, jnp.dtype(dt))
            out[name] = x
        if self.window_blocks:
            out["block_tables"] = (
                {"block_tables": out["block_tables"]},
                {"block_tables": out.pop("window_tables"),
                 "table_base": out.pop("window_base")})
        return tuple(out.values())
