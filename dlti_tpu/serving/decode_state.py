"""Device-resident decode state — the serving half of the
host-latency-hiding layer, owned by ``EngineExecutor``.

The scheduler's per-slot mirrors — block tables, slot keys, gen counts,
temperature/top-k/top-p, and one more where the programs take it (adapter
ids or recurrent state slots) — live here as persistent device arrays,
maintained *incrementally*, vLLM-style (Kwon et al., SOSP 2023:
incremental scheduler state is what keeps decode host overhead flat as
batch size grows); a typical step dirties only a handful of slots (an
admission, a retirement, a block-table row growing by one):

* The scheduler marks a slot dirty at admission, release (retire /
  preempt / abort), block-table growth, prefill completion, and when it
  leaves a slot whose request ends with the token in flight out of a round.
  :meth:`sync` then scatters just the dirty rows into the device arrays
  (one fused jitted update, row count padded to a power of two so the
  compile surface stays O(log max_seqs)).
* A **clean step uploads nothing**: every decode dispatch between
  scheduling events reuses the resident arrays as-is (asserted in tier-1:
  ``tests/test_host_overlap.py``).
* Gen counts advance **on device**: after a K-step window the cache bumps
  the resident counts by K (matching the host mirror's per-token append
  for every slot that survived the window; a slot that finished mid-window
  was released, which marks it dirty). No host→device traffic for the one
  mirror that changes every single step.
* **A row is uploaded as of the round being launched, not as of the last
  emission.** The engine's loop launches a one-step round while the round
  before is still in flight (``InferenceEngine.step``): the device has
  then counted a token the host has not seen, and the host's mirror of a
  riding slot's count is one behind the resident one. The mirrors a
  :meth:`sync` is given are the scheduler's view *at the launch* (kept
  tokens plus the round in flight), so that a row dirtied by block growth
  draws its next token with the next count and not the last one again.
* Prefilling slots' block-table rows are masked to the trash block at
  upload time: a decode program can never scribble on KV a
  partially-prefilled slot has written.

The speculative path ships the mirrors whole (it uploads the full token
history anyway); a spec round calls :meth:`mark_all_dirty` so the next
plain dispatch resynchronizes. For every *active* slot the resident rows
equal the scheduler's view at each dispatch (tier-1 holds the outputs to
references that do not share this path, including across preemption and
re-admission, and to the same engine fetching every round before it plans
the next).

Updates deliberately do **not** donate the old arrays: they are KB-scale,
and the previous window's program may still hold them as in-flight
(non-donated) operands.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Mirror names in the decode programs' argument order (after ids/positions).
_FIELDS = ("block_tables", "slot_keys", "gen_counts",
           "temperature", "top_k", "top_p")


class DecodeStateCache:
    """Persistent device twins of the scheduler's per-slot host mirrors."""

    def __init__(self, num_slots: int, device=None, mesh=None,
                 stats: Optional[dict] = None,
                 extra_fields: Sequence[str] = ()):
        # The extra per-slot mirror the executor names rides after the
        # base six, so block_tables stays at index 0 (masked for
        # prefilling rows) and gen_counts at index 2 (bumped on device).
        self._fields = _FIELDS + tuple(extra_fields)
        self._num_slots = num_slots
        self._device = device
        self._mesh = mesh
        self._dev: Optional[Tuple[jax.Array, ...]] = None
        self._dirty: set = set()
        self._all_dirty = True
        # Counters surfaced through the engine's stats dict (and so the
        # /metrics scalar source): upload syncs, rows shipped, clean syncs.
        self.stats = stats if stats is not None else {}
        for k in ("decode_state_uploads", "decode_state_rows",
                  "decode_state_clean_syncs"):
            self.stats.setdefault(k, 0)
        # One jitted updater; XLA specializes per padded row count.
        self._update = jax.jit(self._apply_rows)
        self._bump = jax.jit(lambda cnt, k: cnt + k)

    # ------------------------------------------------------------------
    @staticmethod
    def _apply_rows(dev, idx, rows):
        return tuple(a.at[idx].set(r) for a, r in zip(dev, rows))

    def place(self, x: np.ndarray) -> jax.Array:
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            return jax.device_put(x, NamedSharding(self._mesh, P()))
        if self._device is not None:
            return jax.device_put(x, self._device)
        return jnp.asarray(x)

    # -- dirty tracking (engine-side scheduling events) -----------------
    def mark_dirty(self, slot_id: int) -> None:
        self._dirty.add(slot_id)

    def mark_all_dirty(self) -> None:
        """Resident state is stale wholesale (a spec round ran);
        re-upload everything at the next sync."""
        self._all_dirty = True

    # ------------------------------------------------------------------
    def sync(self, mirrors: Dict[str, np.ndarray],
             masked_rows: Sequence[int] = ()) -> Tuple[jax.Array, ...]:
        """Bring the device arrays up to date with the host ``mirrors``
        and return them in decode-program argument order.

        ``masked_rows``: slot ids whose block-table row must read as the
        trash block (partially-prefilled slots).
        """
        masked = set(masked_rows)
        if self._dev is None or self._all_dirty:
            # Copies: the CPU backend may alias a host array it is given,
            # and the scheduler writes its mirrors while a round that was
            # launched with these arrays is still in flight.
            host = [np.array(mirrors[f]) for f in self._fields]
            if masked:
                host[0][sorted(masked)] = 0
            self._dev = tuple(self.place(h) for h in host)
            self.stats["decode_state_uploads"] += 1
            self.stats["decode_state_rows"] += self._num_slots
            self._all_dirty = False
            self._dirty.clear()
        elif self._dirty:
            idx = sorted(self._dirty)
            n = len(idx)
            npad = 1
            while npad < n:
                npad *= 2
            npad = min(npad, self._num_slots)
            # Pad with a repeat of the first dirty row: duplicate scatter
            # indices carry identical values, so the .set is well-defined.
            idx_arr = np.full((npad,), idx[0], np.int32)
            idx_arr[:n] = idx
            rows: List[np.ndarray] = []
            for f in self._fields:
                r = np.ascontiguousarray(np.asarray(mirrors[f])[idx_arr])
                if f == "block_tables" and masked:
                    for j, sid in enumerate(idx_arr):
                        if int(sid) in masked:
                            r[j] = 0
                rows.append(r)
            self._dev = self._update(self._dev, jnp.asarray(idx_arr),
                                     tuple(jnp.asarray(r) for r in rows))
            self.stats["decode_state_uploads"] += 1
            self.stats["decode_state_rows"] += n
            self._dirty.clear()
        else:
            self.stats["decode_state_clean_syncs"] += 1
        return self._dev

    def warm_row_counts(self, mirrors: Dict[str, np.ndarray],
                        masked_rows: Sequence[int] = ()) -> None:
        """Run the row updater once at every padded count of dirty rows
        (1, 2, 4, ... ``num_slots``), uploading those rows as they stand.
        XLA specializes it per count, and a count first met under traffic
        (nine streams ending and being replaced between two rounds) is a
        compile inside the live decode loop. The upload counters stand
        as they stood: these are no traffic's uploads."""
        slots = self._num_slots
        stats = {k: v for k, v in self.stats.items()
                 if k.startswith("decode_state_")}
        for n in sorted({min(1 << i, slots)
                         for i in range(slots.bit_length() + 1)}):
            self._dirty.update(range(n))
            self.sync(mirrors, masked_rows)
        self.stats.update(stats)

    def bump_gen_counts(self, k: int) -> None:
        """Advance the resident gen counts by ``k`` decode steps — on
        device, mirroring the host appends for every slot that survives
        the window (finished slots were released → marked dirty)."""
        if self._dev is None or k <= 0:
            return
        dev = list(self._dev)
        dev[2] = self._bump(dev[2], np.int32(k))
        self._dev = tuple(dev)
