"""Input pipeline: tokenize → truncate/pack → per-host sharded batches.

Reference contract: tokenize with truncation to ``max_length=512``, no
padding at map time (``train_baseline.py:152-165``), dynamic padding in the
collator with labels = input_ids (``train_baseline.py:195-198``). Here the
collator is replaced by static-shape batches (XLA needs static shapes):
right-padding to ``max_seq_len`` with a loss mask, or optional sequence
*packing* (multiple documents per row + segment ids) which the reference
lacks and which removes pad waste — the single biggest input-side perf lever
on TPU.

Multi-host: each host materializes only its slice of every global batch
(``shard_by_host``), indexed by ``jax.process_index()`` — the analog of
the per-rank ``DistributedSampler`` HF Trainer gives the reference
implicitly — while the *schedule* (which rows feed which optimizer step)
stays a pure function of (corpus, seed, global batch shape), independent
of world size, so an elastic mesh reshape preserves it exactly.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence

import numpy as np

from dlti_tpu.data.tokenizer import Tokenizer


def tokenize_and_truncate(
    texts: Sequence[str],
    tokenizer: Tokenizer,
    max_seq_len: int = 512,
    add_eos: bool = True,
) -> List[List[int]]:
    """Tokenize each text, truncating to ``max_seq_len`` (reference:
    ``truncation=True, max_length=512`` — ``train_baseline.py:155``)."""
    out = []
    for t in texts:
        ids = tokenizer.encode(t, add_bos=True, add_eos=add_eos)
        out.append(ids[:max_seq_len])
    return out


def pad_to_batch(
    seqs: List[List[int]], seq_len: int, pad_id: int
) -> tuple:
    """Right-pad to (len(seqs), seq_len); loss_mask 1 on real tokens."""
    n = len(seqs)
    ids = np.full((n, seq_len), pad_id, dtype=np.int32)
    mask = np.zeros((n, seq_len), dtype=np.int32)
    for i, s in enumerate(seqs):
        L = min(len(s), seq_len)
        ids[i, :L] = s[:L]
        mask[i, :L] = 1
    return ids, mask


def pack_sequences(
    seqs: List[List[int]], seq_len: int, pad_id: int, open_rows: int = 64
) -> tuple:
    """Greedy windowed first-fit packing: (ids, loss_mask, segment_ids).

    segment_ids are 1-based per document, 0 on padding — consumed by the
    attention segment mask so packed documents cannot attend across
    boundaries.

    Only the last ``open_rows`` rows are candidates for placement, keeping
    packing O(docs * open_rows) instead of O(docs * rows) — at corpus scale
    (the reference dataset is 136k docs, train.ipynb:50) unbounded first-fit
    is billions of Python iterations. The assignment loop runs in C++
    (``native/packer.cc``, built on first use) with a vectorized numpy
    scatter; the pure-Python path below is the fallback and oracle.
    """
    from dlti_tpu.utils.native import load_native_runtime

    # Zero-length docs pack to nothing; dropping them up front keeps the
    # native and Python paths identical (and the Python path from indexing
    # an empty row's segment list).
    seqs = [s for s in seqs if s]

    native = load_native_runtime()
    if native is not None and seqs:
        return _pack_sequences_native(native, seqs, seq_len, pad_id, open_rows)

    rows: List[List[int]] = []
    row_segs: List[List[int]] = []
    open_idx: List[int] = []  # indices of still-open rows, oldest first
    for s in seqs:
        s = s[:seq_len]
        placed = False
        for oi, i in enumerate(open_idx):
            if len(rows[i]) + len(s) <= seq_len:
                seg_id = row_segs[i][-1] + 1
                rows[i].extend(s)
                row_segs[i].extend([seg_id] * len(s))
                if len(rows[i]) == seq_len:
                    open_idx.pop(oi)
                placed = True
                break
        if not placed:
            rows.append(list(s))
            row_segs.append([1] * len(s))
            open_idx.append(len(rows) - 1)
            if len(open_idx) > open_rows:
                open_idx.pop(0)
    n = len(rows)
    ids = np.full((n, seq_len), pad_id, dtype=np.int32)
    segs = np.zeros((n, seq_len), dtype=np.int32)
    for i, (row, seg) in enumerate(zip(rows, row_segs)):
        ids[i, : len(row)] = row
        segs[i, : len(seg)] = seg
    mask = (segs > 0).astype(np.int32)
    return ids, mask, segs


def _pack_sequences_native(native, seqs, seq_len: int, pad_id: int,
                           open_rows: int) -> tuple:
    """C++ assignment + vectorized token scatter (same outputs as the
    Python path, bit for bit)."""
    import ctypes

    n = len(seqs)
    lens = np.array([min(len(s), seq_len) for s in seqs], np.int64)
    out_row = np.empty(n, np.int32)
    out_col = np.empty(n, np.int32)
    out_seg = np.empty(n, np.int32)
    n_rows = native.dlti_pack_assign(
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        np.int32(n), np.int32(seq_len), np.int32(open_rows),
        out_row.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out_col.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        out_seg.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )

    total = int(lens.sum())
    # Measured at 50k docs: fromiter over one flat generator beats
    # per-doc np.asarray + np.concatenate ~2x (50k tiny array
    # constructions dominate the latter).
    tokens = np.fromiter(
        (t for s in seqs for t in (s if len(s) <= seq_len else s[:seq_len])),
        np.int32, count=total) if total else np.empty(0, np.int32)
    # Flat destination index of every token: row*seq_len + col + offset.
    starts = out_row.astype(np.int64) * seq_len + out_col
    flat_pos = np.repeat(starts, lens) + _ranges(lens)

    ids = np.full(n_rows * seq_len, pad_id, np.int32)
    segs = np.zeros(n_rows * seq_len, np.int32)
    ids[flat_pos] = tokens
    segs[flat_pos] = np.repeat(out_seg, lens)
    ids = ids.reshape(n_rows, seq_len)
    segs = segs.reshape(n_rows, seq_len)
    return ids, (segs > 0).astype(np.int32), segs


def _ranges(lens: np.ndarray) -> np.ndarray:
    """[0..l0), [0..l1), ... concatenated (vectorized arange per doc)."""
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, np.int64)
    idx = np.arange(total, dtype=np.int64)
    doc_start = np.repeat(np.cumsum(lens) - lens, lens)
    return idx - doc_start


def packed_loss_mask(segment_ids: np.ndarray) -> np.ndarray:
    """Loss mask for packed rows: target position p is valid iff it belongs
    to a document (seg > 0) and its predicting position p-1 is in the *same*
    document — the boundary token of doc k must not be trained to predict
    doc k+1's first token."""
    mask = np.zeros_like(segment_ids)
    mask[:, 1:] = (segment_ids[:, 1:] > 0) & (
        segment_ids[:, 1:] == segment_ids[:, :-1]
    )
    return mask.astype(np.int32)


def packed_positions(segment_ids: np.ndarray) -> np.ndarray:
    """Per-document positions (RoPE restarts at 0 for each packed doc).

    Vectorized: position = index - index_of_current_doc_start.
    """
    n, L = segment_ids.shape
    idx = np.broadcast_to(np.arange(L, dtype=np.int32), (n, L))
    is_start = np.ones((n, L), dtype=bool)
    is_start[:, 1:] = (segment_ids[:, 1:] != segment_ids[:, :-1]) | (
        segment_ids[:, 1:] == 0
    )
    start_idx = np.where(is_start, idx, 0)
    start_idx = np.maximum.accumulate(start_idx, axis=1)
    return (idx - start_idx).astype(np.int32)


class HostShardedSchedule:
    """World-size-invariant global schedule + seeded epoch shuffle +
    ``skip_steps`` resume, with per-host materialization.

    Shared by :class:`TokenBatchDataset` and
    :class:`~dlti_tpu.data.streaming.StreamingTokenDataset` so the row
    *schedule* (epoch permutation, per-step chunking, resume skip) cannot
    desynchronize between the in-memory and disk-backed paths. Note the
    shared piece is the schedule over rows, not row construction: in packed
    mode the two paths build rows from different document orders
    (TokenBatchDataset pre-shuffles the corpus before packing; the store
    writer packs in arrival order), so a packed checkpoint resumes
    byte-identically only against the same dataset kind it was trained
    with. Unpacked rows are identical either way.

    The schedule is a pure function of (corpus, seed, global batch shape)
    and NOT of the world size: one seeded *global* permutation, chunked
    ``samples_per_step`` rows per optimizer step; host p then materializes
    only its 1/process_count batch-column slice of each chunk. That
    invariance is what lets elastic training reshape the mesh to a
    surviving world and resume the exact batch schedule (with
    :func:`~dlti_tpu.training.elastic.rescale_batch_schedule` trading
    batch rows for grad-accum steps) — under the pre-r06 contiguous
    range-split, a shrunk world would have silently fed different rows
    per step.

    Subclasses call :meth:`_init_procs` early (fail fast, before any
    expensive row construction), then :meth:`_init_host_shard` with their
    row count, and implement
    ``_gather(row_indices) -> {field: (n, seq_len) array}``.
    """

    def _init_procs(self, shard_by_host: bool) -> None:
        import jax

        self._procs = jax.process_count() if shard_by_host else 1
        self._proc_id = jax.process_index() if shard_by_host else 0
        if self.micro_batch_size % self._procs != 0:
            raise ValueError(
                f"global micro_batch_size {self.micro_batch_size} must be "
                f"divisible by process_count {self._procs}"
            )

    def _init_host_shard(self, n_rows: int, shard_by_host: bool) -> None:
        if not hasattr(self, "_procs"):
            self._init_procs(shard_by_host)
        self._n_rows = n_rows

    @property
    def samples_per_step(self) -> int:
        """Global samples consumed per optimizer step."""
        return self.micro_batch_size * self.grad_accum_steps

    def steps_per_epoch(self) -> int:
        # Global chunking: every host agrees by construction (a ragged
        # split would deadlock collectives on the last step), at any
        # world size.
        if getattr(self, "drop_remainder", True):
            return self._n_rows // self.samples_per_step
        return -(-self._n_rows // self.samples_per_step)

    def _pad_partial(self, fields: dict, present: np.ndarray) -> dict:
        """Pad a partial final step to the static step shape: pad rows are
        all ``pad_id`` tokens with an all-zero loss mask (and zero
        segment ids / positions), so they contribute nothing to the loss
        or gradients while keeping every compiled shape identical. Pad
        positions are fixed in GLOBAL batch coordinates, so the padded
        step is world-size invariant too."""
        out = {}
        n = present.shape[0]
        for k, v in fields.items():
            fill = self.pad_id if k == "input_ids" else 0
            full = np.full((n,) + v.shape[1:], fill, v.dtype)
            full[present] = v
            out[k] = full
        return out

    def epoch(self, epoch_idx: int = 0, skip_steps: int = 0) -> Iterator[dict]:
        order = np.arange(self._n_rows)
        if self.shuffle_seed is not None:
            # One GLOBAL permutation, identical on every host.
            rng = np.random.default_rng(self.shuffle_seed + epoch_idx)
            rng.shuffle(order)
        S = self.samples_per_step
        bs = self.micro_batch_size
        bs_local = bs // self._procs
        shape = (self.grad_accum_steps, bs_local, self.seq_len)
        drop = getattr(self, "drop_remainder", True)
        # This host's positions within a step's global chunk: local batch
        # element (a, b) is global chunk row a*bs + proc_id*bs_local + b —
        # the slice make_global_batch reassembles along the batch dim.
        g_idx = (np.arange(self.grad_accum_steps)[:, None] * bs
                 + self._proc_id * bs_local
                 + np.arange(bs_local)[None, :]).ravel()
        for step_i, start in enumerate(range(0, self._n_rows, S)):
            chunk = order[start:start + S]
            if len(chunk) < S and drop:
                break  # legacy behavior: the ragged tail is dropped
            if step_i < skip_steps:
                continue
            present = g_idx < len(chunk)
            fields = self._gather(chunk[g_idx[present]])
            if not present.all():
                fields = self._pad_partial(fields, present)
            yield {k: v.reshape(shape) for k, v in fields.items()}


@dataclasses.dataclass
class TokenBatchDataset(HostShardedSchedule):
    """In-memory tokenized dataset yielding train-step-shaped batches.

    Yields dicts with ``input_ids`` / ``loss_mask`` (and, when packing,
    ``segment_ids`` / ``positions``) shaped (accum, micro_bs, seq_len) —
    exactly what :func:`dlti_tpu.training.make_train_step` consumes.

    ``micro_batch_size`` is the *global* (all-hosts, all-devices) microbatch;
    each host materializes 1/process_count of it when ``shard_by_host``.

    ``drop_remainder=False`` keeps the final partial step of each epoch by
    padding it to the full static step shape with all-pad rows (loss mask
    zero — no loss/grad contribution); the default drops it, matching the
    reference's drop_last semantics.
    """

    sequences: List[List[int]]
    seq_len: int
    pad_id: int
    micro_batch_size: int
    grad_accum_steps: int = 1
    shuffle_seed: Optional[int] = 0
    shard_by_host: bool = True
    drop_remainder: bool = True
    pack: bool = False

    def __post_init__(self) -> None:
        self._init_procs(self.shard_by_host)  # validate before packing
        if self.pack:
            # Pack once over the (seed-shuffled) corpus; epochs reshuffle rows.
            order = np.arange(len(self.sequences))
            if self.shuffle_seed is not None:
                np.random.default_rng(self.shuffle_seed).shuffle(order)
            ids, mask, segs = pack_sequences(
                [self.sequences[j] for j in order], self.seq_len, self.pad_id
            )
            self._packed = (ids, packed_loss_mask(segs), segs, packed_positions(segs))
            n_rows = ids.shape[0]
        else:
            self._packed = None
            n_rows = len(self.sequences)
        self._init_host_shard(n_rows, self.shard_by_host)

    def _row(self, j: int) -> tuple:
        if self._packed is not None:
            ids, mask, segs, pos = self._packed
            return ids[j], mask[j], segs[j], pos[j]
        s = self.sequences[j]
        ids, mask = pad_to_batch([s], self.seq_len, self.pad_id)
        return ids[0], mask[0], None, None

    def _gather(self, row_indices: np.ndarray) -> dict:
        rows = [self._row(j) for j in row_indices]
        fields = {
            "input_ids": np.stack([r[0] for r in rows]),
            "loss_mask": np.stack([r[1] for r in rows]),
        }
        if self._packed is not None:
            fields["segment_ids"] = np.stack([r[2] for r in rows])
            fields["positions"] = np.stack([r[3] for r in rows])
        return fields


def make_batches(
    texts: Sequence[str],
    tokenizer: Tokenizer,
    seq_len: int = 512,
    micro_batch_size: int = 1,
    grad_accum_steps: int = 1,
    shuffle_seed: Optional[int] = 0,
    shard_by_host: bool = True,
    pack: bool = False,
) -> TokenBatchDataset:
    seqs = tokenize_and_truncate(texts, tokenizer, seq_len)
    return TokenBatchDataset(
        sequences=seqs,
        seq_len=seq_len,
        pad_id=tokenizer.pad_id,
        micro_batch_size=micro_batch_size,
        grad_accum_steps=grad_accum_steps,
        shuffle_seed=shuffle_seed,
        shard_by_host=shard_by_host,
        pack=pack,
    )
