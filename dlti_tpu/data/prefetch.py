"""Bounded background batch prefetcher — the training half of the
host-latency-hiding layer.

``steps_per_sync`` amortizes *dispatch*, but the host work between
compiled windows — batch
gather/pack/stack in ``TokenBatchDataset._gather`` plus the host→device
transfer — still sat on the critical path: the device idles while Python
stacks numpy rows. This module runs that work on a background thread,
double-buffered (depth ``Config.data.prefetch_depth``, default 2), and
optionally issues ``jax.device_put`` with the step's input sharding ahead of
need, so by the time the step thread asks for batch N+1 it is already
device-resident. The canonical design is tf.data's bounded prefetch queue
(Murray et al., VLDB 2021); this is the in-tree, schedule-preserving
equivalent.

Guarantees, in priority order:

1. **Identical batch order.** One worker thread consumes the source
   iterator sequentially into a FIFO queue — the step thread sees exactly
   the sequence it would have seen calling ``next()`` itself, so the loss
   trajectory is bit-identical with prefetch on or off (equivalence-tested
   in ``tests/test_host_overlap.py``).
2. **Bounded memory.** At most ``depth`` batches (plus the one in flight)
   are ever materialized ahead of the consumer.
3. **Preemption-safe shutdown.** :meth:`close` unblocks a worker stuck on
   a full queue, joins it, and is idempotent — the Trainer calls it on
   SIGTERM/``request_stop`` paths and at epoch end, so no daemon thread
   outlives the loop holding dataset references.
4. **Exception transparency.** A source-iterator failure re-raises on the
   consumer thread at the ``next()`` that would have produced the batch.

Telemetry: a queue-depth gauge and a per-fetch stall-time histogram
(names pinned in ``tests/test_bench_contract.py``), ``train/prefetch``
spans from the worker thread, and a raw ``stats`` dict for benchmarks.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterable, Iterator, Optional, Tuple

from dlti_tpu.telemetry.registry import Gauge, Histogram

# Host-path latencies: stalls are ideally ~0 (buffer hit) and otherwise the
# gather/pack cost — microseconds to tens of milliseconds.
PREFETCH_STALL_BUCKETS = (
    1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3, 5e-3,
    1e-2, 2.5e-2, 5e-2, 0.1, 0.25, 0.5, 1.0, 2.5,
)

# Exposition-name contract (scraped/pinned like the dlti_<stat> names).
PREFETCH_METRIC_NAMES = (
    "dlti_train_prefetch_queue_depth",
    "dlti_train_prefetch_stall_seconds",
)

_OK, _ERR, _END = 0, 1, 2


class HostPrefetcher:
    """Iterate ``source`` on a background thread through a bounded queue.

    Yields ``(host_batch, placed_batch)`` pairs: ``host_batch`` is the
    source item untouched (the Trainer's recorder and window-stacking
    paths need host numpy), ``placed_batch`` is ``place_fn(host_batch)``
    when a placement function is given (typically ``jax.device_put`` with
    the step's input sharding — an *async* dispatch, so the transfer
    overlaps the in-flight step) and the same object otherwise.
    """

    def __init__(
        self,
        source: Iterable,
        depth: int = 2,
        place_fn: Optional[Callable] = None,
        tracer=None,
        span_name: str = "train/prefetch",
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._source = source
        self._place = place_fn
        self._span_name = span_name
        if tracer is None:
            from dlti_tpu.telemetry.tracer import get_tracer

            tracer = get_tracer()
        self._tracer = tracer
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._done = False
        self.queue_depth = Gauge(
            PREFETCH_METRIC_NAMES[0],
            help="batches buffered ahead of the training step thread")
        self.stall_time = Histogram(
            PREFETCH_METRIC_NAMES[1], PREFETCH_STALL_BUCKETS,
            help="time the step thread blocked waiting for the next batch",
            stats_key="train_prefetch_stall_seconds")
        # Raw counters for benchmarks (benchmarks_dev/host_overlap.py).
        self.stats = {"fetches": 0, "stalls": 0, "stall_time_s": 0.0}
        self._thread = threading.Thread(
            target=self._worker, name="dlti-prefetch", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------
    def _put(self, item) -> bool:
        """Queue ``item``, yielding to :meth:`close` every 50 ms."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self) -> None:
        try:
            it = iter(self._source)
            while not self._stop.is_set():
                with self._tracer.span(self._span_name, cat="train"):
                    try:
                        batch = next(it)
                    except StopIteration:
                        break
                    placed = self._place(batch) if self._place is not None \
                        else batch
                if not self._put((_OK, (batch, placed))):
                    return  # closed while blocked on a full queue
                self.queue_depth.set(self._q.qsize())
        except BaseException as e:  # noqa: BLE001 — re-raised on consumer
            self._put((_ERR, e))
            return
        self._put((_END, None))

    # ------------------------------------------------------------------
    def __iter__(self) -> Iterator[Tuple]:
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        t0 = time.perf_counter()
        tag, payload = self._q.get()
        stall = time.perf_counter() - t0
        self.queue_depth.set(self._q.qsize())
        if tag == _END:
            self._done = True
            self._thread.join(timeout=5.0)
            raise StopIteration
        if tag == _ERR:
            self._done = True
            raise payload
        # Stall accounting covers real batches only (the end-of-epoch
        # sentinel wait is not an input stall).
        self.stall_time.observe(stall)
        self.stats["fetches"] += 1
        self.stats["stall_time_s"] += stall
        if stall > 1e-4:  # below this the buffer effectively had it ready
            self.stats["stalls"] += 1
        return payload

    def buffered_batches(self) -> list:
        """The *placed* batches currently buffered ahead of the step
        thread — the memory ledger's ``prefetch_buffers`` owner handle
        (device bytes only exist where place_fn issued a device_put; a
        host-only buffer contributes nothing and that is correct).
        Racy-by-design read of the queue's internal deque: the ledger
        snapshot tolerates a batch popping mid-walk (deleted arrays are
        skipped), and no lock is worth taking on the step thread's hot
        producer/consumer path."""
        try:
            return [payload[1] for tag, payload in list(self._q.queue)
                    if tag == _OK]
        except Exception:
            return []

    def close(self) -> None:
        """Stop the worker and drop buffered batches. Idempotent; safe to
        call with the worker blocked on a full queue (preemption path)."""
        self._done = True
        self._stop.set()
        # Drain so a worker blocked in put() can observe the stop event.
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)
        self.queue_depth.set(0)
