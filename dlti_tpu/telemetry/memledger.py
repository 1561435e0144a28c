"""HBM memory ledger: per-owner device-memory attribution with
conservation by construction.

The goodput ledger (``ledger.py``) answers "where did the time go"; this
module answers the second axis of the paper's experiment grid, "where
does the memory live". The reference repo's whole ZeRO-1/2/3 comparison
is a *memory* story — peak device bytes per sharding strategy — yet a
single ``peak_bytes_in_use`` scalar cannot say whether the bytes are
parameters, optimizer state, KV blocks, or a leak.

**Model.** Subsystems register named *owners* (``params``,
``optimizer_state``, ``kv_block_pool``, ``prefix_cache_hbm``,
``prefetch_buffers``, ...) with their
pytree/array handles (or a zero-arg callable returning one, for handles
that are swapped out across steps). A :meth:`MemoryLedger.snapshot` sums
per-device ``nbytes`` over each owner's live arrays, reconciles against
``jax.live_arrays()`` (device arrays nobody claimed → ``untracked``) and
``device.memory_stats()`` (allocator overhead beyond array payloads →
``residual``), and emits a bucket map whose values **sum to
bytes-in-use exactly, by construction** — the same conservation property
the goodput ledger pins for seconds, here pinned for bytes
(``tests/test_memledger.py``). Compiled-executable ``memory_analysis()``
(temp/argument/output bytes) folds in as the activation-peak estimate —
the transient bytes a snapshot between steps can never see.

**CPU determinism.** The CPU backend exposes no ``memory_stats()``; the
ledger then takes bytes-in-use := live-array bytes (``source:
"live_arrays"``, residual 0) and capacity from the configured budget, so
conservation, headroom admission and the squeeze-chaos drill all run
deterministically under ``JAX_PLATFORMS=cpu`` tier-1 tests.

**Consumers.** The trainer and serving engine each hold one ledger and
feed: ``dlti_hbm_bytes{owner=}`` / ``dlti_hbm_{peak,headroom,untracked}_
bytes`` on /metrics, ``hbm_*`` series on /debug/vars + /dashboard,
``GET /debug/memory`` (full per-owner per-device map + top-K live
arrays), ``memory.json`` in every flight dump (OOM forensics — rendered
by ``scripts/postmortem.py`` as "where the memory went"), the watchdog's
``hbm_pressure`` rule, and the engine's headroom-aware admission (defer,
don't fault). :class:`MemoryBalloon` is the chaos ``hbm-squeeze``
injector that proves the defer path without a real OOM.

Cost contract (same as the goodput ledger): a *disabled* ledger's
``snapshot()``/``scalars()``/``headroom_bytes()`` are one attribute read
+ early return. Metric names are a scrape contract (pinned in
``tests/test_bench_contract.py``).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from dlti_tpu.telemetry.registry import Gauge

# Canonical owner names (a label catalog, not a closed set — any snake_case
# owner registers fine; these are the ones the Trainer and engine wire up
# and postmortem/dashboards know how to read).
MEMORY_OWNERS = (
    "params",
    "optimizer_state",
    "grad_buffers",
    "kv_block_pool",
    "prefix_cache_hbm",
    "prefetch_buffers",
    "kv_handoff_staging",  # disagg: host-staged prefill→decode KV payloads
    "lora_adapters",      # multi-LoRA serving: the stacked A/B adapter pool
    "chaos_balloon",      # the hbm-squeeze injector, visible by design
)

# Reconciliation buckets appended after the owners; owners + these sum to
# bytes-in-use exactly (see snapshot()).
UNTRACKED_BUCKET = "untracked"    # live device arrays nobody registered
RESIDUAL_BUCKET = "residual"      # allocator bytes beyond array payloads

# Name-stability contracts (pinned in tests/test_bench_contract.py).
MEMLEDGER_METRIC_NAMES = (
    "dlti_hbm_bytes",             # per-owner gauge (owner label)
    "dlti_hbm_peak_bytes",
    "dlti_hbm_headroom_bytes",
    "dlti_hbm_untracked_bytes",
)

# Module-level metrics (the goodput-ledger pattern: the trainer / engine
# sampler refreshes them, the server registry registers them for
# /metrics).
hbm_bytes_gauge = Gauge(
    MEMLEDGER_METRIC_NAMES[0],
    help="device bytes attributed per registered owner (owner label)")
hbm_peak_gauge = Gauge(
    MEMLEDGER_METRIC_NAMES[1],
    help="peak observed device bytes in use")
hbm_headroom_gauge = Gauge(
    MEMLEDGER_METRIC_NAMES[2],
    help="capacity minus bytes in use (0 when capacity unknown)")
hbm_untracked_gauge = Gauge(
    MEMLEDGER_METRIC_NAMES[3],
    help="live device bytes owned by no registered owner")
# What the trainer spends its headroom on (training.remat_plan).
remat_kept_blocks_gauge = Gauge(
    "dlti_remat_kept_blocks",
    help="blocks whose activations the train step keeps (the rest remat)")


# ----------------------------------------------------------------------
# Free helpers (usable without a ledger)
# ----------------------------------------------------------------------

def _is_jax_array(x: Any) -> bool:
    # Committed device arrays only: numpy leaves and python scalars in a
    # pytree hold host memory, not HBM.
    return hasattr(x, "nbytes") and hasattr(x, "addressable_shards") \
        and hasattr(x, "is_deleted")


def _device_key(dev: Any) -> str:
    return f"{getattr(dev, 'platform', 'dev')}:{getattr(dev, 'id', 0)}"


def _array_per_device(arr: Any) -> Dict[str, int]:
    """Per-device payload bytes of one array, summing shard ``nbytes``
    (a sharded array holds only its shard bytes on each device)."""
    out: Dict[str, int] = {}
    try:
        shards = arr.addressable_shards
    except Exception:
        shards = []
    if shards:
        for sh in shards:
            try:
                key = _device_key(sh.device)
                out[key] = out.get(key, 0) + int(sh.data.nbytes)
            except Exception:
                continue
        if out:
            return out
    try:  # unsharded / fallback: whole payload on the array's device
        devs = list(getattr(arr, "devices", lambda: [])()) or [None]
        key = _device_key(devs[0]) if devs[0] is not None else "dev:0"
        out[key] = int(arr.nbytes)
    except Exception:
        pass
    return out


def tree_nbytes(tree: Any) -> int:
    """Total device payload bytes of every live jax array in a pytree."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if _is_jax_array(leaf) and not leaf.is_deleted():
            total += sum(_array_per_device(leaf).values())
    return total


def is_oom_error(exc: BaseException) -> bool:
    """Is ``exc`` a device out-of-memory? Matches the PJRT/XLA
    RESOURCE_EXHAUSTED family plus plain host ``MemoryError`` — the guard
    the trainer step and engine admit/prefill/KV-growth paths use to
    decide a failure deserves a ``memory.json`` forensics dump."""
    if isinstance(exc, MemoryError):
        return True
    msg = f"{type(exc).__name__}: {exc}".lower()
    return ("resource_exhausted" in msg or "resource exhausted" in msg
            or "out of memory" in msg or "out_of_memory" in msg
            or "allocation failure" in msg)


def executable_memory_analysis(compiled: Any) -> Dict[str, int]:
    """Best-effort ``memory_analysis()`` of a compiled executable as a
    plain dict (bytes). Empty when the backend doesn't implement it (CPU
    commonly doesn't) — callers treat it as advisory."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out: Dict[str, int] = {}
    for field in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        v = getattr(ma, field, None)
        if isinstance(v, int) and v >= 0:
            out[field] = v
    if out:
        # The transient high-water estimate: temps live alongside args
        # and outputs while the step runs.
        out["activation_peak_bytes"] = (
            out.get("temp_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0))
    return out


def device_bytes_in_use() -> Dict[str, Dict[str, int]]:
    """``memory_stats()`` across ALL local devices:
    ``{device: {bytes_in_use, peak_bytes_in_use, bytes_limit}}`` (missing
    keys omitted; empty dict when no backend reports stats — CPU)."""
    import jax

    out: Dict[str, Dict[str, int]] = {}
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        entry = {}
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            v = stats.get(k)
            if isinstance(v, int) and v >= 0:
                entry[k] = v
        if entry:
            out[_device_key(dev)] = entry
    return out


# ----------------------------------------------------------------------
# The ledger
# ----------------------------------------------------------------------

class MemoryLedger:
    """Per-owner device-memory attribution with exact conservation.

    Thread-safety: ``register``/``unregister`` happen at wiring time;
    ``snapshot``/``scalars`` may be called concurrently by the sampler
    thread and HTTP handlers, so the owner map and peak/activation state
    share one lock. Owner *handles* are read without copying — providers
    must return a stable pytree (the trainer's state object / the
    engine's cache), not build one per call.
    """

    def __init__(self, enabled: bool = True, capacity_bytes: int = 0):
        self.enabled = enabled
        # 0 = auto-detect from memory_stats().bytes_limit (sums across
        # local devices); a configured budget wins when detection finds
        # nothing (the CPU path).
        self.capacity_bytes = int(capacity_bytes)
        self._lock = threading.Lock()
        self._owners: Dict[str, Any] = {}
        # owner -> (parent_owner, bytes_fn): sub-owners carved out of a
        # parent's bytes (see register_carve).
        self._carves: Dict[str, Any] = {}
        self._peak = 0
        self._owner_peaks: Dict[str, int] = {}
        self._activation: Dict[str, int] = {}
        self._remat_plan: Dict[str, int] = {}

    # -- wiring ---------------------------------------------------------
    def register(self, owner: str, handle: Any) -> None:
        """Attach ``handle`` (a pytree of jax arrays, or a zero-arg
        callable returning one) under ``owner``. Re-registering replaces
        — handles that are rebuilt (a fresh TrainState after restore)
        should register a callable so the ledger follows the swap."""
        if not self.enabled:
            return
        with self._lock:
            self._owners[owner] = handle

    def register_carve(self, owner: str, parent: str,
                       bytes_fn: Callable[[], int]) -> None:
        """Attribute a slice of ``parent``'s bytes to ``owner`` without
        double counting — for sub-tenants living *inside* another owner's
        arrays (prefix-cache blocks resident in the KV pool). At snapshot
        time ``min(bytes_fn(), parent bytes)`` moves from parent to
        owner, so conservation is untouched."""
        if not self.enabled:
            return
        with self._lock:
            self._carves[owner] = (parent, bytes_fn)

    def unregister(self, owner: str) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._owners.pop(owner, None)
            self._carves.pop(owner, None)
            self._owner_peaks.pop(owner, None)

    def owners(self) -> List[str]:
        with self._lock:
            return sorted(self._owners)

    def set_capacity(self, capacity_bytes: int) -> None:
        self.capacity_bytes = int(capacity_bytes)

    def note_activation_peak(self, info: Dict[str, int]) -> None:
        """Fold in a compiled step's :func:`executable_memory_analysis`
        (keeps the max per field across recompiles)."""
        if not self.enabled or not info:
            return
        with self._lock:
            for k, v in info.items():
                if isinstance(v, int):
                    self._activation[k] = max(self._activation.get(k, 0), v)

    def note_remat_plan(self, fields: Dict[str, int]) -> None:
        """The trainer's kept-block plan (``training.remat_plan``: blocks
        kept, planned, limit and a block's bytes), as it stands now."""
        if not self.enabled:
            return
        with self._lock:
            self._remat_plan = dict(fields)

    # -- snapshot -------------------------------------------------------
    def _materialize(self) -> Dict[str, List[Any]]:
        """owner -> live jax arrays, deduped by identity across owners
        (first registration order wins — an aliased array is one
        allocation and must be counted once)."""
        with self._lock:
            items = list(self._owners.items())
        import jax

        seen: set = set()
        out: Dict[str, List[Any]] = {}
        for owner, handle in items:
            try:
                tree = handle() if callable(handle) else handle
            except Exception:
                tree = None
            arrs = []
            for leaf in jax.tree_util.tree_leaves(tree):
                if not _is_jax_array(leaf) or leaf.is_deleted():
                    continue
                if id(leaf) in seen:
                    continue
                seen.add(id(leaf))
                arrs.append(leaf)
            out[owner] = arrs
        return out

    def snapshot(self, top_k: int = 0) -> dict:
        """The full reconciliation. Returns a dict whose ``buckets``
        (owners + ``untracked`` + ``residual``) sum to ``bytes_in_use``
        **exactly** — integers, no rounding escape hatch:

        * per owner: payload bytes of its live registered arrays
          (per-device breakdown included),
        * ``untracked``: ``jax.live_arrays()`` members no owner claimed,
        * ``bytes_in_use``: summed ``memory_stats()`` across local
          devices (``source: "device"``) or, when no backend reports
          stats, tracked+untracked live bytes (``source:
          "live_arrays"``),
        * ``residual``: bytes_in_use − tracked − untracked (allocator
          overhead / fragmentation; 0 on the live_arrays path). A
          negative residual (stats lagging a just-freed array) is shaved
          off the largest bucket, mirroring ``request_breakdown``'s
          exact-conservation arithmetic for time.
        """
        if not self.enabled:
            return {}
        import jax

        per_owner_arrays = self._materialize()
        owners: Dict[str, dict] = {}
        tracked_ids: set = set()
        tracked_total = 0
        for owner, arrs in per_owner_arrays.items():
            per_dev: Dict[str, int] = {}
            for a in arrs:
                tracked_ids.add(id(a))
                for dev, b in _array_per_device(a).items():
                    per_dev[dev] = per_dev.get(dev, 0) + b
            total = sum(per_dev.values())
            tracked_total += total
            owners[owner] = {"bytes": total, "per_device": per_dev}

        with self._lock:
            carves = list(self._carves.items())
        for owner, (parent, bytes_fn) in carves:
            if parent not in owners:
                continue
            try:
                want = max(0, int(bytes_fn()))
            except Exception:
                want = 0
            moved = min(want, owners[parent]["bytes"])
            owners[parent]["bytes"] -= moved
            owners[owner] = {"bytes": moved, "per_device": {},
                             "carved_from": parent}

        untracked_total = 0
        untracked_arrays: List[Any] = []
        try:
            live = jax.live_arrays()
        except Exception:
            live = []
        for a in live:
            if not _is_jax_array(a) or a.is_deleted():
                continue
            if id(a) in tracked_ids:
                continue
            tracked_ids.add(id(a))  # live_arrays can alias-duplicate
            untracked_total += sum(_array_per_device(a).values())
            untracked_arrays.append(a)

        dev_stats = device_bytes_in_use()
        if dev_stats:
            source = "device"
            bytes_in_use = sum(s.get("bytes_in_use", 0)
                               for s in dev_stats.values())
            device_peak = sum(s.get("peak_bytes_in_use", 0)
                              for s in dev_stats.values())
            detected_cap = sum(s.get("bytes_limit", 0)
                               for s in dev_stats.values())
        else:
            source = "live_arrays"
            bytes_in_use = tracked_total + untracked_total
            device_peak = 0
            detected_cap = 0
        capacity = detected_cap or self.capacity_bytes

        buckets: Dict[str, int] = {o: d["bytes"] for o, d in owners.items()}
        buckets[UNTRACKED_BUCKET] = untracked_total
        residual = bytes_in_use - tracked_total - untracked_total
        buckets[RESIDUAL_BUCKET] = max(0, residual)
        if residual < 0 and buckets:
            # Conservation over raw fidelity: shave the overshoot off the
            # largest bucket so the emitted map sums to bytes_in_use.
            top = max(buckets, key=lambda k: buckets[k])
            buckets[top] = max(0, buckets[top] + residual)

        with self._lock:
            self._peak = max(self._peak, bytes_in_use, device_peak)
            peak = self._peak
            for o, d in owners.items():
                self._owner_peaks[o] = max(self._owner_peaks.get(o, 0),
                                           d["bytes"])
            owner_peaks = dict(self._owner_peaks)
            activation = dict(self._activation)
            remat = dict(self._remat_plan)

        snap = {
            "source": source,
            "bytes_in_use": bytes_in_use,
            "peak_bytes": peak,
            "capacity_bytes": capacity,
            "headroom_bytes": (max(0, capacity - bytes_in_use)
                               if capacity else None),
            "tracked_bytes": tracked_total,
            "untracked_bytes": untracked_total,
            "residual_bytes": max(0, residual),
            "owners": owners,
            "owner_peak_bytes": owner_peaks,
            "buckets": buckets,
            "activation_peak": activation,
            "remat_plan": remat,
            "device_stats": dev_stats,
            "num_live_arrays": len(live),
        }
        if top_k > 0:
            ranked = sorted(untracked_arrays,
                            key=lambda a: -int(a.nbytes))[:top_k]
            snap["top_untracked_arrays"] = [{
                "shape": list(getattr(a, "shape", ())),
                "dtype": str(getattr(a, "dtype", "?")),
                "nbytes": int(a.nbytes),
                "per_device": _array_per_device(a),
            } for a in ranked]
        return snap

    # -- reads ----------------------------------------------------------
    def headroom_bytes(self,
                       snap: Optional[dict] = None) -> Optional[int]:
        """Capacity minus bytes-in-use; None when disabled or capacity is
        unknown (callers must then skip headroom gating, not treat it as
        zero)."""
        if not self.enabled:
            return None
        if snap is None:
            snap = self.snapshot()
        return snap.get("headroom_bytes")

    def scalars(self) -> Dict[str, float]:
        """``hbm_*`` keys for the time-series ring / ``/debug/vars``
        (what the watchdog's hbm_pressure rule, the dashboard panel and
        the steplog fields consume) — and the refresh point for the
        module-level gauges, so /metrics stays current wherever the
        sampler runs."""
        if not self.enabled:
            return {}
        snap = self.snapshot()
        out: Dict[str, float] = {
            "hbm_bytes_in_use": snap["bytes_in_use"],
            "hbm_tracked_bytes": snap["tracked_bytes"],
            "hbm_untracked_bytes": snap["untracked_bytes"],
            "hbm_peak_bytes": snap["peak_bytes"],
        }
        for o, d in snap["owners"].items():
            out[f"hbm_owner_{o}_bytes"] = d["bytes"]
        headroom = snap.get("headroom_bytes")
        cap = snap.get("capacity_bytes", 0)
        if headroom is not None:
            out["hbm_headroom_bytes"] = headroom
            if cap:
                out["hbm_headroom_frac"] = round(headroom / cap, 6)
        hbm_peak_gauge.set(snap["peak_bytes"])
        hbm_untracked_gauge.set(snap["untracked_bytes"])
        hbm_headroom_gauge.set(headroom or 0)
        for o, d in snap["owners"].items():
            hbm_bytes_gauge.labels(owner=o).set(d["bytes"])
        return out

    def to_dict(self, top_k: int = 8) -> dict:
        """The ``GET /debug/memory`` / ``memory.json`` payload."""
        if not self.enabled:
            return {}
        snap = self.snapshot(top_k=top_k)
        snap["ts"] = time.time()
        return snap

    def save(self, path: str, **extra) -> Optional[str]:
        """Atomic JSON write of :meth:`to_dict` + ``extra``; never raises
        (accounting must not kill the run it accounts). None disabled."""
        if not self.enabled:
            return None
        try:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump({**self.to_dict(), **extra}, f)
            os.replace(tmp, path)
            return path
        except OSError:
            return None


# ----------------------------------------------------------------------
# Chaos: the hbm-squeeze balloon
# ----------------------------------------------------------------------

class MemoryBalloon:
    """A deterministic HBM squeeze: allocate ``n`` device bytes and
    register them with the ledger as ``chaos_balloon`` — the headroom
    shrinks by exactly what the ledger can see, so the defer-don't-fault
    admission path and the hbm_pressure watchdog rule are provable on
    CPU without a real OOM. ``deflate()`` releases the bytes and the
    owner entry."""

    OWNER = "chaos_balloon"

    def __init__(self, ledger: Optional[MemoryLedger] = None):
        self.ledger = ledger
        self._arrays: List[Any] = []

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self._arrays
                   if not a.is_deleted())

    def inflate(self, nbytes: int) -> int:
        """Allocate ~``nbytes`` more device memory (float32 zeros,
        materialized). Returns the balloon's new total size."""
        import jax
        import jax.numpy as jnp

        n = max(1, int(nbytes) // 4)
        arr = jax.block_until_ready(jnp.zeros((n,), dtype=jnp.float32))
        self._arrays.append(arr)
        if self.ledger is not None:
            self.ledger.register(self.OWNER, lambda: self._arrays)
        return self.nbytes

    def deflate(self) -> None:
        for a in self._arrays:
            try:
                a.delete()
            except Exception:
                pass
        self._arrays = []
        if self.ledger is not None:
            self.ledger.unregister(self.OWNER)


# ----------------------------------------------------------------------
# Process-global accessor (the flightrecorder pattern): chaos injectors
# and postmortem hooks reach the live ledger without plumbing.
# ----------------------------------------------------------------------

_current: Optional[MemoryLedger] = None


def install(ledger: Optional[MemoryLedger]) -> None:
    global _current
    _current = ledger


def get_ledger() -> Optional[MemoryLedger]:
    return _current
