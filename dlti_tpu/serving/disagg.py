"""Prefill/decode disaggregation: split engine pools with paged-KV handoff.

Chunked prefill (``EngineConfig.max_prefill_tokens_per_step``) bounds how
long one prompt can stall the step loop, but every prefill chunk still
steals a decode step from all co-resident slots — a long-document request
landing on a chat replica inflates every neighbour's TPOT p99. The
disaggregation literature (DistServe, Splitwise) removes the interference
structurally: prefill and decode run in *separate pools*, each batching
for its own regime, and a finished prefill's KV state migrates to a
decode replica.

:class:`DisaggController` is that split, built on the proven pieces:

* **Pools** are two :class:`~dlti_tpu.serving.replicas.ReplicatedEngine`
  fleets sharing one :class:`~dlti_tpu.telemetry.RequestTelemetry`.
  Prefill engines run with ``prefill_only=True`` (admission + chunked
  prefill, never a decode dispatch — and never a decode-ladder warmup);
  decode engines are full engines, so they can re-prefill on failover.
* **Handoff** rides the prefix-tier transport: the origin engine's
  ``export_handoff`` fetches each written block device→host
  (``EngineExecutor.fetch_block_kv``, staged through ``pinned_host``
  where the backend has it), and the target's ``adopt_handoff`` scatters
  the payloads back with the jitted ``.at[block].set`` restore. The
  snapshot carries the sampled first token plus the origin slot's actual
  rng key bytes, so the decode replica's ``fold_in(key, gen_count)``
  stream continues exactly where prefill sampling left it — outputs are
  byte-identical with disaggregation on or off.
* **Phase accounting**: the staged wait opens a ``kv_handoff`` stall mark
  (``telemetry.ledger.note_requeue``) closed by the decode-side
  admission, so ``request_breakdown()`` books the migration as its own
  phase and ``/debug/slow`` timelines show the handoff leg.
* **Failover**: each pool keeps ReplicatedEngine's retry-capped
  failover. A dead prefill replica's requests re-prefill on surviving
  prefill replicas (or, pool extinct, colocate onto decode replicas via
  the ``failover_fallback`` hook); a dead decode replica's requests
  re-admit from their staged handoff snapshot when one exists, else
  re-prefill on a surviving decode replica.
* **Backpressure**: staged snapshots per decode replica are bounded
  (``handoff_queue_depth``); a full pool leaves finished prefills in
  their slots, which shrinks the gateway's dispatch room — load sheds at
  admission, host memory stays bounded. Staged payload bytes register
  with each decode engine's memory ledger under ``kv_handoff_staging``.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence

from dlti_tpu.serving.engine import (
    EngineConfig, GenerationResult, InferenceEngine, Request, SamplingParams,
)
from dlti_tpu.serving.replicas import (
    FAULT_INJECT_ENV, ReplicatedEngine, _parse_fault_inject,
)
from dlti_tpu.telemetry import RequestTelemetry
from dlti_tpu.telemetry.ledger import note_requeue
from dlti_tpu.telemetry.registry import Histogram
from dlti_tpu.utils.logging import get_logger

# Name-stability contracts for the /metrics exposition (pinned in
# tests/test_bench_contract.py, walked by tests/test_metric_naming.py).
POOL_METRIC_NAMES = (
    "dlti_pool_prefill_replicas_alive",
    "dlti_pool_decode_replicas_alive",
    "dlti_pool_prefill_waiting",
    "dlti_pool_decode_waiting",
    "dlti_pool_prefill_active",
    "dlti_pool_decode_active",
)
KV_HANDOFF_METRIC_NAMES = (
    "dlti_kv_handoff_total",
    "dlti_kv_handoff_bytes_total",
    "dlti_kv_handoff_staged",
    "dlti_kv_handoff_fallbacks_total",
    "dlti_kv_handoff_sheds_total",
    "dlti_kv_handoff_seconds",
)

# Module-level histogram (the watchdog/flight-counter pattern: the server
# registry registers it for /metrics): prefill-finish → decode-adoption
# latency per migrated request.
handoff_seconds = Histogram(
    "dlti_kv_handoff_seconds",
    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5),
    help="prefill→decode KV handoff latency (harvest to adoption)")

_POOLS = ("prefill", "decode")


def _parse_pool_fault(spec: str) -> Dict[str, str]:
    """"POOL:REPLICA:STEP[:MODE]" -> {pool: "REPLICA:STEP[:MODE]"}; empty
    dict when unset. Validates eagerly (construction time beats step
    time for a config typo)."""
    spec = (spec or "").strip()
    if not spec:
        return {}
    pool, _, rest = spec.partition(":")
    if pool not in _POOLS:
        raise ValueError(
            f"disagg fault_inject_step must be 'POOL:REPLICA:STEP[:MODE]' "
            f"with POOL in {_POOLS}, got {spec!r}")
    _parse_fault_inject(rest)  # raises on a malformed remainder
    return {pool: rest}


def _payload_nbytes(payloads: List[dict]) -> int:
    return sum(int(arr.nbytes) for blk in payloads
               for layer in blk.values() for arr in layer.values())


class _Staged:
    """One harvested prefill waiting for a decode slot."""

    __slots__ = ("snap", "t0", "holder")

    def __init__(self, snap: dict, t0: float):
        self.snap = snap
        self.t0 = t0
        # Fake slot so AsyncEngine._drain_events (which walks
        # controller.slots by .request) streams the first token while the
        # request is in transit between pools.
        self.holder = SimpleNamespace(request=snap["request"])


class DisaggController:
    """Prefill pool + decode pool behind one engine-compatible facade.

    API mirrors :class:`~dlti_tpu.serving.replicas.ReplicatedEngine`
    (``submit`` / ``step`` / ``generate`` / ``has_work`` / stats surface),
    so the AsyncEngine stepper, the admission gateway, and the metrics
    registry drive it unchanged. ``step()`` is one controller iteration:
    prefill pool steps, finished prefills are harvested into per-decode-
    replica staging queues, staged snapshots inject into free decode
    slots, decode pool steps.
    """

    def __init__(
        self,
        model_cfg,
        params,
        engine_cfg: EngineConfig = EngineConfig(),
        lora_cfg=None,
        *,
        prefill_replicas: int = 1,
        decode_replicas: int = 1,
        tensor: int = 1,
        devices: Optional[Sequence] = None,
        max_retries: int = 2,
        fault_inject_step: str = "",
        handoff_queue_depth: int = 8,
        handoff_deadline_s: float = 0.0,
        affinity_spill_threshold: int = 4,
        lifecycle_cfg=None,
    ):
        import jax

        from dlti_tpu.serving.engine import refuse_state_handoff

        refuse_state_handoff(model_cfg, "disaggregated serving (--disagg)")
        if prefill_replicas < 1 or decode_replicas < 1:
            raise ValueError(
                f"prefill_replicas ({prefill_replicas}) and decode_replicas "
                f"({decode_replicas}) must be >= 1")
        devices = list(devices if devices is not None else jax.devices())
        need = (prefill_replicas + decode_replicas) * tensor
        if need > len(devices):
            raise ValueError(
                f"disagg needs {need} devices ({prefill_replicas} prefill + "
                f"{decode_replicas} decode replicas x tensor={tensor}), "
                f"have {len(devices)}")
        self.logger = get_logger()
        self.telemetry = RequestTelemetry()
        self._tracer = self.telemetry.tracer
        faults = _parse_pool_fault(
            os.environ.get(FAULT_INJECT_ENV) or fault_inject_step)
        # The env var is pool-scoped here; hide it from the inner
        # ReplicatedEngines (their parser rejects the POOL: prefix) and
        # route the remainder to the right pool via the explicit kwarg.
        env_saved = os.environ.pop(FAULT_INJECT_ENV, None)
        try:
            split = prefill_replicas * tensor
            self.prefill = ReplicatedEngine(
                model_cfg, params, engine_cfg, lora_cfg,
                replicas=prefill_replicas, tensor=tensor,
                devices=devices[:split], max_retries=max_retries,
                fault_inject_step=faults.get("prefill", ""),
                affinity_spill_threshold=affinity_spill_threshold,
                telemetry=self.telemetry, lifecycle_cfg=lifecycle_cfg)
            self.decode = ReplicatedEngine(
                model_cfg, params, engine_cfg, lora_cfg,
                replicas=decode_replicas, tensor=tensor,
                devices=devices[split:split + decode_replicas * tensor],
                max_retries=max_retries,
                fault_inject_step=faults.get("decode", ""),
                affinity_spill_threshold=affinity_spill_threshold,
                telemetry=self.telemetry, lifecycle_cfg=lifecycle_cfg)
        finally:
            if env_saved is not None:
                os.environ[FAULT_INJECT_ENV] = env_saved
        for eng in self.prefill.engines:
            eng.prefill_only = True
        # Pool-extinction rescue (degraded colocation): with no prefill
        # replica left, stranded prompts re-prefill on a decode replica
        # (full engines); with no decode replica left, a live prefill
        # engine flips colocated and decodes everything itself.
        self.prefill.failover_fallback = self._rescue_to_decode
        self.decode.failover_fallback = self._rescue_to_prefill
        self.max_retries = max_retries
        self.handoff_queue_depth = max(1, handoff_queue_depth)
        self.handoff_deadline_s = handoff_deadline_s
        # Per-decode-replica staging queues (index-aligned with
        # decode.engines). Host-side only; bounded; visible to the memory
        # ledger below.
        self._staging: List[deque] = [deque()
                                      for _ in self.decode.engines]
        self._rr = 0
        self.handoff = {"completed": 0, "bytes": 0, "fallbacks": 0,
                        "sheds": 0}
        # Concurrent pool stepping (opt-in via start()): a prefill-pool
        # thread overlaps long prefills with decode dispatch.
        self._prefill_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        for di, eng in enumerate(self.decode.engines):
            # Host-staged payloads are numpy (post device_get), so the
            # HBM ledger attributes them 0 device bytes — the owner still
            # appears in every snapshot, and on backends where staging
            # pins device-visible host memory the bytes show up here.
            eng.memledger.register(
                "kv_handoff_staging",
                lambda q=self._staging[di]: [s.snap["payloads"] for s in q])

    # -- routing --------------------------------------------------------
    def submit(self, prompt_token_ids: Sequence[int],
               params: Optional[SamplingParams] = None,
               request_id: Optional[str] = None,
               affinity_key: Optional[str] = None,
               adapter: str = "", trace_id: str = "") -> Request:
        """Admit into the prefill pool (least-loaded / affinity routing is
        ReplicatedEngine's); with the prefill pool extinct, degrade to
        colocated admission on the decode pool rather than refusing.

        ``adapter`` rides the Request through the KV handoff: the prefill
        engine pins it from its own pool, ``export_handoff``'s release
        drops that pin, and ``adopt_handoff`` re-acquires on the decode
        replica's pool (adoption defers while that pool is pinned full).
        """
        try:
            return self.prefill.submit(prompt_token_ids, params,
                                       request_id, affinity_key,
                                       adapter=adapter, trace_id=trace_id)
        except RuntimeError:
            if self.decode.num_live == 0:
                raise
            self.logger.warning(
                "prefill pool has no live replicas; admitting colocated "
                "on the decode pool")
            return self.decode.submit(prompt_token_ids, params,
                                      request_id, affinity_key,
                                      adapter=adapter, trace_id=trace_id)

    def _rescue_to_decode(self, req: Request) -> bool:
        live = self.decode.live_engines()
        if not live:
            return False
        target = min(live, key=self.decode._load)
        target.resubmit(req)
        return True

    def _rescue_to_prefill(self, req: Request) -> bool:
        live = self.prefill.live_engines()
        if not live:
            return False
        eng = min(live, key=self.prefill._load)
        if eng.prefill_only:
            # No decode replica left anywhere: this engine must carry its
            # requests end-to-end from now on (colocated mode).
            eng.prefill_only = False
            self.logger.warning(
                "decode pool has no live replicas; prefill replica %d now "
                "runs colocated", self.prefill.engines.index(eng))
        eng.resubmit(req)
        return True

    # -- the controller loop --------------------------------------------
    def step(self) -> List[Request]:
        """One controller iteration. Sequential by default (deterministic:
        the byte-identity contract's test mode, and correct anywhere).
        After :meth:`start`, the prefill pool steps on its own thread and
        ``step()`` covers only inject + decode — the host no longer blocks
        a decode dispatch on a long prefill's result, which is where the
        decode-TPOT win under mixed load comes from."""
        finished: List[Request] = []
        if self._prefill_thread is None:
            finished.extend(self.prefill.step())
            self._harvest()
        finished.extend(self._inject())
        finished.extend(self.decode.step())
        return finished

    def start(self) -> None:
        """Start concurrent pool stepping: a daemon thread runs the
        prefill pool (step + harvest) while the caller's stepper drives
        ``step()`` for inject + decode. Safe against the existing
        threading contract: ``submit`` already races ``step`` in the
        server (HTTP handler threads vs the AsyncEngine stepper), and the
        staging handoff crosses threads on deque append/popleft only."""
        if self._prefill_thread is not None:
            return
        self._stop.clear()
        self._prefill_thread = threading.Thread(
            target=self._prefill_loop, name="disagg-prefill", daemon=True)
        self._prefill_thread.start()

    def stop(self) -> None:
        t = self._prefill_thread
        if t is None:
            return
        self._stop.set()
        t.join(timeout=5.0)
        self._prefill_thread = None

    def _prefill_loop(self) -> None:
        while not self._stop.is_set():
            if self.prefill.has_work:
                try:
                    self.prefill.step()
                    self._harvest()
                except Exception:  # noqa: BLE001 — a pool-wide fault
                    # must not kill the thread silently mid-serve; the
                    # per-replica failover inside step() already absorbed
                    # per-replica faults, so this is last-resort.
                    self.logger.exception("disagg prefill loop error")
                    self._stop.wait(0.05)
            else:
                self._stop.wait(0.001)

    def _harvest(self) -> None:
        """Move finished prefills off their prefill slots into staging.

        A slot is harvestable once it is occupied, done prefilling, and
        its request still wants more tokens (a one-token request finished
        on the prefill engine already). When every staging queue is full
        the slot simply stays occupied — that is the backpressure that
        shrinks gateway dispatch room.
        """
        for pi, eng in enumerate(self.prefill.engines):
            if pi in self.prefill._dead or not eng.prefill_only:
                continue
            for slot in eng.slots:
                req = slot.request
                if (req is None or slot.prefilling or req.done
                        or slot.last_token is None):
                    continue
                di = self._pick_decode_replica()
                if di is None:
                    return  # every queue full: leave slots occupied
                t0 = time.monotonic()
                # The staged wait books as the kv_handoff phase; the mark
                # closes at decode-side admission (adopt or re-prefill).
                note_requeue(req, "kv_handoff")
                snap = eng.export_handoff(slot)
                if snap is None:
                    # Block fetch failed (best-effort transport): release
                    # the slot and re-prefill on the decode side — the
                    # client sees latency, never an error.
                    self.handoff["fallbacks"] += 1
                    eng._release(slot)
                    self.decode.engines[di].resubmit(req)
                    continue
                self.handoff["bytes"] += _payload_nbytes(snap["payloads"])
                self._staging[di].append(_Staged(snap, t0))

    def _pick_decode_replica(self) -> Optional[int]:
        """Least-loaded live decode replica with staging room (round-robin
        tiebreak), counting staged snapshots as load."""
        best, best_load = None, None
        n = len(self.decode.engines)
        for k in range(n):
            i = (self._rr + k) % n
            if i in self.decode._dead:
                continue
            if len(self._staging[i]) >= self.handoff_queue_depth:
                continue
            load = (self.decode._load(self.decode.engines[i])
                    + len(self._staging[i]))
            if best_load is None or load < best_load:
                best, best_load = i, load
        if best is not None:
            self._rr = (best + 1) % n
        return best

    def _inject(self) -> List[Request]:
        """Drain staging queues into free decode slots; honor cancels,
        deadlines, and decode-replica death while staged."""
        finished: List[Request] = []
        now = time.monotonic()
        for di, q in enumerate(self._staging):
            dead = di in self.decode._dead
            while q:
                staged = q[0]
                req = staged.snap["request"]
                if req.cancel_requested:
                    q.popleft()
                    self.handoff["sheds"] += 1
                    req.finish_reason = "stop"
                    req.finish_time = now
                    self._finish_ring(di).append(req)
                    self.telemetry.on_finished(req)
                    finished.append(req)
                    continue
                if dead:
                    # The decode replica died with this snapshot staged:
                    # re-admit from the snapshot on a survivor (adopt), or
                    # re-prefill there when adoption can't take it now.
                    q.popleft()
                    self._reroute(staged)
                    continue
                if (self.handoff_deadline_s > 0
                        and now - staged.t0 > self.handoff_deadline_s):
                    # Staged too long (slot famine on this replica):
                    # degrade to a re-prefill instead of waiting forever.
                    q.popleft()
                    self.handoff["sheds"] += 1
                    self.decode.engines[di].resubmit(req)
                    continue
                eng = self.decode.engines[di]
                if not eng.adopt_handoff(staged.snap):
                    break  # no slot/blocks free — retry next step
                q.popleft()
                dt = time.monotonic() - staged.t0
                self.handoff["completed"] += 1
                handoff_seconds.observe(dt)
                self._tracer.complete(
                    "engine/kv_handoff", staged.t0, staged.t0 + dt,
                    cat="engine", id=req.request_id,
                    trace=req.trace_id, decode_replica=di)
                req.replica = (len(self.prefill.engines) + di)
        return finished

    def _reroute(self, staged: "_Staged") -> None:
        req = staged.snap["request"]
        for di in range(len(self.decode.engines)):
            if di in self.decode._dead:
                continue
            if len(self._staging[di]) < self.handoff_queue_depth:
                self._staging[di].append(staged)
                return
        # Nowhere to stage: re-prefill least-loaded (live decode replica,
        # else the prefill-pool rescue path errors it out properly).
        live = self.decode.live_engines()
        if live:
            self.handoff["fallbacks"] += 1
            min(live, key=self.decode._load).resubmit(req)
            return
        if not self._rescue_to_prefill(req):
            req.finish_reason = "error"
            req.finish_time = time.monotonic()
            self._finish_ring(0).append(req)
            self.telemetry.on_finished(req)

    def _finish_ring(self, di: int):
        return self.decode.engines[di].finished

    # -- engine-compatible surface --------------------------------------
    @property
    def has_work(self) -> bool:
        return (self.prefill.has_work or self.decode.has_work
                or any(self._staging))

    def generate(self, prompts: Sequence[Sequence[int]],
                 params: Optional[SamplingParams] = None,
                 ) -> List[GenerationResult]:
        """Offline batch generation across both pools."""
        reqs = [self.submit(p, params) for p in prompts]
        while self.has_work:
            self.step()
        eng = self.decode.engines[0]
        return [eng._result(r) for r in reqs]

    def live_engines(self) -> List[InferenceEngine]:
        """Live PREFILL engines — the admission side: the gateway's
        dispatch room must track where new prompts land. With the
        prefill pool extinct, the decode pool (degraded colocation) is
        the admission side."""
        live = self.prefill.live_engines()
        return live if live else self.decode.live_engines()

    @property
    def num_live(self) -> int:
        return self.prefill.num_live + self.decode.num_live

    # -- replica lifecycle (pool-aware) ---------------------------------
    @property
    def lifecycle_pending(self) -> bool:
        return self.prefill.lifecycle_pending or self.decode.lifecycle_pending

    def lifecycle_counts(self) -> dict:
        """/health summary aggregated across both pools."""
        pc, dc = self.prefill.lifecycle_counts(), self.decode.lifecycle_counts()
        return {k: pc[k] + dc[k] for k in pc}

    def request_reload(self, weights_provider) -> bool:
        """Rolling weight reload across BOTH pools (prefill first — a
        mixed-version window between the pools is unavoidable mid-roll;
        each pool stays internally consistent)."""
        ok_p = self.prefill.request_reload(weights_provider)
        ok_d = self.decode.request_reload(weights_provider)
        return ok_p and ok_d

    @property
    def failover(self) -> dict:
        pf, df = self.prefill.failover, self.decode.failover
        return {k: pf[k] + df[k] for k in pf}

    @property
    def affinity(self) -> dict:
        pa, da = self.prefill.affinity, self.decode.affinity
        return {k: pa[k] + da[k] for k in pa}

    def warmup_decode_ladder(self) -> None:
        # Decode pool only: prefill-only engines never dispatch decode,
        # so warming their ladder would burn startup time compiling
        # programs that cannot run.
        self.decode.warmup_decode_ladder()

    @property
    def cfg(self) -> EngineConfig:
        return self.decode.engines[0].cfg

    @property
    def slots(self) -> list:
        staged = [s.holder for q in self._staging for s in q]
        return self.prefill.slots + staged + self.decode.slots

    @property
    def finished(self) -> List[Request]:
        return self.prefill.finished + self.decode.finished

    @property
    def waiting(self) -> List[Request]:
        return self.prefill.waiting + self.decode.waiting

    @property
    def num_active(self) -> int:
        return (self.prefill.num_active + self.decode.num_active
                + sum(len(q) for q in self._staging))

    @property
    def num_free_blocks(self) -> int:
        return self.prefill.num_free_blocks + self.decode.num_free_blocks

    def abort_all(self, reason: str = "abort") -> List[Request]:
        aborted = self.prefill.abort_all(reason=reason)
        for q in self._staging:
            while q:
                req = q.popleft().snap["request"]
                req.finish_reason = reason
                req.finish_time = time.monotonic()
                self.telemetry.on_finished(req)
                aborted.append(req)
        aborted.extend(self.decode.abort_all(reason=reason))
        return aborted

    @property
    def stats(self) -> dict:
        """Aggregated counters across both pools, with per-pool detail
        under "pools" and the handoff counters under "kv_handoff"."""
        ps, ds = self.prefill.stats, self.decode.stats
        agg = {k: ps[k] + ds[k] for k in ps if k != "replicas"}
        agg["pools"] = {"prefill": ps, "decode": ds}
        agg["kv_handoff"] = {**self.handoff,
                             "staged": sum(len(q) for q in self._staging)}
        return agg

    def pool_scalars(self) -> dict:
        """Scalar source for the metrics registry (``dlti_pool_*`` /
        ``dlti_kv_handoff_*`` series; server.build_registry wires it)."""
        return {
            "pool_prefill_replicas_alive": self.prefill.num_live,
            "pool_decode_replicas_alive": self.decode.num_live,
            "pool_prefill_waiting": len(self.prefill.waiting),
            "pool_decode_waiting": len(self.decode.waiting),
            "pool_prefill_active": self.prefill.num_active,
            "pool_decode_active": self.decode.num_active,
            "kv_handoff_total": self.handoff["completed"],
            "kv_handoff_bytes_total": self.handoff["bytes"],
            "kv_handoff_staged": sum(len(q) for q in self._staging),
            "kv_handoff_fallbacks_total": self.handoff["fallbacks"],
            "kv_handoff_sheds_total": self.handoff["sheds"],
        }


# Gauge keys for pool_scalars (point-in-time values; the rest expose as
# counters). server.build_registry passes these to add_scalar_source.
POOL_GAUGE_KEYS = (
    "pool_prefill_replicas_alive", "pool_decode_replicas_alive",
    "pool_prefill_waiting", "pool_decode_waiting",
    "pool_prefill_active", "pool_decode_active", "kv_handoff_staged",
)
