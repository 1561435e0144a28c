"""Data-parallel serving: independent engine replicas over device groups.

The reference claims vLLM serving with tensor parallelism
(``/root/reference/README.md:10``); vLLM scales *throughput* beyond one
TP group by running multiple engine replicas behind a dispatcher. This is
the TPU-native equivalent: the visible devices are partitioned into
``replicas`` groups of ``tensor`` chips, each group gets a fully
independent :class:`InferenceEngine` (its own sharded weights, KV pool,
scheduler, prefix cache), and requests are dispatched least-loaded.

Replication is deliberately *above* the engine rather than a mesh axis
inside it: batch rows of one jitted program sharded over a ``data`` axis
would lock every replica to the same program counter (one global decode
step), while independent engines prefill, decode and preempt on their own
schedules — the same reason vLLM runs one engine per data-parallel rank.
Within a replica, jit dispatch is async, so driving the replicas
round-robin from one host thread overlaps their device work.
"""

from __future__ import annotations

import hashlib
import itertools
import os
import time
from typing import List, Optional, Sequence, Tuple

import jax

from dlti_tpu.config import (
    LoRAConfig, ModelConfig, ParallelConfig, ReplicaLifecycleConfig,
)
from dlti_tpu.serving.engine import (
    EngineConfig, GenerationResult, InferenceEngine, Request, SamplingParams,
)
from dlti_tpu.serving.lifecycle import ReplicaLifecycle, canary_digest
from dlti_tpu.telemetry import RequestTelemetry
from dlti_tpu.utils.logging import get_logger

# Env override for the deterministic chaos hook (same "REPLICA:STEP"
# format as GatewayConfig.fault_inject_step): lets a chaos run kill a
# replica on a live server without a config edit.
FAULT_INJECT_ENV = "DLTI_GATEWAY_FAULT_INJECT"


_FAULT_MODES = ("raise", "nan-logits", "preempt")


def _parse_fault_inject(spec: str) -> Optional[Tuple[int, int, str]]:
    """"REPLICA:STEP[:MODE]" -> (replica_idx, 1-based step count, mode);
    None if unset. MODE "raise" (default) raises :class:`ReplicaFault` in
    place of a device fault; "nan-logits" instead poisons the replica's
    params with NaN so the engine's REAL numeric guard
    (:class:`~dlti_tpu.serving.engine.NumericFault`) detects the garbage
    output and trips the same quarantine path; "preempt" simulates a
    planned preemption notice — the replica drains via live KV migration
    to survivors (:meth:`ReplicatedEngine.drain_replica`) and enters the
    lifecycle quarantine instead of faulting."""
    spec = (spec or "").strip()
    if not spec:
        return None
    try:
        rep, _, rest = spec.partition(":")
        step, _, mode = rest.partition(":")
        mode = mode or "raise"
        if mode not in _FAULT_MODES:
            raise ValueError(mode)
        return int(rep), int(step), mode
    except ValueError:
        raise ValueError(
            f"fault_inject_step must be 'REPLICA:STEP[:MODE]' with MODE "
            f"in {_FAULT_MODES}, got {spec!r}")


class ReplicaFault(RuntimeError):
    """Raised by the fault-injection hook in place of a real device fault."""


class ReplicatedEngine:
    """N independent engine replicas (each optionally TP-sharded) behind a
    least-loaded dispatcher. API mirrors :class:`InferenceEngine`:
    ``submit`` / ``step`` / ``generate`` / ``has_work``.

    **Fault isolation & failover:** a replica whose ``step()`` raises is
    marked dead and excluded from dispatch; its in-flight and queued
    requests are resubmitted on surviving replicas (recompute-on-readmit,
    the preemption path's semantics) up to ``max_retries`` per request —
    one replica fault degrades capacity instead of erroring the fleet.
    Requests past the retry cap (or with no survivors left) finish with
    ``finish_reason="error"``. ``fault_inject_step`` (or the
    ``DLTI_GATEWAY_FAULT_INJECT`` env var), format ``"REPLICA:STEP"``,
    kills a replica deterministically for tests and chaos runs."""

    # Class-level defaults so `__new__`-built test skeletons (which skip
    # __init__) still have the deploy-controller surface.
    shadow_tap = None
    last_reload_ok: Optional[bool] = None

    def __init__(
        self,
        model_cfg: ModelConfig,
        params,
        engine_cfg: EngineConfig = EngineConfig(),
        lora_cfg: Optional[LoRAConfig] = None,
        *,
        replicas: int = 1,
        tensor: int = 1,
        devices: Optional[Sequence] = None,
        max_retries: int = 2,
        fault_inject_step: str = "",
        affinity_spill_threshold: int = 4,
        telemetry: Optional[RequestTelemetry] = None,
        lifecycle_cfg: Optional[ReplicaLifecycleConfig] = None,
        lifecycle_clock=None,
    ):
        devices = list(devices if devices is not None else jax.devices())
        if replicas < 1 or tensor < 1:
            raise ValueError(
                f"replicas ({replicas}) and tensor ({tensor}) must be >= 1")
        need = replicas * tensor
        if need > len(devices):
            raise ValueError(
                f"{replicas} replicas x tensor={tensor} needs {need} "
                f"devices, have {len(devices)}")
        from dlti_tpu.parallel.mesh import build_mesh

        # One shared request-telemetry instance: every replica observes
        # into the same TTFT/TPOT/queue-time histograms, so the fleet's
        # latency distributions aggregate without a merge step. An
        # injected instance extends the sharing across pools (the disagg
        # controller's prefill and decode fleets report as one).
        self.telemetry = telemetry if telemetry is not None \
            else RequestTelemetry()
        self.engines: List[InferenceEngine] = []
        # Rebuild materials (lifecycle reinstates, rolling reloads): each
        # replica's device group / mesh / final engine config, plus the
        # model/lora configs, are enough to construct a replacement
        # engine from a host weight tree.
        self._model_cfg = model_cfg
        self._lora_cfg = lora_cfg
        self._groups: List[list] = []
        self._meshes: List[Optional[object]] = []
        self._rep_cfgs: List[EngineConfig] = []
        for r in range(replicas):
            group = devices[r * tensor:(r + 1) * tensor]
            mesh = (build_mesh(ParallelConfig(tensor=tensor), devices=group)
                    if tensor > 1 else None)
            # Single-chip replicas (tensor=1) pin weights to their device
            # explicitly — engines would otherwise all initialize onto the
            # default device.
            rep_params = (params if mesh is not None
                          else jax.device_put(params, group[0]))
            rep_cfg = engine_cfg
            if engine_cfg.prefix_disk_dir:
                # Per-replica disk-tier namespace: one shared dir would
                # let replica A's budget eviction delete a block dir
                # replica B's index still points at.
                import dataclasses

                rep_cfg = dataclasses.replace(
                    engine_cfg, prefix_disk_dir=os.path.join(
                        engine_cfg.prefix_disk_dir, f"replica{r}"))
            self._groups.append(group)
            self._meshes.append(mesh)
            self._rep_cfgs.append(rep_cfg)
            self.engines.append(
                InferenceEngine(model_cfg, rep_params, rep_cfg, lora_cfg,
                                mesh=mesh, telemetry=self.telemetry))
        self._rr = 0
        # Own id namespace: each engine's req-N counter starts at 0, so
        # auto-ids from different replicas would collide in any id-keyed
        # consumer (server streams, generate()'s by_id map).
        self._req_counter = itertools.count()
        self.logger = get_logger()
        self.max_retries = max_retries
        self._dead: set = set()  # replica indices excluded from dispatch
        self._step_counts = [0] * replicas
        self._fault_inject = _parse_fault_inject(
            os.environ.get(FAULT_INJECT_ENV) or fault_inject_step)
        # Failover counters, read by the gateway's dlti_gateway_* metrics
        # (kept out of `stats` so the aggregated per-engine keys — a
        # /stats name contract — stay untouched).
        self.failover = {"retries": 0, "replica_faults": 0,
                         "failover_errors": 0}
        # Cache-affinity routing (the tiered-prefix-cache companion: a
        # warm cache is per-replica, so repeat sessions must LAND on it).
        # A submit carrying an affinity key routes by rendezvous hashing
        # over the live replicas — stable under replica death (only keys
        # sticky to the dead replica re-rank; everyone else stays warm) —
        # with load-aware spill: when the sticky target's backlog exceeds
        # its slots by more than affinity_spill_threshold, the request
        # goes least-loaded instead (latency beats cache warmth).
        self.affinity_spill_threshold = affinity_spill_threshold
        self.affinity = {"sticky": 0, "spill": 0}
        # Last-resort rescue hook (disagg): when THIS pool has no live
        # replicas left, a stranded request is offered to the callable
        # (returning True = rehomed elsewhere) before erroring — the
        # controller routes it to the other pool (degraded colocation).
        self.failover_fallback = None
        # Replica lifecycle (serving.lifecycle): the state machine always
        # exists (it backs /health counts and the dlti_replica_state
        # gauge), but self-healing behavior — quarantine instead of
        # permanent death, probation probes, reinstates — only runs when
        # the config enables it; disabled, a faulted replica is marked
        # dead forever (the legacy contract the kill-drill tests pin).
        self.lifecycle_cfg = lifecycle_cfg if lifecycle_cfg is not None \
            else ReplicaLifecycleConfig()
        self._heal = self.lifecycle_cfg.enabled
        self.lifecycle = ReplicaLifecycle(
            self.lifecycle_cfg, replicas,
            clock=lifecycle_clock if lifecycle_clock is not None
            else time.monotonic)
        # Planned drains (rolling reload of a sole replica): dispatch
        # stops but the engine keeps stepping its in-flight work, unlike
        # _dead whose engines never step again.
        self._draining: set = set()
        self._warmed = False
        self._reload: Optional[dict] = None
        # Outcome of the most recent rolling reload (None until one ran):
        # the deployment controller polls this to learn whether its
        # promotion completed or aborted mid-roll.
        self.last_reload_ok: Optional[bool] = None
        # Shadow-traffic tap (serving.deploy): when set, every client
        # submit is offered to the callable as (prompt_token_ids, params,
        # live_request) AFTER dispatch — the tap mirrors a sampled
        # fraction onto a canary engine; its results never reach clients
        # and a tap failure never breaks a client submit.
        self.shadow_tap = None
        # Known-good weights for quarantine rebuilds: a host snapshot of
        # the boot tree (only paid when healing is on); a completed
        # rolling reload replaces it with the new tree.
        self._weights_host = None
        self._canary_digest: Optional[str] = None
        if self._heal:
            self._weights_host = jax.device_get(params)
            toks = self._run_canary(self.engines[0])
            if toks is not None:
                self._canary_digest = canary_digest(toks)
            else:
                self.logger.warning(
                    "lifecycle: canary digest could not be pinned at "
                    "construction; probes will gate on generation "
                    "success only")

    # ------------------------------------------------------------------
    def _load(self, eng: InferenceEngine) -> int:
        return len(eng.waiting) + eng.num_active

    def live_engines(self) -> List[InferenceEngine]:
        return [e for i, e in enumerate(self.engines)
                if i not in self._dead and i not in self._draining]

    @property
    def num_live(self) -> int:
        return len(self.engines) - len(self._dead | self._draining)

    def _sticky_target(self, key: str,
                       live: List[InferenceEngine]) -> InferenceEngine:
        """Rendezvous (highest-random-weight) hashing: every live replica
        scores sha256(key:replica_index); the max wins. Removing a
        replica re-ranks only the keys it owned — the property that keeps
        the rest of the fleet's caches warm through a failover."""
        def score(eng: InferenceEngine) -> bytes:
            idx = self.engines.index(eng)
            return hashlib.sha256(f"{key}:{idx}".encode()).digest()

        return max(live, key=score)

    def submit(self, prompt_token_ids: Sequence[int],
               params: Optional[SamplingParams] = None,
               request_id: Optional[str] = None,
               affinity_key: Optional[str] = None,
               adapter: str = "", trace_id: str = "") -> Request:
        """Dispatch to the least-loaded live replica (round-robin
        tiebreak) — or, with an ``affinity_key``, to its sticky
        rendezvous-hash target unless that replica's backlog exceeds its
        decode slots by more than ``affinity_spill_threshold``.

        ``adapter`` names a registered LoRA adapter; the catalog is
        process-global, so any replica can resolve it (each replica pins
        it into its own pool at admission). On failover the adapter name
        rides the Request — the survivor re-acquires from its own pool.
        """
        live = self.live_engines()
        if not live:
            raise RuntimeError("all replicas dead (step faults); "
                               "engine cannot accept requests")
        eng = None
        if affinity_key:
            sticky = self._sticky_target(affinity_key, live)
            backlog = self._load(sticky) - sticky.cfg.max_seqs
            if backlog <= self.affinity_spill_threshold:
                eng = sticky
                self.affinity["sticky"] += 1
            else:
                self.affinity["spill"] += 1
        if eng is None:
            order = (live[self._rr % len(live):]
                     + live[:self._rr % len(live)])
            self._rr = (self._rr + 1) % len(live)
            eng = min(order, key=self._load)
        if request_id is None:
            request_id = f"rep-req-{next(self._req_counter)}"
        req = eng.submit(prompt_token_ids, params, request_id,
                         trace_id=trace_id,
                         **({"adapter": adapter} if adapter else {}))
        req.replica = self.engines.index(eng)
        tap = self.shadow_tap
        if tap is not None:
            try:
                tap(list(prompt_token_ids), params, req)
            except Exception:  # noqa: BLE001 — shadow never hurts clients
                self.logger.debug("shadow tap raised", exc_info=True)
        return req

    @property
    def has_work(self) -> bool:
        return any(e.has_work for e in self.engines)

    def step(self) -> List[Request]:
        """One scheduler iteration on every live replica that has work.

        jit dispatch is async, so each replica's device program launches
        before the next replica's host-side scheduling runs — the chips
        decode concurrently even though this is one Python loop.

        A replica whose step raises is failed over (see
        :meth:`_fail_replica`); the exception never escapes, so one
        replica fault can no longer orphan requests on healthy replicas
        mid-drain (the old ``generate()`` bug) or error the whole fleet.
        """
        finished: List[Request] = []
        for i, eng in enumerate(self.engines):
            if i in self._dead or not eng.has_work:
                continue
            try:
                self._step_counts[i] += 1
                if (self._fault_inject is not None
                        and self._fault_inject[0] == i
                        and self._step_counts[i] == self._fault_inject[1]):
                    if self._fault_inject[2] == "nan-logits":
                        # Poison the replica's params so this step's REAL
                        # forward emits NaN logits — the engine's numeric
                        # guard (not this hook) must catch it before any
                        # garbage token streams.
                        self._poison_params_nan(eng, i)
                    elif self._fault_inject[2] == "preempt":
                        # Planned preemption notice: drain via live KV
                        # migration (no fault dump — nothing is broken),
                        # then quarantine; the probe reinstates shortly.
                        self.logger.warning(
                            "chaos: preemption notice for replica %d at "
                            "step %d", i, self._step_counts[i])
                        finished.extend(self.drain_replica(i))
                        continue
                    else:
                        raise ReplicaFault(
                            f"gateway.fault_inject_step: injected fault on "
                            f"replica {i} step {self._step_counts[i]}")
                finished.extend(eng.step())
            except Exception as e:  # noqa: BLE001 — isolate per replica
                finished.extend(self._fail_replica(i, e))
        self._lifecycle_tick()
        return finished

    def _poison_params_nan(self, eng: InferenceEngine, idx: int) -> None:
        """nan-logits chaos: overwrite the first float param leaf of one
        replica with NaN (on that replica's own devices) — the honest
        silent-corruption simulation; detection is the engine guard's
        job."""
        import jax.numpy as jnp

        leaves, treedef = jax.tree_util.tree_flatten(eng.executor.params)
        for j, leaf in enumerate(leaves):
            if (hasattr(leaf, "dtype")
                    and jnp.issubdtype(leaf.dtype, jnp.inexact)):
                poisoned = jax.device_put(
                    jnp.full(leaf.shape, jnp.nan, leaf.dtype),
                    leaf.sharding)
                leaves[j] = poisoned
                break
        eng.executor.params = jax.tree_util.tree_unflatten(
            treedef, leaves)
        self.logger.warning(
            "chaos: poisoned replica %d params with NaN (nan-logits "
            "fault injection)", idx)

    def _fail_replica(self, idx: int, exc: Exception) -> List[Request]:
        """Mark replica ``idx`` dead and fail its requests over.

        The faulted engine's device state is suspect, so nothing is
        salvaged from it: its slots are detached host-side (no block frees
        — the pool dies with the engine) and every stranded request is
        resubmitted least-loaded onto a survivor, where admission
        recomputes prompt + generated-so-far exactly like re-admission
        after preemption. Requests over ``max_retries`` (or with no
        survivors) finish as ``"error"`` and are returned so callers see
        them retire."""
        self._dead.add(idx)
        self._draining.discard(idx)
        # Lifecycle: with healing on this is a quarantine — the probe
        # loop rebuilds the engine from known-good weights and canaries
        # it back to live (unless the flap breaker evicts); with healing
        # off it is the legacy permanent death.
        if self._heal:
            self.lifecycle.on_fault(idx)
        else:
            self.lifecycle.mark_dead(idx)
        self.failover["replica_faults"] += 1
        eng = self.engines[idx]
        from dlti_tpu.telemetry import get_recorder

        rec = get_recorder()
        if rec is not None:
            # Black box before failover rewrites the dead replica's
            # bookkeeping: which replica died, with what, holding what.
            rec.dump(reason="replica_fault", exc=exc, force=True,
                     extra={"replica": idx,
                            "in_flight": eng.num_active,
                            "queued": len(eng.waiting),
                            "survivors": self.num_live})
        self.logger.error(
            "replica %d step failed (%s: %s); failing over %d in-flight + "
            "%d queued request(s) to %d survivor(s)", idx, type(exc).__name__,
            exc, eng.num_active, len(eng.waiting), self.num_live)
        stranded: List[Request] = []
        for slot in eng.slots:
            if slot.request is not None and not slot.request.done:
                stranded.append(slot.request)
            # Detach host bookkeeping only: the dead engine's pool and KV
            # are abandoned wholesale, never reused.
            slot.request = None
            slot.blocks = []
            slot.seq_len = 0
            slot.next_pos = 0
            slot.prefill_end = 0
        stranded.extend(eng.waiting)
        eng.waiting.clear()

        errored: List[Request] = []
        live = self.live_engines()
        from dlti_tpu.telemetry.ledger import note_requeue

        for req in stranded:
            if not live or req.num_retries >= self.max_retries:
                if (not live and req.num_retries < self.max_retries
                        and self.failover_fallback is not None):
                    note_requeue(req, "failover")
                    if self.failover_fallback(req):
                        req.num_retries += 1
                        self.failover["retries"] += 1
                        continue
                req.finish_reason = "error"
                req.finish_time = time.monotonic()
                self.failover["failover_errors"] += 1
                self.telemetry.on_finished(req)
                # Visible in the finished ring so the server's event drain
                # (which walks slots + finished) delivers the error.
                eng.finished.append(req)
                errored.append(req)
                continue
            req.num_retries += 1
            self.failover["retries"] += 1
            # Critical-path attribution: the wait from here to
            # re-admission on the survivor books as "failover", not as
            # inflated prefill/decode (telemetry.ledger.note_requeue).
            note_requeue(req, "failover")
            target = min(live, key=self._load)
            target.resubmit(req)
            req.replica = self.engines.index(target)
        return errored

    # -- Replica lifecycle: drain/migrate, rebuild, canary, reload ------
    def _rehome(self, req: Request, eng: InferenceEngine,
                survivors: List[InferenceEngine], kind: str,
                ) -> List[Request]:
        """Failover-style resubmit of one request onto a survivor
        (recompute-on-readmit); errors it out past the retry cap or with
        no survivors (after offering the disagg rescue hook). Returns
        the request iff it errored."""
        from dlti_tpu.telemetry.ledger import note_requeue

        if not survivors or req.num_retries >= self.max_retries:
            if (not survivors and req.num_retries < self.max_retries
                    and self.failover_fallback is not None):
                note_requeue(req, kind)
                if self.failover_fallback(req):
                    req.num_retries += 1
                    self.failover["retries"] += 1
                    return []
            req.finish_reason = "error"
            req.finish_time = time.monotonic()
            self.failover["failover_errors"] += 1
            self.telemetry.on_finished(req)
            eng.finished.append(req)
            return [req]
        req.num_retries += 1
        self.failover["retries"] += 1
        note_requeue(req, kind)
        target = min(survivors, key=self._load)
        target.resubmit(req)
        req.replica = self.engines.index(target)
        return []

    def drain_replica(self, idx: int, *, kind: str = "preempt",
                      quarantine: bool = True) -> List[Request]:
        """Planned drain of one replica: move its in-flight decodes to
        survivors over the paged-KV handoff path (``export_handoff`` /
        ``adopt_handoff``) — generated-so-far tokens and the slot's rng
        stream survive byte-exactly, no re-prefill — falling back to a
        failover-style resubmit when handoff fails; queued and
        mid-prefill requests (nothing decodable to migrate) resubmit
        directly. With ``quarantine`` the replica then enters the
        lifecycle (healing on: quarantined → probe → live; healing off:
        dead); the rolling-reload driver passes ``quarantine=False`` and
        swaps weights itself. Returns the requests that errored out."""
        eng = self.engines[idx]
        self.lifecycle.begin_drain(idx)
        self._dead.add(idx)
        self._draining.discard(idx)
        survivors = self.live_engines()
        from dlti_tpu.telemetry.ledger import note_requeue

        migrated = fallbacks = 0
        errored: List[Request] = []
        for slot in list(eng.slots):
            req = slot.request
            if req is None or req.done:
                continue
            # The wall time from here to re-admission on the survivor
            # books as a requeue stall of this kind (the survivor's
            # adopt/admit closes the mark), not as inflated decode.
            note_requeue(req, kind)
            snap = None
            if survivors and not slot.prefilling:
                snap = eng.export_handoff(slot)
            if snap is not None:
                adopted = False
                for target in sorted(survivors, key=self._load):
                    if target.adopt_handoff(snap):
                        req.num_migrations += 1
                        req.replica = self.engines.index(target)
                        migrated += 1
                        adopted = True
                        break
                if adopted:
                    continue
                fallbacks += 1
            elif survivors and not slot.prefilling:
                fallbacks += 1
            # export_handoff leaves the slot intact on failure; release
            # it (the drained engine stays healthy — blocks go back to
            # its pool) and fail the request over.
            if slot.request is not None:
                eng._release(slot)
            errored.extend(self._rehome(req, eng, survivors, kind))
        stranded = list(eng.waiting)
        eng.waiting.clear()
        for req in stranded:
            errored.extend(self._rehome(req, eng, survivors, kind))
        if migrated:
            self.lifecycle.note_migration(migrated)
        if fallbacks:
            self.lifecycle.note_migration_fallback(fallbacks)
        self.logger.warning(
            "replica %d drained (%s): %d decode(s) migrated via KV "
            "handoff, %d re-prefill fallback(s), %d queued rehomed, %d "
            "errored", idx, kind, migrated, fallbacks, len(stranded),
            len(errored))
        if quarantine:
            if self._heal:
                self.lifecycle.on_fault(idx)
            else:
                self.lifecycle.mark_dead(idx)
        return errored

    def _rebuild_replica(self, idx: int, host_params=None) -> None:
        """Fresh engine for one replica from a host weight tree, on the
        replica's own device group. The fleet's SHARED telemetry is
        threaded through — a rebuilt replica keeps booking into the same
        histograms, and requests that later fail over again keep their
        ``stall_s`` phase attribution in ``request_breakdown()``."""
        host = host_params if host_params is not None else self._weights_host
        if host is None:
            raise RuntimeError(
                "no weights snapshot to rebuild from (lifecycle healing "
                "was disabled at construction)")
        old = self.engines[idx]
        mesh = self._meshes[idx]
        rep_params = (host if mesh is not None
                      else jax.device_put(host, self._groups[idx][0]))
        eng = InferenceEngine(self._model_cfg, rep_params,
                              self._rep_cfgs[idx], self._lora_cfg,
                              mesh=mesh, telemetry=self.telemetry)
        eng.prefill_only = old.prefill_only
        self.engines[idx] = eng
        if self._warmed and not eng.prefill_only:
            eng.warmup_decode_ladder()

    def _run_canary(self, eng: InferenceEngine) -> Optional[List[int]]:
        """Short greedy canary generation on one engine (only ever an
        engine carrying no live traffic: a rebuilt quarantined replica,
        or replica 0 at construction before any dispatch). Returns the
        emitted token ids, or None when generation fails — a NaN-poisoned
        replica trips the engine's numeric guard here, never in front of
        a client."""
        cfg = self.lifecycle_cfg
        vocab = max(2, self._model_cfg.vocab_size)
        prompt = [(i % min(97, vocab - 1)) + 1
                  for i in range(max(1, cfg.canary_prompt_tokens))]
        sp = SamplingParams(temperature=0.0,
                            max_tokens=max(1, cfg.canary_max_tokens))
        prev = eng.prefill_only
        eng.prefill_only = False
        try:
            req = eng.submit(prompt, sp,
                             f"canary-{next(self._req_counter)}")
            for _ in range(1000):
                if req.done:
                    break
                eng.step()
            if not req.done or req.finish_reason == "error":
                return None
            return list(req.output_token_ids)
        except Exception as e:  # noqa: BLE001 — a failed canary is a verdict
            self.logger.warning("canary generation failed: %s", e)
            return None
        finally:
            eng.prefill_only = prev

    def _probe_replica(self, idx: int) -> None:
        """Probation elapsed: rebuild the quarantined replica from
        known-good weights and gate reinstatement on the canary matching
        the pinned digest."""
        self.lifecycle.begin_probe(idx)
        toks = None
        try:
            self._rebuild_replica(idx)
            toks = self._run_canary(self.engines[idx])
        except Exception as e:  # noqa: BLE001 — a failed rebuild re-quarantines
            self.logger.error("replica %d rebuild/canary raised: %s", idx, e)
        ok = toks is not None and (
            self._canary_digest is None
            or canary_digest(toks) == self._canary_digest)
        if self.lifecycle.on_probe_result(idx, ok) == "live":
            self._dead.discard(idx)

    def request_reload(self, weights_provider, *, verify=None) -> bool:
        """Enqueue a rolling weight reload (thread-safe: one GIL-atomic
        attribute write; the roll itself runs on the stepper thread).
        ``weights_provider()`` is called once there and must return a
        host param tree with the boot tree's structure — the server's
        /v1/reload handler wraps a verified checkpoint-store load.
        ``verify()``, when given, is re-run immediately before EVERY
        per-replica swap (not just at the initial load): an export whose
        bytes rot mid-roll aborts the roll before the next replica
        touches it, instead of canary-failing halfway through. Returns
        False if a roll is already in progress."""
        if self._reload is not None:
            return False
        self._reload = {"provider": weights_provider, "host": None,
                        "queue": None, "digest": None, "verify": verify}
        return True

    def _reload_tick(self) -> None:
        """One rolling-reload action per step: drain-via-migration one
        replica, swap in the new weights, canary, reinstate — clients on
        other replicas never notice. The first upgraded replica pins the
        new canary digest with a determinism double-run; a canary failure
        aborts the roll (the failed replica re-quarantines and heals back
        onto the PREVIOUS weights — the fleet stays consistent)."""
        st = self._reload
        if st["host"] is None:
            try:
                st["host"] = st["provider"]()
            except Exception as e:  # noqa: BLE001 — bad checkpoint aborts roll
                self.logger.error(
                    "rolling reload aborted: weights provider failed: %s", e)
                self.last_reload_ok = False
                self._reload = None
                return
            st["queue"] = [i for i in range(len(self.engines))
                           if self.lifecycle.state(i) != "evicted"]
            self.logger.info("rolling reload: %d replica(s) queued",
                             len(st["queue"]))
        if not st["queue"]:
            self._weights_host = st["host"]
            if st["digest"] is not None:
                self._canary_digest = st["digest"]
            self.last_reload_ok = True
            self._reload = None
            self.logger.info("rolling reload complete")
            return
        idx = st["queue"][0]
        if st.get("verify") is not None:
            # Re-verify the export bytes before EVERY swap, not just the
            # initial provider load — a reload source corrupted mid-roll
            # (disk fault, concurrent overwrite) aborts here, before the
            # next replica is drained, instead of burning a drain +
            # rebuild on weights the canary would reject anyway. The
            # replicas already swapped keep the verified tree they loaded.
            ok_verify = False
            try:
                ok_verify = bool(st["verify"]())
            except Exception as e:  # noqa: BLE001 — verify fault = fail
                self.logger.error("reload re-verify raised: %s", e)
            if not ok_verify:
                self.logger.error(
                    "rolling reload aborted: export failed re-verification "
                    "before replica %d swap; fleet keeps serving (%d "
                    "replica(s) already on new weights stay)", idx,
                    len(self.engines) - len(st["queue"]))
                self.last_reload_ok = False
                self._reload = None
                return
        eng = self.engines[idx]
        others = [e for i, e in enumerate(self.engines)
                  if i != idx and i not in self._dead
                  and i not in self._draining]
        if others:
            self.drain_replica(idx, quarantine=False)
        else:
            # Sole live replica: no migration target. Lame-duck it (stop
            # dispatch, keep stepping) and wait for in-flight work to
            # finish before swapping; the gateway queues/sheds meanwhile.
            if idx not in self._draining and idx not in self._dead:
                self.lifecycle.begin_drain(idx)
                self._draining.add(idx)
            if eng.has_work:
                return
            self._draining.discard(idx)
            self._dead.add(idx)
        toks = None
        try:
            self._rebuild_replica(idx, host_params=st["host"])
            toks = self._run_canary(self.engines[idx])
        except Exception as e:  # noqa: BLE001 — failed swap handled below
            self.logger.error("replica %d reload rebuild failed: %s", idx, e)
        ok = toks is not None
        if ok and st["digest"] is None:
            # First replica on the new weights: nothing to compare
            # against, so gate on determinism (two identical greedy
            # runs) and pin the digest the rest of the roll checks.
            ok = self._run_canary(self.engines[idx]) == toks
            if ok:
                st["digest"] = canary_digest(toks)
        elif ok:
            ok = canary_digest(toks) == st["digest"]
        st["queue"].pop(0)
        if self.lifecycle.on_probe_result(idx, ok) == "live":
            self._dead.discard(idx)
        if not ok:
            self.logger.error(
                "rolling reload aborted: replica %d failed canary on new "
                "weights; fleet stays on previous weights", idx)
            self.last_reload_ok = False
            self._reload = None

    def _lifecycle_tick(self) -> None:
        """End-of-step lifecycle work, at most one heavy action per tick
        (bounded step latency): advance a rolling reload, else probe one
        quarantined replica whose probation elapsed. Runs on the stepper
        thread — the only thread allowed to touch slots/engines."""
        if self._reload is not None:
            self._reload_tick()
            return
        if not self._heal:
            return
        due = self.lifecycle.due_probes()
        if due:
            self._probe_replica(due[0])

    @property
    def lifecycle_pending(self) -> bool:
        """True when the stepper must keep ticking without client work —
        a reload is rolling or a quarantined replica awaits its probe.
        The server's AsyncEngine polls instead of parking on its event
        when this is set."""
        if self._reload is not None:
            return True
        if not self._heal:
            return False
        return any(s in ("quarantined", "probing")
                   for s in self.lifecycle.states().values())

    def lifecycle_counts(self) -> dict:
        """/health summary: ``quarantined`` replicas are healing (probe
        pending/running) and expected back; ``dead`` ones (flap-evicted,
        or faulted with healing off) are gone for good."""
        c = self.lifecycle.counts()
        return {"live": c["live"],
                "quarantined": c["quarantined"] + c["probing"],
                "draining": c["draining"],
                "dead": c["evicted"]}

    def generate(self, prompts: Sequence[Sequence[int]],
                 params: Optional[SamplingParams] = None,
                 ) -> List[GenerationResult]:
        """Offline batch generation across all replicas. Per-replica step
        faults fail over inside :meth:`step`, so a single replica death
        mid-drain no longer orphans requests on healthy replicas."""
        reqs = [self.submit(p, params) for p in prompts]
        while self.has_work:
            self.step()
        out = []
        for r in reqs:
            eng = self.engines[r.replica]
            out.append(eng._result(r))
        return out

    # -- InferenceEngine-compat surface (AsyncEngine / gateway) ---------
    def warmup_decode_ladder(self) -> None:
        self._warmed = True  # rebuilt replicas re-warm before reinstating
        for e in self.engines:
            e.warmup_decode_ladder()

    @property
    def cfg(self) -> EngineConfig:
        return self.engines[0].cfg

    @property
    def slots(self) -> list:
        return [s for e in self.engines for s in e.slots]

    @property
    def finished(self) -> List[Request]:
        return [r for e in self.engines for r in e.finished]

    @property
    def waiting(self) -> List[Request]:
        return [r for e in self.engines for r in e.waiting]

    @property
    def num_active(self) -> int:
        return sum(e.num_active for e in self.engines)

    @property
    def num_free_blocks(self) -> int:
        return sum(e.num_free_blocks for e in self.live_engines())

    def abort_all(self, reason: str = "abort") -> List[Request]:
        aborted: List[Request] = []
        for i, e in enumerate(self.engines):
            if i not in self._dead:
                aborted.extend(e.abort_all(reason=reason))
        return aborted

    @property
    def stats(self) -> dict:
        """Aggregated counters across replicas (per-replica under 'replicas')."""
        keys = self.engines[0].stats.keys()
        agg = {k: sum(e.stats[k] for e in self.engines) for k in keys}
        agg["replicas"] = [dict(e.stats) for e in self.engines]
        return agg
