"""Fleet engine worker: one ``InferenceEngine`` behind the wire protocol.

``EngineWorker`` serves a single supervisor connection at a time (strict
request/response — the supervisor is the only client) and survives garbage
input: a malformed, truncated, oversized, or digest-failing frame gets an
FT_ERROR reply where possible, then the connection is dropped and the
accept loop continues. The worker process never dies from bad bytes; only
the supervisor decides evictions.

Token streaming works by delta: each FT_STEP reply carries, per in-flight
request, the tokens/logprobs appended since the previous report plus the
finish reason once done — the supervisor applies them to its mirror
``Request`` objects, so the HTTP layer's event drain works unchanged
against mirrors. FT_HEALTH doubles as the heartbeat and exports the
worker's metrics registry snapshot for supervisor-side federation.
"""

from __future__ import annotations

import os
import socket
import time
from typing import Any, Callable, Dict, List, Optional, Set

from dlti_tpu.serving import wire
from dlti_tpu.utils.logging import get_logger


def _numeric_only(d: dict) -> dict:
    return {k: v for k, v in d.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


class EngineWorker:
    """Wrap one engine behind the fleet wire protocol on a TCP socket."""

    def __init__(self, engine, *, host: str = "127.0.0.1", port: int = 0,
                 worker_id: int = 0, registry=None,
                 reload_fn: Optional[Callable[[Any], Any]] = None,
                 max_frame_bytes: int = wire.DEFAULT_MAX_FRAME,
                 tracer=None, span_ship_max: int = 512):
        self.engine = engine
        self.worker_id = worker_id
        self.registry = registry
        self.logger = get_logger()
        # Span federation: this worker ships its span-ring tail
        # incrementally in FT_STEP/FT_HEALTH replies (cursor = total
        # appends, so ring eviction between ships is counted, not
        # silent). None = the engine's tracer (the process-global one in
        # a real worker process); tests pass private per-worker tracers
        # so thread-fleet fakes get genuinely distinct rings.
        self.tracer = tracer if tracer is not None \
            else getattr(engine.telemetry, "tracer", None)
        self.span_ship_max = span_ship_max
        self._span_cursor = 0
        # Last clock offset the supervisor estimated for this worker
        # (supervisor_clock ≈ our_clock + offset) — echoed down in
        # step/health requests and persisted into flight-dump context so
        # postmortem --all can merge per-worker dumps onto one clock.
        self._clock_offset: Optional[dict] = None
        # Rolling reload: rebuilds the engine from a host param tree
        # (shipped over the wire by the supervisor). None = unsupported.
        self._reload_fn = reload_fn
        self.max_frame_bytes = max_frame_bytes
        self._owned: Set[str] = set()        # request ids this worker holds
        self._reported: Dict[str, int] = {}  # tokens already reported per id
        self._stop = False
        self._conn: Optional[socket.socket] = None  # live supervisor conn
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(2)
        self.host, self.port = self._listener.getsockname()[:2]

    # -- lifecycle -----------------------------------------------------
    def close(self) -> None:
        self._stop = True
        # shutdown() first: closing a listening socket from another thread
        # does not wake a serve thread parked in accept() on Linux, so
        # whether close() ended the thread depended on which of the two
        # got there first.
        for end in (lambda: self._listener.shutdown(socket.SHUT_RDWR),
                    self._listener.close):
            try:
                end()
            except OSError:
                pass
        # Unblock a serve thread parked in recv on the live connection —
        # without this, close() from another thread (or the in-process
        # test fake's kill path) leaves the worker hung mid-frame.
        conn = self._conn
        if conn is not None:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass

    def serve_forever(self) -> None:
        while not self._stop:
            try:
                conn, peer = self._listener.accept()
            except OSError:
                return  # listener closed
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._conn = conn
            self.logger.info("worker %d: supervisor connected from %s",
                             self.worker_id, peer)
            try:
                self._serve_connection(conn)
            finally:
                self._conn = None
                try:
                    conn.close()
                except OSError:
                    pass
            if self._stop:
                return

    def _serve_connection(self, conn: socket.socket) -> None:
        while not self._stop:
            try:
                ftype, payload = wire.recv_frame(conn, self.max_frame_bytes)
            except wire.WireClosed:
                self.logger.info("worker %d: supervisor disconnected",
                                 self.worker_id)
                return
            except wire.WireError as e:
                # Garbage input never kills the worker: best-effort error
                # reply, then drop the connection and re-accept. The
                # stream past a framing error is unparseable, so the
                # connection cannot be salvaged.
                self.logger.warning("worker %d: protocol error: %s",
                                    self.worker_id, e)
                try:
                    wire.send_frame(conn, wire.FT_ERROR, wire.pack_obj(
                        {"error": f"{type(e).__name__}: {e}"}))
                except wire.WireError:
                    pass
                return
            try:
                reply = self._dispatch(ftype, wire.unpack_obj(payload))
            except Exception as e:  # noqa: BLE001 — handler isolation
                self.logger.exception("worker %d: %s handler failed",
                                      self.worker_id,
                                      wire.FRAME_NAMES.get(ftype, ftype))
                self._dump_fault(ftype, e)
                try:
                    wire.send_frame(conn, wire.FT_ERROR, wire.pack_obj(
                        {"error": f"{type(e).__name__}: {e}"}))
                except wire.WireError:
                    return
                continue
            try:
                wire.send_frame(conn, wire.FT_OK, wire.pack_obj(reply))
            except wire.WireError:
                return
            if self._stop:
                return

    def _dump_fault(self, ftype: int, exc: Exception) -> None:
        from dlti_tpu.telemetry import get_recorder

        rec = get_recorder()
        if rec is not None and ftype == wire.FT_STEP:
            # Black box before the supervisor tears this process down:
            # the per-worker dump dir + DLTI_PROCESS_ID tag make this
            # discoverable by postmortem.py --all incident merging.
            rec.dump(reason="worker_step_fault", exc=exc, force=True,
                     extra={"worker": self.worker_id,
                            "in_flight": self.engine.num_active,
                            "queued": len(self.engine.waiting)})

    # -- dispatch ------------------------------------------------------
    def _dispatch(self, ftype: int, obj: Any) -> Any:
        if ftype == wire.FT_SUBMIT:
            return self._on_submit(obj)
        if ftype == wire.FT_STEP:
            return self._on_step(obj)
        if ftype == wire.FT_DRAIN:
            return self._on_drain(obj)
        if ftype == wire.FT_ADOPT:
            return self._on_adopt(obj)
        if ftype == wire.FT_HEALTH:
            return self._on_health(obj)
        if ftype == wire.FT_ABORT:
            return self._on_abort(obj)
        if ftype == wire.FT_RELOAD:
            return self._on_reload(obj)
        if ftype == wire.FT_SHUTDOWN:
            self._stop = True
            return {"ok": True}
        raise wire.WireError(f"unexpected frame type {ftype}")

    def _gauges(self) -> dict:
        eng = self.engine
        return {"active": eng.num_active, "waiting": len(eng.waiting),
                "free_blocks": eng.num_free_blocks,
                "has_work": bool(eng.has_work)}

    def _span_tail(self) -> dict:
        """Unshipped span-ring tail for step/health replies (empty dict
        when tracing is off — replies stay byte-light and old supervisors
        reading with .get() see nothing new)."""
        tracer = self.tracer
        if tracer is None or not tracer.enabled:
            return {}
        evs, dropped, self._span_cursor = tracer.events_since(
            self._span_cursor, self.span_ship_max)
        if not evs and not dropped:
            return {}
        return {"spans": evs, "spans_dropped": dropped}

    def _note_clock(self, obj: Any) -> None:
        """Record the supervisor's offset estimate for this worker's
        clock (rides down in step/health requests). Kept on the instance
        and mirrored into the flight recorder context, so a dump from
        this process carries enough to rebase its span tail."""
        if not isinstance(obj, dict) or "clock_offset" not in obj:
            return
        off = {"clock_offset_s": obj.get("clock_offset"),
               "clock_uncertainty_s": obj.get("clock_uncertainty")}
        if off == self._clock_offset:
            return
        self._clock_offset = off
        from dlti_tpu.telemetry import get_recorder

        rec = get_recorder()
        if rec is not None:
            rec.note(**off)

    def _on_submit(self, obj: dict) -> dict:
        desc = obj["request"]
        if obj.get("resubmit"):
            # Failover/rehome of an existing request: keep id, params, and
            # generated-so-far tokens — admission recomputes prompt+output
            # exactly like re-admission after preemption.
            req = wire.request_from_wire(desc)
            self.engine.resubmit(req)
        else:
            params = wire.request_from_wire(desc).params
            adapter = desc.get("adapter", "")
            req = self.engine.submit(
                desc["prompt_token_ids"], params, desc["request_id"],
                # Adopt the supervisor's trace context so every process's
                # spans for this request join one timeline (absent from
                # old supervisors: submit mints a local id instead).
                trace_id=desc.get("trace_id", "") or "",
                **({"adapter": adapter} if adapter else {}))
            req.tenant = desc.get("tenant", "")
            req.priority = desc.get("priority", "")
        self._owned.add(req.request_id)
        self._reported[req.request_id] = len(req.output_token_ids)
        return {"ok": True, **self._gauges()}

    def _on_step(self, obj: dict) -> dict:
        self._note_clock(obj)
        for rid in obj.get("cancels") or ():
            for req in list(self.engine.waiting):
                if req.request_id == rid:
                    req.cancel_requested = True
            for slot in self.engine.slots:
                if (slot.request is not None
                        and slot.request.request_id == rid):
                    slot.request.cancel_requested = True
        if self.engine.has_work:
            self.engine.step()
        events: List[dict] = []
        live = [s.request for s in self.engine.slots
                if s.request is not None]
        live.extend(r for r in list(self.engine.finished)
                    if r.request_id in self._owned)
        for req in live:
            rid = req.request_id
            if rid not in self._owned:
                continue
            seen = self._reported.get(rid, 0)
            ev = {"id": rid,
                  "tokens": list(req.output_token_ids[seen:]),
                  "logprobs": list(req.output_logprobs[seen:]),
                  "preemptions": req.num_preemptions}
            self._reported[rid] = len(req.output_token_ids)
            if req.done:
                ev["finish_reason"] = req.finish_reason
                self._owned.discard(rid)
                self._reported.pop(rid, None)
            if ev["tokens"] or "finish_reason" in ev:
                events.append(ev)
        # "time" gives the supervisor a clock-offset sample on every step
        # RPC (busy workers rarely see FT_HEALTH); the span tail
        # piggybacks so federation lag is one step, not one heartbeat.
        return {"events": events, "stats": dict(self.engine.stats),
                "time": time.monotonic(), **self._span_tail(),
                **self._gauges()}

    def _on_drain(self, obj: dict) -> dict:
        """Export every decodable in-flight request as a handoff envelope
        (queued / mid-prefill ones, with nothing decodable to migrate,
        return as plain resubmit descriptors). The worker keeps nothing:
        its engine ends empty either way."""
        eng = self.engine
        envelopes: List[bytes] = []
        resubmits: List[dict] = []
        for slot in list(eng.slots):
            req = slot.request
            if req is None or req.done:
                continue
            snap = None
            if not slot.prefilling:
                snap = eng.export_handoff(slot)
            if snap is not None:
                envelopes.append(wire.pack_handoff(snap))
            else:
                # export_handoff leaves the slot intact on failure;
                # release it (blocks return to this healthy engine's
                # pool) and hand the request back for a resubmit.
                if slot.request is not None:
                    eng._release(slot)
                resubmits.append(wire.request_to_wire(req))
            self._owned.discard(req.request_id)
            self._reported.pop(req.request_id, None)
        for req in list(eng.waiting):
            resubmits.append(wire.request_to_wire(req))
            self._owned.discard(req.request_id)
            self._reported.pop(req.request_id, None)
        eng.waiting.clear()
        return {"handoffs": envelopes, "resubmits": resubmits,
                **self._gauges()}

    def _on_adopt(self, obj: dict) -> dict:
        snap = wire.unpack_handoff(obj["envelope"])
        req = snap["request"]
        adopted = bool(self.engine.adopt_handoff(snap))
        if adopted:
            self._owned.add(req.request_id)
            # The supervisor's mirror already streamed the generated-so-far
            # tokens; report only what this worker produces from here on.
            self._reported[req.request_id] = len(req.output_token_ids)
        return {"adopted": adopted, **self._gauges()}

    def _on_health(self, obj: Any) -> dict:
        self._note_clock(obj)
        metrics: Dict[str, float] = {}
        if self.registry is not None:
            metrics = _numeric_only(self.registry.stats_dict())
        return {"ok": True, "pid": os.getpid(),
                "worker_id": self.worker_id, "time": time.monotonic(),
                "stats": dict(self.engine.stats), "metrics": metrics,
                **self._span_tail(), **self._gauges()}

    def _on_abort(self, obj: dict) -> dict:
        reason = (obj or {}).get("reason", "abort")
        aborted = self.engine.abort_all(reason=reason)
        self._owned.clear()
        self._reported.clear()
        return {"ok": True,
                "aborted": [r.request_id for r in aborted],
                **self._gauges()}

    def _on_reload(self, obj: dict) -> dict:
        if self._reload_fn is None:
            raise RuntimeError("this worker cannot reload weights "
                               "(no reload_fn wired)")
        if self.engine.num_active or len(self.engine.waiting):
            raise RuntimeError("reload on a non-drained worker refused")
        self.engine = self._reload_fn(obj["params"])
        self._owned.clear()
        self._reported.clear()
        return {"ok": True}
