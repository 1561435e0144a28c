#!/usr/bin/env python
"""Serving CLI — launch the OpenAI-compatible TPU inference server.

The serving leg the reference claims (vLLM + TP, ``README.md:10,16``) but
never ships (SURVEY.md §0): paged KV cache, continuous batching, streaming
SSE, ``/v1/completions`` + ``/v1/chat/completions``.

Usage:
    # serve a consolidated export written by scripts/train.py --export-dir
    python scripts/serve.py --model-dir exports/run1 \
        --tokenizer meta-llama/Llama-2-7b-hf --port 8000

    # hermetic smoke: random-weight tiny model + byte tokenizer
    python scripts/serve.py --random-init llama_tiny --tokenizer byte

    # disaggregated: 2 prefill + 2 decode replicas, paged-KV handoff
    python scripts/serve.py --random-init llama_tiny --tokenizer byte \
        --disagg --prefill-replicas 2 --decode-replicas 2
"""

from __future__ import annotations

import argparse
import os
import sys
import time

# Source checkout wins over any installed copy; an installed dlti-tpu
# serves scripts run from outside a checkout.
_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.isdir(os.path.join(_repo_root, "dlti_tpu")):
    sys.path.insert(0, _repo_root)
del _repo_root

from dlti_tpu.telemetry.startup import install_compile_listener, mark_startup
from dlti_tpu.utils.platform import enable_compilation_cache

enable_compilation_cache()
# /metrics then says what compiling and fetching programs cost
# (dlti_compile*), and how long after process start each start-up phase
# was passed (dlti_startup_<phase>_seconds).
install_compile_listener()


def parse_args():
    p = argparse.ArgumentParser(description="TPU-native LLM server",
                                formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--model-dir", default=None,
                   help="consolidated export dir (scripts/train.py --export-dir)")
    p.add_argument("--random-init", default=None, metavar="PRESET",
                   help="serve a random-weight model preset (smoke/bench)")
    p.add_argument("--tokenizer", default="meta-llama/Llama-2-7b-hf")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--max-seqs", type=int, default=8, help="decode batch slots")
    p.add_argument("--num-blocks", type=int, default=2048, help="KV pool blocks")
    p.add_argument("--block-size", type=int, default=16, help="tokens per KV block")
    p.add_argument("--max-model-len", type=int, default=2048)
    p.add_argument("--max-tokens-default", type=int, default=256)
    p.add_argument("--enable-prefix-caching", action="store_true",
                   help="reuse KV blocks across requests sharing a prompt "
                        "prefix (content-addressed, LRU-evicted)")
    # -- prefix-cache tiering (dlti_tpu.serving.prefix_tiers) -----------
    p.add_argument("--prefix-host-blocks", type=int, default=0,
                   help="host-RAM prefix tier budget in KV blocks: evicted "
                        "HBM prefix blocks demote here instead of being "
                        "discarded, and restore with one host->device "
                        "scatter instead of a re-prefill (0 = tier off; "
                        "implies --enable-prefix-caching)")
    p.add_argument("--prefix-disk-dir", default="",
                   help="disk prefix tier directory: host-tier overflow "
                        "demotes to digest-verified block dirs here "
                        "(checkpoint-store manifest/SHA-256 format; corrupt "
                        "blocks quarantine to _quarantine/ and read as "
                        "misses)")
    p.add_argument("--prefix-disk-blocks", type=int, default=0,
                   help="disk prefix tier budget in block dirs (0 = disk "
                        "tier off; needs --prefix-disk-dir)")
    p.add_argument("--tensor", type=int, default=1,
                   help="tensor-parallel extent: shard weights + KV pools "
                        "over this many chips (ICI collectives via GSPMD)")
    p.add_argument("--replicas", type=int, default=1,
                   help="data-parallel engine replicas (each tensor-wide); "
                        "a replica whose step faults is excluded and its "
                        "requests fail over to survivors")
    # -- multi-process fleet (dlti_tpu.serving.fleet) -------------------
    p.add_argument("--fleet-workers", type=int, default=0,
                   help="serve from N engine WORKER PROCESSES behind the "
                        "fleet supervisor (TCP wire protocol, per-process "
                        "failure domains): a SIGKILL'd worker is "
                        "respawned and canary-reinstated while its "
                        "in-flight work fails over / migrates; outputs "
                        "are byte-identical to the in-process engine "
                        "(0 = off; overrides --replicas)")
    p.add_argument("--fleet-runtime-dir", default="",
                   help="fleet scratch dir (worker spec, port files, "
                        "per-worker logs); default: a per-PID dir under "
                        "the system temp dir")
    p.add_argument("--fleet-respawn-backoff", type=float, default=0.5,
                   help="initial respawn backoff after a worker death "
                        "(doubles per consecutive failure, capped at 30s)")
    p.add_argument("--fleet-restart-budget", type=int, default=8,
                   help="respawns allowed per worker before it is "
                        "permanently evicted")
    # -- prefill/decode disaggregation (dlti_tpu.serving.disagg) --------
    p.add_argument("--disagg", action="store_true",
                   help="prefill/decode disaggregation: prompts prefill on "
                        "a dedicated pool, then their paged-KV blocks "
                        "migrate to a decode pool — long prefills stop "
                        "inflating neighbours' decode TPOT (overrides "
                        "--replicas; pool sizes below)")
    p.add_argument("--prefill-replicas", type=int, default=1,
                   help="prefill-pool replicas (each tensor-wide; needs "
                        "--disagg)")
    p.add_argument("--decode-replicas", type=int, default=1,
                   help="decode-pool replicas (each tensor-wide; needs "
                        "--disagg)")
    p.add_argument("--handoff-queue-depth", type=int, default=8,
                   help="finished prefills staged per decode replica "
                        "awaiting a free slot; full queues leave prefill "
                        "slots occupied (admission backpressure)")
    p.add_argument("--handoff-deadline-s", type=float, default=0.0,
                   help="staged longer than this re-prefills on the decode "
                        "replica instead of waiting for adoption (0 = "
                        "wait indefinitely)")
    # -- admission gateway (dlti_tpu.serving.gateway) -------------------
    p.add_argument("--gateway", action="store_true",
                   help="enable the admission gateway: bounded queue with "
                        "429 overflow, per-tenant rate limits, "
                        "interactive>batch priority, deadline shed, "
                        "graceful SIGTERM drain")
    p.add_argument("--max-queued-requests", type=int, default=256,
                   help="gateway queue bound (requests); overflow -> 429 "
                        "+ Retry-After")
    p.add_argument("--max-queued-tokens", type=int, default=0,
                   help="gateway queue bound (total queued prompt tokens); "
                        "0 = request bound only")
    p.add_argument("--rate-limit-rps", type=float, default=0.0,
                   help="per-tenant sustained admission rate (req/s); "
                        "0 = off")
    p.add_argument("--rate-limit-burst", type=float, default=0.0,
                   help="per-tenant token-bucket burst capacity; 0 derives "
                        "max(1, 2*rps)")
    p.add_argument("--tenant-weights", default="",
                   help="weighted fair dequeue, e.g. 'teamA:4,teamB:1' "
                        "(unlisted tenants weigh 1)")
    p.add_argument("--drain-grace", type=float, default=30.0,
                   help="seconds SIGTERM waits for in-flight requests "
                        "before exiting anyway")
    p.add_argument("--max-retries", type=int, default=2,
                   help="failover resubmissions per request after a "
                        "replica step fault")
    p.add_argument("--fault-inject-step", default="",
                   help="chaos hook 'REPLICA:STEP[:MODE]': kill that "
                        "replica on its STEP-th step — MODE 'raise' "
                        "(default) raises in place of a device fault; "
                        "'nan-logits' poisons the replica's params so "
                        "the engine's numeric output guard trips the "
                        "same quarantine; 'preempt' simulates a planned "
                        "preemption notice (drain via live KV migration, "
                        "then quarantine) (also env "
                        "DLTI_GATEWAY_FAULT_INJECT)")
    p.add_argument("--self-heal", action="store_true",
                   help="replica lifecycle healing: a faulted replica is "
                        "quarantined, rebuilt from known-good weights, "
                        "and reinstated after a passing canary probe "
                        "(default: a faulted replica stays dead)")
    p.add_argument("--probation", type=float, default=2.0,
                   help="seconds before a quarantined replica's first "
                        "reinstate probe (doubles per failed probe, "
                        "capped at 60s)")
    p.add_argument("--flap-window", type=float, default=300.0,
                   help="flap-breaker window: more than --flap-max-cycles "
                        "quarantines inside this many seconds evicts the "
                        "replica permanently")
    p.add_argument("--flap-max-cycles", type=int, default=3,
                   help="quarantine/reinstate cycles tolerated inside "
                        "--flap-window before permanent eviction")
    p.add_argument("--reload-checkpoint", default="",
                   help="kick off a rolling weight reload at startup "
                        "from this checkpoint-store params export (same "
                        "artifact POST /v1/reload takes); mostly useful "
                        "with --self-heal drills")
    p.add_argument("--no-numeric-guard", action="store_true",
                   help="disable the nonfinite decode-output guard "
                        "(NumericFault -> replica quarantine; leaving it "
                        "on is how a numerically-dead replica never "
                        "streams garbage to users)")
    p.add_argument("--guard-token-storm", type=int, default=0,
                   help="quarantine a replica after N consecutive decode "
                        "steps where every active slot sampled the same "
                        "token (degenerate-output storm; 0 = off)")
    p.add_argument("--no-memory-ledger", action="store_true",
                   help="disable the HBM memory ledger "
                        "(telemetry.memledger): no per-owner attribution, "
                        "/debug/memory, hbm_* gauges, or memory.json in "
                        "flight dumps")
    p.add_argument("--hbm-budget-bytes", type=int, default=0,
                   help="HBM capacity for headroom accounting (0 = "
                        "auto-detect from device memory_stats(); stays "
                        "unknown on CPU, keeping headroom features off)")
    p.add_argument("--admit-min-headroom-frac", type=float, default=0.0,
                   help="defer admitting new requests while ledger "
                        "headroom is below this fraction of capacity "
                        "(0 = off; deferred requests stay queued — "
                        "latency, never a client error)")
    p.add_argument("--affinity", action="store_true",
                   help="cache-affinity routing: sticky rendezvous-hash a "
                        "session key (X-Session header, else hashed prompt "
                        "prefix) to a replica so repeat sessions land on "
                        "warm prefix caches; spills least-loaded past the "
                        "backlog threshold (needs --gateway)")
    p.add_argument("--affinity-spill-threshold", type=int, default=4,
                   help="spill to least-loaded when the sticky replica's "
                        "backlog exceeds its decode slots by more than "
                        "this many requests")
    p.add_argument("--affinity-prefix-tokens", type=int, default=32,
                   help="prompt tokens hashed into the affinity key when "
                        "no X-Session header is present")
    # -- multi-LoRA serving (dlti_tpu.serving.adapters) -----------------
    p.add_argument("--adapter-slots", type=int, default=0,
                   help="HBM adapter-pool slots: one decode batch serves "
                        "up to this many distinct LoRA adapters over ONE "
                        "shared base (S-LoRA-style gathered einsum); "
                        "0 = multi-LoRA off, engine traces identically to "
                        "an adapter-free build")
    p.add_argument("--adapter-rank", type=int, default=16,
                   help="pool rank ceiling R: registered adapters of rank "
                        "<= R zero-pad into the stacked pool (float-exact)")
    p.add_argument("--adapter", action="append", default=[],
                   metavar="NAME=DIR",
                   help="register adapter NAME from checkpoint-store DIR "
                        "(scripts/train.py --export-adapter-dir / "
                        "save_adapter) at startup; repeatable. More can "
                        "hot-load later via POST /v1/adapters")
    p.add_argument("--adapter-map", default="",
                   help="tenant->adapter routing, e.g. "
                        "'teamA:ad1,teamB:ad2': requests without an "
                        "explicit X-Adapter header get their tenant's "
                        "adapter (needs --gateway; X-Adapter always works)")
    p.add_argument("--kv-cache-dtype", default="bfloat16",
                   choices=["bfloat16", "float16", "float32", "int8"],
                   help="KV pool dtype; int8 stores per-row-scaled "
                        "payloads at half the bf16 HBM (roughly double "
                        "the decode slots on a fixed chip)")
    p.add_argument("--quantization", default="none", choices=["none", "int8"],
                   help="weight-only quantization (int8 + per-channel scales; "
                        "~halves weight HBM)")
    p.add_argument("--speculative", default="none", choices=["none", "ngram"],
                   help="n-gram prompt-lookup speculative decoding (exact "
                        "greedy outputs, multiple tokens per model call)")
    p.add_argument("--num-draft-tokens", type=int, default=4)
    p.add_argument("--max-prefill-tokens", type=int, default=0,
                   help="chunked prefill: cap prompt tokens prefilled per "
                        "engine step so decode never stalls a full prompt "
                        "length (latency mode; 0 = unbounded throughput "
                        "mode)")
    p.add_argument("--ngram-size", type=int, default=2,
                   help="trailing n-gram length matched for prompt lookup")
    p.add_argument("--spec-min-acceptance", type=float, default=0.25,
                   help="adaptive speculative gate: pause proposing when "
                        "mean extra tokens per greedy slot-round fall below "
                        "this (0 = always speculate)")
    p.add_argument("--spec-probe-window", type=int, default=64,
                   help="greedy slot-rounds measured before each gate "
                        "decision")
    p.add_argument("--spec-cooldown", type=int, default=32,
                   help="engine rounds the gate pauses a slot's proposing "
                        "for after a failed probe window")
    p.add_argument("--no-spec-adaptive", action="store_true",
                   help="pin the draft length at --num-draft-tokens "
                        "instead of picking it per round from live "
                        "per-slot acceptance (the pow2 draft-length "
                        "ladder; outputs are byte-identical either way)")
    p.add_argument("--trace-dir", default="",
                   help="enable the host-side span tracer (per-request "
                        "lifecycle + engine step phases) and export a "
                        "Chrome-trace JSON here on shutdown; a live "
                        "snapshot is served at GET /debug/trace. Open "
                        "either in Perfetto (ui.perfetto.dev)")
    p.add_argument("--trace-capacity", type=int, default=65536,
                   help="span ring-buffer capacity (most recent events "
                        "kept; a long-lived server never grows past it)")
    # -- self-monitoring (dlti_tpu.telemetry.{watchdog,flightrecorder}) --
    # A /debug/vars time-series ring + /dashboard page are always on.
    p.add_argument("--watchdog", action="store_true",
                   help="enable the anomaly watchdog: throughput "
                        "collapse, gateway queue/shed buildup rules over "
                        "the /debug/vars ring, alerting via "
                        "dlti_watchdog_alerts_total + JSONL event log")
    p.add_argument("--watchdog-action", default="log",
                   choices=["log", "dump", "abort"],
                   help="alert escalation: log only, also dump a flight "
                        "record, or dump + abort the process (CI chaos)")
    p.add_argument("--watchdog-queue-depth", type=int, default=64,
                   help="queue_buildup rule threshold (gateway queue "
                        "depth sustained 3 samples; 0 = rule off)")
    p.add_argument("--watchdog-shed-rate", type=float, default=1.0,
                   help="shed_buildup rule threshold (gateway "
                        "sheds+rejections per second; 0 = rule off)")
    # -- SLO engine (dlti_tpu.telemetry.slo) ---------------------------
    p.add_argument("--slo", action="store_true",
                   help="enable the SLO engine: objectives over the "
                        "request SLIs, rolling error budgets, "
                        "multi-window burn-rate alerting (watchdog "
                        "slo_burn rule), GET /debug/slo, dlti_slo_* "
                        "gauges, slo.json in flight dumps")
    p.add_argument("--slo-window", type=float, default=3600.0,
                   help="SLO compliance / error-budget window seconds")
    p.add_argument("--slo-burn-tiers", default="14:60:5,6:300:30",
                   help="burn-rate alert tiers 'factor:long_s:short_s,"
                        "...' — fires when the budget burns >= factor x "
                        "over BOTH windows of a tier")
    p.add_argument("--slo-ttft-s", type=float, default=0.0,
                   help="TTFT objective threshold seconds (snapped to "
                        "the nearest histogram bucket bound; 0 = off)")
    p.add_argument("--slo-ttft-target", type=float, default=0.99,
                   help="fraction of requests that must meet the TTFT "
                        "threshold")
    p.add_argument("--slo-tpot-s", type=float, default=0.0,
                   help="per-token decode latency objective threshold "
                        "seconds (0 = off)")
    p.add_argument("--slo-tpot-target", type=float, default=0.99,
                   help="fraction of requests that must meet the TPOT "
                        "threshold")
    p.add_argument("--slo-queue-s", type=float, default=0.0,
                   help="engine queue-delay objective threshold seconds "
                        "(0 = off)")
    p.add_argument("--slo-queue-target", type=float, default=0.99,
                   help="fraction of requests that must meet the "
                        "queue-delay threshold")
    p.add_argument("--slo-availability-target", type=float, default=0.0,
                   help="fraction of gateway arrivals that must be "
                        "served (not shed/rejected), per priority class "
                        "and overall; needs --gateway; 0 = off")
    p.add_argument("--flight-dir", default="",
                   help="enable the flight recorder: on engine fault, "
                        "replica death, SIGTERM, or watchdog escalation, "
                        "dump a flight-*/ black box (span tail, metrics, "
                        "time-series tail) here; render with "
                        "scripts/postmortem.py")
    p.add_argument("--slow-log-k", type=int, default=32,
                   help="worst-latency requests retained with full "
                        "critical-path timelines (queue, prefill, tier "
                        "restore, failover, decode) for GET /debug/slow")
    p.add_argument("--deploy-watch", default="", metavar="DIR",
                   help="continuous delivery (serving.deploy): watch this "
                        "training checkpoint dir for newly COMMITted "
                        "verified steps, export each candidate, canary it "
                        "on shadow traffic beside the fleet, and promote "
                        "or roll back autonomously; needs a replicated "
                        "fleet (--replicas/--self-heal/--fleet-workers)")
    p.add_argument("--deploy-export-dir", default="",
                   help="where candidate params exports land "
                        "(default: <watch>/_deploy_exports)")
    p.add_argument("--deploy-poll-interval", type=float, default=5.0,
                   help="checkpoint-dir poll cadence, seconds")
    p.add_argument("--canary-shadow-frac", type=float, default=0.25,
                   help="fraction of live requests mirrored onto the "
                        "canary engine as shadow traffic (shadow results "
                        "never reach clients and never book into client "
                        "SLIs)")
    p.add_argument("--canary-min-requests", type=int, default=8,
                   help="completed shadow/live request pairs required "
                        "before the canary verdict")
    p.add_argument("--canary-max-wait", type=float, default=120.0,
                   help="max seconds to wait for --canary-min-requests "
                        "before judging with whatever shadow traffic "
                        "arrived")
    p.add_argument("--promote-max-logprob-drift", type=float, default=0.25,
                   help="max |mean greedy logprob delta| per pinned probe "
                        "prompt vs the incumbent before the candidate is "
                        "rejected")
    p.add_argument("--promote-backoff", type=float, default=30.0,
                   help="initial backoff after a rollback before the next "
                        "candidate is canaried (doubles per consecutive "
                        "rollback)")
    return p.parse_args()


def main() -> None:
    args = parse_args()
    if not args.model_dir and not args.random_init:
        raise SystemExit("need --model-dir or --random-init PRESET")
    if args.fleet_workers > 0:
        # Worker processes share a host only on the CPU backend; on a TPU
        # host this exits here, before any weight is built or worker spawned.
        from dlti_tpu.utils.platform import refuse_multiprocess_on_tpu

        refuse_multiprocess_on_tpu(
            f"scripts/serve.py --fleet-workers {args.fleet_workers}")

    import jax
    import jax.numpy as jnp

    from dlti_tpu.data import get_tokenizer
    from dlti_tpu.serving import (
        EngineConfig, InferenceEngine, SamplingParams, ServerConfig, serve,
    )

    tok = get_tokenizer(args.tokenizer)
    mark_startup("imports")

    tracer = None
    if args.trace_dir:
        from dlti_tpu.telemetry import configure_tracer

        # Enable BEFORE the engine is built so its lifecycle hooks see an
        # enabled tracer from the first request.
        tracer = configure_tracer(enabled=True,
                                  capacity=args.trace_capacity)

    if args.model_dir:
        from dlti_tpu.checkpoint import load_exported_model

        params, cfg = load_exported_model(args.model_dir)
        model_cfg = cfg.model
        lora_cfg = cfg.lora if cfg.lora.enabled else None
        print(f"loaded export {args.model_dir} "
              f"(layers={model_cfg.num_layers}, hidden={model_cfg.hidden_size})")
    else:
        from dlti_tpu.config import resolve_model
        from dlti_tpu.models import build_model

        model_cfg = resolve_model(args.random_init)
        lora_cfg = None
        model = build_model(model_cfg, None)
        params = model.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        print(f"random-initialized preset {args.random_init} "
              f"(layers={model_cfg.num_layers})")

    mark_startup("weights")
    tiered = args.prefix_host_blocks > 0 or (
        args.prefix_disk_blocks > 0 and args.prefix_disk_dir)
    ec = EngineConfig(
        max_seqs=args.max_seqs, block_size=args.block_size,
        num_blocks=args.num_blocks, max_model_len=args.max_model_len,
        eos_token_id=tok.eos_id,
        enable_prefix_caching=args.enable_prefix_caching or tiered,
        prefix_host_blocks=args.prefix_host_blocks,
        prefix_disk_dir=args.prefix_disk_dir,
        prefix_disk_blocks=args.prefix_disk_blocks,
        cache_dtype=args.kv_cache_dtype,
        quantization=args.quantization,
        speculative=args.speculative,
        num_draft_tokens=args.num_draft_tokens,
        ngram_size=args.ngram_size,
        spec_min_acceptance=args.spec_min_acceptance,
        spec_probe_window=args.spec_probe_window,
        spec_cooldown=args.spec_cooldown,
        spec_adaptive=not args.no_spec_adaptive,
        max_prefill_tokens_per_step=args.max_prefill_tokens,
        guard_nonfinite=not args.no_numeric_guard,
        guard_token_storm=args.guard_token_storm,
        memory_ledger=not args.no_memory_ledger,
        hbm_budget_bytes=args.hbm_budget_bytes,
        admit_min_headroom_frac=args.admit_min_headroom_frac,
        adapter_slots=args.adapter_slots,
        adapter_rank=args.adapter_rank,
    )
    if args.adapter:
        # Register BEFORE the engines are built: verification (manifest
        # digests) fails fast on a corrupt directory at startup, and the
        # catalog is process-global so every replica resolves the names.
        from dlti_tpu.serving.adapters import register_adapter

        if args.adapter_slots <= 0:
            raise SystemExit("--adapter needs --adapter-slots > 0")
        for spec in args.adapter:
            name, sep, adir = spec.partition("=")
            if not sep or not name.strip() or not adir.strip():
                raise SystemExit(f"--adapter expects NAME=DIR, got {spec!r}")
            register_adapter(name.strip(), adir.strip())
            print(f"registered adapter {name.strip()!r} from {adir.strip()}")
    from dlti_tpu.config import ReplicaLifecycleConfig

    lc_cfg = ReplicaLifecycleConfig(
        enabled=args.self_heal,
        probation_initial_s=args.probation,
        flap_window_s=args.flap_window,
        flap_max_cycles=args.flap_max_cycles)
    if args.fleet_workers > 0:
        if args.disagg:
            raise SystemExit("--fleet-workers and --disagg are mutually "
                             "exclusive (disagg pools stay in-process)")
        import dataclasses
        import tempfile

        from dlti_tpu.config import FleetConfig
        from dlti_tpu.serving import FleetSupervisor, make_subprocess_spawner

        runtime_dir = args.fleet_runtime_dir or os.path.join(
            tempfile.gettempdir(), f"dlti_fleet_{os.getpid()}")
        # Everything a worker needs to build a byte-identical engine: the
        # same model source, engine config, adapters, and the parent's
        # matmul precision (the env half of the platform setup is
        # inherited through the child env).
        spec = {
            "model_dir": args.model_dir,
            "model_preset": args.random_init,
            "engine": dataclasses.asdict(ec),
            "matmul_precision": jax.config.jax_default_matmul_precision,
            "adapters": {name.strip(): adir.strip()
                         for name, _, adir in
                         (s.partition("=") for s in args.adapter)},
            "warmup": True,
            "slow_log_k": args.slow_log_k,
            "flight_dir": args.flight_dir,
        }
        # Fleet healing is always on: respawn-on-death is the point of
        # per-process failure domains (--self-heal only tunes probation).
        engine = FleetSupervisor(
            ec, workers=args.fleet_workers,
            spawner=make_subprocess_spawner(spec, runtime_dir,
                                            host="127.0.0.1"),
            fleet_cfg=FleetConfig(
                workers=args.fleet_workers,
                respawn_backoff_s=args.fleet_respawn_backoff,
                restart_budget=args.fleet_restart_budget),
            lifecycle_cfg=dataclasses.replace(lc_cfg, enabled=True),
            max_retries=args.max_retries,
            affinity_spill_threshold=args.affinity_spill_threshold,
            canary_vocab=model_cfg.vocab_size)
        print(f"fleet supervisor: {args.fleet_workers} worker "
              f"process(es) ready (runtime dir {runtime_dir})")
    elif args.disagg:
        from dlti_tpu.serving import DisaggController

        engine = DisaggController(
            model_cfg, params, ec, lora_cfg,
            prefill_replicas=args.prefill_replicas,
            decode_replicas=args.decode_replicas,
            tensor=args.tensor,
            max_retries=args.max_retries,
            # Pool-scoped here: "POOL:REPLICA:STEP[:MODE]".
            fault_inject_step=args.fault_inject_step,
            handoff_queue_depth=args.handoff_queue_depth,
            handoff_deadline_s=args.handoff_deadline_s,
            affinity_spill_threshold=args.affinity_spill_threshold,
            lifecycle_cfg=lc_cfg)
    elif args.replicas > 1 or args.self_heal or args.reload_checkpoint:
        # A sole replica still gets the lifecycle layer when healing or
        # a rolling reload is requested — quarantine/probe/reinstate and
        # weight swaps work fleet-of-one (migration has no survivors, so
        # drains wait for in-flight work instead).
        from dlti_tpu.serving import ReplicatedEngine

        engine = ReplicatedEngine(
            model_cfg, params, ec, lora_cfg,
            replicas=args.replicas, tensor=args.tensor,
            max_retries=args.max_retries,
            fault_inject_step=args.fault_inject_step,
            affinity_spill_threshold=args.affinity_spill_threshold,
            lifecycle_cfg=lc_cfg)
    else:
        mesh = None
        if args.tensor > 1:
            from dlti_tpu.config import ParallelConfig
            from dlti_tpu.parallel import build_mesh

            mesh = build_mesh(ParallelConfig(tensor=args.tensor))
        engine = InferenceEngine(model_cfg, params, ec, lora_cfg, mesh=mesh,
                                 donate_params=True)
    mark_startup("kv_pool")
    # The engine owns (a possibly quantized copy of) the weights now; this
    # frame's reference would otherwise pin the original tree in HBM for
    # the server's lifetime — 13.5 GB of dead bf16 under --quantization.
    del params
    gw_cfg = None
    if args.gateway:
        from dlti_tpu.config import GatewayConfig

        gw_cfg = GatewayConfig(
            enabled=True,
            max_queued_requests=args.max_queued_requests,
            max_queued_tokens=args.max_queued_tokens,
            rate_limit_rps=args.rate_limit_rps,
            rate_limit_burst=args.rate_limit_burst,
            tenant_weights=args.tenant_weights,
            drain_grace_s=args.drain_grace,
            max_retries=args.max_retries,
            fault_inject_step=args.fault_inject_step,
            affinity=args.affinity,
            affinity_spill_threshold=args.affinity_spill_threshold,
            affinity_prefix_tokens=args.affinity_prefix_tokens,
            adapter_map=args.adapter_map)
    from dlti_tpu.config import (
        FlightRecorderConfig, SLOConfig, TelemetryConfig, WatchdogConfig,
    )

    tel_cfg = TelemetryConfig(
        trace_dir=args.trace_dir,
        trace_capacity=args.trace_capacity,
        slo=SLOConfig(
            enabled=args.slo,
            window_s=args.slo_window,
            burn_tiers=args.slo_burn_tiers,
            ttft_threshold_s=args.slo_ttft_s,
            ttft_target=args.slo_ttft_target,
            tpot_threshold_s=args.slo_tpot_s,
            tpot_target=args.slo_tpot_target,
            queue_threshold_s=args.slo_queue_s,
            queue_target=args.slo_queue_target,
            availability_target=args.slo_availability_target),
        watchdog=WatchdogConfig(
            enabled=args.watchdog,
            action=args.watchdog_action,
            queue_depth_limit=args.watchdog_queue_depth,
            shed_rate_limit=args.watchdog_shed_rate,
            alert_log_path=(os.path.join(args.flight_dir,
                                         "watchdog_alerts.jsonl")
                            if args.flight_dir else "")),
        flight_recorder=FlightRecorderConfig(dir=args.flight_dir))
    sc = ServerConfig(host=args.host, port=args.port,
                      default_params=SamplingParams(max_tokens=args.max_tokens_default),
                      gateway=gw_cfg, telemetry=tel_cfg)
    # Critical-path slow log sizing (telemetry.ledger): the engines share
    # one RequestTelemetry, so one SlowLog serves the whole fleet.
    engine.telemetry.critical_path.slow.k = max(1, args.slow_log_k)
    print("pre-compiling decode programs (single-step + multi-step ladder)...")
    t0 = time.time()
    engine.warmup_decode_ladder()
    print(f"decode programs ready in {time.time() - t0:.0f}s")
    if args.disagg:
        # Concurrent pool stepping: long prefills overlap decode dispatch
        # instead of serializing with it in the stepper thread.
        engine.start()
        print(f"disaggregated pools: {args.prefill_replicas} prefill + "
              f"{args.decode_replicas} decode replicas "
              f"(handoff queue depth {args.handoff_queue_depth})")
    if args.reload_checkpoint:
        # Startup-kicked rolling upgrade (the drill path: boot on old
        # weights, roll to new ones under load): same verified-load
        # contract as POST /v1/reload.
        reload_fn = getattr(engine, "request_reload", None)
        if reload_fn is None:
            raise SystemExit("--reload-checkpoint needs a replicated "
                             "fleet (--replicas > 1 or --disagg)")
        from dlti_tpu.checkpoint.store import load_pytree

        rdir = args.reload_checkpoint
        reload_fn(lambda: load_pytree(rdir, verify=True))
        print(f"rolling weight reload queued from {rdir}")
    deploy = None
    if args.deploy_watch:
        # Continuous delivery: the controller watches the training run's
        # checkpoint dir, exports each new verified step, canaries it on
        # a shadow replica built BESIDE the fleet (client capacity never
        # shrinks), and promotes through the same rolling-reload path as
        # POST /v1/reload — or rolls back, quarantines, and refuses.
        if getattr(engine, "request_reload", None) is None:
            raise SystemExit("--deploy-watch needs a replicated fleet "
                             "(--replicas > 1, --self-heal, or "
                             "--fleet-workers)")
        import dataclasses as _dc

        from dlti_tpu.checkpoint.store import load_pytree as _load_pytree
        from dlti_tpu.config import DeployConfig
        from dlti_tpu.serving.deploy import DeploymentController

        dcfg = DeployConfig(
            enabled=True,
            watch_dir=args.deploy_watch,
            export_dir=args.deploy_export_dir,
            poll_interval_s=args.deploy_poll_interval,
            canary_shadow_frac=args.canary_shadow_frac,
            canary_min_requests=args.canary_min_requests,
            canary_max_wait_s=args.canary_max_wait,
            promote_max_logprob_drift=args.promote_max_logprob_drift,
            promote_backoff_s=args.promote_backoff,
            slo_ttft_threshold_s=args.slo_ttft_s,
            slo_tpot_threshold_s=args.slo_tpot_s)
        # The canary engine is a deliberately small shadow replica: a few
        # slots and a modest KV pool judge gates fine, and the tiered
        # prefix cache / adapters / memory ledger stay off so the shadow
        # can never contend with the fleet for those singletons.
        canary_ec = _dc.replace(
            ec, max_seqs=min(ec.max_seqs, 4),
            num_blocks=min(ec.num_blocks, 512),
            enable_prefix_caching=False, prefix_host_blocks=0,
            prefix_disk_dir="", prefix_disk_blocks=0,
            memory_ledger=False, adapter_slots=0)

        def _canary_factory(export_dir):
            cparams = _load_pytree(export_dir, verify=True)
            return InferenceEngine(model_cfg, cparams, canary_ec, None,
                                   donate_params=True)

        incumbent = args.model_dir if (args.model_dir and os.path.isfile(
            os.path.join(args.model_dir, "MANIFEST.json"))) else ""
        deploy = DeploymentController(
            engine, dcfg, canary_factory=_canary_factory,
            incumbent_dir=incumbent)
        print(f"deploy controller: watching {args.deploy_watch} "
              f"(shadow frac {args.canary_shadow_frac}, min pairs "
              f"{args.canary_min_requests}; control: /v1/deploy)")
    print(f"serving on http://{args.host}:{args.port}  "
          f"(pool: {args.num_blocks} blocks x {args.block_size} tokens)")
    print(f"live dashboard: http://{args.host}:{args.port}/dashboard  "
          f"(JSON: /debug/vars; profiler: POST /debug/profile)")
    # The collector's pauses, booked from here to shutdown
    # (dlti_gc_pause_seconds_total{generation=}; a gc/collect span while the
    # tracer is on): a stall of the stepper is held against them.
    from dlti_tpu.telemetry import get_tracer, install_gc_hook, remove_gc_hook

    install_gc_hook(get_tracer())
    try:
        serve(engine, tok, sc, deploy=deploy)
    finally:
        remove_gc_hook()
        if args.fleet_workers > 0:
            engine.close()  # FT_SHUTDOWN + terminate/kill ladder
        if args.disagg:
            engine.stop()
        if tracer is not None:
            path = tracer.export(os.path.join(
                args.trace_dir, f"trace_serve_{os.getpid()}.json"))
            print(f"telemetry trace -> {path} (open in ui.perfetto.dev)")


if __name__ == "__main__":
    main()
