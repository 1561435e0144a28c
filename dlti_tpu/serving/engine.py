"""Continuous-batching inference engine.

The TPU-native re-design of the vLLM engine the reference claims but never
ships (``README.md:10,16``; ``requirements.txt:18``). Architecture, XLA-first:

* **Two compiled programs, static shapes.** Prefill runs one request at a
  time at a bucketed prompt length (one compile per bucket); decode runs the
  whole slot batch one token per step. Nothing recompiles as requests come
  and go — liveness is data (positions / block tables), not shape.
* **Paged KV.** One physical block pool per layer in HBM
  (``dlti_tpu.ops.kv_cache``); the host-side :class:`BlockManager` hands out
  blocks; block tables are tiny int32 arrays shipped to the device each step.
* **Continuous batching.** Between decode steps the scheduler retires
  finished slots, admits waiting requests into free slots (prefill), and
  grows block tables as sequences cross block boundaries. Out-of-memory is
  handled by preempting the youngest sequence back to the waiting queue
  (recompute-on-readmit, vLLM's recompute policy).
* **One round ahead of the host.** A plain one-step decode round is left
  in flight when ``step()`` returns, and the next ``step()`` launches the
  round after it from the tokens still on the device before it fetches
  anything (:meth:`InferenceEngine.step`): the host's work between two
  decode programs runs under a program.
* **Fused sampling.** Greedy / temperature / top-k / top-p are per-slot
  *data* (``dlti_tpu.serving.sampling``), sampled inside the compiled decode
  step — mixed batches never recompile; the one branch inside it (sort the
  vocabulary or not) is taken on the device from that data. Per-request
  ``seed`` keys make a request's draw stream independent of batch
  composition.

This module is the scheduler half and imports no jax: it plans each round in
host (numpy) arrays and hands them, by name, to
:class:`dlti_tpu.serving.executor.EngineExecutor`, which owns every device
array and every program's calling convention.
"""

from __future__ import annotations

import collections
import itertools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from dlti_tpu.config import LoRAConfig, ModelConfig
from dlti_tpu.serving.adapters import AdapterError
from dlti_tpu.ops.kv_cache import window_blocks, window_group_blocks
from dlti_tpu.serving.block_manager import BlockManager
from dlti_tpu.serving.executor import (
    RIDES, EngineExecutor, PrefillCallRefused)
from dlti_tpu.serving.sampling import SamplingParams
from dlti_tpu.telemetry import RequestTelemetry
from dlti_tpu.telemetry.distributed_trace import mint_trace_id
from dlti_tpu.telemetry.flightrecorder import get_recorder
from dlti_tpu.telemetry.ledger import DEVICE_WAIT
from dlti_tpu.telemetry.memledger import MemoryLedger, is_oom_error
from dlti_tpu.utils.logging import get_logger

# Speculative-decode /metrics names (registered by server.build_registry's
# spec scalar source; the engine's stats dict stays the source of truth).
# Name-stability contract — external dashboards scrape these; pinned in
# tests/test_bench_contract.py.
SPEC_METRIC_NAMES = (
    "dlti_spec_proposed_total",
    "dlti_spec_accepted_total",
    "dlti_spec_paused_rounds_total",
    "dlti_spec_acceptance_rate",
    "dlti_spec_draft_len",
)


@dataclass
class EngineConfig:
    """Engine sizing. Defaults suit a tiny test model; production configs
    come from ``scripts/serve.py``."""

    max_seqs: int = 8              # decode batch slots
    block_size: int = 16           # tokens per KV block
    num_blocks: int = 256          # physical pool size (per layer)
    max_model_len: int = 512       # max prompt+generation length per request
    prefill_buckets: Sequence[int] = ()  # default: powers of 2 up to max_model_len
    cache_dtype: str = "bfloat16"
    eos_token_id: int = 2          # Llama-2 </s>
    # Automatic prefix caching (dlti_tpu.serving.prefix_cache): retired
    # sequences' full KV blocks are kept content-addressed and reused by
    # later requests sharing a prompt prefix; unreferenced blocks are
    # evicted LRU under pool pressure.
    enable_prefix_caching: bool = False
    # Prefix-cache tiering (dlti_tpu.serving.prefix_tiers): with a host
    # and/or disk budget set (and prefix caching on), evicted HBM blocks
    # demote HBM -> host RAM -> disk instead of being discarded, and a
    # prefix match that runs past the HBM blocks restores lower-tier
    # blocks with a host->device scatter (charged as a restore, not a
    # re-prefill). prefix_host_blocks bounds the host tier (blocks);
    # prefix_disk_blocks bounds digest-verified block dirs under
    # prefix_disk_dir (0 = that tier off).
    prefix_host_blocks: int = 0
    prefix_disk_dir: str = ""
    prefix_disk_blocks: int = 0
    # Weight-only quantization: "int8" stores matmul weights as int8 +
    # per-channel scales (~half the weight HBM -> bigger KV pool),
    # dequantized inside the compiled programs. "none" keeps param_dtype.
    quantization: str = "none"
    # Speculative decoding: "ngram" proposes draft tokens by prompt lookup
    # (match the trailing n-gram against earlier context, copy what
    # followed) and verifies them in a (k+1)-position forward — greedy-exact
    # up to batched-matmul numerics (a (k+1)-position forward tiles
    # differently than a 1-position one, the same ~1e-2 bf16 logit delta any
    # batch-shape change causes; ties only flip on near-ties, which trained
    # models rarely produce at the argmax). Proposal, verification, and
    # acceptance all run ON DEVICE, one round a program call: up to
    # num_draft_tokens+1 tokens per host sync on repetitive text.
    # Gating is PER SLOT: greedy slots accept draft
    # prefixes while sampling slots in the same batch take their
    # single-step sampled token (same fold_in rng stream), so one sampling
    # request no longer disables speculation batch-wide. Caveat of that
    # composition: a sampling slot's position-0 logits then come from a
    # (k+1)-position forward, which tiles differently than the 1-position
    # plain decode — the same ~1e-2 bf16 logit delta as above. Greedy
    # argmax only flips on near-ties, but a categorical draw can flip
    # whenever the shifted CDF crosses the rng uniform, so under
    # speculative mode a seeded sampling request's tokens are reproducible
    # for a fixed engine config but not bitwise-independent of batch
    # composition on bf16 (exact on f32). speculative="none" keeps the
    # strict batch-independence promise.
    speculative: str = "none"          # "none" | "ngram"
    num_draft_tokens: int = 4
    ngram_size: int = 2
    # Adaptive gate: a greedy slot-round wins (emitted-1) extra tokens over
    # plain decode. When the mean win over the last >=spec_probe_window
    # greedy slot-rounds drops below spec_min_acceptance (extra tokens per
    # round — rounds where prompt lookup finds no match count as 0), pause
    # proposing for spec_cooldown engine rounds (which run the plain
    # path), then re-probe. 0.0 disables the gate (always
    # speculate). On by default: on text where prompt lookup never hits,
    # the (k+1)-position forwards are pure overhead, and the gate is what
    # makes --speculative ngram safe to leave enabled.
    spec_min_acceptance: float = 0.25
    spec_probe_window: int = 64
    spec_cooldown: int = 32
    # Draft-length ladder: compile spec programs for the pow2 halving
    # ladder of k (num_draft_tokens, /2, ..., 1) and pick the dispatch k
    # each engine round from the live per-slot acceptance windows —
    # shorter drafts on text where prompt lookup barely lands, full-k on
    # repetitive text. Greedy exactness holds at every k (an accepted
    # prefix under smaller k is a prefix of the full-k acceptance), so
    # this only trades verify-forward width for wasted lanes. False pins
    # dispatch at k=num_draft_tokens (the pre-ladder behavior).
    spec_adaptive: bool = True
    # Chunked prefill (the vLLM latency lever the throughput headline
    # lacks): cap prompt tokens prefilled per engine step, so admission
    # never stalls running decodes for a whole prompt length — partially
    # prefilled slots carry their remaining suffix across steps and join
    # the decode batch when it lands. 0 = unbounded (throughput mode:
    # whole prompts in one batched call per bucket).
    max_prefill_tokens_per_step: int = 0
    # Numeric output guard (PR 8): before ANY token from a decode round
    # is appended/streamed, its logprob (computed device-side alongside
    # the sample — NaN/inf logits surface there) must be finite; a
    # nonfinite round raises NumericFault, which AsyncEngine treats as an
    # engine fault and ReplicatedEngine answers by quarantining the
    # replica and recomputing the round's requests on survivors — users
    # never see the garbage tokens a numerically-dead replica samples.
    guard_nonfinite: bool = True
    # Token-storm guard: N consecutive decode steps in which EVERY active
    # slot (>= 2 of them) sampled the same token reads as a degenerate
    # output distribution (the all-pad storm a silently-corrupted model
    # produces) and raises NumericFault. 0 = off (legitimate decodes CAN
    # agree; enable with a window sized for your traffic).
    guard_token_storm: int = 0
    # Memory ledger (telemetry.memledger): per-owner HBM attribution
    # (params / kv_block_pool / prefix_cache_hbm / lora_adapters),
    # feeding /debug/memory, the hbm_* metric gauges, and memory.json in
    # engine flight dumps.
    memory_ledger: bool = True
    # HBM capacity budget in bytes for headroom accounting (0 =
    # auto-detect from device memory_stats(); unknown on CPU unless set).
    hbm_budget_bytes: int = 0
    # Headroom-aware admission: defer admitting new requests while ledger
    # headroom is below this fraction of capacity (0 = gating off, and it
    # is also off whenever capacity is unknown). Deferred requests stay
    # queued — the degraded mode is latency, never a client error.
    admit_min_headroom_frac: float = 0.0
    # Multi-LoRA serving (dlti_tpu.serving.adapters): with adapter_slots
    # > 0 the executor carries a stacked per-module A/B adapter pool
    # ((slots+1, in, r) and (slots+1, r, out) per targeted projection;
    # row 0 is the all-zero base no-op) and every compiled program
    # gathers each batch row's factors by adapter id — one program
    # serves a batch of heterogeneous adapters (S-LoRA/Punica's BGMV).
    # 0 keeps every program signature byte-identical to an adapter-free
    # engine. adapter_rank is the pool-wide max (smaller adapters
    # zero-pad, which is float-exact); adapter_targets name the
    # projections the pool covers.
    adapter_slots: int = 0
    adapter_rank: int = 16
    adapter_targets: Sequence[str] = (
        "q_proj", "k_proj", "v_proj", "o_proj")

    def buckets(self) -> List[int]:
        if self.prefill_buckets:
            return sorted(self.prefill_buckets)
        out, b = [], self.block_size
        while b < self.max_model_len:
            out.append(b)
            b *= 2
        out.append(self.max_model_len)
        return out

    @property
    def max_blocks_per_seq(self) -> int:
        return -(-self.max_model_len // self.block_size)

    @property
    def spec_hist_width(self) -> int:
        """Columns of a token-history row (speculative decode): positions
        0..max_model_len-1, the draft positions past them, and two cells
        of slack, so that no slice the program takes near a row's end is
        moved to fit."""
        return self.max_model_len + self.num_draft_tokens + 2


class NumericFault(RuntimeError):
    """A decode round produced numerically-dead output (nonfinite
    logits/logprobs, or an all-slots token storm). Raised BEFORE any of
    the round's tokens are appended, so nothing garbage is ever streamed;
    the replica layer answers by quarantining the engine and recomputing
    its requests on survivors (:meth:`ReplicatedEngine._fail_replica`)."""


@dataclass
class Request:
    """One generation request (token-level; text handled by the server)."""

    request_id: str
    prompt_token_ids: List[int]
    params: SamplingParams = field(default_factory=SamplingParams)
    arrival_time: float = field(default_factory=time.monotonic)
    # Filled by the engine:
    output_token_ids: List[int] = field(default_factory=list)
    output_logprobs: List[float] = field(default_factory=list)
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    finish_reason: Optional[str] = None
    num_preemptions: int = 0
    # Which replica owns this request (set by ReplicatedEngine.submit).
    replica: int = 0
    # Failover resubmissions consumed (ReplicatedEngine moves a dead
    # replica's requests onto survivors up to a retry cap).
    num_retries: int = 0
    # Live migrations survived (planned drains hand this request's paged
    # KV to a survivor mid-decode instead of re-prefilling; the server
    # surfaces the count so load drills can assert on it).
    num_migrations: int = 0
    # Admission metadata (set by the gateway when one is configured; the
    # engine itself schedules FCFS and ignores them).
    tenant: str = ""
    priority: str = ""
    # Absolute monotonic deadline (None = none). The gateway sheds queued
    # requests past it before prefill and flips cancel_requested on
    # in-flight ones.
    deadline: Optional[float] = None
    # When the request was first admitted into a decode slot (monotonic;
    # None while queued). Kept across preemption/re-admission so the
    # queue-time histogram measures the first wait only.
    admitted_time: Optional[float] = None
    # Early-cancel flag (server stop-string matching, client disconnect):
    # SET from any thread (a GIL-atomic bool write, the same contract as
    # AsyncEngine.submit), CONSUMED by the stepper thread at the next
    # token-emission walk — the slot is released there, so a cancelled
    # request costs at most one decode round.
    cancel_requested: bool = False
    # Critical-path attribution inputs (telemetry.ledger): when the
    # request came through the admission gateway, its enqueue time (the
    # client-observed t0); seconds spent restoring lower-tier prefix
    # blocks at admission; requeue stalls by kind ("failover"/"preempt")
    # with the pre-first-token portion split out; and the open requeue
    # mark note_requeue/note_readmitted maintain.
    gateway_enqueue_time: Optional[float] = None
    restore_s: float = 0.0
    stall_s: Dict[str, float] = field(default_factory=dict)
    stall_prefill_s: float = 0.0
    _requeue_mark: Optional[tuple] = None
    # Of its decode, the wall its engines spent in other requests' prefill
    # calls while it held a decoding slot ("decode_prefill_stall"): the
    # engine's running total of prefill wall is noted when the request's
    # own prefill has handed it a slot (_prefill_stall_mark) and the
    # difference settled when it leaves the slot. O(1) a request.
    prefill_stall_s: float = 0.0
    _prefill_stall_mark: Optional[float] = None
    # Multi-LoRA serving: the registered adapter this request generates
    # under ("" = base model). _adapter_slot is the resolved pool row
    # (-1 = unresolved): acquisition happens at admission and the pin is
    # dropped with the decode slot, so preemption and failover
    # re-acquire — the row may have been evicted meanwhile.
    adapter: str = ""
    _adapter_slot: int = -1
    # Deployment-controller shadow mirror (serving.deploy): results never
    # reach a client, and telemetry/SLO/gateway accounting skips these.
    shadow: bool = False
    # Distributed-trace context (telemetry.distributed_trace): minted at
    # the gateway (or at submit for direct clients) and PROPAGATED — it
    # rides the FT_SUBMIT descriptor, handoff envelopes, drain
    # migrations, failover resubmits, disagg staging, and shadow-tap
    # replays, so spans emitted in any process for any leg of this
    # request share one id. "" = untraced (wire canaries, old peers).
    trace_id: str = ""

    @property
    def done(self) -> bool:
        return self.finish_reason is not None


@dataclass
class GenerationResult:
    request_id: str
    prompt_token_ids: List[int]
    output_token_ids: List[int]
    output_logprobs: List[float]
    finish_reason: str
    ttft_s: float
    latency_s: float


class _Slot:
    """Host state for one active decode slot."""

    def __init__(self, slot_id: int):
        self.slot_id = slot_id
        self.request: Optional[Request] = None
        self.blocks: List[int] = []
        self.seq_len = 0  # tokens written to the KV cache
        self.last_token = 0
        # Chunked prefill bookkeeping, as positions into the request's
        # (prompt + output) token list: next_pos = where the next chunk
        # starts, prefill_end = one past the last prompt token. A slot
        # with next_pos < prefill_end is admitted but not yet decodable.
        self.next_pos = 0
        self.prefill_end = 0
        # Leading blocks this sequence shares with the prefix cache since
        # its admission (hits and tier restores): already matchable.
        self.shared_blocks = 0
        # A window group's blocks (a model whose layers differ in their
        # window): those of logical blocks [window_first, window_first +
        # len(window_blocks)); what lay before has been released.
        self.window_blocks: List[int] = []
        self.window_first = 0

    @property
    def free(self) -> bool:
        return self.request is None

    @property
    def prefilling(self) -> bool:
        return self.request is not None and self.next_pos < self.prefill_end


def _pairs_under_window(n: int, window: int) -> int:
    """(query, key) pairs the first ``n`` positions of a sequence can see
    under ``window``: the sum over positions i < n of min(i + 1, window)."""
    if n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def refuse_state_handoff(model_cfg: ModelConfig, what: str) -> None:
    """Disaggregated serving and k/v hand-off move a sequence as its k/v
    blocks; a recurrent state is not in them, a latent block is not what
    the wire format packs, and a model whose layers differ in their window
    keeps a list of blocks a group of layers."""
    if len(model_cfg.kv_group_windows) > 1:
        raise ValueError(
            f"{what} moves a sequence between engines as ONE list of k/v "
            f"blocks that are all there; a model whose layers differ in "
            f"their attention window keeps a list a group of layers, the "
            f"window group's released behind the window "
            f"(wire.pack_handoff carries neither). Serve it colocated (no "
            f"--disagg)")
    if model_cfg.latent_dim:
        raise ValueError(
            f"{what} moves a sequence between engines as its k/v blocks "
            f"(wire.pack_handoff packs \"k\" and \"v\"); a model with latent "
            f"attention keeps latent blocks, which the hand-off does not "
            f"carry. Serve it colocated (no --disagg)")
    if model_cfg.has_recurrent_state:
        raise ValueError(
            f"{what} moves a sequence between engines as its k/v blocks; a "
            f"model with layer_pattern {model_cfg.layer_pattern!r} also "
            f"keeps a recurrent state per decode slot, which the hand-off "
            f"does not carry. Serve it colocated (no --disagg)")



class InferenceEngine:
    """Synchronous engine core: ``submit()`` requests, ``step()`` in a loop.

    The HTTP server wraps this in a background thread; ``generate()`` is the
    offline batch entry point.
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        params,
        engine_cfg: EngineConfig = EngineConfig(),
        lora_cfg: Optional[LoRAConfig] = None,
        mesh=None,
        donate_params: bool = False,
        telemetry: Optional[RequestTelemetry] = None,
    ):
        # Request-lifecycle telemetry (dlti_tpu.telemetry.lifecycle):
        # TTFT/TPOT/queue-time histograms observed on-engine + per-request
        # Chrome-trace spans. A shared instance (ReplicatedEngine) makes
        # the histograms aggregate across replicas.
        self.telemetry = telemetry if telemetry is not None \
            else RequestTelemetry()
        self._tracer = self.telemetry.tracer
        # The stepper thread's phase clock (telemetry.ledger): every span
        # of the step's path is entered through it, so it books always and
        # the tracer's span of the same name opens only while enabled.
        self._account = self.telemetry.stepper
        self._phase = self._account.phase
        self._account.doing = self._doing
        if engine_cfg.max_blocks_per_seq > engine_cfg.num_blocks - 1:
            # Block 0 is the reserved trash block, so only num_blocks-1 are
            # allocatable. A config where one max-length sequence can never
            # fit would livelock _admit() at the FCFS head forever.
            raise ValueError(
                f"max_model_len={engine_cfg.max_model_len} needs "
                f"{engine_cfg.max_blocks_per_seq} KV blocks but the pool has "
                f"only {engine_cfg.num_blocks - 1} allocatable "
                f"(num_blocks={engine_cfg.num_blocks} minus the reserved "
                f"trash block); raise num_blocks or lower max_model_len"
            )
        self.cfg = engine_cfg
        self.model_cfg = model_cfg
        self.logger = get_logger()
        self.mesh = mesh
        # Aggregate stats for the /stats endpoint and load reports.
        self.stats = {"requests": 0, "generated_tokens": 0, "prefill_tokens": 0,
                      "preemptions": 0, "decode_steps": 0,
                      # slot x step units CONSUMED (a row thrown away
                      # because its request ended in the round before does
                      # not count, though the device ran it — that waste
                      # deliberately shows up as occupancy < 100%);
                      # decode_slot_steps / (max_seqs * decode_steps) is
                      # the mean slot occupancy — the first thing to look
                      # at when throughput undershoots (synchronized
                      # cohort retirement drains slots faster than
                      # admission refills them).
                      "decode_slot_steps": 0,
                      # Rounds launched while the round before was still in
                      # flight (over decode_steps: the share of steps whose
                      # host work hid under a program), and rows such a
                      # round computed for a request that turned out to
                      # have ended in the round before (its token is thrown
                      # away; decode_slot_steps counts kept tokens only).
                      "decode_rounds_launched_ahead": 0,
                      "decode_rows_discarded": 0,
                      # Slot-seconds the streams stood still for: the wall
                      # of every prefill call (launch to fetch) times the
                      # slots that held a decoding stream when it began.
                      # Over decode_slot_steps (kept tokens): the seconds of
                      # an average gap between two tokens that were a
                      # prefill's.
                      "decode_stream_stall_seconds_prefill": 0.0,
                      # Tokens of context the decode steps attended over:
                      # each round adds the sum of its active slots'
                      # seq_len times its steps, so decode_context_tokens /
                      # decode_steps is the mean context one step reads
                      # (what the paged-attention kernel's bytes follow).
                      "decode_context_tokens": 0,
                      # The same under a window group's window: each slot
                      # adds min(seq_len, window). What the window layers'
                      # kernel calls read; 0 for a model with one group.
                      "decode_window_context_tokens": 0,
                      # Keys the paged decode kernel's live tiles hold for
                      # the same rounds: a slot about to attend over n keys
                      # costs ceil(n / tile) whole tiles (the tile: the
                      # executor's decode_tile_tokens, the kernel's own
                      # rule). decode_context_tokens over this is the share
                      # of what the kernel fetches that is live; a sliding
                      # window is left out of both.
                      "decode_kernel_tile_tokens": 0,
                      # Decode steps whose sampling sorted the vocabulary:
                      # some slot's row set top-k or top-p, the predicate
                      # sample_tokens evaluates on the device (read here
                      # from the same mirrors). 0 under default traffic.
                      "decode_steps_sorted_sampling": 0,
                      "prefix_cached_tokens": 0,
                      # Cached tokens the prefill calls attended over beside
                      # their own: each row of a call adds the position its
                      # tokens start at (a prefix hit's tail, a later piece
                      # of a long prompt).
                      "prefill_context_tokens": 0,
                      # (query, key) pairs the prefill calls' tokens
                      # could see: a row of T tokens that start at
                      # position p adds p T + T (T + 1) / 2. What
                      # attention's products of a prefill are counted
                      # from, whatever the model.
                      "prefill_attention_pairs": 0,
                      # The same under a window group's window (a query
                      # sees at most ``window`` keys); 0 with one group.
                      "prefill_window_attention_pairs": 0,
                      # Tokens whose KV came back from a LOWER tier (host
                      # or disk) via a restore scatter instead of either
                      # an HBM hit or a re-prefill. Present (at 0) even
                      # without tiering so the /metrics schema is stable.
                      "prefix_restored_tokens": 0,
                      # Prefill program dispatches. Present (at 0) so
                      # the /metrics schema is stable.
                      "prefill_batches": 0,
                      # The widest of them, in padded tokens (rows x
                      # bucket): a gauge. What one admission pass can ask
                      # of the device's memory at once.
                      "prefill_widest_call_tokens": 0,
                      # Prefill calls the executor refused (the program
                      # of that shape could not be built; nothing ran):
                      # calls of several rows that went again as one-row
                      # calls, and one-row calls, each of which failed its
                      # own request (_prefill_refused). 0 in a healthy run.
                      "prefill_calls_split": 0,
                      "prefill_calls_failed": 0,
                      "spec_proposed": 0, "spec_accepted": 0,
                      "spec_paused_rounds": 0,
                      # What decode rounds cost the host, booked by the
                      # executor: arrays staged host-to-device for them and
                      # program calls made for them. Each over the rounds
                      # launched reads 1.0 for plain rounds.
                      "decode_host_uploads": 0, "decode_program_calls": 0,
                      # Numeric-guard trips (nonfinite decode outputs /
                      # token storms). Present (at 0) so the /metrics
                      # schema is stable.
                      "numeric_faults": 0,
                      # Headroom-aware memory control (telemetry.
                      # memledger): admission passes skipped for want of
                      # HBM headroom — deferred, not faulted. Present (at
                      # 0) so the /metrics schema is stable.
                      "hbm_deferred_admissions": 0}
        # The device half: weights, the paged cache, the resident per-slot
        # decode state and every compiled program with its calling
        # convention live in the executor. This class keeps ONLY host-side
        # scheduling state (slots, queues, block accounting, the numpy
        # mirrors), plans each round in host arrays, and calls in.
        self.executor = EngineExecutor(
            model_cfg, params, engine_cfg, lora_cfg, mesh=mesh,
            donate_params=donate_params, stats=self.stats)
        del params  # the executor owns (a possibly quantized copy of) them
        # What the model counts (EngineExecutor.counter_names), summed over
        # every program call under the counter's own name, and the decode
        # steps' part of it under ``<name>_decode``. (A largest-of counter
        # is the sum over calls of each call's largest.) No such key for a
        # model that counts nothing.
        for name in self.executor.counter_names:
            self.stats[name] = self.stats[f"{name}_decode"] = 0
        # The latent family's prefill calls (models.latent): real query
        # tokens whose own keys went through the flash forward kernel, and
        # steps of the loop over cached latents times the program's rows.
        if self.executor.prefill_kernel_counts is not None:
            self.stats["mla_kernel_query_tokens_total"] = 0
            self.stats["mla_walked_key_blocks_total"] = 0
        # (a looped stack's passes also by the prefill calls' part)
        if model_cfg.ut_steps > 1:
            self.stats["loop_passes_prefill"] = 0
        ec = engine_cfg
        self.block_manager = BlockManager(ec.num_blocks, ec.block_size)
        # A model whose layers differ in their attention window has a
        # second group of layers with pools, an allocator and tables of its
        # own (ops.kv_cache.window_group_blocks): a sequence holds there
        # the blocks of its last ``window`` keys alone, and they are
        # released as the sequence moves on (:meth:`_window_cover`).
        self.window = 0
        self.window_manager = None
        groups = model_cfg.kv_group_windows
        if len(groups) > 1:
            self.window = groups[1]
            self.window_manager = BlockManager(
                window_group_blocks(
                    self.window, ec.block_size, ec.max_seqs,
                    self.executor.prefill_call_tokens),
                ec.block_size)
        # The cache's books (kv_metrics): blocks released by group and why,
        # and the seconds the window group's release took.
        self.kv_freed = {("full", "end"): 0, ("window", "window"): 0,
                         ("window", "end"): 0}
        self.kv_window_free_s = 0.0
        self.prefix_cache = None
        if ec.enable_prefix_caching:
            from dlti_tpu.serving.prefix_cache import PrefixCachingAllocator

            tier_store = None
            if ec.prefix_host_blocks > 0 or ec.prefix_disk_blocks > 0:
                from dlti_tpu.serving.prefix_tiers import TieredBlockStore

                tier_store = TieredBlockStore(
                    host_blocks=ec.prefix_host_blocks,
                    disk_dir=ec.prefix_disk_dir,
                    disk_blocks=ec.prefix_disk_blocks)
            self.prefix_cache = PrefixCachingAllocator(
                self.block_manager, tier_store=tier_store,
                kv_fetch=self.executor.fetch_block_kv
                if tier_store is not None else None)
        self.slots = [_Slot(i) for i in range(ec.max_seqs)]
        # Wall seconds of this engine's prefill calls so far (not times
        # slots): what a request's decode_prefill_stall is the change of.
        self._prefill_wall_s = 0.0
        self.waiting: collections.deque[Request] = collections.deque()
        # Recently-finished requests, for observability only (results are
        # returned via step()/generate()); bounded so a long-lived server
        # doesn't grow without limit.
        self.finished: collections.deque[Request] = collections.deque(maxlen=256)
        self._req_counter = itertools.count()

        # Host mirrors of the per-slot device inputs.
        S, MB = ec.max_seqs, ec.max_blocks_per_seq
        self._block_tables = np.zeros((S, MB), np.int32)
        # The window group's table of a decode round, as wide as a slot's
        # live blocks need, and the token its column 0 starts at.
        self._window_tables = np.zeros(
            (S, window_blocks(self.window, ec.block_size, 1)
             if self.window else 0), np.int32)
        self._window_base = np.zeros((S,), np.int32)
        self._temperature = np.ones((S,), np.float32)
        self._top_k = np.zeros((S,), np.int32)
        self._top_p = np.ones((S,), np.float32)
        # Per-slot sampling key (uint32[2] threefry data) + tokens generated
        # so far; decode folds key with the count, so a seeded request's
        # draws don't depend on batch composition or admission order.
        self._slot_keys = np.zeros((S, 2), np.uint32)
        self._gen_counts = np.zeros((S,), np.int32)
        # Multi-LoRA: each slot's adapter-pool row (0 = the all-zero base
        # row). Maintained unconditionally so _state_mirrors stays
        # uniform; without a pool it is never shipped to the device.
        self._adapter_ids = np.zeros((S,), np.int32)
        # Recurrent state (models with such layers): the slot each decode row may
        # write its state to — its own while the slot decodes, out of
        # range (S) while it is free or still prefilling, so a decode call
        # never touches a state that a prefill is building.
        self._state_slots = np.full((S,), S, np.int32)

        # Host mirror of every slot's token history at its context
        # positions, maintained incrementally at admission/append — the
        # spec program's proposal input, without rebuilding O(context)
        # arrays from Python lists every sync. Rows beyond a slot's
        # seq_len are never read (proposal masks on seq_len), so stale
        # tails from previous occupants need no zeroing.
        self._spec_hist = (
            np.zeros((ec.max_seqs, ec.spec_hist_width), np.int32)
            if ec.speculative == "ngram" else None)
        # Per-slot adaptive controller (replaces the old engine-wide
        # _spec_pause): each slot carries its own rolling acceptance
        # window and cooldown, so one zero-hit slot pauses alone while
        # its batchmates keep speculating. prop/acc count slot-rounds and
        # extra accepted tokens since that slot's last gate decision;
        # pause is decode rounds left in that slot's cooldown; ewma is
        # the smoothed accepted-drafts-per-round estimate feeding the
        # draft-length ladder (optimistically seeded at full k so a fresh
        # slot probes with the widest draft).
        self._spec_slot_prop = np.zeros((S,), np.int64)
        self._spec_slot_acc = np.zeros((S,), np.int64)
        self._spec_slot_pause = np.zeros((S,), np.int32)
        self._spec_slot_ewma = np.full((S,), float(ec.num_draft_tokens),
                                       np.float64)
        # Last dispatched draft length (0 = no spec round in flight /
        # speculation off) — the dlti_spec_draft_len gauge.
        self._spec_last_k = 0

        # Disaggregated serving (serving/disagg.py): a prefill-only engine
        # runs admission and chunked prefill but never dispatches decode —
        # finished prefills are harvested via export_handoff() and their
        # KV migrated to a decode replica, which continues the stream via
        # adopt_handoff(). Plain engines leave this False.
        self.prefill_only = False

        # Token-storm guard run length (consecutive all-slots-identical
        # decode steps).
        self._storm_run = 0

        # The plain one-step round left in flight when step() returned
        # (what _decode_launch gave), or None: see step().
        self._inflight = None

        # Memory ledger (telemetry.memledger). The executor names the
        # device arrays it holds; prefix-cached blocks live INSIDE the pool
        # arrays, so that owner is a carve — bytes move from kv_block_pool
        # to prefix_cache_hbm without double counting.
        self.memledger = MemoryLedger(
            enabled=ec.memory_ledger, capacity_bytes=ec.hbm_budget_bytes)
        self.executor.register_memory_owners(self.memledger)
        # Bytes of the per-slot recurrent state (0 without such layers).
        self.recurrent_state_pool_bytes = \
            self.executor.recurrent_state_pool_bytes
        if self.prefix_cache is not None:
            per_block = self.executor.pool_bytes // max(1, ec.num_blocks)
            self.memledger.register_carve(
                "prefix_cache_hbm", "kv_block_pool",
                lambda: self.prefix_cache.num_cached_blocks() * per_block)

    def warmup_decode_ladder(self) -> None:
        """Pre-compile the decode program ahead of traffic."""
        self.executor.warmup_decode_ladder()

    def _bucket_for(self, n: int) -> int:
        for b in self.cfg.buckets():
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds max_model_len={self.cfg.max_model_len}")

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------
    def submit(self, prompt_token_ids: Sequence[int],
               params: Optional[SamplingParams] = None,
               request_id: Optional[str] = None,
               affinity_key: Optional[str] = None,
               adapter: str = "", trace_id: str = "") -> Request:
        """Enqueue a request. Returns immediately; tokens arrive via step().

        ``trace_id`` adopts an upstream-minted distributed-trace context
        (gateway admission, fleet supervisor descriptor); "" mints a
        fresh one — direct clients get traced too.

        ``affinity_key`` is a replica-routing concern (session/prefix
        stickiness — :meth:`ReplicatedEngine.submit`); a single engine
        has nowhere to route, so it is accepted and ignored here to keep
        the two submit surfaces interchangeable.

        ``adapter`` names a catalog-registered LoRA adapter ("" = base
        model); resolution to a pool row — including any checkpoint-store
        load — happens at admission on the stepper thread, keeping this
        method's thread-safety contract intact.

        THREAD-SAFETY CONTRACT (load-bearing): AsyncEngine runs step() on
        its stepper thread *without* holding a lock while HTTP handlers
        call submit() concurrently. That is only sound because submit()
        does nothing beyond (a) one GIL-atomic ``self.waiting.append`` and
        (b) touching its own ``stats["requests"]`` key — no slot, cache,
        block-allocator, or prefix-cache state. Admission consumes
        ``waiting`` at a single point inside step(), so a racing submit
        lands this step or the next. If you add ANY engine-state work here
        (prefix-cache probing, block preallocation, ...), it must move
        into step()-side admission or AsyncEngine must buffer submissions
        on its own lock and hand them over from the stepper thread.
        """
        if not prompt_token_ids:
            raise ValueError("prompt must contain at least one token")
        if len(prompt_token_ids) >= self.cfg.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt_token_ids)} tokens) must be shorter than "
                f"max_model_len={self.cfg.max_model_len}"
            )
        req = Request(
            request_id=request_id or f"req-{next(self._req_counter)}",
            prompt_token_ids=list(prompt_token_ids),
            params=params or SamplingParams(),
            adapter=adapter,
            # A local uuid when no upstream context arrived — no engine
            # state touched, so the thread-safety contract below holds.
            trace_id=trace_id or mint_trace_id(),
        )
        self.waiting.append(req)
        self.stats["requests"] += 1
        # Tracer-only (no engine state): an instant event under the
        # tracer's own lock, a no-op when tracing is disabled — within
        # the thread-safety contract above.
        self.telemetry.on_submitted(req)
        return req

    def resubmit(self, req: Request) -> None:
        """Re-enqueue an EXISTING request (replica failover): the request
        keeps its id, params, arrival time, and generated-so-far tokens —
        admission recomputes prompt+output exactly like re-admission after
        preemption. Same thread-safety contract as :meth:`submit` (one
        GIL-atomic deque append); ``stats["requests"]`` is NOT incremented
        — the request was already counted at first submission.

        The adapter-pool pin does NOT survive failover (the dead
        replica's pool is gone; this engine's pool may not even hold the
        adapter): reset to unresolved so admission re-acquires here —
        ``req.adapter`` itself rides along, so the request finishes
        under the same adapter it started with."""
        req._adapter_slot = -1
        self.waiting.append(req)

    @property
    def num_active(self) -> int:
        return sum(not s.free for s in self.slots)

    @property
    def num_free_blocks(self) -> int:
        return self.block_manager.num_free

    @property
    def spec_acceptance_rate(self) -> float:
        """Cumulative accepted/proposed draft-token ratio (0.0 before any
        proposal) — the dlti_spec_acceptance_rate gauge."""
        p = self.stats.get("spec_proposed", 0)
        return self.stats.get("spec_accepted", 0) / p if p else 0.0

    @property
    def spec_draft_len(self) -> int:
        """Draft length of the last dispatched decode round (0 = the
        round ran plain decode: speculation off, paused, or no greedy
        slot) — the dlti_spec_draft_len gauge."""
        return self._spec_last_k

    @property
    def has_work(self) -> bool:
        return bool(self.waiting) or self.num_active > 0

    def generate(self, prompts: Sequence[Sequence[int]],
                 params: Optional[SamplingParams] = None,
                 ) -> List[GenerationResult]:
        """Offline batch generation: submit all, step until drained."""
        reqs = [self.submit(p, params) for p in prompts]
        while self.has_work:
            self.step()
        by_id = {r.request_id: r for r in reqs}
        return [self._result(by_id[r.request_id]) for r in reqs]

    def step(self) -> List[Request]:
        """One scheduler iteration: launch, fetch and retire, admit.

        Returns requests that finished during this step.

        The loop runs one round ahead of the host. A plain decode round
        (no speculation: one step, always) is left IN FLIGHT when this
        returns; the next call then

        1. plans the round after it from what the host knows without its
           tokens (who is live, each position, block growth, who ends by
           length) and launches it, the riding rows reading their input
           token on the device (:meth:`_decode_prepare`);
        2. fetches the round that was in flight and walks its emissions
           (:meth:`_decode_complete`);
        3. admits and prefills: a slot freed in 2 is refilled at once, and
           the admission joins the round after next with its first token
           as a host id.

        So what the host does between two decode programs (the emission
        walk, the handler threads woken by it, the next plan) runs while a
        program does. A request that sampled its end-of-sequence in the
        round fetched in 2 has a row in the round launched in 1: that row
        is thrown away when its round is fetched (``decode_rows_discarded``).
        Its one write lands past the sequence's last kept token, in a block
        that is freed with the slot and never registered with the prefix
        cache, and whatever reuses the block, the slot or its recurrent
        state is dispatched after it on the one device queue.

        The loop does not run ahead when it cannot plan without the last
        tokens: nothing is in flight (the first round, or after a drain), a
        speculative round on either side, a plan that would have to
        preempt. It then fetches first and plans from the host's tokens,
        which is the only other order there is. A speculative round is
        fetched in the step that launched it, after admission, whose
        prefill work hides under it.
        """
        phase = self._phase
        # Whatever is in flight is this call's to fetch: a fault below
        # drops it, and the round launched behind it, with the step.
        inflight, self._inflight = self._inflight, None
        try:
            finished: List[Request] = []
            launched = None
            if inflight is not None:
                launched = self._decode_dispatch(ahead_of=inflight)
                finished = self._decode_complete(inflight)
            if launched is None and not self.prefill_only and any(
                    not s.free and not s.prefilling for s in self.slots):
                launched = self._decode_dispatch()
            with phase("engine/admit", "engine"):
                self._admit()
            if self.cfg.max_prefill_tokens_per_step > 0:
                with phase("engine/prefill_chunks", "engine"):
                    self._prefill_work()
            if launched is None:
                return finished
            if launched[0] == "plain" and self.has_work:
                self._inflight = launched
                return finished
            # A speculative round; or every request the round carried has
            # ended and none waits: nothing would come for it.
            return finished + self._decode_complete(launched)
        except Exception as e:
            if is_oom_error(e):
                # OOM forensics: file the black box as an OOM (with
                # memory.json carrying the ownership map at death) before
                # the fault propagates to the replica/server layer.
                rec = get_recorder()
                if rec is not None:
                    rec.dump(reason="oom", force=True, exc=e,
                             extra={"where": "engine_step"})
            raise

    def _drop_inflight(self) -> None:
        """Wait for the round in flight, if there is one, and throw its
        tokens away: for an engine whose requests are about to go
        (:meth:`abort_all`, which the server's stop calls too). Every
        request keeps exactly the tokens that were emitted to it. Not for
        an engine that decodes on: the round has written its keys, values
        and recurrent state."""
        inflight, self._inflight = self._inflight, None
        if inflight is None:
            return
        try:
            self.executor.fetch(inflight[-1])
        except Exception:  # noqa: BLE001 — the round of a faulted engine
            self.logger.exception("the dropped decode round did not finish")

    # ------------------------------------------------------------------
    # Scheduling internals
    # ------------------------------------------------------------------
    def _alloc(self, n: int) -> Optional[List[int]]:
        """Allocate blocks, evicting LRU cached prefixes under pressure."""
        if self.prefix_cache is not None:
            return self.prefix_cache.allocate(n)
        return self.block_manager.allocate(n)

    def _window_cover(self, slot: _Slot, first: int, upto: int) -> None:
        """The window group's blocks of ``slot`` for a call that writes
        positions ``[first, upto)``: release what lies wholly before the
        lowest key the call can see (``first - window + 1``), then take
        blocks up to ``upto``. A released block may still be read by a
        program in flight: whatever writes it next is dispatched behind
        that program on the one device queue (as a block freed at a
        sequence's end always was). The pool is sized so that this never
        waits (``ops.kv_cache.window_group_blocks``)."""
        bs = self.cfg.block_size
        keep_from = max(0, first - self.window + 1) // bs
        drop = max(0, min(keep_from - slot.window_first,
                          len(slot.window_blocks)))
        if drop:
            t0 = time.monotonic()
            self.window_manager.free(slot.window_blocks[:drop])
            del slot.window_blocks[:drop]
            self.kv_freed["window", "window"] += drop
            t1 = time.monotonic()
            self.kv_window_free_s += t1 - t0
            if self._tracer.enabled:
                self._tracer.complete("engine/window_free", t0, t1,
                                      cat="engine", blocks=drop)
        slot.window_first = slot.window_first + drop \
            if slot.window_blocks else keep_from
        need = -(-upto // bs) - slot.window_first - len(slot.window_blocks)
        if need > 0:
            got = self.window_manager.allocate(need)
            if got is None:
                raise RuntimeError(
                    f"the window group's pool is out of blocks "
                    f"({self.window_manager.num_free} free, {need} asked): "
                    f"it is sized for calls of at most "
                    f"{self.executor.prefill_call_tokens} tokens")
            slot.window_blocks.extend(got)
        if drop or need > 0:
            row = self._window_tables[slot.slot_id]
            n = min(len(slot.window_blocks), len(row))
            row[:n] = slot.window_blocks[:n]
            row[n:] = 0
            self._window_base[slot.slot_id] = slot.window_first * bs

    def kv_metrics(self) -> tuple:
        """The cache's gauges and counters, read from the engine's books
        at a scrape: blocks in use and in the pool by group (a model with
        one group reports ``full`` alone), the tokens the live slots hold,
        blocks released by group and why, the window group's release
        seconds."""
        from dlti_tpu.telemetry.registry import ReadCounter, ReadGauge

        managers = {"full": self.block_manager}
        if self.window_manager is not None:
            managers["window"] = self.window_manager

        def in_use():
            return {g: m.num_blocks - 1 - m.num_free
                    for g, m in managers.items()}

        return (
            ReadGauge("dlti_kv_blocks_in_use", in_use, "group",
                      help="blocks of the cache handed out, by group of "
                           "layers (the trash block apart)"),
            ReadGauge("dlti_kv_pool_blocks",
                      lambda: {g: m.num_blocks for g, m in managers.items()},
                      "group", help="blocks of a layer's pool, by group"),
            ReadGauge("dlti_kv_context_tokens",
                      lambda: {"": sum(s.seq_len for s in self.slots)},
                      help="tokens the live slots have in the cache"),
            ReadGauge("dlti_kv_cache_entries",
                      lambda: {"": self.model_cfg.cache_entries},
                      help="entries of a sequence's cache: one a layer, a "
                           "looped stack one a (pass, layer)"),
            ReadGauge("dlti_kv_bytes_per_context_token",
                      lambda: {"": self.executor.kv_bytes_per_context_token},
                      help="bytes a token of context holds over every "
                           "entry of the pool of --num-blocks"),
            ReadCounter("dlti_kv_blocks_freed_total",
                        lambda: {k: v for k, v in self.kv_freed.items()
                                 if k[0] in managers},
                        ("group", "why"),
                        help="blocks released: behind a window, or at a "
                             "sequence's end"),
            ReadCounter("dlti_kv_window_free_seconds_total",
                        lambda: {"": self.kv_window_free_s},
                        help="seconds the stepper spent releasing a window "
                             "group's blocks"),
        )

    def _doing(self) -> str:
        """For the log line of a program built after start-up: the shape of
        the newest prefill call, which is the call a program built inside
        ``engine/prefill_launch`` was built for (a shape the warm-up did
        not form)."""
        shape = getattr(self.executor, "last_prefill_shape", None)
        if shape is None:
            return ""
        return ("newest prefill call %d rows x %d tokens x %d blocks a row"
                % shape)

    def _streams_standing(self, chunks) -> int:
        """Slots that hold a stream the prefill call of ``chunks`` makes
        wait: a request that has had a token and is not prefilling, the
        call's own rows apart (a preempted request's recompute is one)."""
        mine = {c[0].slot_id for c in chunks}
        return sum(1 for s in self.slots
                   if s.request is not None and not s.prefilling
                   and s.request.first_token_time is not None
                   and s.slot_id not in mine)

    def _admit(self) -> None:
        """Admit waiting requests into free slots via bucketed prefill.

        Admissions collected in one pass are prefilled in *batched*
        program calls (grouped by suffix bucket): on a deep queue the
        admission stall is a handful of model calls instead of one per
        request — the dominant TTFT term there.
        """
        # Headroom-aware admission (telemetry.memledger): under HBM
        # pressure (a fragmented allocator, a co-tenant balloon, a tier
        # restore burst), DEFER the whole admission pass rather than
        # prefill into memory that is about to run out — the queue holds
        # the requests, the next step retries, and the client sees
        # latency, never an error. Gating needs a known capacity; when
        # capacity is unknown (CPU without a budget) it stays off.
        if (self.memledger.enabled
                and self.cfg.admit_min_headroom_frac > 0 and self.waiting):
            snap = self.memledger.snapshot()
            cap = snap.get("capacity_bytes", 0)
            headroom = snap.get("headroom_bytes")
            if (cap and headroom is not None
                    and headroom < self.cfg.admit_min_headroom_frac * cap):
                self.stats["hbm_deferred_admissions"] += 1
                return

        admissions: List[tuple] = []
        for slot in self.slots:
            # Cancelled while queued (disconnect before admission): finish
            # without ever taking a slot or prefilling.
            while self.waiting and self.waiting[0].cancel_requested:
                req = self.waiting.popleft()
                # A queue-head request may hold an adapter pin from an
                # earlier pass that then broke on block exhaustion.
                self._release_adapter(req)
                req.finish_reason = "stop"
                req.finish_time = time.monotonic()
                self.finished.append(req)
                self.telemetry.on_finished(req)
            if not self.waiting or not slot.free:
                continue
            req = self.waiting[0]
            # Resolve the request's adapter to a pool row BEFORE any
            # block work: a pool-full miss leaves the request queued
            # (FCFS, the KV-exhaustion contract), a load failure fails
            # THIS request without touching engine state, and a hit/load
            # pins the row until the slot releases. Idempotent across
            # passes via the -1 sentinel (a pass that pinned the row but
            # broke on blocks does not re-acquire).
            if req._adapter_slot < 0:
                if not req.adapter:
                    req._adapter_slot = 0
                elif self.executor.adapter_pool is None:
                    self.waiting.popleft()
                    self._fail_waiting(
                        req, f"request names adapter {req.adapter!r} but "
                        "the engine has no adapter pool "
                        "(adapter_slots=0)")
                    continue
                else:
                    t_ad = time.monotonic()
                    try:
                        row, loaded = self.executor.adapter_pool.acquire(
                            req.adapter)
                    except AdapterError as e:
                        self.waiting.popleft()
                        self._fail_waiting(req, str(e))
                        continue
                    if row < 0:
                        break  # every row pinned: FCFS, retry next step
                    req._adapter_slot = row
                    if loaded:
                        # A pool-miss load is restore work on THIS
                        # request's critical path (telemetry.ledger) —
                        # same phase as a tier restore, and visibly NOT
                        # queueing or prefill.
                        now = time.monotonic()
                        req.restore_s += now - t_ad
                        self._tracer.complete(
                            "engine/adapter_load", t_ad, now, cat="engine",
                            id=req.request_id, adapter=req.adapter)
            tokens = req.prompt_token_ids + req.output_token_ids
            cached_blocks: List[int] = []
            n_cached = 0
            tier_keys: List[tuple] = []
            if self.prefix_cache is not None:
                # Chain keys are namespaced by the request's adapter: the
                # same prompt under two adapters produces different KV,
                # so cross-adapter block reuse would be silent corruption.
                cached_blocks, n_cached = self.prefix_cache.match_prefix(
                    tokens, ns=req.adapter or None)
                # Pin the matched blocks BEFORE allocating the suffix —
                # otherwise the allocation's own eviction could reclaim them.
                self.prefix_cache.acquire(cached_blocks)
                # Continue the chain into host/disk tiers: these keys'
                # payloads restore into freshly allocated blocks below
                # (a restore scatter instead of a re-prefill).
                tier_keys = self.prefix_cache.match_tiers(
                    tokens, len(cached_blocks), ns=req.adapter or None)
            need = (self.block_manager.blocks_needed(len(tokens) + 1)
                    - len(cached_blocks))
            blocks = self._alloc(need)
            if blocks is None:
                if cached_blocks:
                    self.prefix_cache.release(cached_blocks)
                break  # head-of-line blocking: FCFS, no starvation
            restored_by_tier: Dict[str, int] = {}
            n_restored = 0
            t_restore = time.monotonic() if tier_keys else 0.0
            for j, key in enumerate(tier_keys):
                # The alloc's own evictions may have demoted MORE blocks
                # since the match, but never removed these keys (puts
                # only add); a fetch can still miss if the alloc cascaded
                # them off the bounded disk tier, or fail verification —
                # either way the chain stops and the rest prefills.
                payload, tier = self.prefix_cache.fetch_restore(key)
                if payload is None:
                    break
                self.executor.restore_block(blocks[j], payload)
                self.prefix_cache.register_restored(key, blocks[j])
                restored_by_tier[tier] = restored_by_tier.get(tier, 0) + 1
                n_restored += 1
            if n_restored:
                # Charge the tier fetch + restore dispatch to THIS
                # request's critical path (telemetry.ledger): a warm-tier
                # admission's TTFT decomposes into restore vs prefill.
                now = time.monotonic()
                req.restore_s += now - t_restore
                self._tracer.complete(
                    "engine/tier_restore", t_restore, now, cat="engine",
                    id=req.request_id, blocks=n_restored)
            if self.prefix_cache is not None:
                self.stats["prefix_cached_tokens"] += n_cached
                self.stats["prefix_restored_tokens"] += \
                    n_restored * self.cfg.block_size
                self.prefix_cache.record_admission(cached_blocks,
                                                   restored_by_tier)
            self.waiting.popleft()
            n_prefix = n_cached + n_restored * self.cfg.block_size
            admissions.append((slot, req, cached_blocks + blocks, n_prefix))

        if self.cfg.max_prefill_tokens_per_step > 0:
            # Chunked mode: register now, prefill in bounded chunks from
            # _prefill_work — decode slots never stall for a prompt length.
            for slot, req, blocks, n_cached in admissions:
                tokens = req.prompt_token_ids + req.output_token_ids
                self._register_slot(slot, req, blocks, len(tokens))
                slot.next_pos = n_cached  # _register_slot set it to the end
                slot.shared_blocks = n_cached // self.cfg.block_size
            return

        suffix_lens = [len(req.prompt_token_ids) + len(req.output_token_ids)
                       - n_cached
                       for _slot, req, _blocks, n_cached in admissions]
        by_bucket: Dict[int, List[tuple]] = {}
        for adm, suffix_len in zip(admissions, suffix_lens):
            by_bucket.setdefault(self._bucket_for(suffix_len), []).append(adm)
        for bucket, group in by_bucket.items():
            rows = self._prefill_rows(bucket)
            for i in range(0, len(group), rows):
                self._prefill_group(bucket, group[i:i + rows])

    def _prefill_rows(self, bucket: int) -> int:
        """Rows of one bucketed prefill call: wide admission waves go out 8
        rows at a time (past that the batched program's marginal win
        flattens while its padded work and jit-shape surface keep growing),
        and fewer, a power of two, where the model holds a call to
        ``prefill_call_tokens`` padded tokens or the device a call of
        several rows to ``prefill_group_tokens`` (a row at least). One row
        where a call of several rows of this bucket has been refused in
        this process (:meth:`_prefill_refused`): the one-row program of
        every bucket is warmed at start-up, a narrower one of several rows
        may be one more that does not fit, found by one more failed
        compile. ``prefill_group_tokens`` reckons with float32 logits over
        every position, which no program computes any more (it heads one
        position a row); the limit stays for the set of shapes it makes
        the engine form, which are the ones the benchmark's cells warm
        (``executor.PREFILL_LOGITS_SHARE`` says when it goes)."""
        if any(b == bucket and r > 1
               for r, b, _width in self.executor.refused_prefill_shapes):
            return 1
        limit = min((n for n in (self.executor.prefill_call_tokens,
                                 self.executor.prefill_group_tokens) if n),
                    default=0)
        rows = 8
        while limit and rows > 1 and rows * bucket > limit:
            rows //= 2
        return rows

    def _prefill_work(self) -> None:
        """Chunked prefill: spend up to ``max_prefill_tokens_per_step``
        prompt tokens on partially-prefilled slots (FCFS by arrival), in
        per-bucket batched program calls. A slot whose suffix completes
        samples its first token and joins the next decode step."""
        budget = self.cfg.max_prefill_tokens_per_step
        chunks: List[tuple] = []  # (slot, tokens, start_pos, is_last)
        for slot in sorted((s for s in self.slots if s.prefilling),
                           key=lambda s: s.request.arrival_time):
            if budget <= 0:
                break
            req = slot.request
            remaining = slot.prefill_end - slot.next_pos
            take = min(remaining, budget,
                       self.executor.prefill_call_tokens or remaining)
            # Position p holds (prompt + output)[p], so the chunk is an
            # index slice — no per-slot token copy is carried between steps.
            tokens = req.prompt_token_ids + req.output_token_ids
            piece = tokens[slot.next_pos: slot.next_pos + take]
            chunks.append((slot, piece, slot.next_pos, take == remaining))
            slot.next_pos += take
            budget -= take
        by_bucket: Dict[int, List[tuple]] = {}
        for ch in chunks:
            by_bucket.setdefault(self._bucket_for(len(ch[1])), []).append(ch)
        for bucket, group in by_bucket.items():
            rows = self._prefill_rows(bucket)
            for i in range(0, len(group), rows):
                self._run_prefill_batch(bucket, group[i:i + rows])

    def _register_slot(self, slot: _Slot, req: Request, blocks: List[int],
                       n: int) -> None:
        """Host-side bookkeeping for an admitted request (block table row,
        sampling params, per-slot key + generated-token count)."""
        ec = self.cfg
        self.telemetry.on_admitted(req)
        slot.request = req
        slot.blocks = blocks
        slot.seq_len = n
        # Fully prefilled by default (throughput mode); the chunked-admit
        # path rewinds next_pos to the cached-prefix boundary.
        slot.next_pos = n
        slot.prefill_end = n
        row = np.zeros((ec.max_blocks_per_seq,), np.int32)
        row[: len(blocks)] = blocks
        self._block_tables[slot.slot_id] = row
        self._temperature[slot.slot_id] = req.params.temperature
        self._top_k[slot.slot_id] = req.params.top_k
        self._top_p[slot.slot_id] = req.params.top_p
        self._slot_keys[slot.slot_id] = self.executor.slot_key(
            req.params.seed)
        # Count of tokens generated so far (nonzero on re-admission after
        # preemption, so the seeded draw stream continues where it left off).
        self._gen_counts[slot.slot_id] = len(req.output_token_ids)
        # max(.., 0): requests that never resolved a pool row (no pool,
        # handoff adoption of a base request) decode under row 0, the
        # all-zero base adapter.
        self._adapter_ids[slot.slot_id] = max(req._adapter_slot, 0)
        # Not a decode row until its prefill has handed the slot a state.
        self._state_slots[slot.slot_id] = self.cfg.max_seqs
        if self._spec_hist is not None:
            ctx = req.prompt_token_ids + req.output_token_ids
            self._spec_hist[slot.slot_id, :len(ctx)] = ctx

    def _prefill_group(self, bucket: int, group: List[tuple]) -> None:
        """Batched bucketed prefill: one program call for every admission
        sharing a suffix bucket (throughput mode: whole suffixes at once).

        On re-admission after preemption the generated-so-far tokens are
        part of the recomputed prompt (vLLM recompute semantics); with a
        prefix-cache hit only the suffix past the cached blocks is
        prefilled.
        """
        chunks = []
        limit = self.executor.prefill_call_tokens
        for slot, req, blocks, n_cached in group:
            tokens = req.prompt_token_ids + req.output_token_ids
            self._register_slot(slot, req, blocks, len(tokens))
            slot.shared_blocks = n_cached // self.cfg.block_size
            # A model that holds a call to ``limit`` padded tokens takes a
            # longer suffix as several calls (the group is then this one
            # row: _prefill_rows), each over what the earlier ones wrote.
            while limit and len(tokens) - n_cached > limit \
                    and not slot.free:
                self._run_prefill_batch(self._bucket_for(limit), [
                    (slot, tokens[n_cached:n_cached + limit], n_cached,
                     False)])
                n_cached += limit
            if slot.free:  # a call of its own was refused: it has failed
                continue
            if limit and bucket > limit:
                bucket = self._bucket_for(len(tokens) - n_cached)
            chunks.append((slot, tokens[n_cached:], n_cached, True))
        if chunks:
            self._run_prefill_batch(bucket, chunks)

    def _run_prefill_batch(self, bucket: int, chunks: List[tuple]) -> None:
        """One prefill program call over ``chunks``: rows of
        ``(slot, tokens, start_pos, is_last)`` sharing a length bucket.

        Rows are padded to a power of two — padding rows carry position -1
        everywhere, which slot_mapping turns into dropped writes. Each
        *final* chunk's first generated token is sampled from its last
        real logit in one batched sample call; non-final chunks (chunked
        prefill) write KV only. A call the executor refuses goes to
        :meth:`_prefill_refused`.
        """
        tr, phase, acct = self._tracer, self._phase, self._account
        # The arguments cost a pass over the rows: only for a tracer that
        # keeps them.
        args = {"rows": len(chunks), "bucket": bucket,
                "prompt_tokens": sum(len(c[1]) for c in chunks)} \
            if tr.enabled else {}
        streams = self._streams_standing(chunks)
        try:
            # (The group is the tracer's alone, for its arguments: what of
            # it is neither launch nor wait books to the phase round it.)
            with tr.span("engine/prefill_group", cat="engine", **args):
                with phase("engine/prefill_launch", "engine"):
                    began = acct.last
                    sampled = self._prefill_launch(bucket, chunks)
                if sampled is not None:
                    with phase("engine/prefill_wait", "engine", DEVICE_WAIT):
                        toks, lps = self.executor.fetch(sampled)
                # The call's wall, launch to fetch (a mid-prompt chunk's
                # is its launch: what it keeps the device for shows in the
                # wait of whatever is fetched next), from the two clock
                # reads the phases took. Booked before the rows' first
                # tokens are emitted: a request's mark then holds its own
                # prefill already.
                wall = acct.last - began if acct.mine() else 0.0
                self._prefill_wall_s += wall
                self.stats["decode_stream_stall_seconds_prefill"] += \
                    streams * wall
                if sampled is None:
                    return  # mid-prompt chunks: KV writes only
                if self.executor.counter_names:
                    self._count(toks[len(lps):][None, :], decode=False)
                self._prefill_emit(chunks, toks, lps)
        except PrefillCallRefused as refused:
            self._prefill_refused(bucket, chunks, refused)

    def _prefill_refused(self, bucket: int, chunks: List[tuple],
                         refused: PrefillCallRefused) -> None:
        """A prefill call that could not be built (the executor has logged
        its name, shape and error). Nothing ran and the cache is whole, so
        this is not a fault of the engine's (``abort_all`` is for a round
        that leaves the cache in doubt): a call of several rows goes again
        as one-row calls, in order, in this step, which is what a narrower
        admission would have run (each row's key and count are its own);
        a one-row call costs its own request, and the step goes on."""
        if len(chunks) > 1:
            self.stats["prefill_calls_split"] += 1
            for chunk in chunks:
                self._run_prefill_batch(bucket, [chunk])
            return
        slot = chunks[0][0]
        req = slot.request
        self.stats["prefill_calls_failed"] += 1
        # register=False: what its earlier chunks wrote is not offered to
        # the prefix cache on a failed request's behalf.
        self._release(slot, register=False)
        self._fail_waiting(req, str(refused))

    def _prefill_emit(self, chunks: List[tuple], toks: np.ndarray,
                      lps: np.ndarray) -> None:
        """Guard and emit the first tokens of a prefill batch's final
        chunks (``toks``, ``lps``: one per row, on the host)."""
        if self.cfg.guard_nonfinite:
            bad = [slot.slot_id
                   for r, (slot, *_rest, is_last) in enumerate(chunks)
                   if is_last and not np.isfinite(lps[r])]
            if bad:
                # First-token guard: a numerically-dead model's prefill
                # sample must not stream either (failover's resubmit
                # preserves generated-so-far tokens).
                self.stats["numeric_faults"] += 1
                raise NumericFault(
                    f"nonfinite prefill output on slot(s) {bad}: the "
                    f"model is producing NaN/inf logits")
        for r, (slot, tokens, start, is_last) in enumerate(chunks):
            if is_last:
                self._append_token(slot, int(toks[r]), float(lps[r]))
                if not slot.free:  # (the first token may have ended it)
                    self._state_slots[slot.slot_id] = slot.slot_id
                    self._publish_prompt_blocks(slot)
                    slot.request._prefill_stall_mark = self._prefill_wall_s

    def _publish_prompt_blocks(self, slot: _Slot) -> None:
        """Prefix caching: the whole blocks a prefill has just written become
        matchable now, not when the sequence retires
        (``PrefixCachingAllocator.register``). The same prompt asked again
        while its first request still decodes is then a hit on the running
        sequence's blocks, where it used to be a second cold prefill holding
        a second copy (in a closed loop over a few long documents those
        copies drove the documents already cached out of the pool: PERF.md
        section 6, PR 38). Decode never writes a whole block of the prompt."""
        n = slot.prefill_end // self.cfg.block_size
        if self.prefix_cache is None or n <= slot.shared_blocks:
            return  # (a hit on every whole block has nothing to add)
        req = slot.request
        tokens = (req.prompt_token_ids + req.output_token_ids)[
            :n * self.cfg.block_size]
        blocks = self.prefix_cache.register(tokens, slot.blocks[:n],
                                            ns=req.adapter or None)
        slot.blocks[:n] = blocks
        slot.shared_blocks = n
        self._block_tables[slot.slot_id, :n] = blocks

    def _prefill_launch(self, bucket: int, chunks: List[tuple]):
        """The host arrays of one batch and its prefill call (program, key
        fold and sampling, none of them waited for): the sampled
        ``(tokens, logprobs)`` still on the device, or None when no chunk
        is final."""
        ec = self.cfg
        B = 1
        while B < len(chunks):
            B *= 2
        nblk_needed = 1
        for slot, tokens, start, _ in chunks:
            nblk_needed = max(nblk_needed, self.block_manager.blocks_needed(
                start + len(tokens)))
        # Block-table width quantized so jit specializations stay
        # O(log^2) over (suffix bucket, table bucket) x O(log) batch.
        nblk_bucket = 1
        while nblk_bucket < nblk_needed:
            nblk_bucket *= 2
        nblk_bucket = min(nblk_bucket, ec.max_blocks_per_seq)
        if self.executor.prefill_whole_tables:
            nblk_bucket = ec.max_blocks_per_seq

        ids = np.zeros((B, bucket), np.int32)
        pos = np.full((B, bucket), -1, np.int32)  # -1 -> write dropped
        bt = np.zeros((B, nblk_bucket), np.int32)
        if self.window_manager is not None:
            # The window group's table of the call: each row's blocks from
            # the first its tokens can see, as wide as the widest call
            # needs (one program a (rows, bucket)).
            wt = np.zeros((B, window_blocks(
                self.window, ec.block_size,
                self.executor.prefill_call_tokens)), np.int32)
            wbase = np.zeros((B,), np.int32)
            for r, (slot, tokens, start, _) in enumerate(chunks):
                self._window_cover(slot, start, start + len(tokens))
                wt[r, :len(slot.window_blocks)] = slot.window_blocks
                wbase[r] = slot.window_first * ec.block_size
        last_idx = np.zeros((B,), np.int32)
        slot_keys = np.zeros((B, 2), np.uint32)
        counts = np.zeros((B,), np.int32)
        temps = np.ones((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        adapter_ids = np.zeros((B,), np.int32)
        # State hand-off (a model with recurrent layers): each row names
        # the slot its prefill fills (a padding row none), and the program
        # writes the state after the row's last real token there.
        state_slots = np.full((B,), ec.max_seqs, np.int32)
        for r, (slot, tokens, start, is_last) in enumerate(chunks):
            req = slot.request
            ids[r, : len(tokens)] = tokens
            pos[r, : len(tokens)] = np.arange(start, start + len(tokens))
            bt[r, : min(len(slot.blocks), nblk_bucket)] = \
                slot.blocks[:nblk_bucket]
            last_idx[r] = len(tokens) - 1
            slot_keys[r] = self._slot_keys[slot.slot_id]
            counts[r] = self._gen_counts[slot.slot_id]
            temps[r] = req.params.temperature
            top_k[r] = req.params.top_k
            top_p[r] = req.params.top_p
            adapter_ids[r] = self._adapter_ids[slot.slot_id]
            state_slots[r] = slot.slot_id

        sample = None
        if any(is_last for *_, is_last in chunks):
            sample = {"slot_keys": slot_keys, "gen_counts": counts,
                      "temperature": temps, "top_k": top_k, "top_p": top_p}
        if self.window_manager is not None:
            bt = ({"block_tables": bt},
                  {"block_tables": wt, "table_base": wbase})
        sampled = self.executor.prefill(
            bucket, input_ids=ids, positions=pos, block_tables=bt,
            last_idx=last_idx, adapter_ids=adapter_ids,
            state_slots=state_slots, sample=sample)
        if self.window_manager is not None:
            # What the call has written lies behind the next one's window
            # but for its last ``window`` keys: released now, so that a
            # pass of several calls holds one call's blocks at a time.
            for slot, tokens, start, _ in chunks:
                self._window_cover(slot, start + len(tokens),
                                   start + len(tokens))
        # Booked for a call that went out (a refused one raised above).
        self.stats["prefill_batches"] += 1
        self.stats["prefill_widest_call_tokens"] = max(
            self.stats["prefill_widest_call_tokens"], B * bucket)
        self.stats["prefill_tokens"] += sum(len(c[1]) for c in chunks)
        self.stats["prefill_context_tokens"] += sum(c[2] for c in chunks)
        self.stats["prefill_attention_pairs"] += sum(
            c[2] * len(c[1]) + len(c[1]) * (len(c[1]) + 1) // 2
            for c in chunks)
        if self.executor.prefill_kernel_counts is not None:
            tokens, walked = self.executor.prefill_kernel_counts(
                B, bucket, [(len(c[1]), c[2]) for c in chunks],
                ec.block_size)
            self.stats["mla_kernel_query_tokens_total"] += tokens
            self.stats["mla_walked_key_blocks_total"] += walked
        if self.window:
            self.stats["prefill_window_attention_pairs"] += sum(
                _pairs_under_window(c[2] + len(c[1]), self.window)
                - _pairs_under_window(c[2], self.window) for c in chunks)
        return sampled

    def _count(self, counters: np.ndarray, decode: bool) -> None:
        """Book the model's counters: ``(program calls or decode steps,
        len(counter_names))``, in the order of ``counter_names``."""
        totals = counters.astype(np.int64).sum(axis=0)
        part = "_decode" if decode else "_prefill"
        for name, total in zip(self.executor.counter_names, totals):
            self.stats[name] += int(total)
            if name + part in self.stats:
                self.stats[name + part] += int(total)

    def _state_mirrors(self) -> dict:
        return {"block_tables": self._block_tables,
                **({"window_tables": self._window_tables,
                    "window_base": self._window_base} if self.window else {}),
                "slot_keys": self._slot_keys,
                "gen_counts": self._gen_counts,
                "temperature": self._temperature,
                "top_k": self._top_k, "top_p": self._top_p,
                "adapter_ids": self._adapter_ids,
                "state_slots": self._state_slots}

    def _sampling_sorts(self) -> bool:
        """Whether a decode step dispatched now takes ``sample_tokens``'s
        sorted branch: its predicate, over the rows the program is given."""
        return bool((self._top_k > 0).any() or (self._top_p < 1.0).any())

    def _masked_rows(self) -> list:
        return [s.slot_id for s in self.slots if s.prefilling]

    def _decode_dispatch(self, ahead_of=None):
        """Schedule a decode round and dispatch its program call WITHOUT
        syncing: returns an opaque pending tuple whose device arrays are
        still being computed, for :meth:`_decode_complete`. All host
        mirrors are snapshotted here (the executor uploads copies at call
        time), so emission and admission may mutate them while the call is
        in flight. ``ahead_of``: the plain round still in flight, behind
        which this one is launched; None then means that the round cannot
        be planned without that round's tokens (or that nothing would
        decode), and the caller fetches first."""
        phase = self._phase
        with phase("engine/decode_prep", "engine"):
            plan = self._decode_prepare(ahead_of)
        if plan is None:
            return None
        with phase("engine/decode_launch", "engine"):
            if plan[0] == "spec":
                return self._spec_launch(*plan[1:])
            return self._decode_launch(*plan[1:], ahead_of)

    def _ends_with_its_next_token(self, req: Request) -> bool:
        """Whether the host can tell, before it sees the token a round in
        flight draws for ``req``, that the token is the request's last:
        the answer's or the model's length, or a cancellation. (An
        end-of-sequence or stop token it cannot foresee.)"""
        n = len(req.output_token_ids) + 1
        return (req.cancel_requested or n >= req.params.max_tokens
                or len(req.prompt_token_ids) + n >= self.cfg.max_model_len)

    def _decode_prepare(self, ahead_of=None):
        """Everything of a decode round before its program call: spec
        gate, block growth (and preemption), batch assembly and the upload
        of the per-slot state. ``(kind, *arguments of the launch)``, or
        None when nothing is left to decode.

        Behind a round still in flight (``ahead_of``) the plan is made from
        what the host knows without that round's tokens. A slot that rides
        in it stands one token further than the host has seen: its
        position, its block growth and its gen count are those of this round's launch, and its input id is
        ``RIDES``. A slot whose request ends with the token in flight is
        left out and reads as a free slot does (:meth:`_clear_row`). None
        also when such a plan cannot be made: a speculative round, or a
        pool that is out of blocks (preemption needs every request's
        tokens on the host).

        Three phases inside the caller's ``engine/decode_prep``: the plan
        (:meth:`_decode_plan`), the assembly of the round's host arrays,
        and the executor's staging of them (one packed upload)."""
        ec = self.cfg
        phase = self._phase
        with phase("engine/decode_plan", "engine"):
            plan = self._decode_plan(ahead_of)
        if plan is None:
            return None
        active, riding, spec_parts, spec_k = plan
        if spec_parts:
            return self._spec_prepare(active, spec_parts, spec_k)

        with phase("engine/decode_assemble", "engine"):
            ids = np.zeros((ec.max_seqs, 1), np.int32)
            pos = np.zeros((ec.max_seqs, 1), np.int32)  # inactive -> trash
            for s in active:
                rides = s.slot_id in riding
                ids[s.slot_id, 0] = RIDES if rides else s.last_token
                pos[s.slot_id, 0] = s.seq_len + rides  # the new token's
            self._book_decode_context(active, riding)
            self.stats["decode_steps_sorted_sampling"] += \
                self._sampling_sorts()
            mirrors = self._state_mirrors()
            if riding:
                # Every row goes up as of this round's launch: a riding
                # row's token in flight is drawn and not yet counted by
                # the mirror (a row drawn with the count before would
                # repeat a draw).
                mirrors["gen_counts"] = self._gen_counts.copy()
                mirrors["gen_counts"][[s.slot_id for s in active
                                       if s.slot_id in riding]] += 1
            masked = self._masked_rows()
        # The round's tokens, positions and every per-slot row go up as
        # one packed array, which the decode program unpacks itself.
        with phase("engine/decode_stage", "engine"):
            staged = self.executor.stage_decode(ids, pos, mirrors, masked)
        return ("plain", [(s, s.request) for s in active], staged)

    def _decode_plan(self, ahead_of=None):
        """Who decodes in the round: the speculation gate, block growth
        (and preemption), who rides behind the round in flight. ``(active,
        riding, spec_parts, spec_k)`` (``spec_parts`` empty: a plain
        round), or None (:meth:`_decode_prepare`)."""
        ec = self.cfg
        # Prefilling slots are admitted but not yet decodable: excluded
        # everywhere below, with their block-table rows masked to the
        # trash block.
        active0 = [s for s in self.slots if not s.free and not s.prefilling]
        riding: set = set()

        # Grow block tables to cover what the round writes: one token a
        # slot, or a speculative round's ``spec_window``; preempt the
        # youngest if the pool is exhausted. (Prefilling slots already own
        # blocks for prompt+1 from admission and are not decoding yet.)
        def grow_tables(spec_window: int) -> bool:
            for slot in sorted(active0,
                               key=lambda s: s.request.arrival_time):
                if slot.free:  # preempted by an earlier iteration
                    continue
                # Sampling slots advance exactly one real token per spec
                # round; their draft-position writes past that land on the
                # trash block (unallocated table entries are 0), so don't
                # allocate — and possibly preempt for — the full window.
                window = spec_window \
                    if slot.request.params.temperature == 0.0 else 1
                at = slot.seq_len + (slot.slot_id in riding)
                need = self.block_manager.blocks_needed(at + window)
                while need > len(slot.blocks):
                    got = self._alloc(1)
                    if got is None:
                        if ahead_of is not None or \
                                not self._preempt_youngest(exclude=slot):
                            return False
                        continue
                    slot.blocks.extend(got)
                    self._block_tables[
                        slot.slot_id, len(slot.blocks) - 1] = got[0]
                if self.window_manager is not None:
                    # (between rounds: behind the first position the round
                    # writes, which is the shortest it can end at)
                    self._window_cover(slot, at, at + window)
            return True

        if ahead_of is not None:
            if self._spec_hist is not None and any(
                    s.request.params.temperature == 0.0
                    and not self._spec_slot_pause[s.slot_id]
                    for s in active0):
                return None
            riding = {s.slot_id for s, req in ahead_of[1] if s.request is req}
            ending = [s for s in active0 if s.slot_id in riding
                      and self._ends_with_its_next_token(s.request)]
            active0 = [s for s in active0 if s not in ending]
            # A plain round (nobody speculates). Its blocks first: a plan
            # given up for want of them has changed nothing (no cooldown
            # ticked, no row cleared) but the blocks granted, which stay on
            # slots that need them whatever order the rounds take.
            if not grow_tables(1):
                return None
            for s in ending:
                # No row of this round, and not free before the round in
                # flight is fetched: for the device it is free already (the
                # trash block, never position 0 of its own table).
                self._clear_row(s.slot_id)
        # Speculative decode engages per ROUND when any active greedy slot
        # is unpaused (per-slot gating: _spec_round_gate ticks cooldowns
        # and returns this round's participants, and the program masks the
        # rest to single-step) and every active slot has room for the
        # worst-case window at the SELECTED draft length. When every
        # greedy slot is paused the round is a plain one: the (k+1)-wide
        # verify forward would be pure overhead. Trade-off: the room check
        # is batch-wide, so one slot within k+1 tokens of max_model_len
        # falls the whole batch back to plain rounds until it retires: at
        # most its last k+1 decode rounds.
        spec_parts: list = []
        spec_k = 0
        if self._spec_hist is not None and active0:
            spec_parts = self._spec_round_gate(active0)
        if spec_parts:
            spec_k = self._spec_pick_k(spec_parts)
            if any(s.seq_len + spec_k + 1 > ec.max_model_len
                   for s in active0):
                spec_parts = []
        self._spec_last_k = spec_k if spec_parts else 0

        if ahead_of is None and not grow_tables(
                spec_k + 1 if spec_parts else 1):
            # Defer, don't fault: a speculative round that cannot reserve
            # its worst-case blocks shrinks to a plain one (blocks already
            # granted stay on their slots and carry over; table rows past
            # the one step are never read). One block per active slot is
            # guaranteed by the admission-time max_blocks_per_seq check,
            # so a plain round can only fail on genuine exhaustion.
            if not spec_parts or not grow_tables(1):
                raise RuntimeError(
                    "KV pool exhausted and nothing to preempt; "
                    "increase num_blocks or lower max_seqs"
                )
            spec_parts, self._spec_last_k = [], 0

        active = [s for s in active0 if not s.free and not s.prefilling]
        if not active:
            return None
        return active, riding, spec_parts, spec_k

    def _decode_launch(self, rows: List[tuple], staged, ahead_of=None):
        """The compiled decode call (not waited for). ``rows``: the
        ``(slot, request)`` pairs it decodes for."""
        if ahead_of is not None:
            self.stats["decode_rounds_launched_ahead"] += 1
        return ("plain", rows, self.executor.launch_decode(
            staged, ahead_of[-1] if ahead_of is not None else None))

    def _decode_complete(self, pending) -> List[Request]:
        """Sync a dispatched decode round's results and walk emissions."""
        phase = self._phase
        kind, *plan, device = pending
        # The wait ends when the round's results are on the host. The
        # emission walk after it is host time, under the next round's
        # program where the loop ran ahead and under none where it did not.
        with phase("engine/decode_wait", "engine", DEVICE_WAIT):
            host = self.executor.fetch(device)
        with phase("engine/decode_emit", "engine"):
            walk = self._spec_emit if kind == "spec" else self._decode_emit
            return walk(*plan, *host)

    def _decode_emit(self, rows: List[tuple], tokens: np.ndarray,
                     logprobs: np.ndarray) -> List[Request]:
        """Numeric guards and the per-slot emission walk of a plain round
        (``tokens``, ``logprobs``: (S,), a token a row, on the host). A
        row counts only if its slot still holds the request it held at the
        launch: a request that ended in the round before has a row in a
        round launched ahead, and its slot may hold another request by
        now, to whom that token does not belong."""
        self.stats["decode_steps"] += 1
        if self.executor.counter_names:
            # Rows after the slots': the step's counters.
            self._count(tokens[None, self.cfg.max_seqs:], decode=True)
            tokens = tokens[:self.cfg.max_seqs]
        active = [s for s, req in rows if s.request is req]
        self.stats["decode_rows_discarded"] += len(rows) - len(active)

        # Numeric guard — the WHOLE round is validated before any token
        # is appended: a partially-appended round would survive failover
        # (resubmit keeps generated-so-far tokens) and stream garbage.
        if self.cfg.guard_nonfinite:
            bad = [s.slot_id for s in active
                   if not np.isfinite(logprobs[s.slot_id])]
            if bad:
                self.stats["numeric_faults"] += 1
                raise NumericFault(
                    f"nonfinite decode output on slot(s) {bad}: the model "
                    f"is producing NaN/inf logits")
        if self.cfg.guard_token_storm > 0 and len(active) >= 2:
            col = {int(tokens[s.slot_id]) for s in active}
            self._storm_run = self._storm_run + 1 if len(col) == 1 else 0
            if self._storm_run >= self.cfg.guard_token_storm:
                self.stats["numeric_faults"] += 1
                raise NumericFault(
                    f"token storm: every active slot sampled the "
                    f"same token for {self._storm_run} consecutive "
                    f"steps (token {col.pop()})")

        finished = []
        for s in active:
            req = s.request  # (retirement clears the slot's)
            self.stats["decode_slot_steps"] += 1
            s.seq_len += 1  # the input token is now in the cache
            if self._append_token(s, int(tokens[s.slot_id]),
                                  float(logprobs[s.slot_id])):
                finished.append(req)
        return finished

    def _spec_round_gate(self, active: List["_Slot"]) -> List["_Slot"]:
        """Per-slot adaptive acceptance gate (``spec_min_acceptance``):
        tick each paused greedy slot's cooldown and return the greedy
        slots allowed to propose this round. A slot in cooldown rides the
        spec program masked to single-step (or the plain path, when every
        greedy slot is paused at once) — its batchmates keep speculating
        either way. ``spec_paused_rounds`` counts paused SLOT-rounds."""
        gate_on = self.cfg.spec_min_acceptance > 0.0
        out = []
        for s in active:
            if s.request.params.temperature != 0.0:
                continue
            sid = s.slot_id
            if gate_on and self._spec_slot_pause[sid] > 0:
                self._spec_slot_pause[sid] -= 1
                self.stats["spec_paused_rounds"] += 1
            else:
                out.append(s)
        return out

    def _spec_pick_k(self, parts: List["_Slot"]) -> int:
        """Draft length for this round, from the halving ladder
        (num_draft_tokens, /2, ..., 1): the smallest ladder member with
        one token of probe slack over the most optimistic participant's
        smoothed acceptance estimate. The slack is what lets the estimate
        climb back up — at the saturating k the estimate caps at k, and
        wanting k+1 selects the next rung. spec_adaptive=False pins the
        pre-ladder behavior (always the full draft)."""
        kmax = self.cfg.num_draft_tokens
        if not self.cfg.spec_adaptive:
            return kmax
        est = max(self._spec_slot_ewma[s.slot_id] for s in parts)
        want = min(kmax, int(np.ceil(est)) + 1)
        ladder = []
        kk = kmax
        while kk >= 1:
            ladder.append(kk)
            kk //= 2
        for kk in reversed(ladder):
            if kk >= want:
                return kk
        return kmax

    def _spec_note_slot(self, sid: int) -> None:
        """Close a slot's probe window when full: a window of mostly-
        rejected drafts pauses THAT slot for ``spec_cooldown`` rounds."""
        if (self.cfg.spec_min_acceptance > 0.0
                and self._spec_slot_prop[sid] >= self.cfg.spec_probe_window):
            rate = self._spec_slot_acc[sid] / self._spec_slot_prop[sid]
            if rate < self.cfg.spec_min_acceptance:
                self._spec_slot_pause[sid] = self.cfg.spec_cooldown
            self._spec_slot_prop[sid] = 0
            self._spec_slot_acc[sid] = 0

    def _spec_reset_slot(self, sid: int) -> None:
        self._spec_slot_prop[sid] = 0
        self._spec_slot_acc[sid] = 0
        self._spec_slot_pause[sid] = 0
        self._spec_slot_ewma[sid] = float(self.cfg.num_draft_tokens)

    def _book_decode_context(self, active: List[_Slot],
                             riding=frozenset()) -> None:
        """What a decode round attends over, booked when it is dispatched:
        the active slots' cached tokens (one more for a slot ``riding`` in
        the round still in flight), and the keys of the kernel tiles that
        hold them and the new token."""
        lens = np.fromiter((s.seq_len + (s.slot_id in riding) for s in active),
                           np.int64, len(active))
        tile = self.executor.decode_tile_tokens
        self.stats["decode_context_tokens"] += int(lens.sum())
        if self.window:
            self.stats["decode_window_context_tokens"] += \
                int(np.minimum(lens, self.window).sum())
        self.stats["decode_kernel_tile_tokens"] += \
            int((lens // tile + 1).sum()) * tile

    def _spec_prepare(self, active: List[_Slot], parts: List[_Slot],
                      k: int):
        """Arguments of the fused propose→verify→accept program.

        ``parts`` are the greedy slots allowed to propose this round
        (per-slot gate output); everyone else — sampling slots and greedy
        slots in cooldown — is masked to single-step inside the program.
        ``k`` is the ladder draft length picked for this round."""
        ec = self.cfg
        phase = self._phase
        with phase("engine/decode_assemble", "engine"):
            t_in = np.zeros((ec.max_seqs,), np.int32)
            seq_len = np.zeros((ec.max_seqs,), np.int32)
            spec_mask = np.zeros((ec.max_seqs,), np.bool_)
            for s in active:
                t_in[s.slot_id] = s.last_token
                seq_len[s.slot_id] = s.seq_len
            self._book_decode_context(active)
            self.stats["decode_steps_sorted_sampling"] += \
                self._sampling_sorts()
            for s in parts:
                spec_mask[s.slot_id] = True
            # Multi-query attention takes the gather path (the Pallas paged
            # kernel is single-token); bound its window to the blocks the
            # round's k + 1 positions can touch, quantized pow2 so jit
            # specializations stay O(log).
            nblk = max(self.block_manager.blocks_needed(
                s.seq_len + k + 1) for s in active)
            width = 1
            while width < nblk:
                width *= 2
            width = min(width, ec.max_blocks_per_seq)
        with phase("engine/decode_stage", "engine"):
            staged = self.executor.stage_spec(
                self._spec_hist, t_in, seq_len, spec_mask,
                self._state_mirrors(), self._masked_rows(),
                table_width=width)
        return ("spec", active, spec_mask, k, staged)

    def _spec_launch(self, active: List[_Slot], spec_mask, k: int, staged):
        """Dispatch the spec program (no sync)."""
        return ("spec", active, spec_mask,
                self.executor.launch_spec(staged, k))

    def _spec_emit(self, active: List[_Slot], spec_mask: np.ndarray,
                   toks: np.ndarray, lps: np.ndarray, emit: np.ndarray,
                   prop: np.ndarray, acc: np.ndarray) -> List[Request]:
        """Walk a spec round's emissions (``toks``, ``lps``: (S, k+1);
        ``emit``, ``prop``, ``acc``: (S,); all on the host). Per slot the
        device reports how many tokens were emitted (greedy: accepted
        prefix + bonus; sampling: exactly one); the host consumes them in
        order, stopping a slot at EOS/limit and discarding the rest."""
        self.stats["decode_steps"] += 1

        # Numeric guard over every EMITTED token (rejected draft
        # positions legitimately carry junk), before anything appends —
        # same no-garbage-survives-failover contract as plain decode.
        if self.cfg.guard_nonfinite:
            bad = [s.slot_id for s in active
                   if not np.isfinite(
                       lps[s.slot_id, :int(emit[s.slot_id])]).all()]
            if bad:
                self.stats["numeric_faults"] += 1
                raise NumericFault(
                    f"nonfinite speculative-decode output on slot(s) "
                    f"{bad}: the model is producing NaN/inf logits")

        finished = []
        for s in active:
            sid = s.slot_id
            req = s.request  # (retirement clears the slot's)
            self.stats["decode_slot_steps"] += 1
            # Only unmasked greedy slots actually proposed this round —
            # masked slots (sampling, or greedy in cooldown) ran single-
            # step and must not feed the acceptance windows.
            proposing = bool(spec_mask[sid])
            if proposing:
                self._spec_slot_prop[sid] += 1
                self._spec_slot_acc[sid] += int(emit[sid]) - 1
                # Smoothed accepted-drafts-per-round estimate for the
                # draft-length ladder (rounds with no lookup hit pull
                # it toward 0, as they should).
                self._spec_slot_ewma[sid] += 0.2 * (
                    int(acc[sid]) - self._spec_slot_ewma[sid])
                self.stats["spec_proposed"] += int(prop[sid])
                self.stats["spec_accepted"] += int(acc[sid])
            done = False
            for j in range(int(emit[sid])):
                s.seq_len += 1
                done = self._append_token(s, int(toks[sid, j]),
                                          float(lps[sid, j]))
                if done:
                    finished.append(req)
                    break
            if proposing and not done:
                self._spec_note_slot(sid)
        return finished

    def _append_token(self, slot: _Slot, token: int, logprob: float) -> bool:
        """Record a generated token; retire the slot when finished."""
        req = slot.request
        now = time.monotonic()
        if req.first_token_time is None:
            req.first_token_time = now
            self.telemetry.on_first_token(req)
        req.output_token_ids.append(token)
        req.output_logprobs.append(logprob)
        slot.last_token = token
        if self._spec_hist is not None:
            self._spec_hist[slot.slot_id, len(req.prompt_token_ids)
                            + len(req.output_token_ids) - 1] = token
        self._gen_counts[slot.slot_id] = len(req.output_token_ids)
        self.stats["generated_tokens"] += 1

        reason = None
        if req.cancel_requested:
            # Server-side early cancel (stop-string hit, disconnect):
            # finish as a normal stop so usage/latency accounting and
            # slot release follow the standard path.
            reason = "stop"
        elif token == self.cfg.eos_token_id or token in req.params.stop_token_ids:
            reason = "stop"
        elif len(req.output_token_ids) >= req.params.max_tokens:
            reason = "length"
        elif len(req.prompt_token_ids) + len(req.output_token_ids) >= self.cfg.max_model_len:
            reason = "length"
        if reason is not None:
            req.finish_reason = reason
            req.finish_time = now
            self.finished.append(req)
            self._settle_prefill_stall(req)
            self.telemetry.on_finished(req)
            self._release(slot)
            return True
        return False

    def _settle_prefill_stall(self, req: Request) -> None:
        """Close the request's mark on this engine's prefill wall: what the
        total has grown by since its prefill handed it a slot was other
        requests' prefill calls, during its decode."""
        if req._prefill_stall_mark is not None:
            req.prefill_stall_s += \
                self._prefill_wall_s - req._prefill_stall_mark
            req._prefill_stall_mark = None

    def _release_adapter(self, req: Request) -> None:
        """Drop the request's adapter-pool pin and reset it to unresolved
        (idempotent). Preemption and failover re-acquire at re-admission
        — the row may legitimately be LRU-evicted in between."""
        if self.executor.adapter_pool is not None and req._adapter_slot > 0:
            self.executor.adapter_pool.release(req._adapter_slot)
        req._adapter_slot = -1

    def _fail_waiting(self, req: Request, msg: str) -> None:
        """Finish a request that holds no slot as an error (unknown or
        corrupt adapter; a prefill call of its own that was refused, its
        slot released): strictly request-scoped — the engine, its slots,
        and the rest of the queue are untouched."""
        self.logger.warning("request %s failed at admission: %s",
                            req.request_id, msg)
        self._release_adapter(req)
        req.finish_reason = "error"
        req.finish_time = time.monotonic()
        self.finished.append(req)
        self.telemetry.on_finished(req)

    def _release(self, slot: _Slot, register: bool = True) -> None:
        if self.prefix_cache is not None and slot.request is not None:
            # Register the written full blocks for reuse (shared blocks get
            # their refcount dropped; the partial tail goes back to the
            # pool). A preempted mid-prefill slot has written only
            # next_pos tokens — caching past that would serve unwritten KV.
            # ``register=False`` (abort after a faulted step): the slot's
            # KV may never have been written at all, so drop shared refs
            # and free owned blocks WITHOUT registering any content keys —
            # an empty token chain does exactly that.
            req = slot.request
            n_written = slot.next_pos if slot.prefilling else slot.seq_len
            written = ((req.prompt_token_ids + req.output_token_ids)[:n_written]
                       if register else [])
            self.prefix_cache.release_sequence(written, slot.blocks,
                                               ns=req.adapter or None)
        else:
            self.block_manager.free(slot.blocks)
        self.kv_freed["full", "end"] += len(slot.blocks)
        if slot.window_blocks:
            self.window_manager.free(slot.window_blocks)
            self.kv_freed["window", "end"] += len(slot.window_blocks)
        slot.window_blocks = []
        slot.window_first = 0
        if slot.request is not None:
            self._release_adapter(slot.request)
            self._settle_prefill_stall(slot.request)
        slot.request = None
        slot.blocks = []
        slot.seq_len = 0
        slot.next_pos = 0
        slot.prefill_end = 0
        slot.shared_blocks = 0
        self._clear_row(slot.slot_id)
        self._spec_reset_slot(slot.slot_id)

    def _clear_row(self, slot_id: int) -> None:
        """A slot's row of every mirror as a free slot's reads, for the next
        upload: the trash block, default sampling (which sorts nothing), no
        recurrent state to write (it is dropped with the slot: nothing
        reads it again, and the next admission starts from zero)."""
        self._block_tables[slot_id] = 0
        self._window_tables[slot_id] = 0
        self._window_base[slot_id] = 0
        self._temperature[slot_id] = 1.0
        self._top_k[slot_id] = 0
        self._top_p[slot_id] = 1.0
        self._slot_keys[slot_id] = 0
        self._gen_counts[slot_id] = 0
        self._adapter_ids[slot_id] = 0
        self._state_slots[slot_id] = self.cfg.max_seqs

    # ------------------------------------------------------------------
    # Disaggregated prefill/decode handoff (serving/disagg.py)
    # ------------------------------------------------------------------
    def export_handoff(self, slot: _Slot) -> Optional[dict]:
        """Snapshot everything a decode replica needs to continue ``slot``'s
        request byte-identically, then release the slot locally.

        The KV leaves over the proven tier path (fetch_block_kv: device→
        host, staged through pinned_host where the backend has it) — only
        the blocks covering WRITTEN positions (0..seq_len-1) travel; the
        decode side allocates its own chain and restores into it. The
        snapshot carries the origin slot's actual rng key bytes: an
        unseeded request's key came from the origin engine's private rng
        split and cannot be re-derived elsewhere, and the decode program's
        fold_in(key, gen_count) stream must continue exactly where prefill
        sampling left it. Returns None (slot untouched) if any block fetch
        fails — the caller falls back to a re-prefill elsewhere.

        With a decode round in flight the snapshot is as of the tokens
        emitted: the token that round draws for this slot is thrown away
        when the round is fetched (the slot no longer holds the request),
        and the adopting engine draws it again from the same key and count.
        The block fetches wait for the round, whose one write for this
        sequence is the key and value the adopter's first step writes too.
        """
        refuse_state_handoff(self.model_cfg, "export_handoff")
        req = slot.request
        n_blocks = self.block_manager.blocks_needed(slot.seq_len)
        payloads = []
        for b in slot.blocks[:n_blocks]:
            p = self.executor.fetch_block_kv(b)
            if p is None:
                return None
            payloads.append(p)
        snap = {
            "request": req,
            "payloads": payloads,
            "seq_len": slot.seq_len,
            "last_token": slot.last_token,
            "slot_key": self._slot_keys[slot.slot_id].copy(),
            "gen_count": int(self._gen_counts[slot.slot_id]),
            # Adaptive-spec controller state rides along so the adopting
            # engine's gate resumes mid-window instead of re-probing from
            # scratch (the token history itself is rebuilt from the
            # request's tokens on adopt). Additive dict of plain scalars:
            # serializes through the generic wire envelope unchanged.
            "spec": {
                "prop": int(self._spec_slot_prop[slot.slot_id]),
                "acc": int(self._spec_slot_acc[slot.slot_id]),
                "pause": int(self._spec_slot_pause[slot.slot_id]),
                "ewma": float(self._spec_slot_ewma[slot.slot_id]),
            },
        }
        self._release(slot)
        return snap

    def adopt_handoff(self, snap: dict) -> bool:
        """Admit a prefilled request whose KV arrives as host payloads
        (:meth:`export_handoff` counterpart): take a free slot, allocate a
        fresh block chain, scatter the payloads in via the tier-restore
        path, and seed the slot so the next decode step samples exactly
        the token the origin engine would have. Returns False (nothing
        consumed) when no slot or not enough blocks are free — the caller
        retries or degrades to a re-prefill."""
        refuse_state_handoff(self.model_cfg, "adopt_handoff")
        slot = next((s for s in self.slots if s.free), None)
        if slot is None:
            return False
        req = snap["request"]
        # Re-pin the request's adapter on THIS engine's pool before
        # consuming anything: the origin pin died with the origin slot.
        # Busy pool or load failure → False, nothing consumed — the
        # caller retries or degrades to a re-prefill, where _admit's
        # resolution path owns failing the request properly.
        if req.adapter and req._adapter_slot < 0:
            if self.executor.adapter_pool is None:
                return False
            try:
                row, _ = self.executor.adapter_pool.acquire(req.adapter)
            except AdapterError:
                return False
            if row < 0:
                return False
            req._adapter_slot = row
        seq_len = snap["seq_len"]
        # +1: the first decode step writes KV at position seq_len.
        blocks = self._alloc(self.block_manager.blocks_needed(seq_len + 1))
        if blocks is None:
            return False
        # Closes the kv_handoff stall mark (note_readmitted); the origin
        # admission already stamped admitted_time, so queue-time samples
        # are not double counted.
        self.telemetry.on_admitted(req)
        slot.request = req
        slot.blocks = blocks
        slot.seq_len = seq_len
        slot.next_pos = seq_len
        slot.prefill_end = seq_len
        slot.last_token = snap["last_token"]
        req._prefill_stall_mark = self._prefill_wall_s
        row = np.zeros((self.cfg.max_blocks_per_seq,), np.int32)
        row[: len(blocks)] = blocks
        self._block_tables[slot.slot_id] = row
        self._temperature[slot.slot_id] = req.params.temperature
        self._top_k[slot.slot_id] = req.params.top_k
        self._top_p[slot.slot_id] = req.params.top_p
        self._slot_keys[slot.slot_id] = snap["slot_key"]
        self._gen_counts[slot.slot_id] = snap["gen_count"]
        self._adapter_ids[slot.slot_id] = max(req._adapter_slot, 0)
        if self._spec_hist is not None:
            ctx = req.prompt_token_ids + req.output_token_ids
            self._spec_hist[slot.slot_id, : len(ctx)] = ctx
        spec = snap.get("spec")
        if spec:
            # Resume the per-slot adaptive gate where the origin left it
            # (.get: snapshots from engines predating the controller —
            # or with speculation off — restore to the fresh-slot state).
            self._spec_slot_prop[slot.slot_id] = int(spec.get("prop", 0))
            self._spec_slot_acc[slot.slot_id] = int(spec.get("acc", 0))
            self._spec_slot_pause[slot.slot_id] = int(spec.get("pause", 0))
            self._spec_slot_ewma[slot.slot_id] = float(
                spec.get("ewma", self.cfg.num_draft_tokens))
        for b, payload in zip(blocks, snap["payloads"]):
            self.executor.restore_block(b, payload)
        return True

    def abort_all(self, reason: str = "abort") -> List[Request]:
        """Fail every in-flight and queued request and free their slots.

        The server's step-failure recovery: after a faulted
        ``engine.step()`` the queues' consumers are gone, so leaving the
        requests in place would either hot-loop the same failing program
        (persistent faults) or burn decode rounds generating
        tokens nobody reads (transient faults). Returns the aborted
        requests (their ``finish_reason`` is set to ``reason``). A round
        in flight is waited for and its tokens thrown away first.
        """
        self._drop_inflight()
        aborted: List[Request] = []
        for slot in self.slots:
            if slot.request is not None:
                req = slot.request
                req.finish_reason = reason
                req.finish_time = time.monotonic()
                aborted.append(req)
                # register=False: the faulted step may never have written
                # this slot's KV — registering it in the prefix cache
                # would serve garbage to later cache hits.
                self._release(slot, register=False)
        while self.waiting:
            req = self.waiting.popleft()
            # A queue-head request may hold an adapter pin (resolution
            # happened, block allocation then broke the pass).
            self._release_adapter(req)
            req.finish_reason = reason
            req.finish_time = time.monotonic()
            aborted.append(req)
        for req in aborted:
            self.telemetry.on_finished(req)
        return aborted

    def _preempt_youngest(self, exclude: _Slot) -> bool:
        """Evict the most-recently-arrived sequence back to the queue."""
        candidates = [s for s in self.slots if not s.free and s is not exclude]
        if not candidates:
            return False
        victim = max(candidates, key=lambda s: s.request.arrival_time)
        req = victim.request
        req.num_preemptions += 1
        self.stats["preemptions"] += 1
        self.telemetry.on_preempted(req)
        self.waiting.appendleft(req)
        self._release(victim)
        self.logger.info("preempted %s (recompute on readmit)", req.request_id)
        return True

    # ------------------------------------------------------------------
    def _result(self, req: Request) -> GenerationResult:
        return GenerationResult(
            request_id=req.request_id,
            prompt_token_ids=req.prompt_token_ids,
            output_token_ids=req.output_token_ids,
            output_logprobs=req.output_logprobs,
            finish_reason=req.finish_reason or "abort",
            ttft_s=(req.first_token_time or req.arrival_time) - req.arrival_time,
            latency_s=(req.finish_time or time.monotonic()) - req.arrival_time,
        )
