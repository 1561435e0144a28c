"""The device half of the serving engine.

:class:`EngineExecutor` owns everything that touches a device array: the
weights, the paged cache, the per-slot decode state, the adapter pool, the
compiled programs and their calling convention. The scheduler
(:class:`dlti_tpu.serving.engine.InferenceEngine`) plans a round in host
(numpy) arrays and calls one entry here per kind of program call; what
comes back is on the device and not waited for until :meth:`fetch`.
"""

from __future__ import annotations

import json
import math
from functools import partial
from typing import TYPE_CHECKING, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dlti_tpu.config import LoRAConfig, ModelConfig
from dlti_tpu.models import build_model
from dlti_tpu.ops.kv_cache import (
    bind_call, init_cache, unbind_call, window_blocks,
)
from dlti_tpu.ops.pallas.latent_attention import (
    tile_tokens as latent_tile_tokens,
)
from dlti_tpu.ops.pallas.paged_attention import tile_tokens
from dlti_tpu.serving.decode_state import RoundPacking
from dlti_tpu.serving.sampling import sample_tokens
from dlti_tpu.telemetry.memledger import MemoryLedger, tree_nbytes
from dlti_tpu.utils.logging import get_logger
from dlti_tpu.utils.native import native_runtime_name
from dlti_tpu.utils.platform import device_facts

if TYPE_CHECKING:
    from dlti_tpu.serving.engine import EngineConfig

# The id the scheduler gives a decode row whose input token is not on the
# host yet: the row rides from the round before, still in flight, and the
# decode program reads that round's sampled token on the device.
RIDES = -1


class PrefillCallRefused(RuntimeError):
    """A prefill program could not be called at a shape: the call raised
    before the program ran (it did not compile, or was refused its memory),
    so nothing was written and the cache is whole. ``shape``: ``(rows,
    bucket, table width)``, rows as padded. (No program holds logits
    over every position, the array the compiler used to refuse; one can
    still fail to fit beside the weights and the cache.)"""

    def __init__(self, shape: tuple, first_line: str):
        super().__init__(
            "prefill program refused at %d rows x %d tokens x %d blocks "
            "a row: %s" % (*shape, first_line))
        self.shape = shape


# A call of several rows is held to the padded tokens whose float32 logits
# over every position would take this share of one device's memory. No
# program computes those any more (a prefill program heads one position a
# row: ``_model_cache_call``, ``last_idx``), so the limit guards against
# nothing in the program. It stays because it decides which calls the
# engine forms, and those are the shapes the benchmark's cells warm (up to
# 4,096 padded tokens of qwen2_7b's 152k-row head, 8 x 2,048 of mistral_7b's
# 32k rows): a call of another shape inside a measured window is a
# compilation. It goes once the cells' warm lists are wider (ROADMAP C16).
PREFILL_LOGITS_SHARE = 0.2


def prefill_group_tokens(vocab_size: int, device_bytes: int) -> int:
    """The most padded tokens a prefill call of several rows may hold
    (``PREFILL_LOGITS_SHARE``); 0, no limit, where the device's memory is
    not known (the CPU backend reports none)."""
    return int(PREFILL_LOGITS_SHARE * device_bytes) // (4 * vocab_size)


def refuse_unsupported(model_cfg: ModelConfig, engine_cfg: "EngineConfig",
                       mesh=None) -> None:
    """Refuse, at start-up and with one clear error each, every feature
    that takes a sequence's state to be its k/v blocks when the model has
    recurrent layers or keeps latents, and what the patterned and the
    latent-attention families do not implement. A model with stream maps
    (``hc_mult``) is of the latent family and is named as such."""
    if model_cfg.num_nextn_predict_layers > 0:
        raise ValueError(
            f"num_nextn_predict_layers {model_cfg.num_nextn_predict_layers}: "
            f"no model here builds a multi-token-prediction module and the "
            f"engine's speculative rounds draft by ngram alone; serve the "
            f"model's own layers with num_nextn_predict_layers 0")
    if not (model_cfg.layer_pattern or model_cfg.latent_dim):
        refuse_unsupported_dense(model_cfg, engine_cfg, mesh)
        return
    ec = engine_cfg
    what = (f"a model with layer_pattern {model_cfg.layer_pattern!r}"
            + (" (Mamba-1 layers, paired heads for differential attention, "
               "one pool that several layers read)"
               if model_cfg.is_sambay else
               " (Mamba-1 layers with inner norms, plain attention layers "
               "with their own pools)" if model_cfg.is_jamba else "")
            if model_cfg.layer_pattern else
            f"a model with latent attention (kv_lora_rank "
            f"{model_cfg.kv_lora_rank})")
    if model_cfg.hc_mult:
        what += (f" and {model_cfg.hc_mult} hyper-connected residual "
                 f"streams (float32 stream maps round every sublayer)")
    if model_cfg.latent_dim:
        if ec.cache_dtype == "int8":
            raise ValueError(
                f"{what} keeps one row of latent and rotated key a token, "
                f"which has no int8 layout (one scale cannot serve both "
                f"parts); serve it with --kv-cache-dtype bfloat16")
        if ec.prefix_host_blocks > 0 or ec.prefix_disk_blocks > 0:
            raise ValueError(
                f"{what} keeps its cache as latent blocks; the host and "
                f"disk prefix tiers store and verify k/v payloads "
                f"(serving/prefix_tiers.py). Serve it with "
                f"--enable-prefix-caching alone (the HBM tier treats a "
                f"latent block like any other)")
    if (model_cfg.is_sambay or model_cfg.is_jamba) \
            and ec.cache_dtype == "int8":
        raise ValueError(
            f"{what} keeps its keys and values in fused pools (a token's "
            f"row is its key-value heads side by side: two heads' keys in "
            f"one row of the decoder-hybrid-decoder family, the jamba "
            f"family's one head), which have no int8 scale a row and no "
            f"reference held against one; serve it with --kv-cache-dtype "
            f"bfloat16")
    if model_cfg.has_recurrent_state:
        why = (f"{what} keeps a recurrent state per decode slot beside its "
               f"k/v blocks, and ")
        if (ec.enable_prefix_caching or ec.prefix_host_blocks > 0
                or ec.prefix_disk_blocks > 0):
            raise ValueError(
                why + "prefix caching (and its host/disk tiers) reuses k/v "
                "blocks alone: a cached prefix would resume from the wrong "
                "state. Serve it without --enable-prefix-caching; state "
                "snapshots for prefix reuse are not implemented")
    if ec.speculative != "none":
        raise ValueError(
            f"{what} cannot be served with speculative decoding: rejected "
            f"drafts are rolled back by position in the k/v cache, and a "
            f"recurrent state cannot be rolled back (nor does the "
            f"speculative program thread it, or the counters of a model "
            f"that counts; the verify step over latents is held to no "
            f"reference). Serve it with --speculative none")
    if mesh is not None:
        raise ValueError(
            f"{what} has no tensor-parallel sharding rules (state-space, "
            f"latent-attention and held-expert layers, tables a group of "
            f"layers); serve it on one chip per replica")
    if ec.quantization != "none":
        raise ValueError(
            f"{what} is served in its own precision: weight-only "
            f"{ec.quantization} is not implemented for state-space, "
            f"latent-attention and expert layers")
    if ec.adapter_slots > 0:
        raise ValueError(
            f"{what} has no multi-LoRA adapter branch; serve it with "
            f"--adapter-slots 0")


def refuse_unsupported_dense(model_cfg: ModelConfig,
                             engine_cfg: "EngineConfig", mesh=None) -> None:
    """The same for the Llama family: what takes a sequence's cache to be
    ONE list of blocks that are all there, when the layers' windows differ
    (a window group's blocks are released behind the window); and what
    held experts under the Llama block do not implement."""
    ec = engine_cfg
    if model_cfg.ut_steps > 1:
        # (What walks a sequence's cache entry by entry works as it is:
        # prefix caching and its tiers, hand-off, preemption, int8 keys
        # and values: tests/test_looped_layers.py.)
        what = (f"a model whose layers run {model_cfg.ut_steps} times over "
                f"one set of weights (ut_steps)")
        if ec.speculative != "none":
            raise ValueError(
                f"{what} counts what its forward pass did (passes, the "
                f"exit gate), which the speculative program does not "
                f"thread. Serve it with --speculative none")
        if mesh is not None:
            raise ValueError(
                f"{what} has no tensor-parallel placement for its exit "
                f"gate, and its pools of {model_cfg.ut_steps} entries a "
                f"layer have been sharded by no test; serve it on one chip "
                f"per replica")
        if ec.quantization != "none":
            raise ValueError(
                f"{what} is served in its own precision: weight-only "
                f"{ec.quantization} has no rule for the exit gate, and a "
                f"rounding made once is applied {model_cfg.ut_steps} times "
                f"a token, which no reference has been held against")
        if ec.adapter_slots > 0:
            raise ValueError(
                f"{what} has no multi-LoRA adapter branch held to a "
                f"reference (an adapter would be applied in every pass); "
                f"serve it with --adapter-slots 0")
    groups = model_cfg.kv_group_windows
    if len(groups) > 1:
        what = (f"a model whose layers differ in their attention window "
                f"(layer_windows: {groups[1]} and every key) keeps a block "
                f"list a group of layers and releases a window group's "
                f"blocks behind the window, and ")
        if (ec.enable_prefix_caching or ec.prefix_host_blocks > 0
                or ec.prefix_disk_blocks > 0):
            raise ValueError(
                what + "prefix caching (and its host/disk tiers) matches "
                "and shares one list of whole blocks: a hit would need the "
                "prefix's last window in the window group too. Serve it "
                "without --enable-prefix-caching")
        if ec.speculative != "none":
            raise ValueError(
                what + "speculative decoding rolls rejected drafts back by "
                "position over blocks that may have been released. Serve "
                "it with --speculative none")
        if mesh is not None:
            raise ValueError(
                what + "its tables have no tensor-parallel placement; "
                "serve it on one chip per replica")
    if model_cfg.moe_num_experts > 0:
        what = (f"a model with held routed experts "
                f"({model_cfg.moe_held} of {model_cfg.moe_num_experts})")
        if mesh is not None:
            raise ValueError(
                f"{what} has no tensor-parallel sharding rules for its "
                f"expert layers; serve it on one chip per replica")
        if ec.quantization != "none":
            raise ValueError(
                f"{what} is served in its own precision: weight-only "
                f"{ec.quantization} is not implemented for expert layers")
        if ec.adapter_slots > 0:
            raise ValueError(
                f"{what} has no multi-LoRA adapter branch; serve it with "
                f"--adapter-slots 0")
        if ec.speculative != "none":
            raise ValueError(
                f"{what} counts what its forward pass did, which the "
                f"speculative program does not thread. Serve it with "
                f"--speculative none")


class EngineExecutor:
    """The device half of the engine: weights, paged-KV pools, the
    packing of a decode round's host inputs (:class:`RoundPacking`), the
    adapter pool,
    and every compiled program (bucketed prefill, the decode ladder,
    speculative decode, fused sampling, the tier-restore scatter) with
    its calling convention, plus the device<->host block transport
    (:meth:`fetch_block_kv` / :meth:`restore_block`).

    Holds NO scheduling state — slots, queues, block accounting,
    admission, and retirement live in :class:`InferenceEngine`, which
    plans each round in host arrays and calls one entry per kind of
    program call: :meth:`prefill`, :meth:`stage_decode` then
    :meth:`launch_decode`, :meth:`stage_spec` then :meth:`launch_spec`
    (the split follows the spans ``engine/decode_prep`` and
    ``engine/decode_launch``), and :meth:`fetch` to wait for results.
    This class alone rebinds the donated cache, chooses and builds the
    program, uploads and orders the arguments, and knows what rides
    after the per-slot state (``_trailing``). Disaggregated serving
    (``serving.disagg``) builds on the same split: a prefill-only
    engine's executor never runs (or warms) the decode ladder, and
    paged-KV handoff between pools talks to the block transport.
    """

    def __init__(
        self,
        model_cfg: ModelConfig,
        params,
        engine_cfg: "EngineConfig",
        lora_cfg: Optional[LoRAConfig] = None,
        mesh=None,
        donate_params: bool = False,
        stats: Optional[dict] = None,
    ):
        self.cfg = engine_cfg
        self.model_cfg = model_cfg
        self.logger = get_logger()
        self.mesh = mesh
        if mesh is not None:
            # Tensor-parallel serving: weights and KV pools shard over the
            # 'tensor' axis (attention heads / MLP hidden / vocab); GSPMD
            # inserts the collectives in the jitted prefill/decode programs.
            # Other axes stay 1 — batch-level scaling is a replica concern.
            bad = [ax for ax, n in mesh.shape.items()
                   if n > 1 and ax != "tensor"]
            if bad:
                raise ValueError(
                    f"serving mesh may only extend the 'tensor' axis; got "
                    f"{dict(mesh.shape)} (axes {bad} > 1)")
            tp = mesh.shape["tensor"]
            if model_cfg.num_kv_heads % tp or model_cfg.num_heads % tp:
                raise ValueError(
                    f"tensor={tp} must evenly divide num_heads="
                    f"{model_cfg.num_heads} and num_kv_heads="
                    f"{model_cfg.num_kv_heads}")
        refuse_unsupported(model_cfg, engine_cfg, mesh)
        self.model = build_model(model_cfg, lora_cfg, mesh)
        # A model may count what its forward pass did (``counter_names``:
        # int32 scalars it returns, by name, with ``return_counters``);
        # every program then returns them as rows after its tokens, and
        # the engine books them in ``stats`` under their names. A model
        # with recurrent layers keeps a per-slot state beside the paged
        # cache, and every program takes each row's slot (``state_slots``)
        # as the last of its per-slot arguments.
        self.counter_names = tuple(getattr(self.model, "counter_names", ()))
        # The most padded tokens one prefill call may hold (0: no limit).
        self.prefill_call_tokens = getattr(self.model, "prefill_call_tokens", 0)
        # The most a call of several rows may hold (0: no limit), by the
        # smallest device's memory (PREFILL_LOGITS_SHARE says why it stays).
        self.prefill_group_tokens = prefill_group_tokens(
            model_cfg.vocab_size, min(
                ((d.memory_stats() or {}).get("bytes_limit", 0)
                 for d in jax.local_devices()), default=0))
        # The ``(rows, bucket, table width)`` at which a prefill call has
        # been refused in this process (``PrefillCallRefused``): the
        # scheduler does not form such a call again.
        self.refused_prefill_shapes: set = set()
        # Whether a prefill call takes each row's whole block table (a model
        # whose cached context is cheap to gather) or the narrowest power
        # of two that holds the call's rows.
        self.prefill_whole_tables = getattr(
            self.model, "prefill_whole_tables", False)
        # What a prefill call's attention does by its shape, for a model
        # that says (the latent family: tokens through the flash kernel,
        # steps of the loop over cached latents); None for the others.
        self.prefill_kernel_counts = getattr(
            self.model, "prefill_kernel_counts", None)
        self._recurrent = model_cfg.has_recurrent_state
        self._quantized = engine_cfg.quantization == "int8"
        if engine_cfg.quantization not in ("none", "int8"):
            raise ValueError(f"unknown quantization {engine_cfg.quantization!r}")
        if self._quantized:
            # Composes with TP: the sharding rules match quantized
            # {"q","scale"} leaves on the kernel's own path (int8 kernels
            # shard like their fp ancestors; scales follow the output
            # channels and replicate for row-parallel kernels).
            # donate_params frees each source leaf as it quantizes — at 7B
            # the bf16 and int8 trees cannot coexist in one chip's HBM.
            from dlti_tpu.models.quantization import quantize_params_int8

            params = quantize_params_int8(params, donate=donate_params)
        self._device = None
        if mesh is None:
            # Pin host-resident weights to a serving device once.
            # Checkpoint restores hand back numpy arrays; without this
            # every compiled call re-uploads the whole tree. Leaves that
            # are already committed jax.Arrays keep
            # their placement — ReplicatedEngine pins each replica's copy
            # to its own device before construction — and that device
            # becomes THE engine device: the KV pool is committed to it
            # too (below), so warmup's AOT lowering and every compiled
            # call agree on placement instead of relying on jit's
            # uncommitted-operand migration.
            dev = next((d for leaf in jax.tree_util.tree_leaves(params)
                        if isinstance(leaf, jax.Array)
                        and getattr(leaf, "committed", False)
                        for d in leaf.devices()), jax.devices()[0])
            self._device = dev
            params = jax.tree_util.tree_map(
                lambda x: x if isinstance(x, jax.Array)
                and getattr(x, "committed", False)
                else jax.device_put(x, dev), params)
        self.params = params

        # Multi-LoRA adapter pool: stacked per-module A/B tensors the
        # compiled programs gather per batch row (serving.adapters). Built
        # AFTER quantization/placement so the target-shape walk sees the
        # final param layout (int8 kernels keep their shape in "q") and
        # the pool lands on the engine device alongside the weights.
        self.adapter_pool = None
        if engine_cfg.adapter_slots > 0:
            from dlti_tpu.serving.adapters import AdapterPool

            self.adapter_pool = AdapterPool(
                self.params, engine_cfg.adapter_slots,
                engine_cfg.adapter_rank, engine_cfg.adapter_targets,
                device=self._device, mesh=mesh)

        ec = engine_cfg
        from dlti_tpu.utils.dtypes import resolve_dtype

        # "int8" selects the quantized pool layout (int8 payload +
        # per-row fp32 scales — ops.kv_cache): half the KV HBM of bf16,
        # which buys roughly twice the decode slots on a fixed chip.
        dtype = "int8" if ec.cache_dtype == "int8" else resolve_dtype(ec.cache_dtype)
        # One cache, one entry a layer: block pools of keys and values for
        # attention layers, per-slot recurrent state for Mamba-2 layers.
        # Attention layers of one window form a group with its own pools,
        # table and allocator (one group for a model whose layers agree).
        self.kv_groups = model_cfg.kv_group_windows
        self._layer_groups = None if len(self.kv_groups) == 1 else [
            model_cfg.kv_group_of_layer(i)
            for i in range(model_cfg.num_layers)]
        self.cache = init_cache(
            model_cfg, ec.num_blocks, ec.block_size, ec.max_seqs, dtype,
            call_tokens=self.prefill_call_tokens)
        if mesh is not None:
            self._shard_for_tp(mesh)
        elif self._device is not None:
            # Commit the pool to the engine device (see the params pin
            # above): a replica off the default device otherwise starts
            # with a device-0 pool that only migrates on first dispatch.
            self.cache = jax.device_put(self.cache, self._device)
        # Fixed when the pool is made. (Not read off the arrays at scrape
        # time: a handler thread would meet buffers a program call has just
        # been given.)
        self.pool_bytes = tree_nbytes(self.cache)
        self.recurrent_state_pool_bytes = tree_nbytes(
            [c for c in self.cache if "ssm" in c])
        # Bytes a token of context holds over every entry of the pools of
        # ``num_blocks`` (a looped stack's hold ``ut_steps`` entries each;
        # a window group's smaller pools apart).
        pools = [c.get("k", c.get("latent")) for c in self.cache]
        self.kv_bytes_per_context_token = sum(
            tree_nbytes(c) for c, pool in zip(self.cache, pools)
            if pool is not None
            and pool.shape[0] == model_cfg.ut_steps * ec.num_blocks
        ) // (ec.num_blocks * ec.block_size)
        # Keys a step of the paged decode kernel covers at this engine's
        # shapes (the scheduler's decode_kernel_tile_tokens counts in it):
        # by the rule of the kernel the pool is read by, of latents or of
        # keys and values.
        pool = next((p for p in pools if p is not None), None)
        token_bytes = 0 if pool is None \
            else math.prod(pool.shape[2:]) * pool.dtype.itemsize
        rule = latent_tile_tokens if any("latent" in c for c in self.cache) \
            else tile_tokens
        self.decode_tile_tokens = rule(
            ec.block_size, ec.max_blocks_per_seq, token_bytes)

        self._restore_fn = None  # lazily-jitted tier/handoff restore scatter
        # Block fetches stage device→host through pinned_host when the
        # backend exposes it — the ZeRO-3 offload path. Both prefix-tier
        # demotion and disaggregated KV handoff use it.
        self._demote_sharding = None
        dev = self._device or jax.devices()[0]
        memory_kinds = sorted(m.kind for m in dev.addressable_memories())
        if "pinned_host" in memory_kinds:
            from jax.sharding import SingleDeviceSharding

            self._demote_sharding = SingleDeviceSharding(
                dev, memory_kind="pinned_host")

        # One line, once per engine: where it runs and what each attention
        # site resolved to (chip_smoke.py and operators read it).
        from dlti_tpu.ops.attention import resolve_paged_decode

        decode_path, decode_why = resolve_paged_decode(
            model_cfg.paged_attention_impl,
            tp_sharded=mesh is not None and mesh.shape["tensor"] > 1)
        own = list(mesh.devices.flat) if mesh is not None else [dev]
        self.logger.info("engine build: %s", json.dumps({
            **device_facts(),
            "engine_devices": [str(d) for d in own],
            # Weights and pool are placed: what each of THIS engine's
            # chips holds now (null on the CPU backend — no stats).
            "device_bytes_in_use": {
                str(d): (d.memory_stats() or {}).get("bytes_in_use")
                for d in own},
            "model_layers": model_cfg.num_layers,
            # a looped stack keeps an entry a (pass, layer)
            "cache_entries": model_cfg.cache_entries,
            "kv_bytes_per_context_token": self.kv_bytes_per_context_token,
            "prefill_group_tokens": self.prefill_group_tokens,
            "param_dtype": ("int8" if self._quantized
                            else model_cfg.param_dtype),
            "kv_cache_dtype": ec.cache_dtype,
            "prefill_attention": "xla",
            "prefill_attention_reason": (
                "prefill walks the paged cache in blocks of keys"
                if self._layer_groups is not None else
                "prefill attends over the gathered paged window"),
            "kv_groups": list(self.kv_groups),
            "paged_decode": decode_path,
            "paged_decode_reason": decode_why,
            "host_staging": ("pinned_host" if self._demote_sharding
                             is not None else "none"),
            "memory_kinds": memory_kinds,
            "block_allocator": native_runtime_name(),
        }, sort_keys=True))

        self._prefill_fns: Dict[int, callable] = {}
        self._decode_fn = self._build_decode_fn()
        if ec.speculative not in ("none", "ngram"):
            raise ValueError(f"unknown speculative mode {ec.speculative!r}")
        # Draft-length ladder (spec_adaptive): one spec program per pow2 k
        # on the halving ladder, compiled lazily on first dispatch at that
        # k; the full-k program is built with the engine.
        self._spec_fns: Dict[int, callable] = {}
        if ec.speculative == "ngram":
            self._spec_fns[ec.num_draft_tokens] = self._build_spec_decode_fn(
                ec.num_draft_tokens)
        self._sample_fn = jax.jit(sample_tokens)
        # ``(rows, bucket, table width)`` of the newest prefill call.
        self.last_prefill_shape: Optional[tuple] = None
        if self.counter_names:
            # First tokens of a prefill with the prefill program's counters
            # as rows after them: one fetch brings both.
            def sample_counted(logits, keys, temperature, top_k, top_p,
                               counters):
                tokens, logprobs = sample_tokens(logits, keys, temperature,
                                                 top_k, top_p)
                return jnp.concatenate([tokens, counters]), logprobs

            self._sample_counted_fn = jax.jit(sample_counted)

        # Batched per-slot key folding (the same fold the decode program
        # applies to raw uint32 key data): one async dispatch instead of a
        # synchronous device round trip per admitted row.
        self._fold_keys = jax.jit(jax.vmap(jax.random.fold_in))
        # Source of the per-slot sampling keys of unseeded requests.
        self._rng = jax.random.PRNGKey(0)
        # Counters of prefill chunks that sampled nothing (chunked
        # prefill), still on the device: added to the next fetch.
        self._prefill_counters = None

        # What every program takes after its per-row arguments, the rule's
        # one home (``_named`` is its receiving side, inside the programs):
        # one more per-row array — each row's adapter-pool row, or the slot
        # a row may write its recurrent state to — and after it, with a
        # multi-LoRA pool, the pool's tree.
        self._row_extra = ("adapter_ids" if self.adapter_pool is not None
                           else "state_slots" if self._recurrent else None)
        # A plain decode round's host inputs (tokens, positions, every
        # per-slot mirror) go up as one packed int32 array that the decode
        # program unpacks itself: one transfer and one program call a
        # round, nothing per-slot resident between rounds.
        self.round_packing = RoundPacking(
            ec.max_seqs, ec.max_blocks_per_seq, self._row_extra,
            window_blocks=0 if self._layer_groups is None else window_blocks(
                self.kv_groups[1], ec.block_size, 1))
        # Where a round goes, committed: this engine's device, or every
        # chip of the tensor mesh (replicated).
        self._round_sharding = self._device
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._round_sharding = NamedSharding(mesh, P())
        # What a decode round costs the host, booked in ``stats`` (the
        # scheduler's dict, where it gives one; on /metrics): arrays
        # staged host-to-device for decode rounds, and program calls made
        # for them. Each over the rounds launched reads 1.0 for plain
        # rounds (a speculative round ships its mirrors one by one).
        self.stats = stats if stats is not None else {}
        for k in ("decode_host_uploads", "decode_program_calls"):
            self.stats.setdefault(k, 0)
        # What the one-step decode program takes for "the round before"
        # when there is none: nothing rides, so nothing reads it.
        self._no_prev = jax.device_put(
            np.zeros((ec.max_seqs + len(self.counter_names),), np.int32),
            self._round_sharding)

    # ------------------------------------------------------------------
    def _shard_for_tp(self, mesh) -> None:
        """Place weights and KV pools on the TP mesh.

        Params follow the training TP rules (column/row-parallel
        projections, sharded vocab); each layer's K/V pool shards its
        kv_heads dim. Block tables and sampling state stay replicated.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlti_tpu.config import Config, ParallelConfig
        from dlti_tpu.parallel.sharding import param_shardings

        cfg = Config(model=self.model_cfg,
                     parallel=ParallelConfig(tensor=mesh.shape["tensor"]))
        p_sh = param_shardings(self.params, cfg, mesh)
        self.params = jax.tree_util.tree_map(jax.device_put, self.params, p_sh)
        kv_sh = NamedSharding(mesh, P(None, None, "tensor", None))
        scale_sh = NamedSharding(mesh, P(None, None, "tensor"))
        self.cache = [
            {k: jax.device_put(v, scale_sh if k.endswith("_scale") else kv_sh)
             for k, v in l.items()}
            for l in self.cache
        ]

    # ------------------------------------------------------------------
    # Compiled programs
    # ------------------------------------------------------------------
    def _model_cache_call(self, params, cache_kv, block_tables, input_ids,
                          positions, adapter_ids=None, adapters=None,
                          state_slots=None, own_rows: bool = False,
                          last_idx=None):
        """Run the model over the cache; returns ``(logits, new cache list,
        counters)``. ``state_slots`` (a model with recurrent layers): each
        row's decode slot, out of range for a row that must write no
        recurrent state; ``own_rows`` says that this is a decode call, in
        which row i is slot i. ``counters`` is a vector in the order of
        ``self.counter_names``, or None for a model that counts nothing.

        ``last_idx`` (a prefill call; ``(rows,)``): the one position of each
        row whose logits anyone reads. The model then hands back its final
        hidden states, each row's state there is taken out of them, and the
        head (``model.head_matrix``: the matrix and the dtypes of
        ``__call__``'s own product) multiplies those ``(rows, hidden)``
        alone: logits ``(rows, vocab)``, and no array of every position
        by the vocabulary in the program.

        Quantized params pass through as-is — each module dequantizes its
        own weights at the consumer (``models.quantization.maybe_dequantize``),
        so only the executing layer holds a compute-dtype copy.

        With a multi-LoRA pool, ``adapters`` (the stacked A/B tree) rides
        in as a Flax variable collection and ``adapter_ids`` (one pool row
        per batch row) gathers each row's factors inside LoRADense; both
        absent leaves the traced program identical to an adapter-free
        engine (the branch is Python-static)."""
        cache = bind_call(cache_kv, block_tables, state_slots, own_rows,
                          groups=self._layer_groups)
        variables = {"params": params}
        kw = {}
        if adapters is not None:
            variables["adapters"] = adapters
            kw["adapter_ids"] = adapter_ids
        if self.counter_names:
            kw["return_counters"] = True
        if last_idx is not None:
            kw["return_hidden"] = True
        out, new_cache, *counted = self.model.apply(
            variables, input_ids, positions=positions, cache=cache,
            deterministic=True, **kw,
        )
        if last_idx is not None:
            last = jnp.take_along_axis(
                out, last_idx[:, None, None], axis=1)[:, 0]
            out = jnp.dot(last, self.model.head_matrix(params, last),
                          preferred_element_type=jnp.float32)
        counters = jnp.stack([counted[0][n] for n in self.counter_names]) \
            if counted else None
        return out, unbind_call(new_cache), counters

    def _named(self, extra: tuple) -> dict:
        """What follows the six per-slot state arrays in a program's
        arguments, by the name ``_model_cache_call`` knows it under:
        ``(adapter_ids, adapters)`` with a multi-LoRA pool,
        ``(state_slots,)`` for a model with recurrent layers (the two are
        never on together: ``refuse_unsupported``), else nothing — and the
        traced program is the one it always was."""
        if self._recurrent:
            return {"state_slots": extra[0]}
        return dict(zip(("adapter_ids", "adapters"), extra))

    def _pool_tree(self) -> tuple:
        """The adapter pool's tree as a program's LAST argument. NOT
        donated — a round in flight may still read the previous
        buffers, and a one-row scatter (acquire miss) rebinds ``pool.tree``
        between rounds."""
        return () if self.adapter_pool is None else (self.adapter_pool.tree,)

    def _trailing(self, adapter_ids: np.ndarray,
                  state_slots: np.ndarray) -> tuple:
        """The sending side of ``_named``, for a call whose per-row arrays
        come from the host: the one of the two this engine's programs
        take, uploaded, and the pool tree."""
        if self._row_extra is None:
            return ()
        rows = adapter_ids if self._row_extra == "adapter_ids" \
            else state_slots
        return (jnp.asarray(rows), *self._pool_tree())

    @staticmethod
    def _built(table: Dict[int, callable], key: int, build):
        """``table[key]``, built on first use: the prefill buckets and the
        spec ladder each hold a bounded set of programs, compiled when
        traffic first needs one."""
        fn = table.get(key)
        if fn is None:
            fn = table[key] = build(key)
        return fn

    def _prefill_fn(self, bucket: int):
        return self._built(self._prefill_fns, bucket, self._build_prefill_fn)

    def _build_prefill_fn(self, bucket: int):
        @partial(jax.jit, donate_argnums=(1,))
        def prefill(params, cache_kv, input_ids, positions, block_table,
                    last_idx, *lora):
            # input_ids/positions: (B, bucket); block_table: (B, nblk) —
            # sliced so attention's gathered window is bucket-sized, not
            # max_model_len-sized. B > 1 batches several admissions into
            # one program call (padding rows carry position -1, whose
            # writes slot_mapping drops); last_idx (B,) selects each
            # row's final real position, the one the head is applied to.
            # With a multi-LoRA pool, *lora is (adapter_ids, adapters) —
            # per-row adapter gather; empty otherwise (the traced program
            # is then unchanged).
            last, new_kv, counters = self._model_cache_call(
                params, cache_kv, block_table, input_ids, positions,
                **self._named(lora), last_idx=last_idx)
            if counters is not None:  # a model that counts (Python-static)
                return new_kv, last, counters
            return new_kv, last

        return prefill

    def _build_decode_fn(self):
        S = self.cfg.max_seqs

        @partial(jax.jit, donate_argnums=(1,))
        def decode(params, cache_kv, prev_tokens, packed, *pool):
            # packed: the round as the host packed it (RoundPacking):
            # input_ids/positions (S, 1), block_tables (S, max_blocks), the
            # sampling state, and the one extra per-slot row where the
            # programs take one. *pool: the multi-LoRA pool's tree, LAST.
            # prev_tokens: the token output of the round before, as that
            # call returned it (the model's counter rows after the slots',
            # cut off here). A row whose host id is negative (RIDES) takes
            # its input from there: the round before need not have reached
            # the host when this one is launched.
            (input_ids, positions, block_tables, slot_keys, gen_counts,
             temperature, top_k, top_p, *lora) = self.round_packing.unpack(packed)
            input_ids = jnp.where(input_ids < 0, prev_tokens[:S, None],
                                  input_ids)
            logits, new_kv, counters = self._model_cache_call(
                params, cache_kv, block_tables, input_ids, positions,
                **self._named((*lora, *pool)), own_rows=True)
            rngs = jax.vmap(jax.random.fold_in)(slot_keys, gen_counts)
            tokens, logprobs = sample_tokens(
                logits[:, 0, :], rngs, temperature, top_k, top_p
            )
            if counters is not None:
                # The model's counters ride as rows after the slots'
                # tokens: the fetch that exists brings them.
                tokens = jnp.concatenate([tokens, counters])
            if self.mesh is not None:
                # The next call takes these tokens back as they lie: pin
                # the layout the warmed executable was lowered for.
                tokens = jax.lax.with_sharding_constraint(
                    tokens, self._no_prev.sharding)
            return new_kv, tokens, logprobs

        return decode

    @staticmethod
    def _aot_or_jit(compiled, jit_fn):
        """Dispatch through an AOT executable, permanently falling back to
        the jit path the first time the executable REJECTS the inputs
        (aval/sharding drift — should not happen with the engine's static
        decode shapes, but a warmup must never be able to break serving).
        Only input-validation errors raised BEFORE execution (so no
        donated buffer is consumed) trigger the fallback: TypeError, and
        the sharding-mismatch ValueError (e.g. a replica pinned off the
        default device meeting an executable compiled for it). A runtime
        failure mid-execution may already have consumed the donated KV
        cache, so retrying via jit would only mask the real error with
        'Array has been deleted' — let it propagate."""
        state = {"aot": True}

        def _is_input_rejection(e: Exception) -> bool:
            return isinstance(e, TypeError) or (
                isinstance(e, ValueError)
                and "Compiled object called with input sharding" in str(e))

        def call(*a):
            if state["aot"]:
                try:
                    return compiled(*a)
                except (TypeError, ValueError) as e:
                    if not _is_input_rejection(e):
                        raise
                    state["aot"] = False
                    get_logger().warning(
                        "AOT decode executable rejected inputs (%s); "
                        "falling back to jit dispatch permanently", e)
            return jit_fn(*a)

        call._aot_state = state  # test hook: did dispatch stay on the AOT path?
        call._jit_fn = jit_fn    # warmup idempotency: the lowerable fn
        return call

    def _build_spec_decode_fn(self, k: int):
        """One propose→verify→accept round, entirely on device:

        1. **Propose** (prompt lookup): per slot, match the trailing
           ``ngram_size``-gram of the token history against every earlier
           position (one vectorized window comparison on the VPU) and copy
           the k tokens that followed the most recent hit; no hit → an
           all-(-1) draft, which degrades that slot to single-step.
        2. **Verify**: one forward over (S, k+1) positions — the current
           input token plus the k drafts.
        3. **Accept**: greedy slots emit the longest draft prefix matching
           the argmax plus one bonus token (exact greedy decoding);
           sampling slots emit their position-0 ``sample_tokens`` draw
           (identical fold_in rng stream to plain decode). The host keeps
           the token history (the scheduler's mirror, which the next
           round's proposal is given).

        The host syncs once per call: up to k+1 tokens. KV writes past a
        slot's accepted prefix are garbage but live at positions its
        next round (or next plain decode) overwrites before any query can
        attend to them (causal masking; same invariant as chunked prefill's
        trash-block masking).
        """
        n = self.cfg.ngram_size
        W = self.cfg.spec_hist_width

        def propose(hist, seq_len):
            # hist rows hold context tokens at their positions (the input
            # token already placed at seq_len); valid length = seq_len+1.
            S = hist.shape[0]
            tails = jax.vmap(
                lambda row, sl: jax.lax.dynamic_slice(row, (sl + 1 - n,), (n,))
            )(hist, seq_len)                                     # (S, n)
            win = jnp.stack(
                [hist[:, j:W - n + 1 + j] for j in range(n)], axis=-1
            )                                                    # (S, W-n+1, n)
            eq = jnp.all(win == tails[:, None, :], axis=-1)
            ii = jnp.arange(W - n + 1)[None, :]
            # A hit must be an *earlier* occurrence fully inside known
            # context: window ends at ii+n-1 <= seq_len-1.
            valid = eq & (ii <= (seq_len - n)[:, None]) & (seq_len >= n)[:, None]
            found = jnp.any(valid, axis=1)
            best = jnp.argmax(jnp.where(valid, ii, -1), axis=1)  # most recent
            drafts = jax.vmap(
                lambda row, b: jax.lax.dynamic_slice(row, (b,), (k,))
            )(hist, best + n)                                    # (S, k)
            j = jnp.arange(k)[None, :]
            ok = found[:, None] & ((best + n)[:, None] + j <= seq_len[:, None])
            return jnp.where(ok, drafts, -1)

        @partial(jax.jit, donate_argnums=(1,))
        def spec_decode(params, cache_kv, hist, t_in, seq_len, spec_mask,
                        block_tables, slot_keys, gen_counts, temperature,
                        top_k, top_p, *lora):
            S = t_in.shape[0]
            hist = hist.at[jnp.arange(S), seq_len].set(t_in)
            drafts = propose(hist, seq_len)                      # (S, k)
            # Per-slot gate: a paused slot's draft is forced to the
            # all-(-1) no-hit form, degrading just that slot to
            # single-step while its neighbors keep speculating.
            drafts = jnp.where(spec_mask[:, None], drafts, -1)
            ids = jnp.concatenate(
                [t_in[:, None], jnp.maximum(drafts, 0)], axis=1)
            pos = seq_len[:, None] + jnp.arange(k + 1)[None, :]
            logits, new_kv, _ = self._model_cache_call(
                params, cache_kv, block_tables, ids, pos,
                **self._named(lora))
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            g = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # (S, k+1)
            g_lp = jnp.take_along_axis(logp, g[..., None], axis=-1)[..., 0]
            # Position-0 emission via sample_tokens for EVERY slot:
            # greedy rows reduce to the same argmax, sampling rows get
            # exactly the plain-decode draw for fold_in(key, cnt).
            rngs = jax.vmap(jax.random.fold_in)(slot_keys, gen_counts)
            s_tok, s_lp = sample_tokens(
                logits[:, 0, :], rngs, temperature, top_k, top_p)
            eq = (drafts == g[:, :k]) & (drafts >= 0)
            m = jnp.sum(jnp.cumprod(eq.astype(jnp.int32), axis=1), axis=1)
            emit = jnp.where(temperature == 0.0, m + 1, 1).astype(jnp.int32)
            prop_cnt = jnp.sum(drafts >= 0, axis=1).astype(jnp.int32)
            return (new_kv, g.at[:, 0].set(s_tok), g_lp.at[:, 0].set(s_lp),
                    emit, prop_cnt, m)

        return spec_decode

    def _spec_fn(self, k: int):
        """The spec program for draft length ``k`` (pow2 halving-ladder
        member)."""
        return self._built(self._spec_fns, k, self._build_spec_decode_fn)

    # ------------------------------------------------------------------
    # Program calls: host arrays in by name, device results out, none of
    # them waited for (``fetch`` waits)
    # ------------------------------------------------------------------
    def slot_key(self, seed: Optional[int]) -> np.ndarray:
        """A slot's sampling key as uint32[2] threefry data: the request's
        seed, or the next split of the engine's own stream."""
        if seed is not None:
            key = jax.random.PRNGKey(seed)
        else:
            self._rng, key = jax.random.split(self._rng)
        return np.asarray(
            jax.random.key_data(key)
            if jnp.issubdtype(key.dtype, jax.dtypes.prng_key) else key,
            np.uint32)

    def prefill(self, bucket: int, *, input_ids: np.ndarray,
                positions: np.ndarray, block_tables: np.ndarray,
                last_idx: np.ndarray, adapter_ids: np.ndarray,
                state_slots: np.ndarray, sample: Optional[dict] = None):
        """One call of the ``bucket`` prefill program over ``(B, bucket)``
        rows (a padding row: positions -1, ``state_slots`` out of range).
        ``sample`` (``slot_keys``, ``gen_counts``, ``temperature``,
        ``top_k``, ``top_p``, one per row) when some row is the last chunk
        of its prompt: the first tokens are then drawn from each row's
        last real logit, on the same per-slot key + count stream the decode
        programs use, and ``(tokens, logprobs)`` come back (the model's
        counters as rows after the tokens); else None — the call wrote the
        cache only, and its counters wait for the next sampled call.

        :class:`PrefillCallRefused` when the program call raises before the
        program has run: a shape nobody compiled whose program does not
        fit the device raises from the call itself, and the donated cache
        is consumed only by a dispatch that succeeded (checked here, not
        assumed: with the cache gone the error is passed on as it is)."""
        # (for the line that names a program built after start-up; of a
        # table a group of layers, the first: the group that sees every key)
        width = jax.tree_util.tree_leaves(block_tables)[0].shape[1]
        self.last_prefill_shape = (*input_ids.shape, width)
        try:
            self.cache, last_logits, *counters = self._prefill_fn(bucket)(
                self.params, self.cache, jnp.asarray(input_ids),
                jnp.asarray(positions),
                jax.tree_util.tree_map(jnp.asarray, block_tables),
                jnp.asarray(last_idx),
                *self._trailing(adapter_ids, state_slots))
        except jax.errors.JaxRuntimeError as e:
            if any(leaf.is_deleted()
                   for leaf in jax.tree_util.tree_leaves(self.cache)):
                raise
            refused = PrefillCallRefused(
                (*input_ids.shape, width),
                (str(e).splitlines() or [type(e).__name__])[0])
            self.refused_prefill_shapes.add(refused.shape)
            # One record: the line that names the shape, then the error as
            # the compiler gave it (its largest allocations name the arrays).
            self.logger.warning("jit_prefill: %s", refused, exc_info=e)
            raise refused from e
        if counters and self._prefill_counters is not None:
            counters = [self._prefill_counters + counters[0]]
            self._prefill_counters = None
        if sample is None:
            if counters:
                self._prefill_counters = counters[0]
            return None
        keys = self._fold_keys(jnp.asarray(sample["slot_keys"]),
                               jnp.asarray(sample["gen_counts"]))
        fn = self._sample_counted_fn if counters else self._sample_fn
        return fn(last_logits, keys, jnp.asarray(sample["temperature"]),
                  jnp.asarray(sample["top_k"]), jnp.asarray(sample["top_p"]),
                  *counters)

    def stage_decode(self, input_ids: np.ndarray, positions: np.ndarray,
                     mirrors: Dict[str, np.ndarray],
                     masked_rows: Sequence[int]) -> tuple:
        """Upload a plain decode round, whole, as ONE array: the ``(S, 1)``
        tokens (``RIDES`` where a row's token is the one the round before
        sampled, which the program reads on the device) and positions, and
        every row of the per-slot ``mirrors`` as of THIS round's launch
        (``gen_counts`` of a riding row counts the token still in flight),
        packed (:class:`RoundPacking`) and placed with one transfer.
        ``masked_rows``: slots still prefilling, whose block tables must
        read as the trash block. What :meth:`launch_decode` takes."""
        packed = self.round_packing.pack(
            input_ids, positions, mirrors, masked_rows)
        self.stats["decode_host_uploads"] += 1
        return (jax.device_put(packed, self._round_sharding),
                *self._pool_tree())

    def launch_decode(self, staged: tuple, prev=None):
        """Call the decode program: ``(tokens, logprobs)``, ``(S,)`` each
        (the model's counters as rows after the slots'). ``prev``: what
        the launch of the round before returned, fetched or not; the rows
        staged as ``RIDES`` read their token from it. The one program call
        of the round."""
        self.stats["decode_program_calls"] += 1
        self.cache, tokens, logprobs = self._decode_fn(
            self.params, self.cache,
            self._no_prev if prev is None else prev[0], *staged)
        return tokens, logprobs

    def stage_spec(self, hist: np.ndarray, t_in: np.ndarray,
                   seq_len: np.ndarray, spec_mask: np.ndarray,
                   mirrors: Dict[str, np.ndarray],
                   masked_rows: Sequence[int], table_width: int) -> tuple:
        """Upload a speculative round: the token history and the mirrors,
        an array each (the history dwarfs the rest). What
        :meth:`launch_spec` takes."""
        tables = mirrors["block_tables"]
        if len(masked_rows):
            tables = tables.copy()
            tables[list(masked_rows)] = 0
        staged = (
            jnp.asarray(hist), jnp.asarray(t_in), jnp.asarray(seq_len),
            jnp.asarray(spec_mask), jnp.asarray(tables[:, :table_width]),
            *(jnp.asarray(mirrors[f]) for f in (
                "slot_keys", "gen_counts", "temperature", "top_k", "top_p")),
            *self._trailing(mirrors["adapter_ids"], mirrors["state_slots"]))
        self.stats["decode_host_uploads"] += \
            len(staged) - len(self._pool_tree())
        return staged

    def launch_spec(self, staged: tuple, k: int):
        """Call the draft-length-``k`` spec program: ``(tokens, logprobs)``,
        ``(S, k + 1)`` each, and ``(emitted, proposed, accepted)``,
        ``(S,)`` each."""
        self.stats["decode_program_calls"] += 1
        self.cache, *out = self._spec_fn(k)(self.params, self.cache, *staged)
        return tuple(out)

    @staticmethod
    def fetch(arrays) -> list:
        """Wait for program results and bring them to the host."""
        return [np.asarray(jax.device_get(x)) for x in arrays]

    def warmup_decode_ladder(self) -> None:
        """Pre-compile the decode program (the one a plain round calls;
        nothing else is warmed here) BEFORE traffic: its first use
        otherwise stalls the live decode loop on an XLA compile.
        AOT-lowers on abstract shapes (donation only consumes avals here —
        no scratch KV pool is materialized), then KEEPS the compiled
        executable and swaps it into the dispatch path: relying on the
        persistent compilation cache alone does nothing for a compile that
        finishes under the cache's min-compile-time floor."""
        def avals(tree):
            # Carry each leaf's ACTUAL sharding: a ReplicatedEngine pins
            # every replica's params/KV to its own device, and an aval
            # without it lowers for the default device — an executable
            # replica 1 can only reject at dispatch time.
            return jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(
                    v.shape, v.dtype,
                    sharding=getattr(v, "sharding", None)), tree)

        # The packed round arrives COMMITTED (``stage_decode``), like the
        # round-before's tokens (the stand-in or a call's output); lower
        # with the sharding each will carry so the AOT executable accepts
        # them (same reason params/cache carry theirs).
        pk = self.round_packing
        # Idempotent: a re-warm unwraps back to the raw jit fn (the
        # _aot_or_jit wrapper has no .lower) and rebuilds the executable.
        raw = getattr(self._decode_fn, "_jit_fn", self._decode_fn)
        self._decode_fn = self._aot_or_jit(
            raw.lower(
                avals(self.params), avals(self.cache), avals(self._no_prev),
                jax.ShapeDtypeStruct((pk.num_slots, pk.width), jnp.int32,
                                     sharding=self._no_prev.sharding),
                *avals(self._pool_tree())).compile(),
            raw)

    def register_memory_owners(self, ledger: MemoryLedger) -> None:
        """The device arrays this class holds, by owner (telemetry.
        memledger). Handles are callables because the arrays rebind
        (donated programs return a fresh cache list)."""
        ledger.register("params", lambda: self.params)
        ledger.register(
            "kv_block_pool",
            lambda: [c for c in self.cache if "ssm" not in c])
        ledger.register(
            "recurrent_state_pool",
            lambda: [c for c in self.cache if "ssm" in c] or None)
        ledger.register(
            "lora_adapters",
            lambda: (self.adapter_pool.tree
                     if self.adapter_pool is not None else None))

    # -- paged-KV block transport (tier demotion + disagg handoff) -----
    def fetch_block_kv(self, block: int):
        """One physical block's KV rows from every layer pool, fetched
        device→host — the prefix-tier demotion path, reused verbatim as
        the disaggregated-serving handoff transport. Runs on the stepper
        thread; ``self.cache`` then holds the committed output of the
        last dispatched program, so the read sees every write the block
        ever received. Payload keys follow the disk format
        ("l00000": {"k": ..., "v": ..., int8 scales if present})."""
        try:
            # (a looped stack: the block of every pass's entry, a leading
            # axis of ``ut_steps``; models.llama.entry_of_pass)
            at = block if self.model_cfg.ut_steps == 1 \
                else slice(block, None, self.cfg.num_blocks)
            rows = [{name: arr[at] for name, arr in layer.items()}
                    for layer in self.cache]
            if self._demote_sharding is not None:
                # Stage through pinned_host: the D2H DMA lands in pinned
                # memory the host reads without a bounce (TPU path).
                rows = jax.device_put(rows, self._demote_sharding)
            host = jax.device_get(rows)
        except Exception as e:  # noqa: BLE001 — the fetch is best-effort:
            # a failure degrades to discard (demotion) or re-prefill
            # (handoff), never faults the step loop that triggered it.
            self.logger.warning("block KV fetch failed "
                                "(%s: %s); block discarded",
                                type(e).__name__, e)
            return None
        return {f"l{i:05d}": {k: np.asarray(v) for k, v in r.items()}
                for i, r in enumerate(host)}

    def restore_block(self, block: int, payload: dict) -> None:
        """Scatter a fetched payload into physical ``block`` of every
        layer pool. Dispatch is async (jit): the scatter overlaps host-side
        admission work, and the following prefill/decode programs see the
        restored rows through the ``self.cache`` data dependency."""
        if self._restore_fn is None:
            passes = self.model_cfg.ut_steps

            @partial(jax.jit, donate_argnums=(0,))
            def restore(cache_kv, rows, bid):
                if passes > 1:  # the block of every pass's entry
                    bid = bid + jnp.arange(passes) * self.cfg.num_blocks
                return [
                    {k: v.at[bid].set(r[k].astype(v.dtype)) for k, v in
                     layer.items()}
                    for layer, r in zip(cache_kv, rows)
                ]

            self._restore_fn = restore
        rows = [payload[f"l{i:05d}"] for i in range(len(self.cache))]
        self.cache = self._restore_fn(self.cache, rows,
                                      jnp.asarray(block, jnp.int32))
