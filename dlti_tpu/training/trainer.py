"""The training loop — in-tree replacement for HF ``Trainer`` + DeepSpeed.

One class drives what the reference spreads across four scripts
(``training/train_baseline.py`` / ``train_deepspeed_zero{1,2,3}.py``):

* build mesh + shard state per the configured ZeRO stage / TP / SP
* iterate epochs of per-host sharded batches
* per-``logging_steps`` loss/throughput logging (``train_baseline.py:184``)
* step- or epoch-based checkpointing with rotation
  (``train_deepspeed_zero1.py:243-245``: save_steps=100, keep 3)
* scan-latest-and-resume (``train_deepspeed_zero1.py:267-279``)
* final metrics in the reference CSV schema + tokens/sec/chip + MFU
  (``train_baseline.py:239-259``)
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from typing import Iterable, Optional

import jax
import numpy as np

from dlti_tpu.config import Config
from dlti_tpu.models import LlamaForCausalLM, build_model, count_params
from dlti_tpu.ops.attention import resolve_flash
# Submodule imports (not the package) so that `dlti_tpu.parallel` ->
# `training.state` -> `dlti_tpu.training` (which re-exports Trainer) does
# not cycle back into the half-initialized parallel package.
from dlti_tpu.parallel.mesh import build_mesh
from dlti_tpu.parallel.sharding import make_sharded_train_step
from dlti_tpu.telemetry import (
    AnomalyWatchdog, FlightRecorder, GoodputLedger, Heartbeat,
    StepLogWriter, TimeSeriesSampler, build_slo_tracker, configure_tracer,
    get_recorder, get_tracer, install_recorder, schedule_lr,
)
from dlti_tpu.telemetry.ledger import (
    goodput_fraction_gauge, goodput_mfu_gauge, goodput_seconds_total,
)
from dlti_tpu.telemetry import memledger as memledger_mod
from dlti_tpu.telemetry.memledger import (
    MemoryLedger, executable_memory_analysis, is_oom_error,
)
from dlti_tpu.training import remat_plan
from dlti_tpu.training.optimizer import build_optimizer
from dlti_tpu.training.state import TrainState, create_train_state
from dlti_tpu.training.step import make_train_step
from dlti_tpu.utils import durable_io
from dlti_tpu.utils.experiment import experiment_name_from_config
from dlti_tpu.utils.logging import StepTimer, get_logger, is_main_process
from dlti_tpu.utils.metrics import (
    MetricsRecord,
    chip_peak_flops,
    compute_mfu,
    device_peak_memory,
    print_metrics_summary,
    save_training_metrics,
)
from dlti_tpu.utils.platform import device_facts


def _batch_compatible(a: dict, b: dict) -> bool:
    """Same keys/shapes/dtypes — stackable into one steps_per_sync window.

    Metadata-only checks (``np.shape`` / ``.dtype`` attributes): no copy,
    so device-resident batch leaves never round-trip to host here."""
    if a.keys() != b.keys():
        return False
    return all(np.shape(a[k]) == np.shape(b[k])
               and getattr(a[k], "dtype", None) == getattr(b[k], "dtype", None)
               for k in a)


def _validate_pipeline_config(cfg: Config) -> None:
    """Reject strategy combinations the GPipe path does not implement —
    loudly, at construction, instead of silently mis-sharding (PP is
    reachable from the production Trainer, so it must refuse here)."""
    par = cfg.parallel
    illegal = []
    # The whole ZeRO family composes as of r05. ZeRO-1: optimizer state
    # shards over 'data'; the update runs under GSPMD outside the
    # pipeline's shard_map. ZeRO-2: grads additionally pinned to the
    # optimizer-state layout after the pipe step's value_and_grad
    # (reduce-scatter over 'data' instead of all-reduce). ZeRO-3:
    # stacked leaves shard over 'fsdp' on a non-layer dim
    # (pipeline_param_shardings), 'fsdp' rides GSPMD as an auto axis
    # inside the pipe shard_map (per-tick all-gather at use, grads
    # pinned to the reduce-scatter layout) — the same mechanism that
    # carried PP x TP.
    # 'tensor', 'data', 'expert', and 'sequence' all compose:
    # stage-internal TP, batch-row DP, and expert parallelism ride as
    # GSPMD auto axes inside the pipeline's shard_map; SP does too —
    # under pipe, ring_attention DELEGATES to reference_attention and
    # GSPMD partitions it over the auto 'sequence' axis (all-gather SP;
    # a nested manual ring computes wrong grads or fails verification
    # on this jax — see ring_attention's delegation comment). pipe x
    # tensor x data is full 3D; fsdp (ZeRO-3), expert, and sequence
    # extend it.
    if par.sequence > 1 and cfg.train.loss_chunk:
        # Mirror the flat-path rejection (make_sharded_train_step): the
        # chunk reshape would regather the 'sequence'-sharded hidden.
        illegal.append(f"sequence={par.sequence} with train.loss_chunk "
                       "(the chunk reshape regathers the sequence-"
                       "sharded activations; set loss_chunk=0)")
    if par.fsdp > 1 and int(par.zero_stage) != 3:
        illegal.append(f"fsdp={par.fsdp} without zero_stage=3 (the fsdp "
                       "axis only carries ZeRO-3 param sharding)")
    # Host offload composes (r05) in boundary-transfer mode — the flat
    # path's fallback semantics: offloaded leaves (optimizer moments
    # and/or the frozen base) rest in pinned host memory between steps
    # and cross at step boundaries (_build_step). In-step per-layer
    # STREAMING stays flat-only (pinned_host operands cannot enter the
    # pipe shard_map stage-sharded). offload_params needs LoRA: it
    # offloads the frozen base, and a full fine-tune has none.
    if par.offload_params and not cfg.lora.enabled:
        illegal.append("offload_params without LoRA (it offloads the "
                       "frozen base params; a full fine-tune has none)")
    # fp16 dynamic loss scaling composes: the pipelined step scales the
    # loss, unscales grads, and evolves TrainState.scaler via the same
    # apply_loss_scaler helper the flat step uses.
    # quantize_frozen_base composes: the stage body dequantizes int8
    # leaves like the unpipelined block, and pipeline_forward dequantizes
    # embed/head on the fly (quantized kernels TP-shard too via the shared
    # quant-path normalization in parallel.sharding).
    # loss_chunk composes: pipeline_forward returns hidden states and the
    # pipelined loss applies the head per sequence chunk
    # (pipeline_head_matrix + chunked_causal_lm_loss).
    # MoE composes: the stage scan collects each layer's sown router
    # aux loss (edge ticks masked so fill/drain recomputes don't
    # double-count), psum'd over 'pipe'; EP composes too (see above).
    # Packed sequences compose: segment ids ride each microbatch through
    # the stages (pipeline_forward segment_ids), per-doc positions included.
    # Every named remat policy composes as of r05 (the scanned stage body
    # passes cfg.remat_policy through the flat path's policy table), and
    # remat_stride does too: layers scan in GROUPS of stride with every
    # stride-th block keeping its activations (pipeline_forward); a
    # non-dividing stride warns in make_pipeline_train_step and falls
    # back to full remat.
    # The Pallas flash kernel on a TPU composes with pipe only while no
    # other axis is sharded: GSPMD cannot partition a Mosaic call over the
    # auto axes of a stage, and the per-shard wrapper the flat path uses
    # (per_shard_attention) would be a nested shard_map, untrainable on
    # this jax. Interpreted (CPU) and reference attention partition like
    # any jnp code. Under sequence > 1 the ring delegates to the reference.
    auto_axes = [f"{ax}={getattr(par, ax)}"
                 for ax in ("data", "fsdp", "tensor", "expert")
                 if getattr(par, ax) > 1]
    if auto_axes and par.sequence == 1 and resolve_flash(
            cfg.model.attention_impl, seq_q=cfg.data.max_seq_len,
            seq_kv=cfg.data.max_seq_len,
            head_dim=cfg.model.resolved_head_dim)[0] == "pallas":
        illegal.append(
            f"{', '.join(auto_axes)} with the Pallas flash kernel on a TPU "
            "(a Mosaic kernel cannot be partitioned inside a pipeline "
            "stage; set model.attention_impl='reference')")
    import jax as _jax

    if _jax.process_count() > 1:
        # Multi-host PP composes when the batch-row axes (data x fsdp)
        # span the processes: rows then shard across hosts and
        # make_global_batch assembles a consistent global array, with
        # the pipe/tensor/expert axes process-local (mesh order is
        # data-major). Without that, batch rows would be REPLICATED
        # across hosts while each host feeds its own different shard —
        # silent divergence. Proven by the 2-process 'pipe' leg in
        # tests/test_distributed.py (data=4 x pipe=2 over 2 processes).
        rows = par.data * par.fsdp
        if rows % _jax.process_count() != 0:
            illegal.append(
                f"multi-host meshes with batch-row extent data*fsdp={rows} "
                f"not divisible by process_count={_jax.process_count()} "
                "(batch rows must shard across hosts; a host-replicated "
                "batch would silently differ per host)")
    if illegal:
        raise ValueError(
            "pipeline parallelism (parallel.pipe="
            f"{par.pipe}) does not compose with: {', '.join(illegal)}. "
            "Legal: pipe x tensor x data x fsdp x sequence x expert "
            "(GPipe stages, stage-internal TP, batch-row DP, ZeRO-1/2/3, "
            "GSPMD-partitioned SP, expert parallelism) with "
            "bf16-or-int8-base LoRA or full fine-tune, dense or MoE "
            "models, packed or padded batches, fp16 scaler, loss_chunk, "
            "any named remat policy — single-host, or multi-host when "
            "data*fsdp divides by process_count (batch rows shard across "
            "hosts, pipe stages process-local)")
    if cfg.train.grad_accum_steps < 1:
        raise ValueError("grad_accum_steps must be >= 1 under pipe")


class Trainer:
    def __init__(self, cfg: Config, model: Optional[LlamaForCausalLM] = None,
                 base_params: Optional[dict] = None):
        self.cfg = cfg
        self.logger = get_logger()
        # Pretrained base weights (e.g. from models.load_hf_checkpoint) to
        # overlay onto the initialized tree — the from_pretrained analog.
        self.base_params = base_params
        if cfg.model.ut_steps > 1:
            raise NotImplementedError(
                f"ut_steps {cfg.model.ut_steps}: training through weights "
                f"that a step uses several times (gradients summed over the "
                f"passes, the activations of every pass, an exit-gate loss) "
                f"has been held to no reference; the looped stack is served, "
                f"not trained")
        self.tx = build_optimizer(cfg.optimizer)
        if cfg.parallel.pipe > 1:
            _validate_pipeline_config(cfg)
        self.mesh = None
        if cfg.parallel.num_devices > 1:
            self.mesh = build_mesh(cfg.parallel)
        # The model needs the mesh for sequence parallelism: with
        # parallel.sequence > 1 attention runs the ring schedule
        # (dlti_tpu.parallel.ring_attention) over the 'sequence' axis.
        # A model of the caller's is trained as it was built; ours is
        # rebuilt once the kept-block count is known (plan_remat).
        self._own_model = model is None
        self.model = model or build_model(
            cfg.model, cfg.lora if cfg.lora.enabled else None, self.mesh
        )
        self.remat_plan: Optional[remat_plan.RematPlan] = None
        self._step_fn = None
        self._ckpt_mgr = None
        # Preemption flag: set by SIGTERM (cluster eviction) or
        # request_stop(); honored at the next step boundary.
        self._stop_requested = False
        # Chaos injector (dlti_tpu.training.chaos); (re)parsed per train().
        self._fault = None
        self._last_eval_loss = float("nan")
        # Host-side span tracer (telemetry.tracer): per-step phase spans
        # (batch fetch, host→device, dispatch, device sync, eval, save).
        # Disabled by default; cfg.telemetry.trace_dir enables it in
        # train() — span sites cost one attribute read while disabled.
        self._tracer = get_tracer()
        # Flight-recorder context hook (telemetry.flightrecorder): a
        # dict-merge no-op until train() installs a recorder; methods
        # outside the loop (_run_eval, _maybe_save) call it too.
        self._fnote = lambda **kw: None
        # Goodput ledger (telemetry.ledger): train() replaces this with a
        # live phase clock when cfg.telemetry.goodput_ledger is on; the
        # disabled placeholder keeps every enter() site a one-attribute-
        # read no-op (methods outside the loop transition through it too).
        self._ledger = GoodputLedger(enabled=False)
        self._log_build()

    def _log_build(self) -> None:
        """One line, once per trainer: where it runs and what the flash
        site resolved to (chip_smoke.py and operators read it)."""
        cfg = self.cfg
        if cfg.parallel.sequence > 1:
            flash = ("xla", "ring attention over the 'sequence' mesh axis")
        else:
            flash = resolve_flash(
                cfg.model.attention_impl, seq_q=cfg.data.max_seq_len,
                seq_kv=cfg.data.max_seq_len,
                head_dim=cfg.model.resolved_head_dim)
        self.logger.info("trainer build: %s", json.dumps({
            **device_facts(),
            "mesh": ({ax: n for ax, n in self.mesh.shape.items() if n > 1}
                     if self.mesh is not None else {}),
            "model_layers": cfg.model.num_layers,
            "compute_dtype": cfg.model.dtype,
            "param_dtype": cfg.model.param_dtype,
            "frozen_base": (cfg.train.quantize_frozen_base
                            or cfg.model.param_dtype),
            "flash": flash[0],
            "flash_reason": flash[1],
        }, sort_keys=True))

    # ------------------------------------------------------------------
    def init_state(self, rng: Optional[jax.Array] = None) -> TrainState:
        """A fresh state, where it will live.

        One device: initialised in place, leaf by leaf. Under a mesh the
        state is born sharded — a compiled initialiser whose outputs carry
        their final shardings — so no device ever holds more than its own
        share: on a four-chip host the whole 7B tree on chip 0 next to
        chip 0's shard would not fit (``chip_smoke.py --chips 4`` reads the
        per-device peak from the "device memory after init" line).
        """
        rng = rng if rng is not None else jax.random.PRNGKey(self.cfg.train.seed)
        cfg = self.cfg
        quantize = cfg.train.quantize_frozen_base
        if quantize and quantize != "int8":
            raise ValueError(
                f"unknown quantize_frozen_base={quantize!r} (only 'int8')")
        if quantize and not cfg.lora.enabled:
            raise ValueError(
                "quantize_frozen_base requires LoRA: it compresses the "
                "frozen base params, and a full fine-tune has none")
        from dlti_tpu.models.quantization import quantize_params_int8

        def fresh(rng):
            return create_train_state(
                rng, self.model, self.tx,
                (cfg.train.micro_batch_size, cfg.data.max_seq_len),
                lora_enabled=cfg.lora.enabled,
                fp16_initial_scale=(
                    float(2 ** cfg.train.fp16_initial_scale_power)
                    if cfg.train.fp16 else None),
                fp16_hysteresis=cfg.train.fp16_hysteresis)

        if self.mesh is None:
            state = fresh(rng)
            if self.base_params is not None:
                from dlti_tpu.models import graft_base_params

                state = state.replace(params=graft_base_params(
                    state.params, self.base_params))
            if quantize:
                # donate=True retires each bf16 source as its int8 twin
                # lands, so quantizing a 7B tree never holds both in HBM.
                state = state.replace(
                    params=quantize_params_int8(state.params, donate=True))
            return state

        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlti_tpu.parallel.sharding import (
            launder_transfer_created, place_on_mesh, state_shardings,
        )

        repl = NamedSharding(self.mesh, P())
        piped = cfg.parallel.pipe > 1
        offload = cfg.parallel.offload_params or cfg.parallel.offload_optimizer

        def in_hbm(shardings):
            # The initialisers compute in device memory; leaves that rest
            # in pinned host memory move there at the end, shard by shard.
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s.spec), shardings)

        # Flat layout first (the layout a base checkpoint grafts onto);
        # int8 and the pipeline's stacked layout follow, sharded to sharded.
        state = jax.jit(
            fresh, in_shardings=repl,
            out_shardings=in_hbm(state_shardings(
                jax.eval_shape(fresh, rng), cfg, self.mesh)))(rng)
        if self.base_params is not None:
            from dlti_tpu.models import graft_base_params

            # place_on_mesh + launder: see sharding.place_on_mesh (multi-
            # process placement without broadcasts, safe to donate).
            state = state.replace(params=launder_transfer_created(
                graft_base_params(
                    state.params, self.base_params,
                    place=lambda b, p: place_on_mesh(
                        b.astype(p.dtype) if isinstance(b, jax.Array)
                        else np.asarray(b, dtype=p.dtype), p.sharding))))

        def finish(state):
            if quantize:
                state = state.replace(
                    params=quantize_params_int8(state.params))
            if piped:
                from dlti_tpu.parallel.pipeline import to_pipeline_state

                state = to_pipeline_state(state, cfg.model.num_layers)
            return state

        if quantize or piped:
            state = jax.jit(
                finish, donate_argnums=0,
                out_shardings=in_hbm(self._resting_shardings(
                    jax.eval_shape(finish, state))))(state)
        if offload:
            state = jax.device_put(state, self._resting_shardings(state))
        return state

    def _resting_shardings(self, state) -> TrainState:
        """Where each leaf of ``state`` (arrays or their shapes) rests
        between steps under this trainer's mesh."""
        from dlti_tpu.parallel.sharding import (
            opt_state_shardings, state_shardings,
        )

        if self.cfg.parallel.pipe == 1:
            return state_shardings(state, self.cfg, self.mesh)
        # Pipeline layout: layers_{i} subtrees stack with a leading layer
        # dim, sharded over 'pipe'; embed/norm/head + optimizer state
        # replicate (they are a few percent of params/FLOPs).
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dlti_tpu.parallel.pipeline import pipeline_param_shardings

        repl = NamedSharding(self.mesh, P())
        param_sh = pipeline_param_shardings(state.params, self.mesh)
        if self.cfg.parallel.offload_params:
            # PP x param host-offload (boundary-transfer mode, the flat
            # path's fallback semantics): FROZEN base leaves rest in
            # pinned host memory between steps; trainable (LoRA) leaves
            # stay device-resident. _build_step moves the frozen tree
            # HBM-ward per step and splices the still-valid host copies
            # back after.
            from dlti_tpu.parallel.sharding import _host_memory_kind
            from dlti_tpu.training.state import (
                combine_params, partition_params,
            )

            kind = _host_memory_kind(self.mesh)
            if kind is not None:
                trainable_sh, frozen_sh = partition_params(
                    param_sh, self.cfg.lora.enabled)
                frozen_sh = jax.tree_util.tree_map(
                    lambda s: NamedSharding(self.mesh, s.spec,
                                            memory_kind=kind),
                    frozen_sh)
                param_sh = combine_params(trainable_sh, frozen_sh)
        # opt_state_shardings is shape-based, so it applies to the stacked
        # trainable tree unchanged: ZeRO-1/2 x PP shard Adam moments over
        # 'data', ZeRO-3 x PP over 'fsdp' (the update runs under GSPMD
        # outside the pipeline's shard_map); stage NONE (or a size-1 axis)
        # falls out replicated.
        return state.replace(
            step=repl, params=param_sh,
            opt_state=opt_state_shardings(state.opt_state, self.cfg,
                                          self.mesh),
            scaler=(jax.tree_util.tree_map(lambda _: repl, state.scaler)
                    if state.scaler is not None else None))

    def plan_remat(self, state) -> remat_plan.RematPlan:
        """How many blocks may keep their activations on one device that
        holds its share of ``state`` (arrays, or shapes with shardings):
        ``training.remat_plan``'s arithmetic against the device's limit
        (the telemetry budget where one is stated)."""
        if not self._own_model:
            return remat_plan.RematPlan(
                0, self.cfg.model.num_layers, why_not="the caller's model")

        def on_a_device(leaf) -> int:
            shape = leaf.shape
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None:
                shape = sharding.shard_shape(shape)
            return int(np.prod(shape, dtype=np.int64))

        leaves = [x for x in jax.tree_util.tree_leaves(state)
                  if hasattr(x, "shape") and hasattr(x, "dtype")]
        held = sum(on_a_device(x) * x.dtype.itemsize for x in leaves)
        # float32 gradients of what trains, beside the state
        held += 4 * sum(on_a_device(x) for x in jax.tree_util.tree_leaves(
            state.trainable_and_frozen()[0]))
        limit = self.cfg.telemetry.hbm_budget_bytes or next(
            (d["bytes_limit"] for d in
             memledger_mod.device_bytes_in_use().values()
             if d.get("bytes_limit")), 0)
        return remat_plan.plan(self.cfg, held, limit)

    def adopt_remat_plan(self, plan: remat_plan.RematPlan) -> None:
        """Build the model that keeps ``plan.keep_blocks`` blocks (the
        tree of parameters is the same whatever the count)."""
        self.remat_plan = plan
        memledger_mod.remat_kept_blocks_gauge.set(plan.keep_blocks)
        if self._own_model:
            cfg = self.cfg
            self.model = build_model(
                dataclasses.replace(cfg.model,
                                    remat_keep_blocks=plan.keep_blocks),
                cfg.lora if cfg.lora.enabled else None, self.mesh)

    def _build_step(self, state: TrainState):
        if self.mesh is not None and self.cfg.parallel.pipe > 1:
            from dlti_tpu.parallel.pipeline import make_pipeline_train_step

            accum = self.cfg.train.grad_accum_steps
            pipe = self.cfg.parallel.pipe
            if accum < 4 * pipe and is_main_process():
                self.logger.warning(
                    "GPipe bubble: grad_accum_steps=%d microbatches over "
                    "pipe=%d stages idles %.0f%% of ticks; use >= %d "
                    "microbatches for >80%% utilization",
                    accum, pipe, 100 * (pipe - 1) / (accum + pipe - 1),
                    4 * pipe)
            pipe_step = make_pipeline_train_step(
                self.cfg, self.tx, self.mesh, num_microbatches=accum)

            def step_fn(state, batch, rng):
                # (accum, micro_bs, seq) -> (accum*micro_bs, seq): grad
                # accumulation happens through the microbatch schedule.
                # Packed batches ride along: segment_ids/positions flatten
                # the same way and pipeline_forward masks per microbatch.
                flat = {k: v.reshape((-1,) + v.shape[2:])
                        for k, v in batch.items()}
                return pipe_step(state, flat, rng)

            if (self.cfg.parallel.offload_optimizer
                    or self.cfg.parallel.offload_params):
                # PP x host offload (boundary-transfer mode, the flat
                # path's fallback semantics): one shared wrapper — it
                # derives shardings from the PLACED state and is a no-op
                # when nothing actually rests in host memory (backend
                # without pinned_host).
                from dlti_tpu.parallel.sharding import wrap_boundary_offload

                step_fn = wrap_boundary_offload(
                    step_fn, state, self.mesh, self.cfg.lora.enabled)

            return step_fn
        if self.mesh is not None:
            return make_sharded_train_step(
                self.model, state, self.cfg, self.mesh,
                accum_steps=self.cfg.train.grad_accum_steps,
            )
        return jax.jit(
            make_train_step(
                self.model, accum_steps=self.cfg.train.grad_accum_steps,
                fp16_scale_window=self.cfg.train.fp16_scale_window,
                fp16_min_scale=self.cfg.train.fp16_min_scale,
                fp16_hysteresis=self.cfg.train.fp16_hysteresis,
                loss_chunk=self.cfg.train.loss_chunk,
            ),
            donate_argnums=(0,),
        )

    # ------------------------------------------------------------------
    def train(
        self,
        batches_per_epoch: Iterable[dict] | None = None,
        dataset=None,
        eval_dataset=None,
        state: Optional[TrainState] = None,
        resume: Optional[bool] = None,
    ) -> tuple:
        """Run the configured number of epochs. Returns (state, MetricsRecord).

        ``dataset`` (a :class:`~dlti_tpu.data.TokenBatchDataset`) enables
        epoch re-iteration and exact resume of the data schedule;
        ``batches_per_epoch`` is a simpler single-epoch iterable for custom
        loops (resume restores weights but not batch order).
        """
        cfg = self.cfg
        # Goodput ledger: the phase clock starts before state init so
        # compile/init time books as "startup" — every second of train()
        # lands in exactly one bucket (conservation is tier-1-tested).
        ledger = self._ledger = GoodputLedger(
            enabled=cfg.telemetry.goodput_ledger)
        state = state or self.init_state()
        if is_main_process():
            # Every local chip's in-use and peak bytes once the state is
            # placed: a device that held more than its share on the way
            # shows as a peak above the others' (empty on CPU: no stats).
            jax.block_until_ready(state)
            self.logger.info("device memory after init: %s", json.dumps(
                memledger_mod.device_bytes_in_use(), sort_keys=True))
        resume = cfg.checkpoint.resume if resume is None else resume

        # Memory ledger (telemetry.memledger): owners registered as
        # callables through a one-slot box because the functional state
        # rebinds every step (donated buffers delete; the ledger skips
        # deleted arrays, and the box is refreshed at every bookkeep /
        # restore / rollback so snapshots track the live state).
        memledger = self._memledger = MemoryLedger(
            enabled=cfg.telemetry.memory_ledger,
            capacity_bytes=cfg.telemetry.hbm_budget_bytes)
        memledger_mod.install(memledger)
        mem_state = {"state": state}
        memledger.register("params", lambda: mem_state["state"].params)
        memledger.register("optimizer_state",
                           lambda: mem_state["state"].opt_state)
        memledger.register(
            "prefetch_buffers",
            lambda: (self._prefetcher.buffered_batches()
                     if getattr(self, "_prefetcher", None) is not None
                     else None))

        # Preemption-aware checkpointing (SURVEY.md §5.3): the reference's
        # only resilience is frequent periodic saves; here SIGTERM (the
        # cluster-eviction signal) triggers one final checkpoint at the
        # next step boundary — or, with steps_per_sync > 1, the next
        # window boundary (a filling window is dropped; an in-flight
        # scanned program finishes first) — so resume loses at most one
        # dispatch unit instead of up to save_steps.
        import signal as _signal

        self._stop_requested = False  # a reused Trainer trains again
        self._last_eval_loss = float("nan")
        prev_handler = None
        sigterm_installed = False
        try:
            prev_handler = _signal.signal(
                _signal.SIGTERM, lambda *_: self.request_stop())
            sigterm_installed = True
        except ValueError:
            pass  # not the main thread (e.g. embedded in a server)

        # Deterministic chaos hook (dlti_tpu.training.chaos): fresh per
        # train() call so a resumed run re-reads the spec/env.
        from dlti_tpu.training.chaos import TrainFaultInjector

        self._fault = TrainFaultInjector.from_spec(cfg.train.fault_inject_step)

        start_step = 0
        resume_meta = None
        self._rollback_due = None
        self._sdc_evict = False
        if resume and cfg.checkpoint.save_strategy != "no":
            from dlti_tpu.checkpoint import restore_latest_verified

            # Verified resume: digest-checks newest-first, quarantining
            # incomplete/corrupt checkpoints (kill mid-save, bit rot) and
            # falling back to the newest good one instead of crashing.
            ledger.enter("checkpoint_restore")
            restored = restore_latest_verified(cfg.checkpoint.output_dir,
                                               state)
            ledger.enter("startup")
            if restored is not None:
                state, step, resume_meta = restored
                mem_state["state"] = state
                start_step = int(step)
                self.logger.info(
                    "resumed from verified checkpoint step %d", start_step)
                if resume_meta and resume_meta.get("seed", cfg.train.seed) \
                        != cfg.train.seed:
                    self.logger.warning(
                        "checkpoint was saved with train.seed=%s but this "
                        "run uses %s — the resumed loss trajectory will "
                        "not match the original run's",
                        resume_meta.get("seed"), cfg.train.seed)

        # How many blocks keep their activations, from what this device
        # holds now and its limit; one compile in the normal case. A
        # program the compiler then refuses for memory steps the count
        # down (stepped_down_after, at the program's first call).
        first_row: dict = {}  # joins the step log's first row

        def adopt(plan):
            self.adopt_remat_plan(plan)
            memledger.note_remat_plan(plan.scalars())
            first_row.update(plan.scalars())

        adopt(self.plan_remat(state))
        if is_main_process():
            self.logger.info(self.remat_plan.line())
        step_fn = self._build_step(state)
        sync_k = max(1, int(cfg.train.steps_per_sync))
        multi_fn = None
        if sync_k > 1:
            if cfg.parallel.offload_optimizer or cfg.parallel.offload_params:
                raise ValueError(
                    "train.steps_per_sync > 1 does not compose with host "
                    "offload: the offload fallback moves state between host "
                    "and HBM at host-level step boundaries, which a scanned "
                    "window has none of; set steps_per_sync=1")
            if jax.process_count() > 1:
                raise ValueError(
                    "train.steps_per_sync > 1 is single-host only: "
                    "per-window global-batch assembly is not implemented "
                    "for multi-host meshes")
            from dlti_tpu.training.step import make_multi_step

            multi_fn = make_multi_step(step_fn)
        # Per-step rng keys are folded from a fixed base by *global step
        # index* (not a split chain): step N uses fold_in(base, N) whether
        # the run reached N directly or resumed into it, which is what
        # makes a mid-epoch resume's loss trajectory bit-identical to the
        # uninterrupted run's — a split chain would desynchronize on
        # resume (and on preemption-dropped window batches).
        rng_base = jax.random.PRNGKey(cfg.train.seed + 1)
        timer = StepTimer(warmup_steps=2)

        trainable, total = count_params(state.params)
        if is_main_process():
            self.logger.info(
                "trainable params: %s / %s (%.4f%%)",
                f"{trainable:,}", f"{total:,}", 100 * trainable / total,
            )

        tokens_per_step = (
            cfg.train.micro_batch_size * cfg.train.grad_accum_steps * cfg.data.max_seq_len
        )

        # -- unified telemetry (dlti_tpu.telemetry) ---------------------
        tcfg = cfg.telemetry
        if tcfg.trace_dir:
            self._tracer = configure_tracer(enabled=True,
                                            capacity=tcfg.trace_capacity)
        tracer = self._tracer
        steplog = None
        if tcfg.step_log_path and is_main_process():
            steplog = StepLogWriter(tcfg.step_log_path, run_meta={
                "experiment": experiment_name_from_config(cfg),
                "num_gpus": cfg.parallel.num_devices,
                "zero_stage": int(cfg.parallel.zero_stage),
                "strategy": self._strategy(),
            })
        heartbeat = None
        if tcfg.heartbeat_interval_steps > 0:
            heartbeat = Heartbeat()

        # -- self-monitoring: time-series ring + watchdog + black box ---
        # (telemetry.timeseries / .watchdog / .flightrecorder): the ring
        # samples the live training scalars below; the watchdog's
        # hung-step rule is fed by notify_step in bookkeep; the flight
        # recorder dumps on fatal exceptions, preemption stops, watchdog
        # escalation, and the chaos injector's pre-fire hook.
        wcfg, fcfg = tcfg.watchdog, tcfg.flight_recorder
        sampler = None
        watchdog = None
        flight = None
        self._live = {"train_step": start_step}
        # Sentinel handles for _train_scalars (populated after resume).
        self._sentinel = None
        self._skiplist = None
        self._sdc_probe = None

        # Elastic supervision (dlti_tpu.training.elastic): when launched
        # by the ElasticLauncher, report per-step liveness via heartbeat
        # files (the supervisor's staleness + chaos-trigger input) and
        # expose the generation/world gauges.
        from dlti_tpu.training import elastic as _elastic

        einfo = _elastic.elastic_info()
        if einfo is not None:
            _elastic.generation_gauge.set(einfo["generation"])
            _elastic.world_size_gauge.set(jax.process_count())
            self._live["elastic_generation"] = einfo["generation"]
            self._live["elastic_world_size"] = jax.process_count()
            self._live["elastic_restarts"] = _elastic.restarts_total.value
            _elastic.beat(start_step)  # liveness before the first step

        def _train_scalars():
            from dlti_tpu.telemetry import startup as _startup
            from dlti_tpu.checkpoint.store import (
                corrupt_skipped, last_verified_step, save_retries,
            )

            d = dict(self._live)
            d["ckpt_save_retries"] = save_retries.value
            d["ckpt_corrupt_skipped"] = corrupt_skipped.value
            d["ckpt_last_verified_step"] = last_verified_step.value
            d["trace_dropped_events"] = tracer.dropped_events
            # Programs compiled / fetched from the cache so far (counted
            # by the listener scripts/train.py installs): a count that
            # grows mid-run is a recompile.
            d.update(_startup.compile_scalars())
            # Sentinel/SDC counters (set once the sentinel initializes a
            # few lines below the sampler start): the watchdog's
            # loss_spike / nonfinite_step / sdc_mismatch rules watch
            # these ring series.
            if self._sentinel is not None:
                d.update(self._sentinel.scalars())
                d["sentinel_quarantined_windows"] = len(
                    self._skiplist.quarantined())
            if self._sdc_probe is not None:
                d.update(self._sdc_probe.scalars())
            # Goodput ledger: per-bucket seconds + the derived fraction
            # ride the ring (the watchdog's goodput_collapse rule, the
            # /dashboard sparkline, and every flight dump read these).
            if ledger.enabled:
                d.update(ledger.scalars())
            # Memory ledger: hbm_* series (the hbm_pressure rule, the
            # dashboard's "where the memory lives" panel, flight dumps).
            if memledger.enabled:
                d.update(memledger.scalars())
            if heartbeat is not None and heartbeat.last_seen:
                # Straggler lag on /debug/vars (the gauge twin lives in
                # Heartbeat.register; this is the ring-series form).
                d["heartbeat_lag"] = heartbeat.lag()
            # Durable-writer health: disk free/error/degraded series (the
            # watchdog's disk_pressure rule and flight dumps read these).
            d.update(durable_io.scalars())
            return d

        if wcfg.enabled or fcfg.enabled:
            sampler = TimeSeriesSampler(interval_s=wcfg.interval_s)
            sampler.add_source(_train_scalars)
        if fcfg.enabled and (is_main_process() or einfo is not None):
            # Every rank records under an elastic supervisor: per-rank
            # black boxes (tagged -gG-rR) are what postmortem --all
            # renders into one incident, and the SDC probe's suspect rank
            # must be able to dump before it evicts itself.
            if not tracer.enabled:
                # The black box needs a span tail even without a
                # --trace-dir export: recording is cheap (ring appends),
                # missing evidence is not.
                self._tracer = tracer = configure_tracer(
                    enabled=True, capacity=tcfg.trace_capacity)
            flight = FlightRecorder(
                fcfg.dir, tracer=tracer, sampler=sampler, config=cfg,
                max_spans=fcfg.max_spans,
                timeseries_tail=fcfg.timeseries_tail, keep=fcfg.keep)
            flight.add_metrics_source(_train_scalars)
            if memledger.enabled:
                # Every dump carries memory.json — the full ownership map
                # at death, the OOM postmortem's primary evidence.
                flight.add_memory_source(memledger.to_dict)
            flight.note(role="training", phase="init", step=start_step,
                        last_completed_step=start_step,
                        experiment=experiment_name_from_config(cfg))
            install_recorder(flight)
            self._fnote = flight.note
            if self._fault is not None:
                # Chaos forensics: the injected fault's last act is
                # writing the black box — even for N:kill, where the
                # pre-fire hook is the only code that runs before
                # SIGKILL. The drill exists to produce the evidence.
                self._fault.pre_fire = \
                    lambda mode, where, step: flight.dump(
                        reason=f"chaos_{mode}", force=True,
                        extra={"where": where, "injected_at_step": step})
        # Training-side SLO tracker: the goodput-fraction objective over
        # the ledger's own SLI (telemetry.slo) — burn-rate state rides
        # the ring, the watchdog's slo_burn rule, and slo.json in every
        # flight dump.
        slo_tracker = None
        if ledger.enabled and getattr(tcfg, "slo", None) is not None:
            slo_tracker = build_slo_tracker(
                tcfg.slo, goodput_fn=ledger.goodput_fraction)
        if slo_tracker is not None:
            if sampler is not None:
                sampler.add_source(slo_tracker.scalars)
            if flight is not None:
                flight.add_slo_source(slo_tracker.to_dict)
        if wcfg.enabled:
            watchdog = AnomalyWatchdog(wcfg, sampler, heartbeat=heartbeat,
                                       tracer=tracer, slo=slo_tracker)
            if flight is not None:
                flight.add_context_source(
                    lambda: {"watchdog_alerts": list(watchdog.alerts)})
        if sampler is not None:
            sampler.start()
        if watchdog is not None:
            watchdog.start()
        fnote = self._fnote

        # Constants for the per-step MFU/throughput fields (same terms
        # _final_metrics uses for the run-level record). The ledger needs
        # them too: its MFU gauge is the /metrics twin of the steplog's.
        # None on the CPU backend: the MFU fields are then null / unset.
        peak_flops = chip_peak_flops()
        n_for_flops = (cfg.model.num_active_params()
                       if cfg.model.num_experts > 0 else total)

        losses: list = []
        global_step = start_step
        samples_seen = 0
        t_start = time.time()

        # Resume the *data schedule* too, not just the weights: skip the
        # epochs/steps already consumed so no batch is trained twice (the
        # reference delegates this to HF Trainer's resume machinery).
        # The DATA CURSOR is tracked separately from the optimizer step:
        # they are equal until the sentinel quarantines a data window,
        # after which the cursor leads the step by the windows skipped
        # (the sidecar records both, so resume replays exactly).
        spe = dataset.steps_per_epoch() if dataset is not None else 0
        data_cursor = start_step
        if resume_meta and resume_meta.get("data_pos") is not None:
            data_cursor = int(resume_meta["data_pos"])
        start_epoch, skip_steps = 0, 0
        if data_cursor > 0 and dataset is not None:
            if spe > 0:
                start_epoch = min(data_cursor // spe, cfg.train.num_epochs)
                skip_steps = data_cursor % spe
            if resume_meta and resume_meta.get("dataset"):
                # The sidecar records the data cursor the checkpoint was
                # saved at; a mismatch means the resumed run is feeding a
                # different schedule than the original (exact replay off).
                saved = resume_meta["dataset"]
                if saved.get("steps_per_epoch") not in (None, 0, spe):
                    self.logger.warning(
                        "checkpoint sidecar recorded steps_per_epoch=%s "
                        "but this dataset yields %s — mid-epoch resume "
                        "will replay a different batch schedule",
                        saved.get("steps_per_epoch"), spe)
                cur_shuffle = getattr(dataset, "shuffle_seed", None)
                if saved.get("shuffle_seed", cur_shuffle) != cur_shuffle:
                    self.logger.warning(
                        "checkpoint sidecar recorded shuffle_seed=%s but "
                        "this dataset uses %s — batch order will differ "
                        "from the original run",
                        saved.get("shuffle_seed"), cur_shuffle)

        # Mutable resume point: rollback rewinds it mid-run.
        resume_point = {"epoch": start_epoch, "skip": skip_steps}
        # fetch: next data position the loop will consume; committed:
        # position after the last EXECUTED batch (what the sidecar
        # records — prefetched/dropped batches replay on resume).
        cursor = {"fetch": data_cursor, "committed": data_cursor}

        def epoch_batches(epoch):
            if dataset is not None:
                return dataset.epoch(
                    epoch,
                    skip_steps=resume_point["skip"]
                    if epoch == resume_point["epoch"] else 0)
            return batches_per_epoch

        # -- numeric-fault sentinel (dlti_tpu.training.sentinel) --------
        # Detection is pure host math over the metrics the compiled step
        # already syncs; rollback needs a dataset (exact replay) and a
        # checkpoint store to restore from.
        from dlti_tpu.training import sentinel as sentinel_mod

        scfg = cfg.train.sentinel
        sentinel = None
        skiplist = None
        sdc_probe = None
        if scfg.enabled:
            sentinel = sentinel_mod.NumericSentinel(scfg)
            skiplist = sentinel_mod.DataSkipList(scfg.quarantine_after)
            if cfg.checkpoint.save_strategy != "no":
                skiplist.load(cfg.checkpoint.output_dir)
            if resume_meta:
                skiplist.merge_meta(resume_meta.get("skip_list"))
            if skiplist.quarantined() and is_main_process():
                self.logger.warning(
                    "sentinel: honoring persistent skip-list — %d data "
                    "window(s) quarantined: %s", len(skiplist.quarantined()),
                    sorted(skiplist.quarantined()))
            if scfg.sdc_check_interval > 0 and jax.process_count() > 1:
                sdc_probe = sentinel_mod.SDCProbe(scfg.sdc_check_interval)
        self._sentinel, self._skiplist, self._sdc_probe = \
            sentinel, skiplist, sdc_probe
        rollback_allowed = (sentinel is not None and dataset is not None
                            and cfg.checkpoint.save_strategy != "no"
                            and scfg.rollback_after > 0)
        # step -> data position of the batch that fed it (bounded; the
        # rollback path looks up the anomalous streak's windows here).
        step_pos: dict = {}
        skipped_windows = 0

        # -- background batch prefetch (dlti_tpu.data.prefetch) ---------
        # Gather/pack runs on a worker thread, double-buffered
        # cfg.data.prefetch_depth deep; where the step's input sharding is
        # known host-side the worker also issues the device_put ahead of
        # need (an async dispatch — the transfer overlaps the in-flight
        # step). Batch ORDER is untouched (one worker, FIFO queue), so the
        # loss trajectory is bit-identical to the inline path.
        # Only dataset-driven epochs prefetch: a custom batches_per_epoch
        # iterable may be a side-effecting generator whose *laziness* is
        # load-bearing (e.g. requesting a stop at yield time), and eager
        # consumption would reorder those effects against the step loop.
        prefetch_depth = (max(0, int(cfg.data.prefetch_depth))
                          if dataset is not None else 0)
        prefetch_place = None
        if prefetch_depth > 0 and multi_fn is None:
            if self.mesh is None:
                # Single-device jit: plain default-device placement.
                prefetch_place = jax.device_put
            elif (jax.process_count() == 1 and cfg.parallel.pipe == 1
                  and not (cfg.parallel.offload_optimizer
                           or cfg.parallel.offload_params)):
                # Flat sharded path: place with the step's own batch
                # sharding (make_sharded_train_step's in_shardings), so
                # dispatch finds the operands already resident. Pipe and
                # offload steps keep host batches (their wrappers reshape
                # or move operands themselves); multi-host keeps
                # make_global_batch on the step thread.
                from jax.sharding import NamedSharding

                from dlti_tpu.parallel.sharding import batch_pspec

                _b_sh = NamedSharding(self.mesh, batch_pspec(cfg))
                prefetch_place = lambda b: {  # noqa: E731
                    k: jax.device_put(v, _b_sh) for k, v in b.items()}
        # steps_per_sync windows stack HOST batches (exec_window), so the
        # worker prefetches the gather only — placement would be a wasted
        # second transfer. Window mode still benefits: the gather/pack for
        # batch N+1 overlaps the scanned window N.
        self._prefetcher = None

        def make_batch_iter(epoch):
            src = epoch_batches(epoch)
            if prefetch_depth > 0:
                from dlti_tpu.data.prefetch import HostPrefetcher

                self._prefetcher = HostPrefetcher(
                    src, depth=prefetch_depth, place_fn=prefetch_place,
                    tracer=tracer)
                return iter(self._prefetcher)
            return iter(src)

        def close_prefetcher():
            if self._prefetcher is not None:
                self._prefetcher.close()
                self._prefetcher = None

        eval_fn = None
        if eval_dataset is not None and cfg.train.eval_steps:
            if cfg.parallel.pipe > 1:
                from dlti_tpu.parallel.pipeline import make_pipeline_eval_step

                # Packed eval batches are fine: make_pipeline_eval_step
                # passes segment_ids/positions through pipeline_forward.
                eval_fn = make_pipeline_eval_step(cfg, self.mesh)
                params_dev_sh = getattr(step_fn,
                                        "params_dev_shardings", None)
                if params_dev_sh is not None:
                    # PP x offload_params: eval feeds params into the
                    # same pipe shard_map, which cannot take pinned_host
                    # stage-sharded operands. Tag the shardings for
                    # _run_eval, which transfers the frozen tree
                    # HBM-ward ONCE per eval pass (not per batch — a 7B
                    # base x 50 eval batches would be hundreds of GB of
                    # needless DMA) and releases the copy after.
                    inner_eval = eval_fn

                    def eval_fn(state, batch, _inner=inner_eval):
                        return _inner(state, batch)

                    eval_fn.params_dev_shardings = params_dev_sh
            else:
                from dlti_tpu.training.step import make_eval_step

                eval_fn = jax.jit(make_eval_step(
                    self.model, loss_chunk=self.cfg.train.loss_chunk))

        # Profiler window state: "pending" -> "active" -> "done" (at most
        # one trace per run; ">=" so a resume past the start step still
        # captures the next profile_num_steps steps).
        profile_state = "pending"
        profile_stop_at = None

        recorder = None
        if cfg.train.record_replay_dir and is_main_process():
            from dlti_tpu.utils.debug import StepRecorder

            recorder = StepRecorder(cfg.train.record_replay_dir,
                                    keep=cfg.train.record_replay_keep,
                                    every_steps=cfg.train.record_replay_every)
        # steps_per_sync window of (host_batch, global_batch, step_rng)
        # pending dispatch; always empty when multi_fn is None.
        window: list = []

        # In a steps_per_sync run the standalone per-step executable only
        # compiles when a drain first needs it (full windows trace step_fn
        # inline); that first call's compile time must not pollute the
        # step-time samples.
        step_fn_warm = {"done": multi_fn is None}

        # Activation-peak estimate: fold the compiled step's
        # memory_analysis() (temp/argument/output bytes — the transient
        # HBM a between-steps snapshot can never see) into the memory
        # ledger, once. Opt-in via env: the jit wrapper exposes no handle
        # to its cached executable, so this lowers+compiles a second time
        # — free on the tiny CI models that assert on it, not on a 7B run.
        mem_act = {"due": (memledger.enabled and os.environ.get(
            "DLTI_HBM_ANALYZE_STEP", "0") != "0")}

        def fold_step_memory_analysis(state, gb, r):
            mem_act["due"] = False
            try:
                info = executable_memory_analysis(
                    step_fn.lower(state, gb, r).compile())
            except Exception:
                return
            memledger.note_activation_peak(info)

        def stepped_down_after(exc, state) -> bool:
            """A program refused for memory before it ran, with blocks
            kept: keep one fewer, build the programs again, say so. False
            for any other fault (and for one that took the donated state
            with it: nothing is left to train)."""
            nonlocal step_fn, multi_fn
            plan = self.remat_plan
            if not (plan.keep_blocks and is_oom_error(exc)) or any(
                    x.is_deleted() for x in jax.tree_util.tree_leaves(state)
                    if isinstance(x, jax.Array)):
                return False
            adopt(plan.stepped_down())
            self.logger.warning(
                "remat: the step that keeps %d blocks was refused its "
                "memory (%s); keeping %d", plan.keep_blocks,
                str(exc).strip().splitlines()[0][:200],
                self.remat_plan.keep_blocks)
            step_fn = self._build_step(state)
            if multi_fn is not None:
                from dlti_tpu.training.step import make_multi_step

                multi_fn = make_multi_step(step_fn)
            return True

        def run_stepping_down(program, state, *inputs):
            """``program()(state, *inputs)``; the thunk because a program
            the compiler refuses for memory is built again with a block
            fewer kept."""
            while True:
                try:
                    return program()(state, *inputs)
                except Exception as exc:
                    if not stepped_down_after(exc, state):
                        raise

        def exec_steps(state, items):
            """Classic path: one compiled call + host sync per step."""
            executed = []
            for hb, gb, r, pos in items:
                if mem_act["due"]:
                    fold_step_memory_analysis(state, gb, r)
                warm = step_fn_warm["done"]
                if warm:
                    timer.start()
                fnote(phase="step_dispatch")
                ledger.enter("step_compute")
                with tracer.span("train/step_dispatch", cat="train"):
                    state, m = run_stepping_down(lambda: step_fn, state,
                                                 gb, r)
                fnote(phase="device_sync")
                ledger.enter("device_sync")
                with tracer.span("train/device_sync", cat="train"):
                    m = jax.device_get(m)  # blocks: true step time
                if warm:
                    timer.stop()
                else:
                    step_fn_warm["done"] = True
                executed.append((hb, r, m, pos))
            return state, executed

        def exec_window(state):
            """One scanned program runs the whole window; sync once.

            Stacks the *host* batches: multi-host runs are rejected for
            steps_per_sync > 1, and single-process ``make_global_batch``
            is a pass-through, so the host batch IS the step input — the
            stack never round-trips device arrays."""
            import jax.numpy as jnp

            k = len(window)
            stacked = {key: np.stack([it[0][key] for it in window])
                       for key in window[0][0]}
            rngs = jnp.stack([it[2] for it in window])
            with timer.measure(steps=k):
                fnote(phase="step_dispatch")
                ledger.enter("step_compute")
                with tracer.span("train/step_dispatch", cat="train",
                                 window=k):
                    state, mstack = run_stepping_down(
                        lambda: multi_fn, state, stacked, rngs)
                fnote(phase="device_sync")
                ledger.enter("device_sync")
                with tracer.span("train/device_sync", cat="train"):
                    mstack = jax.device_get(mstack)
            executed = [(window[i][0], window[i][2],
                         {key: v[i] for key, v in mstack.items()},
                         window[i][3])
                        for i in range(k)]
            window.clear()
            return state, executed

        def drain_window(state):
            """Run pending window items through the per-step path (epoch
            tail or a max_steps-capped short window — the scanned program
            is shape-specialized to full windows), capped to the
            remaining step budget."""
            items = list(window)
            window.clear()
            if cfg.train.max_steps:
                items = items[:max(0, cfg.train.max_steps - global_step)]
            if not items:
                return state, []
            return exec_steps(state, items)

        def sidecar_meta():
            """Full-state sidecar saved next to the arrays: the data
            cursor + rng schedule that make a resumed run replay the
            exact batch/rng sequence (prefetched-but-unexecuted batches
            are dropped on every exit path, so the cursor IS the step)."""
            committed = cursor["committed"]
            return {
                "format": 1,
                "step": global_step,
                # Data cursor: equals the step until the sentinel skips
                # quarantined windows, after which it leads the step.
                "data_pos": committed,
                "epoch": (committed // spe) if spe else 0,
                "step_in_epoch": (committed % spe) if spe else 0,
                "samples_seen": samples_seen,
                "seed": cfg.train.seed,
                "rng_schedule": "fold_in_v1",
                # Persistent data quarantine (dlti_tpu.training.sentinel):
                # strike-counted windows; quarantined ones are skipped on
                # this run and every resume.
                "skip_list": skiplist.to_meta() if skiplist is not None
                else [],
                "dataset": {
                    "kind": type(dataset).__name__ if dataset is not None
                    else None,
                    "steps_per_epoch": spe,
                    "shuffle_seed": getattr(dataset, "shuffle_seed", None),
                    "packed": bool(getattr(dataset, "pack",
                                           getattr(dataset, "packed",
                                                   False))),
                },
                "prefetch_depth": prefetch_depth,
                "fp16": bool(cfg.train.fp16),
            }

        def bookkeep(state, executed):
            """Per-step records for a batch of executed steps, then
            window-boundary eval/save (cadence-crossing aware, so
            eval_steps/save_steps need not divide steps_per_sync)."""
            nonlocal global_step, samples_seen
            step_before = global_step
            window_anomalous = False
            # Memory ledger: follow the state rebind, then one snapshot
            # per bookkeep (not per step — live_arrays walks aren't free)
            # feeding the window's steplog records and the /metrics
            # gauges.
            mem_state["state"] = state
            mem_scalars = memledger.scalars() if memledger.enabled else {}
            # Goodput bookkeeping: host-side accounting books to "other";
            # the deltas accrued since the previous bookkeep feed the
            # steplog's per-phase fields and the /metrics counter (a
            # checkpoint issued below lands in the NEXT bookkeep's
            # deltas). Replay ends once the run passes its pre-rollback
            # high-water step — from here on, progress is fresh.
            ledger.enter("other")
            deltas = ledger.take_deltas()
            n_exec = max(1, len(executed))
            if (ledger.replay_until is not None
                    and global_step + len(executed)
                    >= ledger.replay_until):
                ledger.end_replay()
            for k, v in deltas.items():
                goodput_seconds_total.labels(bucket=k).inc(v)
            for hb, r, m, pos in executed:
                global_step += 1
                samples_seen += (cfg.train.micro_batch_size
                                 * cfg.train.grad_accum_steps)
                losses.append(float(m["loss"]))
                cursor["committed"] = pos + 1
                step_pos[global_step] = pos
                verdict = None
                if sentinel is not None:
                    # Anomaly verdict over the metrics this already-paid
                    # host sync delivered: nonfinite, loss/grad spikes vs
                    # the rolling window, streak accounting.
                    verdict = sentinel.observe(
                        global_step, float(m["loss"]),
                        float(m["grad_norm"]),
                        bool(float(m.get("skipped_update", 0.0))))
                    if verdict["kind"]:
                        window_anomalous = True
                        self.logger.warning(
                            "sentinel: %s anomaly at step %d (loss %.4g, "
                            "grad_norm %.4g, data window %d, streak %d)",
                            verdict["kind"], global_step, float(m["loss"]),
                            float(m["grad_norm"]), pos,
                            len(verdict["streak"]))
                        fnote(sentinel_last_anomaly={
                            "step": global_step, "kind": verdict["kind"],
                            "data_pos": pos})
                    if (verdict["rollback_due"] and rollback_allowed
                            and self._rollback_due is None):
                        self._rollback_due = {
                            "streak": verdict["streak"],
                            "positions": [step_pos[s]
                                          for s, _ in verdict["streak"]
                                          if s in step_pos]}
                if recorder is not None:
                    # Record the pre-assembly host-local batch: the
                    # global array's shards span other hosts' devices
                    # and cannot be fetched here.
                    recorder.record(global_step, hb, r, m)
                # what the model counted in this step (models.jamba: the
                # documents that started inside its rows), by its own names
                counted = {k: int(float(m[k])) for k in getattr(
                    self.model, "train_counters", ()) if k in m}
                self._live.update(
                    {"train_" + k: v for k, v in counted.items()})
                if steplog is not None:
                    # Per-step JSONL telemetry (rank-0): the MegaScale-
                    # style in-framework stream. Window-executed steps
                    # share the window's per-step time.
                    dt = timer.last_step_seconds
                    tok_s_chip = (tokens_per_step / dt
                                  / max(jax.device_count(), 1)
                                  if dt > 0 else 0.0)
                    peak_gb, peak_src = device_peak_memory()
                    mfu_step = compute_mfu(tok_s_chip, n_for_flops,
                                           peak_flops,
                                           trainable_params=trainable)
                    steplog.log_step(
                        global_step,
                        loss=losses[-1],
                        grad_norm=float(m["grad_norm"]),
                        lr=schedule_lr(cfg.optimizer, global_step),
                        tokens_per_second_per_chip=round(tok_s_chip, 2),
                        mfu_percent=(None if mfu_step is None
                                     else round(mfu_step, 4)),
                        peak_memory_gb=round(peak_gb, 4),
                        peak_memory_source=peak_src,
                        step_time_s=round(dt, 6),
                        anomaly=(verdict or {}).get("kind", ""),
                        skipped_update=int(bool(float(
                            m.get("skipped_update", 0.0)))),
                        rollbacks_total=(sentinel.rollbacks
                                         if sentinel is not None else 0),
                        # Goodput-ledger per-phase fields (steplog
                        # schema): the window's accrual split evenly
                        # across its records; 0.0 when the ledger is off.
                        data_wait_s=round(
                            deltas.get("data_wait", 0.0) / n_exec, 6),
                        sync_s=round(
                            deltas.get("device_sync", 0.0) / n_exec, 6),
                        ckpt_s=round(
                            (deltas.get("checkpoint_save", 0.0)
                             + deltas.get("checkpoint_restore", 0.0))
                            / n_exec, 6),
                        rollback_s=round(
                            (deltas.get("rollback", 0.0)
                             + deltas.get("replay", 0.0)) / n_exec, 6),
                        # Memory-ledger per-step fields (steplog schema):
                        # headroom is -1 when capacity is unknown (CPU
                        # without a budget); both 0 when the ledger is
                        # off.
                        hbm_bytes_in_use=int(
                            mem_scalars.get("hbm_bytes_in_use", 0)),
                        hbm_headroom_bytes=int(
                            mem_scalars.get(
                                "hbm_headroom_bytes",
                                -1 if memledger.enabled else 0)),
                        **counted,
                        **first_row,
                    )
                    first_row.clear()
                if global_step % cfg.train.logging_steps == 0 and is_main_process():
                    self.logger.info(
                        "step %d | loss %.4f | grad_norm %.3f | %.2f steps/s | %.0f tok/s/chip",
                        global_step, losses[-1], float(m["grad_norm"]),
                        timer.steps_per_second,
                        timer.steps_per_second * tokens_per_step
                        / max(jax.device_count(), 1),
                    )
            # Self-monitoring bookkeeping: refresh the sampled scalars,
            # feed the hung-step heartbeat, and stamp the flight context
            # with the last completed step (what a postmortem names).
            dt = timer.last_step_seconds
            self._live.update(
                train_step=global_step,
                train_step_time_s=dt,
                train_tokens_per_s=(tokens_per_step / dt if dt > 0 else 0.0),
                samples_seen=samples_seen)
            if ledger.enabled:
                # Goodput fraction + MFU as /metrics gauges (module-level
                # like the ckpt-store counters) and a /debug/vars series.
                goodput_fraction_gauge.set(ledger.goodput_fraction())
                if peak_flops and dt > 0:
                    mfu_now = compute_mfu(
                        tokens_per_step / dt / max(jax.device_count(), 1),
                        n_for_flops, peak_flops,
                        trainable_params=trainable)
                    self._live["train_mfu_percent"] = round(mfu_now, 4)
                    goodput_mfu_gauge.set(round(mfu_now, 4))
            if losses:
                self._live["train_loss"] = losses[-1]
            if watchdog is not None:
                watchdog.notify_step(global_step)
            if einfo is not None:
                # Per-step liveness file for the elastic supervisor
                # (independent per process — unlike the collective
                # Heartbeat below, it keeps reporting when a peer dies).
                _elastic.beat(global_step)
                if ledger.enabled:
                    # Refresh this generation's ledger file (throttled):
                    # a SIGKILLed worker never reaches its exit-path
                    # save, and the supervisor's stitched ledger must
                    # still book the generation's rollback/replay time.
                    _elastic.save_generation_ledger(ledger.to_dict(),
                                                    step=global_step)
            fnote(step=global_step, last_completed_step=global_step,
                  phase="between_steps")
            if len(step_pos) > 4096:
                for s in sorted(step_pos)[:-2048]:
                    del step_pos[s]
            # Cross-rank SDC probe — BEFORE the collective heartbeat/
            # eval/save below: on a mismatch the suspect rank exits and
            # the survivors must stop without entering another
            # collective (which would wedge on the dead peer).
            if sdc_probe is not None and sdc_probe.due(step_before,
                                                       global_step):
                fnote(phase="sdc_probe")
                ledger.enter("sdc_probe")
                with tracer.span("train/sdc_probe", cat="train",
                                 step=global_step):
                    res = sdc_probe.check(state.params, global_step)
                ledger.enter("other")
                if res["mismatch"]:
                    suspect_self = res["rank"] in res["suspects"]
                    alert = {
                        "wall": time.time(), "rule": "sdc_mismatch",
                        "message": (
                            f"cross-rank param digest mismatch at step "
                            f"{global_step}: suspect rank(s) "
                            f"{res['suspects']} (digests {res['digests']})"),
                        "step": global_step, "suspects": res["suspects"],
                        "rank": res["rank"]}
                    self.logger.error("sentinel: %s", alert["message"])
                    from dlti_tpu.training.elastic import mirror_alert

                    try:
                        mirror_alert(alert)
                    except Exception:
                        pass
                    if flight is not None:
                        flight.dump(reason="sdc_mismatch", force=True,
                                    extra={"alert": alert,
                                           "suspect_self": suspect_self})
                    if suspect_self:
                        # This host's replicated params diverged from the
                        # fleet: its memory/compute is untrustworthy. The
                        # black box is written; exit with the distinctive
                        # code so the elastic supervisor books THIS slot
                        # failed, reshapes the survivors, and rejoins the
                        # slot later with checkpoint-fresh params.
                        self.logger.error(
                            "sentinel: this rank (%d) is the SDC suspect; "
                            "exiting %d for supervisor eviction",
                            res["rank"], sentinel_mod.SDC_EXIT_CODE)
                        os._exit(sentinel_mod.SDC_EXIT_CODE)
                    # Healthy ranks: stop cleanly with NO further
                    # collectives (no final save — its consolidation
                    # would hang on the evicted peer); the relaunched
                    # generation resumes from the last verified step.
                    self._sdc_evict = True
                    self._stop_requested = True
                    return
            if heartbeat is not None and (
                    global_step // tcfg.heartbeat_interval_steps
                    > step_before // tcfg.heartbeat_interval_steps):
                # COLLECTIVE on multi-host meshes: every process reaches
                # this boundary at the same global_step (the loop is
                # step-synchronous), so the allgather lines up.
                heartbeat.beat(global_step)
                if is_main_process():
                    report = heartbeat.straggler_report()
                    if report:
                        self.logger.warning("heartbeat: %s", report)
            if (eval_fn is not None and cfg.train.eval_steps
                    and (global_step // cfg.train.eval_steps
                         > step_before // cfg.train.eval_steps)):
                self._run_eval(eval_fn, state, eval_dataset, global_step)
            if window_anomalous:
                # Never checkpoint a state produced by an anomalous step:
                # a spike's update is exactly what rollback exists to
                # discard, and saving it would make it the resume target.
                ck = self.cfg.checkpoint
                if (ck.save_strategy == "steps"
                        and global_step // ck.save_steps
                        > step_before // ck.save_steps):
                    self.logger.warning(
                        "sentinel: save suppressed at step %d (anomalous "
                        "window)", global_step)
            else:
                self._maybe_save(state, global_step, epoch_end=False,
                                 crossed_from=step_before,
                                 meta=sidecar_meta())
            if self._fault is not None:
                # Step-boundary chaos: fires after the step booked (and
                # its save, if due, was issued) — the crash point real
                # preemptions hit.
                self._fault.maybe_fire_step(global_step)

        def do_rollback(state, epoch):
            """Automatic numeric-fault recovery: restore the last
            digest-verified checkpoint, strike the data windows that fed
            the anomalous streak (quarantining repeat offenders), rewind
            the step counter and data cursor, and let the epoch loop
            re-enter at the restored position. The lr/rng schedule is a
            pure function of the step index, so the replayed steps are
            bit-identical to a run that never went anomalous."""
            nonlocal global_step
            info = self._rollback_due
            self._rollback_due = None
            if sentinel.over_budget():
                raise sentinel_mod.SentinelGiveUp(
                    f"sentinel rollback budget exhausted "
                    f"({sentinel.rollbacks} rollbacks, anomalies persist); "
                    f"a human must look at the data/hardware")
            from dlti_tpu.checkpoint import (
                restore_latest_verified, wait_for_saves)

            ckdir = cfg.checkpoint.output_dir
            pre_rollback_step = global_step
            ledger.enter("rollback")
            wait_for_saves(ckdir)
            fnote(phase="sentinel_rollback")
            with tracer.span("train/sentinel_rollback", cat="train",
                             step=global_step):
                restored = restore_latest_verified(ckdir, state)
            sentinel.note_rollback()
            if restored is None:
                self.logger.error(
                    "sentinel: rollback wanted after %d consecutive "
                    "anomalies but no verified checkpoint exists; "
                    "continuing in place (streak reset)",
                    len(info["streak"]))
                return state, epoch
            new_state, step, meta = restored
            mem_state["state"] = new_state
            ck_cursor = int((meta or {}).get("data_pos", step))
            # Strike ONLY the windows that fed anomalous steps — the
            # innocent windows since the checkpoint replay untouched.
            positions = sorted({p for p in info["positions"]
                                if p >= ck_cursor})
            newly_q = skiplist.strike(positions, step=global_step)
            if cfg.checkpoint.save_strategy != "no":
                skiplist.save(ckdir)
            if flight is not None:
                flight.dump(reason="sentinel_rollback", force=True, extra={
                    "streak": info["streak"], "restored_step": int(step),
                    "struck_windows": positions, "quarantined": newly_q,
                    "rollbacks": sentinel.rollbacks})
            self.logger.warning(
                "sentinel: ROLLBACK #%d after %d consecutive anomalies "
                "(last: %s) — restored verified step %d, struck data "
                "window(s) %s%s", sentinel.rollbacks, len(info["streak"]),
                info["streak"][-1][1], step, positions,
                f"; QUARANTINED {newly_q}" if newly_q else
                " (replaying once)")
            global_step = int(step)
            # Until the run passes its pre-rollback high-water step, the
            # re-executed steps are replay — recovery cost, not fresh
            # progress (the ledger reclasses their step buckets).
            ledger.begin_replay(pre_rollback_step)
            cursor["committed"] = ck_cursor
            cursor["fetch"] = ck_cursor
            step_pos.clear()
            self._live["train_step"] = global_step
            # A re-reached save boundary must re-save (no committed dir
            # newer than the restore target can exist — it would have
            # been the restore target).
            self._last_save_step = None
            if einfo is not None:
                # The rollback booking must reach the supervisor's
                # stitched ledger even if this worker is killed mid-replay.
                _elastic.save_generation_ledger(ledger.to_dict(),
                                                step=global_step, force=True)
            if dataset is not None and spe:
                new_epoch = min(ck_cursor // spe, cfg.train.num_epochs)
                resume_point["epoch"] = new_epoch
                resume_point["skip"] = ck_cursor % spe
                return new_state, new_epoch
            return new_state, epoch

        _EPOCH_END = object()  # sentinel: a batch is never this object
        try:
            epoch = start_epoch
            while epoch < cfg.train.num_epochs:
                batch_iter = make_batch_iter(epoch)
                if dataset is not None and spe:
                    cursor["fetch"] = epoch * spe + (
                        resume_point["skip"]
                        if epoch == resume_point["epoch"] else 0)
                while True:
                    # Manual iteration so the data-pipeline wait is its
                    # own trace span (the phase MegaScale singles out:
                    # input stalls masquerade as slow steps otherwise).
                    # Under prefetch this span measures the *stall* only —
                    # the gather itself runs in the worker's
                    # train/prefetch spans.
                    fnote(phase="batch_fetch")
                    ledger.enter("data_wait")
                    with tracer.span("train/batch_fetch", cat="train"):
                        batch = next(batch_iter, _EPOCH_END)
                    if batch is _EPOCH_END:
                        break
                    # Data position of THIS batch in the global schedule
                    # (epoch * steps_per_epoch + index): the key the
                    # sentinel's quarantine list is kept in — optimizer
                    # steps renumber once windows are skipped, positions
                    # never do.
                    pos = cursor["fetch"]
                    cursor["fetch"] += 1
                    if skiplist is not None and pos in skiplist.quarantined():
                        skipped_windows += 1
                        self._live["sentinel_windows_skipped"] = \
                            skipped_windows
                        if is_main_process():
                            self.logger.warning(
                                "sentinel: skipping quarantined data "
                                "window %d", pos)
                        continue
                    # A pending window always has len < take <= remaining
                    # step budget (it drains the moment it reaches take),
                    # so this check never skips queued-but-unrun steps.
                    if cfg.train.max_steps and global_step >= cfg.train.max_steps:
                        break
                    if cfg.train.profile_dir and is_main_process():
                        if (profile_state == "pending"
                                and global_step >= cfg.train.profile_start_step):
                            # Through the tracer: the capture then
                            # carries the train/* spans, trace dir or not.
                            tracer.start_capture(cfg.train.profile_dir)
                            profile_state = "active"
                            profile_stop_at = (global_step
                                               + cfg.train.profile_num_steps)
                        elif (profile_state == "active"
                              and global_step >= profile_stop_at):
                            tracer.stop_capture()
                            profile_state = "done"
                            self.logger.info("profiler trace -> %s",
                                             cfg.train.profile_dir)
                    if self._prefetcher is not None:
                        # (host numpy batch, worker-placed batch); placed
                        # is the host batch itself when placement stayed
                        # on the step thread (windows, multi-host, pipe).
                        host_batch, batch = batch
                    else:
                        host_batch = batch
                    if self._fault is not None:
                        # Numeric chaos (nan-grad / poison-batch): corrupt
                        # the HOST batch before placement so the fault
                        # flows through the genuine compiled step.
                        corrupted = self._fault.maybe_corrupt_batch(
                            pos, global_step + len(window) + 1, host_batch)
                        if corrupted is not None:
                            host_batch = corrupted
                            batch = corrupted
                    if self.mesh is not None:
                        from dlti_tpu.parallel.sharding import make_global_batch

                        ledger.enter("host_to_device")
                        with tracer.span("train/host_to_device",
                                         cat="train"):
                            # Single-process: pass-through (worker-placed
                            # batches arrive here already device-resident).
                            batch = make_global_batch(batch, cfg, self.mesh)
                    # This batch executes as optimizer step global_step +
                    # len(window) + 1 (window always empty on the plain
                    # path); folding by that index keeps the schedule
                    # stateless — resumable and drop-safe.
                    step_rng = jax.random.fold_in(
                        rng_base, global_step + len(window) + 1)
                    if multi_fn is None:
                        state, executed = exec_steps(
                            state, [(host_batch, batch, step_rng, pos)])
                    else:
                        if window and not _batch_compatible(
                                window[0][0], host_batch):
                            # Custom batches_per_epoch iterables may change
                            # shape mid-stream (e.g. a ragged drop_last
                            # tail): drain the pending window per-step and
                            # start a new one — matching what the per-step
                            # jit would do (recompile), instead of a stack
                            # error.
                            state, executed = drain_window(state)
                            if executed:
                                bookkeep(state, executed)
                        window.append((host_batch, batch, step_rng, pos))
                        take = sync_k
                        if cfg.train.max_steps:
                            take = min(take,
                                       cfg.train.max_steps - global_step)
                        if len(window) < take:
                            if self._stop_requested:
                                # Preemption while the window fills: drop
                                # the queued batches (never counted, so
                                # resume replays them) and checkpoint now
                                # instead of up to K-1 batches later.
                                break
                            continue
                        if len(window) == sync_k:
                            state, executed = exec_window(state)
                        else:  # max_steps-capped short window
                            state, executed = drain_window(state)
                    # The step's books and its log line: host time between
                    # one step's sync and the next batch's fetch (eval and
                    # saves inside it keep their own spans).
                    with tracer.span("train/bookkeep", cat="train"):
                        bookkeep(state, executed)
                    if self._fault is not None:
                        # param-flip chaos: corrupt a replicated leaf in
                        # the LIVE state at the step boundary (rank-gated)
                        # — the SDC probe's drill input.
                        flipped = self._fault.maybe_corrupt_state(
                            global_step, state)
                        if flipped is not None:
                            state = flipped
                    if self._rollback_due is not None:
                        break
                    if self._stop_requested:
                        break
                # Epoch over (or preempted / max_steps / rollback): stop
                # the worker and drop its buffered batches — they were
                # never counted, so resume/rollback replays them.
                close_prefetcher()
                if (window and not self._stop_requested
                        and self._rollback_due is None):
                    # Epoch tail shorter than the window. On preemption the
                    # pending window is dropped instead — those steps never
                    # counted, so resume replays them.
                    state, executed = drain_window(state)
                    if executed:
                        bookkeep(state, executed)
                if self._rollback_due is not None:
                    window.clear()
                    state, epoch = do_rollback(state, epoch)
                    continue  # re-enter at the restored data position
                self._maybe_save(state, global_step, epoch_end=True,
                                 meta=sidecar_meta())
                if cfg.train.max_steps and global_step >= cfg.train.max_steps:
                    break
                if self._stop_requested:
                    break
                epoch += 1
            if (self._stop_requested and not self._sdc_evict
                    and cfg.checkpoint.save_strategy != "no"):
                from dlti_tpu.checkpoint import (
                    save_train_state, wait_for_saves)

                # _maybe_save may have just written this very step (e.g. the
                # stop landed on a save_steps boundary or at epoch end);
                # settle any in-flight async save first. The already-saved
                # check is the trainer's own marker, NOT latest_step(): a
                # filesystem probe races the (rank-0-only) async writer on
                # multi-process meshes, and a rank-dependent answer would
                # send only some ranks into the collective consolidation
                # below — a deadlock, not a redundant write.
                wait_for_saves(cfg.checkpoint.output_dir)
                if getattr(self, "_last_save_step", None) != global_step:
                    save_train_state(
                        cfg.checkpoint.output_dir, global_step, state,
                        keep=cfg.checkpoint.save_total_limit,
                        async_save=False, train_meta=sidecar_meta(),
                        retries=cfg.checkpoint.save_retries,
                        retry_backoff_s=cfg.checkpoint.save_retry_backoff_s)
                    self.logger.info(
                        "preemption checkpoint written at step %d", global_step)
        finally:
            ledger.enter("shutdown")
            close_prefetcher()  # a mid-epoch exception must not leak the worker
            if flight is not None:
                # The black box goes down with the ship: a fatal
                # exception (or a preemption stop) dumps before any
                # cleanup rewrites state. dump() never raises and
                # throttles duplicates (the chaos pre-fire hook may have
                # dumped milliseconds ago), so the original exception is
                # never masked.
                exc = sys.exc_info()[1]
                if exc is not None:
                    # An OOM death is filed as such: the dump's
                    # memory.json (add_memory_source above) is what
                    # postmortem.py renders as "where the memory went".
                    flight.dump(reason="oom" if is_oom_error(exc)
                                else "fatal_exception", exc=exc)
                elif self._stop_requested and not self._sdc_evict:
                    # (an SDC eviction already dumped its own black box)
                    flight.dump(reason="preemption_stop")
            if watchdog is not None:
                watchdog.stop()
            if sampler is not None:
                sampler.stop()
            if flight is not None:
                if get_recorder() is flight:
                    install_recorder(None)
                self._fnote = lambda **kw: None
            if memledger_mod.get_ledger() is memledger:
                memledger_mod.install(None)
            if sigterm_installed:
                # signal.signal reports a non-Python-installed previous
                # handler as None; SIG_DFL is the closest restorable state.
                _signal.signal(_signal.SIGTERM,
                               prev_handler if prev_handler is not None
                               else _signal.SIG_DFL)
            if profile_state == "active":  # run ended inside the trace window
                tracer.stop_capture()
            if cfg.checkpoint.save_strategy != "no":
                # Settle in-flight async saves on EVERY exit path —
                # exception and normal return alike — so a training crash
                # cannot strand a half-written "latest" checkpoint (write
                # failures are logged by the store, never raised here,
                # which keeps an original exception unmasked).
                from dlti_tpu.checkpoint import wait_for_saves

                try:
                    wait_for_saves(cfg.checkpoint.output_dir)
                except Exception:
                    self.logger.exception(
                        "settling in-flight checkpoint saves failed")
            if ledger.enabled:
                # Settle the goodput accounting on EVERY exit path: flush
                # the residual deltas into the /metrics counter, set the
                # final fraction, and (under an elastic supervisor) save
                # this generation's ledger for cross-restart stitching.
                for k, v in ledger.take_deltas().items():
                    goodput_seconds_total.labels(bucket=k).inc(v)
                goodput_fraction_gauge.set(ledger.goodput_fraction())
                if einfo is not None:
                    _elastic.save_generation_ledger(
                        ledger.to_dict(), step=global_step, force=True)

        wall = time.time() - t_start
        record = self._final_metrics(
            losses, wall, samples_seen, tokens_per_step, global_step - start_step,
            trainable, total, timer,
        )
        if steplog is not None:
            # The final record is the full MetricsRecord dict, which keeps
            # the JSONL stream a superset of the reference CSV schema.
            steplog.log_final(record)
            steplog.close()
        if tcfg.trace_dir and is_main_process():
            trace_path = tracer.export(os.path.join(
                tcfg.trace_dir,
                f"trace_train_steps_{start_step}-{global_step}.json"))
            self.logger.info(
                "telemetry trace -> %s (open in https://ui.perfetto.dev)",
                trace_path)
        if ledger.enabled and is_main_process():
            totals = ledger.totals()
            top = sorted(totals.items(), key=lambda kv: -kv[1])[:6]
            self.logger.info(
                "goodput: %.1f%% productive over %.1fs booked — %s",
                100 * ledger.goodput_fraction(totals), sum(totals.values()),
                ", ".join(f"{k} {v:.1f}s" for k, v in top))
        if is_main_process():
            # Every local chip, not their sum: a mesh that left a chip
            # empty shows here (absent on the CPU backend — no stats).
            self.logger.info("device memory: %s", json.dumps(
                memledger_mod.device_bytes_in_use(), sort_keys=True))
            print_metrics_summary(record)
            save_training_metrics(record, csv_path=cfg.train.metrics_csv)
        return state, record

    # ------------------------------------------------------------------
    def request_stop(self) -> None:
        """Ask the training loop to checkpoint and exit at the next step
        boundary (what the SIGTERM handler calls on preemption)."""
        self._stop_requested = True

    def _run_eval(self, eval_fn, state, eval_dataset, step: int) -> float:
        dev_sh = getattr(eval_fn, "params_dev_shardings", None)
        if dev_sh is not None:
            # PP x offload_params: one host->HBM transfer of the frozen
            # tree covers the WHOLE eval pass; the device copy goes out
            # of scope (and frees) when this returns.
            state = state.replace(
                params=jax.device_put(state.params, dev_sh))
        losses, toks = [], 0.0
        self._fnote(phase="eval")
        self._ledger.enter("eval")
        with self._tracer.span("train/eval", cat="train", step=step):
            for batch in eval_dataset.epoch(0):
                flat = {
                    k: v.reshape((-1,) + v.shape[2:]) for k, v in batch.items()
                }  # eval ignores the accum dim
                m = jax.device_get(eval_fn(state, flat))
                losses.append(float(m["loss"]) * float(m["num_tokens"]))
                toks += float(m["num_tokens"])
        self._ledger.enter("other")
        eval_loss = sum(losses) / toks if toks else float("nan")
        if toks and is_main_process():
            self.logger.info("eval @ step %d | loss %.4f", step, eval_loss)
        self._last_eval_loss = eval_loss
        return eval_loss

    def _maybe_save(self, state: TrainState, step: int, epoch_end: bool,
                    crossed_from: Optional[int] = None,
                    meta: Optional[dict] = None) -> None:
        cfg = self.cfg.checkpoint
        if cfg.save_strategy == "no":
            return
        if crossed_from is None:
            steps_due = step % cfg.save_steps == 0
        else:
            # A steps_per_sync window advanced (crossed_from, step]; save
            # when it crossed a save_steps boundary, at the window-end
            # state (mid-window states are never materialized on host).
            steps_due = (step // cfg.save_steps
                         > crossed_from // cfg.save_steps)
        due = (
            (cfg.save_strategy == "steps" and steps_due and step > 0)
            or (cfg.save_strategy == "epoch" and epoch_end)
        )
        if not due:
            return
        if getattr(self, "_last_save_step", None) == step:
            # Already saved this step (a save_steps boundary that is also
            # the epoch end books two due saves). The store dedups the
            # *write*, but on a multi-process mesh the state consolidation
            # is a collective launch — skip it symmetrically on every
            # rank, not just where the writer lives.
            return
        self._last_save_step = step
        from dlti_tpu.checkpoint import save_train_state

        self._fnote(phase="checkpoint_save")
        self._ledger.enter("checkpoint_save")
        with self._tracer.span("train/checkpoint_save", cat="train",
                               step=step):
            save_train_state(
                cfg.output_dir, step, state,
                keep=cfg.save_total_limit, async_save=cfg.async_save,
                train_meta=meta, retries=cfg.save_retries,
                retry_backoff_s=cfg.save_retry_backoff_s,
            )
        self._ledger.enter("other")
        if self._fault is not None:
            # Mid-save chaos: with async_save the write is in flight right
            # now — a save-kill here is the honest torn-checkpoint case.
            self._fault.maybe_fire_save(step)

    def _strategy(self) -> str:
        """Strategy label for the reference CSV / telemetry stream."""
        par = self.cfg.parallel
        if par.pipe > 1:
            return f"pipe{par.pipe}"
        if int(par.zero_stage) == 0:
            return "baseline"
        return f"zero{int(par.zero_stage)}"

    def _final_metrics(
        self, losses, wall, samples_seen, tokens_per_step, steps, trainable, total, timer,
    ) -> MetricsRecord:
        cfg = self.cfg
        final_loss = losses[-1] if losses else float("nan")
        sps = samples_seen / wall if wall > 0 else 0.0
        tok_s_chip = (
            timer.steps_per_second * tokens_per_step / max(jax.device_count(), 1)
        )
        peak_flops = chip_peak_flops()
        # MoE: FLOPs/token follow the k *routed* experts, not all E.
        n_for_flops = (cfg.model.num_active_params()
                       if cfg.model.num_experts > 0 else total)
        mfu = compute_mfu(tok_s_chip, n_for_flops, peak_flops,
                          trainable_params=trainable)
        peak_gb, peak_src = device_peak_memory()
        return MetricsRecord(
            experiment=experiment_name_from_config(cfg),
            num_gpus=cfg.parallel.num_devices,
            zero_stage=int(cfg.parallel.zero_stage),
            strategy=self._strategy(),
            training_time_hours=wall / 3600.0,
            samples_per_second=sps,
            peak_memory_gb=peak_gb,
            final_loss=final_loss,
            tokens_per_second_per_chip=tok_s_chip,
            mfu_percent=mfu,
            peak_memory_source=peak_src,
            eval_loss=getattr(self, "_last_eval_loss", float("nan")),
        )
