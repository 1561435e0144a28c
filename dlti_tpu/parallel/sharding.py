"""Sharding rules: ZeRO stages + TP + SP as ``NamedSharding`` presets.

The DeepSpeed ZeRO engine (reference ``configs/ds_config_zero{1,2,3}.json``)
re-expressed in the XLA/GSPMD model (SURVEY.md §2b):

* **ZeRO-1** — params replicated; *optimizer state* sharded over ``data``.
  GSPMD then all-gathers the sharded AdamW update into the replicated params
  (the analog of ``allgather_partitions``, ``ds_config_zero1.json:36``).
* **ZeRO-2** — as ZeRO-1, plus gradients constrained to the optimizer-state
  sharding before the update, forcing a reduce-scatter instead of all-reduce
  (the analog of ``reduce_scatter: true``, ``ds_config_zero1.json:40``).
* **ZeRO-3** — parameters themselves sharded over ``fsdp``; XLA all-gathers
  weights per-layer inside the step and re-shards after use (FSDP). Host
  offload of params/optimizer is a separate memory-kind option
  (``ds_config_zero3.json:19-27`` parity).
* **TP** — attention heads + MLP hidden sharded over ``tensor``; the
  all-reduce after o_proj/down_proj is inserted by GSPMD.
* **SP** — batch also sharded over ``sequence`` on the length dim for ring
  attention (see ``dlti_tpu.parallel.ring_attention``).

Rules are *path + shape* based over the model's deterministic param naming
(``q_proj/kernel``: (in, out), etc.) rather than linen metadata — explicit,
inspectable, and independent of module internals.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlti_tpu.config import Config, ZeROStage
from dlti_tpu.parallel.mesh import BATCH_AXES
from dlti_tpu.training.state import TrainState
from dlti_tpu.utils.logging import get_logger

# ----------------------------------------------------------------------
# Tensor-parallel rules: param-name regex -> (dim sharded by 'tensor')
# Kernels are (in_features, out_features); None = no TP for that param.
# ----------------------------------------------------------------------
_TP_RULES = [
    (r".*(q_proj|k_proj|v_proj)/kernel$", 1),   # column-parallel (heads)
    (r".*(q_proj|k_proj|v_proj)/lora_b$", 1),   # lora_b out dim follows base
    (r".*o_proj/kernel$", 0),                    # row-parallel
    (r".*o_proj/lora_a$", 0),                    # lora_a in dim follows base
    (r".*(gate_proj|up_proj)/kernel$", 1),       # column-parallel (mlp hidden)
    (r".*(gate_proj|up_proj)/lora_b$", 1),
    (r".*down_proj/kernel$", 0),                 # row-parallel
    (r".*down_proj/lora_a$", 0),
    (r".*embed_tokens$", 0),                     # shard vocab rows
    (r".*lm_head$", 1),                          # shard vocab cols
    (r".*mlp/(w1|w3)$", 2),                      # expert ffn hidden (E,h,m)
    (r".*mlp/w2$", 1),                           # (E,m,h) row-parallel
]

# Expert-parallel rule: stacked expert weights shard dim 0 over 'expert'.
_EP_PATTERN = re.compile(r".*mlp/(w1|w2|w3)$")

# Don't FSDP-shard tiny params (norm scales, LoRA factors with dim < 1024):
# the all-gather latency outweighs memory savings. Shared by the flat and
# pipeline param-sharding rules; tests monkeypatch it to exercise FSDP
# placement on tiny models.
_MIN_FSDP_DIM = 1024


def _path_str(path: tuple) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif isinstance(p, tuple):
            parts.extend(str(q) for q in p)
        else:
            parts.append(str(p))
    return "/".join(parts)


def _tp_dim(path_s: str) -> Optional[int]:
    for pattern, dim in _TP_RULES:
        if re.match(pattern, path_s):
            return dim
    return None


def _largest_divisible_dim(shape: tuple, size: int, taken=()) -> Optional[int]:
    """Pick the largest dim divisible by ``size`` (excluding ``taken`` dims)."""
    if taken is None or isinstance(taken, int):
        taken = (taken,)
    best, best_len = None, 0
    for d, n in enumerate(shape):
        if d in taken:
            continue
        if n % size == 0 and n > best_len:
            best, best_len = d, n
    return best


def _quant_normalized_path(path_s: str, value: Any) -> str:
    """Alias a quant-node leaf ("{kernel}/q" or "{kernel}/scale") to its
    kernel's own path so the TP rules match quantized trees.

    "q" keeps the kernel's rank and sharding; "scale" has size 1 on the
    contraction dim, so divisibility checks at the call sites
    automatically replicate it for row-parallel kernels and shard it with
    the output channels for column-parallel ones. Gated on the quant-node
    layout so ordinary leaves that happen to be *named* scale (RMSNorm's
    param) are never aliased to their parent path.
    """
    if path_s.endswith("/q") and value.dtype == jnp.int8:
        return path_s[:-2]
    if path_s.endswith("/scale") and path_s.rsplit("/", 2)[-2] in (
            "kernel", "embed_tokens", "lm_head", "w1", "w2", "w3"):
        return path_s.rsplit("/", 1)[0]
    return path_s


def strategy_axes(path_s: str, shape: tuple, *, ep: int = 1, tp: int = 1,
                  fsdp: int = 1, dim_shift: int = 0,
                  taken: tuple = ()) -> dict:
    """THE shared EP/TP/FSDP placement rule for one (quant-normalized)
    param leaf: returns ``{dim: axis_name}``.

    ``dim_shift`` relocates the flat rules for stacked pipeline layouts
    (a leading layer dim shifts every flat dim by +1); ``taken`` marks
    dims already claimed (e.g. the stacked layout's 'pipe' dim 0) that
    FSDP must not grab. Both the flat ``param_pspec`` and the pipeline's
    ``pipeline_param_shardings`` call this one function, so the flat and
    pipelined layouts of a given strategy cannot drift apart.
    """
    out: dict = {}
    ep_d = None
    if (ep > 1 and _EP_PATTERN.match(path_s) and dim_shift < len(shape)
            and shape[dim_shift] % ep == 0):
        ep_d = dim_shift  # flat expert dim 0, shifted for stacked layouts
        out[ep_d] = "expert"
    tp_d = None
    if tp > 1:
        d = _tp_dim(path_s)
        if (d is not None and d + dim_shift < len(shape)
                and d + dim_shift != ep_d
                and shape[d + dim_shift] % tp == 0):
            tp_d = d + dim_shift
            out[tp_d] = "tensor"
    if fsdp > 1:
        d = _largest_divisible_dim(shape, fsdp, taken=taken + (tp_d, ep_d))
        if d is not None and shape[d] >= _MIN_FSDP_DIM:
            out[d] = "fsdp"
    return out


def param_pspec(path: tuple, value: Any, cfg: Config, mesh: Mesh) -> P:
    """PartitionSpec for one param leaf under the configured strategy."""
    shape = value.shape
    if len(shape) == 0:
        return P()
    # Weight-only int8 trees (serving) wrap each quantized kernel as
    # {"q": int8, "scale": fp32} — rules match on the kernel's own path.
    path_s = _quant_normalized_path(_path_str(path), value)
    spec: list = [None] * len(shape)
    fsdp_size = (mesh.shape["fsdp"]
                 if cfg.parallel.zero_stage == ZeROStage.ZERO3 else 1)
    for d, axis in strategy_axes(path_s, shape,
                                 ep=mesh.shape.get("expert", 1),
                                 tp=mesh.shape["tensor"],
                                 fsdp=fsdp_size).items():
        spec[d] = axis
    return P(*spec)


def _zero_opt_leaf_pspec(shape: tuple, axis: str, size: int) -> P:
    """Shard an optimizer-state leaf (ZeRO-1/2): largest divisible dim."""
    if len(shape) == 0 or size <= 1:
        return P()
    d = _largest_divisible_dim(shape, size)
    if d is None:
        return P()
    spec: list = [None] * len(shape)
    spec[d] = axis
    return P(*spec)


def _host_memory_kind(mesh: Mesh) -> Optional[str]:
    """"pinned_host" when the backend exposes it, else None (no offload)."""
    kinds = {m.kind for m in mesh.devices.flat[0].addressable_memories()}
    return "pinned_host" if "pinned_host" in kinds else None


def param_shardings(params: Any, cfg: Config, mesh: Mesh) -> Any:
    """Pytree of NamedShardings for the full param tree.

    With ``offload_params`` (ZeRO-3 CPU-offload parity,
    ``ds_config_zero3.json:24-27``) the frozen base params live in pinned
    host memory; ``make_sharded_train_step`` streams them into the step —
    as in-program host operands when the runtime supports it, else via
    boundary transfers. Trainable (LoRA) leaves always stay on device —
    they are updated every step.
    """
    host_kind = None
    if cfg.parallel.offload_params:
        if not cfg.lora.enabled:
            raise ValueError(
                "offload_params currently requires LoRA (it offloads the "
                "frozen base params; a full fine-tune has none)")
        host_kind = _host_memory_kind(mesh)

    def leaf(path, v):
        path_s = _path_str(path)
        kind = host_kind
        if kind is not None and ("lora_a" in path_s or "lora_b" in path_s):
            kind = None  # trainable leaves stay in HBM
        return NamedSharding(mesh, param_pspec(path, v, cfg, mesh),
                             memory_kind=kind)

    return jax.tree_util.tree_map_with_path(leaf, params)


def opt_state_shardings(opt_state: Any, cfg: Config, mesh: Mesh) -> Any:
    """Shardings for optimizer state (ZeRO-1/2/3 semantics).

    Shape-based: each array leaf is sharded on its largest divisible dim —
    over ``data`` for ZeRO-1/2, over ``fsdp`` for ZeRO-3; replicated for the
    baseline (the reference keeps the full optimizer on every rank). Scalars
    (step counts) are replicated.
    """
    stage = cfg.parallel.zero_stage
    if stage in (ZeROStage.ZERO1, ZeROStage.ZERO2):
        axis, size = "data", mesh.shape["data"]
    elif stage == ZeROStage.ZERO3:
        axis, size = "fsdp", mesh.shape["fsdp"]
    else:
        axis, size = "data", 1

    # ZeRO-3 CPU-offload parity (configs/ds_config_zero3.json:19-23): place
    # optimizer state in host memory; XLA streams it in for the update.
    memory_kind = None
    if cfg.parallel.offload_optimizer:
        memory_kind = _host_memory_kind(mesh)

    def leaf(v):
        if not hasattr(v, "shape"):
            return NamedSharding(mesh, P())
        # Scalars (step counts) stay on device: offloading them buys nothing
        # and scalar host-placement trips the SPMD partitioner.
        kind = memory_kind if len(v.shape) >= 1 else None
        return NamedSharding(
            mesh, _zero_opt_leaf_pspec(v.shape, axis, size), memory_kind=kind
        )

    return jax.tree_util.tree_map(leaf, opt_state)


def batch_pspec(cfg: Config) -> P:
    """Batch layout for (accum, micro_bs, seq): batch over data+fsdp,
    sequence over the SP axis."""
    seq_axis = "sequence" if cfg.parallel.sequence > 1 else None
    return P(None, BATCH_AXES, seq_axis)


def make_global_batch(batch: dict, cfg: Config, mesh: Mesh) -> dict:
    """Assemble per-host numpy batches into global jax.Arrays.

    On a multi-host pod each process holds only its slice of the global
    batch (``TokenBatchDataset`` shards rows per host); jit with global
    in_shardings requires global arrays. Single-process: pass through.
    """
    if jax.process_count() == 1:
        return batch
    sharding = NamedSharding(mesh, batch_pspec(cfg))
    return {
        k: jax.make_array_from_process_local_data(sharding, v)
        for k, v in batch.items()
    }


def state_shardings(state: TrainState, cfg: Config, mesh: Mesh) -> TrainState:
    """A TrainState-shaped pytree of NamedShardings."""
    p_sh = param_shardings(state.params, cfg, mesh)
    o_sh = opt_state_shardings(state.opt_state, cfg, mesh)
    repl = NamedSharding(mesh, P())
    scaler_sh = (jax.tree_util.tree_map(lambda _: repl, state.scaler)
                 if state.scaler is not None else None)
    return state.replace(
        step=repl, params=p_sh, opt_state=o_sh, scaler=scaler_sh
    )


def place_on_mesh(x, s):
    """Place one host-resident leaf onto a mesh sharding.

    Single-process: plain ``device_put``. Multi-process: assemble the
    global array from this process's local shards
    (``make_array_from_callback`` — the checkpoint store's restore
    placement) instead of ``device_put``, whose uncommitted-array path
    broadcasts every full value through ``multihost_utils.assert_equal``
    — hundreds of redundant gloo collectives for a replicated-init state
    (every process computed the identical value from the same seed), and
    on this image's CPU gloo they desynchronize and crash the pairs.
    """
    if not hasattr(x, "shape"):
        return x
    if jax.process_count() > 1:
        host = np.asarray(x)
        return jax.make_array_from_callback(
            host.shape, s, lambda idx: host[idx])
    return jax.device_put(x, s)


def launder_transfer_created(tree):
    """Multi-process placement products must be laundered before they can
    be DONATED into a compiled step: on this image's CPU jaxlib, donating
    a transfer-created array (``make_array_from_callback`` over host
    numpy) corrupts the process heap — the same root cause the
    checkpoint store's restore path works around (``store._launder``,
    where the full forensics live). Single-process trees pass through
    untouched (their leaves are executable outputs already)."""
    if jax.process_count() <= 1:
        return tree
    from dlti_tpu.checkpoint.store import _launder

    return _launder(tree)


def shard_train_state(state: TrainState, cfg: Config, mesh: Mesh) -> TrainState:
    """Place an (unsharded, host-resident) TrainState onto the mesh."""
    sh = state_shardings(state, cfg, mesh)
    return launder_transfer_created(
        jax.tree_util.tree_map(place_on_mesh, state, sh))


def make_sharded_train_step(
    model,
    state: TrainState,
    cfg: Config,
    mesh: Mesh,
    *,
    accum_steps: int = 1,
    donate: bool = True,
) -> Callable:
    """Jit the train step over the mesh with explicit in/out shardings.

    GSPMD inserts the ZeRO/TP collectives; XLA's latency-hiding scheduler
    overlaps them with compute (the analog of ``overlap_comm: true``,
    ``ds_config_zero1.json:38``).
    """
    from dlti_tpu.training.step import make_train_step

    if cfg.parallel.pipe > 1:
        raise ValueError(
            "make_sharded_train_step does not implement pipeline "
            "parallelism; with parallel.pipe > 1 use "
            "dlti_tpu.parallel.pipeline.make_pipeline_train_step (the GPipe "
            "schedule) — running this step on a pipe mesh would silently "
            "replicate all work across the pipe axis"
        )
    dp = mesh.shape["data"] * mesh.shape["fsdp"]
    if cfg.train.micro_batch_size % dp != 0:
        raise ValueError(
            f"global micro_batch_size={cfg.train.micro_batch_size} must be "
            f"divisible by the batch-sharding extent data*fsdp={dp}"
        )

    st_sh = state_shardings(state, cfg, mesh)
    b_sh = NamedSharding(mesh, batch_pspec(cfg))
    rng_sh = NamedSharding(mesh, P())

    grad_constraint = None
    if cfg.parallel.zero_stage in (ZeROStage.ZERO2, ZeROStage.ZERO3):
        # ZeRO-2 semantics: pin accumulated grads to the optimizer-state
        # layout so XLA reduce-scatters instead of all-reducing.
        axis = "data" if cfg.parallel.zero_stage == ZeROStage.ZERO2 else "fsdp"
        size = mesh.shape[axis]

        def grad_constraint(grads):
            return jax.tree_util.tree_map(
                lambda g: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, _zero_opt_leaf_pspec(g.shape, axis, size))
                ),
                grads,
            )

    def activation_constraint(input_ids):
        return jax.lax.with_sharding_constraint(
            input_ids, NamedSharding(mesh, P(BATCH_AXES,
                                             "sequence" if cfg.parallel.sequence > 1 else None))
        )

    if cfg.train.loss_chunk and cfg.parallel.sequence > 1:
        raise ValueError(
            "train.loss_chunk does not compose with sequence parallelism "
            "(the chunk reshape would regather the 'sequence'-sharded "
            "activations); set loss_chunk=0")
    step_fn = make_train_step(
        model,
        accum_steps=accum_steps,
        sharding_constraint=activation_constraint,
        grad_constraint=grad_constraint,
        fp16_scale_window=cfg.train.fp16_scale_window,
        fp16_min_scale=cfg.train.fp16_min_scale,
        fp16_hysteresis=cfg.train.fp16_hysteresis,
        loss_chunk=cfg.train.loss_chunk,
    )

    # Host offload (ds_config_zero3.json:19-27 parity): offloaded leaves
    # *rest* in pinned host memory (st_sh carries memory kinds).
    has_offload = any(
        getattr(s, "memory_kind", None) == "pinned_host"
        for s in jax.tree_util.tree_leaves(st_sh))
    st_sh_dev = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s.spec) if isinstance(s, NamedSharding) else s,
        st_sh)

    # Every batch field (input_ids/loss_mask/segment_ids/positions) shares
    # the (accum, batch, seq) layout; a prefix pytree applies b_sh to all.
    jitted = jax.jit(
        step_fn,
        in_shardings=(st_sh_dev, b_sh, rng_sh),
        out_shardings=(st_sh_dev, NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else (),
    )
    if not has_offload:
        return jitted

    frozen_offloaded = any(
        getattr(s, "memory_kind", None) == "pinned_host"
        for s in jax.tree_util.tree_leaves(st_sh.params))
    if frozen_offloaded and _supports_host_compute_inputs(mesh):
        # Per-layer streaming (the DeepSpeed per-layer paging analog,
        # ds_config_zero3.json:19-27): the frozen base params enter the
        # jitted program AS host-memory operands and are excluded from its
        # outputs, so XLA's latency-hiding scheduler streams each weight
        # HBM-ward at its use point inside the step and frees it after —
        # peak HBM holds the trainable/optimizer leaves plus the layers in
        # flight, never the whole frozen tree. Trainable leaves stay
        # device-resident across steps (no boundary transfers at all).
        return _make_streaming_offload_step(
            step_fn, cfg, mesh, st_sh, st_sh_dev, b_sh, rng_sh, donate)

    # Fallback (runtime without host-compute operands, or only the
    # *optimizer* is offloaded): step-boundary transfer via the ONE
    # shared wrapper (also the pipe path's offload mode) — HBM holds
    # offloaded tensors only for the duration of a step. The wrapper
    # derives shardings from ``state``'s actual placement, so it must be
    # the PLACED state (every caller passes the shard_train_state
    # output).
    return wrap_boundary_offload(jitted, state, mesh, cfg.lora.enabled)


def wrap_boundary_offload(step_fn, state, mesh: Mesh, lora_enabled: bool):
    """Step-boundary host-offload fallback for layouts that cannot
    stream in-step (the pipe path; flat layouts use
    ``make_sharded_train_step``'s own wrapper): derive host/device
    shardings from the PLACED state, move offloaded leaves HBM-ward for
    the step's duration, splice the still-valid host frozen-param copies
    back after (they never change — half the DMA traffic for LoRA).

    Returns ``step_fn`` unchanged when nothing actually rests in host
    memory (backend without pinned_host, or offload disabled): wrapping
    anyway would splice back frozen buffers the step's donation already
    invalidated ("Array has been deleted" on step 2).
    """
    from dlti_tpu.training.state import combine_params, partition_params

    def shardings(tree):
        return jax.tree_util.tree_map(
            lambda x: x.sharding if hasattr(x, "sharding") else x, tree)

    opt_host = shardings(state.opt_state)
    par_host = shardings(state.params)

    def on_host(tree):
        return any(getattr(s, "memory_kind", None) == "pinned_host"
                   for s in jax.tree_util.tree_leaves(tree)
                   if isinstance(s, NamedSharding))

    params_offloaded = on_host(par_host)
    if not params_offloaded and not on_host(opt_host):
        return step_fn

    def dev(tree):
        return jax.tree_util.tree_map(
            lambda s: (NamedSharding(mesh, s.spec)
                       if isinstance(s, NamedSharding) else s), tree)

    opt_dev, par_dev = dev(opt_host), dev(par_host)

    def wrapped(st, batch, rng):
        host_state = st
        st = st.replace(
            opt_state=jax.device_put(st.opt_state, opt_dev),
            params=jax.device_put(st.params, par_dev),
        )
        new_state, m = step_fn(st, batch, rng)
        new_params = new_state.params
        if params_offloaded:
            t_new, _ = partition_params(new_params, lora_enabled)
            _, f_host = partition_params(host_state.params, lora_enabled)
            new_params = combine_params(t_new, f_host)
        return new_state.replace(
            opt_state=jax.device_put(new_state.opt_state, opt_host),
            params=new_params,
        ), m

    if params_offloaded:
        # The device param shardings double as the eval-side shim input
        # (eval feeds params into the same pipe shard_map, which cannot
        # take pinned_host stage-sharded operands).
        wrapped.params_dev_shardings = par_dev
    return wrapped


_HOST_COMPUTE_PROBE_CACHE: dict = {}


def _supports_host_compute_inputs(mesh: Mesh) -> bool:
    """Probe: can a jitted program take pinned-host operands into device
    compute? (XLA host-memory-space operands; needed for in-step weight
    streaming; degrade to boundary transfers when absent.)

    Probes BOTH a replicated and a mesh-sharded host operand — the real
    frozen tree contains both kinds, and SPMD-partitioner support for the
    placement annotation has differed between them in past XLA versions.
    The answer is a property of the backend + mesh shape, so it is cached.
    """
    key = (jax.default_backend(), tuple(sorted(mesh.shape.items())))
    if key in _HOST_COMPUTE_PROBE_CACHE:
        return _HOST_COMPUTE_PROBE_CACHE[key]

    def probe(spec, rows) -> None:
        host = NamedSharding(mesh, spec, memory_kind="pinned_host")
        dev = NamedSharding(mesh, spec, memory_kind="device")
        x = jax.device_put(jnp.ones((rows, 16), jnp.float32), host)
        # The exact streaming pattern: host operand, explicit in-program
        # move to device space, then compute.
        f = jax.jit(lambda a: jax.device_put(a, dev) * 2.0,
                    in_shardings=host, out_shardings=NamedSharding(mesh, spec))
        jax.block_until_ready(f(x))

    try:
        probe(P(), 16)
        sharded_axes = [ax for ax, n in mesh.shape.items() if n > 1]
        if sharded_axes:
            ax = sharded_axes[0]
            # Rows sized to the axis so the shard is never ragged.
            probe(P(ax), 8 * mesh.shape[ax])
        ok = True
    except Exception as e:  # noqa: BLE001 — the compiler's refusal IS the answer
        # Said out loud: it decides between in-step weight streaming and
        # boundary transfers, and a refusal on a chip is worth reading.
        get_logger().warning(
            "host-memory operands in compiled programs: refused on %s mesh "
            "%s (%s: %s); offload falls back to boundary transfers",
            key[0], dict(mesh.shape), type(e).__name__, str(e)[:300])
        ok = False
    else:
        get_logger().info(
            "host-memory operands in compiled programs: supported on %s "
            "mesh %s", key[0], dict(mesh.shape))
    _HOST_COMPUTE_PROBE_CACHE[key] = ok
    return ok


def _make_streaming_offload_step(step_fn, cfg: Config, mesh: Mesh, st_sh,
                                 st_sh_dev, b_sh, rng_sh, donate: bool):
    """Build the in-step streaming wrapper: frozen params are host operands
    of the compiled program; outputs cover only the dynamic state."""
    from dlti_tpu.training.state import combine_params, partition_params

    lora = cfg.lora.enabled

    def split(tree_state):
        tr, fr = partition_params(tree_state.params, lora)
        return tree_state.replace(params=tr), fr

    dyn_sh, frozen_sh = split(st_sh)
    dyn_sh_dev, frozen_sh_dev = split(st_sh_dev)
    frozen_dev_kind = {
        k: NamedSharding(mesh, s.spec, memory_kind="device")
        for k, s in frozen_sh_dev.items()
    }

    def run(dyn, frozen, batch, rng):
        # Explicit per-leaf host->device moves: ops cannot mix memory
        # spaces, so each frozen weight gets a copy op the latency-hiding
        # scheduler places near (and overlaps with) its first use.
        frozen = {k: jax.device_put(v, frozen_dev_kind[k])
                  for k, v in frozen.items()}
        state = dyn.replace(params=combine_params(dyn.params, frozen))
        new_state, metrics = step_fn(state, batch, rng)
        t_new, _ = partition_params(new_state.params, lora)
        return new_state.replace(params=t_new), metrics

    jitted = jax.jit(
        run,
        # Frozen params enter in pinned host memory and are not outputs.
        # The dynamic part (trainable params + optimizer state) is
        # device-in/device-out: host-memory *outputs* are what the SPMD
        # partitioner cannot handle, so offloaded optimizer leaves rest on
        # host between steps via the boundary transfers below (tiny for a
        # LoRA run — the 14 GB frozen tree is what streams in-step).
        in_shardings=(dyn_sh_dev, frozen_sh, b_sh, rng_sh),
        out_shardings=(dyn_sh_dev, NamedSharding(mesh, P())),
        donate_argnums=(0,) if donate else (),
    )

    def step_streaming(state, batch, rng):
        dyn, frozen = split(state)
        dyn = jax.device_put(dyn, dyn_sh_dev)      # no-op unless opt offloaded
        new_dyn, metrics = jitted(dyn, frozen, batch, rng)
        new_dyn = jax.device_put(new_dyn, dyn_sh)  # opt leaves back to host
        # Reattach the untouched host-resident frozen arrays — no copies.
        return new_dyn.replace(
            params=combine_params(new_dyn.params, frozen)), metrics

    return step_streaming
