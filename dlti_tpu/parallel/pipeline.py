"""Pipeline parallelism: GPipe microbatch schedule over the ``pipe`` axis.

The reference has no pipeline parallelism (SURVEY.md §2c: "PP: No"); this
is the TPU-native extension: the transformer block stack is split into
``pipe`` contiguous stages, each device holds ``num_layers / pipe`` layers,
and microbatches flow through the stages with ``lax.ppermute`` moving
activations stage-to-stage over ICI — the collective-permute pipelining
pattern (scaling-book) rather than host-driven stage processes.

Layout: the per-layer param subtrees of the standard model tree
(``model.layers_{i}``) are stacked into one tree with a leading layer dim
(:func:`to_pipeline_params`), sharded over ``pipe``. Embeddings / final
norm / LM head are replicated and applied outside the pipelined region
(they are a few percent of FLOPs; sharding them rides the ``tensor`` axis
when combined with TP).

Schedule (plain GPipe): with ``P`` stages and ``M`` microbatches, run
``M + P - 1`` ticks; at tick ``t`` stage 0 ingests microbatch ``t`` (while
``t < M``), every stage applies its local layers, and activations
ppermute to the next stage. The last stage's outputs for ticks
``P-1 .. M+P-2`` are microbatch ``0 .. M-1``. Bubble fraction is
``(P-1)/(M+P-1)`` — pick ``M >= 4*P`` for >80% utilization.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlti_tpu.config import Config, LoRAConfig, ModelConfig
from dlti_tpu.models.llama import LlamaBlock, RMSNorm, _dtype, _remat_policy
from dlti_tpu.ops.rope import rope_frequencies


# ----------------------------------------------------------------------
# Param layout: standard tree <-> pipeline (stacked-layer) tree
# ----------------------------------------------------------------------

def to_pipeline_params(params: dict, num_layers: int) -> dict:
    """Standard param tree -> pipeline layout.

    ``model.layers_{i}`` subtrees stack into ``layers`` with a leading
    layer dim; embed/final-norm/lm-head stay as-is.
    """
    model = params["model"]
    layer_trees = [model[f"layers_{i}"] for i in range(num_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layer_trees)
    out = {
        "embed_tokens": model["embed_tokens"],
        "layers": stacked,
        "final_norm": model["final_norm"],
    }
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]
    return out


def from_pipeline_params(pparams: dict, num_layers: int) -> dict:
    """Inverse of :func:`to_pipeline_params`."""
    model: dict = {
        "embed_tokens": pparams["embed_tokens"],
        "final_norm": pparams["final_norm"],
    }
    for i in range(num_layers):
        model[f"layers_{i}"] = jax.tree_util.tree_map(
            lambda x: x[i], pparams["layers"])
    out = {"model": model}
    if "lm_head" in pparams:
        out["lm_head"] = pparams["lm_head"]
    return out


def pipeline_param_shardings(pparams: dict, mesh: Mesh) -> dict:
    """Stacked layers sharded over ``pipe`` on the layer dim; rest replicated.

    When the mesh also has ``tensor`` > 1 (PP x TP), each stacked leaf
    additionally shards over ``tensor`` on the same dim the training TP
    rules use (shifted +1 for the leading layer dim): stage-internal
    tensor parallelism. The ``tensor`` axis stays a GSPMD *auto* axis
    inside the pipeline's shard_map (see :func:`pipeline_forward`), so XLA
    partitions the block math and inserts the TP collectives.

    ``embed_tokens`` / ``lm_head`` follow the flat-TP vocab rules too
    (rows / cols over ``tensor``): the embed lookup and the (b, s, vocab)
    fp32 head einsum sit *outside* the pipe shard_map as ordinary GSPMD
    ops, so sharding the leaves is all it takes for XLA to partition the
    largest single matmul instead of replicating it per device (r04
    advisor finding).

    With ``fsdp`` > 1 (PP x ZeRO-3), each leaf additionally shards over
    ``fsdp`` on its largest remaining divisible dim (same rule + size
    floor as the flat ZeRO-3 path, ``sharding.param_pspec``). ``fsdp``
    rides as a GSPMD auto axis exactly like ``tensor``: XLA all-gathers a
    stage's layer shard at its use point inside the tick and
    reduce-scatters grads — per-stage FSDP, so a stage holds
    layers_per_stage/fsdp params at rest instead of a full layer shard.

    With ``expert`` > 1 (PP x EP), stacked MoE expert weights shard over
    ``expert`` on the expert dim (flat dim 0 -> stacked dim 1, the flat
    ``_EP_PATTERN`` rule shifted) — expert parallelism inside each
    pipeline stage, dispatch all-to-all inserted by GSPMD.
    """
    tp = mesh.shape.get("tensor", 1)
    fsdp = mesh.shape.get("fsdp", 1)
    ep = mesh.shape.get("expert", 1)

    def leaf(prefix, dim_shift, lead_axis):
        """One EP/TP/FSDP-rule lookup for both layouts: stacked layers
        (dim_shift=1 for the leading 'pipe'-sharded layer dim) and
        top-level leaves (dim_shift=0, path prefixed with the tree key so
        the flat rules match). Delegates to the ONE shared placement rule
        (``sharding.strategy_axes``) so the flat and pipelined layouts of
        a strategy cannot drift apart."""
        def f(path, v):
            from dlti_tpu.parallel.sharding import (
                _path_str, _quant_normalized_path, strategy_axes,
            )

            spec = [None] * v.ndim
            if lead_axis:
                spec[0] = lead_axis
            # int8 trees: alias {kernel}/q and {kernel}/scale to the
            # kernel's path so quantized weights shard too (scale's
            # size-1 contraction dim auto-replicates via the divisibility
            # checks inside strategy_axes).
            p = _quant_normalized_path(
                "/".join(x for x in (prefix, _path_str(path)) if x), v)
            for d, axis in strategy_axes(
                    p, v.shape, ep=ep, tp=tp, fsdp=fsdp,
                    dim_shift=dim_shift,
                    taken=(0,) if lead_axis else ()).items():
                spec[d] = axis
            return NamedSharding(mesh, P(*spec))
        return f

    return {
        k: jax.tree_util.tree_map_with_path(
            leaf("", 1, "pipe") if k == "layers" else leaf(k, 0, None), v)
        for k, v in pparams.items()
    }


# ----------------------------------------------------------------------
# Pipelined forward
# ----------------------------------------------------------------------

def pipeline_forward(
    pparams: dict,
    input_ids: jnp.ndarray,
    cfg: ModelConfig,
    mesh: Mesh,
    *,
    lora: Optional[LoRAConfig] = None,
    num_microbatches: int = 4,
    positions: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    deterministic: bool = True,
    dropout_rng: Optional[jax.Array] = None,
    return_hidden: bool = False,
    token_mask: Optional[jnp.ndarray] = None,
    return_aux: bool = False,
) -> jnp.ndarray:
    """Run the full model with the block stack pipelined over ``pipe``.

    ``return_aux``: additionally return the per-microbatch router
    aux-loss sums, shape (num_microbatches,) — MoE models only.
    ``token_mask`` (b, s): keeps padding tokens out of expert capacity
    (packed batches derive it from ``segment_ids`` instead).

    ``input_ids``: (batch, seq); batch must divide by ``num_microbatches``.
    Returns float32 logits (batch, seq, vocab) — the same function as
    ``LlamaForCausalLM.apply`` on the equivalent unstacked params.
    """
    num_stages = mesh.shape["pipe"]
    if cfg.num_layers % num_stages != 0:
        raise ValueError(f"num_layers={cfg.num_layers} must divide into "
                         f"pipe={num_stages} stages")
    moe = cfg.num_experts > 0
    b, s = input_ids.shape
    if b % num_microbatches != 0:
        raise ValueError(f"batch={b} must divide by microbatches={num_microbatches}")
    mb = b // num_microbatches
    dtype = _dtype(cfg.dtype)

    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
    # Cover the actual sequence even past the preset's design length
    # (same fix as models/llama.py: positions >= table length hit
    # jnp.take's NaN fill and training silently NaNs).
    table_len = max(cfg.max_seq_len, s)
    # Trace-time guard: apply_rope clip-gathers, so an
    # under-sized table would silently clamp angles — fail the trace here
    # where the max position (< s) is statically known.
    from dlti_tpu.ops.rope import assert_rope_table_covers

    assert_rope_table_covers(table_len, s, "pipeline forward")
    cos, sin = rope_frequencies(cfg.resolved_head_dim, table_len,
                                cfg.rope_theta)

    # Embed outside the pipelined region (replicated). int8 frozen-base
    # trees quantize the embedding too — gather int8 ROWS then scale
    # (models/llama.py's lookup path): only (b*s, hidden) expands, never
    # the whole (vocab, hidden) matrix in fp.
    from dlti_tpu.models.quantization import is_quant_node, maybe_dequantize

    emb = pparams["embed_tokens"]
    if is_quant_node(emb):
        x = emb["q"][input_ids].astype(dtype) * emb["scale"].astype(dtype)
    else:
        x = jnp.take(emb, input_ids, axis=0).astype(dtype)
    if cfg.embedding_scale:  # Gemma: embeddings scaled by sqrt(hidden)
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, dtype)
    x_mb = x.reshape(num_microbatches, mb, s, -1)
    pos_mb = positions.reshape(num_microbatches, mb, s)
    # PP x DP / PP x ZeRO-3: batch rows shard over 'data' and 'fsdp'
    # (both carry batch, as in the flat batch_pspec) as auto axes inside
    # the shard_map. PP x SP: the sequence dim additionally shards over
    # 'sequence' — inside the stages, ring_attention delegates to
    # reference_attention and GSPMD partitions it over the auto
    # 'sequence' axis (see ring_attention's nested-delegation comment).
    row_axes = tuple(a for a in ("data", "fsdp")
                     if mesh.shape.get(a, 1) > 1) or None
    seq_ax = "sequence" if mesh.shape.get("sequence", 1) > 1 else None
    if row_axes or seq_ax:
        # Keep each microbatch row-sharded. Without the constraint the
        # (b, s) -> (M, mb, s) reshape migrates the batch sharding onto
        # the microbatch index M, and the tick loop's x_mb[m] gathers.
        x_mb = jax.lax.with_sharding_constraint(
            x_mb, NamedSharding(mesh, P(None, row_axes, seq_ax, None)))
        pos_mb = jax.lax.with_sharding_constraint(
            pos_mb, NamedSharding(mesh, P(None, row_axes, seq_ax)))
    # Packed batches: segment ids travel with their microbatch so each
    # stage applies the same intra-doc attention mask the unpipelined
    # model would. A zero array means "one segment" (mask is a no-op) and
    # keeps the scanned stage body shape-stable either way.
    seg_mb = (segment_ids.reshape(num_microbatches, mb, s)
              if segment_ids is not None else None)
    if seg_mb is not None and (row_axes or seq_ax):
        # Same row-sharding pin as x_mb/pos_mb above: without it the
        # reshape migrates the batch sharding onto the microbatch index
        # and every tick's seg_mb[m] gathers across the batch axes.
        seg_mb = jax.lax.with_sharding_constraint(
            seg_mb, NamedSharding(mesh, P(None, row_axes, seq_ax)))

    # Pass the mesh: MoE's expert-dispatch constraint (moe.py
    # _expert_constraint) pins the (E, C, h) dispatched activations to
    # the 'expert' axis — legal inside the pipe shard_map because
    # 'expert' stays a GSPMD auto axis there, and a no-op on dense
    # models / expert==1 meshes. Without it, PP x EP would leave the
    # token->expert all-to-all placement to unpinned propagation.
    block = LlamaBlock(cfg, lora, mesh)

    layers_per_stage = cfg.num_layers // num_stages

    def apply_stage(layer_params, x, pos, seg, tm, rng):
        """Apply this stage's local layers (leading dim = layers/stage).

        Returns (x, aux_sum) — aux_sum is the stage's summed router
        aux losses (0 for dense models)."""
        def body(carry, layer_with_idx):
            h = carry
            one_layer, layer_idx = layer_with_idx
            # Distinct dropout mask per layer (the unpipelined model's
            # layers_{i} module paths fold distinct keys).
            rngs = ({"dropout": jax.random.fold_in(rng, layer_idx)}
                    if not deterministic else None)
            if moe:
                # Collect each MoE layer's sown load-balance loss.
                (out, _), variables = block.apply(
                    {"params": one_layer}, h, cos, sin, pos,
                    seg, None, deterministic, token_mask=tm, rngs=rngs,
                    mutable=["intermediates"])
                from dlti_tpu.models.moe import collect_aux_loss

                aux = collect_aux_loss(variables.get("intermediates", {}))
            else:
                out, _ = block.apply({"params": one_layer}, h, cos, sin, pos,
                                     seg, None, deterministic, rngs=rngs)
                aux = jnp.float32(0.0)
            return out, aux

        stride = cfg.remat_stride if cfg.remat else 0
        if cfg.remat and stride > 1 and layers_per_stage % stride == 0:
            # Selective remat under pipe (flat-path remat_stride parity):
            # scan over GROUPS of `stride` layers, rematting all but the
            # last in each group — every stride-th block keeps its
            # activations, trading ~1/stride of the backward recompute
            # for that fraction of saved activations per stage. Numerics
            # identical (remat changes only what the backward recomputes).
            fn = jax.checkpoint(body, policy=_remat_policy(cfg.remat_policy))

            def group_fn(carry, group):
                params_g, idx_g = group
                h = carry
                aux_sum = jnp.float32(0.0)
                for j in range(stride):  # static unroll within the group
                    layer_j = jax.tree_util.tree_map(
                        lambda v: v[j], params_g)
                    apply_j = body if j == stride - 1 else fn
                    h, aux = apply_j(h, (layer_j, idx_g[j]))
                    aux_sum = aux_sum + aux
                return h, aux_sum

            grouped = (
                jax.tree_util.tree_map(
                    lambda v: v.reshape(
                        (layers_per_stage // stride, stride) + v.shape[1:]),
                    layer_params),
                jnp.arange(layers_per_stage).reshape(-1, stride),
            )
            x, aux_groups = jax.lax.scan(group_fn, x, grouped)
            return x, jnp.sum(aux_groups)
        if cfg.remat:
            # Same policy table as the flat path (llama._remat_policy):
            # the int8/no-remat bench winner aside, 7B-class PP runs need
            # dots_saveable/save_attn_out to fit activations per stage.
            fn = jax.checkpoint(body, policy=_remat_policy(cfg.remat_policy))
        else:
            fn = body
        x, aux_layers = jax.lax.scan(
            fn, x, (layer_params, jnp.arange(layers_per_stage)))
        return x, jnp.sum(aux_layers)

    num_ticks = num_microbatches + num_stages - 1
    perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]

    @functools.partial(
        shard_map, mesh=mesh,
        # Only 'pipe' is manual: every other mesh axis (notably 'tensor')
        # stays a GSPMD auto axis, so stacked-layer leaves that carry a
        # 'tensor' sharding (pipeline_param_shardings under PP x TP) keep
        # it inside the body and XLA partitions the stage's block math +
        # inserts the row/column-parallel collectives.
        axis_names=frozenset({"pipe"}),
        in_specs=(jax.tree_util.tree_map(lambda _: P("pipe"), pparams["layers"]),
                  P(), P(), P(), P(), P()),
        out_specs=(P(), P()),
        # check_vma stays ON for every composition (incl. PP x SP, whose
        # nested ring passes the checker via explicit pcasts in
        # ring_attention_local): disabling it makes the shard_map
        # transpose skip the psum for replicated inputs — gradients come
        # out silently wrong.
    )
    def run_pipeline(local_layers, x_mb, pos_mb, seg_mb, tm_mb, rng):
        # Inside: one pipeline stage per device along 'pipe'.
        stage = jax.lax.axis_index("pipe")
        # Initial carries must be device-varying for the scan's carry type
        # to be stable (they become varying after the first ppermute).
        buf = jax.lax.pcast(jnp.zeros_like(x_mb[0]), "pipe", to="varying")
        outputs = jax.lax.pcast(jnp.zeros_like(x_mb), "pipe", to="varying")
        aux_vec = jax.lax.pcast(
            jnp.zeros((num_microbatches,), jnp.float32), "pipe", to="varying")

        def tick(carry, t):
            buf, outputs, aux_vec = carry
            m_in = jnp.clip(t, 0, num_microbatches - 1)
            inp = jnp.where(stage == 0, x_mb[m_in], buf)
            # Positions for the microbatch this stage is processing at tick
            # t: stage k works on microbatch t - k.
            m_here = jnp.clip(t - stage, 0, num_microbatches - 1)
            pos = pos_mb[m_here]
            seg = seg_mb[m_here] if segment_ids is not None else None
            tm = tm_mb[m_here] if moe else None
            # Fold the stage in as well: stage k's layers are globally
            # layers k*K..(k+1)*K-1, so masks differ across stages too.
            out, aux = apply_stage(local_layers, inp, pos, seg, tm,
                                   jax.random.fold_in(
                                       jax.random.fold_in(rng, t), stage))
            # Edge ticks (pipeline fill/drain) recompute a clipped
            # microbatch; their aux must not double-count.
            valid = ((t - stage >= 0)
                     & (t - stage < num_microbatches)).astype(jnp.float32)
            aux_vec = aux_vec + jax.nn.one_hot(
                m_here, num_microbatches, dtype=jnp.float32) * aux * valid
            # Last stage finished microbatch t - (P-1) at this tick.
            m_out = t - (num_stages - 1)
            write = (stage == num_stages - 1) & (m_out >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                outputs, out, jnp.maximum(m_out, 0), 0)
            outputs = jnp.where(write, updated, outputs)
            buf = jax.lax.ppermute(out, "pipe", perm)
            return (buf, outputs, aux_vec), None

        (buf, outputs, aux_vec), _ = jax.lax.scan(
            tick, (buf, outputs, aux_vec), jnp.arange(num_ticks))
        # Only the last stage holds real outputs; broadcast to every stage
        # (psum over the one-hot mask — a pipe-axis all-reduce on ICI).
        # aux: every stage holds ITS layers' contribution — psum is the
        # sum over the whole layer stack.
        mask = (stage == num_stages - 1).astype(outputs.dtype)
        return (jax.lax.psum(outputs * mask, "pipe"),
                jax.lax.psum(aux_vec, "pipe"))

    rng_arg = (dropout_rng if dropout_rng is not None
               else jax.random.PRNGKey(0))  # unused when deterministic
    seg_arg = (seg_mb if seg_mb is not None
               else jnp.zeros((num_microbatches, mb, s), jnp.int32))
    if moe and token_mask is None and segment_ids is not None:
        token_mask = (segment_ids != 0).astype(jnp.int32)  # packed: 0 = pad
    tm_arg = (token_mask.reshape(num_microbatches, mb, s)
              if (moe and token_mask is not None)
              else jnp.ones((num_microbatches, mb, s), jnp.int32))
    if moe and token_mask is not None and (row_axes or seq_ax):
        # Same row-sharding pin as x_mb/pos_mb/seg_mb above: without it
        # the (b, s) -> (M, mb, s) reshape migrates the batch sharding
        # onto the microbatch index and every tick's tm_mb[m] gathers.
        tm_arg = jax.lax.with_sharding_constraint(
            tm_arg, NamedSharding(mesh, P(None, row_axes, seq_ax)))
    y, aux_vec = run_pipeline(pparams["layers"], x_mb, pos_mb, seg_arg,
                              tm_arg, rng_arg)
    y = y.reshape(b, s, -1)

    # Final norm + head outside the pipeline (replicated).
    norm = RMSNorm(cfg.rms_norm_eps, offset=cfg.rmsnorm_offset)
    y = norm.apply({"params": pparams["final_norm"]}, y)
    if return_hidden:
        # Sequence-chunked loss path: the caller applies the head per
        # chunk (pipeline_head_matrix) so full fp32 logits never sit in
        # HBM — the loss_chunk contract of training.step.
        return (y, aux_vec) if return_aux else y
    if cfg.tie_embeddings or "lm_head" not in pparams:
        # fp32 dequant for the tied head (llama.py head_matrix parity:
        # int8 -> fp32 directly, not via the lookup dtype).
        tied = maybe_dequantize(pparams["embed_tokens"], jnp.float32,
                                anchor=y)
        logits = jnp.einsum("bsh,vh->bsv", y.astype(jnp.float32),
                            jnp.asarray(tied, jnp.float32))
    else:
        lm_head = maybe_dequantize(pparams["lm_head"], y.dtype, anchor=y)
        logits = jnp.dot(y, lm_head.astype(y.dtype),
                         preferred_element_type=jnp.float32)
    logits = logits.astype(jnp.float32)
    return (logits, aux_vec) if return_aux else logits


def pipeline_head_matrix(pparams: dict, cfg: ModelConfig, anchor) -> jnp.ndarray:
    """The (hidden, vocab) head as an explicit matrix on pipeline-layout
    params — the input to ``chunked_causal_lm_loss``. Delegates to the
    ONE shared head contract (``models.llama.head_matrix_from_leaves``)
    so the flat and pipelined chunked paths cannot desynchronize."""
    from dlti_tpu.models.llama import head_matrix_from_leaves

    return head_matrix_from_leaves(
        pparams["embed_tokens"], pparams.get("lm_head"),
        cfg.tie_embeddings, anchor)


def to_pipeline_state(state, num_layers: int):
    """Convert a fresh TrainState to pipeline layout.

    Re-initializes optimizer state over the stacked trainable tree, so use
    at step 0 (converting mid-run would discard Adam moments).
    """
    from dlti_tpu.training.state import partition_params

    pparams = to_pipeline_params(state.params, num_layers)
    trainable, _ = partition_params(pparams, state.lora_enabled)
    return state.replace(params=pparams, opt_state=state.tx.init(trainable))


# ----------------------------------------------------------------------
# Pipelined train step
# ----------------------------------------------------------------------

def make_pipeline_train_step(
    cfg: Config,
    tx,
    mesh: Mesh,
    *,
    num_microbatches: int = 4,
) -> Callable:
    """Build ``step(state, batch, rng) -> (state, metrics)`` where
    ``state.params`` is in *pipeline layout* (see :func:`to_pipeline_params`).

    The loss/optimizer semantics match ``make_train_step`` (token-mean
    causal-LM loss, trainable-subset grads); grad accumulation happens
    through the microbatch schedule itself.
    """
    import optax

    from dlti_tpu.training.state import combine_params, partition_params
    from dlti_tpu.training.step import causal_lm_loss

    layers_per_stage = cfg.model.num_layers // mesh.shape["pipe"]
    if (cfg.model.remat and cfg.model.remat_stride > 1
            and layers_per_stage % cfg.model.remat_stride != 0):
        from dlti_tpu.utils.logging import get_logger

        # Selective remat scans layer GROUPS of `stride`; a stride that
        # does not divide the per-stage layer count cannot group evenly,
        # so every scanned layer remats (plain jax.checkpoint).
        get_logger().warning(
            "remat_stride=%d does not divide layers_per_stage=%d under "
            "pipe=%d; every block remats",
            cfg.model.remat_stride, layers_per_stage, mesh.shape["pipe"])

    lora = cfg.lora if cfg.lora.enabled else None

    loss_chunk = int(cfg.train.loss_chunk or 0)
    moe_coef = (cfg.model.router_aux_loss_coef
                if cfg.model.num_experts > 0 else 0.0)
    if loss_chunk and moe_coef:
        raise ValueError(
            "loss_chunk does not compose with MoE aux-loss collection; "
            "set train.loss_chunk=0 for MoE models")

    def loss_fn(trainable, frozen, batch, rng):
        pparams = combine_params(trainable, frozen)
        loss_mask = batch.get("loss_mask")
        # Unpacked MoE: loss_mask IS the padding mask — keep padding out
        # of expert capacity/aux stats (flat-step parity). Packed batches
        # derive the mask from segment_ids inside pipeline_forward.
        tm = (loss_mask if (moe_coef and loss_mask is not None
                            and batch.get("segment_ids") is None) else None)
        out = pipeline_forward(
            pparams, batch["input_ids"], cfg.model, mesh, lora=lora,
            num_microbatches=num_microbatches,
            positions=batch.get("positions"),
            segment_ids=batch.get("segment_ids"),
            deterministic=False, dropout_rng=rng,
            return_hidden=bool(loss_chunk),
            token_mask=tm, return_aux=bool(moe_coef),
        )
        aux_vec = None
        if moe_coef:
            out, aux_vec = out
        if loss_chunk:
            from dlti_tpu.training.step import chunked_causal_lm_loss

            loss_sum, n_tok = chunked_causal_lm_loss(
                out, pipeline_head_matrix(pparams, cfg.model, out),
                batch["input_ids"], loss_mask, loss_chunk)
        else:
            loss_sum, n_tok = causal_lm_loss(
                out, batch["input_ids"], loss_mask)
        n_tok = jnp.maximum(n_tok, 1.0)
        aux_weighted = jnp.float32(0.0)
        if moe_coef:
            # Flat-step parity: each microbatch's aux weighted by its own
            # token count, so the objective equals the grad-accum loop's
            # sum of (loss_sum_m + coef * aux_m * n_tok_m), all / n_tok.
            b, s = batch["input_ids"].shape
            mask = (loss_mask if loss_mask is not None
                    else jnp.ones((b, s), jnp.int32))
            # The flat step weights aux_m by the microbatch's CE token
            # count — the SHIFTED mask (targets are input_ids[:, 1:]).
            n_tok_m = jnp.sum(
                mask.reshape(num_microbatches, -1, s)[:, :, 1:]
                .astype(jnp.float32), axis=(1, 2))
            aux_weighted = jnp.sum(aux_vec * n_tok_m)
        objective = (loss_sum + moe_coef * aux_weighted) / n_tok
        ce_mean = loss_sum / n_tok
        return objective, (ce_mean, aux_weighted / n_tok, n_tok)

    # PP x ZeRO-2/3: pin trainable grads to the optimizer-state layout
    # (sharded over 'data' for ZeRO-2, 'fsdp' for ZeRO-3) so XLA
    # reduce-scatters instead of all-reducing — the same constraint the
    # flat path applies in make_sharded_train_step.
    zstage = int(cfg.parallel.zero_stage)
    if zstage >= 3 and mesh.shape.get("fsdp", 1) > 1:
        pin_axis, pin_size = "fsdp", mesh.shape["fsdp"]
    elif zstage == 2 and mesh.shape.get("data", 1) > 1:
        pin_axis, pin_size = "data", mesh.shape["data"]
    else:
        pin_axis, pin_size = None, 1
    use_grad_pin = pin_axis is not None

    def step(state, batch, rng):
        trainable, frozen = state.trainable_and_frozen()
        loss_scale = (state.scaler["scale"] if state.scaler is not None
                      else jnp.float32(1.0))

        def scaled_loss(trainable, frozen, batch, rng):
            objective, parts = loss_fn(trainable, frozen, batch, rng)
            return objective * loss_scale, parts

        (_, (ce_mean, aux_mean, n_tok)), grads = jax.value_and_grad(
            scaled_loss, has_aux=True)(trainable, frozen, batch, rng)
        if use_grad_pin:
            from jax.sharding import NamedSharding

            from dlti_tpu.parallel.sharding import _zero_opt_leaf_pspec

            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, _zero_opt_leaf_pspec(
                        g.shape, pin_axis, pin_size))), grads)
        grads = jax.tree_util.tree_map(lambda g: g / loss_scale, grads)
        updates, new_opt = state.tx.update(grads, state.opt_state, trainable)
        new_trainable = optax.apply_updates(trainable, updates)
        grad_norm = optax.global_norm(grads)
        # Reported loss stays pure CE (aux separate), like the flat step.
        metrics = {"loss": ce_mean, "grad_norm": grad_norm,
                   "num_tokens": n_tok}
        if moe_coef:
            metrics["aux_loss"] = aux_mean
        new_scaler = state.scaler
        if state.scaler is not None:
            from dlti_tpu.training.step import apply_loss_scaler

            new_trainable, new_opt, new_scaler, extra = apply_loss_scaler(
                state.scaler, grad_norm, new_trainable, trainable,
                new_opt, state.opt_state, cfg.train.fp16_scale_window,
                cfg.train.fp16_min_scale, cfg.train.fp16_hysteresis)
            metrics.update(extra)
            metrics["nonfinite"] = extra["overflow"]
            metrics["skipped_update"] = extra["overflow"]
        else:
            # bf16 nonfinite gate — same skip semantics as the flat step.
            from dlti_tpu.training.step import guard_nonfinite_update

            new_trainable, new_opt, extra = guard_nonfinite_update(
                grad_norm, ce_mean, new_trainable, trainable,
                new_opt, state.opt_state)
            metrics.update(extra)
        return state.replace(
            step=state.step + 1,
            params=combine_params(new_trainable, frozen),
            opt_state=new_opt,
            scaler=new_scaler,
        ), metrics

    return jax.jit(step, donate_argnums=(0,))


def make_pipeline_eval_step(cfg: Config, mesh: Mesh) -> Callable:
    """``eval_step(state, batch) -> metrics`` on pipeline-layout params.

    Runs :func:`pipeline_forward` deterministically with a single
    microbatch (the full eval batch flows through the stages once; the
    (P-1)/P bubble is irrelevant at eval cadence) — the pipe-mesh analog
    of :func:`dlti_tpu.training.step.make_eval_step`.
    """
    from dlti_tpu.training.step import causal_lm_loss

    lora = cfg.lora if cfg.lora.enabled else None

    loss_chunk = int(cfg.train.loss_chunk or 0)

    def eval_step(state, batch):
        out = pipeline_forward(
            state.params, batch["input_ids"], cfg.model, mesh, lora=lora,
            num_microbatches=1, deterministic=True,
            positions=batch.get("positions"),
            segment_ids=batch.get("segment_ids"),
            return_hidden=bool(loss_chunk),
        )
        if loss_chunk:
            # Mirror the train step: a run whose HBM budget depends on
            # loss_chunk must not OOM at its first periodic eval.
            from dlti_tpu.training.step import chunked_causal_lm_loss

            loss_sum, n_tok = chunked_causal_lm_loss(
                out, pipeline_head_matrix(state.params, cfg.model, out),
                batch["input_ids"], batch.get("loss_mask"), loss_chunk)
        else:
            loss_sum, n_tok = causal_lm_loss(
                out, batch["input_ids"], batch.get("loss_mask"))
        return {"loss": loss_sum / jnp.maximum(n_tok, 1.0),
                "num_tokens": n_tok}

    return jax.jit(eval_step)
