"""Llama-family transformer, TPU-first, in Flax linen.

The reference uses HF ``LlamaForCausalLM`` loaded from the hub
(``training/train_baseline.py:122-126``); this is a from-scratch
implementation of the same architecture family (RMSNorm, RoPE, GQA-capable
attention, SwiGLU MLP, untied LM head) designed for XLA:

* bf16 matmuls with fp32 reductions (MXU-friendly, no loss scaling —
  replaces the reference's fp16 dynamic loss scaler,
  ``configs/ds_config_zero1.json:25-32``)
* ``jax.checkpoint`` per block when ``remat=True`` (replaces CUDA gradient
  checkpointing, ``training/train_baseline.py:181``)
* LoRA grafted natively via :class:`~dlti_tpu.models.lora.LoRADense` on the
  projections named by ``LoRAConfig.target_modules`` (reference PEFT graft,
  ``training/train_baseline.py:131-140``)
* a functional KV cache threaded through ``__call__`` for the serving engine
  (the reference's claimed-but-absent vLLM leg, ``README.md:10``).

All shapes are static; decode uses fixed-capacity caches + dynamic-slice
updates so the whole engine stays inside one compiled program.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dlti_tpu.config import LoRAConfig, ModelConfig
from dlti_tpu.models.lora import LoRADense
from dlti_tpu.ops.attention import (
    multi_head_attention, reference_attention, resolve_flash,
    resolve_paged_decode,
)
from dlti_tpu.ops.rope import (
    apply_rope, assert_rope_table_covers, rope_frequencies,
)


from dlti_tpu.utils.dtypes import resolve_dtype as _dtype  # shared table


class RMSNorm(nn.Module):
    """Llama RMSNorm; stats in fp32 regardless of compute dtype.

    ``offset`` selects Gemma's ``(1 + weight)`` parameterization (weights
    stored zero-centered, HF state dicts carry ``w`` with the +1 applied at
    run time); init follows suit (zeros instead of ones).
    """

    eps: float = 1e-5
    param_dtype: Any = jnp.float32
    offset: bool = False
    # Seeded weights init_mean x (1 + N(0, init_std)) instead of ones
    # (init_std 0: ones).
    init_std: float = 0.0
    init_mean: float = 1.0

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        orig_dtype = x.dtype
        init = nn.initializers.zeros if self.offset else nn.initializers.ones
        if self.init_std:
            def init(key, shape, dtype, std=self.init_std,
                     mean=self.init_mean):
                return (mean * (1.0 + std * jax.random.normal(key, shape))
                        ).astype(dtype)
        scale = self.param("scale", init, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        normed = x32 * jax.lax.rsqrt(var + self.eps)
        s = scale.astype(jnp.float32)
        if self.offset:
            s = 1.0 + s
        return (normed * s).astype(orig_dtype)


def _lora_kwargs(cfg: ModelConfig, lora: Optional[LoRAConfig], name: str) -> dict:
    """LoRA hyperparams for projection ``name``, or r=0 when untargeted."""
    if lora is not None and lora.enabled \
            and name in cfg.lora_targets_of(lora):
        return dict(lora_r=lora.r, lora_alpha=lora.alpha, lora_dropout=lora.dropout)
    return dict(lora_r=0)


# Seeded spread of the query and key norms' weights (``qk_norm``): away from
# 1, as ``models.moe.SCORE_BIAS_STD`` keeps the selection bias away from 0,
# so that a program without the norms' weights differs from one with them.
QK_NORM_INIT_STD = 0.25
# The same for the four norms of a sandwich-normed block (``sandwich_norm``):
# a program that leaves out a sublayer's second norm, or shares one weight
# between its two, then differs from the stated one.
SANDWICH_NORM_INIT_STD = 0.25
# ... and the mean of the two norms AFTER the sublayers (the two before them
# spread round 1), and the gain of seeded query and key projections. A norm
# after a sublayer gives its output a fixed size whatever it computed, and
# seeded attention is near uniform, an average over the context in which
# what the positions share survives and what tells them apart cancels: so
# what the states of a batch have in common grows from layer to layer, and
# the next pass starts from it. At the published widths and 4 x 48 layers,
# with every norm near 1 (and at 0.1 as well), every prompt and every
# position ended in ONE state: the same greedy token at log-prob -7.50 in
# every answer, fp8-rounded weights no further from the reference than
# bf16, three passes for four within 0.04 (my chip runs, PR 49; the
# reference alone on the CPU reads a cosine of 0.82 between two prompts'
# last states after one pass, 0.99 after two, 1.00 after four). A check on
# such outputs holds nothing. So the sublayers are seeded as small
# increments (0.02) on an embedding of unit scale that carries the token,
# as the held-expert families seed theirs, and queries and keys three times
# LeCun's scale, score spread ~9, so that a query attends to a few keys as
# a trained one does.
SANDWICH_OUT_NORM_MEAN = 0.02
SEEDED_QK_GAIN = 3.0
# What a looped stack (``ut_steps`` > 1) counts, as ``HeldExpertsMLP``'s
# counters travel: passes a program call ran (``ut_steps`` while every token
# runs every pass: what an adaptive exit would lower), and over the call's
# real tokens the sum of 1000 x the expected exit pass ``sum_u u p_u`` of
# the exit gate's distribution.
LOOP_COUNTERS = ("loop_passes", "loop_exit_pass_e3")
# Seeded spread of the exit gate's bias: away from 0, so that lambda_u is
# not 0.5 in the mean and a program that dropped the bias differs.
EXIT_GATE_BIAS_STD = 1.0
# Most padded tokens (rows x bucket) the serving engine gives one prefill
# call of a model whose layers disagree about their window: a longer prompt
# goes as several calls, each over what the earlier ones wrote. It bounds
# what the window group of the cache has to hold for a call
# (``ops.kv_cache.window_group_blocks``) and the held-expert layer's layout,
# as the latent family's limit does (``models.latent.PREFILL_CALL_TOKENS``).
PREFILL_CALL_TOKENS = 2048


def over_paged_cache(cfg: ModelConfig, mesh, window, q, k, v, cache,
                     positions):
    """The serving engine's paged cache: this call's keys and values
    into the layer's pool (``k`` None: the pool is another layer's, which
    wrote this call's tokens already), then its queries over each row's
    table. ``(out (b, s, heads, head_dim), the layer's new cache)``."""
    s = q.shape[1]
    # Stale/unallocated slots are at logical positions > the query
    # position, so the explicit-position causal mask hides them.
    from dlti_tpu.ops.attention import attend_over_cache, walks_cache
    from dlti_tpu.ops.kv_cache import paged_gather, paged_update, slot_mapping

    nb, blk_size = cache["k"].shape[0], cache["k"].shape[1]
    if "table_base" in cache:
        # A window group's table (ops.kv_cache): column 0 is the block that
        # holds token ``table_base`` of the row, what lies before it has
        # been released. Keys are stored rotated and the mask reads
        # differences of positions, so the cache is addressed, and attended
        # over, in positions counted from there.
        positions = jnp.where(
            positions >= 0, positions - cache["table_base"][:, None], -1)
    slots = slot_mapping(cache["block_tables"], positions, blk_size, nb)
    new_cache = cache if k is None else paged_update(cache, k, v, slots)
    # The kernel for decode steps (s == 1); a prefill call walks the
    # cache in blocks or gathers the row's window (``walks_cache``).
    path, _ = resolve_paged_decode(
        cfg.paged_attention_impl,
        tp_sharded=mesh is not None and mesh.shape.get("tensor", 1) > 1)
    use_kernel = s == 1 and path != "xla"
    if use_kernel:
        # Pallas kernel: reads K/V blocks in place via the block table (no
        # O(batch*max_len) gather); decode steps only.
        from dlti_tpu.ops.pallas.paged_attention import (
            paged_decode_attention,
        )

        out = paged_decode_attention(
            q, new_cache["k"], new_cache["v"],
            cache["block_tables"], positions[:, 0] + 1,
            k_scale=new_cache.get("k_scale"),
            v_scale=new_cache.get("v_scale"),
            window=window,
            interpret=path == "pallas-interpret",
        ).astype(q.dtype)
    elif walks_cache(s, cache["block_tables"].shape[1] * blk_size,
                     "table_base" in cache):
        out = attend_over_cache(q, new_cache, cache["block_tables"],
                                positions, window)
    else:
        ck, cv = paged_gather(new_cache, cache["block_tables"], q.shape[-1])
        out = reference_attention(
            q, ck.astype(q.dtype), cv.astype(q.dtype),
            causal=True, q_positions=positions,
            window=window,
        )
    return out, new_cache


class LlamaAttention(nn.Module):
    cfg: ModelConfig
    lora: Optional[LoRAConfig] = None
    # Device mesh, threaded in by the parallel layer. When its 'sequence'
    # axis is >1, training attention runs the ring schedule
    # (dlti_tpu.parallel.ring_attention) — the reference has no SP at all
    # (SURVEY.md §5.7); here it is first-class.
    mesh: Optional[Any] = None
    # Which layer this is: its window and whether it rotates are the
    # configuration's (``window_of_layer``, ``rope_on_full_layers``).
    layer: int = 0

    @property
    def window(self) -> Optional[int]:
        return self.cfg.window_of_layer(self.layer)

    def _over_paged_cache(self, q, k, v, cache, positions):
        return over_paged_cache(self.cfg, self.mesh, self.window, q, k, v,
                                cache, positions)

    def _effective_window(self, segment_ids) -> Optional[int]:
        """Sliding window combined with the packed doc-length bound.

        For packed batches a window of ``packed_attention_window`` is
        *exact*: intra-document attention can never reach further back
        than the document's own length, and the segment mask handles the
        rest — so the flash kernel's banded sweep (or the ring's chunk
        skip) applies without changing any logit.
        """
        cfg = self.cfg
        window = self.window
        if segment_ids is not None and cfg.packed_attention_window:
            window = (min(window, cfg.packed_attention_window)
                      if window else cfg.packed_attention_window)
        return window

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        cos: jnp.ndarray,
        sin: jnp.ndarray,
        positions: jnp.ndarray,
        segment_ids: Optional[jnp.ndarray] = None,
        cache: Optional[dict] = None,
        deterministic: bool = True,
        adapter_ids: Optional[jnp.ndarray] = None,
    ):
        cfg = self.cfg
        dtype = _dtype(cfg.dtype)
        pdtype = _dtype(cfg.param_dtype)
        b, s, _ = x.shape
        hd = cfg.resolved_head_dim

        def proj(name: str, features: int, use_bias: bool = False, **init):
            return LoRADense(
                features=features, use_bias=use_bias, dtype=dtype, param_dtype=pdtype,
                name=name, **_lora_kwargs(cfg, self.lora, name), **init,
            )

        # Qwen2-style bias on q/k/v only, never o (config.attention_bias).
        qkv_bias = cfg.attention_bias
        # (seeded queries and keys of a sandwich-normed stack: SEEDED_QK_GAIN)
        sharp = {"kernel_init": nn.initializers.variance_scaling(
            SEEDED_QK_GAIN ** 2, "fan_in", "truncated_normal")} \
            if cfg.sandwich_norm else {}
        q = proj("q_proj", cfg.num_heads * hd, qkv_bias, **sharp)(
            x, deterministic, adapter_ids)
        k = proj("k_proj", cfg.num_kv_heads * hd, qkv_bias, **sharp)(
            x, deterministic, adapter_ids)
        v = proj("v_proj", cfg.num_kv_heads * hd, qkv_bias)(x, deterministic,
                                                            adapter_ids)

        q = q.reshape(b, s, cfg.num_heads, hd)
        k = k.reshape(b, s, cfg.num_kv_heads, hd)
        v = v.reshape(b, s, cfg.num_kv_heads, hd)

        window = self.window
        if cfg.qk_norm:
            # a head at a time over its head_dim values, before the rotation
            q = RMSNorm(cfg.rms_norm_eps, init_std=QK_NORM_INIT_STD,
                        name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, init_std=QK_NORM_INIT_STD,
                        name="k_norm")(k)
        # the nemotron_h family applies no rotation (cos, sin None); a hybrid
        # model's layers that see every key may carry no position either
        if cfg.rope and (window or cfg.rope_on_full_layers):
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)

        new_cache = None
        if cache is not None and "block_tables" in cache:
            # (scopes for a model whose layers differ alone: the others'
            # programs are named as they were)
            with (jax.named_scope("dlti_attn_window" if window
                                  else "dlti_attn_full")
                  if cfg.layer_windows else contextlib.nullcontext()):
                out, new_cache = self._over_paged_cache(
                    q, k, v, cache, positions)
        elif cache is not None:
            # Fixed-capacity cache: (b, max_len, kv_heads, hd). `index` is the
            # write offset (same for the whole batch in the engine's design —
            # per-sequence offsets live in the paged serving cache instead).
            ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                              (0, cache["index"], 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                              (0, cache["index"], 0, 0))
            new_cache = {"k": ck, "v": cv, "index": cache["index"] + s}
            # Cache slot index == token position (contiguous writes), so the
            # position-explicit causal mask also masks unwritten slots.
            out = reference_attention(
                q, ck.astype(q.dtype), cv.astype(q.dtype),
                causal=True, q_positions=positions,
                window=window,
            )
        elif (self.mesh is not None and "sequence" in self.mesh.shape
              and self.mesh.shape["sequence"] > 1):
            # Sequence-parallel training: exact ring attention over the
            # 'sequence' mesh axis. RoPE positions are passed through so
            # the ring's causal mask always agrees with the embedded
            # positions; packed batches travel their segment ids around
            # the ring and segment-disjoint chunks skip their matmuls.
            # The packed doc-length bound is NOT passed here: the ring
            # masks by *per-document* positions (always < the bound), so
            # as a window it could never fire — segment disjointness is
            # the mechanism that prunes packed chunks on this path.
            from dlti_tpu.parallel.ring_attention import ring_attention

            out = ring_attention(q, k, v, self.mesh, positions=positions,
                                 segment_ids=segment_ids, causal=True,
                                 window=window)
        else:
            window = self._effective_window(segment_ids)
            attend = functools.partial(
                multi_head_attention, causal=True, impl=cfg.attention_impl,
                block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv,
                window=window)
            path, _ = resolve_flash(cfg.attention_impl, seq_q=s, seq_kv=s,
                                    head_dim=hd)
            if path == "xla":
                out = attend(q, k, v, segment_ids=segment_ids)
            else:
                # A Pallas kernel: GSPMD cannot partition it, so under a
                # mesh every device runs it on its own rows and heads.
                from dlti_tpu.parallel.ring_attention import (
                    per_shard_attention,
                )

                out = per_shard_attention(attend, q, k, v, self.mesh,
                                          segment_ids)

        # Remat seam: with remat_policy="save_attn_out", the backward reuses
        # this (b, s, h*d) tensor instead of re-running the whole attention
        # (flash fwd is the most expensive thing under recompute) while
        # everything else still remats — a memory/FLOPs middle ground
        # between nothing_saveable and dots_*.
        out = checkpoint_name(out.reshape(b, s, cfg.num_heads * hd),
                              "attn_out")
        out = proj("o_proj", cfg.hidden_size)(out, deterministic, adapter_ids)
        return out, new_cache


_MLP_ACTIVATIONS = {
    "silu": nn.silu,
    "gelu_tanh": nn.gelu,  # flax default: tanh approximation
    "gelu_exact": lambda x: nn.gelu(x, approximate=False),
}


class LlamaMLP(nn.Module):
    """Gated MLP: down(act(gate(x)) * up(x)); act is SwiGLU's silu for the
    Llama/Mistral/Qwen2 families, gelu_tanh for Gemma-style configs."""

    cfg: ModelConfig
    lora: Optional[LoRAConfig] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True,
                 adapter_ids: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        cfg = self.cfg
        dtype = _dtype(cfg.dtype)
        pdtype = _dtype(cfg.param_dtype)
        act = _MLP_ACTIVATIONS[cfg.mlp_activation]

        def proj(name: str, features: int):
            return LoRADense(
                features=features, use_bias=False, dtype=dtype, param_dtype=pdtype,
                name=name, **_lora_kwargs(cfg, self.lora, name),
            )

        gate = proj("gate_proj", cfg.intermediate_size)(x, deterministic,
                                                        adapter_ids)
        up = proj("up_proj", cfg.intermediate_size)(x, deterministic,
                                                    adapter_ids)
        return proj("down_proj", cfg.hidden_size)(act(gate) * up,
                                                  deterministic, adapter_ids)


class LlamaBlock(nn.Module):
    """One layer: ``x + Attn(norm(x))`` then ``x + MLP(norm(x))``, or with
    ``post_sublayer_norm`` ``x + norm(Attn(x))`` then ``x + norm(MLP(x))``
    (the same two norms, after their sublayers), or with ``sandwich_norm``
    FOUR norms, ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``
    (``input_norm``, ``attn_out_norm``, ``post_attn_norm``,
    ``mlp_out_norm``). Called several times (``ut_steps``) the block's
    weights are the same every time. The MLP is ``LlamaMLP``;
    ``MoEMLP`` where ``num_experts`` > 0; with ``moe_num_experts`` > 0
    ``HeldExpertsMLP`` from layer ``first_k_dense`` on, and the block then
    returns that layer's counters as a third value."""

    cfg: ModelConfig
    lora: Optional[LoRAConfig] = None
    mesh: Optional[Any] = None
    layer: int = 0

    @nn.compact
    def __call__(self, x, cos, sin, positions, segment_ids=None, cache=None,
                 deterministic: bool = True, token_mask=None,
                 adapter_ids=None):
        cfg = self.cfg

        def norm(name, mean=1.0):
            return RMSNorm(
                cfg.rms_norm_eps, offset=cfg.rmsnorm_offset, name=name,
                init_std=SANDWICH_NORM_INIT_STD if cfg.sandwich_norm else 0.0,
                init_mean=mean)

        input_norm, post_attn_norm = norm("input_norm"), norm("post_attn_norm")
        after = cfg.post_sublayer_norm
        attn_out, new_cache = LlamaAttention(
            cfg, self.lora, self.mesh, self.layer, name="attn")(
            x if after else input_norm(x),
            cos, sin, positions, segment_ids, cache, deterministic,
            adapter_ids,
        )
        if cfg.sandwich_norm:
            x = x + norm("attn_out_norm", SANDWICH_OUT_NORM_MEAN)(attn_out)
            mlp_out = LlamaMLP(cfg, self.lora, name="mlp")(
                post_attn_norm(x), deterministic, adapter_ids)
            return x + norm("mlp_out_norm", SANDWICH_OUT_NORM_MEAN)(
                mlp_out), new_cache
        x = x + (input_norm(attn_out) if after else attn_out)
        normed = x if after else post_attn_norm(x)
        if cfg.moe_num_experts > 0:
            if self.layer < cfg.first_k_dense:
                mlp_out, counted = LlamaMLP(cfg, None, name="mlp")(normed), None
            else:
                from dlti_tpu.models.moe import HeldExpertsMLP

                mlp_out, counted = HeldExpertsMLP(cfg, name="mlp")(
                    normed, token_mask)
            return (x + (post_attn_norm(mlp_out) if after else mlp_out),
                    new_cache, counted)
        if cfg.num_experts > 0:
            from dlti_tpu.models.moe import MoEMLP

            if self.lora is not None and any(
                    t in ("gate_proj", "up_proj", "down_proj")
                    for t in self.lora.target_modules):
                raise NotImplementedError(
                    "LoRA on MLP projections is not supported for MoE "
                    "models (experts have no adapter branch); target "
                    "attention projections only")
            mlp_out = MoEMLP(cfg, self.mesh, name="mlp")(
                normed, deterministic, token_mask)
        else:
            mlp_out = LlamaMLP(cfg, self.lora, name="mlp")(
                normed, deterministic, adapter_ids)
        return x + (post_attn_norm(mlp_out) if after else mlp_out), new_cache


def _remat_policy(name: str):
    policies = {
        "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
        "dots_with_no_batch_dims_saveable":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # Save only each block's attention output (tagged in LlamaAttention):
        # the backward skips the flash-fwd recompute at the cost of one
        # (b, s, hidden) tensor per layer.
        "save_attn_out":
            jax.checkpoint_policies.save_only_these_names("attn_out"),
    }
    return policies[name]


def entry_of_pass(layer_cache: dict, u, ut_steps: int) -> dict:
    """Pass ``u``'s entry of a layer's paged cache. A looped stack's pool
    holds ``ut_steps`` times the blocks of ``--num-blocks``, a run of them
    a pass: block b of pass u lies at ``u x (blocks a pass) + b``, so one
    block table serves every pass and the scatter, the gather and the
    decode kernel address the pool as they always did."""
    per_pass = layer_cache["k"].shape[0] // ut_steps
    return {**layer_cache,
            "block_tables": layer_cache["block_tables"] + u * per_pass}


class LoopPass(nn.Module):
    """One pass of a looped stack (``ut_steps`` > 1), the body that
    ``LlamaModel`` scans: the blocks in order, each over its entry of this
    pass, then the final norm (inside the loop: its output is the next
    pass's input) and the exit gate on the normed state. Carry ``(x, the
    layers' caches)``; returns the pass's exit rate ``(b, s)``."""

    cfg: ModelConfig
    lora: Optional[LoRAConfig] = None
    mesh: Optional[Any] = None
    deterministic: bool = True

    @nn.compact
    def __call__(self, carry, u, cos, sin, positions, segment_ids,
                 token_mask, adapter_ids):
        cfg = self.cfg
        x, cache = carry
        new_cache = None if cache is None else []
        # (the loop index is traced: one scope for the body, run u = 0 ..
        # ut_steps - 1)
        with jax.named_scope("dlti_loop_pass_u"):
            rest = (cos, sin, positions, segment_ids)
            if self.is_initializing():
                # the tree: a submodule a layer
                def block(i, x, layer_cache):
                    return LlamaBlock(
                        cfg, self.lora, self.mesh, name=f"layers_{i}")(
                        x, *rest, layer_cache, self.deterministic,
                        token_mask, adapter_ids)
            else:
                # Every layer has the same shapes, so the block is ONE
                # jitted function of a layer's weights: traced once a
                # program, not once a layer (a looped stack's layers differ
                # in nothing but their weights; tracing 48 of them took
                # most of a prefill program's first call on the chip's
                # host: PERF.md section 6, PR 49).
                shared = LlamaBlock(cfg, self.lora, self.mesh, parent=None)
                traced_once = jax.jit(
                    lambda w, x, layer_cache, rest, mask, ids: shared.apply(
                        {"params": w}, x, *rest, layer_cache,
                        self.deterministic, mask, ids))
                weights = self.variables["params"]

                def block(i, x, layer_cache):
                    return traced_once(weights[f"layers_{i}"], x, layer_cache,
                                       rest, token_mask, adapter_ids)

            for i in range(cfg.num_layers):
                layer_cache = None if cache is None else entry_of_pass(
                    cache[i], u, cfg.ut_steps)
                x, written = block(i, x, layer_cache)
                if cache is not None:
                    # the pools as written, the call's own table again
                    new_cache.append({**written, "block_tables":
                                      cache[i]["block_tables"]})
            x = RMSNorm(cfg.rms_norm_eps, offset=cfg.rmsnorm_offset,
                        name="final_norm")(x)
            rate = exit_rate(
                x,
                self.param("exit_gate_kernel", nn.initializers.lecun_normal(),
                           (cfg.hidden_size, 1), jnp.float32),
                self.param("exit_gate_bias",
                           nn.initializers.normal(EXIT_GATE_BIAS_STD), (1,),
                           jnp.float32))
        return (x, new_cache), rate


class LlamaModel(nn.Module):
    """Transformer body (embeddings + blocks + final norm; with
    ``ut_steps`` > 1 the blocks, the norm and an exit gate as one scanned
    pass, ``loop`` in the tree)."""

    cfg: ModelConfig
    lora: Optional[LoRAConfig] = None
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None, cache=None,
                 deterministic: bool = True, token_mask=None,
                 adapter_ids=None):
        cfg = self.cfg
        dtype = _dtype(cfg.dtype)
        pdtype = _dtype(cfg.param_dtype)
        b, s = input_ids.shape
        if token_mask is None and segment_ids is not None:
            token_mask = (segment_ids != 0).astype(jnp.int32)  # packed: 0 = pad

        embed = self.param(
            "embed_tokens",
            # With held experts, seeded at unit scale as the other held-
            # expert families are: the residual stream carries the token and
            # the layers add to it (models.moe.centred_out_init). The same
            # under sandwich norms (SANDWICH_OUT_NORM_MEAN).
            nn.initializers.normal(
                stddev=1.0 if cfg.moe_num_experts > 0 or cfg.sandwich_norm
                else 0.02),
            (cfg.vocab_size, cfg.hidden_size),
            pdtype,
        )
        if isinstance(embed, dict):
            # int8 serving: gather int8 rows, then scale (per-channel).
            x = (embed["q"][input_ids].astype(dtype)
                 * embed["scale"].astype(dtype))
        else:
            x = jnp.take(embed, input_ids, axis=0).astype(dtype)
        if cfg.embedding_scale:  # Gemma: embeddings scaled by sqrt(hidden)
            x = x * jnp.asarray(cfg.hidden_size ** 0.5, dtype)

        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))

        # RoPE tables sized to cache capacity when decoding, else seq len.
        if cache is None:
            # Cover the actual sequence even past the preset's design
            # length: the table is computed (not learned), so extending it
            # is exact for in-range positions. This sizing is the
            # LOAD-BEARING invariant: apply_rope now gathers with
            # mode="clip" (r05 — the NaN-fill bounds check cost a
            # lax.cond per gather and broke vma typing under PP x SP), so
            # an under-sized table no longer NaNs loudly (the r03 bug
            # class, seq 512 > table 128) — it would silently clamp.
            # Keep every table-sizing branch >= max(positions) + 1.
            table_len = max(cfg.max_seq_len, s)
            # Trace-time enforcement of the invariant above:
            # positions here are bounded by the static sequence length
            # (arange(s) by default; packed per-doc positions < s), so an
            # under-sized table fails the trace instead of silently
            # clamping rotary angles.
            assert_rope_table_covers(table_len, s, "training/no-cache path")
        elif "block_tables" in cache[0]:
            # Paged: capacity = logical window = blocks/seq * block_size.
            # Positions are bounded by the engine's seq_len < capacity =
            # table_len by construction (not statically knowable here).
            # (The widest of the layers' tables: a window group's holds a
            # window's blocks alone.)
            table_len = max(c["block_tables"].shape[1] for c in cache) \
                * cache[0]["k"].shape[1]
        else:
            table_len = cache[0]["k"].shape[1]
            # Decode over a dense cache: the query chunk's positions lie
            # inside the cache window; the chunk itself must fit.
            assert_rope_table_covers(table_len, s, "dense-cache decode path")
        cos, sin = rope_frequencies(cfg.resolved_head_dim, table_len, cfg.rope_theta)

        block_cls = LlamaBlock
        if cfg.remat and cache is None:
            block_cls = nn.remat(
                LlamaBlock,
                policy=_remat_policy(cfg.remat_policy),
                static_argnums=(7,),  # deterministic (arg 0 is the module)
            )

        counters = None
        if cfg.moe_num_experts > 0:
            from dlti_tpu.models.moe import MOE_COUNTERS

            counters = dict.fromkeys(MOE_COUNTERS, jnp.int32(0))
            # Held experts route and count real tokens alone: not padding,
            # nor a decode row of a slot that is free or still prefilling
            # (position 0, a table of the reserved trash block).
            routed = positions >= 0
            if cache is not None and "block_tables" in cache[0]:
                routed = routed & (cache[0]["block_tables"][:, :1] > 0)
            token_mask = routed if token_mask is None \
                else routed & token_mask.astype(bool)
        if cfg.ut_steps > 1:
            x, new_caches, rates = self._passes(
                x, cos, sin, positions, segment_ids, cache, deterministic,
                token_mask, adapter_ids)
            real = positions >= 0
            if cache is not None:
                real = real & (cache[0]["block_tables"][:, :1] > 0)
            self.sow("intermediates", "exit_rates", rates)
            expected = expected_exit_pass(exit_distribution(rates))
            return x, new_caches, {
                "loop_passes": jnp.int32(cfg.ut_steps),
                "loop_exit_pass_e3": jnp.sum(jnp.where(
                    real, jnp.round(1000.0 * expected), 0.0)
                ).astype(jnp.int32)}
        new_caches = [] if cache is not None else None
        for i in range(cfg.num_layers):
            # Selective remat: every remat_stride-th block keeps its
            # activations instead of recomputing them in the backward —
            # stride k trades ~1/k of the recompute forward for that
            # fraction of saved activations in HBM. So do the last
            # remat_keep_blocks (the trainer's count of what fits).
            cls_i = block_cls
            if cfg.remat and cache is None and (
                    (cfg.remat_stride > 1 and i % cfg.remat_stride == 0)
                    or i >= cfg.num_layers - (cfg.remat_keep_blocks or 0)):
                cls_i = LlamaBlock
            layer_cache = cache[i] if cache is not None else None
            x, layer_new_cache, *counted = cls_i(
                cfg, self.lora, self.mesh, i, name=f"layers_{i}")(
                x, cos, sin, positions, segment_ids, layer_cache, deterministic,
                token_mask, adapter_ids,
            )
            if cache is not None:
                new_caches.append(layer_new_cache)
            if counted and counted[0] is not None:
                for name, n in zip(counters, counted[0]):
                    counters[name] = jnp.maximum(counters[name], n) \
                        if name == "moe_expert_load_max" \
                        else counters[name] + n

        x = RMSNorm(cfg.rms_norm_eps, offset=cfg.rmsnorm_offset, name="final_norm")(x)
        if counters is not None:
            return x, new_caches, counters
        return x, new_caches

    def _passes(self, x, cos, sin, positions, segment_ids, cache,
                deterministic, token_mask, adapter_ids):
        """``ut_steps`` passes over the stack as ONE scanned body
        (:class:`LoopPass`): a program holds ``num_layers`` block bodies
        whatever the passes (unrolled, 192 bodies of the 48-layer model
        compile in 66 s a program; PERF.md section 6, PR 49), the weights
        are the loop's constants and the pools its carry, written in
        place. (Neither a multi-LoRA pool's tree nor a dropout stream is
        handed to the body: both are refused for a looped stack.) Returns
        ``(x, the layers' new caches, exit rates (b, s, passes))``."""
        cfg = self.cfg
        if cache is not None and "block_tables" not in cache[0]:
            raise NotImplementedError(
                "ut_steps > 1 over the dense (batch, max_len) cache: a "
                "looped stack is served over the paged cache alone")
        args = (cos, sin, positions, segment_ids, token_mask, adapter_ids)
        if self.is_initializing():
            # The tree is made by ONE pass, called as the submodule
            # ``loop``: every pass reads the same weights.
            (x, new_caches), rate = LoopPass(
                cfg, self.lora, self.mesh, deterministic, name="loop")(
                (x, cache), jnp.int32(0), *args)
            return x, new_caches, jnp.stack([rate] * cfg.ut_steps, -1)
        # ``lax.scan`` over the pass applied as a function of the tree's
        # ``loop`` (not ``nn.scan``, which traces its body twice: a prefill
        # program's first call cost 41 s of tracing on the chip's host, 16
        # programs a start-up; PERF.md section 6, PR 49).
        weights = {"params": self.variables["params"]["loop"]}
        one_pass = LoopPass(cfg, self.lora, self.mesh, deterministic,
                            parent=None)
        (x, new_caches), rates = jax.lax.scan(
            lambda carry, u: one_pass.apply(weights, carry, u, *args),
            (x, cache), jnp.arange(cfg.ut_steps, dtype=jnp.int32))
        return x, new_caches, jnp.moveaxis(rates, 0, -1)


def exit_rate(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """The exit gate on one pass's normed state, ``lambda = sigmoid(w . x +
    b)`` (b, s) in float32: one ``Linear(hidden, 1)`` with bias, the same
    for every pass."""
    return jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w[:, 0],
        precision=jax.lax.Precision.HIGHEST) + b[0])


def exit_distribution(exit_rates: jnp.ndarray) -> jnp.ndarray:
    """``p_u = lambda_u prod_{v<u} (1 - lambda_v)`` for every pass but the
    last, which takes the rest: ``(..., passes)`` from the rates ``(...,
    passes)``, summing to 1."""
    stay = jnp.cumprod(1.0 - exit_rates[..., :-1], axis=-1)
    before = jnp.concatenate([jnp.ones_like(stay[..., :1]), stay[..., :-1]],
                             axis=-1)
    return jnp.concatenate([exit_rates[..., :-1] * before, stay[..., -1:]],
                           axis=-1)


def expected_exit_pass(p: jnp.ndarray) -> jnp.ndarray:
    """``sum_u u p_u`` with passes counted from 1."""
    return jnp.sum(p * jnp.arange(1, p.shape[-1] + 1, dtype=p.dtype), -1)


def head_matrix_from_leaves(embed_leaf, head_leaf, tie_embeddings: bool,
                            anchor) -> jnp.ndarray:
    """The (hidden, vocab) head as an explicit matrix from raw param
    leaves — ONE implementation of the chunked-loss head contract, shared
    by the flat (``LlamaForCausalLM.head_matrix``) and pipeline-layout
    (``parallel.pipeline.pipeline_head_matrix``) callers so a head change
    cannot desynchronize the two chunked paths. Dtypes match __call__
    exactly: tied embeddings project in float32, untied heads in the
    activation dtype with fp32 accumulation."""
    from dlti_tpu.models.quantization import maybe_dequantize

    if tie_embeddings or head_leaf is None:
        embed = maybe_dequantize(embed_leaf, jnp.float32, anchor=anchor)
        return embed.astype(jnp.float32).T
    head = head_leaf
    if isinstance(head, dict):
        head = maybe_dequantize(head, anchor.dtype, anchor=anchor)
    return head.astype(anchor.dtype)


class LlamaForCausalLM(nn.Module):
    """Body + LM head. Returns float32 logits (stable softmax/loss)."""

    cfg: ModelConfig
    lora: Optional[LoRAConfig] = None
    mesh: Optional[Any] = None

    @property
    def counter_names(self) -> tuple:
        """What the forward pass counts (``return_counters``): the held
        experts' counters where the model has them, a looped stack's
        passes and exit gate (the two never meet: ``ModelConfig``)."""
        if self.cfg.ut_steps > 1:
            return LOOP_COUNTERS
        if self.cfg.moe_num_experts > 0:
            from dlti_tpu.models.moe import MOE_COUNTERS

            return MOE_COUNTERS
        return ()

    @property
    def prefill_call_tokens(self) -> int:
        """The most padded tokens of one prefill call (0: no limit): held
        to ``PREFILL_CALL_TOKENS`` where the layers' windows differ."""
        return PREFILL_CALL_TOKENS \
            if len(self.cfg.kv_group_windows) > 1 else 0

    @property
    def prefill_whole_tables(self) -> bool:
        """Such a model's prefill calls take each row's whole block table
        (one program a (rows, bucket) whatever the cached context: the
        walk over the cache ends at the call's highest position)."""
        return len(self.cfg.kv_group_windows) > 1

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None, cache=None,
                 deterministic: bool = True, token_mask=None,
                 return_hidden: bool = False, adapter_ids=None,
                 return_counters: bool = False):
        cfg = self.cfg
        pdtype = _dtype(cfg.param_dtype)
        if cfg.moe_num_experts > 0 and self.lora is not None \
                and self.lora.enabled:
            raise NotImplementedError(
                "LoRA through held experts under the Llama block is not "
                "implemented (HeldExpertsMLP has no adapter branch)")
        x, new_cache, *counted = LlamaModel(
            cfg, self.lora, self.mesh, name="model")(
            input_ids, positions, segment_ids, cache, deterministic, token_mask,
            adapter_ids,
        )
        if return_counters:
            # (a model that counts nothing is never asked: counter_names)
            new_cache = (new_cache, counted[0])
        if return_hidden:
            # Skip the LM head: the caller computes a seq-chunked loss so
            # (B, S, V) fp32 logits are never materialized whole
            # (training.step.chunked_causal_lm_loss). The head params must
            # still be grafted when this module owns them, so init traces
            # the normal path.
            if not self.is_initializing():
                return (x, *new_cache) if return_counters \
                    else (x, new_cache)
        if cfg.tie_embeddings:
            from dlti_tpu.models.quantization import maybe_dequantize

            embed = maybe_dequantize(
                self.variables["params"]["model"]["embed_tokens"],
                jnp.float32, anchor=x)
            logits = jnp.einsum("bsh,vh->bsv", x.astype(jnp.float32),
                                embed.astype(jnp.float32))
        else:
            lm_head = self.param(
                "lm_head", nn.initializers.normal(stddev=0.02),
                (cfg.hidden_size, cfg.vocab_size), pdtype,
            )
            if isinstance(lm_head, dict):
                from dlti_tpu.models.quantization import maybe_dequantize

                lm_head = maybe_dequantize(lm_head, x.dtype, anchor=x)
            logits = jnp.dot(x, lm_head.astype(x.dtype),
                             preferred_element_type=jnp.float32)
        if return_counters:
            return (logits.astype(jnp.float32), *new_cache)
        return logits.astype(jnp.float32), new_cache

    # ------------------------------------------------------------------
    def head_matrix(self, params, anchor):
        """The (hidden, vocab) projection __call__ applies after the body,
        as an explicit matrix — the input to the sequence-chunked loss
        (``training.step.chunked_causal_lm_loss``), kept here so head
        changes cannot desynchronize from the chunked path. Dtypes match
        __call__ exactly: tied embeddings project in float32
        (the einsum above), untied heads in the activation dtype with
        fp32 accumulation."""
        return head_matrix_from_leaves(
            params["model"]["embed_tokens"], params.get("lm_head"),
            self.cfg.tie_embeddings, anchor)

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16) -> list:
        """Allocate a fixed-capacity KV cache for decode."""
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        return [
            {
                "k": jnp.zeros((batch_size, max_len, cfg.num_kv_heads, hd), dtype),
                "v": jnp.zeros((batch_size, max_len, cfg.num_kv_heads, hd), dtype),
                "index": jnp.array(0, dtype=jnp.int32),
            }
            for _ in range(cfg.num_layers)
        ]
