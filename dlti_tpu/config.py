"""Single typed config tree for the whole framework.

Replaces the reference's two stitched-together config systems — per-script
argparse with drifting defaults (``training/train_baseline.py:27-89``,
``train_deepspeed_zero2.py:37-120``) and DeepSpeed JSON files with ``"auto"``
placeholders (``configs/ds_config_zero1.json``) — with one dataclass tree plus
per-strategy presets (see :func:`preset`).

Defaults mirror the reference where the reference has them:

* LoRA r=16, alpha=2*r, dropout=0.05, on q/k/v/o, bias none
  (``training/train_baseline.py:131-140``)
* AdamW betas (0.9, 0.999), eps 1e-8, weight decay 0
  (``configs/ds_config_zero1.json:6-14``)
* WarmupLR 0 -> lr over warmup steps (``configs/ds_config_zero1.json:16-23``)
* grad clip 1.0 (``configs/ds_config_zero1.json:44``)
* max_seq_len 512 truncation (``training/train_baseline.py:155``)
* lr 2e-4, grad-accum 16, micro-batch 1 (``training/train_baseline.py:60-75``)
"""

from __future__ import annotations

import dataclasses
import enum
import json
import os
from dataclasses import dataclass, field
from typing import Any, Optional


class ZeROStage(enum.IntEnum):
    """ZeRO stage, kept as a first-class concept for reference parity.

    On TPU these are sharding presets over the mesh, not an engine:

    * ``NONE``  — pure replicated data parallelism (reference baseline).
    * ``ZERO1`` — optimizer state sharded over the data axis
      (``configs/ds_config_zero1.json:35``).
    * ``ZERO2`` — + gradients reduce-scattered to shards
      (``configs/ds_config_zero2.json:27``).
    * ``ZERO3`` — + parameters sharded (FSDP) with optional host offload
      (``configs/ds_config_zero3.json:17-27``).
    """

    NONE = 0
    ZERO1 = 1
    ZERO2 = 2
    ZERO3 = 3


# The mixer kinds of the decoder-hybrid-decoder family's ``layer_pattern``.
SAMBAY_KINDS = "SDGX"
# ... and of the jamba family's (``models.jamba``): "S" Mamba-1 with its
# inner norms, "A" plain attention; a pattern of this family has an "A".
JAMBA_KINDS = "SA"


@dataclass(frozen=True)
class ModelConfig:
    """Llama-family architecture hyperparameters."""

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32  # < num_heads => GQA
    head_dim: Optional[int] = None  # default hidden_size // num_heads
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # Family knobs beyond Llama-2 (the reference is HF AutoModel-generic,
    # ``training/train_baseline.py:122``, so sibling families must load):
    attention_bias: bool = False        # Qwen2: bias on q/k/v (never o)
    # A local attention window: a query at position i sees keys j with
    # i - window < j <= i. ``sliding_window`` is one for every layer
    # (Mistral); ``layer_windows``, where given, states one a layer (0 = every
    # key) and wins over it (exaone_moe: 128, 128, 128, 0, ...).
    sliding_window: Optional[int] = None
    layer_windows: tuple = ()
    # A norm over each head's values of queries and keys (one weight of
    # head_dim each, shared by the heads) before the rotary embedding.
    qk_norm: bool = False
    # Whether the layers that see every key rotate queries and keys too
    # (false: a hybrid model's global layers carry no position).
    rope_on_full_layers: bool = True
    # Where a block's two norms stand: false ``x + F(norm(x))`` (pre-norm),
    # true ``x + norm(F(x))`` (the exaone4 family's placement).
    post_sublayer_norm: bool = False
    # FOUR norms a block, one before and one after each sublayer:
    # ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))`` (the ouro
    # family's sandwich normalisation). Not a third placement of the same
    # two norms, so a field of its own beside ``post_sublayer_norm``.
    sandwich_norm: bool = False
    # Passes over the stack: the ``num_layers`` blocks run ``ut_steps``
    # times over ONE set of weights, the final norm after every pass (its
    # output is the next pass's input), and pass u of layer l keeps keys and
    # values of its own: a cache entry a (pass, layer),
    # :attr:`cache_entries` of them, pass u's in the u-th run of blocks of
    # the layer's pool (``models.llama.entry_of_pass``). More than one pass
    # brings the exit gate, one ``Linear(hidden, 1)`` with bias on each
    # pass's normed state. 1: every layer once, no gate, the model as it
    # always was.
    ut_steps: int = 1
    # A token leaves at the first pass whose cumulated exit probability
    # reaches this. The programs run every pass for every token, which is
    # what 1.0 (the published value) says; adaptive exit is not implemented
    # and a lower threshold is refused.
    early_exit_threshold: float = 1.0
    mlp_activation: str = "silu"        # "silu" | "gelu_tanh" | "gelu_exact"
    rmsnorm_offset: bool = False        # Gemma: normalize with (1 + weight)
    embedding_scale: bool = False       # Gemma: embed * sqrt(hidden_size)
    # Mixture of Experts (Mixtral family): 0 experts = dense MLP. When > 0
    # every block's MLP is a top-k routed expert layer
    # (dlti_tpu.models.moe.MoEMLP) with GShard capacity dispatch.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_capacity_factor: float = 1.25
    router_aux_loss_coef: float = 0.02
    dtype: str = "bfloat16"  # compute dtype (MXU-friendly)
    param_dtype: str = "bfloat16"  # storage dtype of (frozen) base params
    remat: bool = True  # jax.checkpoint each block (grad-ckpt parity)
    remat_policy: str = "nothing_saveable"  # or "dots_with_no_batch_dims_saveable"
    # Selective remat: every remat_stride-th block skips jax.checkpoint and
    # keeps its activations (1 = remat every block, the DeepSpeed
    # gradient-checkpointing default). Spends HBM headroom to cut the
    # recompute forward: stride k removes 1/k of it.
    remat_stride: int = 1
    # How many blocks (the LAST ones: the backward frees theirs first, so
    # the step's peak stays at the loss) keep their activations although
    # ``remat`` is on. Not a user's knob. None: nobody has said, so a model
    # built from this keeps none and the trainer builds its own with the
    # count the device has room for (``training.remat_plan``); a number is
    # held to (scripts/train.py states 0 beside a stated --remat-policy or
    # --remat-stride).
    remat_keep_blocks: Optional[int] = None
    attention_impl: str = "auto"  # "auto" | "reference" | "flash"
    # Flash kernel tiles. flash_block_q counts query ROWS across the GQA
    # group (the kernel flattens a kv head's query heads into the row
    # dimension), so a tile covers flash_block_q // group positions of each
    # head, rounded down to a power of two: 512 positions for MHA, 128 for
    # Mistral's group of 4. The (rows, block_kv) f32 score tile — what
    # fills VMEM — is then the same size for every model.
    flash_block_q: int = 512
    flash_block_kv: int = 512
    # Packed batches: an upper bound on any packed document's token count
    # (0 = unknown). Intra-document attention can never span further back
    # than the document's own length, so combined with segment masking a
    # window of this size is *exact* — and lets the flash kernel run its
    # banded sweep (O(seq x bound) FLOPs and DMA) instead of the causal
    # triangle. scripts/train.py sets it from the measured corpus when
    # packing. Ignored for unpacked batches.
    packed_attention_window: int = 0
    # Serving decode over the paged cache: "auto" uses the Pallas in-place
    # block-table kernel on TPU and the XLA gather path elsewhere;
    # "kernel" forces the kernel (interpreted off-TPU, for tests);
    # "gather" forces the XLA path.
    paged_attention_impl: str = "auto"
    # Hybrid families (nemotron_h): ONE mixer per layer behind one pre-norm
    # residual, named by character i of the pattern: "M" Mamba-2, "E" routed
    # experts, "*" attention. "" = the Llama block in every layer. A
    # patterned model is built by dlti_tpu.models.build_model.
    # The decoder-hybrid-decoder family (SambaY, models.sambay) has a mixer
    # AND a gated MLP a layer, both behind a LayerNorm with bias, and its
    # own kinds, never mixed with the three above: "S" Mamba-1, "D"
    # differential attention over the layer's own keys and values (under
    # the layer's ``layer_windows`` entry), "G" a gated memory unit that
    # reads the scan output of the last "S" before it
    # (``shared_memory_layer``), "X" differential cross-attention with a
    # query projection alone over the pool of the last "D" before it
    # (``shared_kv_layer``, which sees every key).
    # The jamba family (models.jamba) has a mixer and a gate/up/down MLP a
    # layer behind RMSNorms: "S" Mamba-1, "A" plain causal attention over
    # the layer's own keys and values (no window, no rotation).
    layer_pattern: str = ""
    rope: bool = True  # False: attention applies no rotary embedding
    # Mamba-2 mixer ("M"): d_inner = heads * head_dim; B and C are shared by
    # runs of heads / n_groups; the recurrent state is (heads, head_dim,
    # state_size) a sequence, kept by decode slot in ``mamba_state_dtype``.
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    mamba_state_size: int = 0
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 128  # the prefill scan's block; changes no result
    mamba_state_dtype: str = "float32"
    # Mamba-1 mixer ("S", models.mamba1): d_inner = ``mamba_expand`` x
    # hidden_size channels, each with a state of ``mamba_state_size``
    # values and a time step projected through ``mamba_dt_rank`` values;
    # ``mamba_conv_kernel`` as above. The gated memory unit ("G") is as
    # wide as d_inner.
    mamba_expand: int = 0
    mamba_dt_rank: int = 0
    # RMSNorms with learned weights on the time step's ``mamba_dt_rank``
    # values and on B and C inside the Mamba-1 mixer (jamba's own).
    mamba_inner_norms: bool = False
    # The projections that carry LoRA adapters in this family, where they
    # are not ``LoRAConfig.target_modules`` (the configuration states them:
    # every place that builds a LoRAConfig with the default targets then
    # builds the same adapter tree). (): the LoRAConfig's.
    lora_targets: tuple = ()
    # Dropless routed experts ("E", models.moe.HeldExpertsMLP): the router
    # scores all ``moe_num_experts``; this process holds (and computes)
    # experts [moe_held_start, moe_held_start + moe_held_count) — 0 = all —
    # as expert parallelism gives a chip its share. top-k is
    # ``num_experts_per_tok``; ``mlp_activation`` gives an expert's form:
    # "relu2" ungated, down(relu(up x)^2), as nemotron_h's are; "silu"
    # gated, down(silu(gate x) * up x), as the deepseek_v3 family's.
    moe_num_experts: int = 0
    moe_held_start: int = 0
    moe_held_count: int = 0
    moe_intermediate_size: int = 0
    moe_shared_intermediate_size: int = 0  # 0 = no shared expert
    moe_scoring: str = "sigmoid_bias"  # sigmoid + selection bias, renormed
    moe_routed_scaling: float = 1.0
    # Latent attention (MLA, the deepseek_v3 family; models.latent).
    # ``kv_lora_rank`` > 0 selects it: a token's cache entry is one row of
    # ``kv_lora_rank + qk_rope_head_dim`` values a layer (the normed latent
    # and one rotary key shared by all heads), from which keys
    # (``qk_nope_head_dim`` a head) and values (``v_head_dim``) are
    # up-projected, or into which the queries are absorbed. The first
    # ``first_k_dense`` layers have a dense MLP of ``intermediate_size``,
    # the rest HeldExpertsMLP. The Llama block reads ``first_k_dense`` the
    # same way where ``moe_num_experts`` > 0 (``num_experts`` keeps meaning
    # the capacity layer MoEMLP in every block).
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False  # rotary pairs (2i, 2i+1), not halves
    first_k_dense: int = 0
    # A query latent: q = RMSNorm(x W_qa) W_qb through ``q_lora_rank``
    # values (0: one query projection).
    q_lora_rank: int = 0
    # YaRN (``ops.rope.yarn_inv_freq``): the public config's ``rope_scaling``
    # object (factor, original_max_position_embeddings, beta_fast,
    # beta_slow, mscale, mscale_all_dim), kept as sorted (key, value) pairs
    # so that the configuration stays hashable. None: plain RoPE.
    rope_scaling: Optional[tuple] = None
    # Hyper-connected residual streams (mHC; models.hyper): ``hc_mult`` > 0
    # widens the residual path to that many streams, mixed round every
    # sublayer by maps computed from the token, one of them projected onto
    # doubly stochastic matrices by ``hc_sinkhorn_iters`` Sinkhorn rounds.
    # 0: the plain residual x + F(norm(x)).
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # Multi-token-prediction modules the published model carries after its
    # layers. No model here builds one and the engine refuses to be asked
    # for one (serving.executor.refuse_unsupported).
    num_nextn_predict_layers: int = 0

    def __post_init__(self):
        if self.rope_scaling is not None:
            pairs = (self.rope_scaling.items()
                     if isinstance(self.rope_scaling, dict)
                     else self.rope_scaling)
            object.__setattr__(self, "rope_scaling", tuple(
                sorted((str(k), v) for k, v in pairs)))
        windows = tuple(int(w or 0) for w in self.layer_windows)
        object.__setattr__(self, "layer_windows", windows)
        object.__setattr__(self, "lora_targets",
                           tuple(str(t) for t in self.lora_targets))
        if windows and len(windows) != self.num_layers:
            raise ValueError(
                f"layer_windows states {len(windows)} windows for "
                f"num_layers={self.num_layers}")
        if len(set(windows)) > 1 and (
                0 not in windows or len(set(windows)) > 2):
            raise ValueError(
                f"layer_windows {sorted(set(windows))}: the serving cache "
                f"keeps one pool for the layers that see every key and one "
                f"for the layers of ONE window; several window lengths in "
                f"one model are not implemented")
        if self.sandwich_norm and self.post_sublayer_norm:
            raise ValueError(
                "sandwich_norm puts a norm before AND after each sublayer; "
                "post_sublayer_norm moves a block's two norms after them: "
                "state one of the two")
        if self.ut_steps < 1:
            raise ValueError(f"ut_steps {self.ut_steps}: at least one pass")
        if self.ut_steps > 1 and (
                self.layer_pattern or self.kv_lora_rank
                or self.num_experts or self.moe_num_experts
                or len(set(windows)) > 1):
            raise ValueError(
                f"ut_steps {self.ut_steps}: several passes over one set of "
                f"weights are implemented for the dense Llama family with "
                f"one attention window alone, not for a layer_pattern, "
                f"latent attention, experts or layer_windows that differ")
        if self.ut_steps > 1 and self.early_exit_threshold < 1.0:
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold}: every "
                f"token runs every pass here (rows that leave at different "
                f"passes are not implemented); state 1.0")
        kinds = set(self.layer_pattern)
        if self.layer_pattern and (
                len(self.layer_pattern) != self.num_layers
                or not (kinds <= set("ME*") or kinds <= set(SAMBAY_KINDS)
                        or kinds <= set(JAMBA_KINDS))):
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r} must name one mixer "
                f"(M, E or *; or S, D, G or X; or S and A; the three sets "
                f"never mixed) for each of num_layers={self.num_layers}")
        if self.is_jamba and (self.layer_windows or self.sliding_window
                              or self.rope):
            raise ValueError(
                f"layer_pattern {self.layer_pattern!r}: an A layer sees "
                f"every key of its document and rotates nothing (state "
                f"rope false, no sliding_window and no layer_windows)")
        if self.is_sambay:
            pattern = self.layer_pattern
            if "G" in pattern and "S" not in pattern[:pattern.index("G")]:
                raise ValueError(
                    f"layer_pattern {pattern!r}: a gated memory unit (G) "
                    f"reads the scan output of a Mamba-1 layer (S) before it")
            if "X" in pattern and (
                    "D" not in pattern[:pattern.index("X")]
                    or self.window_of_layer(self.shared_kv_layer)):
                raise ValueError(
                    f"layer_pattern {pattern!r}: a cross-attention layer "
                    f"(X) reads the pool of the last D layer before it, "
                    f"which has to see every key (layer_windows 0 there)")
            if any(w and k != "D" for w, k in zip(windows, pattern)):
                raise ValueError(
                    f"layer_windows {windows}: only a D layer of "
                    f"{pattern!r} keeps keys of its own under a window")

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    def window_of_layer(self, layer: int) -> Optional[int]:
        """Layer ``layer``'s attention window (None: every key)."""
        if self.layer_windows:
            return self.layer_windows[layer] or None
        return self.sliding_window

    @property
    def kv_group_windows(self) -> tuple:
        """The serving cache's groups of attention layers, as the window of
        each (0: every key): layers of equal window share block tables and
        an allocator. One group for a model whose layers agree (every
        model without ``layer_windows``), whatever the window; else the
        layers that see every key first, then the window's."""
        distinct = sorted(set(self.layer_windows))
        return tuple(distinct) if len(distinct) > 1 \
            else (self.window_of_layer(0) or 0,)

    def kv_group_of_layer(self, layer: int) -> int:
        groups = self.kv_group_windows
        return groups.index(self.layer_windows[layer]) \
            if len(groups) > 1 else 0

    @property
    def cache_entries(self) -> int:
        """Entries of a sequence's cache: one a (pass, layer). Whatever
        sizes the cache or counts its bytes reads this, never
        ``num_layers``: a layer's pool holds ``ut_steps`` entries."""
        return self.ut_steps * self.num_layers

    def num_params(self, include_lm_head: bool = True) -> int:
        """Analytic parameter count (for MFU and reporting): each layer's
        weights once, however many passes run over them."""
        return self._count_params(include_lm_head, active_only=False)

    def num_active_params(self, include_lm_head: bool = True) -> int:
        """Params touched per token — equals :meth:`num_params` for dense
        models; for MoE, k routed experts instead of all E (the count that
        drives FLOPs/token and MFU)."""
        return self._count_params(include_lm_head, active_only=True)

    @property
    def mamba_inner_size(self) -> int:
        """Channels of a state-space mixer: Mamba-2's heads x head_dim,
        Mamba-1's ``mamba_expand`` x hidden_size."""
        return (self.mamba_expand * self.hidden_size if self.mamba_expand
                else self.mamba_num_heads * self.mamba_head_dim)

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the causal convolution runs over: x, B and C."""
        return (self.mamba_inner_size
                + 2 * self.mamba_n_groups * self.mamba_state_size)

    @property
    def moe_held(self) -> int:
        """Routed experts this process holds in each expert layer."""
        return self.moe_held_count or self.moe_num_experts

    @property
    def has_recurrent_state(self) -> bool:
        return "M" in self.layer_pattern or "S" in self.layer_pattern

    @property
    def is_sambay(self) -> bool:
        """The decoder-hybrid-decoder family (``models.sambay``)."""
        return bool(self.layer_pattern) \
            and set(self.layer_pattern) <= set(SAMBAY_KINDS)

    @property
    def is_jamba(self) -> bool:
        """The jamba family (``models.jamba``): Mamba-1 and plain
        attention layers."""
        return "A" in self.layer_pattern \
            and set(self.layer_pattern) <= set(JAMBA_KINDS)

    def lora_targets_of(self, lora) -> tuple:
        """The projections that carry adapters under ``lora``
        (a LoRAConfig): this family's own where it states them."""
        return self.lora_targets or tuple(lora.target_modules)

    @property
    def shared_memory_layer(self) -> Optional[int]:
        """The "S" layer whose scan output every "G" layer reads: the last
        before the first "G" (None: no memory unit)."""
        upto = self.layer_pattern.find("G")
        return self.layer_pattern.rfind("S", 0, upto) if upto > 0 else None

    @property
    def shared_kv_layer(self) -> Optional[int]:
        """The "D" layer whose pool every "X" layer reads: the last before
        the first "X" (None: no cross-attention layer)."""
        upto = self.layer_pattern.find("X")
        return self.layer_pattern.rfind("D", 0, upto) if upto > 0 else None

    @property
    def latent_dim(self) -> int:
        """Width of a token's latent cache row (0: no latent attention)."""
        return self.kv_lora_rank + self.qk_rope_head_dim \
            if self.kv_lora_rank else 0

    @property
    def yarn(self) -> Optional[dict]:
        """``rope_scaling`` as the object the public config states."""
        return dict(self.rope_scaling) if self.rope_scaling else None

    def _held_expert_layer_params(self, active_only: bool) -> int:
        """One HeldExpertsMLP layer: of the routed experts those held here
        (active: top-k of the router's width, of which the held share is
        what this process computes in the mean), the shared expert, the
        router and its bias. Gated experts have three matrices, relu2 two."""
        h, f = self.hidden_size, self.moe_intermediate_size
        mats = 3 if self.mlp_activation == "silu" else 2
        n_routed = (self.num_experts_per_tok * self.moe_held
                    / max(1, self.moe_num_experts)
                    if active_only else self.moe_held)
        return (int(n_routed * mats * h * f)
                + mats * h * self.moe_shared_intermediate_size
                + h * self.moe_num_experts + self.moe_num_experts)

    @property
    def held_pad_params(self) -> int:
        """Zeros the expert layers hold beside ``num_params()``: the routed
        experts' matrices lie at ``grouped_experts.held_width`` of the
        published width (models.moe.HeldExpertsMLP); 0 where that is the
        published width, as for every width the kernel takes."""
        if not self.moe_num_experts:
            return 0
        from dlti_tpu.ops.pallas.grouped_experts import held_width

        layers = self.layer_pattern.count("E") if self.layer_pattern \
            else self.num_layers - min(self.first_k_dense, self.num_layers)
        pad = held_width(self.moe_intermediate_size) \
            - self.moe_intermediate_size
        return (layers * self.moe_held * self.hidden_size * pad
                * (3 if self.mlp_activation == "silu" else 2))

    def _count_params(self, include_lm_head: bool, active_only: bool) -> int:
        h, m, v = self.hidden_size, self.intermediate_size, self.vocab_size
        hd = self.resolved_head_dim
        q = h * self.num_heads * hd
        kv = 2 * h * self.num_kv_heads * hd
        o = self.num_heads * hd * h
        attn = q + kv + o
        if self.attention_bias:
            attn += (self.num_heads + 2 * self.num_kv_heads) * hd
        if self.kv_lora_rank:
            # Latent attention, then a dense MLP (the leading layers) or the
            # held experts with their router, bias and shared expert.
            r, nope, rope_d, vd = (self.kv_lora_rank, self.qk_nope_head_dim,
                                   self.qk_rope_head_dim, self.v_head_dim)
            qk = self.num_heads * (nope + rope_d)
            query = (h * self.q_lora_rank + self.q_lora_rank
                     + self.q_lora_rank * qk) if self.q_lora_rank else h * qk
            attn = (query + h * (r + rope_d)
                    + r + r * self.num_heads * (nope + vd)
                    + self.num_heads * vd * h)
            # the stream maps of both sublayers (models.hyper.HyperMaps)
            n = self.hc_mult
            attn += 2 * ((n * h + 1) * (n * n + 2 * n) + 3) if n else 0
            experts = self._held_expert_layer_params(active_only)
            dense = min(self.first_k_dense, self.num_layers)
            total = (v * h + h + self.num_layers * (attn + 2 * h)
                     + dense * 3 * h * m + (self.num_layers - dense) * experts)
            if include_lm_head and not self.tie_embeddings:
                total += h * v
            return total
        if self.is_sambay:
            # A mixer, a gated MLP (fc1 holds gate and up) and two
            # LayerNorms with bias a layer; a final LayerNorm; a tied head.
            d_in, n, r = (self.mamba_inner_size, self.mamba_state_size,
                          self.mamba_dt_rank)
            heads = self.num_heads * hd
            diff = 4 * hd + 2 * hd  # four lambda vectors, the head norm
            per_kind = {
                "S": (h * 2 * d_in + d_in * (self.mamba_conv_kernel + 1)
                      + d_in * (r + 2 * n) + r * d_in + d_in
                      + d_in * n + d_in + d_in * h),
                "D": (h * (heads + 2 * self.num_kv_heads * hd) + heads
                      + 2 * self.num_kv_heads * hd + heads * h + h + diff),
                "G": h * d_in + d_in * h,
                "X": h * heads + heads + heads * h + h + diff,
            }
            total = v * h + 2 * h + sum(per_kind[c] + 3 * h * m + 4 * h
                                        for c in self.layer_pattern)
            if include_lm_head and not self.tie_embeddings:
                total += h * v
            return total
        if self.is_jamba:
            # A mixer and a gate/up/down MLP behind an RMSNorm each; a
            # final RMSNorm; the inner norms on dt, B and C where stated.
            d_in, n, r = (self.mamba_inner_size, self.mamba_state_size,
                          self.mamba_dt_rank)
            per_kind = {
                "S": (h * 2 * d_in + d_in * (self.mamba_conv_kernel + 1)
                      + d_in * (r + 2 * n) + r * d_in + d_in
                      + d_in * n + d_in + d_in * h
                      + (r + 2 * n if self.mamba_inner_norms else 0)),
                "A": attn,
            }
            total = v * h + h + sum(per_kind[c] + 3 * h * m + 2 * h
                                    for c in self.layer_pattern)
            if include_lm_head and not self.tie_embeddings:
                total += h * v
            return total
        if self.layer_pattern:
            # One mixer and one norm a layer; of the routed experts, those
            # held here (active: top-k of the router's width, of which the
            # held share is what this process computes on average).
            d_in, heads = self.mamba_inner_size, self.mamba_num_heads
            mamba = (h * (2 * d_in + 2 * self.mamba_n_groups
                          * self.mamba_state_size + heads)
                     + self.mamba_conv_dim * (self.mamba_conv_kernel + 1)
                     + 3 * heads + d_in + d_in * h)
            experts = self._held_expert_layer_params(active_only)
            per_kind = {"M": mamba, "*": attn, "E": experts}
            total = v * h + h + sum(per_kind[c] + h
                                    for c in self.layer_pattern)
            if include_lm_head and not self.tie_embeddings:
                total += h * v
            return total
        if self.qk_norm:
            attn += 2 * hd
        if self.sandwich_norm:
            attn += 2 * h  # the second norm of each sublayer
        # the exit gate of a looped stack: Linear(hidden, 1) with bias
        gate = h + 1 if self.ut_steps > 1 else 0
        if self.moe_num_experts > 0:
            # Held experts under the Llama block: the leading layers dense,
            # the rest the held experts with router, bias and shared expert.
            experts = self._held_expert_layer_params(active_only)
            dense = min(self.first_k_dense, self.num_layers)
            total = (v * h + h + gate + self.num_layers * (attn + 2 * h)
                     + dense * 3 * h * m + (self.num_layers - dense) * experts)
            if include_lm_head and not self.tie_embeddings:
                total += h * v
            return total
        if self.num_experts > 0:
            n_ffn = (self.num_experts_per_tok if active_only
                     else self.num_experts)
            mlp = n_ffn * 3 * h * m + h * self.num_experts  # experts + router
        else:
            mlp = 3 * h * m
        norms = 2 * h
        per_layer = attn + mlp + norms
        # embed + layers + final norm + exit gate
        total = v * h + self.num_layers * per_layer + h + gate
        if include_lm_head and not self.tie_embeddings:
            total += h * v
        return total


@dataclass(frozen=True)
class LoRAConfig:
    """LoRA adapter config.

    Matches the reference graft: r=16, alpha=32, dropout 0.05, q/k/v/o
    projections, no bias (``training/train_baseline.py:131-140``).
    """

    enabled: bool = True
    r: int = 16
    alpha: int = 32
    dropout: float = 0.05
    target_modules: tuple = ("q_proj", "k_proj", "v_proj", "o_proj")

    @property
    def scaling(self) -> float:
        return self.alpha / self.r


@dataclass(frozen=True)
class OptimizerConfig:
    """AdamW + WarmupLR, mirroring ``configs/ds_config_zero1.json:6-23,44``."""

    learning_rate: float = 2e-4
    betas: tuple = (0.9, 0.999)
    eps: float = 1e-8
    weight_decay: float = 0.0
    warmup_steps: int = 100
    grad_clip: float = 1.0
    schedule: str = "warmup_constant"  # or "warmup_cosine"
    total_steps: int = 0  # used by cosine schedule; 0 = constant after warmup


@dataclass(frozen=True)
class ParallelConfig:
    """Mesh shape + strategy.

    Axes: ``data`` (DP / ZeRO), ``fsdp`` (param sharding, ZeRO-3), ``tensor``
    (TP over ICI, for serving and large models), ``sequence`` (context /
    ring-attention parallelism for long sequences).
    """

    zero_stage: ZeROStage = ZeROStage.NONE
    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    sequence: int = 1
    # Pipeline parallelism: the layer stack is split into `pipe` stages and
    # microbatches flow through a GPipe schedule (dlti_tpu.parallel.pipeline).
    pipe: int = 1
    # Expert parallelism: MoE expert weights and buffers shard over this
    # axis (all-to-all dispatch inserted by GSPMD).
    expert: int = 1
    # ZeRO-3 host offload parity (configs/ds_config_zero3.json:19-27).
    # offload_optimizer places optimizer state in pinned host memory (wired
    # in opt_state_shardings); offload_params places the frozen base params
    # in pinned host memory — streamed into the compiled step as host
    # operands when the runtime supports it, else moved at step boundaries
    # (make_sharded_train_step).
    offload_optimizer: bool = False
    offload_params: bool = False

    @property
    def num_devices(self) -> int:
        return (self.data * self.fsdp * self.tensor * self.sequence
                * self.pipe * self.expert)

    @property
    def dp_like_size(self) -> int:
        """Total batch-sharding degree (data * fsdp axes both carry batch)."""
        return self.data * self.fsdp


@dataclass(frozen=True)
class DataConfig:
    """Data pipeline config (reference: ``scripts/prepare_dataset.py``)."""

    dataset_path: str = "./data/glaive_code_full"
    dataset_name: str = "glaiveai/glaive-code-assistant"
    tokenizer: str = "meta-llama/Llama-2-7b-hf"
    max_seq_len: int = 512  # reference truncation (train_baseline.py:155)
    pack_sequences: bool = False  # reference does not pack; packing is a perf option
    num_samples: Optional[int] = None
    shuffle_seed: int = 0
    # Background batch prefetch depth (dlti_tpu.data.prefetch): the
    # Trainer runs batch gather/pack and the ahead-of-need device_put on a
    # worker thread, double-buffered this many batches deep, so the device
    # never waits on host batch prep. Batch order (and so the loss
    # trajectory) is bit-identical to the synchronous path. 0 = off
    # (legacy inline fetch).
    prefetch_depth: int = 2


@dataclass(frozen=True)
class CheckpointConfig:
    """Checkpoint / resume policy.

    Reference policies: baseline per-epoch keep-2 (``train_baseline.py:188-189``),
    ZeRO-1/2 per-100-steps keep-3 (``train_deepspeed_zero1.py:243-245``),
    ZeRO-3 per-epoch keep-2 (``train_deepspeed_zero3.py:234-236``).
    """

    output_dir: str = "./checkpoints/run"
    save_strategy: str = "steps"  # "steps" | "epoch" | "no"
    save_steps: int = 100
    save_total_limit: int = 3
    # Scan-latest-and-resume (train_deepspeed_zero1.py:267-279) — since the
    # crash-consistency pass, "latest" means latest *verified*: checkpoints
    # failing digest verification are quarantined and resume falls back to
    # the newest good one (dlti_tpu.checkpoint.store).
    resume: bool = True
    async_save: bool = True
    # Bounded retry/backoff for transient checkpoint-write failures (a
    # failed save is logged loudly but never kills the training run).
    save_retries: int = 3
    save_retry_backoff_s: float = 0.2


@dataclass(frozen=True)
class SentinelConfig:
    """Numeric-fault sentinel (``dlti_tpu.training.sentinel``): per-step
    nonfinite/spike detection over the compiled step's own metrics (no
    extra host syncs), automatic rollback to the last verified checkpoint
    with strike-counted data quarantine, and a periodic cross-rank
    parameter-digest probe that attributes silent data corruption to a
    suspect host for the elastic supervisor to evict."""

    # Host-side detection (spike windows, anomaly streaks, steplog
    # fields). The in-step nonfinite update gate is always compiled in —
    # it is a correctness fix, not an option.
    enabled: bool = True
    # Rolling-median spike window and its cold-start sample floor.
    window: int = 32
    min_samples: int = 8
    # Spike thresholds: latest > factor x rolling median (loss moves
    # slowly; grad norms are noisy, hence the wider factor).
    loss_spike_factor: float = 2.0
    grad_spike_factor: float = 10.0
    # Consecutive anomalous steps before automatic rollback to the last
    # verified checkpoint (0 = never roll back; detection still runs).
    rollback_after: int = 3
    # Total rollbacks allowed per run; exceeding raises SentinelGiveUp
    # (anomalies that survive every recovery need a human).
    max_rollbacks: int = 8
    # Strikes (rollbacks implicating a window) before that data window is
    # quarantined permanently; below that it is replayed (transient
    # faults pass on the second try).
    quarantine_after: int = 2
    # Cross-rank param-digest probe cadence in optimizer steps (0 = off;
    # multi-process runs only).
    sdc_check_interval: int = 0


@dataclass(frozen=True)
class TrainConfig:
    """Training loop knobs (reference: ``TrainingArguments`` uses across scripts)."""

    num_epochs: int = 1
    max_steps: int = 0  # 0 = derive from epochs * steps_per_epoch
    # GLOBAL microbatch per forward/backward (summed over all data-parallel
    # devices and hosts; must be divisible by data*fsdp mesh extent). The
    # reference's per-device bs=1 on N GPUs corresponds to micro_batch_size=N
    # here (train_baseline.py:64-68).
    micro_batch_size: int = 1
    grad_accum_steps: int = 16  # train_baseline.py:69-75
    logging_steps: int = 10  # train_baseline.py:184
    seed: int = 42
    eval_steps: int = 0  # 0 = no eval
    # Reference metrics contract: append one row per run
    # (training/utils.py:51-69 -> results/training_metrics.csv).
    metrics_csv: str = "results/training_metrics.csv"
    # fp16 dynamic loss scaling — parity with the reference's DeepSpeed fp16
    # block (configs/ds_config_zero1.json:25-32: loss_scale 0 = dynamic,
    # initial 2^16, window 1000, hysteresis 2, min_loss_scale 1). bf16 (the
    # TPU default) needs none of this; enable only for fp16 parity runs
    # (pair with ModelConfig dtype="float16").
    fp16: bool = False
    fp16_initial_scale_power: int = 16
    # Weight-only quantization of the *frozen* base params during LoRA
    # training ("" = off, "int8" = symmetric per-channel int8 — the QLoRA
    # idea, TPU-style). Grads flow only to the LoRA factors, so the base
    # may rest compressed: a 7B bf16 base is ~13.5 GB of a 16 GB chip,
    # int8 is ~6.8 GB — the freed HBM buys back remat recompute
    # (activation saving). Requires lora.enabled.
    quantize_frozen_base: str = ""
    # Sequence-chunked cross-entropy (0 = off): compute the LM-head matmul
    # + softmax-CE loss_chunk positions at a time inside a rematerialized
    # scan, so (B, S, vocab) fp32 logits are never whole in HBM. Not for
    # sequence-parallel or MoE runs.
    loss_chunk: int = 0
    # Optimizer steps per host sync (1 = classic loop): with K > 1 the
    # Trainer scans K whole train steps into ONE compiled program
    # (lax.scan over stacked batches) and syncs metrics once per window —
    # the training analog of the serving engine's steps_per_sync
    # multi-step decode. Amortizes the fixed per-call host dispatch and
    # sync cost (not measured on the chip). Trajectory is identical to
    # K=1 (same per-step rng schedule); logging/metrics stay per-step;
    # eval/checkpoints land at window boundaries, and so do profiler
    # start/stop — a profile_num_steps < K trace captures a whole K-step
    # window (profile at steps_per_sync=1 for per-step traces). Not with
    # host offload (its step-boundary transfers are host-side) or
    # multi-host runs.
    steps_per_sync: int = 1
    fp16_scale_window: int = 1000
    fp16_hysteresis: int = 2
    fp16_min_scale: float = 1.0
    # jax.profiler trace capture (view in XProf/TensorBoard): writes a
    # trace of steps [profile_start_step, profile_start_step +
    # profile_num_steps) to profile_dir. Empty dir = no profiling.
    # The upgrade over the reference's wall_clock_breakdown:false
    # (configs/ds_config_zero1.json:48) — per-op device timelines.
    profile_dir: str = ""
    profile_start_step: int = 10
    profile_num_steps: int = 3
    # Deterministic-replay forensics (SURVEY.md §5.2 sanitizer analog):
    # persist a ring of (batch, rng, metrics) records so any recent step
    # can be re-executed bit-for-bit against a checkpoint
    # (dlti_tpu.utils.debug.replay_step). Empty dir = off.
    record_replay_dir: str = ""
    record_replay_every: int = 100
    record_replay_keep: int = 8
    # Deterministic trainer-side chaos hook ("STEP[:MODE]", MODE in raise |
    # kill | save-raise | save-kill — dlti_tpu.training.chaos), mirroring
    # the gateway's DLTI_GATEWAY_FAULT_INJECT. Also settable via env
    # DLTI_TRAIN_FAULT_INJECT. Chaos tests and fire drills use it to kill
    # the trainer at an exact step (or mid-async-save) and prove the
    # verified-resume path recovers. "" = off. The additional
    # "STEP:host-kill[:RANK]" mode is SUPERVISOR-owned (the elastic
    # launcher SIGKILLs a whole worker process from outside —
    # dlti_tpu.training.elastic.HostKillSpec); the in-process injector
    # ignores it. Numeric chaos modes (dlti_tpu.training.sentinel
    # drills): "STEP:nan-grad" poisons one batch's loss mask with NaN
    # (transient nonfinite step), "POS:poison-batch" deterministically
    # scrambles the batch at data position POS every time it is fed
    # (re-fires after rollback — the bad-data simulation), and
    # "STEP:param-flip[:RANK]" flips one mantissa bit in a replicated
    # param leaf on rank RANK (the silent-data-corruption simulation the
    # SDC probe must catch). Memory chaos: "STEP:hbm-squeeze" inflates a
    # balloon of device arrays (DLTI_CHAOS_BALLOON_BYTES, default 64 MiB)
    # and raises a RESOURCE_EXHAUSTED-shaped fault, driving the OOM
    # forensics path (flight dump with memory.json) deterministically on
    # CPU.
    fault_inject_step: str = ""
    # Numeric-fault sentinel (dlti_tpu.training.sentinel): see the
    # block's own docstring.
    sentinel: SentinelConfig = field(default_factory=SentinelConfig)


@dataclass(frozen=True)
class WatchdogConfig:
    """Anomaly watchdog (``dlti_tpu.telemetry.watchdog``): a rule engine
    over the in-process time-series ring. Disabled by default; alerts are
    structured events (JSONL log + ``dlti_watchdog_alerts_total{rule=}``
    counter + tracer instants) with a configurable escalation."""

    enabled: bool = False
    # Seconds between rule evaluations (also the time-series sampling
    # cadence the entry points use when the watchdog is on).
    interval_s: float = 1.0
    # Escalation on alert: "log" (record only), "dump" (also write a
    # flight record), "abort" (dump, SIGTERM self for the preemption
    # checkpoint, then hard-exit 86 — for CI chaos runs).
    action: str = "log"
    # JSONL alert event log ("" = alerts go to the logger/counter only).
    alert_log_path: str = ""
    # hung_step: no step completion within max(hung_step_min_s,
    # hung_step_factor x rolling-median step time) of the previous one.
    hung_step_factor: float = 10.0
    hung_step_min_s: float = 30.0
    # throughput_collapse: latest reading below floor_frac x rolling
    # median over at least min_samples ring samples. throughput_series
    # overrides the auto-watched set (train tok/s gauge + serving
    # generated_tokens rate).
    throughput_floor_frac: float = 0.25
    throughput_min_samples: int = 6
    throughput_series: str = ""
    # queue_buildup: gateway queue depth at/above this for 3 consecutive
    # samples (0 = rule off).
    queue_depth_limit: int = 0
    # shed_buildup: gateway sheds+rejections per second over the recent
    # window (0 = rule off).
    shed_rate_limit: float = 0.0
    # heartbeat_stale: a process heartbeat older than this (0 = rule off).
    heartbeat_stale_s: float = 0.0
    # ckpt_retry_storm: save retries accrued across the ring window.
    ckpt_retry_limit: int = 3
    # goodput_collapse: the goodput ledger's productive fraction (the
    # `goodput_fraction` ring series, telemetry.ledger) below
    # goodput_floor_frac x its rolling median over at least
    # goodput_min_samples samples (0 floor = rule off).
    goodput_floor_frac: float = 0.5
    goodput_min_samples: int = 8
    # hbm_pressure: the memory ledger's headroom fraction (the
    # `hbm_headroom_frac` ring series, telemetry.memledger — only
    # published when HBM capacity is known) dropped below this absolute
    # floor (0 = rule off).
    hbm_headroom_floor_frac: float = 0.0
    # disk_pressure: fires when free bytes on the persistence filesystem
    # (the `disk_free_bytes` ring series, utils.durable_io) drop below
    # this floor (0 = free-bytes check off; write-error growth and
    # degraded path classes always fire the rule).
    disk_free_floor_bytes: int = 0
    # replica_flap: fires when the serving replica-lifecycle flap
    # breaker evicts a replica (the flaps counter grew across the
    # watchdog window). 0 = rule off.
    replica_flap_limit: int = 1
    # slo_burn: fires when an SLO tracker reports a (objective, class)
    # burning through its error budget on a fast+slow window pair
    # (telemetry.slo). 0 = rule off even when a tracker is wired.
    slo_burn_limit: int = 1
    # canary_regression: fires when the deployment controller rolls a
    # candidate back (the dlti_deploy_rollbacks_total ring series grew
    # across the watchdog window) — a training run is producing
    # checkpoints the canary gates reject. 0 = rule off.
    canary_regression_limit: int = 1


@dataclass(frozen=True)
class SLOConfig:
    """Declarative SLO engine (``dlti_tpu.telemetry.slo``): objectives
    over existing SLIs, rolling error budgets per (objective, tenant
    class), multi-window multi-burn-rate alerts. Off by default; a zero
    threshold/target disables that objective family individually."""

    enabled: bool = False
    # Rolling error-budget window. An hour by default; drills shrink it
    # to seconds.
    window_s: float = 3600.0
    # Burn-rate alert tiers, "factor:long_s:short_s" comma-separated: a
    # tier fires when the burn rate exceeds factor over BOTH windows.
    burn_tiers: str = "14:60:5,6:300:30"
    # Latency objectives over the request-lifecycle histograms; the
    # threshold snaps to the nearest histogram bucket bound at/below it
    # (server and client then classify with the identical cut). 0 = off.
    ttft_threshold_s: float = 0.0
    ttft_target: float = 0.99
    tpot_threshold_s: float = 0.0
    tpot_target: float = 0.99
    queue_threshold_s: float = 0.0
    queue_target: float = 0.99
    # Admission availability per tenant class (admitted − shed over
    # admitted + rejected, from the gateway's counters). 0 = off.
    availability_target: float = 0.0
    # Training goodput: wall time counts as good while the ledger's
    # goodput fraction sits at/above the floor. 0 floor = off.
    goodput_floor: float = 0.0
    goodput_target: float = 0.99


@dataclass(frozen=True)
class FlightRecorderConfig:
    """Flight recorder (``dlti_tpu.telemetry.flightrecorder``): on fatal
    exception, SIGTERM, replica death, chaos fault, or watchdog
    escalation, dump a ``flight-*/`` black box (span tail, metrics
    snapshot, time-series tail, live context, config fingerprint) that
    ``scripts/postmortem.py`` renders. Enabled by setting ``dir``."""

    dir: str = ""  # "" = recorder off
    max_spans: int = 4096       # tracer events kept in spans.json
    timeseries_tail: int = 240  # ring samples kept in timeseries.json
    keep: int = 8               # dump dirs retained (oldest deleted)

    @property
    def enabled(self) -> bool:
        return bool(self.dir)


@dataclass(frozen=True)
class TelemetryConfig:
    """Unified telemetry layer (``dlti_tpu.telemetry``): span tracing,
    per-step JSONL stream, multi-host heartbeat. All off by default — the
    tracer's disabled path is one attribute read per span site."""

    # Directory for Chrome-trace JSON exports (Perfetto-viewable) of the
    # host-side span tracer: per-step trainer phases (batch fetch,
    # host→device, dispatch, sync, eval, save) and per-request engine
    # lifecycle spans. "" = tracer disabled.
    trace_dir: str = ""
    # Span ring-buffer capacity (events kept; oldest dropped beyond it).
    trace_capacity: int = 65536
    # Per-step JSONL telemetry stream (rank-0): step, loss, grad_norm, lr,
    # tokens/s/chip, MFU, HBM peak — a superset of the reference CSV
    # columns (telemetry.steplog). "" = off.
    step_log_path: str = ""
    # Multi-host heartbeat cadence in optimizer steps (0 = off): every
    # process reports its step (collective on multi-host meshes) and rank
    # 0 logs straggler lag.
    heartbeat_interval_steps: int = 0
    # Goodput ledger (telemetry.ledger): book every wall-clock second of
    # the run to one bucket (step compute, data wait, device sync, ckpt
    # save/restore, rollback + replay, SDC probe, ...) and derive the
    # goodput fraction + per-phase steplog fields. On by default — a
    # transition is ~a clock read; False reduces every site to one
    # attribute read (the tracer's disabled-path contract).
    goodput_ledger: bool = True
    # Memory ledger (telemetry.memledger): attribute device bytes to
    # named owners (params, optimizer state, KV pool, ...), reconcile
    # against jax.live_arrays()/memory_stats(), and feed the
    # hbm_* steplog fields, /debug/memory, and memory.json OOM
    # forensics. On by default; False reduces every site to one
    # attribute read.
    memory_ledger: bool = True
    # HBM capacity budget in bytes for headroom accounting (0 =
    # auto-detect from device memory_stats(); stays unknown on CPU,
    # where headroom-dependent features simply stay off).
    hbm_budget_bytes: int = 0
    # Self-monitoring: anomaly watchdog rules + flight-recorder black box
    # (see the blocks' own docstrings). Both off by default.
    watchdog: WatchdogConfig = field(default_factory=WatchdogConfig)
    flight_recorder: FlightRecorderConfig = field(
        default_factory=FlightRecorderConfig)
    # Declarative SLOs + error-budget burn alerting (telemetry.slo; see
    # the block's own docstring). Off by default.
    slo: SLOConfig = field(default_factory=SLOConfig)


@dataclass(frozen=True)
class GatewayConfig:
    """Admission gateway (``dlti_tpu.serving.gateway``): the scheduling
    front-end between the HTTP layer and the engine(s). Disabled by default
    — the server then admits directly into the engine, byte-for-byte the
    legacy behavior."""

    enabled: bool = False
    # Bounded admission queue: overflow is rejected with HTTP 429 +
    # Retry-After instead of growing without limit. 0 queued tokens = no
    # token bound (request-count bound still applies).
    max_queued_requests: int = 256
    max_queued_tokens: int = 0
    # Per-tenant token-bucket rate limiting (requests/s, sustained). 0 =
    # off. Burst is the bucket capacity; 0 derives max(1, 2*rps).
    rate_limit_rps: float = 0.0
    rate_limit_burst: float = 0.0
    # Weighted fair dequeue across tenants: "tenantA:4,tenantB:1" gives
    # tenantA 4x tenantB's dequeue share under contention. Unlisted
    # tenants weigh 1.
    tenant_weights: str = ""
    default_tenant: str = "default"
    # Tenant → LoRA adapter routing for multi-LoRA serving
    # (dlti_tpu.serving.adapters): "tenantA:ad1,tenantB:ad2" decodes
    # tenantA's requests under registered adapter ad1 unless the request
    # carries its own X-Adapter header. Unlisted tenants use the shared
    # base ("" = no mapping).
    adapter_map: str = ""
    # Retry-After value (seconds) for queue-bound rejections (rate-limit
    # rejections compute their own from the bucket deficit).
    retry_after_s: float = 1.0
    # Replica failover: how many times one request may be resubmitted onto
    # a surviving replica after its replica's step() faulted.
    max_retries: int = 2
    # Cache-affinity routing (dlti_tpu.serving.replicas): route each
    # request to its sticky rendezvous-hash replica (key = X-Session
    # header, else a digest of the first affinity_prefix_tokens prompt
    # ids) so repeat sessions land on the replica whose prefix cache is
    # warm; spill least-loaded when the sticky target's backlog exceeds
    # its decode slots by more than affinity_spill_threshold.
    affinity: bool = False
    affinity_spill_threshold: int = 4
    affinity_prefix_tokens: int = 32
    # Graceful drain: seconds SIGTERM waits for in-flight requests before
    # the server exits anyway.
    drain_grace_s: float = 30.0
    # Deterministic chaos hook: "REPLICA:STEP[:MODE]" kills replica
    # REPLICA on its STEP-th step() call (1-based). MODE "raise"
    # (default) raises in place of a device fault; "nan-logits" poisons
    # the replica's params with NaN so the engine's REAL numeric output
    # guard (EngineConfig.guard_nonfinite) detects the garbage and trips
    # the same quarantine path; "preempt" simulates a planned preemption
    # notice — the replica drains via live KV migration to survivors and
    # enters the lifecycle quarantine (no fault dump). Also settable via
    # env DLTI_GATEWAY_FAULT_INJECT; tests and chaos runs use it to
    # exercise failover without a real device fault.
    fault_inject_step: str = ""


@dataclass(frozen=True)
class PrefixTierConfig:
    """Hierarchical prefix-cache tiering
    (``dlti_tpu.serving.prefix_tiers``): evicted HBM prefix blocks demote
    to a bounded host-RAM tier and from there to digest-verified block
    dirs on disk; a prefix match in a lower tier restores blocks with a
    host→device scatter instead of a re-prefill. All tiers off by
    default (eviction discards, the legacy behavior). Maps onto
    ``EngineConfig.prefix_{host_blocks,disk_dir,disk_blocks}`` (see
    ``scripts/serve.py``)."""

    host_blocks: int = 0     # host-RAM tier budget, in KV blocks (0 = off)
    disk_dir: str = ""       # disk-tier directory ("" = disk tier off)
    disk_blocks: int = 0     # disk-tier budget, in block dirs (0 = off)

    @property
    def enabled(self) -> bool:
        return self.host_blocks > 0 or (bool(self.disk_dir)
                                        and self.disk_blocks > 0)


@dataclass(frozen=True)
class DisaggConfig:
    """Prefill/decode disaggregation (``dlti_tpu.serving.disagg``): split
    the replica fleet into a prefill pool and a decode pool, migrating
    each finished prefill's paged-KV blocks to a decode replica over the
    tier-restore path. Off by default — colocated serving is untouched."""

    enabled: bool = False
    prefill_replicas: int = 1
    decode_replicas: int = 1
    # Per-decode-replica bound on staged handoff snapshots: a full queue
    # backpressures the prefill pool (finished prefills stay in their
    # slots, which shrinks gateway dispatch room) instead of growing
    # host memory without limit.
    handoff_queue_depth: int = 8
    # Staged snapshots older than this re-prefill on the decode side
    # instead of waiting for a slot (0 = wait indefinitely; the request's
    # own gateway deadline still cancels it).
    handoff_deadline_s: float = 0.0
    # Deterministic chaos hook: "POOL:REPLICA:STEP[:MODE]" with POOL in
    # ("prefill", "decode") — same STEP/MODE semantics as
    # GatewayConfig.fault_inject_step, scoped to one pool member.
    fault_inject_step: str = ""


@dataclass(frozen=True)
class ReplicaLifecycleConfig:
    """Serving replica self-healing (``dlti_tpu.serving.lifecycle``): a
    faulted replica is quarantined instead of permanently evicted, its
    engine rebuilt from known-good weights, then reinstated only after a
    passing canary probe — with exponential probation backoff and a flap
    breaker (repeated quarantine/reinstate cycles inside a window →
    permanent eviction + watchdog alert). Off by default: with healing
    disabled a faulted replica stays dead forever (the legacy
    behavior)."""

    enabled: bool = False
    # Probation before the first reinstate probe, and the exponential
    # backoff applied per failed probe (delay = initial * backoff**fails,
    # capped at max).
    probation_initial_s: float = 2.0
    probation_backoff: float = 2.0
    probation_max_s: float = 60.0
    # Canary probe: a short greedy generation on the rebuilt replica,
    # checked against a digest pinned at fleet construction (and
    # re-pinned on weight reload).
    canary_prompt_tokens: int = 8
    canary_max_tokens: int = 4
    # Flap breaker: more than flap_max_cycles quarantines within
    # flap_window_s seconds evicts the replica permanently.
    flap_window_s: float = 300.0
    flap_max_cycles: int = 3


@dataclass(frozen=True)
class FleetConfig:
    """Multi-process serving fleet (``dlti_tpu.serving.fleet``): a
    supervisor process spawns N engine worker processes and drives them
    over the TCP wire protocol (``serving.wire``). Off by default — the
    in-process engine/replica paths are untouched."""

    workers: int = 2
    host: str = "127.0.0.1"
    # Worker startup bound: spawn -> jax import -> model build -> warmup
    # -> port published. Generous because warmup compiles the decode
    # ladder (first boot, cold compilation cache).
    startup_timeout_s: float = 600.0
    # Per-RPC socket timeout. A step can include a first-use prefill
    # bucket compile, so this is a liveness bound, not a latency target.
    rpc_timeout_s: float = 300.0
    # Idle heartbeat: refresh a worker's health/metrics snapshot when its
    # last contact is older than this (piggybacked on the step loop).
    health_interval_s: float = 2.0
    # Respawn backoff after a worker death (exponential, capped) and the
    # total respawns allowed per worker (elastic-launcher pattern).
    respawn_backoff_s: float = 0.5
    respawn_backoff_max_s: float = 30.0
    restart_budget: int = 8
    term_grace_s: float = 5.0
    max_frame_bytes: int = 256 * 1024 * 1024


@dataclass(frozen=True)
class SpeculativeConfig:
    """Adaptive speculative decoding (``dlti_tpu.serving.engine``): the
    n-gram prompt-lookup draft path plus its per-slot adaptive
    controller (acceptance-gated cooldowns and the pow2 draft-length
    ladder). Field names mirror the ``EngineConfig`` ``spec_*`` fields;
    :meth:`engine_kwargs` is the plumbing that applies the block to an
    engine build (``scripts/serve.py`` flags override it per run). Off
    by default — ``mode="none"`` keeps decode byte-identical to an
    engine that never compiled a spec program."""

    mode: str = "none"                 # "none" | "ngram"
    num_draft_tokens: int = 4
    ngram_size: int = 2
    adaptive: bool = True
    min_acceptance: float = 0.25
    probe_window: int = 64
    cooldown: int = 32

    def engine_kwargs(self) -> dict:
        """EngineConfig constructor kwargs for this block."""
        return {
            "speculative": self.mode,
            "num_draft_tokens": self.num_draft_tokens,
            "ngram_size": self.ngram_size,
            "spec_adaptive": self.adaptive,
            "spec_min_acceptance": self.min_acceptance,
            "spec_probe_window": self.probe_window,
            "spec_cooldown": self.cooldown,
        }


@dataclass(frozen=True)
class DeployConfig:
    """Continuous delivery (``dlti_tpu.serving.deploy``): a deployment
    controller that watches a training run's checkpoint directory for
    newly committed verified steps, auto-exports candidate weights
    through the digest-verified ``save_pytree`` path, canaries each
    candidate on one shadow replica under mirrored live traffic, and
    promotes fleet-wide (rolling reload) or rolls back — no human in the
    loop. Off by default; an empty ``watch_dir`` also keeps it off."""

    enabled: bool = False
    # Training checkpoint directory to watch (the checkpoint-store layout
    # scripts/train.py --output-dir writes). "" = controller off.
    watch_dir: str = ""
    # Where candidate exports land (save_pytree dirs named step-N;
    # rejected ones quarantine under <export_dir>/_quarantine).
    # "" = "<watch_dir>/_deploy_exports".
    export_dir: str = ""
    # Seconds between checkpoint-dir polls (injectable-clock ticks).
    poll_interval_s: float = 5.0
    # Fraction of live client submissions mirrored onto the canary as
    # shadow requests (results never reach clients).
    canary_shadow_frac: float = 0.25
    # Shadow-pair samples required before the gates are judged, and the
    # wall-clock bound a canary may wait for them (a quiet fleet judges
    # on the pinned probe set alone after the wait).
    canary_min_requests: int = 8
    canary_max_wait_s: float = 120.0
    # Gate 1 — greedy logprob drift: max |mean logprob delta| across the
    # pinned probe set, candidate vs incumbent baseline.
    promote_max_logprob_drift: float = 0.25
    # Gate 2 — output-length distribution shift: relative mean-length
    # delta between shadow (candidate) and paired live (incumbent)
    # completions (0 = gate off).
    max_length_shift_frac: float = 0.5
    # Gate 3 — per-phase SLO compliance on shadow requests: thresholds in
    # seconds (0 = that phase's gate off) and the compliant fraction
    # required.
    slo_ttft_threshold_s: float = 0.0
    slo_tpot_threshold_s: float = 0.0
    slo_min_compliance: float = 0.95
    # Pinned probe set: deterministic greedy prompts replayed against
    # every candidate and compared to the incumbent baseline.
    probe_prompts: int = 4
    probe_prompt_tokens: int = 8
    probe_max_tokens: int = 4
    # Promotion backoff for flapping candidates: after a rollback the
    # next candidate is not considered for initial * factor**rollbacks
    # seconds (capped), so a training run spewing bad checkpoints cannot
    # thrash the fleet with canary churn.
    promote_backoff_s: float = 30.0
    promote_backoff_factor: float = 2.0
    promote_backoff_max_s: float = 600.0


@dataclass(frozen=True)
class ServingConfig:
    """Serving-side config block (engine sizing stays in
    ``serving.engine.EngineConfig``; this holds the layers above it)."""

    gateway: GatewayConfig = field(default_factory=GatewayConfig)
    prefix_tiers: PrefixTierConfig = field(default_factory=PrefixTierConfig)
    disagg: DisaggConfig = field(default_factory=DisaggConfig)
    lifecycle: ReplicaLifecycleConfig = field(
        default_factory=ReplicaLifecycleConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    speculative: SpeculativeConfig = field(default_factory=SpeculativeConfig)
    deploy: DeployConfig = field(default_factory=DeployConfig)


@dataclass(frozen=True)
class Config:
    """Root config."""

    model: ModelConfig = field(default_factory=ModelConfig)
    lora: LoRAConfig = field(default_factory=LoRAConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    experiment_name: str = ""

    def replace(self, **kwargs: Any) -> "Config":
        return dataclasses.replace(self, **kwargs)

    # ------------------------------------------------------------------
    # Serialization (round-trips through JSON for checkpoint metadata)
    # ------------------------------------------------------------------
    def to_dict(self) -> dict:
        def _convert(obj: Any) -> Any:
            if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
                return {k: _convert(v) for k, v in dataclasses.asdict(obj).items()}
            if isinstance(obj, enum.Enum):
                return obj.value
            if isinstance(obj, tuple):
                return list(obj)
            return obj

        return _convert(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        def _build(dc_cls, sub: dict):
            fields = {f.name: f for f in dataclasses.fields(dc_cls)}
            kwargs = {}
            for k, v in sub.items():
                if k not in fields:
                    continue
                f = fields[k]
                if dataclasses.is_dataclass(f.type) or f.name in (
                    "model", "lora", "optimizer", "parallel", "data",
                    "checkpoint", "train", "telemetry", "serving", "gateway",
                    "watchdog", "flight_recorder", "prefix_tiers", "sentinel",
                    "disagg", "lifecycle", "slo", "fleet", "speculative",
                    "deploy",
                ):
                    sub_cls = {
                        "model": ModelConfig, "lora": LoRAConfig,
                        "optimizer": OptimizerConfig, "parallel": ParallelConfig,
                        "data": DataConfig, "checkpoint": CheckpointConfig,
                        "train": TrainConfig, "telemetry": TelemetryConfig,
                        "serving": ServingConfig, "gateway": GatewayConfig,
                        "watchdog": WatchdogConfig,
                        "flight_recorder": FlightRecorderConfig,
                        "prefix_tiers": PrefixTierConfig,
                        "sentinel": SentinelConfig,
                        "disagg": DisaggConfig,
                        "lifecycle": ReplicaLifecycleConfig,
                        "slo": SLOConfig,
                        "fleet": FleetConfig,
                        "speculative": SpeculativeConfig,
                        "deploy": DeployConfig,
                    }.get(f.name)
                    if sub_cls is not None and isinstance(v, dict):
                        kwargs[k] = _build(sub_cls, v)
                        continue
                if f.name == "zero_stage":
                    kwargs[k] = ZeROStage(v)
                elif isinstance(v, list):
                    kwargs[k] = tuple(v)
                else:
                    kwargs[k] = v
            return dc_cls(**kwargs)

        return _build(cls, d)

    @classmethod
    def from_json(cls, s: str) -> "Config":
        return cls.from_dict(json.loads(s))


# ----------------------------------------------------------------------
# Model size presets
# ----------------------------------------------------------------------

MODEL_PRESETS: dict = {
    # Test-scale model: tiny but structurally identical (GQA, SwiGLU, RoPE).
    "llama_tiny": ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=128, remat=False,
        dtype="float32", param_dtype="float32",
    ),
    # Small debug model (fits anywhere, exercises remat + bf16).
    "llama_debug": ModelConfig(
        vocab_size=4096, hidden_size=256, intermediate_size=512, num_layers=4,
        num_heads=8, num_kv_heads=4, max_seq_len=512,
    ),
    # ~374M config (32k untied vocab): the largest preset whose *full*
    # fine-tune (bf16 params + fp32 AdamW moments + fp32 grad
    # accumulators) fits one 16 GB chip — used for on-hardware
    # convergence runs.
    "llama_300m": ModelConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=24, num_heads=16, num_kv_heads=16, max_seq_len=2048,
    ),
    # ~1.1B TinyLlama-shaped config for single-chip benchmarking.
    "llama_1b": ModelConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=22, num_heads=32, num_kv_heads=4, max_seq_len=2048,
    ),
    # Llama-2-7B (the reference's model: meta-llama/Llama-2-7b-hf).
    "llama2_7b": ModelConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_layers=32, num_heads=32, num_kv_heads=32, max_seq_len=4096,
    ),
    # Llama-2-13B (BASELINE.json config #4: full fine-tune, ZeRO-3 multi-host).
    "llama2_13b": ModelConfig(
        vocab_size=32000, hidden_size=5120, intermediate_size=13824,
        num_layers=40, num_heads=40, num_kv_heads=40, max_seq_len=4096,
    ),
    # Llama-3-8B-shaped (GQA + large vocab), for generality.
    "llama3_8b": ModelConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
        rope_theta=500000.0,
    ),
    # Mistral-7B-v0.1: GQA + sliding-window local attention.
    "mistral_7b": ModelConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
        sliding_window=4096,
    ),
    # Qwen2-7B: biased q/k/v projections, big vocab, long RoPE period.
    "qwen2_7b": ModelConfig(
        vocab_size=152064, hidden_size=3584, intermediate_size=18944,
        num_layers=28, num_heads=28, num_kv_heads=4, max_seq_len=32768,
        rope_theta=1000000.0, attention_bias=True,
    ),
    # Gemma-7B: MHA with wide heads, (1+w) RMSNorm, scaled + tied embeddings.
    "gemma_7b": ModelConfig(
        vocab_size=256000, hidden_size=3072, intermediate_size=24576,
        num_layers=28, num_heads=16, num_kv_heads=16, head_dim=256,
        max_seq_len=8192, rms_norm_eps=1e-6, tie_embeddings=True,
        mlp_activation="gelu_tanh", rmsnorm_offset=True, embedding_scale=True,
    ),
    # Mixtral-8x7B: sparse MoE (8 experts, top-2) on the Mistral base.
    "mixtral_8x7b": ModelConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
        rope_theta=1000000.0, num_experts=8, num_experts_per_tok=2,
    ),
    # Test-scale nemotron_h: every mixer kind twice, 8 sigmoid-routed relu2
    # experts (top-3) with a shared expert, attention without rope.
    "nemotron_h_tiny": ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=48, num_layers=6,
        num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256,
        remat=False, dtype="float32", param_dtype="float32",
        layer_pattern="MEM*EM", rope=False, mlp_activation="relu2",
        mamba_num_heads=8, mamba_head_dim=8, mamba_n_groups=2,
        mamba_state_size=16, mamba_chunk_size=8,
        moe_num_experts=8, num_experts_per_tok=3, moe_intermediate_size=48,
        moe_shared_intermediate_size=96, moe_routed_scaling=2.5,
    ),
    # Test-scale decoder-hybrid-decoder (structurally phi4flash / SambaY):
    # Mamba-1, differential attention under a window and over every key,
    # then gated memory units and cross-attention over layer 7's pool; the
    # kinds in the published order for 12 layers (mb_per_layer 2, the split
    # at num_layers // 2).
    "sambay_tiny": ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=12,
        num_heads=8, num_kv_heads=4, max_seq_len=256, remat=False,
        dtype="float32", param_dtype="float32", tie_embeddings=True,
        layer_pattern="SDSDSDSDGXGX",
        layer_windows=(0, 16, 0, 16, 0, 16, 0, 0, 0, 0, 0, 0),
        rope=False, attention_bias=True, mamba_expand=2,
        mamba_state_size=8, mamba_dt_rank=4,
    ),
    # AI21-Jamba2-3B (`jamba`): 26 Mamba-1 layers with inner norms, plain
    # attention (20 query heads over one key-value head, no positions) in
    # layers 7 and 21, a dense MLP in every layer, tied 65,536-row head;
    # the sizes of benchmark/configs/jamba2_3b.json (a test holds them equal).
    "jamba2_3b": ModelConfig(
        vocab_size=65536, hidden_size=2560, intermediate_size=8192,
        num_layers=28, num_heads=20, num_kv_heads=1, head_dim=128,
        max_seq_len=262144, rms_norm_eps=1e-6, tie_embeddings=True,
        layer_pattern="SSSSSSSASSSSSSSSSSSSSASSSSSS", rope=False,
        mamba_expand=2, mamba_state_size=16, mamba_conv_kernel=4,
        mamba_dt_rank=160, mamba_inner_norms=True,
        lora_targets=("q_proj", "k_proj", "v_proj", "o_proj",
                      "in_proj", "x_proj", "out_proj"),
    ),
    # Test-scale jamba (structurally AI21-Jamba2): Mamba-1 with inner norms
    # and plain one-key-value-head attention without rotation, an attention
    # layer every fourth (attn_layer_period 4, attn_layer_offset 2),
    # adapters on the attention and the state-space projections.
    "jamba_tiny": ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=4,
        num_heads=4, num_kv_heads=1, head_dim=16, max_seq_len=256,
        rms_norm_eps=1e-6, dtype="float32", param_dtype="float32",
        tie_embeddings=True, layer_pattern="SSAS", rope=False,
        mamba_expand=2, mamba_state_size=8, mamba_dt_rank=4,
        mamba_inner_norms=True,
        lora_targets=("q_proj", "k_proj", "v_proj", "o_proj",
                      "in_proj", "x_proj", "out_proj"),
    ),
    # Test-scale latent attention (structurally deepseek_v3: MLA over a
    # latent cache, one dense layer, then gated held experts).
    "latent_tiny": ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=3,
        num_heads=4, num_kv_heads=4, max_seq_len=256, rope_theta=1e6,
        rms_norm_eps=1e-6, remat=False, dtype="float32",
        param_dtype="float32", kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True,
        first_k_dense=1, moe_num_experts=8,
        num_experts_per_tok=3, moe_intermediate_size=24,
        moe_shared_intermediate_size=48, moe_routed_scaling=2.448,
    ),
    # Test-scale MoE (structurally Mixtral: GQA + top-2 of 4 experts).
    "mixtral_tiny": ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=128, remat=False,
        dtype="float32", param_dtype="float32", num_experts=4,
        num_experts_per_tok=2,
    ),
}


def resolve_model(name: str) -> ModelConfig:
    """A preset by name: every place a user names a model
    (``scripts/train.py --model``, ``scripts/serve.py --random-init``).

    ``DLTI_MODEL_LAYERS=N`` in the environment keeps the preset's first N
    layers and every width. It is not a user option: ``chip_smoke.py`` and
    ``bench.py`` set it for runs that must fit a time limit or a small
    host, and say the depth they ran (the trainer's and the engine's build
    lines carry ``model_layers``). It goes when full depth fits there.
    """
    if name not in MODEL_PRESETS:
        raise ValueError(
            f"unknown model {name!r}; presets: {sorted(MODEL_PRESETS)}")
    cfg = MODEL_PRESETS[name]
    cut = os.environ.get("DLTI_MODEL_LAYERS", "")
    if cut:
        if not cut.isdigit() or not 1 <= int(cut) <= cfg.num_layers:
            raise ValueError(
                f"DLTI_MODEL_LAYERS={cut!r}: {name} takes 1.."
                f"{cfg.num_layers} whole layers")
        cfg = dataclasses.replace(
            cfg, num_layers=int(cut),
            layer_pattern=cfg.layer_pattern[:int(cut)],
            layer_windows=cfg.layer_windows[:int(cut)])
    return cfg


def preset(name: str, **overrides: Any) -> Config:
    """Build a :class:`Config` from a strategy preset name.

    Presets mirror the reference experiment matrix
    (``training/train.ipynb``): ``baseline`` and ``zero{1,2,3}_{N}dev``.

    >>> preset("baseline").parallel.zero_stage
    <ZeROStage.NONE: 0>
    >>> preset("zero3_8dev").parallel.fsdp
    8
    """
    model = overrides.pop("model", MODEL_PRESETS["llama2_7b"])
    if isinstance(model, str):
        model = resolve_model(model)

    if name == "baseline":
        par = ParallelConfig(zero_stage=ZeROStage.NONE)
    else:
        import re

        m = re.fullmatch(r"zero([123])(?:_(\d+)dev)?", name)
        if not m:
            raise ValueError(
                f"unknown preset {name!r}; expected 'baseline' or 'zero{{1,2,3}}[_Ndev]'"
            )
        stage = ZeROStage(int(m.group(1)))
        n = int(m.group(2) or 1)
        if stage == ZeROStage.ZERO3:
            par = ParallelConfig(zero_stage=stage, fsdp=n)
        else:
            par = ParallelConfig(zero_stage=stage, data=n)
    return Config(model=model, parallel=par, experiment_name=name, **overrides)
