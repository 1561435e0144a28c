"""How many blocks keep their activations: the trainer's rule.

Rematerialising a block costs its forward a second time; keeping it costs
what its backward reads. A step has room for ``k`` kept blocks while

    (what the step holds with none kept) + k x (a kept block's bytes)

stays under the device's limit less :data:`MARGIN_FRACTION` of it, per
device. Both terms are arithmetic over what the configuration states
(tokens a device a microbatch, widths, dtypes, which projections carry
LoRA) plus the bytes of the placed state, so the rule costs no compile;
``benchmarks_dev/remat_plan_drill.py`` holds the arithmetic to the compiled
step's ``memory_analysis()`` on the chip (``results/remat_plan_v5e.jsonl``,
``tests/test_remat_plan.py``). ``scripts/memory_plan.py`` prints the same
plan from the same functions.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from dlti_tpu.config import Config, LoRAConfig, ModelConfig

# Kept free of the plan: programs' code, prefetched batches, the allocator's
# rounding, and whatever the arithmetic below misses.
MARGIN_FRACTION = 0.05

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}
_ATTN_TARGETS = frozenset(("q_proj", "k_proj", "v_proj"))
_MLP_IN_TARGETS = frozenset(("gate_proj", "up_proj"))


@dataclasses.dataclass(frozen=True)
class RematPlan:
    """What the rule decided, and from what. ``why_not`` says why it stood
    aside (then ``keep_blocks`` is the configuration's own, 0 unless it
    states one, and the byte fields are 0)."""

    keep_blocks: int
    layers: int
    block_bytes: int = 0
    base_bytes: int = 0
    limit_bytes: int = 0
    why_not: str = ""

    @property
    def planned_bytes(self) -> int:
        return self.base_bytes + self.keep_blocks * self.block_bytes

    def stepped_down(self) -> "RematPlan":
        return dataclasses.replace(self, keep_blocks=self.keep_blocks - 1)

    def scalars(self) -> dict:
        """The step log's, the memory ledger's and the planner's fields."""
        return {"remat_kept_blocks": self.keep_blocks,
                "remat_planned_bytes": self.planned_bytes,
                "remat_limit_bytes": self.limit_bytes,
                "remat_block_bytes": self.block_bytes}

    def line(self) -> str:
        if self.why_not:
            return (f"remat: {self.keep_blocks} of {self.layers} blocks keep "
                    f"their activations as the configuration has it, "
                    f"unplanned ({self.why_not})")
        gib = 1024.0 ** 3
        return (f"remat: {self.keep_blocks} of {self.layers} blocks keep "
                f"their activations; planned {self.planned_bytes / gib:.2f} "
                f"of {self.limit_bytes / gib:.2f} GiB a device; a block "
                f"{self.block_bytes / 2 ** 20:.0f} MiB")


def kept_block_bytes(tokens: int, model: ModelConfig,
                     lora: Optional[LoRAConfig] = None,
                     tensor: int = 1) -> int:
    """What one block's backward reads of its forward, so what keeping it
    holds from the forward to the backward: the block's input, the normed
    input where a LoRA factor reads it, q, k and v as the flash kernel
    took them, the attention output and its log-sum-exp, the second
    residual, the gate and up products (and their inputs and product where
    those projections carry LoRA). Norm statistics and the rank-r
    intermediates are left out (a thousandth of the rest). Widths that a
    ``tensor`` axis shards count one device's share."""
    b = _DTYPE_BYTES[model.dtype]
    targets = set(lora.target_modules) if lora is not None else set()
    h = model.hidden_size
    q = model.num_heads * model.resolved_head_dim // tensor
    kv = model.num_kv_heads * model.resolved_head_dim // tensor
    ffn = model.intermediate_size // tensor
    per_token = b * (
        h                                     # the block's input
        + (h if targets & _ATTN_TARGETS else 0)   # normed, for dA
        + q + 2 * kv                          # q, k, v
        + q                                   # attention output
        + h                                   # second residual
        + (h if targets & _MLP_IN_TARGETS else 0)
        + 2 * ffn                             # gate, up
        + (ffn if "down_proj" in targets else 0)
    ) + 4 * model.num_heads // tensor         # log-sum-exp, float32
    return tokens * per_token


def step_base_bytes(tokens: int, model: ModelConfig, state_bytes: int,
                    lora: Optional[LoRAConfig] = None, tensor: int = 1,
                    loss_chunk: int = 0) -> int:
    """What the step holds with every block rematerialised: the placed
    state (``state_bytes``: with the float32 gradients of what trains),
    every block's input, the head's float32 logits (``loss_chunk``
    positions of them where the loss is chunked), and one block's
    activations while its backward recomputes them. Within 2 % of the
    compiled step at the benchmark's shape (the drill's line for 0)."""
    b = _DTYPE_BYTES[model.dtype]
    block_inputs = model.num_layers * tokens * model.hidden_size * b
    head_tokens = min(tokens, loss_chunk) if loss_chunk else tokens
    head = head_tokens * model.vocab_size * 4
    return (state_bytes + block_inputs + head
            + kept_block_bytes(tokens, model, lora, tensor))


def most_blocks_that_fit(layers: int, base_bytes: int, block_bytes: int,
                         limit_bytes: int) -> int:
    """The largest k in 0..layers with base + k x block under the limit
    less its margin."""
    room = limit_bytes - int(MARGIN_FRACTION * limit_bytes) - base_bytes
    if room <= 0 or block_bytes <= 0:
        return 0
    return min(layers, room // block_bytes)


def stands_aside(cfg: Config) -> str:
    """Why the rule does not apply to ``cfg`` ("" where it does): the user
    stated the remat they want, or a device's share cannot be counted."""
    model, par = cfg.model, cfg.parallel
    if model.remat_keep_blocks is not None:
        return "the kept-block count is stated"
    if (not model.remat or model.remat_policy != "nothing_saveable"
            or model.remat_stride != 1):
        return "remat policy or stride is stated"
    if par.pipe > 1:
        return "pipe > 1: a stage's scan remats groups of remat_stride"
    if par.sequence > 1:
        return "sequence > 1: ring attention's blocks are not counted"
    if par.offload_params or par.offload_optimizer:
        return "host offload: the state's device bytes are not counted"
    if (model.num_experts or model.moe_num_experts or model.layer_pattern
            or model.latent_dim or model.ut_steps > 1):
        return "an expert, state-space, latent or looped stack is not counted"
    return ""


def plan(cfg: Config, state_bytes: int, limit_bytes: int) -> RematPlan:
    """The plan for one device: ``state_bytes`` is its share of the placed
    train state, ``limit_bytes`` its memory limit (0: unknown)."""
    layers = cfg.model.num_layers
    why_not = stands_aside(cfg)
    if not why_not and limit_bytes <= 0:
        why_not = "the device states no memory limit"
    if why_not:
        return RematPlan(cfg.model.remat_keep_blocks or 0, layers,
                         limit_bytes=limit_bytes, why_not=why_not)
    par = cfg.parallel
    tokens = (cfg.train.micro_batch_size * cfg.data.max_seq_len
              // max(1, par.data * par.fsdp))
    lora = cfg.lora if cfg.lora.enabled else None
    block = kept_block_bytes(tokens, cfg.model, lora, par.tensor)
    base = step_base_bytes(tokens, cfg.model, state_bytes, lora, par.tensor,
                           cfg.train.loss_chunk)
    return RematPlan(most_blocks_that_fit(layers, base, block, limit_bytes),
                     layers, block, base, limit_bytes)
