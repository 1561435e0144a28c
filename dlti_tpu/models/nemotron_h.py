"""The ``nemotron_h`` family: a per-layer pattern of mixers.

Every layer is ONE mixer behind one pre-norm residual,
``x = x + Mixer_i(RMSNorm(x))``, the mixer named by character ``i`` of
``ModelConfig.layer_pattern``: ``M`` a Mamba-2 layer (``models.mamba2``),
``E`` dropless routed experts with a shared expert
(``models.moe.HeldExpertsMLP``), ``*`` GQA attention without rotary
embedding (``LlamaAttention`` with ``rope=False``). Embedding, ``RMSNorm``,
the untied head and ``head_matrix`` are the Llama model's.

The cache is a list with one entry a layer (``ops.kv_cache.init_cache``):
``{"k", "v"}`` block pools for ``*``, ``{"conv", "ssm"}`` by decode slot for
``M``, ``{}`` for ``E``. The serving engine hands every layer the block
tables and, for the recurrent layers, each row's slot.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlti_tpu.config import LoRAConfig, ModelConfig
from dlti_tpu.models.llama import (
    LlamaAttention, RMSNorm, _dtype, head_matrix_from_leaves,
)
from dlti_tpu.models.mamba2 import Mamba2Mixer
from dlti_tpu.models.moe import MOE_COUNTERS, HeldExpertsMLP

# What a forward pass counts beside its logits (int32 scalars by name, with
# ``return_counters``): the expert layers' counters summed over the layers
# (``moe_expert_load_max``: the largest of them), then rows whose recurrent
# state started from zero and prompt tokens that went through the recurrent
# layers' scan.
COUNTERS = MOE_COUNTERS + ("recurrent_state_resets",
                           "recurrent_prefill_tokens")


# Most padded tokens (rows x bucket) the serving engine gives one prefill call
# of this family, a row at least. On the v5e the 13-layer model at the
# published widths never returns from a prefill of 2 rows x 2,048 (PERF.md
# section 7: 1, 4 and 8 rows x 2,048, 2 x 1,024, layers 0-9 and layers 6-12
# at 2 x 2,048 all run; with or without a `ragged_dot` kernel; cause not
# found), so the engine is kept off every call above 2,048 tokens by
# construction until that program is repaired. Every shape within the limit
# ran on the chip, and runs with the experts held 1,920 wide and every call
# of 512 tokens or more through the grouped kernel (PR 50; PERF.md section 6).
PREFILL_CALL_TOKENS = 2048


class NemotronHBlock(nn.Module):
    cfg: ModelConfig
    kind: str
    lora: Optional[LoRAConfig] = None
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, positions, cache=None, token_mask=None):
        cfg = self.cfg
        h = RMSNorm(cfg.rms_norm_eps, name="norm")(x)
        counters = None
        if self.kind == "M":
            with jax.named_scope("dlti_mamba2"):
                out, new_cache = Mamba2Mixer(cfg, name="mixer")(
                    h, positions, cache)
        elif self.kind == "*":
            out, new_cache = LlamaAttention(
                cfg, self.lora, self.mesh, name="mixer")(
                    h, None, None, positions, None, cache)
        else:
            out, counters = HeldExpertsMLP(cfg, name="mixer")(h, token_mask)
            new_cache = {} if cache is not None else None
        return x + out, new_cache, counters


class NemotronHForCausalLM(nn.Module):
    """Body + untied head. Returns float32 logits and the new cache; with
    ``return_counters`` also ``{name: int32 scalar}`` for ``counter_names``,
    what this pass counted."""

    cfg: ModelConfig
    lora: Optional[LoRAConfig] = None
    mesh: Optional[Any] = None
    counter_names = COUNTERS
    prefill_call_tokens = PREFILL_CALL_TOKENS

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None,
                 cache=None, deterministic: bool = True, token_mask=None,
                 return_hidden: bool = False, return_counters: bool = False):
        cfg = self.cfg
        if segment_ids is not None:
            raise NotImplementedError(
                "packed rows through Mamba-2 layers are not supported: the "
                "recurrence would carry one document's state into the next "
                "(models.mamba1 resets its state at a document's start; "
                "models.mamba2's chunked scan does not yet)")
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        b, s = input_ids.shape
        # Seeded at unit scale: the residual stream carries the token, and
        # the layers add to it (models.moe.centred_out_init).
        embed = self.param("embed_tokens", nn.initializers.normal(1.0),
                           (cfg.vocab_size, cfg.hidden_size), pdtype)
        x = jnp.take(embed, input_ids, axis=0).astype(dtype)
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        real = positions >= 0
        live_rows = jnp.ones((b,), bool)
        if cache is not None:
            slots = next((c["state_slots"] for c in cache
                          if "state_slots" in c), None)
            if slots is not None:
                n_slots = next(c["ssm"].shape[0] for c in cache if "ssm" in c)
                live_rows = (slots >= 0) & (slots < n_slots)
        routed = real & live_rows[:, None]
        if token_mask is not None:
            routed = routed & token_mask.astype(bool)

        counters = dict.fromkeys(COUNTERS, jnp.int32(0))
        new_caches = [] if cache is not None else None
        block = self._block_of_a_kind(positions, routed)
        for i, kind in enumerate(cfg.layer_pattern):
            x, layer_cache, moe = block(
                i, kind, x, cache[i] if cache is not None else None)
            if cache is not None:
                new_caches.append(layer_cache)
            for name, n in zip(MOE_COUNTERS, () if moe is None else moe):
                counters[name] = jnp.maximum(counters[name], n) \
                    if name == "moe_expert_load_max" else counters[name] + n
        if cfg.has_recurrent_state and cache is not None:
            counters["recurrent_state_resets"] = jnp.sum(
                (positions[:, 0] == 0) & live_rows).astype(jnp.int32)
            if s > 1:
                counters["recurrent_prefill_tokens"] = jnp.sum(
                    routed).astype(jnp.int32)
        x = RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)

        def result(out):
            return (out, new_caches, counters) if return_counters \
                else (out, new_caches)

        if return_hidden and not self.is_initializing():
            return result(x)
        lm_head = self.param("lm_head", nn.initializers.normal(0.02),
                             (cfg.hidden_size, cfg.vocab_size), pdtype)
        logits = jnp.dot(x, lm_head.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        return result(logits.astype(jnp.float32))

    def _block_of_a_kind(self, positions, routed):
        """``(i, kind, x, layer cache) -> NemotronHBlock's result`` for layer
        ``i``. The layers of one kind differ in nothing but their weights and
        their cache entry, so outside ``init`` a kind's block is ONE jitted
        function of those: a program traces and lowers a Mamba-2 layer and
        an expert layer once, not six and five times (the same as
        ``models.llama.LoopPass``'s block; XLA inlines the calls; what a
        layer sows stays inside its call, and nothing reads it). On the
        chip's host tracing a 13-layer prefill program took over a second,
        fourteen of them a start: PERF.md section 6, PR 50."""
        cfg = self.cfg
        if self.is_initializing():  # the tree: a submodule a layer
            return lambda i, kind, x, layer_cache: NemotronHBlock(
                cfg, kind, self.lora, self.mesh, name=f"layers_{i}")(
                    x, positions, layer_cache, routed)
        weights = self.variables["params"]
        traced_once = {}

        def block(i, kind, x, layer_cache):
            # what of the entry is not an array stays outside the trace
            static = {k: v for k, v in (layer_cache or {}).items()
                      if isinstance(v, bool)}
            key = (kind, tuple(sorted(static.items())))
            if key not in traced_once:
                shared = NemotronHBlock(cfg, kind, self.lora, self.mesh,
                                        parent=None)
                traced_once[key] = jax.jit(
                    lambda w, x, positions, entry, routed: shared.apply(
                        {"params": w}, x, positions,
                        None if entry is None else {**entry, **static},
                        routed))
            arrays = None if layer_cache is None else {
                k: v for k, v in layer_cache.items() if k not in static}
            return traced_once[key](weights[f"layers_{i}"], x, positions,
                                    arrays, routed)

        return block

    def head_matrix(self, params, anchor):
        return head_matrix_from_leaves(params["embed_tokens"],
                                       params.get("lm_head"), False, anchor)
