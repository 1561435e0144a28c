"""The jamba family (``model_type`` jamba: "Jamba: A Hybrid Transformer-Mamba
Language Model", arXiv:2403.19887), trained and served.

Every layer is a mixer and a gate/up/down MLP, each behind an RMSNorm:
``x = x + Mixer_i(RMSNorm(x))``, then ``x = x + MLP_i(RMSNorm'(x))``, the
mixer named by character ``i`` of ``ModelConfig.layer_pattern``:

* ``S`` Mamba-1 (``models.mamba1``) with RMSNorms on the time step's
  low-rank values and on B and C (``cfg.mamba_inner_norms``);
* ``A`` plain causal attention over the layer's own keys and values
  (``models.llama.LlamaAttention``: grouped queries, no bias, no window),
  without any rotation: the family has no positional encoding.

Embedding rows unscaled, a final RMSNorm, logits over the tied embedding.
The MLP is ``models.llama.LlamaMLP``. (The published family also has
expert layers; ``num_experts`` 1 selects none and none is built here.)

**Training.** Packed rows (``segment_ids``) are taken: an ``A`` layer
attends within the document, an ``S`` layer starts every document from a
zero state. LoRA adapters go on the projections ``cfg.lora_targets_of``
names (the configuration states this family's: q/k/v/o of the two ``A``
layers alone would leave 26 of 28 mixers without an adapter). With
``cfg.remat`` a block is recomputed in the backward pass. A forward pass
without a cache counts the documents that start inside its rows
(``recurrent_state_resets``).

**Serving.** The cache is a list with an entry a layer
(``ops.kv_cache.init_cache``): ``{"conv", "ssm"}`` by decode slot for ``S``,
``{"k", "v"}`` block pools for ``A``.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlti_tpu.config import LoRAConfig, ModelConfig
from dlti_tpu.models.llama import (
    LlamaAttention, LlamaMLP, RMSNorm, _dtype, _remat_policy,
)
from dlti_tpu.models.mamba1 import Mamba1Mixer

# What a forward pass counts beside its logits (int32 scalars by name, with
# ``return_counters``): rows (serving) or documents (training) whose
# recurrent state started from zero, and prompt tokens that went through
# the Mamba-1 layers' scan in prefill calls.
COUNTERS = ("recurrent_state_resets", "recurrent_prefill_tokens")

# Most padded tokens (rows x bucket) the serving engine gives one prefill
# call, as the other patterned families' limit.
PREFILL_CALL_TOKENS = 2048

# Seeded weights: the embedding at unit scale carries the token and the
# norms' weights spread round 1 (a program that drops one differs); the
# final norm's mean keeps tied logits at unit spread.
NORM_INIT_STD = 0.25


class JambaBlock(nn.Module):
    cfg: ModelConfig
    kind: str
    lora: Optional[LoRAConfig] = None
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, cache=None,
                 deterministic: bool = True):
        """One layer; ``cache``: the layer's bound entry. Returns ``(x, new
        cache or None)``."""
        cfg = self.cfg
        h = RMSNorm(cfg.rms_norm_eps, init_std=NORM_INIT_STD,
                    name="input_norm")(x)
        if self.kind == "S":
            with jax.named_scope("dlti_mamba1"):
                out, _, new_cache = Mamba1Mixer(
                    cfg, self.lora, name="mixer")(
                        h, positions, cache, segment_ids, deterministic)
        else:
            with jax.named_scope("dlti_attn_full"):
                out, new_cache = LlamaAttention(
                    cfg, self.lora, self.mesh, name="mixer")(
                        h, None, None, positions, segment_ids, cache,
                        deterministic)
        x = x + out
        x = x + LlamaMLP(cfg, self.lora, name="mlp")(
            RMSNorm(cfg.rms_norm_eps, init_std=NORM_INIT_STD,
                    name="post_mixer_norm")(x), deterministic)
        return x, new_cache


class JambaForCausalLM(nn.Module):
    """Body + tied head. Returns float32 logits and the new cache; with
    ``return_counters`` also ``{name: int32 scalar}`` for ``counter_names``,
    what this pass counted."""

    cfg: ModelConfig
    lora: Optional[LoRAConfig] = None
    mesh: Optional[Any] = None
    counter_names = COUNTERS
    # what ``training.step.make_train_step`` asks a training pass for
    train_counters = ("recurrent_state_resets",)
    prefill_call_tokens = PREFILL_CALL_TOKENS

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None,
                 cache=None, deterministic: bool = True, token_mask=None,
                 return_hidden: bool = False, return_counters: bool = False):
        del token_mask  # no layer routes tokens
        cfg = self.cfg
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        b, s = input_ids.shape
        embed = self.param("embed_tokens", nn.initializers.normal(1.0),
                           (cfg.vocab_size, cfg.hidden_size), pdtype)
        x = jnp.take(embed, input_ids, axis=0).astype(dtype)
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))

        new_caches = [] if cache is not None else None
        block = self._block_of_a_kind(positions, segment_ids, deterministic)
        for i, kind in enumerate(cfg.layer_pattern):
            x, layer_cache = block(i, kind, x,
                                   cache[i] if cache is not None else None)
            if cache is not None:
                new_caches.append(layer_cache)

        counters = dict.fromkeys(COUNTERS, jnp.int32(0))
        if cache is not None:
            slots = next(c["state_slots"] for c in cache
                         if "state_slots" in c)
            n_slots = next(c["ssm"].shape[0] for c in cache if "ssm" in c)
            live_rows = (slots >= 0) & (slots < n_slots)
            counters["recurrent_state_resets"] = jnp.sum(
                (positions[:, 0] == 0) & live_rows).astype(jnp.int32)
            if s > 1:
                counters["recurrent_prefill_tokens"] = jnp.sum(
                    (positions >= 0) & live_rows[:, None]).astype(jnp.int32)
        elif segment_ids is not None:
            # documents that begin inside the rows: where the packed
            # segment changes to a real one
            before = jnp.pad(segment_ids, ((0, 0), (1, 0)))[:, :s]
            counters["recurrent_state_resets"] = jnp.sum(
                (segment_ids != before) & (segment_ids != 0)
            ).astype(jnp.int32)
        else:
            counters["recurrent_state_resets"] = jnp.int32(b)
        x = RMSNorm(cfg.rms_norm_eps, init_std=NORM_INIT_STD,
                    init_mean=cfg.hidden_size ** -0.5, name="final_norm")(x)

        def result(out):
            return (out, new_caches, counters) if return_counters \
                else (out, new_caches)

        if return_hidden and not self.is_initializing():
            return result(x)
        logits = jnp.dot(x, self.head_matrix({"embed_tokens": embed}, x),
                         preferred_element_type=jnp.float32)
        return result(logits.astype(jnp.float32))

    def _block_of_a_kind(self, positions, segment_ids, deterministic):
        """``(i, kind, x, layer cache) -> JambaBlock's result`` for layer
        ``i``. The layers of one kind differ in nothing but their weights
        and their cache entry, so outside ``init`` a kind's block is ONE
        jitted function of those (``models.nemotron_h``'s and
        ``models.sambay``'s way): a program traces and lowers two kinds of
        layer, not twenty-eight. Without a cache and with ``cfg.remat`` that
        function is rematerialised: the backward pass keeps a block's input
        and recomputes the rest."""
        cfg = self.cfg
        if self.is_initializing():  # the tree: a submodule a layer
            return lambda i, kind, x, entry: JambaBlock(
                cfg, kind, self.lora, self.mesh, name=f"layers_{i}")(
                    x, positions, segment_ids, entry, deterministic)
        weights = self.variables["params"]
        dropout = None if deterministic or self.lora is None \
            or not self.lora.enabled or not self.lora.dropout \
            else self.make_rng("dropout")
        traced_once = {}

        def block(i, kind, x, entry):
            # what of the entry is not an array stays outside the trace
            static = {k: v for k, v in (entry or {}).items()
                      if isinstance(v, bool)}
            key = (kind, tuple(sorted(static.items())))
            if key not in traced_once:
                shared = JambaBlock(cfg, kind, self.lora, self.mesh,
                                    parent=None)

                def apply(w, x, positions, segment_ids, arrays, rng):
                    return shared.apply(
                        {"params": w}, x, positions, segment_ids,
                        None if arrays is None else {**arrays, **static},
                        deterministic,
                        rngs=None if rng is None else {"dropout": rng})

                if cfg.remat and entry is None:
                    apply = jax.checkpoint(
                        apply, policy=_remat_policy(cfg.remat_policy))
                traced_once[key] = jax.jit(apply)
            arrays = None if entry is None else {
                k: v for k, v in entry.items() if k not in static}
            rng = None if dropout is None else jax.random.fold_in(dropout, i)
            return traced_once[key](weights[f"layers_{i}"], x, positions,
                                    segment_ids, arrays, rng)

        return block

    def head_matrix(self, params, anchor):
        """The tied head in the activation dtype (``models.sambay``'s
        reason: no float32 copy of the embedding in a program)."""
        del self
        return params["embed_tokens"].astype(anchor.dtype).T
