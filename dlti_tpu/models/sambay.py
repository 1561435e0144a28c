"""The decoder-hybrid-decoder family (SambaY, ``model_type`` phi4flash).

Every layer is a mixer and a gated MLP, each behind a LayerNorm with bias:
``x = x + Mixer_i(LN(x))``, then ``x = x + MLP_i(LN'(x))``, the mixer named
by character ``i`` of ``ModelConfig.layer_pattern``:

* ``S`` Mamba-1 (``models.mamba1``, without the inner norms that
  ``models.jamba`` turns on); the one at
  ``cfg.shared_memory_layer`` also hands its scan output ``y`` (before the
  gate), THE MEMORY, to every ``G`` layer of the same forward pass;
* ``D`` differential attention (arXiv:2410.05258) over the layer's own keys
  and values, under the layer's window (``cfg.window_of_layer``); the one at
  ``cfg.shared_kv_layer`` sees every key, and its pool is what every ``X``
  layer reads;
* ``G`` a gated memory unit, ``out_proj(silu(in_proj h) * memory)``: no
  state of its own;
* ``X`` differential cross-attention: a query projection alone, over the
  shared pool as this call's ``D`` layer left it.

No positional embedding anywhere. Embedding rows unscaled, a final
LayerNorm, logits over the tied embedding.

**Differential attention through the paged kernel.** Heads come in pairs:
``q1_i = q[2i]``, ``q2_i = q[2i+1]``, ``k1_j = k[2j]``, ``k2_j = k[2j+1]``,
``V_j = [v[2j] ; v[2j+1]]`` (head i reads j = i // 2), and
``o_i = (1 - lam0) RMSNorm(softmax(q1_i k1_j^T / sqrt d) V_j
- lam softmax(q2_i k2_j^T / sqrt d) V_j)``. A pool keeps HALF as many
key-value heads, TWICE as wide: key row ``[k1_j ; k2_j]`` and value row
``V_j`` (the projections as they lie, read two heads at a time; at the
published 20 x 64 that is 10 x 128, whole lanes, where a 64-wide row would
be padded to 128 in HBM and double the cache). Then each of the two softmaxes
is an ordinary GQA output of a query padded with zeros, ``[q1_i ; 0]`` and
``[0 ; q2_i]``, scaled by sqrt 2 so that the kernels' ``(2 d) ** -0.5`` gives
``d ** -0.5``: ``over_paged_cache`` (the paged decode kernel, the walk, the
gather) and ``multi_head_attention`` compute them unchanged; the
subtraction, the norm and ``(1 - lam0)`` follow under
``jax.named_scope("dlti_diff_attention")``.

The cache is a list with one entry a layer (``ops.kv_cache.init_cache``):
``{"conv", "ssm"}`` by decode slot for ``S``, ``{"k", "v"}`` block pools for
``D`` (the window group's or the full group's), ``{}`` for ``G`` and ``X``.
The memory is never cached: it is this call's activations.

The family is served, not trained: packed rows and LoRA are refused here
(``models.jamba`` trains the same Mamba-1 mixer over packed rows with
adapters; a forward pass without a cache goes through that mixer's chunked
scan, whose results are the token-a-trip scan's).
"""

from __future__ import annotations

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlti_tpu.config import LoRAConfig, ModelConfig
from dlti_tpu.models.llama import QK_NORM_INIT_STD, _dtype, over_paged_cache
from dlti_tpu.models.lora import LoRADense
from dlti_tpu.models.mamba1 import Mamba1Mixer
from dlti_tpu.ops.attention import multi_head_attention

# What a forward pass counts beside its logits (int32 scalars by name, with
# ``return_counters``): rows whose recurrent state started from zero, prompt
# tokens that went through the Mamba-1 layers' scan, and prompt tokens that
# went through the layers after ``cfg.shared_kv_layer`` in prefill calls
# (only each row's last needs them: what a prefill that skips them saves).
COUNTERS = ("recurrent_state_resets", "recurrent_prefill_tokens",
            "cross_decoder_prefill_tokens")

# Most padded tokens (rows x bucket) the serving engine gives one prefill
# call: it bounds what the window group of the cache holds for a call
# (``ops.kv_cache.window_group_blocks``), as the Llama family's limit does
# (``models.llama.PREFILL_CALL_TOKENS``, the same number).
PREFILL_CALL_TOKENS = 2048

# Seeded weights away from their means, so that a program that drops a term
# differs from the stated one: norms' weights 1 + N(0, 0.25) and biases
# N(0, 0.1); projection biases N(0, 0.1); the four lambda vectors N(0, 0.2)
# (the paper's 0.1 doubled: lambda then moves by ~0.3 round lam0); every
# projection LeCun-normal, queries' and keys' columns ``SEEDED_QK_GAIN``
# times that.
_BIAS_INIT = nn.initializers.normal(0.1)
LAMBDA_INIT_STD = 0.2
# The head is the embedding. A final state x = e + r (e the input token's
# row, r what the layers added) scores its own input token d sigma_e^2 and
# every other about sqrt(d (sigma_e^2 + rho^2)) sigma_e a standard
# deviation, so the greedy token is the INPUT token unless rho^2 / sigma_e^2
# > d / (2 ln vocab): 106 at the published sizes. At unit embedding scale the
# whole stack's rho^2 was ~65 and 554-568 of 576 served tokens repeated the
# token before them (my chip runs, PR 53, call A: a check on such answers
# holds little). An eighth of that scale leaves the first LayerNorm the same
# input and the final state the layers' own; the final norm's weight
# (mean 1 / (sqrt d sigma_e)) keeps the logits at unit spread.
EMBED_INIT_STD = 0.125
# Seeded queries and keys: a score spread of ~2, so that a query weighs its
# keys tenfold apart and not as the mean of its context, as far as bfloat16
# allows: 32 layers whose outputs are the residual stream amplify a flipped
# winner, and at the Llama family's gain of 3 (a spread of ~9, near one-hot)
# the stated program read 0.46-0.66 of log-prob against its float32
# reference (call A; the CPU at hidden 256-512 reads 1.0 at 3, 0.47 at 2,
# 0.27-0.32 at 1.5, 0.21 at 1, the states of different prompts at a cosine
# under 0.15 throughout).
SEEDED_QK_GAIN = 1.5


def lambda_init(depth):
    """The differential attention's ``lam0`` at layer index ``depth``."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * depth)


def _normal_about(mean: float, std: float):
    def init(key, shape, dtype=jnp.float32):
        return (mean * (1.0 + std * jax.random.normal(key, shape))
                ).astype(dtype)
    return init


def _qk_sharp_init(sharp_columns: int):
    """LeCun-normal with the first ``sharp_columns`` columns (queries, and
    keys where the projection is fused) at ``SEEDED_QK_GAIN``."""
    base = nn.initializers.lecun_normal()

    def init(key, shape, dtype=jnp.float32):
        gain = jnp.where(jnp.arange(shape[1]) < sharp_columns,
                         SEEDED_QK_GAIN, 1.0)
        return (base(key, shape, jnp.float32) * gain).astype(dtype)
    return init


class LayerNorm(nn.Module):
    """LayerNorm with weight and bias; statistics in float32."""

    eps: float = 1e-5
    init_mean: float = 1.0

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", _normal_about(self.init_mean,
                                                  QK_NORM_INIT_STD),
                           (x.shape[-1],), jnp.float32)
        bias = self.param("bias", nn.initializers.normal(
            0.1 * self.init_mean), (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        return ((x32 - mean) * jax.lax.rsqrt(var + self.eps) * scale
                + bias).astype(x.dtype)


def _dense(cfg: ModelConfig, name: str, features: int, use_bias: bool = False,
           **init):
    return LoRADense(features=features, use_bias=use_bias,
                     dtype=_dtype(cfg.dtype),
                     param_dtype=_dtype(cfg.param_dtype), name=name, **init)


class SambaYMLP(nn.Module):
    """``fc2(silu(g) * y)`` with ``[g ; y] = fc1 u``: the gate is the FIRST
    half of ``fc1``'s output."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        gate, up = jnp.split(
            _dense(cfg, "fc1", 2 * cfg.intermediate_size)(x), 2, axis=-1)
        return _dense(cfg, "fc2", cfg.hidden_size)(
            nn.silu(gate) * up)


class GatedMemoryUnit(nn.Module):
    """``out_proj(silu(in_proj h) * memory)``: the memory is another
    layer's scan output for the same tokens, (b, s, d_inner) float32."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray, memory: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        gate = _dense(cfg, "in_proj", cfg.mamba_inner_size)(x)
        gated = nn.silu(gate.astype(jnp.float32)) * memory
        return _dense(cfg, "out_proj", cfg.hidden_size)(
            gated.astype(gate.dtype))


class DiffAttention(nn.Module):
    """Differential attention, self (``cross`` False: the layer projects
    and caches keys and values) or cross (a query projection alone, over
    another layer's keys and values)."""

    cfg: ModelConfig
    cross: bool = False
    window: Optional[int] = None
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, positions, depth, cache=None):
        """``cache``: the layer's bound entry (with ``block_tables``); for a
        cross layer the shared layer's, as this call left it, or without a
        serving cache its ``{"k", "v"}`` of this call (b, s, kv_pairs, 2 d).
        Returns ``(out, new cache or None, {"k", "v"} of this call)``."""
        cfg = self.cfg
        b, s, _ = x.shape
        d = cfg.resolved_head_dim
        pairs, kv_pairs = cfg.num_heads // 2, cfg.num_kv_heads // 2
        q_width, kv_width = cfg.num_heads * d, cfg.num_kv_heads * d
        k = v = None
        if self.cross:
            q = _dense(cfg, "q_proj", q_width, True, bias_init=_BIAS_INIT,
                       kernel_init=_qk_sharp_init(q_width))(x)
        else:
            q, k, v = jnp.split(
                _dense(cfg, "qkv_proj", q_width + 2 * kv_width, True,
                       bias_init=_BIAS_INIT,
                       kernel_init=_qk_sharp_init(q_width + kv_width))(x),
                [q_width, q_width + kv_width], axis=-1)
            k = k.reshape(b, s, kv_pairs, 2 * d)
            v = v.reshape(b, s, kv_pairs, 2 * d)
        # [q1_i ; 0] and [0 ; q2_i], sqrt 2 up: two softmaxes a pair.
        q = (q.astype(jnp.float32) * math.sqrt(2.0)).astype(q.dtype)
        q = q.reshape(b, s, pairs, 2, d)
        zeros = jnp.zeros_like(q[:, :, :, 0])
        q = jnp.stack([jnp.concatenate([q[:, :, :, 0], zeros], -1),
                       jnp.concatenate([zeros, q[:, :, :, 1]], -1)],
                      axis=3).reshape(b, s, 2 * pairs, 2 * d)

        new_cache = None
        if cache is not None and "block_tables" in cache:
            a, new_cache = over_paged_cache(cfg, self.mesh, self.window, q,
                                            k, v, cache, positions)
            if self.cross:
                new_cache = {}
        else:
            if self.cross:
                k, v = cache["k"], cache["v"]
            a = multi_head_attention(
                q, k, v, causal=True, impl=cfg.attention_impl,
                block_q=cfg.flash_block_q, block_kv=cfg.flash_block_kv,
                window=self.window)

        lam_std = nn.initializers.normal(LAMBDA_INIT_STD)
        lq1, lk1, lq2, lk2 = (
            self.param(n, lam_std, (d,), jnp.float32)
            for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
        subln = self.param("subln", _normal_about(1.0, QK_NORM_INIT_STD),
                           (2 * d,), jnp.float32)
        with jax.named_scope("dlti_diff_attention"):
            lam0 = lambda_init(jnp.asarray(depth, jnp.float32))
            lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) \
                + lam0
            a = a.reshape(b, s, pairs, 2, 2 * d).astype(jnp.float32)
            diff = a[:, :, :, 0] - lam * a[:, :, :, 1]
            diff = diff * jax.lax.rsqrt(
                jnp.mean(jnp.square(diff), -1, keepdims=True)
                + cfg.rms_norm_eps) * subln
            o = ((1.0 - lam0) * diff).astype(x.dtype).reshape(b, s, q_width)
        out = _dense(cfg, "o_proj", cfg.hidden_size, True,
                     bias_init=_BIAS_INIT)(o)
        return out, new_cache, (None if self.cross else {"k": k, "v": v})


class SambaYBlock(nn.Module):
    cfg: ModelConfig
    kind: str
    window: Optional[int] = None
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x, positions, depth, cache=None, memory=None):
        """One layer. ``depth``: the layer's index (a traced scalar: the
        layers of a kind share one trace). ``cache``: the layer's bound
        entry; an ``X`` layer's is the shared layer's (``DiffAttention``).
        ``memory``: a ``G`` layer's. Returns ``(x, new cache or None, what
        the layer hands on: an S layer's scan output, a D layer's keys and
        values of this call, else None)``."""
        cfg, kind = self.cfg, self.kind
        eps = cfg.rms_norm_eps
        h = LayerNorm(eps, name="input_norm")(x)
        hands = None
        new_cache = {} if cache is not None else None
        if kind == "S":
            with jax.named_scope("dlti_mamba1"):
                out, hands, new_cache = Mamba1Mixer(cfg, name="mixer")(
                    h, positions, cache)
        elif kind == "G":
            with jax.named_scope("dlti_gmu"):
                out = GatedMemoryUnit(cfg, name="mixer")(h, memory)
        else:
            with (jax.named_scope("dlti_cross_attention") if kind == "X"
                  else jax.named_scope("dlti_attn_window" if self.window
                                       else "dlti_attn_full")):
                out, new_cache, hands = DiffAttention(
                    cfg, kind == "X", self.window, self.mesh, name="mixer")(
                        h, positions, depth, cache)
        x = x + out
        x = x + SambaYMLP(cfg, name="mlp")(
            LayerNorm(eps, name="post_mixer_norm")(x))
        return x, new_cache, hands


class SambaYForCausalLM(nn.Module):
    """Body + tied head. Returns float32 logits and the new cache; with
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
                "packed rows through the decoder-hybrid-decoder family are "
                "not wired: its Mamba-1 layers could start every document "
                "anew (models.mamba1 does, for models.jamba), but the "
                "memory units and the attention under a window or over the "
                "shared pool have been held to no reference under packing")
        if self.lora is not None and self.lora.enabled:
            raise NotImplementedError(
                "LoRA through the decoder-hybrid-decoder family's layers "
                "(fused and paired projections, memory units) is not "
                "implemented; the Mamba-1 mixer carries adapters, for "
                "models.jamba")
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        b, s = input_ids.shape
        embed = self.param("embed_tokens",
                           nn.initializers.normal(EMBED_INIT_STD),
                           (cfg.vocab_size, cfg.hidden_size), pdtype)
        x = jnp.take(embed, input_ids, axis=0).astype(dtype)
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))

        new_caches = [] if cache is not None else None
        block = self._block_of_a_kind(positions)
        memory = shared = None
        for i, kind in enumerate(cfg.layer_pattern):
            entry = cache[i] if cache is not None else None
            if kind == "X":
                # the shared pool as this call's D layer left it, under the
                # X layer's own binding (the full group's tables)
                entry = shared if cache is None else {**entry, **shared}
            x, layer_cache, hands = block(i, kind, x, entry,
                                          memory if kind == "G" else None)
            if i == cfg.shared_memory_layer:
                memory = hands
            if i == cfg.shared_kv_layer:
                shared = hands if cache is None else {
                    "k": layer_cache["k"], "v": layer_cache["v"]}
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
                tokens = jnp.sum((positions >= 0) & live_rows[:, None]
                                 ).astype(jnp.int32)
                counters["recurrent_prefill_tokens"] = tokens
                if "X" in cfg.layer_pattern or "G" in cfg.layer_pattern:
                    counters["cross_decoder_prefill_tokens"] = tokens
        x = LayerNorm(cfg.rms_norm_eps,
                      cfg.hidden_size ** -0.5 / EMBED_INIT_STD,
                      name="final_norm")(x)

        def result(out):
            return (out, new_caches, counters) if return_counters \
                else (out, new_caches)

        if return_hidden and not self.is_initializing():
            return result(x)
        logits = jnp.dot(x, self.head_matrix({"embed_tokens": embed}, x),
                         preferred_element_type=jnp.float32)
        return result(logits.astype(jnp.float32))

    def _block_of_a_kind(self, positions):
        """``(i, kind, x, layer cache, memory) -> SambaYBlock's result`` for
        layer ``i``. The layers of one kind (and window) differ in nothing
        but their weights, their index and their cache entry, so outside
        ``init`` a kind's block is ONE jitted function of those, as
        ``models.nemotron_h.NemotronHForCausalLM._block_of_a_kind``: a
        program traces and lowers five kinds of layer, not thirty-two."""
        cfg = self.cfg

        def window(i):
            return cfg.window_of_layer(i) if cfg.layer_pattern[i] == "D" \
                else None

        if self.is_initializing():  # the tree: a submodule a layer
            return lambda i, kind, x, entry, memory: SambaYBlock(
                cfg, kind, window(i), self.mesh, name=f"layers_{i}")(
                    x, positions, i, entry, memory)
        weights = self.variables["params"]
        traced_once = {}

        def block(i, kind, x, entry, memory):
            # what of the entry is not an array stays outside the trace
            static = {k: v for k, v in (entry or {}).items()
                      if isinstance(v, bool)}
            key = (kind, window(i), tuple(sorted(static.items())))
            if key not in traced_once:
                shared = SambaYBlock(cfg, kind, window(i), self.mesh,
                                     parent=None)
                traced_once[key] = jax.jit(
                    lambda w, x, positions, depth, arrays, memory:
                    shared.apply(
                        {"params": w}, x, positions, depth,
                        None if arrays is None else {**arrays, **static},
                        memory))
            arrays = None if entry is None else {
                k: v for k, v in entry.items() if k not in static}
            return traced_once[key](weights[f"layers_{i}"], x, positions,
                                    jnp.int32(i), arrays, memory)

        return block

    def head_matrix(self, params, anchor):
        """The tied head in the activation dtype: the final state is of
        that dtype already, so products of bfloat16 pairs summed in float32
        lose nothing to a float32 copy of the 200k-row embedding, which the
        Llama family's tied contract would make a program hold."""
        del self
        return params["embed_tokens"].astype(anchor.dtype).T
