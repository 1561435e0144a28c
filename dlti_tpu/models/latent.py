"""The latent-attention family (``model_type`` deepseek_v3, xing4_0): MLA
over a latent cache, leading dense MLPs, then held routed experts.

Every layer is ``x = x + Attn(RMSNorm(x)); x = x + MLP(RMSNorm(x))``, or,
with ``hc_mult`` n > 0, the same two sublayers round n residual streams
mixed by maps computed from the token (``models.hyper``). The
attention keeps, a token and layer, ONE row ``[c ; r]``: the normed latent
``c`` (``kv_lora_rank``) and one rotated key ``r`` (``qk_rope_head_dim``)
that all heads share (x the normed input, h a head):

    q_h     = x W_q,h = [q_nope,h ; q_rope,h],   q_rope,h <- RoPE(q_rope,h)
              (with ``q_lora_rank``: q_h = RMSNorm(x W_qa) W_qb,h)
    [c ; r] = x W_kva,   c <- RMSNorm(c),   r <- RoPE(r)
    expanded:  [k_nope,h ; v_h] = c W_kvb,h
               s_h = (q_nope,h . k_nope,h + q_rope,h . r) * scale
               o_h = softmax(s_h) v_h
    absorbed:  q~_h = q_nope,h W_kvb,h[:, :nope]^T
               s_h = (q~_h . c + q_rope,h . r) * scale
               o_h = (softmax(s_h) c) W_kvb,h[:, nope:]
    y = [o_1 .. o_H] W_o

Both forms are the same function of one set of weights (the products
re-associated) and :class:`LatentAttention` holds both: expanded where many
queries share the up-projected keys (training, a prefill call: its own
tokens through ``ops.pallas.flash_attention``'s forward kernel on the TPU,
what earlier calls wrote through a loop over the pool, which on the CPU
takes both), absorbed where few do (a decode step through
``ops.pallas.latent_attention``, whose XLA gather form is the CPU fallback;
the few tokens a prefix hit leaves).
RoPE turns the pairs ``(2i, 2i + 1)`` (``rope_interleave``); ``scale`` is
``1 / sqrt(d_qk)``, under YaRN (``rope_scaling``) times
``ops.rope.yarn_softmax_factor``, the same in all three paths.

The MLP is ``LlamaMLP`` in the first ``first_k_dense`` layers and
``HeldExpertsMLP`` (gated silu experts, several shared experts as one MLP
of their summed width) in the rest. Embedding, ``RMSNorm``, the untied head
and ``head_matrix`` are the Llama model's.

The serving cache is a list with one ``{"latent"}`` block pool a layer
(``ops.kv_cache.init_latent_cache``), addressed by the same block tables as
a pool of keys and values.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlti_tpu.config import LoRAConfig, ModelConfig
from dlti_tpu.models.llama import (
    LlamaMLP, RMSNorm, _dtype, head_matrix_from_leaves,
)
from dlti_tpu.models.hyper import MHC_COUNTERS, HyperMaps, mix_in, mix_out
from dlti_tpu.models.lora import LoRADense
from dlti_tpu.models.moe import MOE_COUNTERS, HeldExpertsMLP
from dlti_tpu.ops.attention import reference_attention, resolve_paged_decode
from dlti_tpu.ops.kv_cache import latent_gather, latent_update, slot_mapping
from dlti_tpu.ops.rope import (
    apply_rope, assert_rope_table_covers, rope_frequencies,
    yarn_softmax_factor,
)

# Most padded tokens (rows x bucket) the serving engine gives one prefill
# call of this family, a row at least: a longer prompt goes as several calls,
# each attending over the latents the earlier ones wrote. What bounds a
# call is the held-expert layer (a call's held assignments laid out by
# expert, up to two rows of activations an assignment:
# ``models.moe.routed_grouped``); the same limit nemotron_h has, whose
# 2 x 2,048 program never returned on the v5e (PERF.md section 7). The
# float32 (heads, queries, KEY_BLOCK) scores of the expanded form bound it
# only where the loop over the pool runs (the CPU form; a later call's walk
# over what earlier calls wrote): a call's own tokens are scored in the
# kernel's VMEM (``prefill_takes_kernel``).
PREFILL_CALL_TOKENS = 2048
# A call with at most this many query tokens a row over a cached context
# takes the absorbed form. Expanding K keys costs 2 K r H (nope + v) FLOP
# whatever the queries; absorbed, a query token pays 2 K H (2 r + rope)
# against the expanded 2 K H (nope + rope + v): at the published sizes the
# two meet at r (nope + v) / (2 r - nope - v) = 171 query tokens.
ABSORB_MAX_QUERIES = 128
# Keys a step of the loop over a cached context covers (whole blocks). The
# loop runs as far as the call's highest position, not as far as the block
# table is wide: a 2,048-token call whose float32 scores against a whole
# 8,704-key table took 200 ms of softmax passes through HBM on the v5e
# (PERF.md section 6, PR 38) pays for the keys it can see. Beside the
# kernel it runs as far as the highest of the rows' FIRST positions: no
# step at all for a call of fresh prompts' first pieces.
KEY_BLOCK = 512
# A prefill call's own tokens go through ``ops.pallas.flash_attention``'s
# forward kernel where the call has more than ``ABSORB_MAX_QUERIES`` tokens a
# row in whole tiles of this many (every warmed bucket from 256 up) and the
# kernel path resolves as it does for decode (``prefill_takes_kernel``).
KERNEL_TOKENS_MULTIPLE = 128
# Queries and keys a tile of that kernel: at 1 x 2,048 tokens, 32 heads,
# 192 / 128 wide, 0.87 ms a call on the v5e against 1.16 at the kernel's own
# 512 (a float32 (2048, 512) tile does not fit its VMEM; PERF.md section 6,
# PR 51).
KERNEL_BLOCK = 1024
NEG_INF = -1e30
# Counters that combine across layers by the largest, not the sum.
LARGEST_OF = ("moe_expert_load_max", "mhc_sinkhorn_residual_e6")


def prefill_takes_kernel(tokens: int, path: str) -> bool:
    """Whether a call of ``tokens`` (padded) tokens a row over a cache scores
    its own tokens through the flash forward kernel; ``path`` as
    ``resolve_paged_decode`` gives it."""
    return (tokens > ABSORB_MAX_QUERIES
            and tokens % KERNEL_TOKENS_MULTIPLE == 0 and path != "xla")


def walk_keys(block_size: int) -> int:
    """Keys a step of the loop over cached latents covers: whole blocks."""
    return max(1, KEY_BLOCK // block_size) * block_size


class LatentAttention(nn.Module):
    cfg: ModelConfig

    @nn.compact
    def __call__(self, x, cos, sin, positions, cache: Optional[dict] = None):
        cfg = self.cfg
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        b, s, _ = x.shape
        H, r = cfg.num_heads, cfg.kv_lora_rank
        nope, rope_d, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                            cfg.v_head_dim)
        yarn_factor = yarn_softmax_factor(cfg.yarn)
        scale = (nope + rope_d) ** -0.5 * yarn_factor

        def proj(name, features):
            return LoRADense(features=features, use_bias=False, dtype=dtype,
                             param_dtype=pdtype, name=name, lora_r=0)

        if cfg.q_lora_rank:
            q = proj("q_b_proj", H * (nope + rope_d))(
                RMSNorm(cfg.rms_norm_eps, name="q_a_norm")(
                    proj("q_a_proj", cfg.q_lora_rank)(x)))
        else:
            q = proj("q_proj", H * (nope + rope_d))(x)
        q = q.reshape(b, s, H, nope + rope_d)
        q_nope = q[..., :nope]
        q_rope = apply_rope(q[..., nope:], cos, sin, positions,
                            interleaved=cfg.rope_interleave)
        kv_a = proj("kv_a_proj", r + rope_d)(x)
        c = RMSNorm(cfg.rms_norm_eps, name="kv_a_norm")(kv_a[..., :r])
        k_rope = apply_rope(kv_a[..., None, r:], cos, sin, positions,
                            interleaved=cfg.rope_interleave)[:, :, 0]
        # One set of weights for both forms: (r, heads, nope + v).
        w_kvb = self.param("kv_b_proj", nn.initializers.lecun_normal(),
                           (r, H * (nope + vd)), pdtype).astype(dtype) \
            .reshape(r, H, nope + vd)

        def expand(latents):
            with jax.named_scope("dlti_mla_expand"):
                kv = jnp.einsum("bkr,rhd->bkhd", latents, w_kvb)
            return kv[..., :nope], kv[..., nope:]

        def absorb_query(q_nope):           # (..., H, nope) -> (..., H, r)
            with jax.named_scope("dlti_mla_absorb"):
                return jnp.einsum("...hn,rhn->...hr", q_nope,
                                  w_kvb[..., :nope])

        def absorb_output(o_lat):           # (..., H, r) -> (..., H, v)
            with jax.named_scope("dlti_mla_absorb"):
                return jnp.einsum("...hr,rhv->...hv", o_lat.astype(dtype),
                                  w_kvb[..., nope:])

        def walk(layer_cache, tables, absorb, before=None):
            """This call's queries against each row's cached rows (its own
            just written among them), a block of ``KEY_BLOCK`` keys at a time
            with an online softmax, up to the call's highest position. A
            key's index in a row's logical window is its position, so the
            explicit-position mask hides what is stale or unallocated.
            ``absorb``: score the rows themselves (few queries); else expand
            each block to keys and values (many). ``before`` (b,): only the
            keys below each row's, what earlier calls wrote, in steps up to
            the highest of them (none where every row starts at 0). Returns
            the online softmax's ``(m, l, acc)``, not yet divided."""
            block_size = layer_cache["latent"].shape[1]
            keys = walk_keys(block_size)
            blocks = keys // block_size
            tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % blocks)))
            q_lat = absorb_query(q_nope) if absorb else None

            def step(j, carry):
                m, l, acc = carry
                rows = latent_gather(layer_cache, jax.lax.dynamic_slice_in_dim(
                    tables, j * blocks, blocks, axis=1)).astype(dtype)
                lat, key = rows[..., :r], rows[..., r:r + rope_d]
                if absorb:
                    scores = jnp.einsum("bshr,bkr->bhsk", q_lat, lat,
                                        preferred_element_type=jnp.float32)
                    values, out = lat, "bhsk,bkr->bshr"
                else:
                    k_nope, values = expand(lat)
                    scores = jnp.einsum("bshn,bkhn->bhsk", q_nope, k_nope,
                                        preferred_element_type=jnp.float32)
                    out = "bhsk,bkhv->bshv"
                scores = (scores + jnp.einsum(
                    "bshd,bkd->bhsk", q_rope, key,
                    preferred_element_type=jnp.float32)) * scale
                visible = (j * keys + jnp.arange(keys))[None, None, None, :] \
                    <= (positions[:, None, :, None] if before is None
                        else before[:, None, None, None] - 1)
                scores = jnp.where(visible, scores, NEG_INF)
                m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
                p = jnp.exp(scores - m_new) * visible
                alpha = jnp.exp(m - m_new)                    # (b, h, s, 1)
                l = alpha * l + jnp.sum(p, -1, keepdims=True)
                acc = acc * jnp.swapaxes(alpha, 1, 2) + jnp.einsum(
                    out, p.astype(dtype), values,
                    preferred_element_type=jnp.float32)
                return m_new, l, acc

            width = r if absorb else vd
            return jax.lax.fori_loop(
                0, jnp.max(positions) // keys + 1 if before is None
                else (jnp.max(before) + keys - 1) // keys, step,
                (jnp.full((b, H, s, 1), NEG_INF, jnp.float32),
                 jnp.zeros((b, H, s, 1), jnp.float32),
                 jnp.zeros((b, s, H, width), jnp.float32)))

        def over_cache(layer_cache, tables, absorb):
            """Every key a query can see through the loop (the CPU form, and
            the absorbed form of a call of few tokens)."""
            _, l, acc = walk(layer_cache, tables, absorb)
            # a padding row (every position -1) saw no key: l = 0
            out = acc / jnp.maximum(jnp.swapaxes(l, 1, 2), 1e-30)
            return absorb_output(out) if absorb else out.astype(dtype)

        def own_then_cached(layer_cache, tables, interpret):
            """A prefill call's queries over two sets of keys, each in the
            form that suits it, merged by their log-sum-exps in float32 (the
            one softmax over all of them, re-associated as the loop's online
            softmax already is). The call's own tokens: expanded once and
            scored by the flash forward kernel, causal by index (a row is
            ``start + arange(n)`` then -1s, so index order is position
            order), a padding token segment 0; nothing of them is read back
            from the pool. What earlier calls wrote: the loop, over keys
            below each row's first position."""
            from dlti_tpu.ops.pallas.flash_attention import (
                flash_attention_fwd,
            )

            k_nope, v = expand(c)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rope[:, :, None], (b, s, H, rope_d))], axis=-1)
            own, lse = flash_attention_fwd(
                jnp.concatenate([q_nope, q_rope], axis=-1), k, v,
                scale=scale, causal=True,
                segment_ids=(positions >= 0).astype(jnp.int32),
                block_q=KERNEL_BLOCK, block_kv=KERNEL_BLOCK,
                interpret=interpret)
            first = jnp.maximum(positions[:, 0], 0)

            def with_cached():
                m, l, acc = walk(layer_cache, tables, False, before=first)
                # The kernel's lse of a query that saw no key (a padding
                # token) is +1e30, for its backward: here it weighs nothing.
                own_lse = jnp.where(lse > -NEG_INF / 2, NEG_INF,
                                    lse)[..., None]
                top = jnp.maximum(m, own_lse)               # (b, h, s, 1)
                w_own, w_cached = jnp.exp(own_lse - top), jnp.exp(m - top)
                # a padding token: both weights 1 over zeros, the divisor 1
                out = (acc * jnp.swapaxes(w_cached, 1, 2)
                       + own.astype(jnp.float32) * jnp.swapaxes(w_own, 1, 2)) \
                    / jnp.swapaxes(w_cached * l + w_own, 1, 2)
                return out.astype(dtype)

            # fresh prompts' first pieces alone: the kernel's output as it is
            return jax.lax.cond(jnp.max(first) > 0, with_cached, lambda: own)

        new_cache = None
        if cache is not None:
            # Paged latents (the serving engine): scatter this call's rows
            # into the pool, then attend over each row's gathered window.
            # Stale or unallocated slots lie at positions past the query's,
            # which the explicit-position mask hides.
            pool = cache["latent"]
            tables = cache["block_tables"]
            slots = slot_mapping(tables, positions, pool.shape[1],
                                 pool.shape[0])
            new_cache = latent_update(
                cache, jnp.concatenate([c, k_rope], axis=-1), slots)
            path, _ = resolve_paged_decode(cfg.paged_attention_impl,
                                           tp_sharded=False)
            if s == 1 and path != "xla":
                from dlti_tpu.ops.pallas.latent_attention import (
                    latent_decode_attention,
                )

                o_lat = latent_decode_attention(
                    jnp.concatenate([absorb_query(q_nope[:, 0]),
                                     q_rope[:, 0]], axis=-1),
                    new_cache["latent"], tables, positions[:, 0] + 1,
                    value_dim=r, scale=scale,
                    interpret=path == "pallas-interpret")
                out = absorb_output(o_lat)[:, None]
            elif prefill_takes_kernel(s, path):
                with jax.named_scope("dlti_mla_prefill_attn"):
                    out = own_then_cached(new_cache, tables,
                                          path == "pallas-interpret")
            else:
                out = over_cache(new_cache, tables,
                                 absorb=s <= ABSORB_MAX_QUERIES)
        else:
            # No cache (training, the reference's side of a test): the
            # expanded form over the call's own tokens.
            k_nope, v = expand(c)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_rope[:, :, None], (b, s, H, rope_d))], axis=-1)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            if yarn_factor != 1.0:      # reference_attention scales by d^-0.5
                q = q * yarn_factor
            out = reference_attention(q, k, v, causal=True,
                                      q_positions=positions)
        out = proj("o_proj", cfg.hidden_size)(
            out.astype(dtype).reshape(b, s, H * vd))
        return out, new_cache


class LatentBlock(nn.Module):
    """One layer. ``cfg.hc_mult`` 0: ``x`` (b, s, h) through the plain
    residual. ``hc_mult`` n >= 1: ``x`` is the n streams (n, b, s, h) and
    both sublayers go through their stream maps (``models.hyper``). Returns
    ``(x, new cache, expert counters or None, map counters or None)``."""

    cfg: ModelConfig
    dense: bool

    @nn.compact
    def __call__(self, x, cos, sin, positions, cache=None, token_mask=None):
        cfg = self.cfg
        new_cache, moe = None, None

        def attention(u):
            nonlocal new_cache
            out, new_cache = LatentAttention(cfg, name="attn")(
                RMSNorm(cfg.rms_norm_eps, name="input_norm")(u),
                cos, sin, positions, cache)
            return out

        def mlp(u):
            nonlocal moe
            h = RMSNorm(cfg.rms_norm_eps, name="post_attn_norm")(u)
            if self.dense:
                return LlamaMLP(cfg, None, name="mlp")(h)
            out, moe = HeldExpertsMLP(cfg, name="mlp")(h, token_mask)
            return out

        if not cfg.hc_mult:
            x = x + attention(x)
            return x + mlp(x), new_cache, moe, None
        mask = jnp.ones(x.shape[1:3], bool) if token_mask is None \
            else token_mask
        counted = []
        for name, sublayer in (("attn_hc", attention), ("mlp_hc", mlp)):
            h_pre, h_post, h_res, n = HyperMaps(cfg, name=name)(x, mask)
            x = mix_out(x, sublayer(mix_in(x, h_pre)), h_post, h_res)
            counted.append(n)
        return x, new_cache, moe, jnp.stack(
            [jnp.maximum(counted[0][0], counted[1][0]),
             counted[0][1] + counted[1][1]])


class LatentForCausalLM(nn.Module):
    """Body + untied head. Returns float32 logits and the new cache; with
    ``return_counters`` also ``{name: int32 scalar}`` for ``counter_names``,
    what this pass counted (the expert layers' counters and, with
    ``hc_mult``, the stream maps').

    With ``hc_mult`` n the residual path is n streams (``models.hyper``):
    the embedding row repeated n times goes in, every block mixes the
    streams round its two sublayers, and their sum goes to ``final_norm``."""

    cfg: ModelConfig
    lora: Optional[LoRAConfig] = None
    mesh: Optional[Any] = None
    prefill_call_tokens = PREFILL_CALL_TOKENS
    # A prefill call takes each row's WHOLE block table, not the narrowest
    # power of two that holds the row: gathering a cached context costs 1,280
    # bytes a token and layer, and a program a table width would multiply
    # the programs a prefix hit's few tokens can meet by the ladder of
    # widths, which no warm-up reaches without replaying the hit.
    prefill_whole_tables = True

    @property
    def counter_names(self) -> tuple:
        return MOE_COUNTERS + (MHC_COUNTERS if self.cfg.hc_mult else ())

    @nn.compact
    def __call__(self, input_ids, positions=None, segment_ids=None,
                 cache=None, deterministic: bool = True, token_mask=None,
                 return_hidden: bool = False, return_counters: bool = False):
        cfg = self.cfg
        if segment_ids is not None:
            raise NotImplementedError(
                "packed rows through latent attention are not supported")
        if self.lora is not None and self.lora.enabled:
            raise NotImplementedError(
                "LoRA through latent attention and held experts is not "
                "implemented (no adapter branch on kv_b_proj or the experts)")
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        b, s = input_ids.shape
        # Seeded at unit scale, as the other held-expert family is: the
        # residual stream carries the token and the layers add to it
        # (models.moe.centred_out_init).
        embed = self.param("embed_tokens", nn.initializers.normal(1.0),
                           (cfg.vocab_size, cfg.hidden_size), pdtype)
        x = jnp.take(embed, input_ids, axis=0).astype(dtype)
        if cfg.hc_mult:
            x = jnp.broadcast_to(x, (cfg.hc_mult, *x.shape))
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        if cache is None:
            table_len = max(cfg.max_seq_len, s)
            assert_rope_table_covers(table_len, s, "training/no-cache path")
        else:
            # capacity of a row's logical window: blocks a row x block size
            table_len = cache[0]["block_tables"].shape[1] \
                * cache[0]["latent"].shape[1]
        cos, sin = rope_frequencies(cfg.qk_rope_head_dim, table_len,
                                    cfg.rope_theta, cfg.yarn)
        routed = positions >= 0
        if cache is not None:
            # A decode row of a slot that is free or still prefilling carries
            # position 0 and a table of the reserved trash block (block 0,
            # which no sequence is ever given): not a token to route or count.
            routed = routed & (cache[0]["block_tables"][:, :1] > 0)
        if token_mask is not None:
            routed = routed & token_mask.astype(bool)

        counters = dict.fromkeys(self.counter_names, jnp.int32(0))
        new_caches = [] if cache is not None else None
        for i in range(cfg.num_layers):
            x, layer_cache, moe, maps = LatentBlock(
                cfg, i < cfg.first_k_dense, name=f"layers_{i}")(
                    x, cos, sin, positions,
                    cache[i] if cache is not None else None, routed)
            if cache is not None:
                new_caches.append(layer_cache)
            for names, counted in ((MOE_COUNTERS, moe), (MHC_COUNTERS, maps)):
                for name, n in zip(names, () if counted is None else counted):
                    counters[name] = jnp.maximum(counters[name], n) \
                        if name in LARGEST_OF else counters[name] + n
        if cfg.hc_mult:
            x = jnp.sum(x.astype(jnp.float32), axis=0).astype(dtype)
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

    def head_matrix(self, params, anchor):
        return head_matrix_from_leaves(params["embed_tokens"],
                                       params.get("lm_head"), False, anchor)

    def prefill_kernel_counts(self, rows: int, bucket: int, chunks: list,
                              block_size: int) -> tuple:
        """What a prefill call of ``rows`` x ``bucket`` padded tokens does,
        read on the host from its shape and its real rows' ``(tokens, first
        position)``: ``(query tokens whose own keys go through the flash
        kernel, steps of the loop over cached latents x rows)``; zeros for
        a call that takes another form."""
        path, _ = resolve_paged_decode(self.cfg.paged_attention_impl,
                                       tp_sharded=False)
        if not prefill_takes_kernel(bucket, path):
            return 0, 0
        steps = -(-max(start for _, start in chunks) // walk_keys(block_size))
        return sum(tokens for tokens, _ in chunks), steps * rows
