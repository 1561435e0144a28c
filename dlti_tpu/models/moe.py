"""Mixture-of-Experts MLPs: two layers, two ways to route.

**``MoEMLP``** — Mixtral-style softmax top-k routed SwiGLU experts, expressed
with static shapes: tokens are packed into fixed-capacity per-expert buffers
with one-hot dispatch / combine einsums (the GShard/Switch formulation). The
only data-dependent effect is token dropping when an expert overflows its
capacity — controlled by ``moe_capacity_factor``. It serves the
``num_experts`` presets (Mixtral) in training and serving.

Expert parallelism rides a dedicated ``expert`` mesh axis: the stacked
expert weights ``(E, ...)`` shard on dim 0, the dispatched activations
``(E, C, h)`` shard on their expert dim, and GSPMD inserts the
all-to-all between the token-sharded and expert-sharded layouts.

The router's load-balance auxiliary loss (Switch §2.2 / Mixtral) is
recorded via ``self.sow("intermediates", "router_aux_loss", ...)``; the
train step collects it when ``ModelConfig.num_experts > 0``.

**``HeldExpertsMLP``** — the dropless layer of the patterned families
(``ModelConfig.layer_pattern``, "E" layers) and of the latent-attention
family's expert layers (``models.latent``): no capacity, no token dropped.
The routed sum is computed one of two ways, chosen from the call's static
shape alone (``takes_grouped`` and the note above it): a call of few
tokens (a decode round, a prompt's short tail) runs every held expert over
every token under the routing weights as a mask, which costs the weights'
read and nothing else; a call of many tokens (a prefill), where the kernel
takes the width the experts are held at (``grouped_experts.held_width``:
the published width, or the next one of whole lane tiles, zero-padded,
where that is near), lays the held assignments out by expert and
runs each tile of rows through its expert alone
(``ops.pallas.grouped_experts``): top-k of ``held_n`` of the mask's
products. The same sum either way, no assignment dropped.
The layer is told which experts it holds (``moe_held_start``,
``moe_held_count``), routes over all ``moe_num_experts``, and computes its
own experts' part of the result plus the shared expert — what expert
parallelism asks of one chip. Nothing stands in for the experts held
elsewhere.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from dlti_tpu.config import ModelConfig
from dlti_tpu.models.llama import _dtype
from dlti_tpu.models.lora import LoRADense

# What a HeldExpertsMLP call counts, in this order (summed over the expert
# layers and steps of a program, the maximum apart): routed assignments of
# real tokens; those on experts held here; held experts with at least one;
# the largest count on one held expert in one layer-step; held assignments
# that went through the grouped product (over ``moe_held_assignments``: the
# share of the routed work it took) and the rows of the tiles it ran (the
# former over this: how full its tiles were).
MOE_COUNTERS = ("moe_assignments", "moe_held_assignments",
                "moe_experts_touched", "moe_expert_load_max",
                "moe_grouped_rows", "moe_grouped_tile_rows")


class MoEMLP(nn.Module):
    """Top-k routed expert SwiGLU MLP (drop-in for LlamaMLP)."""

    cfg: ModelConfig
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True,
                 token_mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """``token_mask`` (b, s): 1 for real tokens, 0 for padding. Padding
        tokens are excluded from routing — they'd otherwise consume expert
        capacity (displacing real tokens of later sequences in the batch)
        and bias the load-balance statistics."""
        cfg = self.cfg
        dtype = _dtype(cfg.dtype)
        pdtype = _dtype(cfg.param_dtype)
        b, s, h = x.shape
        E = cfg.num_experts
        k = cfg.num_experts_per_tok
        m = cfg.intermediate_size
        T = b * s
        valid = (jnp.ones((T,), jnp.float32) if token_mask is None
                 else token_mask.reshape(T).astype(jnp.float32))

        # Router in fp32 for stable softmax/top-k.
        router_kernel = self.param(
            "router", nn.initializers.lecun_normal(), (h, E), jnp.float32)
        xt = x.reshape(T, h)
        logits = jnp.dot(xt.astype(jnp.float32), router_kernel)          # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_w, topk_idx = jax.lax.top_k(probs, k)                        # (T, k)
        topk_w = topk_w / jnp.maximum(
            jnp.sum(topk_w, axis=-1, keepdims=True), 1e-9)  # Mixtral renorm
        topk_w = topk_w * valid[:, None]

        # Fixed expert capacity (static shape): each expert accepts at most
        # C of the T*k routed slots; overflow tokens are dropped for that
        # expert (their combine weight is zeroed).
        C = max(int(cfg.moe_capacity_factor * T * k / E), 1)

        # Position of each (token, slot) within its expert's buffer,
        # counted over slots-major order so slot 0 (highest router weight)
        # wins buffer space first.
        flat_e = topk_idx.T.reshape(-1)                                   # (k*T,)
        flat_valid = jnp.tile(valid, k).astype(jnp.int32)
        # Padding tokens take no buffer rank and never dispatch.
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32) * flat_valid[:, None]
        pos = jnp.cumsum(onehot, axis=0) * onehot - onehot                # rank in expert
        pos = jnp.sum(pos, axis=-1)                                       # (k*T,)
        keep = (pos < C) & (flat_valid > 0)

        slot_w = topk_w.T.reshape(-1) * keep                              # (k*T,)
        # dispatch[t, e, c]: token t occupies slot c of expert e.
        disp = (jax.nn.one_hot(flat_e, E, dtype=jnp.float32)[:, :, None]
                * jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                                 dtype=jnp.float32)[:, None, :C])          # (kT,E,C)
        combine = disp * slot_w[:, None, None]
        # Fold the k slots back onto tokens.
        disp = disp.reshape(k, T, E, C).sum(0)
        combine = combine.reshape(k, T, E, C).sum(0)

        expert_in = jnp.einsum("tec,th->ech", disp.astype(dtype),
                               xt.astype(dtype))                          # (E,C,h)
        expert_in = self._expert_constraint(expert_in)

        w1 = self.param("w1", nn.initializers.lecun_normal(), (E, h, m), pdtype)
        w3 = self.param("w3", nn.initializers.lecun_normal(), (E, h, m), pdtype)
        w2 = self.param("w2", nn.initializers.lecun_normal(), (E, m, h), pdtype)
        if isinstance(w1, dict):  # int8 serving (per-expert-channel scales)
            from dlti_tpu.models.quantization import maybe_dequantize

            w1, w2, w3 = (maybe_dequantize(w, dtype, anchor=expert_in)
                          for w in (w1, w2, w3))

        hidden = (nn.silu(jnp.einsum("ech,ehm->ecm", expert_in, w1.astype(dtype)))
                  * jnp.einsum("ech,ehm->ecm", expert_in, w3.astype(dtype)))
        out_e = jnp.einsum("ecm,emh->ech", hidden, w2.astype(dtype))
        out_e = self._expert_constraint(out_e)

        y = jnp.einsum("tec,ech->th", combine.astype(dtype), out_e)       # (T,h)

        # Load-balance aux loss (Switch Transformers eq. 4, Mixtral's k
        # normalization): E * sum_e f_e * P_e with f_e = fraction of routed
        # *assignments* landing on expert e, P_e = mean router prob.
        # Equals 1 at perfect balance, its minimum.
        n_valid = jnp.maximum(jnp.sum(valid), 1.0)
        frac = (jnp.sum(
            jax.nn.one_hot(topk_idx, E, dtype=jnp.float32).sum(1)
            * valid[:, None], axis=0) / (n_valid * k))
        mean_prob = jnp.sum(probs * valid[:, None], axis=0) / n_valid     # (E,)
        aux = E * jnp.sum(frac * mean_prob)
        self.sow("intermediates", "router_aux_loss", aux)

        return y.reshape(b, s, h)

    def _expert_constraint(self, v: jnp.ndarray) -> jnp.ndarray:
        """Pin the expert dim to the 'expert' mesh axis (GSPMD then places
        the all-to-all between token- and expert-sharded layouts).

        Inside a shard_map with manual axes (the PP x EP case: 'pipe' is
        manual, 'expert' auto), a constraint built on the CONCRETE mesh
        is rejected ("axes in vma should be Manual") — the current
        *abstract* mesh carries the right Manual/Auto axis types, so use
        it whenever it is active."""
        if (self.mesh is not None and "expert" in self.mesh.shape
                and self.mesh.shape["expert"] > 1):
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = self.mesh
            try:
                am = jax.sharding.get_abstract_mesh()
                if am is not None and not am.empty and "expert" in am.shape:
                    mesh = am
            except Exception:
                pass
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P("expert", None, None)))
        return v


# Which way HeldExpertsMLP computes a call's routed sum is read from the
# call's token count (rows x bucket, padding included): at least this many
# and the held assignments go through ``ops.pallas.grouped_experts`` in tiles
# of GROUPED_TILE_ROWS rows; fewer and every held expert runs over every
# token under the routing weights as a mask. One layer of 64 held experts at
# the published widths on the v5e, bf16, masked / grouped ms a call
# (``benchmarks_dev/moe_grouped_sweep.py``, my chip runs, PR 42; nemotron's
# last column PR 50):
#
#   tokens  xing4 3,584x1,024  nemotron 2,688 x 1,856          kanana 2,048x768
#           gated, top-4 of 64 relu2, ~3 of 64 held            gated, ~3 of 64
#                              as published   held at 1,920
#       32     2.03 /  1.80     1.82 / ( 1.93)   2.02 /  1.65    0.92 /  0.82
#      128     2.08 /  2.29     1.87 / ( 2.34)                   0.95 /  1.02
#      256     2.16 /  2.38     1.94 / ( 2.46)   2.10 /  2.12    0.99 /  1.08
#      512     4.00 /  2.61     3.61 / ( 2.58)   3.65 /  2.24    1.74 /  1.16
#    1,024     7.68 /  2.76     7.09 / ( 3.07)   7.20 /  2.72    3.37 /  1.35
#    2,048    15.26 /  3.73    14.07 / ( 4.01)  14.24 /  3.52    6.64 /  1.53
#    4,096    30.40 /  5.66    28.76 / ( 6.16)                  13.20 /  2.94
#
# (nemotron as published, grouped, in brackets: an earlier revision of the
# kernel, which took any width as one block, and with which the family's
# whole prefill program did not return: below. The masked column there is
# what its calls cost until PR 50; the same sweep in PR 50 read it 1.82,
# 1.96, 3.62, 7.10, 14.06 at 32, 256, 512, 1,024, 2,048 tokens. Held at
# 2,048, whole chunks: 2.00 / 1.75, 2.14 / 2.25, 4.02 / 2.38, 7.77 / 2.86,
# 15.40 / 3.62 at the same counts, 2.04 / 2.18 at 128, 30.8 / 5.70 at 4,096.)
#
# Up to 256 tokens the mask costs the weights' read and nothing else, and the
# layout (a rank, a scatter, two gathers of rows) is what grouped adds to
# it; from 512 the mask's 64-fold products show and grouped is first, so the
# boundary is the same at every geometry. (Call sizes are powers of two:
# nothing lies between 256 and 512.) Tiles of 128 rows were best or within
# 3 % everywhere (256 rows: +25-30 % under 1,024 tokens for the padding,
# equal from 2,048: an earlier revision's sweep, which took the tile as a
# variant).
# What was tried before, and stays out: sorted by expert through
# ``jax.lax.ragged_dot`` (my chip runs, PR 30: masked / sorted 32 tokens
# 1.89 / 8.73 ms, 512 3.84 / 17.4, 1,024 7.42 / 18.9, 2,048 14.7 / 22.5:
# it pays ~14 ms for its 64 groups however few rows it is given, and a
# 13-layer prefill of 2 rows x 2,048 tokens through it never returned).
#
# The kernel takes an expert's width in whole chunks of 256 columns, or in
# lane tiles of 128 where the width is whole lane tiles and not whole chunks
# (``grouped_experts.takes_width``, ``width_chunk``). Of the geometries
# served one is neither: nemotron3_nano_30b's 1,856 (7.25 chunks, 14.5 lane
# tiles). The form of the kernel that took any width as one block won at
# 1,856 alone (the bracketed column), but that family's 13-layer prefill
# program at 2 rows x 1,024 tokens never returned on the v5e with it in the
# five expert layers, and returned in 0.05 s with the experts 1,792 or 2,048
# wide through the kernel as it stood (my chip runs, PR 42; PERF.md section
# 7 item 6 has the bisection: the width that is not whole lane tiles is what
# it takes; in the program compiled for a v5e such a ``w_up`` is stored with
# the hidden size minor and copied whole, 638 MB a layer a call, to be
# handed to a kernel). So the layer holds its experts at a width the kernel
# takes (``grouped_experts.held_width``: 1,856 at 1,920, the pad zeros;
# every other served width as published) and that family's calls of 512
# tokens or more go through the kernel as every other's do: every prefill
# shape its cell warms returned on the chip (my chip runs, PR 50; PERF.md
# section 6). The price is the decode step's: its mask reads what is held,
# 2.02 ms a layer for 1.82 at 32 tokens. Which width, of 1,920 and 2,048:
# the mask costs the same at both (2.02, 2.00), the kernel is 3-6 % faster
# at 1,920 and 0.44 GB fewer zeros are held.
GROUPED_MIN_TOKENS = 512
GROUPED_TILE_ROWS = 128


def takes_grouped(tokens: int, width: int) -> bool:
    """Whether a call of ``tokens`` (rows x bucket) over experts held
    ``width`` wide goes through the grouped product: read from static shapes
    alone."""
    if tokens < GROUPED_MIN_TOKENS:
        return False
    from dlti_tpu.ops.pallas.grouped_experts import takes_width

    return takes_width(width)


def routed_masked(xs, local, w, w_gate, w_up, w_down):
    """Every held expert over every token, the routing weight as the mask:
    nothing sorted or gathered, each expert's weights read once, which for
    a decode step or a short prompt is all the time there is. ``xs``
    (T, h); ``local`` (T, k) the held expert of each assignment or
    ``held_n``; ``w`` (T, k) float32 routing weights; ``w_gate`` None for
    ungated relu² experts."""
    T, held_n = xs.shape[0], w_up.shape[0]
    gate = jnp.zeros((T, held_n + 1), jnp.float32).at[
        jnp.arange(T)[:, None], local].add(w)[:, :held_n].astype(xs.dtype)
    act = jnp.einsum("th,ehf->tef", xs, w_up)
    act = jax.nn.silu(jnp.einsum("th,ehf->tef", xs, w_gate)) * act \
        if w_gate is not None else _relu2(act)
    return jnp.einsum("tef,efh->th", act * gate[:, :, None], w_down)


def routed_grouped(xs, local, sizes, w, w_gate, w_up, w_down):
    """The same sum over the held assignments laid out by expert: rows of
    ``xs`` gathered into that order, each tile of rows through its expert
    (one kernel), and a token's result the float32 sum over its k
    assignments of routing weight x its row, gathered back (no scatter-add:
    the order of additions is fixed). Arguments as ``routed_masked``, with
    ``sizes`` (held_n,) the assignments on each held expert. Returns
    ``(y, rows of the tiles that ran)``."""
    from dlti_tpu.ops.attention import _kernel_path
    from dlti_tpu.ops.pallas.grouped_experts import (
        group_rows, grouped_experts,
    )

    held_n, tile_rows = w_up.shape[0], GROUPED_TILE_ROWS
    row, source, tile_expert, tiles = group_rows(local, sizes, tile_rows)
    out = grouped_experts(
        jnp.take(xs, source, axis=0, mode="clip"), tile_expert, tiles,
        w_gate, w_up, w_down, tile_rows=tile_rows,
        interpret=_kernel_path() == "pallas-interpret")
    # A row past the last tile that ran was never written: chosen, not
    # multiplied by zero.
    mine = jnp.where((local < held_n)[:, :, None],
                     jnp.take(out, row, axis=0, mode="clip"), 0)
    y = jnp.sum(mine.astype(jnp.float32) * w[:, :, None], axis=1)
    return y.astype(xs.dtype), tiles * tile_rows


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def zero_padded(base, axis: int, width: int):
    """``base`` drawn at ``width`` along ``axis`` (the published shape, so
    the draw is the one an unpadded layer makes) and zeros from there to the
    shape asked for."""

    def init(key, shape, dtype=jnp.float32):
        published = list(shape)
        published[axis] = width
        pad = [(0, 0)] * len(shape)
        pad[axis] = (0, shape[axis] - width)
        return jnp.pad(base(key, tuple(published), dtype), pad)

    return init


def centred_out_init(scale: float, batch_axis=()):
    """Seeded weights of a relu² MLP's down projection: LeCun-normal times
    ``scale``, each output's weights centred over the inputs.

    relu² is positive, so an uncentred projection puts one fixed vector, the
    image of the mean activation, on every token: after five such layers the
    residual streams of different tokens had a cosine of 0.9 and a decode
    step's 32 tokens all chose the same experts, which no trained model's
    traffic does. ``scale`` keeps a layer's part of the residual stream
    below the embedding's, as a trained model's is: a routing flip between
    two near-tied experts (bf16 against float32) then moves a log-prob by
    hundredths, not tenths."""
    base = nn.initializers.variance_scaling(
        scale * scale, "fan_in", "truncated_normal", batch_axis=batch_axis)

    def init(key, shape, dtype=jnp.float32):
        w = base(key, shape, jnp.float32)
        return (w - jnp.mean(w, axis=-2, keepdims=True)).astype(dtype)

    return init


# Seeded scales (see centred_out_init): the routed experts' and the shared
# expert's down projections; the selection bias in units of a score (the
# scores of one token spread by ~0.2, so this moves a choice now and then
# and does not make it).
ROUTED_OUT_SCALE, SHARED_OUT_SCALE, SCORE_BIAS_STD = 0.1, 0.25, 0.025


class HeldExpertsMLP(nn.Module):
    """Dropless top-k routed experts, of which this process holds a range,
    and a shared expert for every token.

        s = sigmoid(x W_r)                      float32, all E experts
        chosen = top-k of s + e_score_correction_bias
        w = s[chosen] / sum(s[chosen]) * moe_routed_scaling
        y = sum_{e chosen and held here} w_e W_down,e f_e(x)  +  S_down f_S(x)

    An expert's inner part ``f`` is one of two, by ``mlp_activation``:
    "relu2" ungated, ``relu(W_up x)^2`` (nemotron_h), or "silu" gated,
    ``silu(W_gate x) * W_up x`` (deepseek_v3). The shared
    part ``S`` has the same form at ``moe_shared_intermediate_size``: one
    shared expert, or several as one MLP of their summed width (the sum of
    n gated MLPs of width f is one of width n x f).

    The routed experts' matrices are held at ``grouped_experts.held_width``
    of the published ``moe_intermediate_size`` ``f``: where that is wider,
    columns ``f:`` of ``w_up`` and ``w_gate`` and rows ``f:`` of ``w_down``
    are zero. ``relu(x 0)^2 = 0`` and ``silu(x 0) (x 0) = 0``, and a zero
    row of ``w_down`` adds nothing, so the sum over the held width is the
    sum over the published one exactly. The seeded draw is made at the
    published shape and padded, so the unpadded part is what an unpadded
    layer draws. A pad's gradient is zero on both sides (the activation
    there is 0, and so is its derivative at 0 times 0), so a training path,
    which goes through the mask, leaves the pads zero."""

    cfg: ModelConfig

    @nn.compact
    def __call__(self, x: jnp.ndarray,
                 token_mask: Optional[jnp.ndarray] = None):
        """``token_mask`` (b, s): true for real tokens; the rest are not
        routed (they would count as load and touch experts). Returns
        ``(y, counters int32 in the order of MOE_COUNTERS)``."""
        cfg = self.cfg
        if cfg.mlp_activation not in ("relu2", "silu") \
                or cfg.moe_scoring != "sigmoid_bias":
            raise NotImplementedError(
                f"HeldExpertsMLP computes ungated relu2 or gated silu "
                f"experts under sigmoid_bias scoring; got mlp_activation "
                f"{cfg.mlp_activation!r}, moe_scoring {cfg.moe_scoring!r}")
        gated = cfg.mlp_activation == "silu"
        dtype, pdtype = _dtype(cfg.dtype), _dtype(cfg.param_dtype)
        b, s, h = x.shape
        T, E, k = b * s, cfg.moe_num_experts, cfg.num_experts_per_tok
        from dlti_tpu.ops.pallas.grouped_experts import held_width

        lo, held_n, f = cfg.moe_held_start, cfg.moe_held, \
            cfg.moe_intermediate_size
        held_f = held_width(f)
        xt = x.reshape(T, h)
        valid = (jnp.ones((T,), bool) if token_mask is None
                 else token_mask.reshape(T).astype(bool))

        router = self.param("router", nn.initializers.lecun_normal(),
                            (h, E), jnp.float32)
        # Seeded non-zero so that a selection that ignored it would differ.
        bias = self.param("e_score_correction_bias",
                          nn.initializers.normal(SCORE_BIAS_STD), (E,),
                          jnp.float32)
        with jax.named_scope("dlti_moe_routed"):
            scores = jax.nn.sigmoid(jnp.dot(
                xt.astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST))
            _, chosen = jax.lax.top_k(scores + bias, k)               # (T,k)
            self.sow("intermediates", "chosen", chosen)  # for checks only
            w = jnp.take_along_axis(scores, chosen, axis=1)
            w = w / jnp.sum(w, axis=1, keepdims=True) * cfg.moe_routed_scaling
            held = (chosen >= lo) & (chosen < lo + held_n) & valid[:, None]

            def drawn(base, axis):
                return base if held_f == f else zero_padded(base, axis, f)

            def inner(name):
                return self.param(name, drawn(nn.initializers.lecun_normal(
                    batch_axis=(0,)), 2), (held_n, h, held_f),
                    pdtype).astype(dtype)

            w_gate = inner("w_gate") if gated else None
            w_up = inner("w_up")
            w_down = self.param("w_down", drawn(centred_out_init(
                ROUTED_OUT_SCALE, batch_axis=(0,)), 1), (held_n, held_f, h),
                pdtype).astype(dtype)
            # Tokens on each held expert (the counters, the grouped layout);
            # an assignment held elsewhere counts as none.
            local = jnp.where(held, chosen - lo, held_n)                # (T,k)
            sizes = jnp.bincount(local.reshape(-1), length=held_n + 1)[
                :held_n].astype(jnp.int32)
            xs = xt.astype(dtype)
            if takes_grouped(T, held_f):
                y, tile_rows = routed_grouped(xs, local, sizes, w, w_gate,
                                              w_up, w_down)
                grouped = (jnp.sum(held), tile_rows)
            else:
                y = routed_masked(xs, local, w, w_gate, w_up, w_down)
                grouped = (0, 0)
        with jax.named_scope("dlti_moe_shared"):
            if cfg.moe_shared_intermediate_size:
                def dense(name, features, **kw):
                    return LoRADense(features=features, use_bias=False,
                                     dtype=dtype, param_dtype=pdtype,
                                     name=name, **kw)

                act = dense("shared_up", cfg.moe_shared_intermediate_size)(xt)
                act = jax.nn.silu(dense(
                    "shared_gate", cfg.moe_shared_intermediate_size)(xt)) \
                    * act if gated else _relu2(act)
                y = y + dense("shared_down", h, kernel_init=centred_out_init(
                    SHARED_OUT_SCALE))(act)
        counters = jnp.stack([
            jnp.sum(valid) * k, jnp.sum(held), jnp.sum(sizes > 0),
            jnp.max(sizes), *grouped]).astype(jnp.int32)
        return y.reshape(b, s, h), counters


def collect_aux_loss(intermediates: dict) -> jnp.ndarray:
    """Sum every sown ``router_aux_loss`` scalar (one per MoE layer)."""
    total = jnp.float32(0.0)
    for leaf in jax.tree_util.tree_leaves(intermediates):
        total = total + jnp.sum(leaf)
    return total
