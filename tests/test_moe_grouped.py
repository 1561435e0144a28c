"""HeldExpertsMLP's two ways to the routed sum, held to each other and to a
float32 per-token loop at tiny shapes on the CPU (the kernel interpreted):
the mask (every held expert over every token) and the grouped product over
the held assignments laid out by expert (``ops.pallas.grouped_experts``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dlti_tpu.models.moe as moe
import dlti_tpu.ops.pallas.grouped_experts as kernel_module
from dlti_tpu.config import MODEL_PRESETS
from dlti_tpu.models.moe import MOE_COUNTERS, HeldExpertsMLP
from dlti_tpu.ops.pallas.grouped_experts import (
    group_rows, grouped_experts, num_tiles,
)

TILE = 8
EXPERTS, TOP_K = 8, 3


def _cfg(activation, **over):
    base = MODEL_PRESETS["nemotron_h_tiny" if activation == "relu2"
                         else "latent_tiny"]
    assert base.mlp_activation == activation
    return dataclasses.replace(base, **{**dict(
        dtype="float32", param_dtype="float32", moe_num_experts=EXPERTS,
        num_experts_per_tok=TOP_K, moe_held_start=0, moe_held_count=EXPERTS,
        moe_shared_intermediate_size=0), **over})


def _boundary(monkeypatch, min_tokens, tile_rows=TILE):
    """The shape rule at a tiny size: grouped from ``min_tokens`` tokens, in
    tiles of ``tile_rows`` rows, the kernel taking the width 8 columns at
    a time."""
    monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", min_tokens)
    monkeypatch.setattr(moe, "GROUPED_TILE_ROWS", tile_rows)
    monkeypatch.setattr(kernel_module, "WIDTH_CHUNK", 8)


def _apply(cfg, params, x, mask, grouped, monkeypatch):
    _boundary(monkeypatch, 1 if grouped else 1 << 30)
    y, counters = HeldExpertsMLP(cfg).apply({"params": params}, x, mask)
    return np.asarray(y), [int(c) for c in counters]


def _loop(cfg, params, x, mask):
    """The docstring's sum, a token and a choice at a time, in float32;
    also the held assignments on each held expert."""
    flat = np.asarray(x, np.float32).reshape(-1, x.shape[-1])
    valid = np.ones(len(flat), bool) if mask is None \
        else np.asarray(mask).reshape(-1)
    p = {k: np.asarray(v, np.float32) for k, v in params.items()}
    scores = 1 / (1 + np.exp(-(flat.astype(np.float64)
                               @ p["router"].astype(np.float64))))
    lo, n = cfg.moe_held_start, cfg.moe_held
    y = np.zeros_like(flat)
    sizes = np.zeros(n, int)
    for t in range(len(flat)):
        chosen = np.argsort(-(scores[t] + p["e_score_correction_bias"]),
                            kind="stable")[:TOP_K]
        w = scores[t, chosen] / scores[t, chosen].sum() \
            * cfg.moe_routed_scaling
        for e, we in zip(chosen, w):
            if not valid[t] or not lo <= e < lo + n:
                continue
            sizes[e - lo] += 1
            up = flat[t] @ p["w_up"][e - lo]
            if cfg.mlp_activation == "silu":
                g = flat[t] @ p["w_gate"][e - lo]
                act = g / (1 + np.exp(-g)) * up
            else:
                act = np.square(np.maximum(up, 0))
            y[t] += we * (act @ p["w_down"][e - lo])
    return y.reshape(x.shape), sizes, int(valid.sum())


def _padding(x):
    mask = np.ones(x.shape[:2], bool)
    mask[0, 7:] = False          # a row's padded tail
    mask[2] = False              # a row of padding alone
    return jnp.asarray(mask)


# name -> (held range, bias by expert, token mask); 3 x 11 tokens x top-3 =
# 99 assignments, which is no multiple of the tile
CASES = {
    "every_token_real": ((0, 8), {}, None),
    "padding_tokens_touch_no_expert": ((0, 8), {}, _padding),
    "half_of_the_experts_held": ((4, 4), {}, None),
    "an_expert_with_no_row": ((0, 8), {2: -10.0, 5: -10.0}, None),
    "one_expert_with_every_row": ((0, 8), {3: 10.0}, None),
    "half_held_under_padding": ((0, 4), {1: 10.0}, _padding),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("activation", ["relu2", "silu"])
def test_grouped_is_masked_is_the_loop(activation, case, monkeypatch):
    (lo, n), bias, masker = CASES[case]
    cfg = _cfg(activation, moe_held_start=lo, moe_held_count=n)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 11, cfg.hidden_size))
    mask = masker(x) if masker else None
    params = dict(HeldExpertsMLP(cfg).init(jax.random.PRNGKey(5), x)["params"])
    for e, b in bias.items():
        params["e_score_correction_bias"] = \
            params["e_score_correction_bias"].at[e].set(b)
    want, sizes, real = _loop(cfg, params, x, mask)

    masked, counted_m = _apply(cfg, params, x, mask, False, monkeypatch)
    grouped, counted_g = _apply(cfg, params, x, mask, True, monkeypatch)
    np.testing.assert_allclose(masked, want, atol=3e-5)
    np.testing.assert_allclose(grouped, want, atol=3e-5)
    np.testing.assert_allclose(grouped, masked, atol=3e-5)

    by_hand = [real * TOP_K, int(sizes.sum()), int((sizes > 0).sum()),
               int(sizes.max())]
    assert counted_m == by_hand + [0, 0]
    tile_rows = int((-(-sizes // TILE) * TILE).sum())
    assert counted_g == by_hand + [int(sizes.sum()), tile_rows]
    assert len(counted_g) == len(MOE_COUNTERS)
    if "no_row" in case:
        assert sizes[2] == sizes[5] == 0
    if "every_row" in case:
        assert sizes.max() == real
    if mask is not None:  # a padding token's result is the zero it was given
        assert not grouped[2].any() and not masked[2].any()


def test_the_path_is_read_from_the_call_s_static_shape_alone():
    """At the constants as they stand: a call of GROUPED_MIN_TOKENS tokens
    over experts whole chunks of the kernel wide is grouped, one token
    fewer is masked whatever the rows x bucket; nemotron's 1,856 is held
    at a width the kernel takes and goes grouped with the rest, and a width
    that is held as it is and is not whole chunks stays masked at any
    count."""
    assert moe.takes_grouped(512, 1024) and moe.takes_grouped(2048, 768)
    assert not moe.takes_grouped(256, 1024)
    assert moe.takes_grouped(2048, kernel_module.held_width(1856))
    assert not moe.takes_grouped(256, kernel_module.held_width(1856))
    assert not moe.takes_grouped(2048, kernel_module.held_width(96))
    cfg = _cfg("silu", moe_intermediate_size=256)
    T = moe.GROUPED_MIN_TOKENS
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T // 2, cfg.hidden_size))
    layer = HeldExpertsMLP(cfg)
    params = layer.init(jax.random.PRNGKey(5), x[:, :4])["params"]
    y, counters = layer.apply({"params": params}, x)
    held = int(counters[1])
    assert held == T * TOP_K == int(counters[4])
    assert int(counters[5]) % moe.GROUPED_TILE_ROWS == 0
    assert held <= int(counters[5]) <= held + EXPERTS * (
        moe.GROUPED_TILE_ROWS - 1)
    short, counters = layer.apply({"params": params}, x[:, :-1])
    assert [int(c) for c in counters[4:]] == [0, 0]
    np.testing.assert_allclose(np.asarray(y[:, :-1]), np.asarray(short),
                               atol=3e-5)
    text = jax.jit(lambda p, x: layer.apply({"params": p}, x)).lower(
        params, x[:, :-1]).as_text()
    assert "dlti_grouped_experts" not in text  # a masked call holds no kernel
    narrow = HeldExpertsMLP(_cfg("silu", moe_intermediate_size=96))
    params = narrow.init(jax.random.PRNGKey(5), x[:, :4])["params"]
    assert [int(c) for c in narrow.apply({"params": params}, x)[1][4:]] \
        == [0, 0]


def test_a_gradient_meets_the_mask_or_an_error_that_says_so(monkeypatch):
    cfg = _cfg("relu2")
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 9, cfg.hidden_size))
    layer = HeldExpertsMLP(cfg)
    params = layer.init(jax.random.PRNGKey(5), x)["params"]

    def loss(p):
        return jnp.sum(layer.apply({"params": p}, x)[0])

    assert np.isfinite(np.asarray(jax.grad(loss)(params)["w_up"])).all()
    _boundary(monkeypatch, 1)
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(loss)(params)


def test_the_two_counters_reach_metrics_and_decode_rounds_stay_masked(
        monkeypatch):
    """Through the normal path: a prompt's prefill call (one row x a bucket
    of 32) is at the boundary set here and goes grouped, its decode rounds
    (4 slots) do not; both counters are in ``/metrics`` beside the four."""
    import types

    from dlti_tpu.models import build_model
    from dlti_tpu.serving.engine import EngineConfig, InferenceEngine
    from dlti_tpu.serving.sampling import SamplingParams
    from dlti_tpu.serving.server import build_registry

    _boundary(monkeypatch, 32, tile_rows=16)
    cfg = MODEL_PRESETS["latent_tiny"]
    params = build_model(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = InferenceEngine(cfg, params, EngineConfig(
        max_seqs=4, block_size=8, num_blocks=96, max_model_len=160,
        cache_dtype="float32"))
    prompt = [int(t) for t in np.random.RandomState(3).randint(3, 512, 27)]
    eng.generate([prompt], SamplingParams(max_tokens=5, temperature=0.0))
    stats = eng.stats
    prefill = stats["moe_held_assignments"] \
        - stats["moe_held_assignments_decode"]
    assert stats["moe_grouped_rows"] == prefill > 0
    assert stats["moe_grouped_rows_decode"] == 0
    assert stats["moe_grouped_tile_rows_decode"] == 0
    assert stats["moe_grouped_tile_rows"] % 16 == 0
    assert stats["moe_grouped_tile_rows"] >= stats["moe_grouped_rows"]
    text = build_registry(
        types.SimpleNamespace(engine=eng)).render_prometheus()
    for name in MOE_COUNTERS:
        assert f"dlti_{name}" in text and f"dlti_{name}_decode" in text


# -- the layout ----------------------------------------------------------------

def _layout(local, experts, tile):
    local = jnp.asarray(local, jnp.int32)
    sizes = jnp.bincount(local.reshape(-1), length=experts + 1)[
        :experts].astype(jnp.int32)
    return [np.asarray(v) for v in group_rows(local, sizes, tile)], \
        np.asarray(sizes)


def test_the_layout_is_the_stable_order_on_whole_tiles():
    rng = np.random.RandomState(0)
    experts, tile, k = 5, 4, 2
    local = rng.randint(0, experts + 1, size=(23, k))
    local[local == 3] = 1                       # expert 3 gets no row
    (row, source, tile_expert, tiles), sizes = _layout(local, experts, tile)
    rows = num_tiles(23 * k, experts, tile) * tile
    assert source.shape == (rows,) and tile_expert.shape == (rows // tile,)
    assert tiles == sum(-(-s // tile) for s in sizes) and sizes[3] == 0
    held = local < experts
    assert (row[~held] == rows).all()
    assert len(set(row[held])) == held.sum()    # no two assignments share one
    for e in range(experts):
        mine = row[local == e]                  # in (token, choice) order
        assert (np.diff(mine) == 1).all()       # contiguous, stable
        if len(mine):
            assert mine[0] % tile == 0          # an expert starts a tile
            assert (tile_expert[mine // tile] == e).all()
    tokens = np.broadcast_to(np.arange(23)[:, None], (23, k))
    assert (source[row[held]] == tokens[held]).all()
    assert ((0 <= tile_expert) & (tile_expert < experts)).all()


def test_a_layout_with_nothing_held_runs_one_tile_and_adds_nothing(
        monkeypatch):
    experts, tile = 4, 8
    _boundary(monkeypatch, 1, tile)
    local = np.full((6, 2), experts)
    (row, source, tile_expert, tiles), _ = _layout(local, experts, tile)
    assert tiles == 0 and (row == len(source)).all()
    assert not source.any() and (tile_expert >= 0).all()
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    xs = jax.random.normal(keys[0], (6, 16))
    w_up = jax.random.normal(keys[1], (experts, 16, 24))
    w_down = jax.random.normal(keys[2], (experts, 24, 16))
    y, tile_rows = moe.routed_grouped(
        xs, jnp.asarray(local), jnp.zeros((experts,), jnp.int32),
        jnp.ones((6, 2)), None, w_up, w_down)
    assert not np.asarray(y).any() and int(tile_rows) == 0


# two chunks of 256; three lane tiles of 128, which is not whole chunks (as
# nemotron3_nano_30b's held 1,920 is fifteen)
@pytest.mark.parametrize("f", [512, 384])
@pytest.mark.parametrize("gated", [False, True], ids=["relu2", "silu"])
def test_the_kernel_takes_each_tile_through_its_expert(gated, f):
    experts, h, tile = 3, 32, 8
    assert kernel_module.width_chunk(f) == (256 if f == 512 else 128)
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    tile_expert = jnp.asarray([2, 0, 0, 1, 1, 1], jnp.int32)
    x = jax.random.normal(keys[0], (6 * tile, h))
    w_gate = jax.random.normal(keys[1], (experts, h, f)) * 0.2 \
        if gated else None
    w_up = jax.random.normal(keys[2], (experts, h, f)) * 0.2
    w_down = jax.random.normal(keys[3], (experts, f, h)) * 0.1
    got = np.asarray(grouped_experts(
        x, tile_expert, jnp.int32(4), w_gate, w_up, w_down, tile_rows=tile,
        interpret=True))
    for i, e in enumerate([2, 0, 0, 1]):
        rows = x[i * tile:(i + 1) * tile]
        act = jnp.dot(rows, w_up[e], precision="highest")
        act = jax.nn.silu(jnp.dot(rows, w_gate[e], precision="highest")) \
            * act if gated else jnp.square(jax.nn.relu(act))
        np.testing.assert_allclose(
            got[i * tile:(i + 1) * tile],
            np.asarray(jnp.dot(act, w_down[e], precision="highest")),
            atol=2e-5)


def test_the_kernel_refuses_rows_that_are_not_whole_tiles():
    w = jnp.zeros((2, 32, 256))
    with pytest.raises(ValueError, match="whole tiles"):
        grouped_experts(jnp.zeros((20, 32)), jnp.zeros((3,), jnp.int32),
                        jnp.int32(2), None, w, jnp.zeros((2, 256, 32)),
                        tile_rows=8, interpret=True)


def test_the_kernel_refuses_a_width_that_is_not_whole_chunks():
    assert kernel_module.takes_width(1024) and kernel_module.takes_width(768)
    # as published it is refused still; the layer holds it at a width that
    # is not: whole lane tiles, taken half a chunk at a time
    assert not kernel_module.takes_width(1856)
    assert kernel_module.takes_width(kernel_module.held_width(1856))
    assert kernel_module.width_chunk(1920) == 128
    assert [kernel_module.width_chunk(f) for f in (768, 1024, 2048)] \
        == [256] * 3
    with pytest.raises(ValueError, match="whole chunks"):
        grouped_experts(jnp.zeros((16, 32)), jnp.zeros((2,), jnp.int32),
                        jnp.int32(2), None, jnp.zeros((2, 32, 320)),
                        jnp.zeros((2, 320, 32)), tile_rows=8, interpret=True)


# -- the held width -----------------------------------------------------------

# published width -> the width the layer holds: nemotron3_nano_30b's alone of
# the served ones is padded (to whole lane tiles of 128); widths the kernel
# takes and the narrow test presets (a pad of more than an eighth of the
# width) are held as they are
HELD_WIDTHS = {1856: 1920, 464: 512, 1024: 1024, 768: 768, 2048: 2048,
               512: 512, 24: 24, 32: 32, 48: 48, 96: 96, 1792: 1792,
               225: 225, 228: 256, 350: 384, 340: 340}


@pytest.mark.parametrize("width", sorted(HELD_WIDTHS))
def test_the_held_width_is_read_from_the_published_width_alone(width):
    held = kernel_module.held_width(width)
    assert held == HELD_WIDTHS[width]
    assert held == width or (kernel_module.takes_width(held)
                             and 8 * (held - width) <= width)


def _padded_layer(activation):
    """A layer published 464 wide (1,856 / 4), which the rule holds at 512,
    with its seeded parameters, and the same parameters cut to 464."""
    cfg = _cfg(activation, moe_intermediate_size=464)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 11, cfg.hidden_size))
    params = dict(HeldExpertsMLP(cfg).init(jax.random.PRNGKey(5), x)["params"])
    inner = ["w_up"] + ["w_gate"] * (activation == "silu")
    cut = {**params, "w_down": params["w_down"][:, :464],
           **{name: params[name][:, :, :464] for name in inner}}
    return cfg, x, params, cut, inner


@pytest.mark.parametrize("activation", ["relu2", "silu"])
def test_a_padded_layer_is_the_sum_over_the_published_width(activation,
                                                            monkeypatch):
    """Masked and grouped (the kernel at its own chunk of 256 columns, two
    of them) over the held 512 columns equal the loop over the 464 published
    ones of the same weights, and each other."""
    cfg, x, params, cut, inner = _padded_layer(activation)
    assert [params[n].shape[-1] for n in inner] == [512] * len(inner)
    assert params["w_down"].shape[1] == 512
    assert kernel_module.width_chunk(512) == 256
    want, sizes, _ = _loop(cfg, cut, x, None)

    def apply(min_tokens):
        monkeypatch.setattr(moe, "GROUPED_MIN_TOKENS", min_tokens)
        monkeypatch.setattr(moe, "GROUPED_TILE_ROWS", TILE)
        y, counters = HeldExpertsMLP(cfg).apply({"params": params}, x)
        return np.asarray(y), [int(c) for c in counters]

    masked, counted_m = apply(1 << 30)
    grouped, counted_g = apply(1)
    np.testing.assert_allclose(masked, want, atol=3e-5)
    np.testing.assert_allclose(grouped, want, atol=3e-5)
    np.testing.assert_allclose(grouped, masked, atol=3e-5)
    assert counted_m[4:] == [0, 0]
    assert counted_g[4] == int(sizes.sum()) > 0


@pytest.mark.parametrize("activation", ["relu2", "silu"])
def test_the_seeded_pads_are_zero_round_the_published_draw(activation,
                                                           monkeypatch):
    """The unpadded part of every seeded weight is, to the bit, what the
    layer drew before it held a pad (the base initialisers at the published
    shape: the down projection centred over the published rows alone), the
    rest is zero, and a gradient through the mask leaves it zero."""
    cfg, x, params, cut, inner = _padded_layer(activation)
    for name in inner:
        assert not np.asarray(params[name][:, :, 464:]).any()
    assert not np.asarray(params["w_down"][:, 464:]).any()
    with monkeypatch.context() as m:
        m.setattr(kernel_module, "held_width", lambda width: width)
        unpadded = HeldExpertsMLP(cfg).init(jax.random.PRNGKey(5), x)["params"]
    assert unpadded["w_up"].shape == (EXPERTS, cfg.hidden_size, 464)
    for name in inner + ["w_down", "router", "e_score_correction_bias"]:
        assert np.array_equal(np.asarray(cut[name]),
                              np.asarray(unpadded[name])), name
    np.testing.assert_allclose(
        np.asarray(params["w_down"]).sum(axis=1), 0, atol=1e-5)

    def loss(p):
        y = HeldExpertsMLP(cfg).apply({"params": p}, x)[0]
        return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape)))

    grads = jax.grad(loss)(params)
    for name in inner:
        assert np.asarray(grads[name][:, :, :464]).any()
        assert not np.asarray(grads[name][:, :, 464:]).any()
    assert np.asarray(grads["w_down"][:, :464]).any()
    assert not np.asarray(grads["w_down"][:, 464:]).any()
