"""Pallas paged decode attention vs the XLA gather reference path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlti_tpu.ops.attention import reference_attention
from dlti_tpu.ops.kv_cache import init_paged_cache, paged_gather
from dlti_tpu.ops.pallas import paged_attention as kernel_module
from dlti_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    tile_blocks,
    tile_tokens,
)


def _random_paged_setup(rng_seed, batch, num_heads, kv_heads, head_dim,
                        block_size, num_blocks, max_blocks, seq_lens):
    """Build a pool + disjoint random block tables with live data."""
    rng = np.random.default_rng(rng_seed)
    k_pool = rng.standard_normal(
        (num_blocks, block_size, kv_heads, head_dim)).astype(np.float32)
    v_pool = rng.standard_normal(
        (num_blocks, block_size, kv_heads, head_dim)).astype(np.float32)
    # Disjoint physical blocks per sequence (as the allocator guarantees).
    perm = rng.permutation(num_blocks)
    tables = np.full((batch, max_blocks), -1, np.int32)
    next_free = 0
    for b in range(batch):
        need = -(-seq_lens[b] // block_size)
        tables[b, :need] = perm[next_free:next_free + need]
        next_free += need
    return jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(tables)


def _reference_decode(q, k_pool, v_pool, tables, seq_lens, window=None):
    """The engine's XLA path: gather the logical window, masked attention."""
    cache = {"k": k_pool, "v": v_pool}
    ck, cv = paged_gather(cache, jnp.maximum(tables, 0))
    # Query sits at position seq_len-1; positions >= seq_len are stale.
    q_pos = (seq_lens - 1)[:, None]
    return reference_attention(q, ck, cv, causal=True, q_positions=q_pos,
                               window=window)


@pytest.mark.parametrize("num_heads,kv_heads", [(8, 8), (8, 2), (4, 1)])
def test_matches_gather_reference(num_heads, kv_heads):
    batch, head_dim, block_size = 3, 64, 16
    seq_lens = np.array([5, 37, 16], np.int32)  # partial / multi / exact block
    max_blocks = 4
    k_pool, v_pool, tables = _random_paged_setup(
        0, batch, num_heads, kv_heads, head_dim, block_size,
        num_blocks=16, max_blocks=max_blocks, seq_lens=seq_lens)
    q = jnp.asarray(np.random.default_rng(1).standard_normal(
        (batch, 1, num_heads, head_dim)).astype(np.float32))

    got = paged_decode_attention(q, k_pool, v_pool, tables,
                                 jnp.asarray(seq_lens), interpret=True)
    want = _reference_decode(q, k_pool, v_pool, tables, jnp.asarray(seq_lens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_stale_pool_rows_never_leak():
    """Poison every block not in a sequence's table with huge values."""
    batch, num_heads, kv_heads, head_dim, block_size = 2, 4, 2, 32, 8
    seq_lens = np.array([3, 9], np.int32)
    k_pool, v_pool, tables = _random_paged_setup(
        2, batch, num_heads, kv_heads, head_dim, block_size,
        num_blocks=8, max_blocks=2, seq_lens=seq_lens)
    used = set(np.asarray(tables)[np.asarray(tables) >= 0].tolist())
    poison = np.asarray(k_pool).copy()
    vpoison = np.asarray(v_pool).copy()
    for blk in range(8):
        if blk not in used:
            poison[blk] = 1e9
            vpoison[blk] = 1e9
    # Also poison the *tail* of the last live block beyond seq_len.
    for b in range(batch):
        last_logical = (seq_lens[b] - 1) // block_size
        phys = int(np.asarray(tables)[b, last_logical])
        vpoison[phys, seq_lens[b] % block_size or block_size:] = 1e9

    q = jnp.asarray(np.random.default_rng(3).standard_normal(
        (batch, 1, num_heads, head_dim)).astype(np.float32))
    got = paged_decode_attention(q, jnp.asarray(poison), jnp.asarray(vpoison),
                                 tables, jnp.asarray(seq_lens), interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    assert np.abs(np.asarray(got)).max() < 1e4


def test_bf16_pool_fp32_accumulation():
    batch, num_heads, kv_heads, head_dim, block_size = 2, 4, 4, 64, 16
    seq_lens = np.array([30, 17], np.int32)
    k_pool, v_pool, tables = _random_paged_setup(
        4, batch, num_heads, kv_heads, head_dim, block_size,
        num_blocks=8, max_blocks=2, seq_lens=seq_lens)
    q = jnp.asarray(np.random.default_rng(5).standard_normal(
        (batch, 1, num_heads, head_dim)))
    got = paged_decode_attention(
        q.astype(jnp.bfloat16), k_pool.astype(jnp.bfloat16),
        v_pool.astype(jnp.bfloat16), tables, jnp.asarray(seq_lens),
        interpret=True)
    want = _reference_decode(q.astype(jnp.float32), k_pool, v_pool, tables,
                             jnp.asarray(seq_lens))
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)


def test_jit_and_grid_edge():
    """Jits cleanly; seq_len filling every block exactly works."""
    batch, num_heads, kv_heads, head_dim, block_size = 1, 2, 2, 32, 8
    seq_lens = np.array([16], np.int32)  # == max_blocks * block_size
    k_pool, v_pool, tables = _random_paged_setup(
        6, batch, num_heads, kv_heads, head_dim, block_size,
        num_blocks=4, max_blocks=2, seq_lens=seq_lens)
    q = jnp.asarray(np.random.default_rng(7).standard_normal(
        (batch, 1, num_heads, head_dim)).astype(np.float32))
    fn = jax.jit(lambda *a: paged_decode_attention(*a, interpret=True))
    got = fn(q, k_pool, v_pool, tables, jnp.asarray(seq_lens))
    want = _reference_decode(q, k_pool, v_pool, tables, jnp.asarray(seq_lens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------------------------
# A grid step covers a tile of T blocks
# ----------------------------------------------------------------------

@pytest.fixture
def tiles_of_64(monkeypatch):
    """Small tiles for small cases. The kernel is jitted on shapes, not on
    the module's constant: drop its traces on the way in and out."""
    monkeypatch.setattr(kernel_module, "TILE_KEYS", 64)
    paged_decode_attention.clear_cache()
    yield
    paged_decode_attention.clear_cache()


def _queries(seed, batch, num_heads, head_dim):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (batch, 1, num_heads, head_dim)).astype(np.float32))


def _check_against_gather(seq_lens, *, num_heads=4, kv_heads=2, head_dim=32,
                          block_size=8, max_blocks=None, window=None,
                          seed=11):
    """Rows of ``seq_lens`` through the kernel and the gather path."""
    seq_lens = np.asarray(seq_lens, np.int32)
    batch = len(seq_lens)
    if max_blocks is None:
        max_blocks = -(-int(seq_lens.max()) // block_size)
    num_blocks = int(sum(-(-int(n) // block_size) for n in seq_lens)) + 2
    k_pool, v_pool, tables = _random_paged_setup(
        seed, batch, num_heads, kv_heads, head_dim, block_size, num_blocks,
        max_blocks, seq_lens)
    q = _queries(seed + 1, batch, num_heads, head_dim)
    got = paged_decode_attention(q, k_pool, v_pool, tables,
                                 jnp.asarray(seq_lens), window=window,
                                 interpret=True)
    want = _reference_decode(q, k_pool, v_pool, tables,
                             jnp.asarray(seq_lens), window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("num_heads,kv_heads", [(32, 8), (28, 4), (32, 2)])
def test_the_cells_head_geometries(num_heads, kv_heads):
    """mistral_7b, qwen2_7b (group 7) and nemotron3_nano_30b (group 16) at
    head_dim 128 and the cells' block of 16: one tile is 256 keys."""
    assert tile_tokens(16, 40) == 256
    _check_against_gather([1, 255, 300, 513], num_heads=num_heads,
                          kv_heads=kv_heads, head_dim=128, block_size=16,
                          max_blocks=40)


@pytest.mark.parametrize("where", ["tile-1", "tile", "tile+1",
                                   "in_a_tiles_first_block"])
def test_context_ends_round_a_tile_border(where):
    block_size, max_blocks = 8, 96
    tile = tile_tokens(block_size, max_blocks)
    assert tile == 256 and tile_blocks(block_size, max_blocks) == 32
    n = {"tile-1": tile - 1, "tile": tile, "tile+1": tile + 1,
         "in_a_tiles_first_block": 2 * tile + 3}[where]
    _check_against_gather([n, 2, n], block_size=block_size,
                          max_blocks=max_blocks)


@pytest.mark.parametrize("seq_len,window", [(100, 40), (300, 40), (64, 64),
                                            (200, 130)])
def test_sliding_window_against_tiles(tiles_of_64, seq_len, window):
    """Tiles of 64 keys. (100, 40): the band [60, 100) starts inside tile 0
    and ends inside tile 1. (300, 40): tiles 0-3 lie wholly before the band
    and are skipped. (64, 64): band and tile coincide. (200, 130): one
    skipped tile, then the band starts inside the next."""
    assert tile_tokens(16, 24) == 64
    _check_against_gather([seq_len, 7, seq_len - 1], block_size=16,
                          max_blocks=24, window=window)


@pytest.mark.parametrize("max_blocks", [5, 33, 47])
def test_table_width_not_a_multiple_of_the_tile(max_blocks):
    """5 blocks: the table is narrower than a tile would be, so the tile is
    the table. 33 and 47: the last tile hangs over the table's end."""
    block_size = 8
    T = tile_blocks(block_size, max_blocks)
    assert T == min(32, max_blocks) and (max_blocks % T != 0 or T == max_blocks)
    full = max_blocks * block_size
    _check_against_gather([full, full - 9, 3], block_size=block_size,
                          max_blocks=max_blocks)


@pytest.mark.parametrize("dead", [0, -1, "poisoned"])
def test_dead_table_entries_are_never_read(dead):
    """Past a row's context the table may hold anything: zeros (the
    engine's), -1, or the id of a block that holds NaN. One row ends inside
    a tile, one exactly on a tile's border, one is shorter than a block."""
    batch, num_heads, kv_heads, head_dim, block_size = 3, 4, 2, 32, 8
    max_blocks, num_blocks = 70, 80
    seq_lens = np.array([300, 256, 5], np.int32)
    k_pool, v_pool, tables = _random_paged_setup(
        21, batch, num_heads, kv_heads, head_dim, block_size, num_blocks,
        max_blocks, seq_lens)
    tables = np.asarray(tables).copy()
    live = tables >= 0
    used = set(tables[live].tolist())
    k_pool, v_pool = np.asarray(k_pool).copy(), np.asarray(v_pool).copy()
    if dead == "poisoned":
        dead = next(b for b in range(1, num_blocks) if b not in used)
    if dead not in used and dead >= 0:
        k_pool[dead] = np.nan
        v_pool[dead] = np.nan
    want_tables = np.where(live, tables, 0)
    tables = np.where(live, tables, dead)
    q = _queries(22, batch, num_heads, head_dim)
    got = paged_decode_attention(q, jnp.asarray(k_pool), jnp.asarray(v_pool),
                                 jnp.asarray(tables), jnp.asarray(seq_lens),
                                 interpret=True)
    clean_k = np.nan_to_num(k_pool)
    clean_v = np.nan_to_num(v_pool)
    want = _reference_decode(q, jnp.asarray(clean_k), jnp.asarray(clean_v),
                             jnp.asarray(want_tables), jnp.asarray(seq_lens))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 24])
def test_a_row_of_length_zero_reads_zero(window):
    """An empty slot (seq_len 0) gives a zero row and leaves its neighbours
    alone, wherever it sits in the batch."""
    batch, num_heads, kv_heads, head_dim, block_size = 3, 4, 2, 32, 8
    seq_lens = np.array([0, 70, 0], np.int32)
    k_pool, v_pool, tables = _random_paged_setup(
        31, batch, num_heads, kv_heads, head_dim, block_size, 16, 40, seq_lens)
    q = _queries(32, batch, num_heads, head_dim)
    got = np.asarray(paged_decode_attention(
        q, k_pool, v_pool, tables, jnp.asarray(seq_lens), window=window,
        interpret=True))
    want = np.asarray(_reference_decode(
        q, k_pool, v_pool, tables, jnp.asarray(seq_lens), window=window))
    assert (got[0] == 0).all() and (got[2] == 0).all()
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block_size,max_blocks,token_bytes,want", [
    (16, 256, 0, 256),           # the cells' tables: 16 blocks a tile
    (16, 256, 8 * 128 * 2, 256),  # mistral_7b's keys, bf16: inside the budget
    (16, 4, 0, 64),              # a table narrower than a tile
    (8, 96, 0, 256),             # 32 blocks of 8
    (128, 32, 0, 256),           # 2 blocks of 128
    (512, 8, 0, 512),            # a block larger than a tile: one block
    (16, 256, 16 * 1024, 64),    # keys too wide for the budget: halved twice
])
def test_tile_tokens_against_a_hand_count(block_size, max_blocks, token_bytes,
                                          want):
    assert tile_tokens(block_size, max_blocks, token_bytes) == want
    assert want == block_size * tile_blocks(block_size, max_blocks,
                                            token_bytes)


# ----------------------------------------------------------------------
# int8 KV pools
# ----------------------------------------------------------------------

def test_int8_pool_update_gather_roundtrip():
    """paged_update quantizes per (token, kv_head); paged_gather returns
    the dequantized window within the symmetric-int8 error bound."""
    from dlti_tpu.ops.kv_cache import paged_update, slot_mapping

    nb, bs, kvh, hd = 8, 4, 2, 16
    cache = init_paged_cache(1, nb, bs, kvh, hd, "int8")[0]
    assert cache["k"].dtype == jnp.int8
    assert cache["k_scale"].shape == (nb, bs, kvh)
    rng = np.random.default_rng(0)
    k = rng.standard_normal((1, 6, kvh, hd)).astype(np.float32) * 3.0
    bt = jnp.array([[2, 5]], jnp.int32)
    pos = jnp.arange(6, dtype=jnp.int32)[None, :]
    slots = slot_mapping(bt, pos, bs, nb)
    cache = paged_update(cache, jnp.asarray(k), jnp.asarray(k), slots)
    gk, gv = paged_gather(cache, bt)
    got = np.asarray(gk[0, :6], np.float32)
    bound = np.abs(k[0]).max(axis=-1, keepdims=True) / 127 + 1e-6
    assert np.all(np.abs(got - k[0]) <= bound + np.abs(k[0]) * 0.01)
    np.testing.assert_allclose(np.asarray(gv[0, :6], np.float32), got)


def test_int8_pool_kernel_matches_dequant_reference():
    """The Pallas kernel's in-place scale folding == gather+dequant+attend."""
    batch, num_heads, kv_heads, head_dim = 3, 4, 2, 32
    block_size, num_blocks, max_blocks = 8, 16, 4
    seq_lens = np.array([5, 17, 32], np.int32)
    kf, vf, tables = _random_paged_setup(
        7, batch, num_heads, kv_heads, head_dim, block_size, num_blocks,
        max_blocks, seq_lens)
    # Quantize the pools the way paged_update stores them.
    from dlti_tpu.ops.kv_cache import _quantize_rows

    kq, ks = _quantize_rows(kf)
    vq, vs = _quantize_rows(vf)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal(
        (batch, 1, num_heads, head_dim)).astype(np.float32))

    got = paged_decode_attention(
        q, kq, vq, tables, jnp.asarray(seq_lens),
        k_scale=ks, v_scale=vs, interpret=True)
    # Reference: dequantized pools through the gather path.
    kd = (kq.astype(jnp.float32) * ks[..., None])
    vd = (vq.astype(jnp.float32) * vs[..., None])
    want = _reference_decode(q, kd, vd, tables, jnp.asarray(seq_lens))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("window", [None, 50])
def test_int8_pool_scales_across_a_tile_border(tiles_of_64, window):
    """Tiles of 64 keys (8 blocks of 8): contexts that end just before, on
    and after a border, so a tile's scales come from several blocks and the
    last tile's from live and dead ones."""
    from dlti_tpu.ops.kv_cache import _quantize_rows

    batch, num_heads, kv_heads, head_dim = 4, 8, 2, 32
    block_size, num_blocks, max_blocks = 8, 64, 20
    assert tile_tokens(block_size, max_blocks) == 64
    seq_lens = np.array([63, 64, 65, 131], np.int32)
    kf, vf, tables = _random_paged_setup(
        41, batch, num_heads, kv_heads, head_dim, block_size, num_blocks,
        max_blocks, seq_lens)
    # Rows of very different size, so that a wrong row's scale shows.
    kf = kf * jnp.asarray(np.random.default_rng(42).uniform(
        0.1, 8.0, (num_blocks, block_size, kv_heads, 1)).astype(np.float32))
    kq, ks = _quantize_rows(kf)
    vq, vs = _quantize_rows(vf)
    q = _queries(43, batch, num_heads, head_dim)
    got = paged_decode_attention(
        q, kq, vq, tables, jnp.asarray(seq_lens), k_scale=ks, v_scale=vs,
        window=window, interpret=True)
    kd = kq.astype(jnp.float32) * ks[..., None]
    vd = vq.astype(jnp.float32) * vs[..., None]
    want = _reference_decode(q, kd, vd, tables, jnp.asarray(seq_lens),
                             window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-4)


@pytest.mark.slow
def test_int8_kv_engine_close_to_bf16(tmp_path):
    """End-to-end: an int8-KV engine's greedy outputs track the bf16-KV
    engine on a tiny model (same contract as the int8-weights test)."""
    from dlti_tpu.config import MODEL_PRESETS
    from dlti_tpu.models import LlamaForCausalLM
    from dlti_tpu.serving import EngineConfig, InferenceEngine, SamplingParams

    cfg = MODEL_PRESETS["llama_tiny"]
    model = LlamaForCausalLM(cfg, None)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]

    def mk(cache_dtype):
        ec = EngineConfig(max_seqs=2, block_size=8, num_blocks=32,
                          max_model_len=48, eos_token_id=-1,
                          cache_dtype=cache_dtype)
        return InferenceEngine(cfg, params, ec)

    prompts = [[5, 9, 3, 7, 1], [11, 2, 6]]
    sp = SamplingParams(temperature=0.0, max_tokens=12)
    want = mk("bfloat16").generate(prompts, sp)
    got = mk("int8").generate(prompts, sp)
    for g, w in zip(got, want):
        assert len(g.output_token_ids) == len(w.output_token_ids)
        # A random tiny model's greedy argmax sits on near-ties, so
        # trajectories may fork under quantization noise and never
        # re-converge; the numerics contract lives in the kernel/roundtrip
        # tests above. Here: the first (prefill-driven) token agrees, and
        # logprobs stay close over the common prefix.
        assert g.output_token_ids[0] == w.output_token_ids[0]
        for a, b, la, lb in zip(g.output_token_ids, w.output_token_ids,
                                g.output_logprobs, w.output_logprobs):
            if a != b:
                break
            np.testing.assert_allclose(la, lb, atol=0.35)
