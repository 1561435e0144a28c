"""The paged decode kernel compiles for the v5e at the geometries that
``tests/benchmark/test_bench_kernels_v5e.py`` (the benchmark's file) does not
hold: nemotron3_nano_30b's two kv heads with sixteen query heads each, and an
int8 pool with its scale tiles; and the grouped expert kernel at the three
configurations' held widths, as ``HeldExpertsMLP`` calls it for a
2,048-token prefill. Nothing runs: the TPU compiler installed here
compiles for a chip that is described, not attached. The topology is
described inside a module-scoped fixture and never at import."""

import re

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but not read back without the chip: keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (query heads, kv heads, window, pool dtype); every engine of the serving
# cells has 32 rows, 4,096 blocks of 16 and 256 blocks a row
GEOMETRIES = {
    "nemotron3_nano_30b": (32, 2, None, "bfloat16"),
    "int8_pool_32q_8kv": (32, 8, None, "int8"),
    "int8_pool_windowed": (32, 8, 4096, "int8"),
    # phi4_mini_flash: 40 zero-padded query heads over 10 paired key rows
    # of 128. Ten kv heads in a 4-D pool lie padded to 16 and the compiler
    # refuses the block copy ("must be aligned to tiling (8), but is 10":
    # builder, PR 53), so its pools are FUSED, a row of 1,280 values a token
    "fused_40q_10kv": (40, 10, None, "bfloat16"),
    "fused_40q_10kv_windowed": (40, 10, 512, "bfloat16"),
    # jamba2_3b's two attention layers: twenty query heads over ONE
    # key-value head (served at test size alone: no cell)
    "fused_jamba2_3b_20q_1kv": (20, 1, None, "bfloat16"),
}


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_paged_decode_kernel_compiles_for_the_v5e(one_chip, name):
    import jax
    import jax.numpy as jnp

    from dlti_tpu.ops.pallas.paged_attention import paged_decode_attention

    heads, kv_heads, window, pool_dtype = GEOMETRIES[name]
    batch, block, head_dim, blocks, max_blocks = 32, 16, 128, 4096, 256

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q = shape((batch, 1, heads, head_dim), jnp.bfloat16)
    pool = shape((blocks, block, kv_heads * head_dim)
                 if name.startswith("fused") else
                 (blocks, block, kv_heads, head_dim), jnp.dtype(pool_dtype))
    args = [q, pool, pool, shape((batch, max_blocks), jnp.int32),
            shape((batch,), jnp.int32)]
    if pool_dtype == "int8":
        scales = shape((blocks, block, kv_heads), jnp.float32)
        args += [scales, scales]

    def decode(q, k, v, tables, lens, k_scale=None, v_scale=None):
        return paged_decode_attention(q, k, v, tables, lens, k_scale=k_scale,
                                      v_scale=v_scale, window=window)

    compiled = jax.jit(decode).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (hidden, expert width, gated, top-k): 64 experts held, a 2,048-token call
EXPERT_GEOMETRIES = {
    "xing4_29b": (3584, 1024, True, 4),
    "nemotron3_nano_30b": (2688, 1856, False, 6),  # held at 15 lane tiles
    "kanana2_30b": (2048, 768, True, 6),
}


@pytest.mark.parametrize("name", sorted(EXPERT_GEOMETRIES))
def test_grouped_expert_kernel_compiles_for_the_v5e(one_chip, name):
    import jax
    import jax.numpy as jnp

    from dlti_tpu.models import moe
    from dlti_tpu.ops.pallas.grouped_experts import (
        grouped_experts, held_width, num_tiles,
    )

    h, published, gated, k = EXPERT_GEOMETRIES[name]
    f = held_width(published)
    assert f == (1920 if name == "nemotron3_nano_30b" else published)
    held, tokens, tile = 64, 2048, moe.GROUPED_TILE_ROWS
    tiles = num_tiles(tokens * k, held, tile)

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    inner = shape((held, h, f), jnp.bfloat16)

    def run(x, tile_expert, n, w_gate, w_up, w_down):
        return grouped_experts(x, tile_expert, n, w_gate if gated else None,
                               w_up, w_down, tile_rows=tile)

    args = (shape((tiles * tile, h), jnp.bfloat16), shape((tiles,), jnp.int32),
            shape((), jnp.int32), inner, inner,
            shape((held, f, h), jnp.bfloat16))
    assert moe.takes_grouped(tokens, f)
    text = jax.jit(run).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # The kernel is handed the weights as they are held: no instruction
    # but the parameters has a weight's shape (a width that is not whole
    # lane tiles had XLA copy ``w_up`` whole in front of the kernel).
    made = re.findall(r"= bf16\[%d,(?:%d,%d|%d,%d)\]\S* ([\w-]+)\("
                      % (held, h, f, f, h), text)
    assert made and set(made) == {"parameter"}, made


@pytest.mark.parametrize("rows", [1, 2])
def test_selective_scan_kernels_compile_for_the_v5e(one_chip, rows):
    """The Mamba-1 training scan at jamba2_3b's sizes (rows of 8,192 tokens,
    5,120 channels of 16 states): the forward kernel and the backward one
    with its 128 kept states a chunk in VMEM."""
    import jax
    import jax.numpy as jnp

    from dlti_tpu.ops.pallas import selective_scan as scan

    length, d, n = 8192, 5120, 16
    assert d % scan.CHANNELS == 0 and d % scan.BWD_CHANNELS == 0

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    tokens, states = shape(rows, length, d), shape(rows, length, n)
    inputs = (tokens, tokens, shape(n, d), states, states,
              shape(rows, length))
    fwd = scan.selective_scan_fwd.lower(*inputs).compile().as_text()
    assert "tpu_custom_call" in fwd and "dlti_selective_scan_fwd" in fwd
    bwd = scan.selective_scan_bwd.lower(
        *inputs, shape(rows, length // scan.CHUNK, n, d),
        tokens).compile().as_text()
    assert "tpu_custom_call" in bwd and "dlti_selective_scan_bwd" in bwd
