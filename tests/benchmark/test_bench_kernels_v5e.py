"""The main path's two Pallas kernels compile for the v5e at both
configurations' real widths. Nothing runs: the TPU compiler that is
installed here compiles for a chip that is described, not attached
(on-chip-measurement guide, section 2). The topology is described inside a
module-scoped fixture and never at import, and all such tests live in this
one file: the process that describes it holds the TPU library's lock."""

import pytest

import bench_paths  # noqa: F401


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


def _shape(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (query heads, kv heads, window, pool blocks): the serving cells' engines
PAGED = {"mistral_7b": (32, 8, 4096, 4096), "qwen2_7b": (28, 4, None, 4096)}


@pytest.mark.parametrize("config", sorted(PAGED))
def test_paged_decode_kernel_compiles_at_the_cells_shapes(one_chip, config):
    import jax
    import jax.numpy as jnp

    from dlti_tpu.ops.pallas.paged_attention import paged_decode_attention

    heads, kv_heads, window, blocks = PAGED[config]
    batch, block, head_dim, max_len = 32, 16, 128, 4096
    q = _shape((batch, 1, heads, head_dim), jnp.bfloat16, one_chip)
    pool = _shape((blocks, block, kv_heads, head_dim), jnp.bfloat16, one_chip)
    tables = _shape((batch, max_len // block), jnp.int32, one_chip)
    lens = _shape((batch,), jnp.int32, one_chip)

    def decode(q, k, v, tables, lens):
        return paged_decode_attention(q, k, v, tables, lens, window=window)

    compiled = jax.jit(decode).lower(q, pool, pool, tables, lens).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (query heads, kv heads, window): the training cell's packed rows
FLASH = {"mistral_7b": (32, 8, 4096), "qwen2_7b": (28, 4, None)}


@pytest.mark.parametrize("config", sorted(FLASH))
def test_flash_kernel_compiles_forward_and_backward_packed(one_chip, config):
    import jax
    import jax.numpy as jnp

    from dlti_tpu.ops.pallas.flash_attention import flash_attention

    heads, kv_heads, window = FLASH[config]
    batch, seq, head_dim = 4, 2048, 128
    q = _shape((batch, seq, heads, head_dim), jnp.bfloat16, one_chip)
    kv = _shape((batch, seq, kv_heads, head_dim), jnp.bfloat16, one_chip)
    seg = _shape((batch, seq), jnp.int32, one_chip)

    def loss(q, k, v, seg):
        out = flash_attention(q, k, v, causal=True, segment_ids=seg,
                              window=window)
        return out.astype(jnp.float32).sum()

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    compiled = step.lower(q, kv, kv, seg).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3  # fwd, dq, dkv
