"""The latent-attention decode kernel compiles for the v5e at the geometry of
``serve.kanana2_30b.doc_turns``: 32 rows, 32 heads, rows of 576 values laid in
640 of which 512 are the values, 16,384 blocks of 16, 544 blocks a row. Nothing
runs: the TPU compiler installed here compiles for a chip that is described,
not attached. The topology is described inside a module-scoped fixture and
never at import."""

import math

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


# (rows, blocks in the pool, blocks a row): the cell's engine, a short table
# whose one tile is narrower than the rule's 384 keys, and one whose whole
# schedule (two rows of one tile each at most) is shorter than the ring
GEOMETRIES = {"doc_turns": (32, 16384, 544), "short_rows": (8, 512, 8),
              "under_the_ring": (2, 64, 16)}
# What a kernel may take of VMEM on this chip without asking for more (the
# compiler's default scoped limit on a v5e).
SCOPED_VMEM_BYTES = 16 * 1024 * 1024


@pytest.mark.parametrize("name", sorted(GEOMETRIES))
def test_latent_decode_kernel_compiles_for_the_v5e(one_chip, name):
    import jax
    import jax.numpy as jnp

    from dlti_tpu.ops.kv_cache import init_latent_cache
    from dlti_tpu.ops.pallas.latent_attention import (
        latent_decode_attention, ring_shape,
    )

    rows, blocks, max_blocks = GEOMETRIES[name]
    heads, latent_dim, value_dim, block = 32, 576, 512, 16
    width = jax.eval_shape(
        lambda: init_latent_cache(4, block, latent_dim))["latent"].shape[-1]
    assert width == 640

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(q, pool, tables, lens):
        return latent_decode_attention(q, pool, tables, lens,
                                       value_dim=value_dim, scale=192 ** -0.5)

    shapes = (shape((rows, heads, latent_dim), jnp.bfloat16),
              shape((blocks, block, width), jnp.bfloat16),
              shape((rows, max_blocks), jnp.int32),
              shape((rows,), jnp.int32))
    text = jax.jit(decode).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    assert "dlti_latent_attention_decode" in text
    # The scratch the call asks for, read off the call itself: the ring's
    # slots and the softmax state, each laid in whole (8, 128) words. (The
    # compiler would have refused a kernel over the chip's scoped VMEM; this
    # says the ring alone leaves the body's float32 tiles their room.)
    tile, depth = ring_shape(block, max_blocks, width * 2)
    assert name != "under_the_ring" or rows * -(-max_blocks // tile) < depth
    call, = _pallas_calls(jax.make_jaxpr(decode)(*shapes).jaxpr)
    scratch = [v.aval for v in call.params["jaxpr"].invars[
        -call.params["grid_mapping"].num_scratch_operands:]]
    slots = [a for a in scratch if str(a.memory_space) == "vmem"]
    assert slots[0].shape == (depth, tile * block, width)
    asked = sum(math.prod(a.shape[:-2]) * -(-a.shape[-2] // 8) * 8
                * -(-a.shape[-1] // 128) * 128 * a.dtype.itemsize
                for a in slots)
    assert asked <= SCOPED_VMEM_BYTES // 2


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _pallas_calls(inner)


# (rows, tokens) of the prefill programs the latent cells warm that take the
# flash forward kernel for their own tokens: queries and keys 192 wide (the
# array's whole last dimension), values 128, 32 heads, bf16 operands
PREFILL_SHAPES = {"one_long_row": (1, 2048), "four_rows": (4, 512),
                  "smallest": (1, 256)}


@pytest.mark.parametrize("name", sorted(PREFILL_SHAPES))
def test_flash_forward_compiles_for_the_v5e_at_the_latent_widths(one_chip,
                                                                 name):
    import jax
    import jax.numpy as jnp

    from dlti_tpu.models import latent
    from dlti_tpu.ops.pallas.flash_attention import flash_attention_fwd

    rows, tokens = PREFILL_SHAPES[name]

    def shape(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def own_tokens(q, k, v, real):
        return flash_attention_fwd(
            q, k, v, scale=192 ** -0.5, segment_ids=real,
            block_q=latent.KERNEL_BLOCK, block_kv=latent.KERNEL_BLOCK)

    compiled = jax.jit(own_tokens).lower(
        shape((rows, tokens, 32, 192), jnp.bfloat16),
        shape((rows, tokens, 32, 192), jnp.bfloat16),
        shape((rows, tokens, 32, 128), jnp.bfloat16),
        shape((rows, tokens), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "dlti_flash_attention_fwd" in text
