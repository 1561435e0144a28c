"""Routed experts over the assignments sorted by expert, in Pallas.

A call of many tokens sends each held expert a few hundred rows at most.
``models.moe.HeldExpertsMLP``'s mask runs every held expert over every
token and multiplies all but top-k of ``held_n`` results by zero; this is
the same sum with the zeros left out: the (token, choice) assignments laid
out by expert, each expert's rows padded up to whole tiles so that a tile
of rows belongs to one expert (``group_rows``, XLA), and one kernel that
takes a tile through its expert's whole MLP (``grouped_experts``):

    out[tile] = f(x[tile] W_gate,e , x[tile] W_up,e) W_down,e     e = expert[tile]

The expert of a tile comes from a scalar-prefetched table and is used in
the weights' index maps, as in ``jax.experimental.pallas.ops.tpu.megablox``;
what differs from it: the padding (no tile is shared by two experts, so
nothing is masked at a store), the three products fused (the (rows, f)
activations never leave VMEM), and a grid that is as long as the tiles
that hold rows (a traced length: a tile past the last real one is not
run). Operands stay in the weights' type with float32 accumulation; the
activation is computed in float32 and cast for the down product.

The grid is the row tiles alone and an expert's whole matrices are one
block each (22 MB of bf16 at 3,584 x 1,024 gated, twice that in VMEM of
the v5e's 128 MiB): the index maps of consecutive tiles of one expert name
the same blocks and the pipeline fetches them once, so the weights are
read once an *expert that has rows*, the next expert's while this one's
tiles are computed. With the width in 2 or 4 grid blocks every tile re-read
its expert and a 2,048-token call took 15-25 % longer (my chip run, PR 42).
Inside a grid step the width is taken ``WIDTH_CHUNK`` columns at a time in
a loop that is not unrolled: the same time to within 2 % as the width
whole (3.65 for 3.60 ms a layer at 2,048 tokens) and 0.58 MB of kernel code
for 1.41, which a prefill program holds once an expert layer and a warm
start loads from the cache (my chip runs, PR 42). A width that is whole lane
tiles of 128 columns and not whole chunks is taken half a chunk at a time
(``width_chunk``: 1,920 = 15 x 128; a width of whole chunks keeps the chunk
and the kernel text it had). Any other width is refused (``takes_width``),
and the layer does not hand it one: ``models.moe.HeldExpertsMLP`` holds its
experts at ``held_width`` of the published width, zeros from there on, which
is whole lane tiles wherever the pad is small against the width
(nemotron3_nano_30b's 1,856 at 1,920) and the published width elsewhere,
where ``models.moe.takes_grouped`` leaves the layer on the mask (why the
form that took any width whole was not kept: the note above
``models.moe.GROUPED_MIN_TOKENS``).

An expert whose matrices, two buffers each, do not fit ``VMEM_WEIGHTS``
(6,144 x 2,048 gated: 144 MB of the v5e's 128 MiB, which the chip's
compiler refuses) has its width in grid blocks after all
(``width_block``, from shapes alone): a second grid axis over blocks of
whole chunks, the tile's float32 result kept across them. Every tile then
re-reads its expert; an expert that fits keeps the one-axis grid and the
program it had.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dlti_tpu.ops.pallas.flash_attention import out_struct


# Columns of an expert's width a step of the kernel's inner loop takes; half
# of it (a lane tile) for a width that is whole lane tiles and not whole
# chunks (``width_chunk``).
WIDTH_CHUNK = 256


# What the blocks of one expert's matrices, two buffers each, may take of
# VMEM (the kernel is given 100 MiB at most, for them, the tiles of rows and
# the float32 activations).
VMEM_WEIGHTS = 80 << 20


def width_chunk(width: int) -> int:
    """The inner loop's step for experts ``width`` wide: ``WIDTH_CHUNK``
    where it divides the width (every kernel of before keeps its text), else
    half of it."""
    return WIDTH_CHUNK if width % WIDTH_CHUNK == 0 else WIDTH_CHUNK // 2


def takes_width(width: int) -> bool:
    """Whether the kernel takes experts ``width`` wide: whole half chunks
    (lane tiles of 128 columns)."""
    return width % (WIDTH_CHUNK // 2) == 0


def held_width(width: int) -> int:
    """The width at which ``models.moe.HeldExpertsMLP`` holds experts
    published ``width`` wide: the next width the kernel takes where the pad
    is at most an eighth of the width (nemotron3_nano_30b's 1,856 is held
    at 1,920: 3.4 %), else the width as published. The pad is zeros, which
    add nothing to the sum, but a decode step's mask reads what is held:
    a narrow layer (a test preset's 24 to 48 columns) is not made a lane
    tile wide for the kernel's sake and stays on the mask."""
    lanes = WIDTH_CHUNK // 2
    padded = -(-width // lanes) * lanes
    return padded if 8 * (padded - width) <= width else width


def width_block(h: int, f: int, itemsize: int, gated: bool) -> int:
    """Columns of an expert's width one grid block holds: the whole width
    where its matrices fit ``VMEM_WEIGHTS`` twice over, else the most whole
    chunks that divide the width and fit."""
    chunk = width_chunk(f)
    chunks = f // chunk
    for n in range(1, chunks + 1):
        if chunks % n == 0 and \
                2 * (2 + gated) * h * (f // n) * itemsize <= VMEM_WEIGHTS:
            return f // n
    return chunk


def num_tiles(assignments: int, experts: int, tile_rows: int) -> int:
    """Most tiles ``assignments`` rows on ``experts`` experts can fill:
    the whole tiles' worth, and a part-filled one an expert."""
    return assignments // tile_rows + experts


def group_rows(local: jnp.ndarray, sizes: jnp.ndarray, tile_rows: int):
    """Lay the held assignments out by expert, each expert on whole tiles.

    Args:
      local: ``(tokens, k)`` int32, the held expert of each assignment in
        ``[0, experts)``, or ``experts`` for one that is not computed here
        (held elsewhere, or of a padding token).
      sizes: ``(experts,)`` int32, the assignments on each held expert.
      tile_rows: rows of a tile.

    Returns ``(row, source, tile_expert, tiles)``: ``row`` ``(tokens, k)``
    int32, where each assignment lies (in token order within an expert: the
    stable order), ``tiles_max x tile_rows`` for one not computed; ``source``
    ``(tiles_max x tile_rows,)`` int32, the token whose activations a row
    holds (token 0 in the padding); ``tile_expert`` ``(tiles_max,)`` int32
    (a valid expert past the last real tile too: with nothing held the one
    tile the kernel runs reads entry 0); ``tiles`` int32 scalar, the tiles
    that hold rows.
    """
    tokens, k = local.shape
    experts = sizes.shape[0]
    tiles_max = num_tiles(tokens * k, experts, tile_rows)
    rows = tiles_max * tile_rows
    flat = local.reshape(-1)
    hot = flat[:, None] == jnp.arange(experts, dtype=flat.dtype)[None, :]
    # An assignment's rank among those of its expert, in token order.
    rank = jnp.sum(jnp.where(hot, jnp.cumsum(hot, axis=0, dtype=jnp.int32), 0),
                   axis=1) - 1
    padded = -(-sizes // tile_rows) * tile_rows
    ends = jnp.cumsum(padded)
    starts = ends - padded
    held = flat < experts
    row = jnp.where(held, starts[jnp.minimum(flat, experts - 1)] + rank, rows)
    source = jnp.zeros((rows,), jnp.int32).at[row].set(
        jnp.arange(tokens * k, dtype=jnp.int32) // k, mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(tiles_max) * tile_rows,
                         side="right"), experts - 1).astype(jnp.int32)
    return (row.reshape(tokens, k), source, tile_expert,
            (ends[-1] // tile_rows).astype(jnp.int32))


def _kernel(tile_expert_ref, x_ref, *refs, gated: bool, chunk: int,
            blocks: int = 1):
    del tile_expert_ref  # read by the index maps
    if gated:
        w_gate_ref, w_up_ref, w_down_ref, o_ref, acc_ref = refs
    else:
        w_up_ref, w_down_ref, o_ref, acc_ref = refs
    x = x_ref[...]
    # Stated, not left to the process's default: bf16 operands take one MXU
    # pass whatever ``jax_default_matmul_precision`` says.
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT, preferred_element_type=jnp.float32)

    def part(cols):
        act = dot(x, w_up_ref[:, cols])
        act = jax.nn.silu(dot(x, w_gate_ref[:, cols])) * act if gated \
            else jnp.square(jnp.maximum(act, 0.0))
        return dot(act.astype(x.dtype), w_down_ref[cols, :])

    def step(c, carry):
        acc_ref[...] += part(pl.ds(pl.multiple_of(c * chunk, chunk), chunk))
        return carry

    # The width a chunk at a time, in a loop and not unrolled: the body's
    # code is a chunk's, and so is the float32 activation.
    if blocks == 1:
        acc_ref[...] = jnp.zeros_like(acc_ref)
        jax.lax.fori_loop(0, w_down_ref.shape[0] // chunk, step, 0)
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)
        return

    # The width in grid blocks (axis 1): the tile's result is kept across
    # them and written with the last.
    @pl.when(pl.program_id(1) == 0)
    def _first():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    jax.lax.fori_loop(0, w_down_ref.shape[0] // chunk, step, 0)

    @pl.when(pl.program_id(1) == blocks - 1)
    def _last():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_experts(
    x: jnp.ndarray,
    tile_expert: jnp.ndarray,
    tiles: jnp.ndarray,
    w_gate: jnp.ndarray | None,
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,
    *,
    tile_rows: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Each tile of rows through its expert's MLP. Forward only: a gradient
    taken through it raises, and says that the masked path has one.

    Args:
      x: ``(tiles_max x tile_rows, h)`` activations as ``group_rows`` lays
        them.
      tile_expert: ``(tiles_max,)`` int32, ``tiles``: int32 scalar (at least
        one tile is run, so that the grid is never empty).
      w_gate: ``(experts, h, f)`` or None for an ungated relu² expert;
        ``w_up`` ``(experts, h, f)``; ``w_down`` ``(experts, f, h)``.

    Returns ``(tiles_max x tile_rows, h)`` in ``x``'s type: the rows of the
    down product. Rows of a tile past ``tiles`` are not written.
    """
    return _jitted(x, tile_expert, tiles, w_gate, w_up, w_down,
                   tile_rows, width_chunk(w_down.shape[1]), interpret)


def _no_backward(*_):
    raise NotImplementedError(
        "ops.pallas.grouped_experts has no backward pass: it serves prefill "
        "calls. models.moe.routed_masked computes the same sum and is "
        "differentiable; a training path through HeldExpertsMLP must take "
        "it (a call under models.moe.GROUPED_MIN_TOKENS tokens does)")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _forward_only(x, tile_expert, tiles, w_gate, w_up, w_down, tile_rows,
                  chunk, interpret):
    rows, h = x.shape
    experts, f, _ = w_down.shape
    gated = w_gate is not None
    if rows % tile_rows:
        raise ValueError(f"grouped_experts: {rows} rows are not whole tiles "
                         f"of {tile_rows}")
    if f % chunk:
        raise ValueError(f"grouped_experts: experts {f} wide are not whole "
                         f"chunks of {chunk} columns")

    item = x.dtype.itemsize
    fb = width_block(h, f, item, gated)
    blocks = f // fb
    if blocks == 1:
        # An expert's whole matrix is one block.
        grid = (jnp.maximum(tiles, 1),)
        inner = pl.BlockSpec((None, h, f), lambda i, e: (e[i], 0, 0))
        outer = pl.BlockSpec((None, f, h), lambda i, e: (e[i], 0, 0))
        by_tile = pl.BlockSpec((tile_rows, h), lambda i, e: (i, 0))
    else:
        grid = (jnp.maximum(tiles, 1), blocks)
        inner = pl.BlockSpec((None, h, fb), lambda i, j, e: (e[i], 0, j))
        outer = pl.BlockSpec((None, fb, h), lambda i, j, e: (e[i], j, 0))
        by_tile = pl.BlockSpec((tile_rows, h), lambda i, j, e: (i, 0))
    # Two buffers a block, and the float32 activations and result.
    vmem = (2 * ((2 + gated) * h * fb + 2 * tile_rows * h) * item
            + 4 * tile_rows * (h + 3 * fb))
    call = pl.pallas_call(
        functools.partial(_kernel, gated=gated, chunk=chunk,
                          **({} if blocks == 1 else {"blocks": blocks})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[by_tile] + [inner] * (1 + gated) + [outer],
            out_specs=by_tile,
            scratch_shapes=[pltpu.VMEM((tile_rows, h), jnp.float32)],
        ),
        out_shape=out_struct((rows, h), x.dtype, x),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=int(min(max(vmem * 5 // 4, 32 << 20),
                                     100 << 20))),
        interpret=interpret,
        # The name under which a device trace shows this kernel.
        name="dlti_grouped_experts",
        # Every tile the layout has room for, as if all held rows, each
        # with an expert of its own.
        cost_estimate=pl.CostEstimate(
            flops=int(2 * rows * h * f * (2 + gated)),
            bytes_accessed=int((rows // tile_rows) * (2 + gated) * h * f * item
                               + 2 * rows * h * item),
            transcendentals=int(rows * f) if gated else 0,
        ),
    )
    weights = (w_gate, w_up, w_down) if gated else (w_up, w_down)
    with jax.named_scope("dlti_grouped_experts"):
        return call(tile_expert, x, *weights)


_forward_only.defvjp(_no_backward, _no_backward)

# Jitted on its own, as the attention kernels are: every expert layer of a
# prefill program shares one trace and one lowering.
_jitted = jax.jit(_forward_only, static_argnums=(6, 7, 8))
