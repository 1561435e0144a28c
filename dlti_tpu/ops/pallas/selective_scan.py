"""Pallas TPU kernels for the Mamba-1 selective scan over whole rows, forward
and backward (``models.mamba1.chunked_selective_scan`` on the TPU).

The recurrence ``s_t = keep_t exp(Dt_t A) s_{t-1} + (Dt_t u_t) (outer) B_t``,
``y_t = s_t C_t`` runs over a row's tokens with the state of a block of
``CHANNELS`` channels, ``(N, CHANNELS)`` float32 (state index on sublanes,
channels on lanes), held on the chip: the grid is (row, channel block, chunk
of ``CHUNK`` tokens), the chunks in order, and a kernel call walks its chunk
a token at a time, unrolled. B and C come transposed, ``(rows, N, L)``, so
that a token's values are a column that broadcasts along the lanes; a token's
``u`` and ``Dt`` are a row that broadcasts along the sublanes.

The forward kernel also writes the state BEFORE each chunk, ``(rows, chunks,
N, D)``: all the backward pass keeps. The backward kernel walks the chunks
in reverse; in each it recomputes the chunk's states from the kept one into
VMEM, then walks the tokens backwards with the adjoint of the state in
registers. Sums over channels (the gradients of B and C) leave the kernel as
partial sums a channel block, lane-reduced a chunk at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tokens of one kernel call, and between two kept states.
CHUNK = 128
# Channels of one kernel call: the state is (N, CHANNELS) float32, eight
# vector registers at N = 16.
CHANNELS = 512
F32 = jnp.float32


def _fwd_kernel(u_ref, dt_ref, a_ref, bt_ref, ct_ref, keep_ref,
                y_ref, kept_ref, s_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    s = s_ref[...]
    kept_ref[...] = s
    a = a_ref[...]
    for t in range(CHUNK):
        dt_t = dt_ref[t:t + 1, :]                               # (1, C)
        decay = jnp.exp(dt_t * a) * keep_ref[:, t:t + 1]        # (N, C)
        s = decay * s + (dt_t * u_ref[t:t + 1, :]) * bt_ref[:, t:t + 1]
        y_ref[t:t + 1, :] = jnp.sum(s * ct_ref[:, t:t + 1], axis=0,
                                    keepdims=True)
    s_ref[...] = s


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_fwd(u, dt, a, b_in, c_in, keep, interpret: bool = False):
    """``(y (rows, L, D), the state before each chunk (rows, chunks, N,
    D))``; u, dt (rows, L, D) float32 with L a multiple of ``CHUNK`` and D
    of ``CHANNELS``; a (N, D); b_in, c_in (rows, L, N); keep (rows, L)."""
    rows, length, d = u.shape
    n = a.shape[0]
    chunks = length // CHUNK
    grid = (rows, d // CHANNELS, chunks)
    tokens = pl.BlockSpec((None, CHUNK, CHANNELS), lambda i, j, k: (i, k, j))
    columns = pl.BlockSpec((None, n, CHUNK), lambda i, j, k: (i, 0, k))
    call = pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[tokens, tokens,
                  pl.BlockSpec((n, CHANNELS), lambda i, j, k: (0, j)),
                  columns, columns,
                  pl.BlockSpec((None, 1, CHUNK), lambda i, j, k: (i, 0, k))],
        out_specs=[tokens,
                   pl.BlockSpec((None, None, n, CHANNELS),
                                lambda i, j, k: (i, k, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((rows, length, d), F32),
                   jax.ShapeDtypeStruct((rows, chunks, n, d), F32)],
        scratch_shapes=[pltpu.VMEM((n, CHANNELS), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dlti_selective_scan_fwd",
        cost_estimate=pl.CostEstimate(
            flops=int(8 * rows * length * d * n),
            transcendentals=int(rows * length * d * n),
            bytes_accessed=int(4 * (3 * rows * length * d
                                    + rows * chunks * n * d))),
    )
    return call(u, dt, a, jnp.swapaxes(b_in, 1, 2), jnp.swapaxes(c_in, 1, 2),
                keep[:, None, :])


# Channels of one backward call: the state, its adjoint and the products of
# the two stay in registers at (N, 256) each.
BWD_CHANNELS = 256
LANES = 128


def _lane_tiles_sum(x):
    """(N, channels) -> (N, LANES): the lane tiles added up."""
    return sum(x[:, i:i + LANES] for i in range(0, x.shape[1], LANES))


def _bwd_kernel(u_ref, dt_ref, a_ref, bt_ref, ct_ref, keep_ref, dy_ref,
                kept_ref, du_ref, ddt_ref, da_ref, db_ref, dc_ref,
                lam_ref, states_ref):
    k, j = pl.program_id(1), pl.program_id(2)
    mine = pl.ds(pl.multiple_of(j * BWD_CHANNELS, BWD_CHANNELS),
                 BWD_CHANNELS)

    @pl.when(k == 0)
    def _():
        lam_ref[:, mine] = jnp.zeros((lam_ref.shape[0], BWD_CHANNELS), F32)
        da_ref[:, mine] = jnp.zeros((da_ref.shape[0], BWD_CHANNELS), F32)

    @pl.when(j == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    a = a_ref[...]

    def token(t):
        dt_t = dt_ref[t:t + 1, :]
        decay = jnp.exp(dt_t * a) * keep_ref[:, t:t + 1]
        return dt_t, decay, dt_t * u_ref[t:t + 1, :]

    # the states before each token of the chunk, from the kept one
    s = kept_ref[...]
    for t in range(CHUNK):
        states_ref[t] = s
        _, decay, dtu = token(t)
        s = decay * s + dtu * bt_ref[:, t:t + 1]

    # lam: the gradient that later tokens send to this token's state
    lam = lam_ref[:, mine]
    da = jnp.zeros_like(a)
    for t in reversed(range(CHUNK)):
        s_prev = states_ref[t]
        dt_t, decay, dtu = token(t)
        dy_t = dy_ref[t:t + 1, :]
        b_t = bt_ref[:, t:t + 1]
        carried = decay * s_prev
        lam = lam + ct_ref[:, t:t + 1] * dy_t
        dc_ref[t] += _lane_tiles_sum((carried + dtu * b_t) * dy_t)
        db_ref[t] += _lane_tiles_sum(lam * dtu)
        through_b = jnp.sum(lam * b_t, axis=0, keepdims=True)      # (1, C)
        through_decay = lam * carried                              # (N, C)
        ddt_ref[t:t + 1, :] = jnp.sum(through_decay * a, axis=0,
                                      keepdims=True) \
            + through_b * u_ref[t:t + 1, :]
        du_ref[t:t + 1, :] = through_b * dt_t
        da = da + through_decay * dt_t
        lam = decay * lam
    lam_ref[:, mine] = lam
    da_ref[:, mine] += da


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan_bwd(u, dt, a, b_in, c_in, keep, kept, dy,
                       interpret: bool = False):
    """The gradients ``(du, ddt (rows, L, D), da (N, D), db, dc (rows, L,
    N))`` of :func:`selective_scan_fwd`'s ``y`` under ``dy``, from its
    inputs and the states it kept."""
    rows, length, d = u.shape
    n = a.shape[0]
    chunks = length // CHUNK
    grid = (rows, chunks, d // BWD_CHANNELS)

    def back(k):        # the chunks last to first
        return chunks - 1 - k

    tokens = pl.BlockSpec((None, CHUNK, BWD_CHANNELS),
                          lambda i, k, j: (i, back(k), j))
    columns = pl.BlockSpec((None, n, CHUNK), lambda i, k, j: (i, 0, back(k)))
    wide = pl.BlockSpec((None, CHUNK, n, LANES),
                        lambda i, k, j: (i, back(k), 0, 0))
    call = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[tokens, tokens,
                  pl.BlockSpec((n, BWD_CHANNELS), lambda i, k, j: (0, j)),
                  columns, columns,
                  pl.BlockSpec((None, 1, CHUNK),
                               lambda i, k, j: (i, 0, back(k))),
                  tokens,
                  pl.BlockSpec((None, None, n, BWD_CHANNELS),
                               lambda i, k, j: (i, back(k), 0, j))],
        out_specs=[tokens, tokens,
                   pl.BlockSpec((None, n, d), lambda i, k, j: (i, 0, 0)),
                   wide, wide],
        out_shape=[jax.ShapeDtypeStruct((rows, length, d), F32),
                   jax.ShapeDtypeStruct((rows, length, d), F32),
                   jax.ShapeDtypeStruct((rows, n, d), F32),
                   jax.ShapeDtypeStruct((rows, length, n, LANES), F32),
                   jax.ShapeDtypeStruct((rows, length, n, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((n, d), F32),
                        pltpu.VMEM((CHUNK, n, BWD_CHANNELS), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="dlti_selective_scan_bwd",
        cost_estimate=pl.CostEstimate(
            flops=int(30 * rows * length * d * n),
            transcendentals=int(2 * rows * length * d * n),
            bytes_accessed=int(4 * (5 * rows * length * d
                                    + rows * chunks * n * d
                                    + 2 * rows * length * n * LANES))),
    )
    du, ddt, da, db, dc = call(
        u, dt, a, jnp.swapaxes(b_in, 1, 2), jnp.swapaxes(c_in, 1, 2),
        keep[:, None, :], dy, kept)
    return du, ddt, da.sum(0), db.sum(-1), dc.sum(-1)
