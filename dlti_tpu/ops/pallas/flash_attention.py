"""Blockwise (flash) causal attention for TPU, in Pallas — fwd and bwd.

The hot op of the whole framework. Replaces the (seq, seq) score
materialization of ``reference_attention`` with an online-softmax sweep over
KV blocks held in VMEM — O(seq) memory, MXU-sized tiles, fp32 accumulators.
The reference repo inherits its fused attention from HF/torch CUDA kernels
(``/root/reference/training/train_baseline.py:122-126`` loads the stock HF
Llama); this is the TPU-native equivalent.

Layout: the grid is (batch * kv_heads, q_blocks, kv_blocks) (fwd, dq) or
(batch * kv_heads, kv_blocks, q_blocks) (dk/dv). **GQA is native**: each
grid row processes all ``group = heads // kv_heads`` query heads of one kv
head together — q tiles are (group, block_q, d) against a single
(block_kv, d) K/V tile, so K/V are never repeated in HBM and the score
matmul keeps its MXU shape. TPU grids execute sequentially
minor-most-first, so per-block running state lives in VMEM scratch across
the innermost sweep.

**Packed sequences are native**: optional per-token segment ids mask
cross-document attention inside the kernel (id 0 = padding, matching
``reference_attention``), and whole (q, kv) tiles whose segment-id
intervals are disjoint are skipped before any MXU work — packed
long-context batches degrade toward block-diagonal cost instead of
O(seq²). Causal blocks outside the (windowed) band are likewise skipped
via ``pl.when``, and the band edges get elementwise iota masks.

Backward is the standard flash decomposition: the forward also emits the
per-row logsumexp L; the backward recomputes p = exp(qk*scale - L) per tile
(no (seq, seq) materialization), with
``D = rowsum(dO * O)``, ``dv += p^T dO``, ``ds = p * (dO v^T - D) * scale``,
``dq += ds k``, ``dk += ds^T q`` — two sweeps, O(seq) memory.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def out_struct(shape, dtype, like):
    """``jax.ShapeDtypeStruct`` carrying the varying-manual-axes (vma) of
    ``like``: inside a ``check_vma`` shard_map (e.g. the pipeline
    schedule's manual 'pipe' region) a pallas_call's out_shape must state
    how its outputs vary across manual axes, or tracing fails with
    "`vma` on `jax.ShapeDtypeStruct` must not be `None`". Outside any
    shard_map, vma is empty and this is a plain struct."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _band_mask(qi, ki, block_q, block_kv, group, causal, window, seq_q,
               seq_kv):
    """Elementwise allowed-mask for the (qi, ki) tile.

    Shape (group*block_q, block_kv): the kernels flatten the GQA query
    group into the row dim (Mosaic's matmul lowering wants 2D operands),
    so row r is query position ``qi*block_q + r % block_q``. Combines the
    causal/sliding-window band with sequence bounds: Pallas does NOT zero
    tile padding on TPU, so rows >= seq_q / cols >= seq_kv hold garbage
    and must be masked in every kernel that *accumulates* across tiles
    (the whole backward; the non-causal forward). Returns None only when
    provably nothing needs masking.
    """
    shape = (group * block_q, block_kv)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if group > 1:
        row = jax.lax.rem(row, block_q)
    q_pos = qi * block_q + row
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    padded = seq_q % block_q != 0 or seq_kv % block_kv != 0
    if not causal and not padded:
        return None
    allowed = None
    if causal:
        allowed = k_pos <= q_pos
        if window:
            allowed &= k_pos > q_pos - window
    if padded:
        bounds = (q_pos < seq_q) & (k_pos < seq_kv)
        allowed = bounds if allowed is None else (allowed & bounds)
    return allowed


def _tile_mask(qi, ki, block_q, block_kv, group, causal, window, seq_q,
               seq_kv, qseg_ref, kseg_ref):
    """Full allowed-mask: causal band ∧ bounds ∧ same-segment (id 0 = pad).
    Shape (group*block_q, block_kv) (see :func:`_band_mask`)."""
    allowed = _band_mask(qi, ki, block_q, block_kv, group, causal, window,
                         seq_q, seq_kv)
    if qseg_ref is not None:
        q_ids = qseg_ref[0]    # (block_q, 1)
        if group > 1:
            q_ids = jnp.broadcast_to(
                q_ids[None], (group, block_q, 1)).reshape(group * block_q, 1)
        kv_ids = kseg_ref[0]   # (1, block_kv)
        seg = (q_ids == kv_ids) & (kv_ids != 0)
        allowed = seg if allowed is None else (allowed & seg)
    return allowed


def _band_run(qi, ki, block_q, block_kv, causal, window):
    """Whole-tile skip predicate (conservative w.r.t. :func:`_band_mask`)."""
    if not causal:
        return True
    run = ki * block_kv <= qi * block_q + (block_q - 1)
    if window:
        run = jnp.logical_and(
            run, ki * block_kv + (block_kv - 1) > qi * block_q - window)
    return run


def _seg_run(qseg_ref, kseg_ref):
    """Dynamic whole-tile skip: if the q and kv tiles' segment-id intervals
    are disjoint, no pair can be equal and the tile contributes nothing.
    Garbage ids in tile padding only *widen* the intervals, so the skip
    stays conservative (a widened interval can only overlap more)."""
    q_ids = qseg_ref[0]
    kv_ids = kseg_ref[0]
    return jnp.logical_and(jnp.min(q_ids) <= jnp.max(kv_ids),
                           jnp.max(q_ids) >= jnp.min(kv_ids))


def _fwd_kernel(*refs, scale: float, block_q: int, block_kv: int,
                group: int, causal: bool, window: int, seq_q: int,
                seq_kv: int, has_segs: bool, window_blocks: int = 0):
    if has_segs:
        (q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, lse_ref,
         m_scratch, l_scratch, acc_scratch) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scratch, l_scratch, acc_scratch) = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    gbq = group * block_q
    # Windowed grid: the kv dimension enumerates only the window_blocks
    # blocks ending at q's diagonal block — blocks outside the band are
    # never visited (and never DMA'd). ki is the *virtual* kv-block index
    # the visit targets; negative values are clamped duplicate fetches of
    # block 0, fully masked and skipped below.
    if window_blocks:
        ki = ((qi + 1) * block_q - 1) // block_kv - (window_blocks - 1) + kj
    else:
        ki = kj

    @pl.when(kj == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    run = _band_run(qi, ki, block_q, block_kv, causal, window)
    if window_blocks:
        run = jnp.logical_and(run, ki >= 0)
    if has_segs:
        run = jnp.logical_and(run, _seg_run(qseg_ref, kseg_ref))

    @pl.when(run)
    def _body():
        # (group, block_q, d) -> (group*block_q, d): Mosaic's matmul wants
        # 2D operands, and the flattened form is one big MXU matmul.
        q = q_ref[0].astype(jnp.float32).reshape(gbq, -1)
        k = k_ref[0].astype(jnp.float32)  # (block_kv, d)
        v = v_ref[0].astype(jnp.float32)  # (block_kv, dv)
        if seq_kv % block_kv != 0:
            # Zero OOB tile padding: Pallas leaves it garbage (NaN in
            # interpret mode) and the p @ v contraction sums over it —
            # 0 * NaN = NaN even though p is masked there.
            cols = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, 1), 0)
            k = jnp.where(cols < seq_kv, k, 0.0)
            v = jnp.where(cols < seq_kv, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (group*block_q, block_kv)

        allowed = _tile_mask(qi, ki, block_q, block_kv, group, causal,
                             window, seq_q, seq_kv, qseg_ref, kseg_ref)
        if allowed is not None:
            s = jnp.where(allowed, s, NEG_INF)

        m_prev = m_scratch[:]  # (group*block_q, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Rows with no valid entry in this block have m_new == NEG_INF,
        # making exp(s - m_new) == 1 for every *masked* entry — explicitly
        # zero them (hit when block_kv > block_q admits blocks strictly
        # above a row's diagonal, or a fully-masked segment row).
        p = jnp.exp(s - m_new) * (s > NEG_INF / 2)
        alpha = jnp.exp(m_prev - m_new)  # (group*block_q, 1)
        l_new = alpha * l_scratch[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scratch[:] = acc_scratch[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scratch[:] = m_new
        l_scratch[:] = l_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scratch[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zero output
        o_ref[0] = (acc_scratch[:] / safe_l).reshape(
            group, block_q, -1).astype(o_ref.dtype)
        # Per-row logsumexp for the backward. Fully-masked rows get +BIG so
        # the backward's exp(s - L) is exactly 0 there.
        lse = jnp.where(l > 0.0, m_scratch[:] + jnp.log(safe_l), -NEG_INF)
        lse_ref[0] = lse.reshape(group, block_q, 1)


def _window_kv_blocks(causal, window, block_q, block_kv, nk):
    """kv-block visits per q block under a sliding window (0 = full sweep).

    The band of q tile qi spans kv blocks
    [floor((qi*Bq - window + 1)/Bkv), floor(((qi+1)*Bq - 1)/Bkv)] — at
    most (Bq + window - 2)//Bkv + 1 blocks; +1 margin keeps the bound
    safe. Only worthwhile when it actually shrinks the sweep.
    """
    if not (causal and window):
        return 0
    w = (block_q + window - 2) // block_kv + 2
    return w if w < nk else 0


def _window_q_blocks(causal, window, block_q, block_kv, nq):
    """q-block visits per kv block for the dk/dv sweep (0 = full sweep)."""
    if not (causal and window):
        return 0
    w = (block_kv + window - 2) // block_q + 2
    return w if w < nq else 0


def _kv_block_index(qi, j, block_q, block_kv, window_blocks, nk):
    """Physical kv-block index for visit j of q tile qi (clamped for DMA;
    the kernel recomputes the unclamped value for masking)."""
    v = ((qi + 1) * block_q - 1) // block_kv - (window_blocks - 1) + j
    return jnp.clip(v, 0, nk - 1)


def _seg_specs(h_kv, block_q, block_kv, transposed=False, kv_index=None,
               q_index=None):
    """BlockSpecs for (b, sq, 1) q-segment and (b, 1, skv) kv-segment arrays.

    The (block_q, 1) / (1, block_kv) tile shapes let the kernel form the
    (block_q, block_kv) equality mask by broadcast — no lane<->sublane
    transposes on TPU. The grid's leading axis is batch*kv_heads; ``// h_kv``
    recovers the batch row. ``kv_index``/``q_index`` remap the minor grid
    dim for windowed sweeps.
    """
    if transposed:  # dkv grid: (bh, kv_block, q_visit)
        qix = q_index or (lambda ki, j: j)
        q_map = lambda b, jk, jq: (b // h_kv, qix(jk, jq), 0)
        kv_map = lambda b, jk, jq: (b // h_kv, 0, jk)
    else:
        kix = kv_index or (lambda i, j: j)
        q_map = lambda b, i, j: (b // h_kv, i, 0)
        kv_map = lambda b, i, j: (b // h_kv, 0, kix(i, j))
    return (pl.BlockSpec((1, block_q, 1), q_map),
            pl.BlockSpec((1, 1, block_kv), kv_map))


def _flash_fwd(q, k, v, q_seg, kv_seg, *, h_kv, scale, block_q, block_kv,
               causal, window, interpret):
    """q: (b*h_kv, group, sq, d); k: (b*h_kv, skv, d); v: (b*h_kv, skv, dv),
    a width of its own; q_seg: (b, sq, 1) / kv_seg: (b, 1, skv) or None
    -> (o (b*h_kv, group, sq, dv), lse)."""
    bh, group, sq, d = q.shape
    skv, dv = k.shape[1], v.shape[2]
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    nk = pl.cdiv(skv, block_kv)
    win_blocks = _window_kv_blocks(causal, window, block_q, block_kv, nk)
    grid = (bh, pl.cdiv(sq, block_q), win_blocks or nk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        group=group, causal=causal, window=window, seq_q=sq, seq_kv=skv,
        has_segs=q_seg is not None, window_blocks=win_blocks,
    )

    def q_spec(width):
        return pl.BlockSpec((1, group, block_q, width),
                            lambda b, i, j: (b, 0, i, 0))

    kv_index = functools.partial(
        _kv_block_index, block_q=block_q, block_kv=block_kv,
        window_blocks=win_blocks, nk=nk) if win_blocks else None

    def kv_spec(width):
        return pl.BlockSpec(
            (1, block_kv, width),
            (lambda b, i, j: (b, kv_index(i, j), 0)) if win_blocks
            else (lambda b, i, j: (b, j, 0)))

    in_specs = [q_spec(d), kv_spec(d), kv_spec(dv)]
    inputs = [q, k, v]
    if q_seg is not None:
        qs_spec, ks_spec = _seg_specs(h_kv, block_q, block_kv,
                                      kv_index=kv_index)
        in_specs += [qs_spec, ks_spec]
        inputs += [q_seg, kv_seg]
    call = pl.pallas_call(
        kernel,
        out_shape=(
            out_struct((bh, group, sq, dv), q.dtype, q),
            out_struct((bh, group, sq, 1), jnp.float32, q),  # logsumexp
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=(
            q_spec(dv),
            pl.BlockSpec((1, group, block_q, 1), lambda b, i, j: (b, 0, i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((group * block_q, 1), jnp.float32),
            pltpu.VMEM((group * block_q, 1), jnp.float32),
            pltpu.VMEM((group * block_q, dv), jnp.float32),
        ],
        interpret=interpret,
        # The names under which a device trace shows the three kernels
        # (benchmark/lib/span_rules.json finds them by these); the scope
        # round each call keeps the model's own scopes out of that name.
        name="dlti_flash_attention_fwd",
        cost_estimate=pl.CostEstimate(
            # Banded fraction: a windowed grid visits win_blocks kv blocks
            # per q tile instead of the causal triangle.
            flops=int(2 * (d + dv) * bh * group * sq
                      * (min(win_blocks * block_kv, skv) if win_blocks
                         else skv * (0.5 if causal else 1.0))),
            bytes_accessed=(2 * q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=int(bh * group * sq
                                * (min(win_blocks * block_kv, skv)
                                   if win_blocks else skv)),
        ),
    )
    with jax.named_scope("dlti_flash_attention_fwd"):
        return call(*inputs)


def _load_bwd_tiles(q_ref, k_ref, v_ref, do_ref, qi, ki, block_q, block_kv,
                    group, seq_q, seq_kv):
    """Load backward tiles (q/do flattened to (group*block_q, d)) with
    padding rows/cols zeroed.

    Pallas does not zero tile padding on TPU; the backward *accumulates*
    across tiles, so garbage (potentially inf/NaN, which survives
    multiplication by zero) in rows >= seq_q / cols >= seq_kv must be
    cleared at load time.
    """
    gbq = group * block_q
    q = q_ref[0].astype(jnp.float32).reshape(gbq, -1)
    k = k_ref[0].astype(jnp.float32)    # (block_kv, d)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32).reshape(gbq, -1)
    if seq_q % block_q != 0:
        rows = jax.lax.broadcasted_iota(jnp.int32, (gbq, 1), 0)
        if group > 1:
            rows = jax.lax.rem(rows, block_q)
        rows = qi * block_q + rows
        q = jnp.where(rows < seq_q, q, 0.0)
        do = jnp.where(rows < seq_q, do, 0.0)
    if seq_kv % block_kv != 0:
        cols = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_kv, 1), 0)
        k = jnp.where(cols < seq_kv, k, 0.0)
        v = jnp.where(cols < seq_kv, v, 0.0)
    return q, k, v, do


def _dq_kernel(*refs, scale, block_q, block_kv, group, causal, window,
               seq_q, seq_kv, has_segs, window_blocks: int = 0):
    if has_segs:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref,
         dq_ref, dq_scratch) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scratch) = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    gbq = group * block_q
    if window_blocks:  # see _fwd_kernel: virtual kv index of this visit
        ki = ((qi + 1) * block_q - 1) // block_kv - (window_blocks - 1) + kj
    else:
        ki = kj

    @pl.when(kj == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    run = _band_run(qi, ki, block_q, block_kv, causal, window)
    if window_blocks:
        run = jnp.logical_and(run, ki >= 0)
    if has_segs:
        run = jnp.logical_and(run, _seg_run(qseg_ref, kseg_ref))

    @pl.when(run)
    def _body():
        q, k, v, do = _load_bwd_tiles(
            q_ref, k_ref, v_ref, do_ref, qi, ki, block_q, block_kv, group,
            seq_q, seq_kv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (group*bq, bk)
        mask = _tile_mask(qi, ki, block_q, block_kv, group, causal, window,
                          seq_q, seq_kv, qseg_ref, kseg_ref)
        lse = lse_ref[0].reshape(gbq, 1)
        delta = delta_ref[0].reshape(gbq, 1)
        p = jnp.exp(s - lse)                               # (group*bq, bk)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        # where() (not just p==0) so garbage lse/delta in padding rows can't
        # poison the product with 0 * inf = NaN.
        ds = p * (dp - delta) * scale                      # (group*bq, bk)
        if mask is not None:
            ds = jnp.where(mask, ds, 0.0)
        dq_scratch[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = dq_scratch[:].reshape(
            group, block_q, -1).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, block_q, block_kv, group, causal, window,
                seq_q, seq_kv, has_segs, window_q_blocks: int = 0):
    if has_segs:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref, kseg_ref,
         dk_ref, dv_ref, dk_scratch, dv_scratch) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scratch, dv_scratch) = refs
        qseg_ref = kseg_ref = None
    ki = pl.program_id(1)
    qj = pl.program_id(2)
    gbq = group * block_q
    if window_q_blocks:
        # Virtual q-block index of this visit: the band of kv block ki
        # starts at its own diagonal q block and extends window forward.
        qi = (ki * block_kv) // block_q + qj
    else:
        qi = qj

    @pl.when(qj == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    run = _band_run(qi, ki, block_q, block_kv, causal, window)
    if window_q_blocks:
        # Clamped duplicate visits past the last real q block are masked.
        run = jnp.logical_and(run, qi * block_q < seq_q)
    if has_segs:
        run = jnp.logical_and(run, _seg_run(qseg_ref, kseg_ref))

    @pl.when(run)
    def _body():
        q, k, v, do = _load_bwd_tiles(
            q_ref, k_ref, v_ref, do_ref, qi, ki, block_q, block_kv, group,
            seq_q, seq_kv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (group*bq, bk)
        mask = _tile_mask(qi, ki, block_q, block_kv, group, causal, window,
                          seq_q, seq_kv, qseg_ref, kseg_ref)
        lse = lse_ref[0].reshape(gbq, 1)
        delta = delta_ref[0].reshape(gbq, 1)
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # Contract over all group*bq rows: one (bkv, group*bq) @
        # (group*bq, d) MXU matmul per tile sums the group contributions.
        dv_scratch[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        if mask is not None:
            ds = jnp.where(mask, ds, 0.0)
        dk_scratch[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qj == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scratch[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, q_seg, kv_seg, *, h_kv, scale, block_q,
               block_kv, causal, window, interpret):
    """q,o,do: (b*h_kv, group, s, d); k,v: (b*h_kv, s, d);
    lse: (b*h_kv, group, s, 1) -> (dq, dk, dv)."""
    bh, group, sq, d = q.shape
    skv = k.shape[1]
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(skv, block_kv)
    has_segs = q_seg is not None
    win_blocks = _window_kv_blocks(causal, window, block_q, block_kv, nk)
    win_q_blocks = _window_q_blocks(causal, window, block_q, block_kv, nq)

    # D_i = rowsum(dO_i * O_i) — tiny elementwise pass, XLA-fused.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)

    q_spec = pl.BlockSpec((1, group, block_q, d), lambda b, i, j: (b, 0, i, 0))
    if win_blocks:
        kv_index = functools.partial(_kv_block_index, block_q=block_q,
                                     block_kv=block_kv,
                                     window_blocks=win_blocks, nk=nk)
        kv_spec = pl.BlockSpec((1, block_kv, d),
                               lambda b, i, j: (b, kv_index(i, j), 0))
    else:
        kv_index = None
        kv_spec = pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, group, block_q, 1), lambda b, i, j: (b, 0, i, 0))

    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    inputs = [q, k, v, do, lse, delta]
    if has_segs:
        qs_spec, ks_spec = _seg_specs(h_kv, block_q, block_kv,
                                      kv_index=kv_index)
        in_specs += [qs_spec, ks_spec]
        inputs += [q_seg, kv_seg]
    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, group=group, causal=causal,
                          window=window, seq_q=sq, seq_kv=skv,
                          has_segs=has_segs, window_blocks=win_blocks),
        out_shape=out_struct((bh, group, sq, d), q.dtype, q),
        grid=(bh, nq, win_blocks or nk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((group * block_q, d), jnp.float32)],
        interpret=interpret,
        name="dlti_flash_attention_bwd_dq",
    )
    with jax.named_scope("dlti_flash_attention_bwd_dq"):
        dq = dq_call(*inputs)

    # dk/dv sweep: grid transposed so kv blocks are outer, q inner.
    if win_q_blocks:
        def q_index(jk, jq):
            return jnp.clip((jk * block_kv) // block_q + jq, 0, nq - 1)
    else:
        q_index = None
    qix = q_index or (lambda jk, jq: jq)
    q_spec_t = pl.BlockSpec((1, group, block_q, d),
                            lambda b, jk, jq: (b, 0, qix(jk, jq), 0))
    kv_spec_t = pl.BlockSpec((1, block_kv, d), lambda b, jk, jq: (b, jk, 0))
    row_spec_t = pl.BlockSpec((1, group, block_q, 1),
                              lambda b, jk, jq: (b, 0, qix(jk, jq), 0))
    in_specs_t = [q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t]
    inputs_t = [q, k, v, do, lse, delta]
    if has_segs:
        qs_spec_t, ks_spec_t = _seg_specs(h_kv, block_q, block_kv,
                                          transposed=True, q_index=q_index)
        in_specs_t += [qs_spec_t, ks_spec_t]
        inputs_t += [q_seg, kv_seg]
    dkv_call = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, group=group, causal=causal,
                          window=window, seq_q=sq, seq_kv=skv,
                          has_segs=has_segs, window_q_blocks=win_q_blocks),
        out_shape=(out_struct((bh, skv, d), k.dtype, k),
                   out_struct((bh, skv, d), v.dtype, v)),
        grid=(bh, nk, win_q_blocks or nq),
        in_specs=in_specs_t,
        out_specs=(kv_spec_t, kv_spec_t),
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d), jnp.float32)],
        interpret=interpret,
        name="dlti_flash_attention_bwd_dkv",
    )
    with jax.named_scope("dlti_flash_attention_bwd_dkv"):
        dk, dv = dkv_call(*inputs_t)
    return dq, dk, dv


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8)
)
def _flash_attention_core(q, k, v, segment_ids, causal, block_q, block_kv,
                          window, interpret):
    """(b, s, h, d) attention; GQA and packing handled inside the kernels."""
    return _core_fwd(q, k, v, segment_ids, causal, block_q, block_kv,
                     window, interpret)[0]


def _split_heads(q, k, v):
    """(b, s, h, d) q -> (b*h_kv, group, s, d); k/v -> (b*h_kv, s, d).

    Query head ``kh * group + g`` reads kv head ``kh`` — the same layout
    ``repeat_kv`` produces, so results are bit-comparable with the
    reference path.
    """
    b, sq, h, d = q.shape
    h_kv = k.shape[2]
    group = h // h_kv
    qt = (q.transpose(0, 2, 1, 3)
          .reshape(b * h_kv, group, sq, d))
    kt = k.transpose(0, 2, 1, 3).reshape(b * h_kv, k.shape[1], d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h_kv, v.shape[1], v.shape[3])
    return qt, kt, vt, h_kv, group


def _forward(q, k, v, segment_ids, scale, causal, block_q, block_kv, window,
             interpret):
    """``(out (b, sq, h, dv), what the backward keeps)``; the kept ``lse``
    is (b*h_kv, group, sq, 1) float32."""
    b, sq, h, _ = q.shape
    qt, kt, vt, h_kv, group = _split_heads(q, k, v)
    if segment_ids is not None:
        if k.shape[1] != sq:
            raise ValueError(
                f"flash_attention segment masking requires self-attention "
                f"shapes (one segment_ids array for both sides); got "
                f"sq={sq}, skv={k.shape[1]}")
        seg = segment_ids.astype(jnp.int32)
        q_seg = seg[:, :, None]   # (b, sq, 1): block tile (block_q, 1)
        kv_seg = seg[:, None, :]  # (b, 1, skv): block tile (1, block_kv)
    else:
        q_seg = kv_seg = None
    o, lse = _flash_fwd(qt, kt, vt, q_seg, kv_seg, h_kv=h_kv, scale=scale,
                        block_q=block_q, block_kv=block_kv, causal=causal,
                        window=window, interpret=interpret)
    out = (o.reshape(b, h, sq, -1).transpose(0, 2, 1, 3))
    return out, (qt, kt, vt, o, lse, q_seg, kv_seg)


def _core_fwd(q, k, v, segment_ids, causal, block_q, block_kv, window,
              interpret):
    return _forward(q, k, v, segment_ids, q.shape[3] ** -0.5, causal,
                    block_q, block_kv, window, interpret)


def _core_bwd(causal, block_q, block_kv, window, interpret, res, g):
    """Flash backward: tile-recomputed p from the saved logsumexp."""
    qt, kt, vt, o, lse, q_seg, kv_seg = res
    bh, group, sq, d = qt.shape
    b = g.shape[0]
    h = g.shape[2]
    h_kv = bh // b
    scale = d ** -0.5
    do = g.transpose(0, 2, 1, 3).reshape(bh, group, sq, d)
    dq, dk, dv = _flash_bwd(
        qt, kt, vt, o, lse, do, q_seg, kv_seg, h_kv=h_kv, scale=scale,
        block_q=block_q, block_kv=block_kv, causal=causal, window=window,
        interpret=interpret)

    dq_out = dq.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    skv = kt.shape[1]
    dk_out = dk.reshape(b, h_kv, skv, d).transpose(0, 2, 1, 3)
    dv_out = dv.reshape(b, h_kv, skv, d).transpose(0, 2, 1, 3)
    dseg = (None if q_seg is None
            else np.zeros(g.shape[:1] + (sq,), jax.dtypes.float0))
    return dq_out, dk_out, dv_out, dseg


_flash_attention_core.defvjp(_core_fwd, _core_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    segment_ids=None,
    block_q: int = 512,
    block_kv: int = 512,
    window: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash attention entry. q: (b, sq, h, d); k/v: (b, skv, h_kv, d).

    GQA runs natively in the kernel (each kv head's query group shares its
    K/V tile — nothing is repeated in HBM). ``block_q`` is the number of
    query ROWS per tile, counted across the group: the kernels flatten the
    ``group = h // h_kv`` query heads of one kv head into the row
    dimension, so a tile covers ``block_q // group`` positions of each.
    That keeps the (rows, block_kv) f32 score tile and its twins — which
    is what fills VMEM — the same size for MHA and GQA models. Sized per
    head instead, Mistral's group of 4 at 512 asks for 2048-row tiles, and
    the backward kernels then run out of VMEM on a v5e as soon as the
    sequence spans more than one tile (seq 2048 and 8192, windowed;
    jax 0.9.0 / libtpu 0.0.34). ``window`` enables
    Mistral-style sliding-window attention with whole-block skipping
    outside the band. ``segment_ids`` (b, s) enables packed-sequence
    masking with whole-block skipping of segment-disjoint tiles; id 0 is
    padding (such tokens attend to nothing and produce zero output).
    """
    return _flash_attention_core(
        q, k, v, segment_ids, causal,
        _positions_a_tile(block_q, q.shape[2] // k.shape[2]), block_kv,
        window or 0, interpret)


def _positions_a_tile(block_q: int, group: int) -> int:
    """Per-head positions per tile: the largest power of two that keeps the
    tile within ``block_q`` rows (floor 8, the sublane tile), so it divides
    every 128-aligned sequence whatever the group (7 -> 64, not 72 with a
    padded last tile)."""
    if group > 1:
        block_q = max(8, 1 << (max(1, block_q // group).bit_length() - 1))
    return block_q


@functools.partial(
    jax.jit, static_argnames=("scale", "causal", "block_q", "block_kv",
                              "window", "interpret"))
def flash_attention_fwd(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    scale: float,
    causal: bool = True,
    segment_ids=None,
    block_q: int = 512,
    block_kv: int = 512,
    window: int | None = None,
    interpret: bool = False,
) -> tuple:
    """The forward kernel alone, for a caller that differentiates nothing
    (a serving program) and merges the result with attention over other
    keys: ``(o, lse)``, o (b, sq, h, dv) in q's dtype and lse (b, h, sq)
    float32, the log of each query's summed ``exp(score)`` over the keys it
    saw, or **+1e30 for a query that saw none** (a padding token; its ``o``
    row is zero), the value the backward kernels want there.

    As :func:`flash_attention` but for: ``scale`` is the caller's (nothing
    is taken from a width, so zero columns appended to q and k change
    nothing); v may be narrower or wider than q and k (q, k: (.., d), v:
    (.., dv)); it is one jitted function, so a program of several layers
    traces and lowers the kernel once (a chip host took ~2 s a program to
    lower seven: PERF.md section 6, PR 51). Both products are float32 products of operands cast in VMEM,
    as training's: on bf16 operands they measured 1 % faster on the v5e
    (1.155 against 1.167 ms at 1 x 2,048 tokens, 32 heads, 192 / 128 wide:
    PERF.md section 6, PR 51), which buys no second form."""
    out, kept = _forward(
        q, k, v, segment_ids, scale, causal,
        _positions_a_tile(block_q, q.shape[2] // k.shape[2]), block_kv,
        window or 0, interpret)
    return out, kept[4].reshape(q.shape[0], q.shape[2], q.shape[1])
