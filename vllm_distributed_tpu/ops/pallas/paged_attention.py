"""Pallas ragged paged-attention kernel — the TPU hot path.

The TPU-native replacement for the CUDA PagedAttention/FlashAttention
kernels the reference inherits from the vLLM image (SURVEY.md §2.2 and
BASELINE.json north_star: "PagedAttention is a Pallas kernel").  One
kernel serves both decode (1 query token/seq) and chunked prefill
(many): queries are grouped per sequence and attention runs flash-style
(online softmax) over the sequence's paged KV.

Design (v2 — rebuilt after the round-3 on-chip ablation, PERF.md):
the round-3 kernel was COMPUTE-bound, not DMA-bound (DMA-only ablation
ran at 779 GB/s while the full kernel ran at ~250 GB/s): decode issued
8 separate per-head op chains on 8-row tiles, so the VPU (softmax, mask,
state updates) and tiny 6%-utilized MXU calls dominated while the DMA
queues idled.  v2 changes, in order of impact:

- **Folded-head block-diagonal compute.**  KV heads are processed in
  fold groups of F heads per matmul: queries are laid out
  block-diagonally as [rows = F*G*mq, F*D] so ONE dot per kv block
  computes F heads' scores ([rows, BLK]), and the whole softmax/state
  chain runs on one wide tile instead of per-head slivers.  For decode
  (mq=1) F grows to put all heads in a single chain (1B: 32 rows, 8
  heads, one chain vs 8); for prefill rows are already plentiful and F
  stays at the 128-lane alignment minimum.  The off-diagonal lanes are
  zeros, so scores are exact; outputs are extracted by diagonal einsum
  outside the kernel.
- **Combined flat KV pool** ``[2, P, page, HD]`` (ops/attention.py):
  two descriptors per page (K plane + V plane) cover all heads'
  contiguous lanes, and a 64-wide head dim is stored unpadded inside
  HD (the r3 layout padded each head to 128 lanes — 2× wasted bytes on
  Llama-1B-class models).
- **Globally rotating triple buffer.**  Buffer index = (number of
  active blocks completed so far) % 3, tracked in SMEM — never resets
  per sequence, so the cross-sequence block-0 prefetch can never target
  the buffer the current (or previous) step reads.  This replaces the
  r3 order-dependent safety argument (ADVICE r3 medium) with a
  structural invariant.
- Causal skip: kv blocks entirely above the q block's last position are
  skipped (no DMA, no compute) — half the work on prefill.

Numerics: scores/softmax/accumulation in float32 regardless of cache
dtype; output cast back to q.dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu.ops.attention import AttentionMetadata
from vllm_distributed_tpu.utils import cdiv, next_power_of_2

_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128
# Per-buffer-slot VMEM budget for the combined K+V block (bytes).
_KV_BUF_BYTES = 1024 * 1024
_NBUF = 3
# Budget for the f32 flash state (acc + m + l) across all fold groups:
# acc is hkv*g*mq*f*d*4 bytes and m+l add hkv*g*mq*256*4 (f-independent).
_STATE_BYTES = 6 * 1024 * 1024
# Decode-shape fold target: grow F until a block's softmax chain has at
# least this many rows (amortizes VPU op issue over more elements).
_ROWS_TARGET = 32
# Added to the byte model below for Mosaic's own scratch and whatever
# the model rounds away (sublane/lane padding of the small blocks).
_VMEM_HEADROOM = 8 * 1024 * 1024


def _kernel(
    # scalar prefetch (+ side_len_ref when has_side)
    block_tables_ref,  # [S, max_pages] int32 (SMEM)
    seq_lens_ref,  # [S] int32
    chunk_starts_ref,  # [S] int32
    # then inputs (q_ref [1,1,NF,ROWS,FD]; side_ref [1,2,K,HD] when
    # has_side; kv_pages_ref [2,P,page,HD] ANY; scale_blk_ref
    # [1,2,Hkv,BLK] f32 when has_quant — a regular pipelined block of
    # the per-sequence TRANSPOSED scale matrix the wrapper gathers in
    # XLA, so the kernel never DMAs sub-128-lane scale slabs), the out
    # block [1,1,NF,ROWS,FD], and scratch (kv_vmem [NBUF,2,BLK,HD],
    # m/l [NF,ROWS,LANES] f32, acc [NF,ROWS,FD] f32, DMA sems [NBUF],
    # cnt SMEM [2] = [completed active blocks (the buffer-rotation
    # cursor), prefetch-pending flag]).
    *rest,
    scale: float,
    soft_cap: float | None,
    page_size: int,
    pages_per_blk: int,
    group_size: int,
    num_fold: int,
    fold_width: int,
    mq_blk: int,
    has_side: bool,
    has_quant: bool,
):
    rest = list(rest)
    side_len_ref = rest.pop(0) if has_side else None
    q_ref = rest.pop(0)
    side_ref = rest.pop(0) if has_side else None
    kv_pages_ref = rest.pop(0)
    scale_blk_ref = rest.pop(0) if has_quant else None
    out_ref, kv_vmem = rest.pop(0), rest.pop(0)
    m_scr, l_scr, acc_scr, sems, cnt = rest
    s = pl.program_id(0)
    qb = pl.program_id(1)
    kvb = pl.program_id(2)
    num_seqs = pl.num_programs(0)
    num_qb = pl.num_programs(1)
    num_kvb = pl.num_programs(2)
    blk = pages_per_blk * page_size
    seq_len = seq_lens_ref[s]
    chunk_start = chunk_starts_ref[s]
    # Number of active kv blocks for (s, qb): the causal skip bound.
    q_pos_max = chunk_start + (qb + 1) * mq_blk - 1
    span = jnp.minimum(seq_len, q_pos_max + 1)
    nb = jnp.where(seq_len > 0, (span + blk - 1) // blk, 0)
    active = kvb < nb

    @pl.when((s == 0) & (qb == 0) & (kvb == 0))
    def _boot():
        cnt[0] = 0
        cnt[1] = 0

    def block_dma(seq, block_idx, buf):
        """Two descriptors per page (K plane, V plane), each covering
        every head's lanes contiguously.  (Quantized pools: the scale
        block arrives through the regular BlockSpec pipeline, not here.)
        """
        copies = []
        for i in range(pages_per_blk):
            page = block_tables_ref[seq, block_idx * pages_per_blk + i]
            for kvi in range(2):
                copies.append(
                    pltpu.make_async_copy(
                        kv_pages_ref.at[kvi, page],
                        kv_vmem.at[
                            buf, kvi, pl.ds(i * page_size, page_size)
                        ],
                        sems.at[buf],
                    )
                )
        return copies

    @pl.when(kvb == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _MASK)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Bootstrap / gap-recovery: if no predecessor prefetched this block
    # (first active step, or the one-step lookahead hit an empty
    # sequence), issue the DMA here and eat the stall.
    @pl.when(active & (cnt[1] == 0))
    def _bootstrap_dma():
        for cp in block_dma(s, kvb, cnt[0] % _NBUF):
            cp.start()

    # Prefetch the NEXT active block (same q block, next q block, or the
    # next sequence) into the next rotation slot while this one computes.
    next_in_qb = kvb + 1 < nb
    # (s, qb+1) restarts from kv block 0; (s+1) likewise.  An empty
    # sequence between live ones defeats the one-step lookahead; the
    # bootstrap above recovers (correctness never depends on lookahead).
    have_next_qb = (qb + 1 < num_qb) & (seq_len > 0)
    next_seq_ok = (s + 1 < num_seqs) & (
        seq_lens_ref[jnp.minimum(s + 1, num_seqs - 1)] > 0
    )
    has_next = next_in_qb | have_next_qb | next_seq_ok
    next_s = jnp.where(next_in_qb | have_next_qb, s, s + 1)
    next_kvb = jnp.where(next_in_qb, kvb + 1, 0)

    @pl.when(active & has_next)
    def _prefetch():
        for cp in block_dma(next_s, next_kvb, (cnt[0] + 1) % _NBUF):
            cp.start()

    block_start = kvb * blk

    def row_positions(ncols):
        """Per-row query position / per-col iota for masking.  Row
        layout: r = (hl*G + g)*mq + m → token index m = r % mq."""
        rows = acc_scr.shape[1]
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, ncols), 0)
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, ncols), 1)
        q_pos = chunk_start + qb * mq_blk + row_ids % mq_blk
        return q_pos, col_ids

    def scale_mat(st, nf, ncols):
        """[ROWS, ncols] dequant factors for fold nf from transposed
        per-head scales st [Hkv, ncols]: row r of the fold covers head
        nf*F + r // (G*mq) (block-diagonal row layout), so each head's
        scale row broadcasts over its G*mq query rows.  Off-diagonal
        lanes get the ROW's head scale (not the lane's) — harmless,
        they are discarded by the diagonal extraction outside."""
        f = acc_scr.shape[1] // (group_size * mq_blk)
        sub = st[nf * f : nf * f + f]  # [F, ncols] (static slice)
        return jnp.broadcast_to(
            sub[:, None, :], (f, group_size * mq_blk, ncols)
        ).reshape(f * group_size * mq_blk, ncols)

    def flash_update(nf, k, v, mask, sk=None, sv=None):
        """One online-softmax accumulation step for fold group nf.
        ``sk``/``sv`` are [ROWS, ncols] dequant factors (int8 pool);
        the K factor folds into the scores, the V factor into p before
        the PV matmul — both cheaper than lane-expanding the scale to
        dequantize the [ncols, FD] tiles themselves."""
        qn = q_ref[0, 0, nf].astype(jnp.float32)  # [ROWS, FD]
        scores = jax.lax.dot_general(
            qn, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [ROWS, ncols]
        if sk is not None:
            scores = scores * sk
        scores = scores * scale
        if soft_cap is not None:
            scores = jnp.tanh(scores / soft_cap) * soft_cap
        scores = jnp.where(mask, scores, _MASK)

        m_prev = m_scr[nf, :, 0:1]
        m_cur = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = l_scr[nf, :, 0:1] * alpha + jnp.sum(
            p, axis=-1, keepdims=True
        )
        if sv is not None:
            p = p * sv
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[nf] = acc_scr[nf] * alpha + pv
        m_scr[nf] = jnp.broadcast_to(m_new, m_scr[nf].shape)
        l_scr[nf] = jnp.broadcast_to(l_new, l_scr[nf].shape)

    @pl.when(active)
    def _compute():
        buf = cnt[0] % _NBUF
        for cp in block_dma(s, kvb, buf):
            cp.wait()
        q_pos, col_ids = row_positions(blk)
        c_pos = block_start + col_ids
        mask = (c_pos <= q_pos) & (c_pos < seq_len)
        if has_quant:
            # Pre-transposed by the wrapper: [Hkv, BLK] — fold slices
            # are contiguous sublane rows.
            st_k = scale_blk_ref[0, 0]
            st_v = scale_blk_ref[0, 1]
        for nf in range(num_fold):
            lo = nf * fold_width
            k = kv_vmem[buf, 0, :, lo : lo + fold_width].astype(jnp.float32)
            v = kv_vmem[buf, 1, :, lo : lo + fold_width].astype(jnp.float32)
            if has_quant:
                flash_update(
                    nf, k, v, mask,
                    sk=scale_mat(st_k, nf, blk),
                    sv=scale_mat(st_v, nf, blk),
                )
            else:
                flash_update(nf, k, v, mask)
        cnt[0] = cnt[0] + 1
        cnt[1] = has_next.astype(jnp.int32)

    @pl.when(kvb == num_kvb - 1)
    def _finalize():
        if has_side:
            # Staged decode writes: this dispatch's K/V rows live in the
            # dense side buffer (positions seq_len + j), not the pool.
            # Fold them into the same online-softmax state before the
            # division.  seq_len here is the POOL length (the runner
            # passes base lengths when staging).
            n_side = side_len_ref[0]
            kblk = side_ref.shape[2]
            q_pos, col_ids = row_positions(kblk)
            side_pos = seq_len + col_ids
            smask = (
                (col_ids < n_side)
                & (side_pos <= q_pos)
                & (seq_len > 0)
            )
            for nf in range(num_fold):
                lo = nf * fold_width
                k = side_ref[0, 0, :, lo : lo + fold_width].astype(
                    jnp.float32
                )
                v = side_ref[0, 1, :, lo : lo + fold_width].astype(
                    jnp.float32
                )
                flash_update(nf, k, v, smask)
        for nf in range(num_fold):
            denom = jnp.maximum(l_scr[nf, :, 0:1], 1e-30)
            out_ref[0, 0, nf] = (acc_scr[nf] / denom).astype(out_ref.dtype)


def _pow2_floor(x: int) -> int:
    return 1 << (max(x, 1).bit_length() - 1)


def _state_bytes(hkv: int, g: int, mq: int, f: int, d: int) -> int:
    """f32 flash state for one grid step: acc [NF, ROWS, F*D] plus
    m/l [NF, ROWS, 128] each — NF*ROWS = hkv*g*mq regardless of F."""
    return hkv * g * mq * (f * d + 2 * _LANES) * 4


def _fold_align(hkv: int, d: int, hd_pad: int) -> int:
    """Smallest legal fold factor: F*D must be a 128-lane multiple (so
    the in-kernel lane slice is tile-aligned).  Returns hkv (single
    group over the whole padded width) when alignment inside the head
    count is impossible."""
    if (hkv * d) % _LANES or hd_pad != hkv * d:
        return hkv
    f = 1
    while (f * d) % _LANES:
        f *= 2
    return f if hkv % f == 0 else hkv


def _pick_fold(hkv: int, d: int, hd_pad: int, g: int, mq_blk: int):
    """Fold factor F (heads per matmul), fold width (lanes), NF groups.

    Constraints: F divides hkv; F*D is a multiple of 128 lanes; the
    whole f32 flash state stays under _STATE_BYTES.  When hkv*D itself
    is not 128-aligned the whole (padded) width is one fold group.
    """
    f = _fold_align(hkv, d, hd_pad)
    if f == hkv:
        return hkv, hd_pad, 1
    while (
        f * g * mq_blk < _ROWS_TARGET
        and hkv % (2 * f) == 0
        and _state_bytes(hkv, g, mq_blk, 2 * f, d) <= _STATE_BYTES
    ):
        f *= 2
    return f, f * d, hkv // f


def _vmem_limit_bytes(
    *,
    blk: int,
    hd_pad: int,
    kv_itemsize: int,
    nf: int,
    rows: int,
    fd: int,
    q_itemsize: int,
    k_side: int,
    side_itemsize: int,
    hkv: int,
    has_quant: bool,
) -> int:
    """The limit the call states, from the same quantities that size
    its tiles: the rotating KV buffers (_NBUF slots of a K+V block), the
    f32 flash state (acc + m + l), the pipelined (double-buffered) q,
    out, side and scale blocks, and one fold's f32 temporaries (K and V
    tiles, the score/probability chain, the PV product)."""
    f32 = 4
    kv_bufs = _NBUF * 2 * blk * hd_pad * kv_itemsize
    state = nf * rows * (fd + 2 * _LANES) * f32
    q_out = 2 * 2 * nf * rows * fd * q_itemsize
    side = 2 * 2 * k_side * hd_pad * side_itemsize
    scales = 2 * 2 * max(hkv, 8) * blk * f32 if has_quant else 0
    cols = max(blk, k_side)
    temps = f32 * (2 * cols * fd + 6 * rows * cols + 3 * rows * fd)
    return kv_bufs + state + q_out + side + scales + temps + _VMEM_HEADROOM


def paged_attention(
    q: jax.Array,  # [T, Hq, D] flat
    kv_pages: jax.Array,  # [2, P, page, HD]
    metadata: AttentionMetadata,
    *,
    scale: float,
    soft_cap: float | None = None,
    num_kv_heads: int | None = None,
    max_q: int = 1,
    side_kv: jax.Array | None = None,  # [S, 2, K, HD] staged decode rows
    side_len: jax.Array | None = None,  # [1] int32: valid side columns
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for paged_attention_reference (same contract), running the
    flash kernel.  `max_q` is the static per-sequence query bound for this
    step (the runner's padded max chunk length).

    ``side_kv``/``side_len``: staged decode writes — the fused decode
    scan keeps each micro-step's K/V rows in a dense per-sequence side
    buffer instead of scattering them into the paged pool every step
    (the pool is flushed once per dispatch).  Row j of a sequence's side
    buffer holds position ``metadata.seq_lens[s] + j`` (seq_lens is the
    POOL-resident length when staging); columns ``>= side_len`` are not
    yet written and are masked.

    An int8 pool arrives as a ``(data, per-head scales)`` tuple
    (ops/attention.py kv_scales_shape); the kernel DMAs the tiny scale
    slabs alongside the data pages and folds the dequant factors into
    the score/probability matrices (the side buffer stays in model
    dtype — only pool history is quantized)."""
    t, hq, d = q.shape
    has_quant = isinstance(kv_pages, tuple)
    kv_scales = None
    if has_quant:
        kv_pages, kv_scales = kv_pages
    _, p_total, page_size, hd_pad = kv_pages.shape
    s, max_pages = metadata.block_tables.shape
    hkv = num_kv_heads if num_kv_heads is not None else hq
    g = hq // hkv

    # maxq padded so a q block always has >= 8 rows (f32 sublane tile).
    maxq = next_power_of_2(max_q)
    while maxq * g * hkv < 8:
        maxq *= 2

    # Split maxq into q blocks whose full f32 flash state (acc + m + l,
    # at the alignment-minimum fold factor) fits the budget, then pick
    # the head fold factor.
    f_min = _fold_align(hkv, d, hd_pad)
    mq_blk = maxq
    while (
        _state_bytes(hkv, g, mq_blk, f_min, d) > _STATE_BYTES and mq_blk > 1
    ):
        mq_blk //= 2
    f, fd, nf = _pick_fold(hkv, d, hd_pad, g, mq_blk)
    while f * g * mq_blk < 8:  # tiny-model corner: widen the q block
        mq_blk *= 2
        maxq = max(maxq, mq_blk)
    num_qb = maxq // mq_blk
    rows = f * g * mq_blk

    # ---- group flat queries per sequence ----
    # Padding tokens carry q_seq_ids == S (one past the end); route their
    # scatter to an out-of-bounds column so it is DROPPED instead of
    # clobbering a real row (scatter drops OOB updates under jit).
    valid = metadata.q_seq_ids < s
    seq_idx = jnp.minimum(metadata.q_seq_ids, s - 1)
    tok_in_chunk = metadata.q_positions - metadata.chunk_starts[seq_idx]
    col = jnp.where(valid, tok_in_chunk, maxq)
    q_grouped = jnp.zeros((s, maxq, hq, d), q.dtype)
    q_grouped = q_grouped.at[seq_idx, col].set(q, mode="drop")

    # ---- block-diagonal fold:  [S, NQB, NF, ROWS, FD] ----
    q7 = q_grouped.reshape(s, num_qb, mq_blk, nf, f, g, d)
    q7 = q7.transpose(0, 1, 3, 4, 5, 2, 6)  # [S,NQB,NF,F,G,mq,D]
    eye = jnp.eye(f, dtype=q.dtype)
    q_bd = (
        q7[:, :, :, :, :, :, None, :]
        * eye[None, None, None, :, None, None, :, None]
    ).reshape(s, num_qb, nf, rows, f * d)
    if fd > f * d:  # padded single-group case: zero lanes at the end
        q_bd = jnp.pad(
            q_bd, [(0, 0), (0, 0), (0, 0), (0, 0), (0, fd - f * d)]
        )

    # ---- kv blocking: size blocks to the VMEM budget ----
    kv_bytes_per_token = 2 * hd_pad * jnp.dtype(kv_pages.dtype).itemsize
    if has_quant:
        kv_bytes_per_token += 2 * hkv * 4  # f32 scale rows
    blk_tokens = max(_KV_BUF_BYTES // kv_bytes_per_token, page_size)
    blk_tokens = min(_pow2_floor(blk_tokens), max_pages * page_size)
    if has_quant and blk_tokens < 128 and blk_tokens < max_pages * page_size:
        # The scale block's lane dim is BLK: it must be a 128 multiple
        # (or cover the whole context) for Mosaic's tiling.
        blk_tokens = min(128, max_pages * page_size)
    pages_per_blk = max(blk_tokens // page_size, 1)
    num_kvb = cdiv(max_pages, pages_per_blk)
    blk = pages_per_blk * page_size
    if max_pages % pages_per_blk:
        # Pad the table so block_dma never reads a page id out of bounds
        # (padding pages are id 0 — a real page, masked out of scores).
        pad = pages_per_blk - max_pages % pages_per_blk
        block_tables = jnp.pad(metadata.block_tables, ((0, 0), (0, pad)))
    else:
        block_tables = metadata.block_tables

    grid = (s, num_qb, num_kvb)
    has_side = side_kv is not None
    kernel = functools.partial(
        _kernel,
        scale=scale,
        soft_cap=soft_cap,
        page_size=page_size,
        pages_per_blk=pages_per_blk,
        group_size=g,
        num_fold=nf,
        fold_width=fd,
        mq_blk=mq_blk,
        has_side=has_side,
        has_quant=has_quant,
    )
    in_specs = [
        pl.BlockSpec(
            (1, 1, nf, rows, fd),
            # Scalar-prefetch refs ride along after grid indices.
            lambda s_, qb_, b_, *refs: (s_, qb_, 0, 0, 0),
        ),
    ]
    scalars = [block_tables, metadata.seq_lens, metadata.chunk_starts]
    inputs = [q_bd]
    if has_side:
        scalars.append(side_len.astype(jnp.int32))
        k_side = side_kv.shape[2]
        in_specs.append(
            pl.BlockSpec(
                (1, 2, k_side, hd_pad),
                lambda s_, qb_, b_, *refs: (s_, 0, 0, 0),
            )
        )
        inputs.append(side_kv)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    inputs.append(kv_pages)
    if has_quant:
        # Per-sequence transposed scale matrix [S, 2, Hkv, CTX_PAD],
        # gathered in XLA (loop-invariant per fused dispatch, so XLA
        # hoists it out of the decode scan).  The kernel consumes
        # lane-aligned [1, 2, Hkv, BLK] blocks via the regular
        # pipeline — a manual [page, Hkv] DMA slab would violate
        # Mosaic's 128-lane slice alignment.  Known cost: prefill/mixed
        # steps pay the gather each step, sized by the pages_pad bucket
        # (Hkv/ (4*D) of the data bytes — ~3% at D=64/f32 scales);
        # acceptable next to the chunk's matmul work, and shrinkable
        # with bf16 scales if it ever shows up in a profile.
        ctx_pad = num_kvb * blk
        sc = kv_scales[:, block_tables]  # [2, S, PAD_PAGES, page, Hkv]
        sc = sc.transpose(1, 0, 4, 2, 3).reshape(s, 2, hkv, ctx_pad)
        in_specs.append(
            pl.BlockSpec(
                (1, 2, hkv, blk),
                lambda s_, qb_, b_, *refs: (s_, 0, 0, b_),
            )
        )
        inputs.append(sc)
    scratch = [pltpu.VMEM((_NBUF, 2, blk, hd_pad), kv_pages.dtype)]
    scratch += [
        pltpu.VMEM((nf, rows, _LANES), jnp.float32),
        pltpu.VMEM((nf, rows, _LANES), jnp.float32),
        pltpu.VMEM((nf, rows, fd), jnp.float32),
        pltpu.SemaphoreType.DMA((_NBUF,)),
        pltpu.SMEM((2,), jnp.int32),
    ]
    out_bd = pl.pallas_call(
        kernel,
        name="vdt_paged_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, 1, nf, rows, fd),
                lambda s_, qb_, b_, *refs: (s_, qb_, 0, 0, 0),
            ),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((s, num_qb, nf, rows, fd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(
                blk=blk,
                hd_pad=hd_pad,
                kv_itemsize=jnp.dtype(kv_pages.dtype).itemsize,
                nf=nf,
                rows=rows,
                fd=fd,
                q_itemsize=jnp.dtype(q.dtype).itemsize,
                k_side=side_kv.shape[2] if has_side else 0,
                side_itemsize=(
                    jnp.dtype(side_kv.dtype).itemsize if has_side else 0
                ),
                hkv=hkv,
                has_quant=has_quant,
            )
        ),
        interpret=interpret,
    )(*scalars, *inputs)

    # ---- extract the diagonal blocks back to the flat layout ----
    ob = out_bd[..., : f * d].reshape(s, num_qb, nf, f, g, mq_blk, f, d)
    # diagonal over (F_row, F_lane): row block i holds head i's output
    # in lane block i; everything off-diagonal is cross-head garbage.
    out7 = jnp.einsum("abcfgmfd->abcfgmd", ob)  # [S,NQB,NF,F,G,mq,D]
    out = out7.transpose(0, 1, 5, 2, 3, 4, 6).reshape(s, maxq, hq, d)
    return out[seq_idx, jnp.clip(tok_in_chunk, 0, maxq - 1)]


paged_attention.needs_max_q = True


def paged_attention_cpu(*args, **kwargs):
    """Interpret-mode entry for CPU tests."""
    return paged_attention(*args, interpret=True, **kwargs)


paged_attention_cpu.needs_max_q = True
