"""Pallas ragged paged-attention kernel — the TPU hot path.

The TPU-native replacement for the CUDA PagedAttention/FlashAttention
kernels the reference inherits from the vLLM image (SURVEY.md §2.2 and
BASELINE.json north_star: "PagedAttention is a Pallas kernel").  One
kernel serves decode (1 query token/seq), chunked prefill (many) and
the mixed steps that hold both; attention runs flash-style (online
softmax) over each sequence's paged KV.

**The grid is (query tile, kv block), and it walks a list.**  A query
tile is ``mq_blk`` consecutive query tokens of ONE sequence.  The
wrapper builds the step's list of tiles from the step's own metadata
(which sequence, first token within its chunk, kv blocks to walk) and
hands it to the kernel by scalar prefetch; its static length comes from
the shapes already in the program's key (``t // mq_blk + s``, never
more than the dense grid), tiles past the count in use are skipped with
``pl.when`` and repeat the last live tile's block indices, so a skipped
step moves no bytes and computes nothing.  The query rows are gathered
from the flat ``[T, Hq, D]`` batch into ``[tiles, mq_blk, Hq, D]`` and
the outputs gathered back per token: no array of sequences × longest
chunk exists anywhere (before PR 30 the grid and the staging were that
dense array: a mixed step of 31 decoding sequences and one 2,048-token
chunk computed ~1,500 tile pairs for ~110 that held a query and staged
537 MB for 16 MB of queries).

Two tile geometries, one kernel body, chosen from what the call can
read of itself:

- ``max_q == 1`` (the fused decode scan's call, a decode-only step):
  one tile a sequence in the **decode geometry** — heads folded until a
  softmax chain has ``_ROWS_TARGET`` rows.  The list is the dense grid
  (tile i = sequence i), a row without pool history walks no block.
- ``max_q > 1`` (prefill / mixed / verify steps): sequences' chunks are
  cut into ``mq_blk``-token tiles (the largest whose f32 flash state
  fits ``_STATE_BYTES``: 128 at Mixtral's widths).  Where such a tile
  is wide (``mq_blk >= _SPLIT_SINGLES_MIN_MQ``), sequences with a
  SINGLE query token leave that list for a second ``pallas_call`` of
  the layer in the decode geometry over the compacted list of those
  sequences, so a decoding sequence riding in a mixed step costs what
  it costs in the scan, not a 128-row tile.  A verify step (every
  sequence a few tokens) stays one call whose list is the dense grid.

The list is computed in XLA by comparison and a cumulative sum — no
``while`` (``searchsorted`` is one): the benchmark tells a fused scan
from a single step by that op.  Contract on the flat batch: a
sequence's query tokens are contiguous and in position order.

Inside a tile:

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
  HD.
- **Globally rotating triple buffer.**  Buffer index = (number of
  active blocks completed so far) % 3, tracked in SMEM — never resets
  per tile, so the prefetch of the next live (tile, kv block) can never
  target the buffer the current (or previous) step reads.  The
  lookahead is one step: the same tile's next block, else block 0 of
  the list's next tile; where it misses (a tile of an empty sequence in
  between) ``_bootstrap_dma`` issues the copy and eats the stall —
  correctness never depends on the lookahead.
- Causal skip: kv blocks entirely above the tile's last position are
  skipped (no DMA, no compute) — half the work on prefill.

Numerics: scores/softmax/accumulation in float32 regardless of cache
dtype; output cast back to q.dtype.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

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
# A lone query token leaves the chunk tiles for the decode geometry when
# a chunk tile holds at least this many tokens: below it the token's
# share of a tile costs less than a second call's walk over its grid
# (the spec-verify windows, a few tokens wide, stay one call).
_SPLIT_SINGLES_MIN_MQ = 16
# Added to the byte model below for Mosaic's own scratch and whatever
# the model rounds away (sublane/lane padding of the small blocks).
_VMEM_HEADROOM = 8 * 1024 * 1024


def _kernel(
    # scalar prefetch (+ side_len_ref when has_side)
    tile_seq_ref,  # [NT] int32 (SMEM): sequence of each query tile
    tile_q0_ref,  # [NT] int32: the tile's first token within its chunk
    tile_nb_ref,  # [NT] int32: kv blocks the tile walks (0: skipped)
    n_live_ref,  # [1] int32: tiles in use (the rest are skipped)
    block_tables_ref,  # [S, max_pages] int32
    seq_lens_ref,  # [S] int32
    chunk_starts_ref,  # [S] int32
    # then inputs (q_ref [1,NF,ROWS,FD]; side_ref [1,2,K,HD] when
    # has_side; kv_pages_ref [2,P,page,HD] ANY; scale_blk_ref
    # [1,2,Hkv,BLK] f32 when has_quant — a regular pipelined block of
    # the per-sequence TRANSPOSED scale matrix the wrapper gathers in
    # XLA, so the kernel never DMAs sub-128-lane scale slabs), the out
    # block [1,NF,ROWS,FD], and scratch (kv_vmem [NBUF,2,BLK,HD],
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
    tile = pl.program_id(0)
    kvb = pl.program_id(1)
    num_tiles = pl.num_programs(0)
    num_kvb = pl.num_programs(1)
    blk = pages_per_blk * page_size
    # Most grid steps are skipped ones (the grid is sized for the
    # bucket, the lists for the step): what a skipped step pays is the
    # scalar work out here, so it is two SMEM reads and a compare; the
    # kv-block counts come ready from the wrapper (``tile_kv_blocks``).
    # A tile past the live count does nothing at all: its block indices
    # are the last live tile's (see the index maps), so neither its
    # state reset nor its output write may run.
    in_list = tile < n_live_ref[0]
    s = tile_seq_ref[tile]
    nb = tile_nb_ref[tile]
    active = kvb < nb

    @pl.when((tile == 0) & (kvb == 0))
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

    @pl.when(in_list & (kvb == 0))
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

    # Prefetch the NEXT live (tile, kv block) into the next rotation
    # slot while this one computes: the same tile's next block, else
    # block 0 of the next tile of the list.  A tile of an empty sequence
    # between live ones defeats the one-step lookahead; the bootstrap
    # above recovers (correctness never depends on the lookahead).
    next_in_tile = kvb + 1 < nb
    next_tile = jnp.minimum(tile + 1, num_tiles - 1)
    has_next = next_in_tile | (
        (tile + 1 < num_tiles) & (tile_nb_ref[next_tile] > 0)
    )
    next_s = jnp.where(next_in_tile, s, tile_seq_ref[next_tile])
    next_kvb = jnp.where(next_in_tile, kvb + 1, 0)

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
        q_start = chunk_starts_ref[s] + tile_q0_ref[tile]
        q_pos = q_start + row_ids % mq_blk
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
        qn = q_ref[0, nf].astype(jnp.float32)  # [ROWS, FD]
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
        seq_len = seq_lens_ref[s]
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

    @pl.when(in_list & (kvb == num_kvb - 1))
    def _finalize():
        if has_side:
            # Staged decode writes: this dispatch's K/V rows live in the
            # dense side buffer (positions seq_len + j), not the pool.
            # Fold them into the same online-softmax state before the
            # division.  seq_len here is the POOL length (the runner
            # passes base lengths when staging).
            n_side = side_len_ref[0]
            seq_len = seq_lens_ref[s]
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
            out_ref[0, nf] = (acc_scr[nf] / denom).astype(out_ref.dtype)


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


class _Geometry(NamedTuple):
    """A query tile: mq_blk tokens of one sequence; F heads folded per
    matmul into FD lanes, NF fold groups of ``rows`` softmax rows; maxq
    is the (padded) longest chunk the geometry was sized for."""

    mq_blk: int
    f: int
    fd: int
    nf: int
    rows: int
    maxq: int


def _tile_geometry(
    max_q: int, hq: int, hkv: int, d: int, hd_pad: int
) -> _Geometry:
    """The query tile for chunks of up to ``max_q`` tokens: as many
    tokens as keep the whole f32 flash state (acc + m + l, at the
    alignment-minimum fold factor) inside its budget, then the head fold
    factor.  For ``max_q = 1`` that is the decode geometry: heads folded
    until a softmax chain has _ROWS_TARGET rows."""
    g = hq // hkv
    # maxq padded so a tile always has >= 8 rows (f32 sublane tile).
    maxq = next_power_of_2(max_q)
    while maxq * g * hkv < 8:
        maxq *= 2
    f_min = _fold_align(hkv, d, hd_pad)
    mq_blk = maxq
    while (
        _state_bytes(hkv, g, mq_blk, f_min, d) > _STATE_BYTES and mq_blk > 1
    ):
        mq_blk //= 2
    f, fd, nf = _pick_fold(hkv, d, hd_pad, g, mq_blk)
    while f * g * mq_blk < 8:  # tiny-model corner: widen the tile
        mq_blk *= 2
        maxq = max(maxq, mq_blk)
    return _Geometry(mq_blk, f, fd, nf, f * g * mq_blk, maxq)


def _splits_singles(mq_blk: int) -> bool:
    """Whether single-token sequences leave the chunk tiles for a call
    of their own in the decode geometry."""
    return mq_blk >= _SPLIT_SINGLES_MIN_MQ


def num_query_tiles(t: int, s: int, mq_blk: int, maxq: int) -> int:
    """Static length of a step's chunk-tile list: ``t`` tokens in at
    most ``s`` chunks fill at most t/mq_blk whole tiles and leave one
    partial tile a chunk, and never more than the dense grid."""
    return max(min(t // mq_blk + s, s * (maxq // mq_blk)), 1)


def tile_list(tiles_per_seq: jax.Array, num_tiles: int, mq_blk: int):
    """The ragged list of query tiles, sequence by sequence — (sequence
    of each tile [NT], first token of the tile within its chunk [NT],
    tiles in use [1]) — and the first tile of each sequence [S].
    Entries past the count in use repeat the last live tile, so they
    change no block index.  Counted by comparison and a cumulative sum:
    no XLA ``while`` may enter a single-step program (the benchmark
    tells a scan from a single step by that op; ``searchsorted`` is
    one)."""
    s = tiles_per_seq.shape[0]
    tile_end = jnp.cumsum(tiles_per_seq.astype(jnp.int32))
    first = tile_end - tiles_per_seq
    used = tile_end[-1:]
    idx = jnp.arange(num_tiles, dtype=jnp.int32)
    idx = jnp.minimum(idx, jnp.maximum(used - 1, 0))
    # A tile's sequence: how many sequences end at or before it.
    seq = jnp.minimum(
        (idx[:, None] >= tile_end[None, :]).sum(axis=1, dtype=jnp.int32),
        s - 1,
    )
    q0 = (idx - first[seq]) * mq_blk
    return (seq, q0, used), first


def tile_kv_blocks(
    tile_seq, tile_q0, n_live, seq_lens, chunk_starts, mq_blk: int, blk: int
) -> jax.Array:
    """kv blocks each query tile walks [NT]: up to the causal bound of
    its last row, cut at the sequence's length; 0 for a tile past the
    live count or of a sequence with no pool history."""
    seq_len = seq_lens[tile_seq]
    span = jnp.minimum(seq_len, chunk_starts[tile_seq] + tile_q0 + mq_blk)
    live = (jnp.arange(tile_seq.shape[0]) < n_live[0]) & (seq_len > 0)
    return jnp.where(live, (span + blk - 1) // blk, 0).astype(jnp.int32)


def query_tile_counts(
    q_lens, *, max_q: int, num_q_heads: int, num_kv_heads: int, head_dim: int
) -> tuple[int, int]:
    """(tiles that hold a query, query rows those tiles compute) of one
    step whose sequences bring ``q_lens`` query tokens: host-side twin
    of the wrapper's tile lists, for the ``runner.dispatch`` span."""
    hd = num_kv_heads * head_dim
    mq_a = _tile_geometry(1, num_q_heads, num_kv_heads, head_dim, hd).mq_blk
    mq_b = _tile_geometry(
        max_q, num_q_heads, num_kv_heads, head_dim, hd
    ).mq_blk
    if max_q == 1:
        n = sum(1 for n in q_lens if n > 0)
        return n, n * mq_a
    split = _splits_singles(mq_b)
    singles = sum(1 for n in q_lens if n == 1) if split else 0
    chunks = sum(cdiv(n, mq_b) for n in q_lens if n > (1 if split else 0))
    return singles + chunks, singles * mq_a + chunks * mq_b


def step_counts_for(model):
    """What a step of ``paged_attention`` counts (``StepShape`` ->
    attributes, increments), bound to ``model``'s widths: a single
    step's ``q_tiles`` / ``q_rows`` on one head shard, beside the
    span's ``tokens``; nothing of a scan or a verify step."""
    heads, kv_heads, head_dim = (
        model.num_heads, model.num_kv_heads, model.head_dim
    )

    def step_counts(shape):
        if shape.kind != "prefill":
            return {}, {}
        tiles, rows = query_tile_counts(
            shape.num_new,
            max_q=shape.max_q_pad,
            num_q_heads=heads // shape.head_shards,
            num_kv_heads=kv_heads // shape.head_shards,
            head_dim=head_dim,
        )
        return {"q_tiles": tiles, "q_rows": rows}, {}

    return step_counts


def _kv_blocking(kv_pages, hkv: int, has_quant: bool, max_pages: int):
    """(pages per kv block, kv blocks, tokens per block): blocks sized
    to the per-slot VMEM budget."""
    _, _, page_size, hd_pad = kv_pages.shape
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
    return (
        pages_per_blk,
        cdiv(max_pages, pages_per_blk),
        pages_per_blk * page_size,
    )


def _attend_tiles(
    q_tiles: jax.Array,  # [NT, mq_blk, Hq, D]: the tiles' query rows
    tiles,  # (tile_seq [NT], tile_q0 [NT], n_live [1])
    geometry: _Geometry,
    kv_pages: jax.Array,
    scale_mat: jax.Array | None,  # [S, 2, Hkv, CTX_PAD] f32 (int8 pool)
    block_tables: jax.Array,  # padded to whole kv blocks
    metadata: AttentionMetadata,
    blocking,  # _kv_blocking's
    *,
    scale: float,
    soft_cap: float | None,
    hkv: int,
    side_kv: jax.Array | None,
    side_len: jax.Array | None,
    interpret: bool,
) -> jax.Array:
    """One ``pallas_call`` over a list of query tiles of one geometry;
    returns the tiles' outputs [NT, mq_blk, Hq, D]."""
    num_tiles, _, hq, d = q_tiles.shape
    mq_blk, f, fd, nf, rows, _ = geometry
    pages_per_blk, num_kvb, blk = blocking
    _, _, page_size, hd_pad = kv_pages.shape
    g = hq // hkv
    has_side = side_kv is not None
    has_quant = scale_mat is not None

    # ---- block-diagonal fold:  [NT, NF, ROWS, FD] ----
    q6 = q_tiles.reshape(num_tiles, mq_blk, nf, f, g, d)
    q6 = q6.transpose(0, 2, 3, 4, 1, 5)  # [NT,NF,F,G,mq,D]
    if f > 1:
        eye = jnp.eye(f, dtype=q_tiles.dtype)
        q6 = (
            q6[:, :, :, :, :, None, :]
            * eye[None, None, :, None, None, :, None]
        )
    q_bd = q6.reshape(num_tiles, nf, rows, f * d)
    if fd > f * d:  # padded single-group case: zero lanes at the end
        q_bd = jnp.pad(q_bd, [(0, 0), (0, 0), (0, 0), (0, fd - f * d)])

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

    # Index maps (scalar-prefetch refs ride along after grid indices).
    # A tile past the live count keeps the last live tile's block
    # indices (the list repeats its sequence there), and the scale
    # block stops at the tile's last kv block: a step the kernel skips
    # moves no bytes.
    def live_tile(tile, refs):
        return jnp.minimum(tile, jnp.maximum(refs[3][0] - 1, 0))

    def tile_map(tile, b_, *refs):
        return (live_tile(tile, refs), 0, 0, 0)

    def seq_map(tile, b_, *refs):
        return (refs[0][tile], 0, 0, 0)

    def scale_map(tile, b_, *refs):
        last = jnp.maximum(refs[2][live_tile(tile, refs)] - 1, 0)
        return (refs[0][tile], 0, 0, jnp.minimum(b_, last))

    tile_seq, tile_q0, n_live = tiles
    scalars = [
        tile_seq,
        tile_q0,
        tile_kv_blocks(
            tile_seq, tile_q0, n_live, metadata.seq_lens,
            metadata.chunk_starts, mq_blk, blk,
        ),
        n_live,
        block_tables,
        metadata.seq_lens,
        metadata.chunk_starts,
    ]
    in_specs = [pl.BlockSpec((1, nf, rows, fd), tile_map)]
    inputs = [q_bd]
    k_side = 0
    if has_side:
        scalars.append(side_len.astype(jnp.int32))
        k_side = side_kv.shape[2]
        in_specs.append(pl.BlockSpec((1, 2, k_side, hd_pad), seq_map))
        inputs.append(side_kv)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    inputs.append(kv_pages)
    if has_quant:
        in_specs.append(pl.BlockSpec((1, 2, hkv, blk), scale_map))
        inputs.append(scale_mat)
    scratch = [
        pltpu.VMEM((_NBUF, 2, blk, hd_pad), kv_pages.dtype),
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
            grid=(num_tiles, num_kvb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, nf, rows, fd), tile_map),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (num_tiles, nf, rows, fd), q_tiles.dtype
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(
                blk=blk,
                hd_pad=hd_pad,
                kv_itemsize=jnp.dtype(kv_pages.dtype).itemsize,
                nf=nf,
                rows=rows,
                fd=fd,
                q_itemsize=jnp.dtype(q_tiles.dtype).itemsize,
                k_side=k_side,
                side_itemsize=(
                    jnp.dtype(side_kv.dtype).itemsize if has_side else 0
                ),
                hkv=hkv,
                has_quant=has_quant,
            )
        ),
        interpret=interpret,
    )(*scalars, *inputs)

    # ---- extract the diagonal blocks back to token rows ----
    ob = out_bd[..., : f * d].reshape(num_tiles, nf, f, g, mq_blk, f, d)
    if f > 1:
        # diagonal over (F_row, F_lane): row block i holds head i's
        # output in lane block i; off-diagonal is cross-head garbage.
        ob = jnp.einsum("acfgmfd->acfgmd", ob)
    else:
        ob = ob.reshape(num_tiles, nf, f, g, mq_blk, d)
    return ob.transpose(0, 4, 1, 2, 3, 5).reshape(num_tiles, mq_blk, hq, d)


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

    The query tokens of a sequence are contiguous in the flat batch and
    in position order: token ``first + j`` of sequence ``s`` sits at
    position ``chunk_starts[s] + j`` (what the runner packs).

    ``side_kv``/``side_len``: staged decode writes — the fused decode
    scan keeps each micro-step's K/V rows in a dense per-sequence side
    buffer instead of scattering them into the paged pool every step
    (the pool is flushed once per dispatch).  Row j of a sequence's side
    buffer holds position ``metadata.seq_lens[s] + j`` (seq_lens is the
    POOL-resident length when staging); columns ``>= side_len`` are not
    yet written and are masked.

    An int8 pool arrives as a ``(data, per-head scales)`` tuple
    (ops/attention.py kv_scales_shape); the kernel takes the scales as
    pipelined blocks of a per-sequence transposed matrix and folds the
    dequant factors into the score/probability matrices (the side
    buffer stays in model dtype — only pool history is quantized)."""
    t, hq, d = q.shape
    has_quant = isinstance(kv_pages, tuple)
    kv_scales = None
    if has_quant:
        kv_pages, kv_scales = kv_pages
    _, _, page_size, hd_pad = kv_pages.shape
    s, max_pages = metadata.block_tables.shape
    hkv = num_kv_heads if num_kv_heads is not None else hq

    blocking = _kv_blocking(kv_pages, hkv, has_quant, max_pages)
    pages_per_blk, num_kvb, blk = blocking
    block_tables = metadata.block_tables
    if max_pages % pages_per_blk:
        # Pad the table so block_dma never reads a page id out of bounds
        # (padding pages are id 0 — a real page, masked out of scores).
        pad = pages_per_blk - max_pages % pages_per_blk
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    scale_mat = None
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
        sc = kv_scales[:, block_tables]  # [2, S, PAD_PAGES, page, Hkv]
        scale_mat = sc.transpose(1, 0, 4, 2, 3).reshape(
            s, 2, hkv, num_kvb * blk
        )
    attend = functools.partial(
        _attend_tiles,
        kv_pages=kv_pages,
        scale_mat=scale_mat,
        block_tables=block_tables,
        metadata=metadata,
        blocking=blocking,
        scale=scale,
        soft_cap=soft_cap,
        hkv=hkv,
        side_kv=side_kv,
        side_len=side_len,
        interpret=interpret,
    )

    # ---- where each sequence's queries are in the flat batch ----
    # Padding tokens carry q_seq_ids == S (one past the end) and match
    # no sequence.  One fused [S, T] comparison: no scatter, no loop.
    tok = jnp.arange(t, dtype=jnp.int32)
    match = metadata.q_seq_ids[None, :] == jnp.arange(s, dtype=jnp.int32)[
        :, None
    ]
    q_len = match.sum(axis=1, dtype=jnp.int32)
    first_tok = jnp.min(jnp.where(match, tok[None, :], t), axis=1)
    seq_idx = jnp.minimum(metadata.q_seq_ids, s - 1)
    tok_in_chunk = tok - first_tok[seq_idx]
    # Padding tokens get zeros, not a gathered row: a tile past the
    # live count is never written, and what such memory holds must not
    # reach the padding rows' K/V (they land in page 0, which masked
    # columns of every table still multiply by p = 0).
    is_token = (metadata.q_seq_ids < s)[:, None, None]

    def attend_list(tiles, geometry):
        """The outputs [NT, mq_blk, Hq, D] of a list's tiles, their
        query rows gathered from the flat batch."""
        tile_seq, tile_q0, _ = tiles
        rows = (first_tok[tile_seq] + tile_q0)[:, None] + jnp.arange(
            geometry.mq_blk, dtype=jnp.int32
        )
        # Rows past a chunk's end (and whole dead tiles) read whatever
        # token is there: finite, computed, never gathered back.
        return attend(q[jnp.clip(rows, 0, t - 1)], tiles, geometry)

    decode = _tile_geometry(1, hq, hkv, d, hd_pad)
    if max_q == 1:
        # Decode (the fused scan's call, a decode-only step): one tile a
        # sequence, in the decode geometry; the list is the dense grid,
        # and a row without pool history is skipped by its length.
        seqs = jnp.arange(s, dtype=jnp.int32)
        tiles = (seqs, jnp.zeros(s, jnp.int32), jnp.full((1,), s, jnp.int32))
        out = attend_list(tiles, decode)[seq_idx, 0]
        return jnp.where(is_token, out, 0)

    # Chunks: tiles of mq_blk tokens where real query tokens are.
    assert side_kv is None, "staged decode rows come with max_q = 1"
    chunk = _tile_geometry(max_q, hq, hkv, d, hd_pad)
    mq_blk = chunk.mq_blk
    split = _splits_singles(mq_blk)
    in_chunks = q_len > 1 if split else q_len > 0
    tiles, first_tile = tile_list(
        jnp.where(in_chunks, (q_len + mq_blk - 1) // mq_blk, 0),
        num_query_tiles(t, s, mq_blk, chunk.maxq),
        mq_blk,
    )
    out_rows = attend_list(tiles, chunk).reshape(-1, hq, d)
    row = first_tile[seq_idx] * mq_blk + tok_in_chunk
    out = out_rows[jnp.clip(row, 0, out_rows.shape[0] - 1)]
    if split:
        # Single-token sequences: a call of their own in the decode
        # geometry, over the compacted list of those sequences, so none
        # of them occupies (and computes) a whole mq_blk-row tile.
        single = q_len == 1
        tiles, first_tile = tile_list(
            single.astype(jnp.int32), s, decode.mq_blk
        )
        out_1 = attend_list(tiles, decode)[first_tile[seq_idx], 0]
        out = jnp.where(single[seq_idx][:, None, None], out_1, out)
    return jnp.where(is_token, out, 0)


paged_attention.needs_max_q = True
paged_attention.step_counts_for = step_counts_for


def paged_attention_cpu(*args, **kwargs):
    """Interpret-mode entry for CPU tests."""
    return paged_attention(*args, interpret=True, **kwargs)


paged_attention_cpu.needs_max_q = True
paged_attention_cpu.step_counts_for = step_counts_for
