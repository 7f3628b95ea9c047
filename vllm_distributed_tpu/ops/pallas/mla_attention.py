"""Pallas latent (absorbed MLA) attention over the one-plane paged pool.

The pool is ``[1, P, page, WP]``: one row ``[c, k_rope]`` a token
(``W = kv_lora_rank + qk_rope_head_dim`` live lanes, padded to ``WP``,
whole 128-lane tiles: Mosaic moves no narrower slice of HBM), key and
value at once — the value is the row's first ``value_width`` lanes.
The queries arrive absorbed (``[q_nope · W_uk, q_rope]``, ``W`` lanes a
head), so every head scores the same row: a page is fetched ONCE for all heads, and a
query tile's softmax chain has ``heads × tokens`` rows over it.

The ragged list of query tiles, the globally rotating triple buffer
and the one-fetch lookahead are ops/pallas/paged_attention.py's (its
docstring has the reasons; the list code is imported from there, not
copied): a tile is ``mq_blk`` consecutive query tokens of one sequence;
``max_q == 1`` (the fused decode scan, a decode-only step) is one tile a
sequence; in chunked steps single-token sequences leave the chunk tiles
for a second call in the decode geometry.  No XLA ``while`` is made
here either.

**The grid is the tile list alone; the walk over a tile's cached rows
is a loop inside the kernel** (PR 39).  A grid step is one query tile;
in it a ``fori_loop`` runs over the tile's live kv sub-blocks, as many
as ``tile_kv_blocks`` counts for it (a dynamic trip count: 0 for a tile
past the list or of an empty sequence), so a sequence costs what its
length costs: DMAs, score columns and loop trips for its live
sub-blocks and nothing for the rest of the pages bucket.  While the
walk was a grid axis, a fine block paid a grid step each (~0.35 us,
worked or skipped) and a coarse one paid dead rows: at the LongCat
cell's lengths (~650 cached tokens a sequence in a 4,096-token bucket)
1,024-token blocks skipped three grid steps in four and fetched 1.9 x
the live rows.  The lookahead is one sub-block (this tile's next, else
the first of the next tile if that walks any); where it misses, the
tile fetches its own first sub-block (``_bootstrap_dma``).  A tile's
last sub-block is fetched and folded in at half width where its rows
end in the first half.  The staged side buffer's columns ride the first
sub-block's softmax update.

What differs: one DMA a page instead of two, no head folding (there is
one key/value row), no int8 form, and the score is two dots — the
latent lanes and the rope lanes — so that neither contracts over a
width that is not a lane-tile multiple.  Operands keep the pool's dtype
on the MXU; scores, softmax state and the output accumulate in
float32.  The staged side buffer of the fused scan is ``[S, 1, K, WP]``.

**Which rows take this kernel.**  The absorbed form pays where one
cached row serves every head of ONE query token (2·(W + value_width)
operations a query–key pair a head: 3.4 × the per-head form's at the
LongCat widths, and a W-wide query and a value_width-wide output a
head through HBM).  A chunked step therefore enters through
``mla_attention_from_heads`` (bound by ``ops/attention.bind_max_q``),
which sends each sequence by what the step's own metadata says of it:
a fresh chunk to the materialised form (ops/pallas/
mla_chunk_attention.py), a one-token row to this kernel's decode
geometry, a chunk with a cached prefix to this kernel's chunk tiles
behind one ``lax.cond``.  ``mla_attention`` itself (every row absorbed)
stays the fused scan's and the decode-only step's call, and the
building block of the other two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu.models.common import (
    SCOPE_ATTN,
    SCOPE_MLA_ABSORB,
)
from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    mla_absorb_queries,
    mla_unabsorb_outputs,
)
from vllm_distributed_tpu.ops.pallas.mla_chunk_attention import (
    mla_chunk_attention,
)
from vllm_distributed_tpu.ops.pallas.paged_attention import (
    _pow2_floor,
    _splits_singles,
    num_query_tiles,
    tile_kv_blocks,
    tile_list,
)
from vllm_distributed_tpu.utils import cdiv, next_power_of_2

_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128
_NBUF = 3
# Per-slot budget of the rotating page buffer, and of the f32 score /
# probability tile [rows, kv sub-block] a loop trip holds.  A latent row
# is 1,280 B: 512 tokens a sub-block.  The walk is a loop in the kernel,
# so the size no longer buys grid steps; it trades dead rows in each
# sequence's last sub-block against what a trip costs beside its
# columns (~0.4 us: the softmax chain, the MXU's fill and drain).  With
# the last sub-block cut to half where it can be, over the LongCat
# cell's lengths: 256 tokens fill 0.90 and the fused scan's call takes
# 172 us, 512 fill 0.85 and 143 us, 1,024 fill 0.73 and 144 us (220 us
# before PR 39; tools/mla_microbench.py --ctx-mix, my chip run, PR 39).
_KV_BUF_BYTES = 640 * 1024
_SCORE_BYTES = 2 * 1024 * 1024
# Budget of the f32 flash state (acc [rows, value_width] + m + l).
_STATE_BYTES = 3 * 1024 * 1024
_VMEM_HEADROOM = 8 * 1024 * 1024


def _kernel(
    # scalar prefetch (+ side_len_ref when has_side)
    tile_seq_ref,  # [NT] int32: sequence of each query tile
    tile_q0_ref,  # [NT] int32: the tile's first token within its chunk
    tile_nb_ref,  # [NT] int32: kv sub-blocks the tile walks (0: none)
    tile_rows_ref,  # [NT] int32: cached rows the tile walks
    n_live_ref,  # [1] int32: tiles in use
    block_tables_ref,  # [S, max_pages] int32
    seq_lens_ref,  # [S] int32
    chunk_starts_ref,  # [S] int32
    # then q_ref [1, ROWS, W] (row r = token r // heads of the tile,
    # head r % heads: the flat batch's own order), side_ref
    # [1, 1, K, WP] when has_side, kv_pages_ref [1, P, page, WP] ANY, the
    # out block [1, ROWS, VW], and scratch: kv_vmem [NBUF, BLK, WP],
    # m/l [ROWS, LANES] f32, acc [ROWS, VW] f32, DMA sems [NBUF], cnt
    # SMEM [2] = [completed sub-blocks, prefetch-pending flag].
    *rest,
    scale: float,
    page_size: int,
    pages_per_blk: int,
    num_heads: int,
    value_width: int,
    width: int,  # W: the row's live lanes
    has_side: bool,
):
    rest = list(rest)
    side_len_ref = rest.pop(0) if has_side else None
    q_ref = rest.pop(0)
    side_ref = rest.pop(0) if has_side else None
    kv_pages_ref = rest.pop(0)
    out_ref, kv_vmem, m_scr, l_scr, acc_scr, sems, cnt = rest
    tile = pl.program_id(0)
    num_tiles = pl.num_programs(0)
    blk = pages_per_blk * page_size
    vw = value_width
    in_list = tile < n_live_ref[0]
    s = tile_seq_ref[tile]
    nb = tile_nb_ref[tile]

    @pl.when(tile == 0)
    def _boot():
        cnt[0] = 0
        cnt[1] = 0

    def is_short(tile_idx, block_idx):
        """Whether the tile's rows end in the first half of this
        sub-block, which is then fetched and folded in at half width
        (``None``: a sub-block of one page has no half)."""
        if pages_per_blk % 2:
            return None
        return tile_rows_ref[tile_idx] - block_idx * blk <= blk // 2

    def start_fetch(seq, block_idx, buf, short):
        """Sub-block ``block_idx`` of ``seq``'s rows into slot ``buf``:
        one descriptor a page (the row is key and value at once), all on
        the slot's semaphore; a ``short`` one stops at half the pages.
        The page ids are read before the first descriptor goes out: read
        one by one between the descriptors, each waited for its scalar
        load (~3% of the call, my chip run, PR 39)."""
        first = block_idx * pages_per_blk

        def issue(lo, hi):
            pages = [block_tables_ref[seq, first + i] for i in range(lo, hi)]
            for i, page in zip(range(lo, hi), pages):
                pltpu.make_async_copy(
                    kv_pages_ref.at[0, page],
                    kv_vmem.at[buf, pl.ds(i * page_size, page_size)],
                    sems.at[buf],
                ).start()

        if short is None:
            issue(0, pages_per_blk)
        else:
            issue(0, pages_per_blk // 2)
            pl.when(jnp.logical_not(short))(
                lambda: issue(pages_per_blk // 2, pages_per_blk)
            )

    @pl.when(in_list)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _MASK)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Nobody fetched this tile's first sub-block ahead: the call's first
    # live tile, or one behind a tile that walks nothing.
    @pl.when((nb > 0) & (cnt[1] == 0))
    def _bootstrap_dma():
        start_fetch(s, 0, cnt[0] % _NBUF, is_short(tile, 0))

    next_tile = jnp.minimum(tile + 1, num_tiles - 1)
    next_tile_live = (tile + 1 < num_tiles) & (tile_nb_ref[next_tile] > 0)
    next_tile_seq = tile_seq_ref[next_tile]

    def row_positions(ncols):
        """Row r holds query token r // heads of the tile."""
        rows = acc_scr.shape[0]
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, ncols), 0)
        col_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, ncols), 1)
        q_pos = (
            chunk_starts_ref[s] + tile_q0_ref[tile] + row_ids // num_heads
        )
        return q_pos, col_ids

    def flash_update(groups):
        """One online-softmax step over the column groups ``(c, kr,
        mask)``: rows ``[c | kr]`` ([ncols, VW], [ncols, W - VW]) of
        the pool's dtype.  Groups share the step's maximum and its one
        rescale of the state: their dots and exponents do not wait for
        each other."""
        nt = (((1,), (1,)), ((), ()))
        scores = [
            jnp.where(
                mask,
                (
                    jax.lax.dot_general(
                        q_ref[0, :, :vw], c, nt,
                        preferred_element_type=jnp.float32,
                    )
                    + jax.lax.dot_general(
                        q_ref[0, :, vw:], kr, nt,
                        preferred_element_type=jnp.float32,
                    )
                ) * scale,
                _MASK,
            )
            for c, kr, mask in groups
        ]  # [ROWS, ncols] each
        m_prev = m_scr[:, 0:1]
        m_new = functools.reduce(
            jnp.maximum,
            [jnp.max(sc, axis=-1, keepdims=True) for sc in scores],
            m_prev,
        )
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, 0:1] * alpha
        acc = acc_scr[:] * alpha
        for sc, (c, _, mask) in zip(scores, groups):
            p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
            l_new = l_new + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc + jax.lax.dot_general(
                p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        acc_scr[:] = acc
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    def side_group():
        """This dispatch's rows, staged in the side buffer (positions
        seq_len + j; seq_len is the POOL length when staging)."""
        n_side = side_len_ref[0]
        seq_len = seq_lens_ref[s]
        q_pos, col_ids = row_positions(side_ref.shape[2])
        mask = (
            (col_ids < n_side) & (seq_len + col_ids <= q_pos) & (seq_len > 0)
        )
        return side_ref[0, 0, :, :vw], side_ref[0, 0, :, vw:width], mask

    def sub_block(i, carry, *, with_side=False):
        """Sub-block ``i`` of this tile: fetch the one behind it (this
        tile's next, else the first of the next tile if that walks
        any), then wait for this one's pages and fold them in."""
        buf = cnt[0] % _NBUF
        next_in_tile = i + 1 < nb
        has_next = next_in_tile | next_tile_live
        next_s = jnp.where(next_in_tile, s, next_tile_seq)
        next_i = jnp.where(next_in_tile, i + 1, 0)
        next_short = is_short(jnp.where(next_in_tile, tile, next_tile), next_i)

        @pl.when(has_next)
        def _prefetch():
            start_fetch(next_s, next_i, (cnt[0] + 1) % _NBUF, next_short)

        def fold(ncols):
            # One wait for the slot's pages: their copies signal one
            # semaphore, and a wait takes off it what its descriptor moves.
            rows = kv_vmem.at[buf, pl.ds(0, ncols)]
            pltpu.make_async_copy(rows, rows, sems.at[buf]).wait()
            q_pos, col_ids = row_positions(ncols)
            c_pos = i * blk + col_ids
            mask = (c_pos <= q_pos) & (c_pos < seq_lens_ref[s])
            groups = [(
                kv_vmem[buf, :ncols, :vw], kv_vmem[buf, :ncols, vw:width], mask
            )]
            if with_side:
                groups.append(side_group())
            flash_update(groups)

        short = is_short(tile, i)
        if short is None:
            fold(blk)
        else:
            pl.when(short)(lambda: fold(blk // 2))
            pl.when(jnp.logical_not(short))(lambda: fold(blk))
        cnt[0] = cnt[0] + 1
        cnt[1] = has_next.astype(jnp.int32)
        return carry

    # The walk over the tile's cached rows: as many sub-blocks as its
    # sequence has live, a dynamic trip count (0 for a tile past the
    # list or of a sequence with no pool history).  The staged side
    # buffer's columns ride the first sub-block's update (a sequence
    # with no pool history has no staged rows either): one softmax
    # chain a tile less.
    if has_side:
        @pl.when(nb > 0)
        def _first():
            sub_block(0, 0, with_side=True)

    jax.lax.fori_loop(1 if has_side else 0, nb, sub_block, 0)

    @pl.when(in_list)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0:1], 1e-30)
        out_ref[0] = (acc_scr[:] / denom).astype(out_ref.dtype)


def _tile_tokens(max_q: int, num_heads: int, value_width: int) -> int:
    """Query tokens a tile: the decode geometry (``max_q == 1``) is one
    token (more only where a tiny head count leaves under a sublane
    tile of rows); a chunk tile holds as many as keep the f32 flash
    state inside its budget."""
    mq = 1
    while mq * num_heads < 8:
        mq *= 2
    if max_q == 1:
        return mq
    row_bytes = (value_width + 2 * _LANES) * 4
    most = max(_pow2_floor(_STATE_BYTES // (row_bytes * num_heads)), mq)
    return max(min(next_power_of_2(max_q), most), mq)


def _kv_blocking(width: int, itemsize: int, rows: int, page_size: int,
                 max_pages: int):
    """(pages a kv sub-block, tokens a sub-block): as many tokens as
    the buffer slot and the score tile's budget hold, whole pages."""
    lanes = cdiv(width, _LANES) * _LANES
    tokens = min(
        _KV_BUF_BYTES // (lanes * itemsize), _SCORE_BYTES // (rows * 4)
    )
    tokens = min(_pow2_floor(max(tokens, page_size)), max_pages * page_size)
    pages_per_blk = max(tokens // page_size, 1)
    return pages_per_blk, pages_per_blk * page_size


def _vmem_limit_bytes(
    *, blk: int, width: int, value_width: int, rows: int, itemsize: int,
    k_side: int,
) -> int:
    """The limit the call states: the rotating page buffers, the f32
    flash state, the pipelined (double-buffered) query, output and side
    blocks, and a step's f32 temporaries (scores, probabilities and
    their exponent chain, the PV product)."""
    lanes = cdiv(width, _LANES) * _LANES
    kv_bufs = _NBUF * blk * lanes * itemsize
    state = rows * (value_width + 2 * _LANES) * 4
    q_out = 2 * rows * (lanes + value_width) * itemsize
    side = 2 * max(k_side, 16) * lanes * itemsize
    cols = max(blk, k_side)
    temps = 4 * (6 * rows * cols + 3 * rows * value_width)
    return kv_bufs + state + q_out + side + temps + _VMEM_HEADROOM


def mla_query_tile_counts(
    q_lens, *, max_q: int, num_heads: int, value_width: int
) -> tuple[int, int]:
    """(tiles that hold a query, query rows those tiles compute) of one
    step: host-side twin of the wrapper's lists, for the
    ``runner.dispatch`` span."""
    mq_a = _tile_tokens(1, num_heads, value_width)
    mq_b = _tile_tokens(max_q, num_heads, value_width)
    if max_q == 1:
        n = sum(1 for n in q_lens if n > 0)
        return n, n * mq_a
    split = _splits_singles(mq_b)
    singles = sum(1 for n in q_lens if n == 1) if split else 0
    chunks = sum(cdiv(n, mq_b) for n in q_lens if n > (1 if split else 0))
    return singles + chunks, singles * mq_a + chunks * mq_b


def mla_kv_rows_fetched(
    kv_lens, *, num_heads: int, value_width: int, lanes: int, itemsize: int,
    page_size: int, max_pages: int,
) -> int:
    """Rows the kernel's DMAs move in one call of the decode geometry
    over sequences of ``kv_lens`` pool-resident tokens (whole
    sub-blocks of each sequence's live rows, the last one half a
    sub-block where the rows end in its first half): host-side twin of
    the wrapper's lists, for the decode ``runner.dispatch`` span;
    ``sum(kv_lens)`` over it is the fill."""
    rows = num_heads * _tile_tokens(1, num_heads, value_width)
    pages_per_blk, blk = _kv_blocking(
        lanes, itemsize, rows, page_size, max_pages
    )
    half = blk if pages_per_blk % 2 else blk // 2

    def fetched(n):
        full, rest = divmod(n, blk)
        return full * blk + (0 if not rest else half if rest <= half else blk)

    return sum(fetched(n) for n in kv_lens)


def _attend_tiles(
    q_tiles: jax.Array,  # [NT, mq_blk, H, W]
    tiles,  # (tile_seq [NT], tile_q0 [NT], n_live [1])
    kv_pages: jax.Array,  # [1, P, page, WP]
    metadata: AttentionMetadata,
    *,
    scale: float,
    value_width: int,
    side_kv: jax.Array | None,
    side_len: jax.Array | None,
    interpret: bool,
) -> jax.Array:
    """One ``pallas_call`` over a list of query tiles of one geometry;
    returns the tiles' outputs [NT, mq_blk, H, value_width]."""
    num_tiles, mq_blk, h, w = q_tiles.shape
    _, _, page_size, wp = kv_pages.shape
    _, max_pages = metadata.block_tables.shape
    rows = h * mq_blk
    vw = value_width
    itemsize = jnp.dtype(kv_pages.dtype).itemsize
    pages_per_blk, blk = _kv_blocking(
        wp, itemsize, rows, page_size, max_pages
    )
    block_tables = metadata.block_tables
    if max_pages % pages_per_blk:
        # Padding pages are id 0: a real page, masked out of scores.
        pad = pages_per_blk - max_pages % pages_per_blk
        block_tables = jnp.pad(block_tables, ((0, 0), (0, pad)))
    has_side = side_kv is not None

    # rows in the flat batch's own order (token, head): no transpose;
    # operands in the pool's dtype on the MXU
    q_rows = q_tiles.reshape(num_tiles, rows, w).astype(kv_pages.dtype)

    def live_tile(tile, refs):
        return jnp.minimum(tile, jnp.maximum(refs[4][0] - 1, 0))

    def tile_map(tile, *refs):
        return (live_tile(tile, refs), 0, 0)

    def seq_map(tile, *refs):
        return (refs[0][tile], 0, 0, 0)

    tile_seq, tile_q0, n_live = tiles
    scalars = [
        tile_seq,
        tile_q0,
        tile_kv_blocks(
            tile_seq, tile_q0, n_live, metadata.seq_lens,
            metadata.chunk_starts, mq_blk, blk,
        ),
        # the rows those sub-blocks hold for the tile: its causal bound,
        # cut at the sequence's length (``tile_kv_blocks``' own span)
        jnp.minimum(
            metadata.seq_lens[tile_seq],
            metadata.chunk_starts[tile_seq] + tile_q0 + mq_blk,
        ).astype(jnp.int32),
        n_live,
        block_tables,
        metadata.seq_lens,
        metadata.chunk_starts,
    ]
    in_specs = [pl.BlockSpec((1, rows, w), tile_map)]
    inputs = [q_rows]
    k_side = 0
    if has_side:
        scalars.append(side_len.astype(jnp.int32))
        k_side = side_kv.shape[2]
        in_specs.append(pl.BlockSpec((1, 1, k_side, wp), seq_map))
        inputs.append(side_kv.astype(kv_pages.dtype))
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    inputs.append(kv_pages)
    kernel = functools.partial(
        _kernel,
        scale=scale,
        page_size=page_size,
        pages_per_blk=pages_per_blk,
        num_heads=h,
        value_width=vw,
        width=w,
        has_side=has_side,
    )
    out = pl.pallas_call(
        kernel,
        name="vdt_mla_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(num_tiles,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, rows, vw), tile_map),
            scratch_shapes=[
                pltpu.VMEM((_NBUF, blk, wp), kv_pages.dtype),
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, vw), jnp.float32),
                pltpu.SemaphoreType.DMA((_NBUF,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((num_tiles, rows, vw), q_tiles.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(
                blk=blk, width=wp, value_width=vw, rows=rows,
                itemsize=itemsize, k_side=k_side,
            )
        ),
        interpret=interpret,
    )(*scalars, *inputs)
    return out.reshape(num_tiles, mq_blk, h, vw)


def mla_attention(
    q: jax.Array,  # [T, H, W] absorbed queries, flat
    kv_pages: jax.Array,  # [1, P, page, W]
    metadata: AttentionMetadata,
    *,
    scale: float,
    value_width: int,
    max_q: int = 1,
    side_kv: jax.Array | None = None,  # [S, 1, K, WP] staged decode rows
    side_len: jax.Array | None = None,  # [1] int32: valid side columns
    interpret: bool = False,
) -> jax.Array:
    """Drop-in for ``mla_attention_reference`` (ops/attention.py; same
    contract, ``paged_attention``'s on the flat batch, ``max_q`` and the
    staged side buffer).  Returns [T, H, value_width].

    The body is a jitted function of its own: a step program calls it
    once a sublayer with the same shapes, and the kernels' bodies are
    then traced once a program and not once a sublayer (5.5 s of a
    program's 7.3 s of tracing at eight sublayers, compiled for a
    described v5e)."""
    return _attention(
        q, kv_pages, metadata, side_kv, side_len, scale=scale,
        value_width=value_width, max_q=max_q, interpret=interpret,
    )


def _flat_rows(metadata: AttentionMetadata, t: int):
    """Where each sequence's queries are in the flat batch: (query
    tokens a sequence [S], its first flat row [S], each row's sequence
    [T] (padding rows: the last), each row's place in its chunk [T]).
    Padding tokens carry q_seq_ids == S and match no sequence."""
    s, _ = metadata.block_tables.shape
    tok = jnp.arange(t, dtype=jnp.int32)
    match = metadata.q_seq_ids[None, :] == jnp.arange(s, dtype=jnp.int32)[
        :, None
    ]
    q_len = match.sum(axis=1, dtype=jnp.int32)
    first_tok = jnp.min(jnp.where(match, tok[None, :], t), axis=1)
    seq_idx = jnp.minimum(metadata.q_seq_ids, s - 1)
    return q_len, first_tok, seq_idx, tok - first_tok[seq_idx]


def _tile_rows(tiles, first_tok, mq_blk: int, t: int):
    """Flat rows [NT, mq_blk] of a tile list's query tokens."""
    tile_seq, tile_q0, _ = tiles
    rows = (first_tok[tile_seq] + tile_q0)[:, None] + jnp.arange(
        mq_blk, dtype=jnp.int32
    )
    return jnp.clip(rows, 0, t - 1)


def _chunk_tiles(in_chunks, q_len, t: int, s: int, mq_blk: int, max_q: int):
    """The chunk-tile list of the sequences ``in_chunks`` [S] marks, and
    the first tile of each sequence [S]."""
    maxq = max(next_power_of_2(max_q), mq_blk)
    return tile_list(
        jnp.where(in_chunks, (q_len + mq_blk - 1) // mq_blk, 0),
        num_query_tiles(t, s, mq_blk, maxq),
        mq_blk,
    )


@functools.partial(
    jax.jit, static_argnames=("scale", "value_width", "max_q", "interpret")
)
def _attention(
    q, kv_pages, metadata, side_kv, side_len, *, scale, value_width, max_q,
    interpret,
):
    t, h, w = q.shape
    s, _ = metadata.block_tables.shape
    attend = functools.partial(
        _attend_tiles,
        kv_pages=kv_pages,
        metadata=metadata,
        scale=scale,
        value_width=value_width,
        side_kv=side_kv,
        side_len=side_len,
        interpret=interpret,
    )
    q_len, first_tok, seq_idx, tok_in_chunk = _flat_rows(metadata, t)
    is_token = (metadata.q_seq_ids < s)[:, None, None]

    def attend_list(tiles, mq_blk):
        return attend(q[_tile_rows(tiles, first_tok, mq_blk, t)], tiles)

    mq_1 = _tile_tokens(1, h, value_width)
    if max_q == 1:
        seqs = jnp.arange(s, dtype=jnp.int32)
        tiles = (seqs, jnp.zeros(s, jnp.int32), jnp.full((1,), s, jnp.int32))
        out = attend_list(tiles, mq_1)[seq_idx, 0]
        return jnp.where(is_token, out, 0)

    assert side_kv is None, "staged decode rows come with max_q = 1"
    mq_blk = _tile_tokens(max_q, h, value_width)
    split = _splits_singles(mq_blk)
    in_chunks = q_len > 1 if split else q_len > 0
    tiles, first_tile = _chunk_tiles(in_chunks, q_len, t, s, mq_blk, max_q)
    out_rows = attend_list(tiles, mq_blk).reshape(-1, h, value_width)
    row = first_tile[seq_idx] * mq_blk + tok_in_chunk
    out = out_rows[jnp.clip(row, 0, out_rows.shape[0] - 1)]
    if split:
        single = q_len == 1
        tiles, first_tile = tile_list(single.astype(jnp.int32), s, mq_1)
        out_1 = attend_list(tiles, mq_1)[first_tile[seq_idx], 0]
        out = jnp.where(single[seq_idx][:, None, None], out_1, out)
    return jnp.where(is_token, out, 0)


def mla_max_q_class(
    max_q: int, tokens: int, *, num_heads: int, value_width: int
) -> int:
    """The longest-chunk bucket a step of ``tokens`` rows is compiled
    for.  Once a chunk tile holds as many tokens as it may, programs of
    different ``max_q`` differ only in the static length of their tile
    list, which ``max_q`` bounds no tighter than the step's rows do
    beyond a few tiles a sequence: the token bucket, which bounds every
    chunk, then stands for all of them, and a model of eight sublayers
    compiles one program a token bucket and not one a (token bucket,
    longest chunk) pair.  Entries of the list past the tiles in use
    change no block index and do no work."""
    if max_q > 1 and _tile_tokens(max_q, num_heads, value_width) == (
        _tile_tokens(tokens, num_heads, value_width)
    ):
        return tokens
    return max_q


@functools.partial(jax.jit, static_argnames=("scale", "max_q", "interpret"))
def mla_attention_from_heads(
    q_nope: jax.Array,  # [T, H, dn]
    q_rope: jax.Array,  # [T, H, dr], rotated
    c: jax.Array,  # [T, C] this step's latents, as cached
    k_rope: jax.Array,  # [T, dr] this step's rope keys, as cached
    w_uk: jax.Array,  # [H, dn, C]
    w_uv: jax.Array,  # [H, C, dv]
    kv_pages: jax.Array,  # [1, P, page, WP], this step's rows written
    metadata: AttentionMetadata,
    *,
    scale: float,
    max_q: int,
    interpret: bool = False,
) -> jax.Array:
    """A chunked step's (``max_q > 1``) latent attention from head space
    to head space: returns [T, H·dv], what ``W_o`` takes (on the chip
    [T, H, dv] is another tiling: a reshape between the two copies the
    array).  Each sequence goes by what the step's own inputs say of it:

    - a **fresh chunk** (more than one token, ``chunk_starts == 0``:
      every key is a row of this step) attends in the materialised form
      over the flat batch (``vdt_mla_chunk_attention``): per-head keys
      and values made once from the step's latents, no absorb product
      and no latent-wide per-head array for its rows;
    - a **one-token row** (a decode row riding the step) takes the
      absorbed kernel in its decode geometry, the absorb products made
      for the gathered rows of such sequences only;
    - a **chunk with a cached prefix** (``chunk_starts > 0``) takes the
      absorbed chunk tiles, behind one ``lax.cond`` on whether the step
      has such a chunk at all, so a step with none pays neither their
      gathers nor the absorb products over every row.

    The two forms share no logic (one cached row for all heads against
    per-head keys and values).  Padding rows come out finite, not zero:
    nobody reads them.  A jitted function, traced once a program, like
    ``mla_attention``'s body and for the same reason.  ``T`` is a token
    bucket: whole query tiles (a power of two)."""
    assert max_q > 1, "the fused scan and decode-only steps stay absorbed"
    t, h, dn = q_nope.shape
    dv = w_uv.shape[-1]
    vw = c.shape[-1]
    s, _ = metadata.block_tables.shape
    dtype = q_nope.dtype
    attend = functools.partial(
        _attend_tiles, kv_pages=kv_pages, metadata=metadata, scale=scale,
        value_width=vw, side_kv=None, side_len=None, interpret=interpret,
    )
    q_len, first_tok, seq_idx, tok_in_chunk = _flat_rows(metadata, t)
    is_token = metadata.q_seq_ids < s
    fresh_seq = (q_len > 1) & (metadata.chunk_starts == 0)
    prefixed_seq = (q_len > 1) & (metadata.chunk_starts > 0)
    tok = jnp.arange(t, dtype=jnp.int32)

    # ---- fresh chunks: per-head keys and values, the flat kernel ----
    fresh = is_token & fresh_seq[seq_idx]
    row_start = jnp.where(fresh, first_tok[seq_idx], tok)
    with jax.named_scope(SCOPE_MLA_ABSORB):
        # [C, H·d] weights (8 MB each at the cell's widths): the
        # products then come out [T, H·d] row-major, as the kernel
        # reads them; from ``tc,hdc->thd`` XLA emits the tokens minor
        # and transposes 34 MB a product (compiled for a described v5e)
        w_k = w_uk.astype(dtype).transpose(2, 0, 1).reshape(vw, h * dn)
        w_v = w_uv.astype(dtype).transpose(1, 0, 2).reshape(vw, h * dv)
        k_nope = jnp.dot(
            c, w_k, preferred_element_type=jnp.float32
        ).astype(dtype)
        v = jnp.dot(c, w_v, preferred_element_type=jnp.float32).astype(dtype)
    with jax.named_scope(SCOPE_ATTN):
        out = mla_chunk_attention(
            q_nope.reshape(t, -1), q_rope.reshape(t, -1), k_nope, k_rope, v,
            row_start, fresh, num_heads=h, scale=scale, interpret=interpret,
        )  # [T, H·dv], and it stays so: [T, H, dv] is another tiling

    # ---- one-token rows: the absorbed kernel, decode geometry ----
    mq_1 = _tile_tokens(1, h, vw)
    with jax.named_scope(SCOPE_ATTN):
        tiles, _ = tile_list((q_len == 1).astype(jnp.int32), s, mq_1)
        rows_1 = _tile_rows(tiles, first_tok, mq_1, t)  # [S, mq_1]
        in_list = jnp.arange(s, dtype=jnp.int32) < tiles[2][0]
        qn_1, qr_1 = q_nope[rows_1], q_rope[rows_1]
    with jax.named_scope(SCOPE_MLA_ABSORB):
        q_1 = mla_absorb_queries(qn_1.reshape(-1, h, dn), qr_1.reshape(
            -1, h, qr_1.shape[-1]), w_uk)
    with jax.named_scope(SCOPE_ATTN):
        lat_1 = attend(q_1.reshape(s, mq_1, h, -1), tiles)[:, 0]
    with jax.named_scope(SCOPE_MLA_ABSORB):
        out_1 = mla_unabsorb_outputs(lat_1, w_uv)  # [S, H, dv]
    with jax.named_scope(SCOPE_ATTN):
        # entries past the list's live count repeat its last tile: dropped
        out = out.at[jnp.where(in_list, rows_1[:, 0], t)].set(
            out_1.reshape(s, -1), mode="drop"
        )

    # ---- chunks with a cached prefix: today's absorbed chunk tiles ----
    mq_blk = _tile_tokens(max_q, h, vw)

    def absorbed_chunks(out):
        with jax.named_scope(SCOPE_MLA_ABSORB):
            q_abs = mla_absorb_queries(q_nope, q_rope, w_uk)
        with jax.named_scope(SCOPE_ATTN):
            tiles, first_tile = _chunk_tiles(
                prefixed_seq, q_len, t, s, mq_blk, max_q
            )
            lat = attend(
                q_abs[_tile_rows(tiles, first_tok, mq_blk, t)], tiles
            ).reshape(-1, h, vw)
            row = first_tile[seq_idx] * mq_blk + tok_in_chunk
            lat = lat[jnp.clip(row, 0, lat.shape[0] - 1)]
        with jax.named_scope(SCOPE_MLA_ABSORB):
            out_p = mla_unabsorb_outputs(lat, w_uv)
        with jax.named_scope(SCOPE_ATTN):
            # rows written in place: the conditional's result then keeps
            # the flat kernel's row-major layout on both sides (a select
            # took the layout of this side's product, tokens minor, and
            # the other side paid two transposes of the whole array)
            prefixed = is_token & prefixed_seq[seq_idx]
            return out.at[jnp.where(prefixed, tok, t)].set(
                out_p.reshape(t, -1), mode="drop"
            )

    with jax.named_scope(SCOPE_ATTN):
        return jax.lax.cond(
            prefixed_seq.any(), absorbed_chunks, lambda out: out, out
        )


def mla_chunk_token_counts(q_lens, chunk_starts) -> tuple[int, int]:
    """(tokens of fresh chunks, tokens of chunks with a cached prefix)
    of one chunked step: host-side twin of ``mla_attention_from_heads``'
    split, for the ``runner.dispatch`` span and the counter."""
    fresh = sum(n for n, c in zip(q_lens, chunk_starts) if n > 1 and c == 0)
    prefixed = sum(n for n, c in zip(q_lens, chunk_starts) if n > 1 and c > 0)
    return fresh, prefixed


def step_counts_for(model):
    """What a step of the latent kernels counts (``StepShape`` ->
    attributes, increments), bound to ``model``'s widths.  A single
    step: ``q_tiles`` / ``q_rows``, and where it is chunked its
    multi-token chunks by the form their attention takes
    (``chunk_tokens_fresh`` / ``chunk_tokens_prefixed``, the counter's
    materialised | absorbed).  A fused scan: ``kv_rows_fetched``, what
    the kernel's DMAs move in one sublayer call for the sequences'
    cached rows, beside the span's ``kv_tokens`` (the counter's live |
    fetched)."""
    widths = dict(num_heads=model.num_heads, value_width=model.kv_lora_rank)
    lanes = model.kv_cache_layout().width

    def step_counts(shape):
        if shape.kind == "decode":
            fetched = mla_kv_rows_fetched(
                shape.starts, **widths, lanes=lanes,
                itemsize=shape.cache_itemsize, page_size=shape.page_size,
                max_pages=shape.pages_pad,
            )
            return {"kv_rows_fetched": fetched}, {
                "mla_kv_rows": {"live": sum(shape.starts), "fetched": fetched}
            }
        if shape.kind != "prefill":
            return {}, {}
        tiles, rows = mla_query_tile_counts(
            shape.num_new, max_q=shape.max_q_pad, **widths
        )
        attrs = {"q_tiles": tiles, "q_rows": rows}
        if shape.max_q_pad == 1:
            return attrs, {}
        fresh, prefixed = mla_chunk_token_counts(shape.num_new, shape.starts)
        attrs.update(chunk_tokens_fresh=fresh, chunk_tokens_prefixed=prefixed)
        return attrs, {
            "mla_chunk_tokens": {"materialised": fresh, "absorbed": prefixed}
        }

    return step_counts


mla_attention.needs_max_q = True
mla_attention.max_q_class = mla_max_q_class
mla_attention.step_counts_for = step_counts_for
mla_attention.from_heads = mla_attention_from_heads


def mla_attention_cpu(*args, **kwargs):
    """Interpret-mode entry for CPU tests."""
    return mla_attention(*args, interpret=True, **kwargs)


mla_attention_cpu.needs_max_q = True
mla_attention_cpu.max_q_class = mla_max_q_class
mla_attention_cpu.step_counts_for = step_counts_for
mla_attention_cpu.from_heads = functools.partial(
    mla_attention_from_heads, interpret=True
)
