"""Pallas ragged causal attention over the flat batch, per head: latent
(MLA) attention in its **materialised** form, for rows whose keys are
all rows of this step.

A prompt chunk that starts at position 0 has nothing in the cache yet:
every key it may see is a row of the step's own flat batch.  For such
rows the per-head keys and values are cheap to make once
(``[k_nope, v] = c · W_kvb``) and a query–key pair then costs
2·(192 + 128) operations a head instead of the absorbed form's
2·(576 + 512), with no 576- or 512-wide per-head array anywhere
(ops/pallas/mla_attention.py keeps the absorbed form for rows that
read the cache, and chooses).

**What the kernel is told.**  Row ``r`` of the flat batch sees the
rows ``row_start[r] .. r`` (sequences are contiguous in the flat
batch, in position order): a fresh chunk's rows carry the flat index
of their sequence's first row, every other row (a decode row, a chunk
with a cached prefix, padding) carries ``r`` itself and comes out as
finite values its caller overwrites or never reads.

**The grid is (head group, live (query tile, key block) pair).**  A
query tile is ``tq`` consecutive rows of the flat batch, whatever
sequences they belong to; its key blocks run from the block of the
earliest row any of its fresh rows sees to the block of its last fresh
row, so blocks of other sequences and blocks above the diagonal are
never visited.  The pairs are a list built in XLA from ``row_start``
by ops/pallas/paged_attention.py's ``tile_list`` (comparison and a
cumulative sum: no XLA ``while``) and read by scalar prefetch; its
static length is the flat causal triangle of the token bucket, entries
past the live count repeat the last live pair (no block index changes,
nothing is computed), and a tile without a fresh row is one entry that
writes zeros.  Nothing is keyed on the longest chunk.

Operands are ``[T, H·D]`` arrays as the projections emit them, read in
column blocks of ``heads`` heads: no transpose on either side.  The
rope part of a key is one ``[T, rope]`` array shared by every head.
Operands stay in their dtype on the MXU; scores, softmax state and the
accumulator are float32 (as in ``vdt_mla_attention``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from vllm_distributed_tpu.ops.pallas.paged_attention import tile_list
from vllm_distributed_tpu.utils import cdiv

_MASK = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128
# Rows of a query tile and of a key block, and heads a grid step: fixed,
# so a program is one a token bucket (tools/mla_microbench.py --fresh
# has the sweep these came from, PERF.md §6 PR 37).
TILE_Q = 512
TILE_K = 512
_HEADS_PER_STEP = 8
_VMEM_HEADROOM = 8 * 1024 * 1024


def _kernel(
    # scalar prefetch
    pair_q_ref,  # [NP] int32: query tile of each list entry
    pair_k_ref,  # [NP] int32: its key block
    tile_nb_ref,  # [NQ] int32: key blocks a tile walks (0: no fresh row)
    n_pairs_ref,  # [1] int32: entries in use
    # blocks
    start_ref,  # [tq, 1] int32: first flat row each query row sees
    qn_ref,  # [tq, G·dn]
    qr_ref,  # [tq, G·dr]
    kn_ref,  # [tk, G·dn]
    kr_ref,  # [tk, dr]: one rope vector a row, every head's
    v_ref,  # [tk, G·dv]
    out_ref,  # [tq, G·dv]
    # scratch, f32
    m_scr,  # [G, tq, LANES]
    l_scr,  # [G, tq, LANES]
    acc_scr,  # [G, tq, dv]
    *,
    scale: float,
    heads: int,
    dn: int,
    dr: int,
    dv: int,
):
    p = pl.program_id(1)
    num_entries = pl.num_programs(1)
    n_pairs = n_pairs_ref[0]
    tq, tk = qn_ref.shape[0], kn_ref.shape[0]
    qt = pair_q_ref[p]
    kb = pair_k_ref[p]
    in_list = p < n_pairs
    works = in_list & (tile_nb_ref[qt] > 0)
    first = (p == 0) | (pair_q_ref[jnp.maximum(p - 1, 0)] != qt)
    last = (p + 1 >= n_pairs) | (
        pair_q_ref[jnp.minimum(p + 1, num_entries - 1)] != qt
    )

    @pl.when(in_list & (tile_nb_ref[qt] == 0))
    def _no_fresh_row():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(works & first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _MASK)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(works)
    def _compute():
        row = qt * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        col = kb * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        mask = (col <= row) & (col >= start_ref[...])
        nt = (((1,), (1,)), ((), ()))
        kr = kr_ref[...]
        for g in range(heads):
            scores = jax.lax.dot_general(
                qn_ref[:, g * dn:(g + 1) * dn],
                kn_ref[:, g * dn:(g + 1) * dn],
                nt, preferred_element_type=jnp.float32,
            ) + jax.lax.dot_general(
                qr_ref[:, g * dr:(g + 1) * dr], kr, nt,
                preferred_element_type=jnp.float32,
            )  # [tq, tk]
            scores = jnp.where(mask, scores * scale, _MASK)
            m_prev = m_scr[g, :, 0:1]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=-1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_new)
            # A row with no visible column in this block (its sequence
            # starts further on) keeps m at the mask value and gathers
            # weight 1 a column; the first visible column (its own, in
            # the tile's last block at the latest) makes alpha 0 and
            # wipes that.
            probs = jnp.exp(scores - m_new)
            l_new = l_scr[g, :, 0:1] * alpha + jnp.sum(
                probs, axis=-1, keepdims=True
            )
            v = v_ref[:, g * dv:(g + 1) * dv]
            pv = jax.lax.dot_general(
                probs.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_scr[g] = acc_scr[g] * alpha + pv
            m_scr[g] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[g] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(works & last)
    def _finalize():
        for g in range(heads):
            denom = jnp.maximum(l_scr[g, :, 0:1], 1e-30)
            out_ref[:, g * dv:(g + 1) * dv] = (acc_scr[g] / denom).astype(
                out_ref.dtype
            )


def heads_per_step(num_heads: int, *widths: int) -> int:
    """Heads a grid step takes: up to ``_HEADS_PER_STEP``, a count that
    divides the heads and makes every column block whole 128-lane
    tiles; all the heads (the whole array, always a legal block) where
    no such count exists."""
    for g in range(min(_HEADS_PER_STEP, num_heads), 0, -1):
        if num_heads % g == 0 and all(g * w % _LANES == 0 for w in widths):
            return g
    return num_heads


def num_pair_entries(rows: int, tq: int = TILE_Q, tk: int = TILE_K) -> int:
    """Static length of the pair list of ``rows`` flat rows (a multiple
    of ``tq``): the flat causal triangle in blocks, which one sequence
    filling the batch walks whole."""
    return sum(cdiv((i + 1) * tq, tk) for i in range(rows // tq))


def pair_list(row_start: jax.Array, fresh: jax.Array, tq: int, tk: int):
    """The step's list of (query tile, key block) pairs from what each
    row sees: (query tile [NP], key block [NP], key blocks a tile walks
    [NQ], entries in use [1]).  ``row_start`` [T] and ``fresh`` [T]
    (bool) with T a multiple of ``tq``."""
    t = row_start.shape[0]
    nq = t // tq
    tok = jnp.arange(t, dtype=jnp.int32)
    lo = jnp.where(fresh, row_start, t).reshape(nq, tq).min(axis=1) // tk
    hi = jnp.where(fresh, tok, -1).reshape(nq, tq).max(axis=1) // tk
    tile_nb = jnp.where(
        fresh.reshape(nq, tq).any(axis=1), hi - lo + 1, 0
    ).astype(jnp.int32)
    # a tile without a fresh row is one entry (it writes zeros)
    (pair_q, offset, n_pairs), _ = tile_list(
        jnp.maximum(tile_nb, 1), num_pair_entries(t, tq, tk), 1
    )
    pair_k = jnp.where(tile_nb[pair_q] > 0, lo[pair_q] + offset, 0)
    return pair_q, pair_k.astype(jnp.int32), tile_nb, n_pairs


def _vmem_limit_bytes(tq, tk, heads, dn, dr, dv, itemsize) -> int:
    """The limit the call states: the pipelined (double-buffered)
    blocks, the f32 flash state, and a head's f32 temporaries (scores,
    probabilities and their exponent chain, the PV product), which
    Mosaic may keep for more than one head of the unrolled loop."""
    def lanes(w):
        return cdiv(w, _LANES) * _LANES

    blocks = 2 * itemsize * (
        tq * (lanes(heads * dn) + lanes(heads * dr) + lanes(heads * dv))
        + tk * (lanes(heads * dn) + lanes(dr) + lanes(heads * dv))
    ) + 2 * tq * _LANES * 4
    state = heads * tq * (lanes(dv) + 2 * _LANES) * 4
    temps = 4 * (8 * tq * tk + 3 * tq * lanes(dv)) * min(heads, 2)
    return blocks + state + temps + _VMEM_HEADROOM


def mla_chunk_attention(
    q_nope: jax.Array,  # [T, H·dn]
    q_rope: jax.Array,  # [T, H·dr]
    k_nope: jax.Array,  # [T, H·dn]
    k_rope: jax.Array,  # [T, dr]
    v: jax.Array,  # [T, H·dv]
    row_start: jax.Array,  # [T] int32: first flat row each row sees
    fresh: jax.Array,  # [T] bool: rows whose output is wanted
    *,
    num_heads: int,
    scale: float,
    tile_q: int = TILE_Q,
    tile_k: int = TILE_K,
    heads: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Causal attention of every fresh row over rows ``row_start[r]..r``
    of the same flat batch, per head.  Returns [T, H·dv]: a fresh row's
    attention; zeros for a tile without a fresh row; for any other row
    the finite value of seeing ``row_start[r]..r`` (callers give such
    rows ``row_start[r] = r``)."""
    t, h = q_nope.shape[0], num_heads
    dn, dr, dv = q_nope.shape[1] // h, k_rope.shape[1], v.shape[1] // h
    tq, tk = min(tile_q, t), min(tile_k, t)
    assert tq % tk == 0 and t % tq == 0, (t, tq, tk)
    g = heads or heads_per_step(h, dn, dr, dv)
    assert h % g == 0, (h, g)
    pair_q, pair_k, tile_nb, n_pairs = pair_list(row_start, fresh, tq, tk)

    def q_map(hg, p, pair_q, pair_k, *_):
        return (pair_q[p], hg)

    def k_map(hg, p, pair_q, pair_k, *_):
        return (pair_k[p], hg)

    kernel = functools.partial(
        _kernel, scale=scale, heads=g, dn=dn, dr=dr, dv=dv
    )
    return pl.pallas_call(
        kernel,
        name="vdt_mla_chunk_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(h // g, pair_q.shape[0]),
            in_specs=[
                pl.BlockSpec((tq, 1), lambda hg, p, pq, *_: (pq[p], 0)),
                pl.BlockSpec((tq, g * dn), q_map),
                pl.BlockSpec((tq, g * dr), q_map),
                pl.BlockSpec((tk, g * dn), k_map),
                pl.BlockSpec((tk, dr), lambda hg, p, pq, pk, *_: (pk[p], 0)),
                pl.BlockSpec((tk, g * dv), k_map),
            ],
            out_specs=pl.BlockSpec((tq, g * dv), q_map),
            scratch_shapes=[
                pltpu.VMEM((g, tq, _LANES), jnp.float32),
                pltpu.VMEM((g, tq, _LANES), jnp.float32),
                pltpu.VMEM((g, tq, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((t, h * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(
                tq, tk, g, dn, dr, dv, jnp.dtype(q_nope.dtype).itemsize
            )
        ),
        interpret=interpret,
    )(
        pair_q, pair_k, tile_nb, n_pairs,
        row_start.astype(jnp.int32)[:, None],
        q_nope, q_rope, k_nope, k_rope, v,
    )
