"""Pallas flush of staged decode K/V rows into the paged pool.

The fused decode scan stages each micro-step's K/V in a dense side
buffer (one in-place dynamic_update_slice per layer per step) instead
of scattering rows into the paged pool; after the scan, this kernel
folds a dispatch's K rows per sequence back into the pool in one pass.

Why a read-modify-write: Mosaic can only DMA tile-aligned slabs of the
pool's (page_size, HD) minor pair — single token rows are not
addressable (see ops/attention.py layout notes).  A sequence's K
consecutive rows [base, base+K) touch at most ceil(K/page)+1 pages, so
the kernel reads those page slabs, overlays the side rows with a
vectorized roll + iota select, and writes the slabs back.  Per
dispatch this is ~2 pages × 2 planes × r+w per sequence per layer —
~1-2 % of the decode step's attention traffic — versus a per-row write
EVERY micro-step on the old path (measured ~1.8 µs/row: several ms per
micro-step at batch 64).

Pipelining: the next sequence's slab reads are started before this
sequence's modify/write-back, so the read latency is hidden; the
write-back is waited in the same grid step (cheap — the slabs are
tens of KB), which keeps semaphore accounting trivially balanced even
when trailing padding rows are skipped.  Sequences' touched pages are
disjoint by the allocator; padding rows (base 0) are skipped, and any
over-read of the reserved dump page 0 via clamped table padding only
rewrites garbage with garbage.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    base_lens_ref,  # [S] int32 (pool-resident length; <=0 = skip row)
    page0_ref,  # [S] int32: base // page_size (logical first page)
    page_ids_ref,  # [S, NPT] int32: touched page ids (clamped, padded)
    n_side_ref,  # [S] int32: rows to flush per sequence (<= K)
    side_ref,  # [1, PL, K, HD] VMEM block (this sequence's staged rows)
    pool_in,  # [PL, P, page, HD] ANY (aliased with pool_out)
    pool_out,
    slab_vmem,  # [2(pipe), PL, NPT*page, HD]
    read_sems,  # [2]
    write_sem,
    *,
    page_size: int,
    npt: int,
    planes: int,  # PL: 2 for K and V, 1 for a latent row
):
    s = pl.program_id(0)
    num_s = pl.num_programs(0)
    buf = s % 2
    live = (base_lens_ref[s] > 0) & (n_side_ref[s] > 0)

    # Page id 0 is the reserved dump page (slack slab columns route
    # there): skip its copies entirely so the dump-page contract stays
    # read-only for this kernel — an unskipped write-back would race the
    # next sequence's prefetched read of the same page.  Start and wait
    # predicates read the same page_ids entries, so DMA semaphore
    # accounting stays balanced.
    def _for_each(seq, buf, action, direction):
        for pt in range(npt):
            page = page_ids_ref[seq, pt]

            @pl.when(page != 0)
            def _go(page=page, pt=pt):
                for kvi in range(planes):
                    slab = slab_vmem.at[
                        buf, kvi, pl.ds(pt * page_size, page_size)
                    ]
                    if direction == "read":
                        cp = pltpu.make_async_copy(
                            pool_in.at[kvi, page], slab, read_sems.at[buf]
                        )
                    else:
                        cp = pltpu.make_async_copy(
                            slab, pool_out.at[kvi, page], write_sem
                        )
                    getattr(cp, action)()

    def start_reads(seq, buf):
        _for_each(seq, buf, "start", "read")

    def wait_reads(seq, buf):
        _for_each(seq, buf, "wait", "read")

    def start_writes(seq, buf):
        _for_each(seq, buf, "start", "write")

    def wait_writes(seq, buf):
        _for_each(seq, buf, "wait", "write")

    # Prologue: nobody prefetched row 0's slabs.
    @pl.when((s == 0) & live)
    def _first_reads():
        start_reads(s, buf)

    # Prefetch the next sequence's slabs while this one modifies/writes.
    # The predicate must MATCH the next grid step's `live` exactly: a
    # started copy whose wait is skipped would leave its semaphore
    # signaled for a later sequence on the same buffer parity.
    nxt = jnp.minimum(s + 1, num_s - 1)

    @pl.when(
        (s + 1 < num_s)
        & (base_lens_ref[nxt] > 0)
        & (n_side_ref[nxt] > 0)
    )
    def _next_reads():
        start_reads(nxt, (s + 1) % 2)

    @pl.when(live)
    def _modify_and_write():
        wait_reads(s, buf)
        n_side = n_side_ref[s]
        rows = npt * page_size
        base = base_lens_ref[s]
        off = base - page0_ref[s] * page_size  # first row's slab offset
        row_ids = jax.lax.broadcasted_iota(
            jnp.int32, (rows, side_ref.shape[3]), 0
        )
        in_window = (row_ids >= off) & (row_ids < off + n_side)
        for kvi in range(planes):
            # Side row j lands at slab row off + j: pad side to the slab
            # height and roll it down by `off`.  Mosaic only rotates
            # 32-bit lanes, so roll in f32 (exact for bf16/int8 values).
            side = side_ref[0, kvi].astype(jnp.float32)  # [K, HD]
            padded = jnp.pad(side, [(0, rows - side.shape[0]), (0, 0)])
            shifted = pltpu.roll(padded, off, 0).astype(slab_vmem.dtype)
            cur = slab_vmem[buf, kvi]
            slab_vmem[buf, kvi] = jnp.where(in_window, shifted, cur)
        start_writes(s, buf)
        wait_writes(s, buf)


# Mosaic's own scratch, beside the byte model below.
_VMEM_HEADROOM = 4 * 2**20


def _vmem_limit_bytes(
    rows: int, hd: int, pool_itemsize: int, k_blk: int, side_itemsize: int,
    planes: int = 2,
) -> int:
    """The limit the call states: the two-deep K+V slab scratch, the
    pipelined side block and the overlay's f32 working set (padded
    side, rolled copy, current slab, select result, row iota)."""
    slabs = 2 * planes * rows * hd * pool_itemsize
    side = 2 * planes * k_blk * hd * side_itemsize
    overlay = 5 * rows * hd * 4
    return slabs + side + overlay + _VMEM_HEADROOM


def kv_flush(
    kv_pages,  # [PL, P, page, HD] — or (int8 data, per-head scales)
    side_kv: jax.Array,  # [S, PL, K, HD] (model dtype); PL planes: 2, or
    # 1 for a latent pool
    block_tables: jax.Array,  # [S, max_pages] int32
    base_lens: jax.Array,  # [S] int32 (0 = padding row, skipped)
    n_side: jax.Array,  # [S] (or [1], broadcast) int32: rows per sequence
    *,
    interpret: bool = False,
):
    """Write each live sequence's staged rows [base, base+n_side[s])
    into the pool, in place (aliased).  Per-sequence lengths let the
    fused decode scan mask under-K request tails (model_runner).

    For an int8 pool the staged rows are quantized per kv head here
    (plain XLA — a [S, 2, K] reduction, off the micro-step path); the
    int8 data planes go through the overlay kernel, while the f32
    scale planes — ~HD/(4·Hkv)× smaller, and too narrow for Mosaic's
    128-lane DMA slice alignment — are written by a functional XLA
    scatter on the donated buffer (in place; worst case one small copy
    per dispatch).  Under shard_map each shard quantizes its own
    heads' lanes, which is bit-identical to the global per-head
    computation."""
    if isinstance(kv_pages, tuple):
        from vllm_distributed_tpu.ops.attention import quantize_kv_heads

        data, scales = kv_pages
        hkv = scales.shape[-1]
        side_q, side_s = quantize_kv_heads(side_kv, hkv)
        data = kv_flush(
            data, side_q, block_tables, base_lens, n_side,
            interpret=interpret,
        )
        s, _, k_blk, _ = side_kv.shape
        page_size = data.shape[2]
        if n_side.shape[0] != s:
            n_side = jnp.broadcast_to(n_side, (s,))
        # Row j of sequence s lands at pool position base+j; rows past
        # n_side[s] (and dead sequences) scatter into dump page 0.
        j = jnp.arange(k_blk, dtype=jnp.int32)[None, :]
        pos = base_lens[:, None] + j  # [S, K]
        live = (base_lens[:, None] > 0) & (j < n_side[:, None])
        page_idx = jnp.where(live, pos // page_size, 0)
        pages = jnp.take_along_axis(
            block_tables, jnp.minimum(page_idx, block_tables.shape[1] - 1),
            axis=1,
        )
        pages = jnp.where(live, pages, 0)
        rows = jnp.where(live, pos % page_size, 0)
        scales = scales.at[0, pages, rows].set(side_s[:, 0])
        scales = scales.at[1, pages, rows].set(side_s[:, 1])
        return (data, scales)
    planes, p_total, page_size, hd = kv_pages.shape
    s, _, k_blk, _ = side_kv.shape
    npt = (k_blk + page_size - 1) // page_size + 1
    if n_side.shape[0] != s:
        n_side = jnp.broadcast_to(n_side, (s,))

    page0 = base_lens // page_size
    pts = page0[:, None] + jnp.arange(npt, dtype=jnp.int32)[None, :]
    # The slab's slack column can step past the table: route it to the
    # reserved dump page 0, NOT a clamped real page — a clamped
    # duplicate would write a stale copy of the sequence's last page
    # over the freshly flushed rows.  (In-table entries past a
    # sequence's allocation are already 0 by table construction.)
    in_table = pts < block_tables.shape[1]
    gathered = jnp.take_along_axis(
        block_tables, jnp.minimum(pts, block_tables.shape[1] - 1), axis=1
    )
    page_ids = jnp.where(in_table, gathered, 0)

    kernel = functools.partial(
        _kernel, page_size=page_size, npt=npt, planes=planes
    )
    out = pl.pallas_call(
        kernel,
        name="vdt_kv_flush",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s,),
            in_specs=[
                pl.BlockSpec(
                    (1, planes, k_blk, hd),
                    lambda s_, *refs: (s_, 0, 0, 0),
                ),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM(
                    (2, planes, npt * page_size, hd), kv_pages.dtype
                ),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
        # Inputs: 0-3 scalar prefetch, 4 side, 5 pool → output 0.
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit_bytes(
                npt * page_size,
                hd,
                jnp.dtype(kv_pages.dtype).itemsize,
                k_blk,
                jnp.dtype(side_kv.dtype).itemsize,
                planes,
            )
        ),
        interpret=interpret,
    )(
        base_lens.astype(jnp.int32),
        page0.astype(jnp.int32),
        page_ids.astype(jnp.int32),
        n_side.astype(jnp.int32),
        side_kv,
        kv_pages,
    )
    return out


def kv_flush_cpu(*args, **kwargs):
    """Interpret-mode entry for CPU tests."""
    return kv_flush(*args, interpret=True, **kwargs)
