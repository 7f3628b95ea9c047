"""Pallas grouped (routed) expert matmuls: rows sorted by expert, each
multiplied by its own expert's weights — int8 weights streamed and cast
in the kernel (``grouped_int8_matmul``, below), or weights in the
activations' own dtype fed to the MXU as they are
(``grouped_matmul``, at the end: the same layout, no cast and no scale).

A many-row MoE step (prefill, a mixed step) is compute-bound, and the
dense dispatch computes every expert for every token: E/k times the
model's FLOPs.  ``jax.lax.ragged_dot`` routes, but takes bf16 weights,
so int8 experts would be dequantized into a bf16 copy of the whole
stack first (written and re-read: more than routing saves).  This
kernel routes AND streams the int8 tiles themselves: the scheme is per
output channel (ops/quant.py), so the MXU is fed ``q.astype(x.dtype)``
(exact: |q| <= 127) and the f32 accumulator's columns are scaled by
``scale[expert]`` afterwards — the mathematics of dequantize-then-dot
with no bf16 copy of the weights anywhere.

Layout: every expert's group is padded to a whole row tile
(``group_layout``), so a row tile belongs to one expert and the
row-tile → expert map goes in by scalar prefetch; the tiles past the
last group are skipped (``pl.when``) and keep the block indices of the
last used tile, so they move no bytes.  The grid is (N tiles, row
tiles) with the row tiles innermost: consecutive row tiles of one
expert keep the same weight block index, so an expert's [K, tn] tile is
fetched once per expert and N tile, and is cast once too (into a VMEM
scratch that the expert's later row tiles reuse).  ``xs`` is read once
per N tile, which the widest ``tn`` the budget admits keeps under the
MXU's time (PERF.md, PR 27).  K is not a grid axis: the cached cast
needs the whole [K, tn] tile resident; it is walked in ``_CAST_CHUNK``
rows so the cast's f32 temporaries stay small.

With a second weight (``q_up``) the kernel computes both projections
of the same row tile and writes ``silu(x @ w) * (x @ w_up)``: the
gated-MLP epilogue, so ``xs`` is read once for both and neither
projection is written out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One row tile: the v5e's MXUs are 128 x 128, and a weight tile that
# fewer rows stream through is bound by its own load.  Smaller groups
# pad up to it, which is what sets the crossover against the dense
# einsum (PERF.md, PR 27).
BLOCK_ROWS = 128
# Working-set cap of one grid step by ``vmem_bytes`` below, of the
# v5e core's 128 MiB of VMEM; the call states its model + headroom as
# ``vmem_limit_bytes`` (the compiler's default scope is 16 MiB).
_VMEM_TILE_BUDGET = 48 * 2**20
_VMEM_HEADROOM = 8 * 2**20
_CAST_CHUNK = 512
_BLOCK_OUT = (1024, 512, 256, 128)


def vmem_bytes(
    block_rows: int, in_dim: int, block_out: int, itemsize: int, n_w: int
) -> int:
    """VMEM model of one grid step: per weight the double-buffered int8
    tile, its cast copy and the f32 accumulator; the double-buffered
    row tile and output tile; one chunk of the cast's f32 temporary."""
    per_w = in_dim * block_out * (2 + itemsize) + block_rows * block_out * 4
    x = 2 * block_rows * in_dim * itemsize
    out = 2 * block_rows * block_out * itemsize
    cast = min(_CAST_CHUNK, in_dim) * block_out * 4
    return n_w * per_w + x + out + cast


def pick_block_out(
    in_dim: int, out_dim: int, itemsize: int, n_w: int,
    block_rows: int = BLOCK_ROWS,
) -> int | None:
    """The widest output tile that divides ``out_dim`` and fits the
    budget (the wider, the fewer passes over ``xs``); ``None`` where the
    compiled kernel has no tile for these dims."""
    if in_dim % 128:
        return None
    for blk in _BLOCK_OUT:
        if out_dim % blk == 0 and vmem_bytes(
            block_rows, in_dim, blk, itemsize, n_w
        ) <= _VMEM_TILE_BUDGET:
            return blk
    return None


@functools.cache
def has_tiles(hidden: int, intermediate: int, itemsize: int = 2) -> bool:
    """Whether the compiled kernel has tiles for an expert MLP of these
    widths: the fused up projection [hidden -> intermediate] and the
    down projection back."""
    return (
        pick_block_out(hidden, intermediate, itemsize, 2) is not None
        and pick_block_out(intermediate, hidden, itemsize, 1) is not None
    )


def padded_rows(rows: int, num_groups: int, block_rows: int) -> int:
    """Rows of the tile-padded layout: enough for any split of ``rows``
    into ``num_groups`` groups (sum of ceil(g / tm) is at most this
    many tiles), and at least one tile."""
    tiles = (rows + num_groups * (block_rows - 1)) // block_rows
    return max(tiles, 1) * block_rows


def group_layout(
    group_sizes: jax.Array, num_rows_padded: int, block_rows: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The tile-padded layout of groups of ``group_sizes`` rows:
    (first padded row of each group [E], expert of each row tile [NT],
    number of tiles in use [1]).  Tiles past the last group carry the
    last used tile's expert, so they change no block index."""
    e = group_sizes.shape[0]
    tiles = (group_sizes + block_rows - 1) // block_rows
    tile_end = jnp.cumsum(tiles)
    used = tile_end[-1:]
    idx = jnp.arange(num_rows_padded // block_rows, dtype=jnp.int32)
    idx = jnp.minimum(idx, jnp.maximum(used - 1, 0))
    # A tile's expert: how many groups end at or before it.  Counted by
    # comparison, not searchsorted: its binary search is an XLA
    # ``while``, and a single-step program holds none (the benchmark
    # tells a fused scan from a single step by that).
    tile_expert = jnp.minimum(
        (idx[:, None] >= tile_end[None, :]).sum(axis=1), e - 1
    )
    starts = (tile_end - tiles) * block_rows
    return (
        starts.astype(jnp.int32),
        tile_expert.astype(jnp.int32),
        used.astype(jnp.int32),
    )


def _cast_chunk(in_dim: int) -> int:
    c = min(_CAST_CHUNK, in_dim)
    while in_dim % c:
        c //= 2
    return max(c, 1)


def _kernel(te_ref, used_ref, x_ref, *refs, n_w: int, chunk: int):
    # x [tm, K]; per weight: q [K, tn] int8, s [1, tn] f32, scratch
    # [K, tn] in x's dtype -> o [tm, tn].
    qs, ss = refs[:n_w], refs[n_w : 2 * n_w]
    o_ref = refs[2 * n_w]
    scr = refs[2 * n_w + 1 :]
    i = pl.program_id(1)
    live = i < used_ref[0]
    fresh = (i == 0) | (te_ref[i] != te_ref[jnp.maximum(i - 1, 0)])

    @pl.when(live & fresh)
    def _cast():
        in_dim = x_ref.shape[1]
        for q_ref, w_ref in zip(qs, scr):

            def body(c, carry, q_ref=q_ref, w_ref=w_ref):
                rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
                w_ref[rows, :] = (
                    q_ref[rows, :].astype(jnp.float32).astype(w_ref.dtype)
                )
                return carry

            jax.lax.fori_loop(0, in_dim // chunk, body, 0)

    @pl.when(live)
    def _dot():
        x = x_ref[...]
        acc = [
            jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
            * s_ref[...]
            for w_ref, s_ref in zip(scr, ss)
        ]
        out = acc[0] if n_w == 1 else jax.nn.silu(acc[0]) * acc[1]
        o_ref[...] = out.astype(o_ref.dtype)


def grouped_int8_matmul(
    xs: jax.Array,  # [MP, K] rows in group_layout's padded layout
    q: jax.Array,  # [E, K, N] int8
    scale: jax.Array,  # [E, N] f32 (per expert and output channel)
    group_sizes: jax.Array,  # [E] int32 rows per expert
    *,
    q_up: jax.Array | None = None,  # second weight: silu(x@w) * (x@w_up)
    scale_up: jax.Array | None = None,
    block_rows: int = BLOCK_ROWS,
    block_out: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``xs[rows of expert e] @ (q[e] * scale[e])`` for rows sorted by
    expert, every group padded to whole ``block_rows`` tiles (row r of
    group e sits at ``group_layout(...)[0][e] + r``).  Rows outside
    every group are not computed; their output is undefined."""
    mp, in_dim = xs.shape
    e, in_q, out_dim = q.shape
    assert in_q == in_dim and q.dtype == jnp.int8, (xs.shape, q.shape)
    assert mp % block_rows == 0, (mp, block_rows)
    n_w = 1 if q_up is None else 2
    itemsize = jnp.dtype(xs.dtype).itemsize
    if block_out is None:
        block_out = pick_block_out(
            in_dim, out_dim, itemsize, n_w, block_rows
        )
        if block_out is None and interpret:
            block_out = out_dim
    if block_out is None or out_dim % block_out:
        raise ValueError(
            f"grouped_int8_matmul has no tile for in={in_dim}, "
            f"out={out_dim} (block {block_out})"
        )
    model = vmem_bytes(block_rows, in_dim, block_out, itemsize, n_w)
    if model > _VMEM_TILE_BUDGET:
        raise ValueError(
            f"grouped_int8_matmul tile budget exceeded (in={in_dim}, "
            f"block={block_out}, rows={block_rows}, weights={n_w})"
        )
    _, tile_expert, used = group_layout(group_sizes, mp, block_rows)

    def row_tile(i, used_ref):
        return jnp.minimum(i, jnp.maximum(used_ref[0] - 1, 0))

    weights = [(q, scale)] if q_up is None else [(q, scale), (q_up, scale_up)]
    q_spec = pl.BlockSpec(
        (None, in_dim, block_out), lambda j, i, te, used: (te[i], 0, j)
    )
    s_spec = pl.BlockSpec(
        (None, 1, block_out), lambda j, i, te, used: (te[i], 0, j)
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_w=n_w, chunk=_cast_chunk(in_dim)),
        name="vdt_grouped_int8_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(out_dim // block_out, mp // block_rows),
            in_specs=[
                pl.BlockSpec(
                    (block_rows, in_dim),
                    lambda j, i, te, used: (row_tile(i, used), 0),
                ),
                *[q_spec] * n_w,
                *[s_spec] * n_w,
            ],
            out_specs=pl.BlockSpec(
                (block_rows, block_out),
                lambda j, i, te, used: (row_tile(i, used), j),
            ),
            scratch_shapes=[
                pltpu.VMEM((in_dim, block_out), xs.dtype)
                for _ in range(n_w)
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((mp, out_dim), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=model + _VMEM_HEADROOM,
        ),
        interpret=interpret,
    )(
        tile_expert,
        used,
        xs,
        *[w for w, _ in weights],
        *[
            s.reshape(e, 1, out_dim).astype(jnp.float32)
            for _, s in weights
        ],
    )


# ---- unquantized experts ---------------------------------------------------
def unquantized_vmem_bytes(
    block_rows: int, in_dim: int, block_out: int, itemsize: int, n_w: int
) -> int:
    """VMEM model of one grid step of ``grouped_matmul``: per weight the
    double-buffered tile and the f32 accumulator; the double-buffered
    row tile and output tile."""
    per_w = 2 * in_dim * block_out * itemsize + block_rows * block_out * 4
    x = 2 * block_rows * in_dim * itemsize
    out = 2 * block_rows * block_out * itemsize
    return n_w * per_w + x + out


def pick_unquantized_block_out(
    in_dim: int, out_dim: int, itemsize: int, n_w: int,
    block_rows: int = BLOCK_ROWS,
) -> int | None:
    """The widest output tile of whole 128-lane columns that divides
    ``out_dim`` and fits the budget — the whole width where it fits, so
    that ``xs`` is read once and an expert's weights once; ``None``
    where the compiled kernel has no tile for these dims."""
    if in_dim % 128 or out_dim % 128:
        return None
    for parts in range(1, out_dim // 128 + 1):
        blk = out_dim // parts
        if out_dim % parts or blk % 128:
            continue
        if unquantized_vmem_bytes(
            block_rows, in_dim, blk, itemsize, n_w
        ) <= _VMEM_TILE_BUDGET:
            return blk
    return None


@functools.cache
def has_unquantized_tiles(
    hidden: int, intermediate: int, itemsize: int = 2
) -> bool:
    """``has_tiles`` for ``grouped_matmul``."""
    return (
        pick_unquantized_block_out(hidden, intermediate, itemsize, 2)
        is not None
        and pick_unquantized_block_out(intermediate, hidden, itemsize, 1)
        is not None
    )


def _unquantized_kernel(te_ref, used_ref, x_ref, *refs, n_w: int):
    # x [tm, K]; per weight w [K, tn] in x's dtype -> o [tm, tn].
    del te_ref  # the block indices read it
    ws, o_ref = refs[:n_w], refs[n_w]

    @pl.when(pl.program_id(1) < used_ref[0])
    def _dot():
        x = x_ref[...]
        acc = [
            jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
            for w_ref in ws
        ]
        out = acc[0] if n_w == 1 else jax.nn.silu(acc[0]) * acc[1]
        o_ref[...] = out.astype(o_ref.dtype)


def grouped_matmul(
    xs: jax.Array,  # [MP, K] rows in group_layout's padded layout
    w: jax.Array,  # [E, K, N] in xs' dtype
    group_sizes: jax.Array,  # [E] int32 rows per expert
    *,
    w_up: jax.Array | None = None,  # second weight: silu(x@w) * (x@w_up)
    block_rows: int = BLOCK_ROWS,
    block_out: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``xs[rows of expert e] @ w[e]`` for rows sorted by expert in
    ``grouped_int8_matmul``'s layout, the weights in the rows' own dtype
    (bfloat16 on the chip): the grid, the row-tile → expert map and the
    skipped tail are the same; a weight tile goes from HBM to the MXU
    as it is, and consecutive row tiles of one expert keep its block
    index, so it is fetched once an expert and N tile."""
    mp, in_dim = xs.shape
    e, in_w, out_dim = w.shape
    assert in_w == in_dim and w.dtype == xs.dtype, (xs.dtype, w.dtype)
    assert mp % block_rows == 0, (mp, block_rows)
    n_w = 1 if w_up is None else 2
    itemsize = jnp.dtype(xs.dtype).itemsize
    if block_out is None:
        block_out = pick_unquantized_block_out(
            in_dim, out_dim, itemsize, n_w, block_rows
        )
        if block_out is None and interpret:
            block_out = out_dim
    if block_out is None or out_dim % block_out:
        raise ValueError(
            f"grouped_matmul has no tile for in={in_dim}, out={out_dim} "
            f"(block {block_out})"
        )
    model = unquantized_vmem_bytes(
        block_rows, in_dim, block_out, itemsize, n_w
    )
    _, tile_expert, used = group_layout(group_sizes, mp, block_rows)

    def row_tile(i, used_ref):
        return jnp.minimum(i, jnp.maximum(used_ref[0] - 1, 0))

    w_spec = pl.BlockSpec(
        (None, in_dim, block_out), lambda j, i, te, used: (te[i], 0, j)
    )
    return pl.pallas_call(
        functools.partial(_unquantized_kernel, n_w=n_w),
        name="vdt_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(out_dim // block_out, mp // block_rows),
            in_specs=[
                pl.BlockSpec(
                    (block_rows, in_dim),
                    lambda j, i, te, used: (row_tile(i, used), 0),
                ),
                *[w_spec] * n_w,
            ],
            out_specs=pl.BlockSpec(
                (block_rows, block_out),
                lambda j, i, te, used: (row_tile(i, used), j),
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((mp, out_dim), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=model + _VMEM_HEADROOM,
        ),
        interpret=interpret,
    )(tile_expert, used, xs, *([w] if w_up is None else [w, w_up]))
