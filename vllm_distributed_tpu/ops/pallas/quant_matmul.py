"""Pallas weight-streaming quantized matmuls: x @ dequant(Wq).

The decode hot path is HBM-bound on weight reads; weight-only int8/int4
cuts those bytes 2×/4× — but ONLY if the compressed weights are what
actually streams.  XLA either hoists the dequant out of the fused
decode scan (materializing the bf16 model; blocked by an
optimization_barrier in model_runner) or materializes a dequantized
copy per micro-step, which pays compressed-read + bf16-write +
bf16-read and erases the win.  These kernels do what the hardware
wants: DMA compressed tiles HBM→VMEM (Pallas pipelines/double-buffers
the grid blocks), dequantize in VMEM, feed the MXU — the only HBM
traffic is the compressed bytes.

int4 packing note: the host packs input rows 2i (low nibble) and 2i+1
(high nibble) into one byte (ops/quant.py).  Un-interleaving rows in
VMEM would be a sublane relayout Mosaic handles poorly, so the kernel
never interleaves: a matmul contraction is order-invariant, so the
CALLER permutes x's columns to [evens | odds] (cheap XLA op on the tiny
activation) and the kernel runs TWO dots — low nibbles against the
even columns, high nibbles against the odd columns.  Group scales along
the input dim stay aligned because rows 2i and 2i+1 always share a
group (group sizes are even): each half's row r maps to group
r // (group/2), a contiguous sublane broadcast.

Activations stay exact (weight-only quantization, same numerics as
``dequantize()`` + matmul).

Used for 2D weights on the single-chip path; under tp>1 the int8 path
shard_maps per shard (ops/quant.py), int4 falls back to
dequant-in-graph.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Working-set cap for one grid step, by the byte models below.  A v5e
# core has 128 MiB of VMEM (jax's pallas/mosaic/tpu_info.py), of which
# the compiler scopes a kernel to a default far below these tiles
# (16 MiB) unless the call states its own limit — so every pallas_call
# here passes vmem_limit_bytes = its model + _VMEM_HEADROOM (Mosaic's
# internal scratch, the double-buffered output tile, the scale rows).
# With that, the largest approved tiles ([4096x1024] and [8192x512]
# int8, [2048x512] int4) compile and run on the chip (PERF.md, PR 21).
_VMEM_TILE_BUDGET = 26 * 2**20
_VMEM_HEADROOM = 8 * 2**20


def int8_vmem_bytes(in_dim: int, block_out: int, x_nbytes: int) -> int:
    """VMEM model per grid step: two double-buffered int8 weight tiles
    (in*blk*2) plus the f32 dequantized tile the kernel materializes
    before the dot (in*blk*4) plus f32-promoted x (~2*x_nbytes).
    Single source of truth for the caller's eligibility check, the
    kernel's own guard and the limit the call states (int4 has its own
    model below — its temporaries are larger)."""
    return in_dim * block_out * 6 + 2 * x_nbytes


def int4_vmem_bytes(in_dim: int, block_out: int, x_nbytes: int) -> int:
    """int4 kernel VMEM model: per LOGICAL input element the kernel
    holds ~half-height planes of int32 q (2B), two f32 nibble planes
    (4B), the expanded scales (2B) and the two scaled operands (4B),
    plus double-buffered packed tiles — ~16B/element against int8's
    6B."""
    return in_dim * block_out * 16 + 2 * x_nbytes


def fits_vmem_budget(in_dim: int, block_out: int, x_nbytes: int) -> bool:
    return int8_vmem_bytes(in_dim, block_out, x_nbytes) <= _VMEM_TILE_BUDGET


def fits_vmem_budget4(in_dim: int, block_out: int, x_nbytes: int) -> bool:
    return int4_vmem_bytes(in_dim, block_out, x_nbytes) <= _VMEM_TILE_BUDGET


def _compiler_params(model_bytes: int) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(
        vmem_limit_bytes=model_bytes + _VMEM_HEADROOM
    )


def _kernel(x_ref, q_ref, s_ref, o_ref, *, out_dtype):
    # x [T, IN] bf16/f32; q [IN, BLK] int8; s [1, BLK] f32 -> o [T, BLK]
    w = q_ref[...].astype(jnp.float32) * s_ref[0, :][None, :]
    acc = jnp.dot(
        x_ref[...].astype(jnp.float32),
        w,
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = acc.astype(out_dtype)


def _kernel4(x_ref, q_ref, s_ref, o_ref, *, group, out_dtype):
    # x [T, IN] (columns permuted to [evens | odds]); q [IN/2, BLK]
    # uint8 (low nibble = even row, high = odd); s [IN/group, BLK] f32.
    half = q_ref.shape[0]
    # Mosaic has no direct uint8->f32 cast; hop through int32.
    q = q_ref[...].astype(jnp.int32)
    low = (q & 0xF).astype(jnp.float32) - 8.0
    high = (q >> 4).astype(jnp.float32) - 8.0
    # Each half's row r belongs to group r // (group/2): expand the
    # scale rows by sublane broadcast (shared by both halves).
    g2 = group // 2
    s = s_ref[...]
    sexp = jnp.broadcast_to(
        s[:, None, :], (s.shape[0], g2, s.shape[1])
    ).reshape(half, s.shape[1])
    x = x_ref[...].astype(jnp.float32)
    acc = jnp.dot(
        x[:, :half], low * sexp, preferred_element_type=jnp.float32
    )
    acc += jnp.dot(
        x[:, half:], high * sexp, preferred_element_type=jnp.float32
    )
    o_ref[...] = acc.astype(out_dtype)


def int4_matmul(
    x: jax.Array,  # [T, IN]
    q: jax.Array,  # [IN/2, OUT] uint8 (packed nibbles)
    scale: jax.Array,  # [IN/group, OUT] f32 (group-wise along IN)
    *,
    group: int,
    block_out: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x @ dequant4(q, scale) with packed int4 weights streamed
    tile-by-tile (see module docstring for the permuted-contraction
    trick)."""
    t, in_dim = x.shape
    half, out_dim = q.shape
    assert half * 2 == in_dim, (x.shape, q.shape)
    assert group % 2 == 0 and group >= 2
    block_out = min(block_out, out_dim)
    if out_dim % block_out:
        raise ValueError(f"out dim {out_dim} % block {block_out} != 0")
    if not fits_vmem_budget4(in_dim, block_out, x.nbytes):
        raise ValueError(
            f"int4_matmul tile budget exceeded (in={in_dim}, "
            f"block={block_out}, T={t})"
        )
    # Permute the contraction to [evens | odds] (cheap: x is the small
    # activation).  The kernel's two dots undo the nibble packing.
    x2 = jnp.concatenate([x[:, 0::2], x[:, 1::2]], axis=-1)
    kernel = functools.partial(
        _kernel4, group=group, out_dtype=x.dtype
    )
    return pl.pallas_call(
        kernel,
        name="vdt_int4_matmul",
        grid=(out_dim // block_out,),
        in_specs=[
            pl.BlockSpec((t, in_dim), lambda j: (0, 0)),
            pl.BlockSpec((half, block_out), lambda j: (0, j)),
            pl.BlockSpec(
                (scale.shape[0], block_out), lambda j: (0, j)
            ),
        ],
        out_specs=pl.BlockSpec((t, block_out), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((t, out_dim), x.dtype),
        compiler_params=_compiler_params(
            int4_vmem_bytes(in_dim, block_out, x.nbytes)
        ),
        interpret=interpret,
    )(x2, q, scale.astype(jnp.float32))


def int8_matmul(
    x: jax.Array,  # [T, IN]
    q: jax.Array,  # [IN, OUT] int8
    scale: jax.Array,  # [OUT] f32 (per output channel)
    *,
    block_out: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """x @ (q * scale) with int8 weights streamed tile-by-tile."""
    t, in_dim = x.shape
    in_q, out_dim = q.shape
    assert in_q == in_dim, (x.shape, q.shape)
    block_out = min(block_out, out_dim)
    if out_dim % block_out:
        raise ValueError(f"out dim {out_dim} % block {block_out} != 0")
    # [8192, 512] int8 = 4 MB/tile; x [T<=256, 8192] bf16 = 4 MB.  Bigger
    # in_dims would need an inner K loop; serving shapes fit.
    if not fits_vmem_budget(in_dim, block_out, x.nbytes):
        raise ValueError(
            f"int8_matmul tile budget exceeded (in={in_dim}, "
            f"block={block_out}, T={t})"
        )
    kernel = functools.partial(_kernel, out_dtype=x.dtype)
    return pl.pallas_call(
        kernel,
        name="vdt_int8_matmul",
        grid=(out_dim // block_out,),
        in_specs=[
            pl.BlockSpec((t, in_dim), lambda j: (0, 0)),
            pl.BlockSpec((in_dim, block_out), lambda j: (0, j)),
            pl.BlockSpec((1, block_out), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((t, block_out), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((t, out_dim), x.dtype),
        compiler_params=_compiler_params(
            int8_vmem_bytes(in_dim, block_out, x.nbytes)
        ),
        interpret=interpret,
    )(x, q, scale.reshape(1, -1).astype(jnp.float32))
