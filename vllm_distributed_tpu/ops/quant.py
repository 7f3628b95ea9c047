"""Weight-only quantization (int8 per-channel, int4 group-wise).

The route to 70B-class models on v5e HBM (the reference's flagship is an
AWQ 4-bit MoE, /root/reference/.env.server:11 and Dockerfile:5-6 — its
quantized kernels come from flashinfer/vLLM; here the TPU-native design
is: store weights compressed in HBM, dequantize on the fly inside the
jitted step).  XLA fuses the convert+scale into the consuming matmul's
operand read, so the win is exactly what decode needs — HBM traffic
halves (int8) or quarters (int4) while the MXU still sees bf16.

Schemes (both symmetric, no zero points):
- int8: per-output-channel scale.  q = round(w / s), s = max|w_col| / 127.
- int4: group-wise scales along the contraction (input) dim, two nibbles
  packed per uint8 byte.  Group size must divide the *per-shard* input
  dim so group boundaries never straddle a tensor-parallel shard.

``QuantizedTensor`` is a pytree node, so quantized params flow through
jit/device_put/tree.map like plain arrays; partition specs mirror the
structure via ``quant_spec``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.logger import init_logger

logger = init_logger(__name__)

METHODS = ("int8", "int4")


@jax.tree_util.register_pytree_node_class
@dataclass
class QuantizedTensor:
    """Compressed weight + scales; dequantizes to ``shape``/``dtype``.

    int8: q [..., in, out] int8, scale [..., out].
    int4: q [..., in/2, out] uint8 (low nibble = even input row),
          scale [..., in/group, out].

    ``matmul`` is the execution backend stamped at LOAD time per tensor
    ("dequant" | "pallas" | "pallas_interpret") — carried on the tensor,
    not in module state, so multiple engines in one process can't flip
    each other's path on a retrace.
    """

    q: Any
    scale: Any
    bits: int
    group: int  # 0 for per-channel (int8)
    shape: tuple  # logical (dequantized) shape
    dtype: Any  # logical dtype
    matmul: str = "dequant"
    # Stamped at device placement under a mesh: the weight's logical
    # partition entries for (input, output) dims plus the mesh itself,
    # so quant_matmul can shard_map the streaming kernel per tp shard.
    spec: Any = None
    mesh: Any = None

    def tree_flatten(self):
        return (self.q, self.scale), (
            self.bits,
            self.group,
            self.shape,
            self.dtype,
            self.matmul,
            self.spec,
            self.mesh,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def nbytes(self) -> int:
        return self.q.nbytes + self.scale.nbytes


def _pow2_divisor(n: int, cap: int) -> int:
    g = 1
    while g * 2 <= cap and n % (g * 2) == 0:
        g *= 2
    return g


def pick_group_size(in_dim: int, shards: int = 1, cap: int = 128) -> int:
    """Largest power-of-2 group size <= cap dividing the per-shard input
    dim (so int4 group boundaries align with tp shard boundaries)."""
    per_shard = in_dim // shards if shards and in_dim % shards == 0 else in_dim
    return _pow2_divisor(per_shard, cap)


def quantize(
    w, bits: int, group: int = 0, dtype=None, matmul: str = "dequant"
) -> QuantizedTensor:
    """Quantize [..., in, out] weights.  Host (numpy) or device arrays.
    `dtype` records the logical dtype dequantization restores."""
    is_jax = isinstance(w, jax.Array)
    xp = jnp if is_jax else np
    wf = w.astype(xp.float32) if is_jax else np.asarray(w, np.float32)
    shape, in_dim = wf.shape, wf.shape[-2]
    if bits == 8:
        s = xp.max(xp.abs(wf), axis=-2) / 127.0  # [..., out]
        s = xp.maximum(s, 1e-8)
        q = xp.clip(xp.round(wf / s[..., None, :]), -127, 127).astype(xp.int8)
        return QuantizedTensor(
            q, s.astype(xp.float32), 8, 0, shape, dtype, matmul
        )
    if bits == 4:
        if group <= 0:
            group = pick_group_size(in_dim)
        if in_dim % group or in_dim % 2:
            raise ValueError(
                f"int4 needs input dim ({in_dim}) divisible by the group "
                f"size ({group}) and by 2"
            )
        g = wf.reshape(*shape[:-2], in_dim // group, group, shape[-1])
        s = xp.max(xp.abs(g), axis=-2) / 7.0  # [..., in/group, out]
        s = xp.maximum(s, 1e-8)
        q = xp.clip(xp.round(g / s[..., None, :]), -8, 7) + 8
        q = q.reshape(*shape[:-1], shape[-1]).astype(xp.uint8)
        packed = (q[..., 0::2, :] | (q[..., 1::2, :] << 4)).astype(xp.uint8)
        return QuantizedTensor(
            packed, s.astype(xp.float32), 4, group, shape, dtype, matmul
        )
    raise ValueError(f"unsupported bits {bits} (use 8 or 4)")


def dequantize(qt: QuantizedTensor, dtype=jnp.bfloat16) -> jax.Array:
    """In-graph dequantize; XLA fuses this into the consuming matmul."""
    dtype = qt.dtype or dtype
    if qt.bits == 8:
        return (
            qt.q.astype(jnp.float32) * qt.scale[..., None, :]
        ).astype(dtype)
    low = (qt.q & 0xF).astype(jnp.int32)
    high = (qt.q >> 4).astype(jnp.int32)
    in_dim = qt.shape[-2]
    # Inverse of packed[i] = (row 2i | row 2i+1 << 4): interleave pairs
    # back onto the input dim.
    q = jnp.stack([low, high], axis=-2).reshape(
        *qt.shape[:-2], in_dim, qt.shape[-1]
    )
    grouped = q.reshape(
        *qt.shape[:-2], in_dim // qt.group, qt.group, qt.shape[-1]
    )
    w = (grouped.astype(jnp.float32) - 8.0) * qt.scale[..., None, :]
    return w.reshape(*qt.shape).astype(dtype)


def maybe_dequantize(w, dtype) -> jax.Array:
    if isinstance(w, QuantizedTensor):
        return dequantize(w, dtype)
    return w.astype(dtype)


def pick_matmul_mode(quant_method: str | None) -> str:
    """Execution backend for quantized matmuls, decided at load time:
    "pallas" streams compressed tiles through the Pallas kernels —
    int8 single-chip and per-tp-shard under shard_map; int4 single-chip
    (tp>1 int4 dequantizes in-graph at call time).  The backend is the
    one VDT_USE_PALLAS names ("reference" = dequant-in-graph);
    non-quantized stays "dequant"."""
    if quant_method not in ("int8", "int4"):
        return "dequant"
    from vllm_distributed_tpu import envs
    from vllm_distributed_tpu.ops import resolve_kernel_backend

    backend = resolve_kernel_backend(envs.VDT_USE_PALLAS)
    return "dequant" if backend == "reference" else backend


def _pick_block(
    out_dim: int, in_dim: int, x_nbytes: int, bits: int = 8
) -> int | None:
    """Largest out-block that divides out_dim and fits the VMEM budget.
    Bigger tiles stream faster ([2048x8192] with blk 2048: 1084 GB/s vs
    723 at blk 512 on v5e) — but the budget only admits them for small
    in_dims (2048-class); 4096/8192-in matmuls cap at 1024/512.  int4
    uses its own (larger-temporaries) budget model."""
    from vllm_distributed_tpu.ops.pallas.quant_matmul import (
        fits_vmem_budget,
        fits_vmem_budget4,
    )

    fits = fits_vmem_budget4 if bits == 4 else fits_vmem_budget
    for blk in (2048, 1024, 512, 256, 128):
        if out_dim % blk == 0 and fits(in_dim, blk, x_nbytes):
            return blk
    return None


def _sharded_int8_matmul(x, w: QuantizedTensor, interpret: bool):
    """Per-tp-shard streaming matmul under shard_map (GSPMD cannot
    partition the Pallas custom call).  Column-parallel weights
    (out dim sharded) keep the output sharded with no collective;
    row-parallel weights (in dim sharded) psum the partial products
    inside the region.  Returns None when the layout is unsupported
    (caller falls back to dequant-in-graph)."""
    from vllm_distributed_tpu.ops.pallas.quant_matmul import int8_matmul

    in_ax, out_ax = w.spec
    mesh = w.mesh
    if in_ax is not None and out_ax is not None:
        return None
    axis = out_ax if out_ax is not None else in_ax
    shards = axis_shards(axis, mesh) if axis is not None else 1
    # Keep the batch dim data-parallel inside the region (dp=1 makes
    # this a no-op; dp>1 must not all-gather the activations).
    dp_ax = "dp" if mesh.shape.get("dp", 1) > 1 else None
    dp = mesh.shape.get("dp", 1) if dp_ax else 1
    if x.shape[0] % dp:
        return None
    if out_ax is not None:
        out_local = w.q.shape[-1] // shards
        blk = _pick_block(out_local, w.q.shape[0], x.nbytes // dp)
        if blk is None:
            return None

        def body(x_, q_, s_):
            return int8_matmul(
                x_, q_, s_, block_out=blk, interpret=interpret
            )

        in_specs = (P(dp_ax), P(None, out_ax), P(out_ax))
        out_specs = P(dp_ax, out_ax)
    else:
        in_local = w.q.shape[0] // shards
        # Each shard's kernel sees x already split over the in dim.
        blk = _pick_block(
            w.q.shape[-1], in_local, x.nbytes // (shards * dp)
        )
        if blk is None:
            return None

        def body(x_, q_, s_):
            part = int8_matmul(
                x_, q_, s_, block_out=blk, interpret=interpret
            )
            if in_ax is not None:
                part = jax.lax.psum(part, in_ax)
            return part

        in_specs = (P(dp_ax, in_ax), P(in_ax, None), P())
        out_specs = P(dp_ax)
    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return f(x, w.q, w.scale)


@functools.cache
def _warn_dequant_in_graph(bits: int, shape: tuple, sharded: bool) -> None:
    """Said once per weight shape (at trace time): a decode-shape call
    that asked for the streaming kernel and got x @ dequantize(w)
    instead is legitimate routing, but it must not be silent."""
    logger.warning(
        "quant_matmul: no streaming-kernel tile for the int%d weight %s%s "
        "(per-shard out dim not a multiple of 128, tile over the VMEM "
        "budget, or int4 under tp); dequantizing in-graph",
        bits,
        shape,
        " per tp shard" if sharded else "",
    )


def quant_matmul(x: jax.Array, w, bias=None) -> jax.Array:
    """x @ w for plain or QuantizedTensor weights.  On the Pallas path
    eligible int8 2D weights stream through ops/pallas/quant_matmul (the
    only HBM traffic is the int8 bytes) — per tp shard under shard_map
    when the weight was placed on a mesh; everything else dequantizes
    in-graph."""
    if isinstance(w, QuantizedTensor):
        from vllm_distributed_tpu.ops.pallas.quant_matmul import (
            int4_matmul,
            int8_matmul,
        )

        interpret = w.matmul == "pallas_interpret"
        eligible = (
            w.matmul != "dequant"
            and w.q.ndim == 2
            and x.ndim == 2
            and x.shape[0] <= 256
        )
        out = None
        if w.bits == 8:
            if eligible and w.mesh is not None and w.spec is not None:
                out = _sharded_int8_matmul(x, w, interpret)
            elif eligible and w.mesh is None:
                blk = _pick_block(w.q.shape[-1], w.q.shape[0], x.nbytes)
                if blk is not None:
                    out = int8_matmul(
                        x, w.q, w.scale, block_out=blk,
                        interpret=interpret,
                    )
        elif (
            w.bits == 4
            and eligible
            and w.mesh is None  # tp>1 int4: dequant-in-graph for now
            and w.group >= 2
            and w.group % 2 == 0
        ):
            blk = _pick_block(w.q.shape[-1], w.shape[-2], x.nbytes, bits=4)
            if blk is not None:
                out = int4_matmul(
                    x, w.q, w.scale, group=w.group, block_out=blk,
                    interpret=interpret,
                )
        if out is None:
            if eligible:
                _warn_dequant_in_graph(w.bits, tuple(w.shape), w.mesh is not None)
            out = x @ dequantize(w, x.dtype)
    else:
        out = x @ w.astype(x.dtype)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


def axis_shards(entry, mesh) -> int:
    names = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for name in names:
        if name is not None:
            n *= mesh.shape.get(name, 1)
    return n


def aligned_spec(spec: P, shape: tuple, mesh) -> P:
    """Drop sharding on any dim the mesh doesn't divide evenly (scales /
    packed nibbles can misalign with shard boundaries; replicating a
    small dim is correct and cheap, and keeps quantization semantics
    independent of the mesh)."""
    out = []
    for pos, entry in enumerate(tuple(spec)):
        if entry is not None and shape[pos] % axis_shards(entry, mesh):
            entry = None
        out.append(entry)
    return P(*out)


def place_quantized(qt: QuantizedTensor, wspec: P, mesh) -> QuantizedTensor:
    """Shard a QuantizedTensor's q/scale parts per the weight's spec."""
    from jax.sharding import NamedSharding

    qs = quant_spec(wspec, qt.bits)
    q_spec = aligned_spec(qs.q, qt.q.shape, mesh)
    return QuantizedTensor(
        jax.device_put(qt.q, NamedSharding(mesh, q_spec)),
        jax.device_put(
            qt.scale,
            NamedSharding(mesh, aligned_spec(qs.scale, qt.scale.shape, mesh)),
        ),
        qt.bits,
        qt.group,
        qt.shape,
        qt.dtype,
        qt.matmul,
        spec=tuple(q_spec) if qt.q.ndim == 2 else None,
        mesh=mesh,
    )


def quant_spec(wspec: P, bits: int) -> QuantizedTensor:
    """PartitionSpec structure mirroring a quantized leaf.

    q shards exactly like the weight (int4 packs along the input dim,
    which preserves divisibility for even per-shard sizes).  Scales drop
    the input dim (int8) or keep a shrunken one (int4)."""
    t = tuple(wspec)
    if len(t) < 2:  # fully/mostly replicated spec: scales replicate too
        return QuantizedTensor(
            q=wspec, scale=P(), bits=bits, group=0, shape=(), dtype=None
        )
    lead, in_ax, out_ax = t[:-2], t[-2], t[-1]
    scale = P(*lead, out_ax) if bits == 8 else P(*lead, in_ax, out_ax)
    return QuantizedTensor(
        q=wspec, scale=scale, bits=bits, group=0, shape=(), dtype=None
    )
