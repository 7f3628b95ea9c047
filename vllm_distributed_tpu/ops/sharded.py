"""shard_map partitioning of the Pallas paged-attention ops over "tp".

GSPMD cannot partition a Pallas custom call: under a tp>1 mesh it either
replicates the kernel (wrong memory/compute) or fails to lower.  The
runner therefore wraps the production kernels in ``jax.shard_map`` so
each device runs the kernel on its *local* head shard — q heads shard
over "tp", and the combined KV pool ``[2, P, page, HD]`` shards its
flat head×dim lanes (dim 3) over "tp", which is exactly per-kv-head
sharding because HD stores heads contiguously (``HD/tp = (Hkv/tp)*D``).
Per-head attention is embarrassingly parallel, so sharded outputs are
bit-identical to the unsharded kernel.  The matmuls around the kernels
stay GSPMD-partitioned; the row-parallel ``wo`` all-reduce is still
inserted by XLA outside the shard_map region.

This is the TPU-native analog of the reference's per-rank attention:
each NCCL rank runs CUDA attention on its head shard inside vLLM
workers (SURVEY.md §2.2, §2.4 TP row; TP-group discipline
launch.py:211-247).

dp>1 is not supported on this path: the KV pool is replicated over "dp",
and a manual per-shard write would diverge the replicas (each dp group
writes different tokens).  The runner keeps the XLA scatter/gather path
for dp>1, where GSPMD maintains replica consistency.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.ops.attention import AttentionMetadata

# Attention metadata is replicated: every device sees every sequence's
# block table / lengths; only heads are sharded.
_META_SPECS = AttentionMetadata(
    q_seq_ids=P(),
    q_positions=P(),
    slot_mapping=P(),
    block_tables=P(),
    seq_lens=P(),
    logits_indices=P(),
    chunk_starts=P(),
)

_Q_SPEC = P(None, "tp", None)  # [T, Hq, D] — heads sharded
# [2, P, page, HD] — flat head lanes sharded (== per-kv-head sharding).
_KV_SPEC = P(None, None, None, "tp")
# [S, 2, K, HD] staged decode side buffer — same lane sharding.
_SIDE_SPEC = P(None, None, None, "tp")


def _check_divisible(mesh: Mesh, num_heads: int, num_kv_heads: int) -> None:
    tp = mesh.shape.get("tp", 1)
    if num_heads % tp or num_kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide num_heads={num_heads} and "
            f"num_kv_heads={num_kv_heads} to shard the Pallas kernels"
        )


def _pool_spec(kv_pages):
    """Sharding spec(s) for a pool operand: an int8 pool is a (data,
    scales) tuple — the scale plane's lane axis is kv heads, which
    shards over tp exactly like the data plane's per-head HD lanes."""
    if isinstance(kv_pages, tuple):
        return (_KV_SPEC, _KV_SPEC)
    return _KV_SPEC


def shard_attention(attn_fn, mesh: Mesh):
    """Wrap a paged-attention kernel to run per-tp-shard under shard_map."""
    tp = mesh.shape.get("tp", 1)

    def wrapped(
        q, kv_pages, metadata, *,
        num_kv_heads=None, side_kv=None, side_len=None, **kw,
    ):
        hkv = num_kv_heads if num_kv_heads is not None else q.shape[1]
        has_side = side_kv is not None

        def body(q_, kv_, m_, *side_args):
            if side_args:
                kw.update(side_kv=side_args[0], side_len=side_args[1])
            return attn_fn(q_, kv_, m_, num_kv_heads=hkv // tp, **kw)

        in_specs = [_Q_SPEC, _pool_spec(kv_pages), _META_SPECS]
        operands = [q, kv_pages, metadata]
        if has_side:
            in_specs += [_SIDE_SPEC, P()]
            operands += [side_kv, side_len]
        f = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=_Q_SPEC,
            check_vma=False,
        )
        return f(*operands)

    wrapped.needs_max_q = getattr(attn_fn, "needs_max_q", False)
    wrapped.step_counts = getattr(attn_fn, "step_counts", None)
    return wrapped


def shard_kv_flush(flush_fn, mesh: Mesh):
    """Wrap the staged-decode flush kernel to run per-tp-shard: pool and
    side buffer shard their flat head lanes; tables/lengths replicate."""

    def wrapped(kv_pages, side_kv, block_tables, base_lens, n_side):
        spec = _pool_spec(kv_pages)
        f = jax.shard_map(
            flush_fn,
            mesh=mesh,
            in_specs=(spec, _SIDE_SPEC, P(), P(), P()),
            out_specs=spec,
            check_vma=False,
        )
        return f(kv_pages, side_kv, block_tables, base_lens, n_side)

    return wrapped
