"""Standalone paged-attention kernel microbenchmark (real TPU).

Times the decode-shape kernel in isolation to attribute the ~180 GB/s
effective bandwidth PERF.md measured: per-page DMA descriptor issue
rate vs DMA size.  Sweeps page_size (descriptor count at constant
bytes) so the two explanations separate.

Measurement notes: each measurement runs the kernel n times inside ONE
jitted fori_loop with a data dependence (q perturbed by the previous
output) and syncs via device_get of a scalar; two loop counts are
differenced to cancel the dispatch overhead.

Usage: python tools/attn_microbench.py [--pages 16 32 64]
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from vllm_distributed_tpu.ops.attention import AttentionMetadata
from vllm_distributed_tpu.ops.pallas.paged_attention import paged_attention


def _time_chained(fn, n_small=8, n_big=104):
    """Per-iteration seconds of fn-in-fori_loop, dispatch cost cancelled."""

    def run(n):
        r = fn(n)
        _ = jax.device_get(r)
        t0 = time.perf_counter()
        r = fn(n)
        _ = jax.device_get(r)
        return time.perf_counter() - t0

    run(n_small)  # compile both variants before timing
    run(n_big)
    ts = min(run(n_small) for _ in range(5))
    tb = min(run(n_big) for _ in range(5))
    return max(tb - ts, 1e-9) / (n_big - n_small)


def run(name, *, s, seq_len, hq, hkv, d_pool, d_q, page_size):
    rng = np.random.default_rng(0)
    pages_per_seq = -(-seq_len // page_size)
    p_total = s * pages_per_seq + 1
    q0 = jnp.asarray(rng.normal(size=(s, hq, d_q)), jnp.bfloat16)
    kv = jnp.asarray(
        rng.normal(size=(2, p_total, page_size, hkv * d_q)), jnp.bfloat16
    )
    bt = (
        rng.permutation(np.arange(1, p_total))
        .reshape(s, pages_per_seq)
        .astype(np.int32)
    )
    meta = AttentionMetadata(
        q_seq_ids=jnp.arange(s, dtype=jnp.int32),
        q_positions=jnp.full(s, seq_len - 1, jnp.int32),
        slot_mapping=jnp.zeros(s, jnp.int32),
        block_tables=jnp.asarray(bt),
        seq_lens=jnp.full(s, seq_len, jnp.int32),
        logits_indices=jnp.arange(s, dtype=jnp.int32),
        chunk_starts=jnp.full(s, seq_len - 1, jnp.int32),
    )

    @partial(jax.jit, static_argnames="n")
    def chained(q, kv, meta, n):
        def body(i, q):
            out = paged_attention(
                q, kv, meta, scale=0.125, num_kv_heads=hkv, max_q=1
            )
            return q + (out * 1e-30).astype(q.dtype)

        q = jax.lax.fori_loop(0, n, body, q)
        return jnp.sum(q, dtype=jnp.float32)

    dt = _time_chained(lambda n: chained(q0, kv, meta, n))
    kv_bytes = 2 * s * pages_per_seq * page_size * hkv * d_q * 2
    n_desc = s * pages_per_seq
    print(
        f"{name:20s} page={page_size:3d} {dt*1e6:8.1f} us/exec  "
        f"{kv_bytes/dt/1e9:7.1f} GB/s  {n_desc:6d} DMAs "
        f"({kv_bytes/n_desc/1024:.0f} KiB each, {n_desc/dt/1e6:5.1f} M desc/s)"
    )
    return dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pages", type=int, nargs="+", default=[16, 32, 64])
    args = ap.parse_args()
    print(f"backend={jax.default_backend()} dev={jax.devices()[0].device_kind}")
    for ps in args.pages:
        # 1B decode shape: 32 seqs x 2048 ctx, hkv=8, head_dim 64 -> 128 pad
        run("1b_b32_ctx2048", s=32, seq_len=2048, hq=32, hkv=8,
            d_pool=128, d_q=64, page_size=ps)
    for ps in args.pages:
        # 7B decode shape: 32 seqs x 1024 ctx, MHA hkv=32, d=128
        run("7b_b32_ctx1024", s=32, seq_len=1024, hq=32, hkv=32,
            d_pool=128, d_q=128, page_size=ps)


if __name__ == "__main__":
    main()
