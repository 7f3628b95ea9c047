"""Standalone paged-attention kernel microbenchmark (real TPU).

Times the decode-shape kernel in isolation to attribute the ~180 GB/s
effective bandwidth PERF.md measured: per-page DMA descriptor issue
rate vs DMA size.  Sweeps page_size (descriptor count at constant
bytes) so the two explanations separate.

Measurement notes: each measurement runs the kernel n times inside ONE
jitted fori_loop with a data dependence (q perturbed by the previous
output) and syncs via device_get of a scalar; two loop counts are
differenced to cancel the dispatch overhead.

``--mixed``: one layer's call of a mixed step at Mixtral's widths (Hq 32,
Hkv 8, D 128, page 16, a 256-page table): 31 single-token sequences of
~660 tokens of context beside one fresh prompt chunk, and the fused
scan's call (64 rows, 31 live, the side buffer) as the control.  Prints
ms a call and the query tiles the kernel walks against the dense
[sequences x longest chunk] grid, one JSON line each.

Usage: python tools/attn_microbench.py [--pages 16 32 64]
       python tools/attn_microbench.py --mixed [--chunks 128 512 2048]
       python tools/attn_microbench.py --mixed --cpu-rehearsal   (toy
       widths, interpreted kernel: times nothing, proves the script runs)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from vllm_distributed_tpu.ops.attention import AttentionMetadata
from vllm_distributed_tpu.ops.pallas import paged_attention as pa
from vllm_distributed_tpu.ops.pallas.paged_attention import paged_attention
from vllm_distributed_tpu.utils import next_power_of_2


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


def run_mixed(name, q_lens, ctx_lens, *, s_pad, pages_pad, hq, hkv, d,
              page_size, dtype, side_k, interpret, loops, out, t_min=16):
    """One call over sequences of ``q_lens`` query tokens whose contexts
    (their own queries included) are ``ctx_lens``; ``side_k`` > 0 makes
    it the fused scan's call (staged rows in a side buffer)."""
    rng = np.random.default_rng(0)
    t_real = sum(q_lens)
    t_pad = max(next_power_of_2(t_real), t_min)
    max_q = next_power_of_2(max(q_lens))
    p_total = sum(-(-c // page_size) for c in ctx_lens) + side_k + 1
    seq_ids = np.full(t_pad, s_pad, np.int32)
    positions = np.zeros(t_pad, np.int32)
    bt = np.zeros((s_pad, pages_pad), np.int32)
    seq_lens = np.zeros(s_pad, np.int32)
    chunk_starts = np.zeros(s_pad, np.int32)
    cursor, page = 0, 1
    for i, (n, ctx) in enumerate(zip(q_lens, ctx_lens)):
        n_pages = -(-(ctx + side_k) // page_size)
        bt[i, :n_pages] = np.arange(page, page + n_pages)
        page += n_pages
        seq_lens[i] = ctx - n if side_k else ctx  # staged: the pool's
        chunk_starts[i] = ctx - n
        positions[cursor : cursor + n] = np.arange(ctx - n, ctx)
        seq_ids[cursor : cursor + n] = i
        cursor += n
    meta = AttentionMetadata(
        q_seq_ids=jnp.asarray(seq_ids),
        q_positions=jnp.asarray(positions),
        slot_mapping=jnp.zeros(t_pad, jnp.int32),
        block_tables=jnp.asarray(bt),
        seq_lens=jnp.asarray(seq_lens),
        logits_indices=jnp.zeros(s_pad, jnp.int32),
        chunk_starts=jnp.asarray(chunk_starts),
    )
    q0 = jnp.asarray(rng.normal(size=(t_pad, hq, d)), dtype)
    kv = jnp.asarray(
        rng.normal(size=(2, max(p_total, page), page_size, hkv * d)), dtype
    )
    kw = {}
    if side_k:
        kw["side_kv"] = jnp.asarray(
            rng.normal(size=(s_pad, 2, side_k, hkv * d)), dtype
        )
        kw["side_len"] = jnp.asarray([1], jnp.int32)

    # Pool, metadata and side buffer are arguments: closed over, they
    # would be baked into the program as constants.
    @partial(jax.jit, static_argnames="n")
    def chained(q, kv, meta, kw, n):
        def body(i, q):
            o = paged_attention(
                q, kv, meta, scale=0.088, num_kv_heads=hkv, max_q=max_q,
                interpret=interpret, **kw,
            )
            return q + (o * 1e-30).astype(q.dtype)

        q = jax.lax.fori_loop(0, n, body, q)
        return jnp.sum(q, dtype=jnp.float32)

    dt = _time_chained(lambda n: chained(q0, kv, meta, kw, n), *loops)
    line = dict(
        name=name, device=jax.devices()[0].device_kind, t_pad=t_pad,
        max_q=max_q, s_pad=s_pad, pages_pad=pages_pad, seqs=len(q_lens),
        tokens=t_real, kv_tokens=int(sum(ctx_lens)),
        # an interpreted kernel on the CPU has no time worth a name
        ms_per_call=None if interpret else dt * 1e3,
    )
    counts = getattr(pa, "query_tile_counts", None)
    if counts is not None:  # a tree from before the tile list has none
        line["q_tiles"], line["q_rows"] = counts(
            q_lens, max_q=max_q, num_q_heads=hq, num_kv_heads=hkv,
            head_dim=d,
        )
    print(json.dumps(line), flush=True)
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "a") as fh:
            fh.write(json.dumps(line) + "\n")


def mixed(args):
    if args.cpu_rehearsal:
        shape = dict(hq=4, hkv=2, d=64, page_size=16, dtype=jnp.float32,
                     interpret=True, loops=(1, 2), pages_pad=8)
        ctx, s_pad, scan_s, chunks, budget = 40, 8, 8, [32], 64
        n_single = 3
    else:
        if jax.default_backend() != "tpu":
            sys.exit("attn_microbench measures the chip; no TPU here "
                     "(--cpu-rehearsal runs the script at toy widths)")
        shape = dict(hq=32, hkv=8, d=128, page_size=16, dtype=jnp.bfloat16,
                     interpret=False, loops=(4, 24), pages_pad=256)
        ctx, s_pad, scan_s, chunks, budget = 660, 32, 64, args.chunks, 2048
        n_single = 31
    common = dict(out=args.out, **shape)
    run_mixed("scan_call", [1] * n_single, [ctx] * n_single, s_pad=scan_s,
              side_k=8, t_min=scan_s, **common)
    run_mixed("decode_only_step", [1] * n_single, [ctx] * n_single,
              s_pad=s_pad, side_k=0, **common)
    for q in chunks:
        chunk = min(q, budget - n_single)  # the step's token budget
        run_mixed(f"mixed_q{q}", [1] * n_single + [chunk],
                  [ctx] * n_single + [chunk], s_pad=s_pad, side_k=0,
                  **common)
    # two chunks of unequal length, one a continuation of a long prompt
    if not args.cpu_rehearsal:
        run_mixed("mixed_two_chunks", [1] * 30 + [700, 300],
                  [ctx] * 30 + [1724, 300], s_pad=s_pad, side_k=0, **common)
        run_mixed("prefill_only_q2048", [2048], [2048], s_pad=s_pad,
                  side_k=0, **common)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pages", type=int, nargs="+", default=[16, 32, 64])
    ap.add_argument("--mixed", action="store_true")
    ap.add_argument("--chunks", type=int, nargs="+",
                    default=[128, 512, 2048])
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/attn_microbench.jsonl")
    args = ap.parse_args()
    if args.mixed:
        return mixed(args)
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
