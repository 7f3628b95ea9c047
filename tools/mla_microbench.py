"""Latent attention and the routed layer of one LongCat-Flash layer on
the chip, at the benchmark configuration's widths (PERF.md, PR 34).

``vdt_mla_attention`` alone: the fused scan's call (64 sequences of
``--ctx`` cached tokens, the staged side buffer) and single steps of
``--tokens`` rows in one chunk a sequence of 8.  The routed layer
(``LongcatFlashForCausalLM._moe``: float32 router, the held experts'
products over their gathered rows) at ``--tokens`` rows.  Times are host-clock seconds
over back-to-back calls that end in ``block_until_ready``.

``--fresh`` instead times one sublayer's many-row attention in both
forms (PERF.md, PR 37) at ``--tokens`` rows: three fresh prompts fill
the flat batch (then the same beside 40 decode rows, then with one of
the three a chunk with a cached prefix, so the conditional is taken).
Absorbed: the whole sublayer, its absorb products alone, its kernel and
tile gathers alone.  Materialised: the whole sublayer, the K/V
up-projection alone, ``vdt_mla_chunk_attention`` alone over a sweep of
(query tile, key block, heads a step), and what is left (layout).

``--ctx-mix`` instead times the fused scan's call alone at the
benchmark cell's ragged lengths (PERF.md, PR 39): the 64 cached lengths
are the (prompt, output) pairs of ``benchmark/traffic/chat-closed-64.json``
with a seeded part of each output behind its prompt, in the cell's own
program shape (64 sequences, a 256-page table, the staged side buffer).
Eight calls a jitted function, as a micro-step's eight sublayers make
them; it prints the time a call on the host's clock (``call_us``: the
kernel and the gathers round it), the kernel's own device time from a
traced pass (``kernel_us``: what ``decode_mla_ms`` counts), the rows its
DMAs fetch, the live rows and their GB/s, and the largest difference
from ``mla_attention_reference`` on the device (``max_abs_err``).
``--kv-buf-kib`` sweeps the kernel's buffer-slot budget (the sub-block's
size) in the same process.

Usage: python tools/mla_microbench.py [--tokens 64 256 2048] [--ctx 1024]
       python tools/mla_microbench.py --fresh [--tokens 256 1024 2048]
       python tools/mla_microbench.py --ctx-mix [--seed 0 1 2]
           [--kv-buf-kib 160 320 640 1280]
       python tools/mla_microbench.py --cpu-rehearsal [--fresh]  (toy
       widths, interpreted kernel: counts nothing, proves the script runs)
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    bind_max_q,
    mla_absorb_queries,
    mla_attention_reference,
    mla_sublayer_attention,
    mla_unabsorb_outputs,
)
from vllm_distributed_tpu.ops.pallas import mla_attention as mla
from vllm_distributed_tpu.ops.pallas.mla_attention import (
    mla_attention,
    mla_attention_cpu,
)
from vllm_distributed_tpu.ops.pallas.mla_chunk_attention import (
    TILE_K,
    TILE_Q,
    heads_per_step,
    mla_chunk_attention,
)

PAGE = 16


def _timed(fn, *args, calls: int) -> float:
    for _ in range(3):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls


def _meta(seqs: int, ctx: int, new: int, t_pad: int, s_pad: int,
          pages_pad: int) -> AttentionMetadata:
    """``seqs`` sequences of ``ctx`` cached tokens and ``new`` new ones
    each, pages handed out in order."""
    ids = np.full(t_pad, s_pad, np.int32)
    pos = np.zeros(t_pad, np.int32)
    bt = np.zeros((s_pad, pages_pad), np.int32)
    lens = np.zeros(s_pad, np.int32)
    starts = np.zeros(s_pad, np.int32)
    per = -(-(ctx + new) // PAGE)
    for s in range(seqs):
        ids[s * new:(s + 1) * new] = s
        pos[s * new:(s + 1) * new] = np.arange(ctx, ctx + new)
        bt[s, :per] = 1 + s * per + np.arange(per)
        lens[s], starts[s] = ctx + new, ctx
    z = jnp.zeros
    return AttentionMetadata(
        q_seq_ids=jnp.asarray(ids), q_positions=jnp.asarray(pos),
        slot_mapping=z(t_pad, jnp.int32), block_tables=jnp.asarray(bt),
        seq_lens=jnp.asarray(lens), logits_indices=z(s_pad, jnp.int32),
        chunk_starts=jnp.asarray(starts))


def _step_meta(seqs, t_pad: int, s_pad: int, pages_pad: int):
    """``seqs``: [(cached tokens, new tokens)] in flat-batch order,
    pages handed out in order from page 1."""
    ids = np.full(t_pad, s_pad, np.int32)
    pos = np.zeros(t_pad, np.int32)
    bt = np.zeros((s_pad, pages_pad), np.int32)
    lens = np.zeros(s_pad, np.int32)
    starts = np.zeros(s_pad, np.int32)
    cursor, page = 0, 1
    for s, (cached, new) in enumerate(seqs):
        per = -(-(cached + new) // PAGE)
        ids[cursor:cursor + new] = s
        pos[cursor:cursor + new] = np.arange(cached, cached + new)
        bt[s, :per] = page + np.arange(per)
        lens[s], starts[s] = cached + new, cached
        cursor, page = cursor + new, page + per
    z = jnp.zeros
    return AttentionMetadata(
        q_seq_ids=jnp.asarray(ids), q_positions=jnp.asarray(pos),
        slot_mapping=z(t_pad, jnp.int32), block_tables=jnp.asarray(bt),
        seq_lens=jnp.asarray(lens), logits_indices=z(s_pad, jnp.int32),
        chunk_starts=jnp.asarray(starts)), page


def fresh_case(args, toy: bool) -> dict:
    """One sublayer's many-row attention, absorbed against
    materialised, part by part."""
    h, dn, dr, dv, c_w, wp = (
        (4, 16, 8, 16, 24, 128) if toy else (64, 128, 64, 128, 512, 640))
    dtype = jnp.float32 if toy else jnp.bfloat16
    tokens = [64] if toy else args.tokens
    calls = 1 if toy else args.calls
    ctx = 48 if toy else args.ctx
    s_pad, n_dec = (8, 3) if toy else (64, 40)
    scale = (dn + dr) ** -0.5
    entry = mla_attention_cpu if toy else mla_attention
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)

    def rnd(k, shape, std=1.0):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    w_uk, w_uv = rnd(ks[0], (h, dn, c_w), 0.05), rnd(ks[1], (h, c_w, dv), 0.05)
    out = []
    for t in tokens:
        third = (t - n_dec - 8) // 3
        mixes = {
            "fresh": [(0, third)] * 3,
            "fresh+decode": [(ctx, 1)] * n_dec + [(0, third)] * 3,
            "fresh+decode+prefixed": (
                [(ctx, 1)] * n_dec + [(0, third)] * 2 + [(ctx, third)]),
        }
        pages_pad = 1 << (-(-(ctx + third) // PAGE) - 1).bit_length()
        q_nope, q_rope = rnd(ks[2], (t, h, dn)), rnd(ks[3], (t, h, dr))
        c, k_rope = rnd(ks[4], (t, c_w)), rnd(ks[5], (t, dr))
        for mix, seqs in mixes.items():
            meta, pages = _step_meta(seqs, t, s_pad, pages_pad)
            pool = rnd(ks[6], (1, pages + 1, PAGE, wp))
            ops = (q_nope, q_rope, c, k_rope, w_uk, w_uv, pool, meta)
            line = {"tokens": t, "mix": mix, "chunk": third}
            for form, fn in (
                ("absorbed", functools.partial(entry, max_q=t)),
                ("materialised", bind_max_q(entry, t)),
            ):
                whole = jax.jit(lambda *a, fn=fn: mla_sublayer_attention(
                    fn, *a, scale=scale))
                line[f"{form}_ms"] = _timed(whole, *ops, calls=calls) * 1e3
            out.append(line)
        # the parts, over the three fresh prompts alone
        meta, pages = _step_meta(mixes["fresh"], t, s_pad, pages_pad)
        pool = rnd(ks[6], (1, pages + 1, PAGE, wp))
        parts = {"tokens": t, "mix": "fresh, by part", "chunk": third}
        absorb = jax.jit(lambda qn, qr, lat: (
            mla_absorb_queries(qn, qr, w_uk), mla_unabsorb_outputs(lat, w_uv)))
        parts["absorbed_products_ms"] = _timed(
            absorb, q_nope, q_rope, rnd(ks[7], (t, h, c_w)), calls=calls) * 1e3
        q_abs = mla_absorb_queries(q_nope, q_rope, w_uk)
        kern = jax.jit(lambda q, kv, m: entry(
            q, kv, m, scale=scale, value_width=c_w, max_q=t))
        parts["absorbed_kernel_and_gathers_ms"] = _timed(
            kern, q_abs, pool, meta, calls=calls) * 1e3
        up = jax.jit(lambda c: (
            jnp.dot(c, w_uk.transpose(2, 0, 1).reshape(c_w, h * dn),
                    preferred_element_type=jnp.float32).astype(dtype),
            jnp.dot(c, w_uv.transpose(1, 0, 2).reshape(c_w, h * dv),
                    preferred_element_type=jnp.float32).astype(dtype)))
        parts["up_projection_ms"] = _timed(up, c, calls=calls) * 1e3
        k_nope, v = up(c)
        ids = np.asarray(meta.q_seq_ids)
        fresh = ids < s_pad
        first = {s: int(np.argmax(ids == s)) for s in set(ids[fresh])}
        row_start = np.array(
            [first[s] if f else r for r, (s, f) in enumerate(zip(ids, fresh))],
            np.int32)
        flat = (q_nope.reshape(t, -1), q_rope.reshape(t, -1), k_nope, k_rope,
                v, jnp.asarray(row_start), jnp.asarray(fresh))
        pairs = 3 * third * (third + 1) / 2
        default = (min(TILE_Q, t), min(TILE_K, t),
                   heads_per_step(h, dn, dr, dv))
        sweep = [default] if toy else [
            (tq, tk, g) for tq, tk in ((256, 256), (512, 512), (512, 256),
                                       (256, 128), (128, 128))
            for g in (2, 4, 8) if tq <= t]
        parts["chunk_kernel"] = []
        for tq, tk, g in sweep:
            kern = jax.jit(functools.partial(
                mla_chunk_attention, num_heads=h, scale=scale, tile_q=tq,
                tile_k=tk, heads=g, interpret=toy))
            sec = _timed(kern, *flat, calls=calls)
            parts["chunk_kernel"].append({
                "tile_q": tq, "tile_k": tk, "heads": g, "ms": sec * 1e3,
                "default": (tq, tk, g) == default,
                "tflops_real_pairs": (
                    pairs * h * (dn + dr + dv) * 2 / sec / 1e12)})
        out.append(parts)
    return {"device": str(jax.devices()[0]), "ctx": ctx, "fresh": out}


def cell_ctx_lens(seed: int, seqs: int) -> list[int]:
    """``seqs`` cached lengths as the cell's traffic leaves them in a
    scan: each (prompt, output) pair of the mix's pool with a seeded
    part of its output behind the prompt."""
    from benchmark.loadgen import length_pool

    mix = json.load(open(os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "traffic",
        "chat-closed-64.json")))
    pool = length_pool(mix)
    rng = np.random.default_rng(seed)
    picks = [pool[i % len(pool)] for i in rng.permutation(max(
        seqs, len(pool)))[:seqs]]
    return [p + int(rng.integers(0, max(o, 1))) for p, o in picks]


def _kernel_us(fn, *args) -> float | None:
    """Median device time of one ``vdt_mla_attention`` call in a traced
    pass: the call without the gathers around it, as the benchmark's
    ``decode_mla_ms`` counts it."""
    import statistics
    import tempfile

    from benchmark.trace_reduce import find_xplane, load_events

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(5):
                out = fn(*args)
            jax.block_until_ready(out)
        events = load_events(find_xplane(d))
    durs = [dur for plane in events["devices"].values()
            for name, _, dur in plane.get("XLA Ops", [])
            if name.startswith("vdt_mla_attention")]
    return statistics.median(durs) / 1e3 if durs else None


def ctx_mix_case(args, toy: bool) -> dict:
    """The fused scan's call at the cell's ragged lengths."""
    h, w, wp, vw = (4, 24, 128, 16) if toy else (64, 576, 640, 512)
    dtype = jnp.float32 if toy else jnp.bfloat16
    s_dec, pages_pad, layers = (8, 16, 2) if toy else (64, 256, 8)
    calls = 1 if toy else args.calls
    itemsize = jnp.dtype(dtype).itemsize
    key = jax.random.PRNGKey(0)
    out = {"device": str(jax.devices()[0]), "seqs": s_dec,
           "pages_pad": pages_pad, "calls_a_jit": layers, "ctx_mix": []}
    budgets = args.kv_buf_kib or [mla._KV_BUF_BYTES // 1024]
    for seed in args.seed:
        lens = cell_ctx_lens(seed, s_dec)
        if toy:
            lens = [n % (pages_pad * PAGE - 8) for n in lens]
        meta, pages = _step_meta(
            [(n, 1) for n in lens], s_dec, s_dec, pages_pad)
        # the scan's call: ``seq_lens`` is the pool-resident length
        meta = dataclasses.replace(meta, seq_lens=meta.seq_lens - 1)
        pool = jax.random.normal(
            key, (1, pages + 1, PAGE, wp), jnp.float32).astype(dtype)
        q = jax.random.normal(
            key, (layers, s_dec, h, w), jnp.float32).astype(dtype)
        side = jnp.zeros((s_dec, 1, 8, wp), dtype)
        side_len = jnp.asarray([4], jnp.int32)
        for kib in budgets:
            mla._KV_BUF_BYTES = kib * 1024
            jax.clear_caches()
            blk = mla._kv_blocking(wp, itemsize, h, PAGE, pages_pad)[-1]
            fetched = mla.mla_kv_rows_fetched(
                lens, num_heads=h, value_width=vw, lanes=wp,
                itemsize=itemsize, page_size=PAGE, max_pages=pages_pad)

            def sublayers(q, kv, meta, side, side_len):
                return [mla_attention(
                    q[i], kv, meta, scale=0.07, value_width=vw, max_q=1,
                    side_kv=side, side_len=side_len, interpret=toy)
                    for i in range(layers)]

            fn = jax.jit(sublayers)
            sec = _timed(fn, q, pool, meta, side, side_len,
                         calls=calls) / layers
            kernel_us = None if toy else _kernel_us(
                fn, q, pool, meta, side, side_len)
            ref = mla_attention_reference(
                q[0], pool, meta, scale=0.07, value_width=vw, side_kv=side,
                side_len=side_len)
            err = float(jnp.max(jnp.abs(
                fn(q, pool, meta, side, side_len)[0].astype(jnp.float32)
                - ref.astype(jnp.float32))))
            live = sum(lens)
            out["ctx_mix"].append({
                "seed": seed, "kv_buf_kib": kib, "sub_block": blk,
                "call_us": sec * 1e6, "kernel_us": kernel_us,
                "max_abs_err": err, "rows_live": live,
                "rows_fetched": fetched, "fill": live / fetched,
                "longest": max(lens), "past_1024": sum(
                    n > 1024 for n in lens),
                "live_gb_per_s": live * w * itemsize / sec / 1e9})
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, nargs="+", default=None)
    ap.add_argument("--fresh", action="store_true")
    ap.add_argument("--ctx", type=int, default=1024)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--ctx-mix", action="store_true")
    ap.add_argument("--attention-only", action="store_true",
                    help="skip the routed layer")
    ap.add_argument("--seed", type=int, nargs="+", default=[0])
    ap.add_argument("--kv-buf-kib", type=int, nargs="+", default=None)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    toy = args.cpu_rehearsal
    if toy:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.ctx_mix:
        out = ctx_mix_case(args, toy)
        print(json.dumps(out, indent=1))
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/mla_microbench_ctx_mix.json", "w") as f:
            json.dump(out, f, indent=1)
        return 0
    if args.fresh:
        args.tokens = args.tokens or [256, 1024, 2048]
        out = fresh_case(args, toy)
        print(json.dumps(out, indent=1))
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/mla_microbench_fresh.json", "w") as f:
            json.dump(out, f, indent=1)
        return 0
    args.tokens = args.tokens or [64, 256, 2048]
    h, w, wp, vw = (4, 24, 128, 16) if toy else (64, 576, 640, 512)
    dtype = jnp.float32 if toy else jnp.bfloat16
    ctx = 48 if toy else args.ctx
    tokens = [16, 32] if toy else args.tokens
    calls = 1 if toy else args.calls
    s_dec = 8 if toy else 64
    key = jax.random.PRNGKey(0)
    pages = 2 + max(s_dec * -(-(ctx + 8) // PAGE),
                    8 * -(-(ctx + max(tokens)) // PAGE))
    pool = jax.random.normal(
        key, (1, pages, PAGE, wp), jnp.float32).astype(dtype)
    out: dict = {"device": str(jax.devices()[0]), "ctx": ctx, "attention": [],
                 "routed_layer": []}

    def attend(max_q, **kw):
        return jax.jit(lambda q, kv, meta, *a: mla_attention(
            q, kv, meta, scale=0.07, value_width=vw, max_q=max_q,
            interpret=toy,
            **({"side_kv": a[0], "side_len": a[1]} if a else {})))

    # the fused scan's call
    pages_pad = 1 << (-(-(ctx + 8) // PAGE) - 1).bit_length()
    meta = _meta(s_dec, ctx, 1, s_dec, s_dec, pages_pad)
    # the scan's call: ``seq_lens`` is the pool-resident length
    meta = dataclasses.replace(meta, seq_lens=meta.seq_lens - 1)
    q = jax.random.normal(key, (s_dec, h, w), jnp.float32).astype(dtype)
    side = jnp.zeros((s_dec, 1, 8, wp), dtype)
    sec = _timed(attend(1), q, pool, meta, side, jnp.asarray([4], jnp.int32),
                 calls=calls)
    kv_bytes = s_dec * ctx * w * jnp.dtype(dtype).itemsize
    out["attention"].append({
        "call": "decode", "seqs": s_dec, "ctx": ctx, "ms": sec * 1e3,
        "kv_gb_per_s": kv_bytes / sec / 1e9})
    # single steps: 8 sequences, one chunk each
    for t in tokens:
        new = t // 8
        pages_pad = 1 << (-(-(ctx + new) // PAGE) - 1).bit_length()
        meta = _meta(8, ctx, new, t, 8, pages_pad)
        q = jax.random.normal(key, (t, h, w), jnp.float32).astype(dtype)
        sec = _timed(attend(new), q, pool, meta, calls=calls)
        pairs = 8 * (new * ctx + new * (new + 1) / 2)
        out["attention"].append({
            "call": "step", "tokens": t, "chunk": new, "ctx": ctx,
            "ms": sec * 1e3,
            "tflops": pairs * h * (w + vw) * 2 / sec / 1e12})

    if args.attention_only:
        print(json.dumps(out, indent=1))
        return 0

    # the routed layer
    from vllm_distributed_tpu import testing
    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.models.registry import get_model_class

    if toy:
        d = testing.write_longcat_config()
    else:
        import tempfile

        conf = json.load(open(os.path.join(
            os.path.dirname(__file__), "..", "benchmark", "configs",
            "longcat-flash-chat-ep32-bf16.json")))
        d = tempfile.mkdtemp()
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump({k: v for k, v in conf.items() if k != "benchmark"}, f)
    mc = EngineArgs(model=d, skip_tokenizer_init=True, load_format="dummy",
                    num_kv_pages=16).create_engine_config().model_config
    model = get_model_class(mc.architecture)(mc)
    hid, e, im = model.hidden_size, model.num_held, model.expert_ffn_size
    ks = jax.random.split(key, 5)

    def nrm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(
            model.dtype)

    layer = {"router": nrm(ks[0], (hid, model.router_width)),
             "router_bias": jax.random.normal(
                 ks[1], (model.router_width,), jnp.float32
             ) / model.router_width,
             "w1": nrm(ks[2], (e, hid, im)), "w3": nrm(ks[3], (e, hid, im)),
             "w2": nrm(ks[4], (e, im, hid))}
    moe = jax.jit(lambda u, layer: model._moe(
        u, layer, jnp.ones(u.shape[0], bool)))
    for t in tokens:
        u = jax.random.normal(key, (t, hid), jnp.float32).astype(model.dtype)
        sec = _timed(moe, u, layer, calls=calls)
        _, picks = moe(u, layer)
        out["routed_layer"].append({
            "tokens": t, "ms": sec * 1e3,
            "picks_zero_held_absent": [int(x) for x in picks]})
    print(json.dumps(out, indent=1))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mla_microbench.json", "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
