"""Latent attention and the routed layer of one LongCat-Flash layer on
the chip, at the benchmark configuration's widths (PERF.md, PR 34).

``vdt_mla_attention`` alone: the fused scan's call (64 sequences of
``--ctx`` cached tokens, the staged side buffer) and single steps of
``--tokens`` rows in one chunk a sequence of 8.  The routed layer
(``LongcatFlashForCausalLM._moe``: float32 router, the held experts'
products over their gathered rows) at ``--tokens`` rows.  Times are host-clock seconds
over back-to-back calls that end in ``block_until_ready``.

Usage: python tools/mla_microbench.py [--tokens 64 256 2048] [--ctx 1024]
       python tools/mla_microbench.py --cpu-rehearsal   (toy widths,
       interpreted kernel: counts nothing, proves the script runs)
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from vllm_distributed_tpu.ops.attention import AttentionMetadata
from vllm_distributed_tpu.ops.pallas.mla_attention import mla_attention

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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, nargs="+", default=[64, 256, 2048])
    ap.add_argument("--ctx", type=int, default=1024)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    toy = args.cpu_rehearsal
    if toy:
        os.environ["JAX_PLATFORMS"] = "cpu"
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
