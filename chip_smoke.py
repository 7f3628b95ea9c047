#!/usr/bin/env python3
"""chip_smoke.py — does the serving path start, compile and answer on the TPU?

The quickest standing proof that this repository still runs on the chip.
With no arguments it needs one TPU and drives, at the full width of
Llama-2-7B (random weights from a seed, no network, no tokenizer):

  phase A  every Pallas kernel on the served path, compiled (not
           interpreted) at that model's shapes, against its XLA reference;
  phase B  ``python -m vllm_distributed_tpu serve`` as a user would start
           it, eight overlapping streaming completions of mixed length
           (chunked prefill past the 2048-token step budget, fused decode),
           the same eight one at a time, one with logprobs, ``/metrics``,
           SIGTERM;
  phase C  the same server again on the same compile cache: the same token
           ids, and no persistent-cache miss for a step program.

``--tp 4`` runs the three phases over four chips at the Llama-2-13B width,
once in bf16 and once with int8 weights (``--quantization`` picks one),
and also requires every device's ``bytes_in_use`` to be even after load
and after the KV pool.

This parent process never initialises a JAX backend: a chip belongs to
one process at a time, so every phase is a child that has exited before
the next starts.  With no TPU it says which platform JAX found and exits
non-zero within seconds; nothing is ever run on the CPU unless
``--cpu-rehearsal`` asks for it (tiny shapes, Pallas interpreted, every
line of output labelled ``cpu`` — what the tier-1 test drives).

The last line of stdout is one JSON object, ``{"ok": true, "device":
{...}}`` on success; the full report is ``chiprun_out/chip_smoke*/
result.json`` beside the servers' logs.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import random
import re
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
# The default run, compilation included, must end inside 1200 s (it took
# ~600 s on a v5e).  One --tp 4 server variant at the full 40 layers does
# not: its sharded step programs compile in ~2 min each, so it gets the
# length of one chip-tool call.
TIME_LIMIT_S = {1: 1140, 4: 3300}
PAGE_SIZE = 16
MAX_NUM_SEQS = 16

# The CPU rehearsal's stand-in: same code paths, toy width.
TINY = dict(
    vocab_size=512, hidden=256, intermediate=512, layers=2,
    heads=8, kv_heads=8, max_pos=512, dtype="bfloat16",
)


class SmokeFailure(Exception):
    pass


# --------------------------------------------------------------------------
# Children (these import JAX; the parent below never calls them directly)
# --------------------------------------------------------------------------
def child_probe() -> None:
    import jax
    import jaxlib

    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = None
    dev = jax.devices()
    print(
        json.dumps(
            {
                "platform": dev[0].platform,
                "kind": dev[0].device_kind,
                "count": len(dev),
                "jax": jax.__version__,
                "jaxlib": jaxlib.__version__,
                "libtpu": libtpu,
            }
        )
    )


def child_kernels(cfg: dict) -> None:
    """Phase A: compile and run each kernel once, compare with XLA."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from vllm_distributed_tpu.ops import quant, sharded
    from vllm_distributed_tpu.ops.attention import (
        AttentionMetadata,
        kv_scales_shape,
        paged_attention_reference,
        quantize_kv_heads,
        write_kv_pages,
    )
    from vllm_distributed_tpu.ops.pallas.kv_flush import kv_flush
    from vllm_distributed_tpu.ops.pallas.paged_attention import (
        paged_attention,
    )
    from vllm_distributed_tpu.ops.pallas.quant_matmul import int4_matmul

    interpret = cfg["interpret"]
    tp = cfg["tp"]
    mesh = None
    if tp > 1:
        from vllm_distributed_tpu.config import ParallelConfig
        from vllm_distributed_tpu.distributed.mesh import build_mesh

        mesh = build_mesh(ParallelConfig(tensor_parallel_size=tp))
    results: list[dict] = []

    def check(name, fn):
        t0 = time.monotonic()
        try:
            detail = fn()
            results.append({"kernel": name, "ok": True, **detail})
        except Exception as e:  # noqa: BLE001 — the compiler's message
            # is the finding; carry on to the other kernels.
            results.append(
                {
                    "kernel": name,
                    "ok": False,
                    "error": f"{type(e).__name__}: {e}"[-3000:],
                }
            )
        results[-1]["seconds"] = round(time.monotonic() - t0, 1)
        print(f"[kernels] {json.dumps(results[-1])}", flush=True)

    def attn_fns(hq, hkv):
        attn = lambda *a, **k: paged_attention(*a, interpret=interpret, **k)
        flush = lambda *a, **k: kv_flush(*a, interpret=interpret, **k)
        if mesh is not None:
            sharded._check_divisible(mesh, hq, hkv)
            attn = sharded.shard_attention(attn, mesh)
            flush = sharded.shard_kv_flush(flush, mesh)
        return attn, flush

    def make_pool(key, num_pages, hd, dtype, hkv, kv_int8):
        pool = jax.random.normal(
            key, (2, num_pages, PAGE_SIZE, hd), jnp.float32
        ).astype(dtype)
        if kv_int8:
            q, s = quantize_kv_heads(pool, hkv)
            assert s.shape == kv_scales_shape(num_pages, PAGE_SIZE, hkv)
            pool = (q, s)
        if mesh is not None:
            pool = jax.device_put(
                pool, NamedSharding(mesh, P(None, None, None, "tp"))
            )
        return pool

    def max_err(got, want):
        return float(
            jnp.max(
                jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
            )
        )

    # bf16 data, f32 accumulation on both sides, but TPU f32 dots run
    # bf16 passes on the MXU and the flash and direct softmax round
    # differently: agreement is bf16-scale (eps 7.8e-3), not f32-scale.
    ATTN_TOL = 2e-2
    MM_TOL = 2e-2

    def decode_case(m, *, kv_int8, side):
        """One fused-decode micro-step: S sequences of mixed context, one
        query each, this dispatch's rows in the staged side buffer."""
        hq, hkv, d = m["heads"], m["kv_heads"], m["head_dim"]
        hd, dtype = hkv * d, jnp.dtype(m["dtype"])
        s, k_steps, ml = m["num_seqs"], m["decode_steps"], m["max_model_len"]
        max_pages = ml // PAGE_SIZE
        fracs = [0.97, 0.73, 0.3, 0.075, 0.01, 0.004, 0.0003]
        lens = [max(int(ml * f), 1) for f in fracs][: s - 1]
        lens += [0] * (s - len(lens))  # padding rows
        tables = np.zeros((s, max_pages), np.int32)
        nxt = 1  # page 0 is the engine's reserved dump page
        for i, n in enumerate(lens):
            if n:
                need = -(-(n + k_steps) // PAGE_SIZE)
                tables[i, :need] = np.arange(nxt, nxt + need)
                nxt += need
        keys = jax.random.split(jax.random.PRNGKey(m["seed"]), 3)
        pool = make_pool(keys[0], nxt, hd, dtype, hkv, kv_int8)
        q = jax.random.normal(keys[1], (s, hq, d), jnp.float32).astype(dtype)
        base = np.asarray(lens, np.int32)
        live = base > 0
        n_side = min(5, k_steps)
        kw = {}
        if side:
            kw = dict(
                side_kv=jax.random.normal(
                    keys[2], (s, 2, k_steps, hd), jnp.float32
                ).astype(dtype),
                side_len=jnp.asarray([n_side], jnp.int32),
            )
        pos = base + (n_side - 1 if side else -1)
        meta = AttentionMetadata(
            q_seq_ids=jnp.asarray(np.where(live, np.arange(s), s), jnp.int32),
            q_positions=jnp.asarray(np.maximum(pos, 0), jnp.int32),
            slot_mapping=jnp.zeros(s, jnp.int32),
            block_tables=jnp.asarray(tables),
            seq_lens=jnp.asarray(base),
            logits_indices=jnp.zeros(s, jnp.int32),
            chunk_starts=jnp.asarray(np.maximum(pos, 0), jnp.int32),
        )
        attn, _ = attn_fns(hq, hkv)
        common = dict(scale=d**-0.5, num_kv_heads=hkv)
        got = jax.jit(
            lambda q, pool, meta, kw: attn(
                q, pool, meta, max_q=1, **common, **kw
            )
        )(q, pool, meta, kw)
        want = paged_attention_reference(q, pool, meta, **common, **kw)
        err = max_err(got[live], want[live])
        if not err <= ATTN_TOL:
            raise AssertionError(f"max abs err {err} > {ATTN_TOL}")
        return {
            "max_abs_err": err,
            "ref_max": float(jnp.max(jnp.abs(want[live]))),
            "contexts": lens,
            "pages": int(nxt),
        }

    def prefill_case(m, *, kv_int8):
        """One chunk of a long prompt: `chunk` queries whose sequence
        already holds half a chunk, i.e. the kernel's multi-q-block,
        multi-kv-block path.  The paged XLA reference gathers [T, ctx]
        per token and cannot hold this shape, so the oracle is dense
        causal attention over the gathered pages."""
        hq, hkv, d = m["heads"], m["kv_heads"], m["head_dim"]
        hd, dtype = hkv * d, jnp.dtype(m["dtype"])
        chunk, ml = m["chunk"], m["max_model_len"]
        start = chunk // 2
        ctx = start + chunk
        s = 8  # the runner's minimum sequence bucket
        max_pages = ml // PAGE_SIZE
        n_pages = -(-ctx // PAGE_SIZE)
        tables = np.zeros((s, max_pages), np.int32)
        perm = np.random.default_rng(m["seed"]).permutation(n_pages) + 1
        tables[0, :n_pages] = perm
        keys = jax.random.split(jax.random.PRNGKey(m["seed"] + 1), 2)
        pool = make_pool(keys[0], n_pages + 1, hd, dtype, hkv, kv_int8)
        q = jax.random.normal(
            keys[1], (chunk, hq, d), jnp.float32
        ).astype(dtype)
        pos = np.arange(start, ctx, dtype=np.int32)
        seq_lens = np.zeros(s, np.int32)
        seq_lens[0] = ctx
        starts = np.zeros(s, np.int32)
        starts[0] = start
        meta = AttentionMetadata(
            q_seq_ids=jnp.zeros(chunk, jnp.int32),
            q_positions=jnp.asarray(pos),
            slot_mapping=jnp.zeros(chunk, jnp.int32),
            block_tables=jnp.asarray(tables),
            seq_lens=jnp.asarray(seq_lens),
            logits_indices=jnp.zeros(s, jnp.int32),
            chunk_starts=jnp.asarray(starts),
        )
        attn, _ = attn_fns(hq, hkv)
        scale = d**-0.5
        got = jax.jit(
            lambda q, pool, meta: attn(
                q, pool, meta, scale=scale, num_kv_heads=hkv, max_q=chunk
            )
        )(q, pool, meta)

        @jax.jit
        def dense(q, pool):
            if kv_int8:
                data, scales = pool
                kv = data.astype(jnp.float32).reshape(
                    2, -1, PAGE_SIZE, hkv, d
                ) * scales[..., None]
            else:
                kv = pool.astype(jnp.float32).reshape(
                    2, -1, PAGE_SIZE, hkv, d
                )
            kv = kv[:, jnp.asarray(perm)].reshape(2, -1, hkv, d)[:, :ctx]
            kv = jnp.repeat(kv, hq // hkv, axis=2)
            scores = jnp.einsum(
                "qhd,khd->hqk", q.astype(jnp.float32), kv[0]
            ) * scale
            mask = pos[:, None] >= jnp.arange(ctx)[None, :]
            probs = jax.nn.softmax(
                jnp.where(mask[None], scores, -jnp.inf), axis=-1
            )
            return jnp.einsum("hqk,khd->qhd", probs, kv[1])

        want = dense(q, pool)
        err = max_err(got, want)
        if not err <= ATTN_TOL:
            raise AssertionError(f"max abs err {err} > {ATTN_TOL}")
        return {
            "max_abs_err": err,
            "ref_max": float(jnp.max(jnp.abs(want))),
            "chunk": chunk,
            "pages": n_pages,
        }

    def flush_case(m, *, kv_int8):
        """The per-dispatch flush of the staged rows into the pool, in
        place, against the functional scatter: exact equality."""
        hkv, d = m["kv_heads"], m["head_dim"]
        hd, dtype = hkv * d, jnp.dtype(m["dtype"])
        s, k_steps = m["num_seqs"], m["decode_steps"]
        max_pages = m["max_model_len"] // PAGE_SIZE
        rng = np.random.default_rng(m["seed"])
        base = rng.integers(
            1, min(40, max_pages - 2) * PAGE_SIZE - k_steps, size=s
        ).astype(np.int32)
        base[-2:] = 0  # padding rows
        base[0] = 3 * PAGE_SIZE - 1  # rows straddle a page boundary
        n_side = rng.integers(0, k_steps + 1, size=s).astype(np.int32)
        n_side[0] = k_steps
        tables = np.zeros((s, max_pages), np.int32)
        nxt = 1
        for i in range(s):
            if base[i]:
                need = -(-(int(base[i]) + k_steps) // PAGE_SIZE)
                tables[i, :need] = np.arange(nxt, nxt + need)
                nxt += need
        keys = jax.random.split(jax.random.PRNGKey(m["seed"] + 2), 2)
        pool = make_pool(keys[0], nxt, hd, dtype, hkv, kv_int8)
        side = jax.random.normal(
            keys[1], (s, 2, k_steps, hd), jnp.float32
        ).astype(dtype)
        # Oracle: scatter each live staged row to its slot.
        rows_k, rows_v, slots = [], [], []
        for i in range(s):
            for j in range(int(n_side[i]) if base[i] else 0):
                p = int(base[i]) + j
                slots.append(
                    int(tables[i, p // PAGE_SIZE]) * PAGE_SIZE + p % PAGE_SIZE
                )
                rows_k.append(side[i, 0, j])
                rows_v.append(side[i, 1, j])
        want = write_kv_pages(
            pool,
            jnp.stack(rows_k).reshape(-1, hkv, d),
            jnp.stack(rows_v).reshape(-1, hkv, d),
            jnp.asarray(slots, jnp.int32),
        )
        _, flush = attn_fns(m["heads"], hkv)
        got = jax.jit(flush)(
            pool, side, jnp.asarray(tables), jnp.asarray(base),
            jnp.asarray(n_side),
        )
        # A bf16 pool must match bit for bit.  An int8 pool quantizes
        # the rows inside two differently fused programs, so a value on
        # a rounding tie may land one step apart.
        worst = 0.0
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            worst = max(worst, max_err(g[:, 1:], w[:, 1:]))
        if worst > (1.0 if kv_int8 else 0.0):
            raise AssertionError(f"flush differs from scatter by {worst}")
        return {"max_abs_err": worst, "rows": len(slots)}

    def matmul_case(m, name, in_dim, out_dim, spec):
        """A decode-shape projection through quant_matmul, as the model
        calls it: it must take the streaming kernel (not the silent
        x @ dequantize(w) route) and agree with that route's result."""
        mode = "pallas_interpret" if interpret else "pallas"
        keys = jax.random.split(jax.random.PRNGKey(in_dim + out_dim), 2)
        w = jax.random.normal(keys[0], (in_dim, out_dim), jnp.float32) * 0.02
        qt = quant.quantize(w, 8, dtype=jnp.dtype(m["dtype"]), matmul=mode)
        del w
        if mesh is not None:
            qt = quant.place_quantized(qt, spec, mesh)
        x = jax.random.normal(
            keys[1], (m["num_seqs"], in_dim), jnp.float32
        ).astype(m["dtype"])
        kernel = "pallas_call" in str(jax.make_jaxpr(quant.quant_matmul)(x, qt))
        # The kernel's tiles are 128 lanes wide: a shard whose out dim
        # is no multiple of that dequantizes in-graph by rule (and says
        # so in the log).  Any other shape must take the kernel.
        out_local = out_dim // (tp if spec is not None and spec[1] else 1)
        if not kernel and out_local % 128 == 0:
            raise AssertionError(
                "quant_matmul routed this decode shape to "
                "x @ dequantize(w), not the kernel"
            )
        got = jax.jit(quant.quant_matmul)(x, qt)
        want = jax.jit(lambda x, qt: x @ quant.dequantize(qt, x.dtype))(x, qt)
        rel = max_err(got, want) / (float(jnp.max(jnp.abs(want))) + 1e-9)
        if not rel <= MM_TOL:
            raise AssertionError(f"rel err {rel} > {MM_TOL}")
        return {"rel_err": rel, "shape": [in_dim, out_dim],
                "routed": "kernel" if kernel else
                f"dequant-in-graph ({out_local} columns per shard)"}

    def int4_case(m, in_dim, out_dim, group):
        keys = jax.random.split(jax.random.PRNGKey(4), 2)
        w = jax.random.normal(keys[0], (in_dim, out_dim), jnp.float32) * 0.02
        qt = quant.quantize(w, 4, group, dtype=jnp.dtype(m["dtype"]))
        x = jax.random.normal(
            keys[1], (m["num_seqs"], in_dim), jnp.float32
        ).astype(m["dtype"])
        blk = quant._pick_block(out_dim, in_dim, x.nbytes, bits=4)
        got = jax.jit(
            lambda x, q, s: int4_matmul(
                x, q, s, group=group, block_out=blk, interpret=interpret
            )
        )(x, qt.q, qt.scale)
        want = x @ quant.dequantize(qt, x.dtype)
        rel = max_err(got, want) / (float(jnp.max(jnp.abs(want))) + 1e-9)
        if not rel <= MM_TOL:
            raise AssertionError(f"rel err {rel} > {MM_TOL}")
        return {"rel_err": rel, "shape": [in_dim, out_dim], "block": blk}

    m = cfg["model"]
    check("paged_attention decode +side", lambda: decode_case(
        m, kv_int8=False, side=True))
    check("paged_attention prefill chunk", lambda: prefill_case(
        m, kv_int8=False))
    check("kv_flush", lambda: flush_case(m, kv_int8=False))
    h, inter, v = m["hidden"], m["intermediate"], m["vocab_size"]
    qd, kvd = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    if mesh is None:  # single chip: the runner fuses Q|K|V and gate|up
        projections = [
            ("qkv", h, qd + 2 * kvd, None),
            ("o", qd, h, None),
            ("gate_up", h, 2 * inter, None),
            ("down", inter, h, None),
            ("lm_head", h, v, None),
        ]
    else:
        col, row = P(None, "tp"), P("tp", None)
        projections = [
            ("q", h, qd, col),
            ("o", qd, h, row),
            ("gate", h, inter, col),
            ("down", inter, h, row),
            ("lm_head", h, v, col),
        ]
    for name, i, o, spec in projections:
        check(f"int8_matmul {name}", lambda: matmul_case(m, name, i, o, spec))
    for x in cfg["extra"]:
        xm = {**m, **x["model"]}
        tag = x["tag"]
        check(f"[{tag}] paged_attention decode", lambda: decode_case(
            xm, kv_int8=False, side=False))
        check(f"[{tag}] paged_attention int8-kv decode +side",
              lambda: decode_case(xm, kv_int8=True, side=True))
        check(f"[{tag}] paged_attention int8-kv decode", lambda: decode_case(
            xm, kv_int8=True, side=False))
        check(f"[{tag}] paged_attention int8-kv prefill chunk",
              lambda: prefill_case(xm, kv_int8=True))
        check(f"[{tag}] kv_flush int8-kv", lambda: flush_case(
            xm, kv_int8=True))
        if "int4" in x:
            check(f"[{tag}] int4_matmul", lambda: int4_case(xm, *x["int4"]))
    print(json.dumps({"kernels": results}))
    sys.exit(0 if all(r["ok"] for r in results) else 1)


# --------------------------------------------------------------------------
# Parent
# --------------------------------------------------------------------------
class Smoke:
    def __init__(self, args: argparse.Namespace) -> None:
        self.args = args
        self.rehearsal = args.cpu_rehearsal
        self.label = "cpu" if self.rehearsal else "tpu"
        self.tp = args.tp
        self.t0 = time.monotonic()
        self.children: list[subprocess.Popen] = []
        name = "chip_smoke" + (f"_tp{self.tp}" if self.tp > 1 else "")
        if self.rehearsal:
            name += "_cpu_rehearsal"
        self.out_dir = args.out_dir or os.path.join(
            REPO, "chiprun_out", name
        )
        os.makedirs(self.out_dir, exist_ok=True)
        self.report: dict = {"label": self.label, "tp": self.tp, "phases": []}
        self.env = dict(os.environ, PYTHONUNBUFFERED="1")
        if self.rehearsal:
            self.env.update(
                JAX_PLATFORMS="cpu",
                VDT_USE_PALLAS="pallas_interpret",
                # As on the chip: the AOT artifact cache on (one device),
                # and every program stored, so phase C's "no miss" holds
                # for CPU compiles that take milliseconds.
                VDT_AOT_CACHE="1",
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
            )
            if self.tp > 1:
                self.env["XLA_FLAGS"] = (
                    self.env.get("XLA_FLAGS", "")
                    + f" --xla_force_host_platform_device_count={self.tp}"
                ).strip()

    # ---- plumbing ----
    def say(self, msg: str) -> None:
        print(f"[{self.label} +{time.monotonic() - self.t0:5.0f}s] {msg}",
              flush=True)

    def remaining(self) -> float:
        limit = TIME_LIMIT_S[self.tp]
        left = limit - (time.monotonic() - self.t0)
        if left <= 0:
            raise SmokeFailure(f"out of time ({limit}s limit)")
        return left

    def spawn(self, argv: list[str], log_path: str) -> subprocess.Popen:
        log = open(log_path, "wb")
        proc = subprocess.Popen(
            argv,
            cwd=REPO,
            env=self.env,
            stdout=log,
            stderr=subprocess.STDOUT,
            start_new_session=True,  # own group: killable as a whole
        )
        log.close()
        self.children.append(proc)
        return proc

    def kill_all(self) -> None:
        for proc in self.children:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()

    def run_child(
        self, name: str, extra: list[str], timeout: float
    ) -> tuple[int, str]:
        """Run one child of this script to its end: (exit code, output)."""
        log_path = os.path.join(self.out_dir, f"{name}.log")
        proc = self.spawn(
            [sys.executable, os.path.abspath(__file__), "--child", *extra],
            log_path,
        )
        try:
            rc = proc.wait(timeout=min(timeout, self.remaining()))
        except subprocess.TimeoutExpired:
            self.kill_all()
            raise SmokeFailure(f"{name} child did not end in {timeout:.0f}s")
        with open(log_path, errors="replace") as f:
            return rc, f.read()

    # ---- the plan ----
    def plan(self) -> None:
        from vllm_distributed_tpu.testing import (
            LLAMA_1B,
            LLAMA_7B,
            LLAMA_13B,
            write_llama_config,
        )

        if self.rehearsal:
            self.shape_name, shape = "tiny", TINY
            self.max_model_len, self.chunk = 256, 128
            self.prompt_lens = [10, 40, 100, 200]
            self.max_tokens = 8
        else:
            self.shape_name, shape = (
                ("llama-2-13b", LLAMA_13B)
                if self.tp > 1
                else ("llama-2-7b", LLAMA_7B)
            )
            self.max_model_len, self.chunk = 4096, 2048
            self.prompt_lens = [40, 300, 1200, 3000]
            self.max_tokens = 64
        if self.args.layers:
            # Depth cut, width kept: every layer is the same program
            # text, so a shallower model compiles the same kernels.
            shape = dict(shape, layers=self.args.layers)
            self.shape_name += f" cut to {self.args.layers} layers"
        self.shape = shape
        self.model_dir = write_llama_config(
            os.path.join(self.out_dir, "model"), **shape
        )
        # bf16 then int8 on the mesh (int8 exercises the per-shard
        # streamer); int8 alone on one chip (7B in bf16 leaves no pool).
        self.variants = [None, "int8"] if self.tp > 1 else ["int8"]
        if self.args.quantization != "all":
            self.variants = [
                None if self.args.quantization == "none" else "int8"
            ]
        model = dict(
            shape,
            head_dim=shape["hidden"] // shape["heads"],
            num_seqs=MAX_NUM_SEQS if not self.rehearsal else 8,
            decode_steps=8,
            max_model_len=self.max_model_len,
            chunk=self.chunk,
            seed=0,
        )
        extra = []
        if self.args.extra_kernels and not self.rehearsal:
            # The r05 cells' other kernels, not on this smoke's served
            # path: int8 KV pools at 7B and 1B widths, int4 at 1B.
            b1 = dict(LLAMA_1B, head_dim=64)
            extra = [
                {"tag": "7b", "model": {}},
                {"tag": "1b", "model": {**b1, "num_seqs": 64},
                 "int4": [2048, 8192, 128]},
            ]
        elif self.args.extra_kernels:
            extra = [{"tag": "tiny", "model": {}, "int4": [256, 512, 128]}]
        self.kernel_cfg = dict(
            interpret=self.rehearsal, tp=self.tp, model=model, extra=extra
        )

    # ---- phase 0: what did JAX find? ----
    def probe(self) -> dict:
        rc, out = self.run_child("probe", ["probe"], 120)
        if rc != 0:
            raise SmokeFailure(
                f"JAX could not start (probe exited {rc}):\n{out[-3000:]}"
            )
        dev = json.loads(out.strip().splitlines()[-1])
        self.report["device"] = dev
        self.say(
            f"JAX {dev['jax']} / jaxlib {dev['jaxlib']} / libtpu "
            f"{dev['libtpu']}: platform {dev['platform']!r}, kind "
            f"{dev['kind']!r}, {dev['count']} device(s)"
        )
        want = "cpu" if self.rehearsal else "tpu"
        if dev["platform"] != want:
            raise SmokeFailure(
                f"JAX found platform {dev['platform']!r} "
                f"({dev['count']} x {dev['kind']}), this check needs "
                f"{want!r}; nothing was run"
                + ("" if self.rehearsal else
                   " (--cpu-rehearsal is the only mode that runs on a CPU)")
            )
        if dev["count"] < self.tp:
            raise SmokeFailure(
                f"--tp {self.tp} needs {self.tp} devices, JAX found "
                f"{dev['count']}"
            )
        return dev

    # ---- phase A ----
    def phase_a(self) -> dict:
        try:
            rc, out = self.run_child(
                "kernels", ["kernels", json.dumps(self.kernel_cfg)], 600
            )
        except SmokeFailure as e:
            rc, out = -1, str(e)
        kernels = []
        for line in out.splitlines():
            if line.startswith("[kernels] "):
                kernels.append(json.loads(line[len("[kernels] "):]))
        for k in kernels:
            self.say(
                f"  {'ok  ' if k['ok'] else 'FAIL'} {k['kernel']}: "
                + json.dumps({a: b for a, b in k.items()
                              if a not in ("kernel", "ok")})
            )
        res = {
            "phase": "A",
            "ok": rc == 0 and bool(kernels) and all(k["ok"] for k in kernels),
            "kernels": kernels,
        }
        if rc != 0 and all(k["ok"] for k in kernels):
            res["error"] = out[-3000:]  # died outside a check
            self.say(f"  FAIL kernels child exited {rc}:\n{res['error']}")
        return res

    # ---- phases B and C ----
    def serve_argv(self, port: int, quant: str | None) -> list[str]:
        argv = [
            sys.executable, "-m", "vllm_distributed_tpu", "serve",
            self.model_dir,
            "--load-format", "dummy",
            "--skip-tokenizer-init",
            "--max-model-len", str(self.max_model_len),
            "--max-num-seqs", str(self.kernel_cfg["model"]["num_seqs"]),
            "--host", "127.0.0.1",
            "--port", str(port),
        ]
        if quant:
            argv += ["--quantization", quant]
        if self.tp > 1:
            argv += ["-tp", str(self.tp)]
        if self.rehearsal:
            # No memory_stats on the CPU backend; and a step budget
            # small enough that the longest prompt is still chunked.
            argv += ["--num-kv-pages", "128",
                     "--max-num-batched-tokens", str(self.chunk)]
        return argv

    def prompts(self) -> list[list[int]]:
        vocab = self.shape["vocab_size"]
        one = [
            [random.Random(1000 + n).randrange(3, vocab) for _ in range(n)]
            for n in self.prompt_lens
        ]
        return one + one  # two copies of each, far apart in the order

    async def drive(self, url: str, proc: subprocess.Popen, t_spawn) -> dict:
        import aiohttp

        timeout = aiohttp.ClientTimeout(total=None, sock_read=self.remaining())
        async with aiohttp.ClientSession(timeout=timeout) as http:
            # /health answers once the engine is loaded and the pool built.
            while True:
                if proc.poll() is not None:
                    raise SmokeFailure(
                        f"server exited {proc.returncode} before /health"
                    )
                try:
                    async with http.get(url + "/health") as r:
                        if r.status == 200:
                            break
                except aiohttp.ClientError:
                    pass
                self.remaining()
                await asyncio.sleep(0.5)
            t_health = time.monotonic()
            self.say(f"  /health 200 after {t_health - t_spawn:.0f}s")

            prompts = self.prompts()
            t_first: list = [None]

            async def stream(prompt, out: list, i: int, started=None) -> None:
                body = {
                    "prompt": prompt,
                    "max_tokens": self.max_tokens,
                    "temperature": 0,
                    "ignore_eos": True,
                    "stream": True,
                }
                ids: list[int] = []
                try:
                    # The router-hop header makes chunks carry token ids
                    # (there is no tokenizer to make text from).
                    async with http.post(
                        url + "/v1/completions", json=body,
                        headers={"X-VDT-Router": "1"},
                    ) as r:
                        if r.status != 200:
                            raise SmokeFailure(
                                f"stream {i}: HTTP {r.status}: "
                                f"{(await r.text())[:500]}"
                            )
                        async for raw in r.content:
                            line = raw.decode().strip()
                            if not line.startswith("data: "):
                                continue
                            if line == "data: [DONE]":
                                break
                            frame = json.loads(line[6:])
                            if "error" in frame:
                                raise SmokeFailure(f"stream {i}: {frame}")
                            for c in frame.get("choices", []):
                                ids += c.get("vdt_token_ids", [])
                            if ids and t_first[0] is None:
                                t_first[0] = time.monotonic()
                            if ids and started is not None:
                                started.set()
                    out[i] = ids
                finally:
                    if started is not None:
                        started.set()

            # Overlapping, but admitted one at a time: request i+1 goes
            # out when request i has its first token, so every run
            # schedules the same step shapes and compiles the same
            # programs.
            overlapped: list = [None] * len(prompts)
            tasks = []
            for i, prompt in enumerate(prompts):
                started = asyncio.Event()
                tasks.append(asyncio.create_task(
                    stream(prompt, overlapped, i, started)))
                await started.wait()
            await asyncio.gather(*tasks)
            self.say(f"  {len(prompts)} overlapping streams done")
            # One at a time: a request alone always takes the same path
            # (its prefill chunks, then fused decode), so these tokens
            # are the ones that must repeat exactly.
            alone: list = [None] * len(prompts)
            for i, prompt in enumerate(prompts):
                await stream(prompt, alone, i)
            self.say(f"  {len(prompts)} streams one at a time done")

            # The logprob path (single-step decode, full-vocab rows).
            body = {
                "prompt": prompts[1],
                "max_tokens": self.max_tokens,
                "temperature": 0,
                "ignore_eos": True,
                "logprobs": 1,
            }
            async with http.post(url + "/v1/completions", json=body) as r:
                if r.status != 200:
                    raise SmokeFailure(
                        f"logprobs request: HTTP {r.status}: "
                        f"{(await r.text())[:500]}"
                    )
                lp = (await r.json())["choices"][0]["logprobs"]
            async with http.get(url + "/metrics") as r:
                metrics = await r.text()
        return {
            "overlapped": overlapped,
            "alone": alone,
            "logprobs": lp,
            "metrics": metrics,
            "boot_to_health_s": round(t_health - t_spawn, 1),
            "boot_to_first_token_s": round(t_first[0] - t_spawn, 1),
        }

    def check_outputs(self, got: dict) -> list[str]:
        problems = []
        vocab = self.shape["vocab_size"]
        n = len(self.prompt_lens)
        for name in ("overlapped", "alone"):
            for i, ids in enumerate(got[name]):
                if ids is None or len(ids) != self.max_tokens:
                    problems.append(
                        f"{name} stream {i}: "
                        f"{None if ids is None else len(ids)} tokens, "
                        f"want {self.max_tokens}"
                    )
                elif not all(0 <= t < vocab for t in ids):
                    problems.append(
                        f"{name} stream {i}: token id outside the vocab"
                    )
        if problems:
            return problems
        # Under overlap a token comes from the fused decode scan or from
        # a mixed single step, whichever the arrivals around it made the
        # scheduler pick; the two differ in low-order bits and random
        # weights are full of near-ties, so only what prefill alone
        # decides — the first token — must agree between the copies.
        for i in range(n):
            for name, upto in (("overlapped", 1), ("alone", None)):
                a, b = got[name][i][:upto], got[name][i + n][:upto]
                if a != b:
                    problems.append(
                        f"{name}: the two copies of the "
                        f"{self.prompt_lens[i]}-token prompt disagree"
                        + (" on the first token" if upto else "")
                    )
        lp = got["logprobs"]
        values = list(lp["token_logprobs"])
        for top in lp["top_logprobs"]:
            values += list(top.values())
        if len(lp["tokens"]) != self.max_tokens:
            problems.append(
                f"logprobs request: {len(lp['tokens'])} tokens, want "
                f"{self.max_tokens}"
            )
        if not all(
            isinstance(x, (int, float)) and math.isfinite(x) and x <= 1e-3
            for x in values
        ):
            problems.append("logprobs request: a logprob is not finite")
        return problems

    @staticmethod
    def metric(text: str, sample: str, **labels: str) -> float:
        """Sum of one sample name over a /metrics exposition."""
        from prometheus_client.parser import text_string_to_metric_families

        return sum(
            s.value
            for family in text_string_to_metric_families(text)
            for s in family.samples
            if s.name == sample
            and all(s.labels.get(k) == v for k, v in labels.items())
        )

    def server_phase(self, name: str, quant: str | None, prior) -> dict:
        tag = f"{name}_{quant or 'bf16'}"
        res: dict = {"phase": name, "quantization": quant or "none",
                     "ok": False}
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        log_path = os.path.join(self.out_dir, f"server_{tag}.log")
        t_spawn = time.monotonic()
        proc = self.spawn(self.serve_argv(port, quant), log_path)
        problems: list[str] = []
        try:
            got = asyncio.run(
                self.drive(f"http://127.0.0.1:{port}", proc, t_spawn)
            )
            problems += self.check_outputs(got)
            res["boot_to_health_s"] = got["boot_to_health_s"]
            res["boot_to_first_token_s"] = got["boot_to_first_token_s"]
            res["tokens"] = got["alone"]
            res["overlapped_tokens"] = got["overlapped"]
            res["logprob_tokens"] = [int(t) for t in got["logprobs"]["tokens"]]
            mt = got["metrics"]
            cache = {
                e: int(self.metric(
                    mt, "vllm:xla_persistent_cache_events_total", event=e))
                for e in ("lookups", "hits", "writes")
            }
            res["persistent_cache"] = cache
            res["step_programs"] = int(
                self.metric(mt, "vllm:xla_compiles_total"))
            res["fallbacks"] = int(
                self.metric(mt, "vllm:worker_fallbacks_total"))
            res["hbm_live_bytes"] = int(self.metric(mt, "vllm:hbm_live_bytes"))
            if res["fallbacks"]:
                problems.append(
                    f"{res['fallbacks']} worker fallback event(s) "
                    "(AOT artifact / compile cache)"
                )
            if prior is not None:
                if res["tokens"] != prior["tokens"]:
                    problems.append(
                        "tokens of the one-at-a-time streams differ from "
                        "phase B"
                    )
                if [t[:1] for t in res["overlapped_tokens"]] != [
                    t[:1] for t in prior["overlapped_tokens"]
                ]:
                    problems.append(
                        "first tokens of the overlapping streams differ "
                        "from phase B"
                    )
                if res["logprob_tokens"] != prior["logprob_tokens"]:
                    problems.append("logprob-path tokens differ from phase B")
                if cache["writes"]:
                    problems.append(
                        f"{cache['writes']} persistent-cache miss(es) big "
                        "enough to be stored: a step program was recompiled"
                    )
                if not cache["hits"]:
                    problems.append("no persistent-cache hit at all")
        except (SmokeFailure, OSError, asyncio.TimeoutError, KeyError,
                ValueError) as e:
            problems.append(f"{type(e).__name__}: {e}")
        finally:
            # SIGTERM must end the server and free the chip for the
            # next phase.
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
                try:
                    proc.wait(timeout=90)
                except subprocess.TimeoutExpired:
                    problems.append("server still alive 90s after SIGTERM")
            self.kill_all()
        if proc.returncode != 0:
            problems.append(f"server exit code {proc.returncode} on SIGTERM")
        problems += self.read_server_log(log_path, res, warm=prior is not None)
        res["problems"] = problems
        res["ok"] = not problems
        for p in problems:
            self.say(f"  FAIL {p}")
        return res

    def read_server_log(self, path: str, res: dict, warm: bool) -> list[str]:
        with open(path, errors="replace") as f:
            log = f.read()
        problems = []
        m = re.search(
            r"kernel backends: attention=(\S+) kv_write=(\S+) "
            r"quant_matmul=(\S+) aot_cache=(\S+)", log)
        if not m:
            return [f"no 'kernel backends' line in {path}:\n{log[-2000:]}"]
        res["backends"] = dict(zip(
            ("attention", "kv_write", "quant_matmul", "aot_cache"),
            m.groups()))
        want = "pallas_interpret" if self.rehearsal else "pallas"
        expect = {
            "attention": want,
            "kv_write": f"kv_flush:{want}",
            "quant_matmul": want if res["quantization"] != "none" else "None",
            "aot_cache": "on" if self.tp == 1 else "off",
        }
        if res["backends"] != expect:
            problems.append(
                f"server chose {res['backends']}, this run requires {expect}"
            )
        m = re.search(r"KV pool: (\d+) pages", log)
        if m:
            res["kv_pool_pages"] = int(m.group(1))
        elif not self.rehearsal:
            problems.append("no KV pool derived from memory_stats()")
        for when, key in (("after load", "hbm_after_load"),
                          ("after KV pool", "hbm_after_pool")):
            m = re.search(rf"HBM {when}: bytes_in_use per device \[([^\]]*)\]",
                          log)
            if m:
                per_dev = [int(x) for x in m.group(1).split(",")]
                res[key] = per_dev
                if len(per_dev) > 1 and max(per_dev) > 1.2 * min(per_dev):
                    problems.append(
                        f"bytes_in_use {when} is uneven across devices: "
                        f"{per_dev}"
                    )
            elif not self.rehearsal:
                problems.append(f"no per-device bytes_in_use {when}")
        saved = log.count("AOT artifact saved")
        res["aot_artifacts_saved"] = saved
        if warm and saved:
            problems.append(f"{saved} AOT artifact(s) re-exported when warm")
        return problems

    # ---- everything ----
    def run(self) -> bool:
        self.plan()
        dev = self.probe()
        phases = self.args.phases.upper()
        if "A" in phases:
            self.say(f"phase A: kernels at {self.shape_name} shapes")
            self.report["phases"].append(self.phase_a())
        for quant in self.variants:
            prior = None
            for name in "BC":
                if name not in phases:
                    continue
                self.say(
                    f"phase {name} ({quant or 'bf16'}): "
                    + ("first start" if name == "B" else "warm restart")
                )
                res = self.server_phase(name, quant, prior)
                self.report["phases"].append(res)
                self.say(
                    f"  backends {res.get('backends')}, KV pool "
                    f"{res.get('kv_pool_pages')} pages, "
                    f"{res.get('step_programs')} step programs, cache "
                    f"{res.get('persistent_cache')}, boot to first token "
                    f"{res.get('boot_to_first_token_s')}s"
                )
                if name == "B" and res["ok"]:
                    prior = res
        ok = bool(self.report["phases"]) and all(
            p["ok"] for p in self.report["phases"]
        )
        self.report["ok"] = ok
        self.report["seconds"] = round(time.monotonic() - self.t0, 1)
        for p in self.report["phases"]:
            self.say(
                f"phase {p['phase']}"
                + (f" ({p['quantization']})" if "quantization" in p else "")
                + f": {'pass' if p['ok'] else 'FAIL'}"
            )
        with open(os.path.join(self.out_dir, "result.json"), "w") as f:
            json.dump(self.report, f, indent=1)
        print(json.dumps({
            "ok": ok,
            "device": {"platform": dev["platform"], "kind": dev["kind"],
                       "count": dev["count"]},
        }))
        return ok


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1, choices=(1, 4))
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny shapes on the CPU, Pallas interpreted")
    ap.add_argument("--phases", default="ABC",
                    help="subset of ABC to run (C needs B)")
    ap.add_argument("--quantization", default="all",
                    choices=("all", "none", "int8"),
                    help="server variants to run (default: the mode's own)")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model's depth (its width stays)")
    ap.add_argument("--extra-kernels", action="store_true",
                    help="phase A also compiles int4 and the int8-KV "
                    "attention/flush variants")
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--child", nargs="+", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        sys.path.insert(0, REPO)
        if args.child[0] == "probe":
            child_probe()
        else:
            child_kernels(json.loads(args.child[1]))
        return 0
    sys.path.insert(0, REPO)
    try:
        import vllm_distributed_tpu.testing  # noqa: F401 — no JAX inside
    except ImportError as e:
        print(f"chip_smoke: the vllm_distributed_tpu package is not beside "
              f"this script ({e}); nothing was run", file=sys.stderr)
        return 2
    smoke = Smoke(args)
    try:
        return 0 if smoke.run() else 1
    except SmokeFailure as e:
        print(f"chip_smoke [{smoke.label}]: {e}", file=sys.stderr)
        return 1
    finally:
        smoke.kill_all()


if __name__ == "__main__":
    sys.exit(main())
