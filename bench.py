"""Throughput bench — prints ONE JSON line for the driver.

Measures steady-state decode throughput (tokens/sec/chip) of the engine
on dummy-weight family-member shapes on the TPU; with no TPU it exits
non-zero, and so does a run in which any config or probe failed.  The
reference publishes no numbers
(BASELINE.md: "published": {}), so vs_baseline is reported as 1.0 by
convention; the `detail` block carries the honest engineering numbers
per config: dispatch percentiles, inter-token latency, an UNCLAMPED
roofline against weight + actually-scheduled-KV traffic, cold/warm
TTFT, prefill tokens/sec, and on-chip kernel checks (Pallas attention
incl. int8 pools, KV writer, int8/int4 weight streamers, grouped
ragged_dot lowering) run before any timing.

Default configs: Llama-1B bf16 b32 and int8 b64 (continuity shapes),
Llama-1B int4+int8KV b64 (streamer), Llama-7B int8 b16 (r4 continuity)
and int8+int8KV b48 (headline 7B), and a Mixtral-shape 8x1B MoE b32
(auto dispatch + a forced-ragged comparison).  The serve probe drives
the OpenAI server over HTTP/SSE at c16 with a c1/c4 sweep and a
matched engine-direct fraction.  The headline value is the best decode
tok/s/chip across configs.

Env knobs: VDT_BENCH_MODEL=1b|7b + VDT_BENCH_BATCH/VDT_BENCH_STEPS/
VDT_BENCH_QUANT/VDT_BENCH_KV run one explicit config instead;
VDT_BENCH_DISPATCHES sizes the timed window; VDT_BENCH_FAST=1 skips the
7B and MoE configs; VDT_BENCH_SERVE=0 skips the serve probe;
VDT_BENCH_SPEC=0 skips the speculative-decoding on/off gate;
VDT_BENCH_PREFIX_CACHE=1 builds the engines with --enable-prefix-caching
(details then report prefix_cache_hit_rate; `tools/ablation` is the
dedicated on/off warm-TTFT comparison).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time


def _check_kernels() -> str:
    """Compare the Pallas kernels against pure-JAX oracles on the live
    backend (VERDICT r1 weak #4: interpret-only testing is not enough —
    aliasing/DMA behavior is exactly where real Mosaic can diverge)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_distributed_tpu.ops.attention import (
        AttentionMetadata,
        merge_kv_pages,
        paged_attention_reference,
        write_kv_pages,
    )
    from vllm_distributed_tpu.ops.pallas.paged_attention import paged_attention

    rng = np.random.default_rng(0)
    hq, hkv, d, page, pages = 8, 4, 128, 16, 8
    s_pad, t = 4, 8
    q = jnp.asarray(rng.normal(size=(t, hq, d)), jnp.float32)
    k_pages = jnp.asarray(
        rng.normal(size=(pages, page, hkv, d)), jnp.float32
    )
    v_pages = jnp.asarray(
        rng.normal(size=(pages, page, hkv, d)), jnp.float32
    )
    # 2 seqs: one decoding at ctx 37, one mid-prefill chunk of 7 at ctx 20.
    seq_ids = np.full(t, s_pad, np.int32)
    positions = np.zeros(t, np.int32)
    seq_ids[0], positions[0] = 0, 36
    seq_ids[1:8], positions[1:8] = 1, np.arange(13, 20)
    meta = AttentionMetadata(
        q_seq_ids=jnp.asarray(seq_ids),
        q_positions=jnp.asarray(positions),
        slot_mapping=jnp.zeros(t, jnp.int32),
        # Page 0 is the engine's reserved dump page (garbage by
        # contract), so harness tables use pages 1..P-1 like the
        # allocator does.
        block_tables=jnp.asarray(
            np.arange(s_pad * pages, dtype=np.int32).reshape(s_pad, pages)
            % (pages - 1)
            + 1
        ),
        seq_lens=jnp.asarray([37, 20, 0, 0], jnp.int32),
        logits_indices=jnp.zeros(s_pad, jnp.int32),
        chunk_starts=jnp.asarray([36, 13, 0, 0], jnp.int32),
    )
    kv_pages = merge_kv_pages(k_pages, v_pages)
    got = np.asarray(
        paged_attention(
            q, kv_pages, meta, scale=0.125, num_kv_heads=hkv, max_q=8
        )
    )
    want = np.asarray(
        paged_attention_reference(
            q, kv_pages, meta, scale=0.125, num_kv_heads=hkv
        )
    )
    # TPU f32 dots truncate to bf16 on the MXU by default, and the two
    # paths round differently (flash online-softmax vs direct), so the
    # agreement bound is bf16-scale (eps ≈ 7.8e-3), not f32-scale.
    err = float(np.max(np.abs(got[:8] - want[:8])))
    if err > 2e-2:
        raise AssertionError(f"pallas kernel mismatch on chip: max err {err}")

    # Multi-kv-block decode (num_kvb >= 2 flips on the cross-sequence
    # block-0 prefetch) — real-Mosaic DMA ordering, not just interpret.
    pages2, s2 = 40, 4
    k2 = jnp.asarray(rng.normal(size=(pages2, page, hkv, d)), jnp.float32)
    v2 = jnp.asarray(rng.normal(size=(pages2, page, hkv, d)), jnp.float32)
    t2 = 4
    q2 = jnp.asarray(rng.normal(size=(t2, hq, d)), jnp.float32)
    lens2 = np.array([500, 300, 0, 620], np.int32)
    sid2 = np.array([0, 1, s2, 3], np.int32)  # row 2 -> dropped padding
    pos2 = np.maximum(lens2 - 1, 0)[np.minimum(sid2, s2 - 1)]
    bt2 = (
        rng.permutation(np.arange(s2 * 40) % (pages2 - 1) + 1)
        .reshape(s2, 40)
        .astype(np.int32)
    )
    meta2 = AttentionMetadata(
        q_seq_ids=jnp.asarray(sid2),
        q_positions=jnp.asarray(pos2),
        slot_mapping=jnp.zeros(t2, jnp.int32),
        block_tables=jnp.asarray(bt2),
        seq_lens=jnp.asarray(lens2),
        logits_indices=jnp.zeros(s2, jnp.int32),
        chunk_starts=jnp.asarray(np.maximum(lens2 - 1, 0)),
    )
    kv2 = merge_kv_pages(k2, v2)
    got2 = np.asarray(
        paged_attention(
            q2, kv2, meta2, scale=0.125, num_kv_heads=hkv, max_q=1
        )
    )
    want2 = np.asarray(
        paged_attention_reference(
            q2, kv2, meta2, scale=0.125, num_kv_heads=hkv
        )
    )
    live = np.array([0, 1, 3])
    err2 = float(np.max(np.abs(got2[live] - want2[live])))
    if err2 > 2e-2:
        raise AssertionError(
            f"cross-seq-prefetch kernel mismatch on chip: max err {err2}"
        )

    # In-place KV writer vs the functional scatter, on the live chip.
    from vllm_distributed_tpu.ops.pallas.kv_update import kv_update

    kq = jnp.asarray(rng.normal(size=(t, hkv, d)), jnp.float32)
    vq = jnp.asarray(rng.normal(size=(t, hkv, d)), jnp.float32)
    slots = jnp.asarray(rng.permutation(pages * page)[:t], jnp.int32)
    # Oracle first: kv_update aliases (donates) the pool buffer.
    want_kv = write_kv_pages(kv_pages, kq, vq, slots)
    got_kv = kv_update(kv_pages, kq, vq, slots)
    kv_err = float(np.max(np.abs(np.asarray(got_kv) - np.asarray(want_kv))))
    if kv_err > 0:
        raise AssertionError(f"kv_update mismatch on chip: max err {kv_err}")

    # MoE ragged dispatch: the TPU ragged_dot lowering must be truly
    # grouped (flops == 2*M*H*I), not masked-dense like the CPU one.
    e_, h_, i_, m_ = 8, 256, 512, 64
    xs_ = jnp.zeros((m_, h_), jnp.bfloat16)
    wg_ = jnp.zeros((e_, h_, i_), jnp.bfloat16)
    gs_ = jnp.full((e_,), m_ // e_, jnp.int32)
    rd_flops = (
        jax.jit(lambda a, b, g: jax.lax.ragged_dot(a, b, g))
        .lower(xs_, wg_, gs_).compile().cost_analysis().get("flops", 0)
    )
    if rd_flops > 2 * m_ * h_ * i_ * 1.5:
        raise AssertionError(
            f"ragged_dot lowering is not sparse: {rd_flops} flops vs "
            f"ideal {2 * m_ * h_ * i_}"
        )

    # int8/int4 weight-streaming matmuls vs dequant-in-graph.
    from vllm_distributed_tpu.ops.pallas.quant_matmul import (
        int4_matmul,
        int8_matmul,
    )
    from vllm_distributed_tpu.ops.quant import dequantize, quantize

    x = jnp.asarray(rng.normal(size=(32, 1024)) * 0.5, jnp.float32)
    w = (rng.normal(size=(1024, 512)) * 0.1).astype(np.float32)
    qt = quantize(w, 8)
    mm_want = np.asarray(x @ dequantize(qt, jnp.float32))
    mm_got = np.asarray(
        int8_matmul(x, jnp.asarray(qt.q), jnp.asarray(qt.scale))
    )
    mm_err = float(
        np.max(np.abs(mm_got - mm_want)) / (np.abs(mm_want).max() + 1e-9)
    )
    if mm_err > 2e-2:
        raise AssertionError(f"int8_matmul mismatch on chip: {mm_err}")
    qt4 = quantize(w, 4, group=128)
    mm4_want = np.asarray(x @ dequantize(qt4, jnp.float32))
    mm4_got = np.asarray(
        int4_matmul(
            x, jnp.asarray(qt4.q), jnp.asarray(qt4.scale), group=128
        )
    )
    mm4_err = float(
        np.max(np.abs(mm4_got - mm4_want)) / (np.abs(mm4_want).max() + 1e-9)
    )
    if mm4_err > 2e-2:
        raise AssertionError(f"int4_matmul mismatch on chip: {mm4_err}")
    return (
        f"pass (attn {err:.1e}; kv_update exact; int8_matmul "
        f"{mm_err:.1e}; int4_matmul {mm4_err:.1e})"
    )


def _hbm_bw() -> tuple[str, float]:
    import jax

    table = (
        ("TPU v6", 1640e9),
        ("TPU v5p", 2765e9),
        ("TPU v5", 819e9),  # v5e / v5 lite
        ("TPU v4", 1228e9),
    )
    kind = jax.devices()[0].device_kind
    for prefix, bw in table:
        if kind.startswith(prefix):
            return kind, bw
    raise RuntimeError(
        f"no HBM bandwidth on record for device kind {kind!r}; add it to "
        "the table with its source rather than assume another chip's"
    )


def _run_config(shapes, *, batch, k_steps, quant, timed_dispatches,
                kv_dtype="auto", model_kind="llama",
                warm_engine_probe=False, prefill_probe=False,
                timed_dispatches_cap=None):
    """One engine, one decode measurement.  Returns a detail dict."""
    import jax

    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    from vllm_distributed_tpu.sampling_params import SamplingParams
    from vllm_distributed_tpu.testing import (
        write_llama_config,
        write_mixtral_config,
    )

    if timed_dispatches_cap is not None:
        timed_dispatches = min(timed_dispatches, timed_dispatches_cap)
    warmup_dispatches = 2
    prompt_len = 32
    max_tokens = 1 + k_steps * (warmup_dispatches + timed_dispatches)
    writer = (
        write_mixtral_config if model_kind == "mixtral" else write_llama_config
    )
    model_dir = writer(**shapes)

    def build():
        return LLMEngine.from_engine_args(
            EngineArgs(
                model=model_dir,
                skip_tokenizer_init=True,
                load_format="dummy",
                max_num_seqs=batch,
                max_num_batched_tokens=max(2048, batch * prompt_len),
                max_model_len=prompt_len + max_tokens + 8,
                num_decode_steps=k_steps,
                max_concurrent_dispatches=int(
                    os.environ.get("VDT_BENCH_PIPELINE", "6")
                ),
                quantization=quant,
                kv_cache_dtype=kv_dtype,
                enable_prefix_caching=(
                    os.environ.get("VDT_BENCH_PREFIX_CACHE", "0") == "1"
                ),
            )
        )

    def free_engine(eng):
        """Release HBM: the jit cache keys on the runner (static self),
        pinning params/KV beyond the engine's lifetime — delete the
        device buffers explicitly."""
        eng.shutdown()
        r = getattr(getattr(eng, "executor", None), "worker", None)
        r = getattr(r, "runner", None)
        if r is not None and r.params is not None:
            for leaf in jax.tree.leaves((r.params, r.kv_caches)):
                leaf.delete()
            carry = getattr(r, "_decode_carry", None)
            if carry is not None:
                carry[2].delete()
            r.params, r.kv_caches, r._decode_carry = None, None, None

    engine = build()
    try:
        return _measure(
            engine, build, free_engine, batch=batch, k_steps=k_steps,
            quant=quant, prompt_len=prompt_len, max_tokens=max_tokens,
            warmup_dispatches=warmup_dispatches,
            warm_engine_probe=warm_engine_probe,
            prefill_probe=prefill_probe,
        )
    finally:
        # Always release HBM — a failed config must not leak its pool
        # into the next config's budget.
        free_engine(engine)


def _measure(engine, build, free_engine, *, batch, k_steps, quant,
             prompt_len, max_tokens, warmup_dispatches, warm_engine_probe,
             prefill_probe=False):
    import jax

    from vllm_distributed_tpu.sampling_params import SamplingParams

    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens, ignore_eos=True)
    for i in range(batch):
        prompt = [(7 * i + j) % 1000 + 1 for j in range(prompt_len)]
        engine.add_request(f"b{i}", prompt_token_ids=prompt, sampling_params=sp)

    produced: dict[str, int] = {}

    def run_step() -> int:
        before = sum(produced.values())
        for out in engine.step():
            produced[out.request_id] = len(out.outputs[0].token_ids)
        return sum(produced.values()) - before

    t0 = time.perf_counter()
    run_step()  # prefill (compiles the prefill program)
    ttft_cold_s = time.perf_counter() - t0
    for _ in range(warmup_dispatches):
        run_step()

    step_ms: list[float] = []
    timed_tokens = 0
    # Snapshot so the reported count covers the timed window only —
    # warmup/prefill-boundary reconciliations are expected and would
    # otherwise mask a nonzero steady-state reading.
    breaks_before = getattr(engine, "pipeline_breaks", 0)
    t0 = time.perf_counter()
    while engine.has_unfinished_requests():
        t1 = time.perf_counter()
        timed_tokens += run_step()
        step_ms.append((time.perf_counter() - t1) * 1e3)
    elapsed = time.perf_counter() - t0
    tps = timed_tokens / elapsed

    # Roofline for one decode micro-step.  Byte model (VERDICT r4 #4 —
    # derived from ACTUALLY SCHEDULED context, not the pages bucket):
    #   weight_bytes: resident param bytes (quantized weights stream
    #     their compressed form; MoE counts every resident expert —
    #     the top-k dispatch reads less, making raw frac conservative).
    #   kv_read_bytes: batch × ceil(mean_ctx/page)×page rows × row
    #     bytes, where mean_ctx = prompt + half the generated tokens
    #     (the timed window's midpoint) and a row is 2 planes × HD ×
    #     itemsize (+ Hkv f32 scales when the pool is int8).  Page
    #     granularity matches the kernel's DMA; the kernel may overread
    #     up to one KV *block* per sequence, so the floor is a slight
    #     underestimate — the frac is reported RAW (can exceed 1 only
    #     if this model is wrong).
    runner = getattr(
        getattr(getattr(engine, "executor", None), "worker", None),
        "runner",
        None,
    )
    param_bytes = 0
    kv_read_bytes = 0
    if runner is not None:
        param_bytes = sum(
            x.nbytes for x in jax.tree.leaves(runner.params)
        )
        mean_ctx = prompt_len + max_tokens // 2
        page = runner.page_size
        rows = -(-mean_ctx // page) * page
        from vllm_distributed_tpu.ops.attention import kv_pool_width

        m = runner.model
        row_bytes = (
            kv_pool_width(m.num_kv_heads, m.head_dim)
            * jax.numpy.dtype(runner.kv_cache_dtype()).itemsize
        )
        if runner.kv_cache_quantized:
            row_bytes += m.num_kv_heads * 4  # f32 scale row
        kv_read_bytes = batch * rows * 2 * row_bytes * m.num_layers
    kind, bw = _hbm_bw()
    floor_ms = (param_bytes + kv_read_bytes) / bw * 1e3
    micro_ms = 1e3 / (tps / batch) if tps else float("inf")
    itl = sorted(ms / k_steps for ms in step_ms)

    def pct(p):
        return round(itl[min(int(len(itl) * p), len(itl) - 1)], 3)

    # Steady-state throughput from the MEDIAN dispatch: the wall-clock
    # number (tokens_per_sec) includes multi-hundred-ms stalls nobody
    # has attributed yet (ROADMAP S3); the p50 number leaves them out.
    p50_ms = statistics.median(step_ms)
    tps_p50 = batch * k_steps / p50_ms * 1e3 if p50_ms else 0.0
    detail = {
        "batch": batch,
        "decode_steps_fused": k_steps,
        "quantization": quant,
        "timed_tokens": timed_tokens,
        "elapsed_s": round(elapsed, 3),
        "tokens_per_sec": round(tps, 1),
        "tokens_per_sec_p50": round(tps_p50, 1),
        # The dispatch tax (ISSUE 7): steady-state p50 throughput minus
        # what the wall clock actually delivered.  0 means the driver
        # never fell off the p50 pace; r03 measured a 2,338 tok/s gap
        # here before the overlapped dispatch pipeline.
        "wall_vs_p50_gap": round(tps_p50 - tps, 1),
        "dispatch_ms_p50": round(p50_ms, 2),
        "dispatch_ms_max": round(max(step_ms), 2),
        # Windows > 2x the median are classified as stalls (transport
        # hiccups or engine-side pauses; compiles are excluded by the
        # warmup dispatches).  The final window is excluded — it drains
        # the whole dispatch pipeline and is ~depth x p50 by design.
        "stall_windows": sum(1 for ms in step_ms[:-1] if ms > 2 * p50_ms),
        "stall_ms_total": round(
            sum(ms - p50_ms for ms in step_ms[:-1] if ms > 2 * p50_ms), 1
        ),
        "decode_microstep_ms": round(micro_ms, 3),
        "itl_ms_p50": pct(0.5),
        "itl_ms_p90": pct(0.9),
        "itl_ms_p99": pct(0.99),
        "roofline_microstep_ms": round(floor_ms, 3),
        # RAW (unclamped): >1 means the byte model is wrong, not that
        # the chip beat physics (VERDICT r4 weak #3).
        "roofline_frac": round(floor_ms / micro_ms, 3),
        "ttft_cold_s": round(ttft_cold_s, 2),
        "param_bytes": param_bytes,
        "kv_read_bytes_per_microstep": kv_read_bytes,
    }
    # Async-scheduling reconciliation drains over the timed window (0 at
    # steady-state decode; each one idles the device for a full drain).
    if hasattr(engine, "pipeline_breaks"):
        detail["pipeline_breaks"] = engine.pipeline_breaks - breaks_before
    sched = getattr(engine, "scheduler", None)
    if sched is not None and getattr(sched, "prefix_cache_queries", 0):
        detail["prefix_cache_hit_rate"] = round(
            sched.prefix_cache_hits / sched.prefix_cache_queries, 4
        )
        # Tiered KV (ISSUE 14): host-tier traffic over the run, when
        # the spill tier is armed (VDT_KV_SPILL_HOST_PAGES > 0).
        if getattr(sched, "kv_spill_pages", 0) or getattr(
            sched, "kv_restore_pages", 0
        ):
            detail["kv_spill_pages"] = sched.kv_spill_pages
            detail["kv_restore_pages"] = sched.kv_restore_pages
            detail["prefix_cache_host_hit_tokens"] = (
                sched.prefix_cache_hits_host
            )
    if warm_engine_probe or prefill_probe:
        # Warm TTFT: a FRESH engine on the same shapes hits the
        # persistent caches this run just wrote (XLA disk cache + AOT
        # export artifacts) — the restart-to-first-token story (§5.4):
        # no retrace, no relower, compile-cache-hit only.  Free the
        # first engine's HBM before the rebuild.  A probe failure must
        # not discard the config's measurement.
        free_engine(engine)
        try:
            engine2 = build()
            try:
                # Replay the SAME admission shape as the measured run
                # (batch x prompt_len) so the first step hits the
                # artifact the run just exported — a lone request would
                # land in a token bucket the first engine never
                # compiled and measure a fresh compile instead.
                sp2 = SamplingParams(
                    temperature=0.0, max_tokens=2, ignore_eos=True
                )
                for i in range(batch):
                    engine2.add_request(
                        f"warm{i}",
                        prompt_token_ids=[
                            (7 * i + j) % 1000 + 1
                            for j in range(prompt_len)
                        ],
                        sampling_params=sp2,
                    )
                t0 = time.perf_counter()
                engine2.step()
                detail["ttft_warm_s"] = round(time.perf_counter() - t0, 2)
                while engine2.has_unfinished_requests():
                    engine2.step()
                if prefill_probe:
                    detail["prefill"] = _prefill_probe(
                        engine2, prompt_len=256, n_prompts=8
                    )
            finally:
                free_engine(engine2)
        except Exception as e:  # noqa: BLE001
            detail["ttft_warm_error"] = f"{type(e).__name__}: {e}"
    return detail


def _prefill_probe(engine, *, prompt_len, n_prompts) -> dict:
    """Prefill tokens/sec (VERDICT r4 #3: 'no prefill tokens/sec number
    anywhere'): run one compile pass, then time a batch of fresh
    prompts through their prefill steps (max_tokens=1 — decode excluded
    by construction)."""
    from vllm_distributed_tpu.sampling_params import SamplingParams

    prompt_len = min(
        prompt_len, engine.config.model_config.max_model_len - 8
    )
    sp = SamplingParams(temperature=0.0, max_tokens=1, ignore_eos=True)

    def run(tag):
        for i in range(n_prompts):
            toks = [(11 * i + j) % 900 + 1 for j in range(prompt_len)]
            engine.add_request(f"{tag}{i}", prompt_token_ids=toks,
                               sampling_params=sp)
        t0 = time.perf_counter()
        while engine.has_unfinished_requests():
            engine.step()
        return time.perf_counter() - t0

    run("pfc")  # compile pass
    elapsed = run("pf")
    total = n_prompts * prompt_len
    return {
        "prompt_len": prompt_len,
        "n_prompts": n_prompts,
        "elapsed_s": round(elapsed, 3),
        "prefill_tokens_per_sec": round(total / elapsed, 1),
    }


def _spec_probe() -> dict:
    """Speculative-decoding gate (ISSUE 11): tokens/s and acceptance
    rate with spec decode on vs off on a repetitive-suffix workload
    (tools/spec_decode_ablation.py).  The result carries `gate_pass`:
    at the measured acceptance rate the speculative engine must beat
    the fused-decode baseline by the configured multiple, and outputs
    must be bit-identical (always fatal if not)."""
    import argparse

    from tools.spec_decode_ablation import run_ablation
    from vllm_distributed_tpu.testing import LLAMA_1B, write_llama_config

    shapes = LLAMA_1B
    n_prompts, max_tokens = 16, 96
    args = argparse.Namespace(
        load_format="dummy",
        num_prompts=n_prompts,
        prompt_len=96,
        pattern_len=24,
        max_tokens=max_tokens,
        spec_k=4,
        num_decode_steps=8,
        num_kv_pages=2048,
        page_size=16,
        gate_acceptance=0.5,
        gate_speedup=1.3,
    )
    result = run_ablation(write_llama_config(**shapes), args)
    if not result["outputs_bit_identical"]:
        raise AssertionError(
            "spec decode outputs diverged from the greedy baseline"
        )
    if result["gate_applicable"] and not result["gate_pass"]:
        raise AssertionError(
            f"spec decode gate failed: {result['decode_speedup']}x < "
            f"{args.gate_speedup}x at acceptance "
            f"{result['acceptance_rate']}"
        )
    return result


def _serve_probe() -> dict:
    """HTTP-path serving metrics (BASELINE.md's TTFT/ITL are SERVING
    numbers): boot the OpenAI server on the 1B dummy model and drive it
    with concurrent SSE completions via `vdt bench serve`'s client."""
    import argparse
    import asyncio
    import socket

    from aiohttp.test_utils import TestServer

    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.engine.async_llm import AsyncLLM
    from vllm_distributed_tpu.entrypoints.cli import _bench_serve_async
    from vllm_distributed_tpu.entrypoints.openai.api_server import (
        build_app,
        init_app_state,
    )
    from vllm_distributed_tpu.testing import LLAMA_1B, write_llama_config

    model_dir = write_llama_config(**LLAMA_1B)
    engine = AsyncLLM.from_engine_args(
        EngineArgs(
            model=model_dir,
            skip_tokenizer_init=True,
            load_format="dummy",
            quantization="int8",
            max_num_seqs=16,
            max_model_len=512,
            num_decode_steps=16,
            max_concurrent_dispatches=6,
            # warmup_decode only: the probe's prefill shapes are
            # multi-request JOINS, which the single-request prefill
            # buckets of --warmup-prefill would not cover anyway — the
            # HTTP warmup passes below compile the real shapes.
            warmup_decode=True,
        )
    )
    state = init_app_state(engine, served_model_name="bench-1b")
    loop = asyncio.new_event_loop()
    try:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        server = TestServer(build_app(state), port=port)
        loop.run_until_complete(server.start_server())
        args = argparse.Namespace(
            url=f"http://127.0.0.1:{port}",
            model="bench-1b",
            num_prompts=48,
            concurrency=16,
            input_len=32,
            output_len=128,
            # Closed-loop probe: the ISSUE 13 --ramp rate sweep is the
            # fleet/autoscaler workload, not a single-replica number.
            ramp=None,
        )
        # Warmup passes at EVERY measured concurrency (each join batch
        # size is its own prefill program shape), then the measured
        # passes: headline at c16 plus a small sweep (r4 weak #6) for
        # per-stream latency at low load.
        sweep_concs = (1, 4)
        for conc in (args.concurrency, *sweep_concs):
            warm = argparse.Namespace(
                **{**vars(args), "output_len": 16, "concurrency": conc,
                   "num_prompts": max(2 * conc, 4)}
            )
            loop.run_until_complete(_bench_serve_async(warm))
        result = loop.run_until_complete(_bench_serve_async(args))
        result["sweep"] = {}
        for conc in sweep_concs:
            a = argparse.Namespace(
                **{**vars(args), "concurrency": conc,
                   "num_prompts": max(3 * conc, 4)}
            )
            r = loop.run_until_complete(_bench_serve_async(a))
            result["sweep"][f"c{conc}"] = {
                k: r[k]
                for k in ("output_tokens_per_s", "ttft_s", "itl_ms")
            }
        loop.run_until_complete(server.close())
        return result
    finally:
        engine.shutdown()
        loop.close()
        # Release HBM so the matched engine-direct run that follows can
        # boot (shutdown alone leaves params/pool pinned by jit caches).
        import jax

        r = getattr(
            getattr(engine.engine, "executor", None), "worker", None
        )
        r = getattr(r, "runner", None)
        if r is not None and r.params is not None:
            for leaf in jax.tree.leaves((r.params, r.kv_caches)):
                leaf.delete()
            carry = getattr(r, "_decode_carry", None)
            if carry is not None:
                carry[2].delete()
            r.params, r.kv_caches, r._decode_carry = None, None, None


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax

    from vllm_distributed_tpu.testing import LLAMA_1B, LLAMA_7B

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # A throughput number from another backend must never appear
        # under a device metric's name: no chip, no bench.
        raise SystemExit(
            f"bench.py measures the TPU; JAX found platform {platform!r}"
        )
    kernel_check = _check_kernels()
    timed = int(os.environ.get("VDT_BENCH_DISPATCHES", "24"))

    explicit = os.environ.get("VDT_BENCH_MODEL")
    if explicit:
        cfg = dict(
            shapes={"1b": LLAMA_1B, "7b": LLAMA_7B}[explicit],
            batch=int(os.environ.get("VDT_BENCH_BATCH", "32")),
            k_steps=int(os.environ.get("VDT_BENCH_STEPS", "16")),
            quant=os.environ.get("VDT_BENCH_QUANT") or None,
            kv_dtype=os.environ.get("VDT_BENCH_KV", "auto"),
        )
        configs = [(explicit, cfg)]
    else:
        from vllm_distributed_tpu.testing import MIXTRAL_8X1B

        configs = [
            # Continuity shapes (pinned since r2/r4 — VERDICT r4 #4).
            ("llama_1b_bf16_b32", dict(
                shapes=LLAMA_1B, batch=32, k_steps=16, quant=None)),
            ("llama_1b_int8_b64", dict(
                shapes=LLAMA_1B, batch=64, k_steps=32, quant="int8",
                prefill_probe=True)),
            # int4 weight streaming (nibble-unpack in VMEM).
            ("llama_1b_int4_b64", dict(
                shapes=LLAMA_1B, batch=64, k_steps=32, quant="int4",
                kv_dtype="int8", timed_dispatches_cap=12)),
        ]
        if os.environ.get("VDT_BENCH_FAST") != "1":
            configs += [
                # 7B KV is ~1 MiB/token (MHA, 32 layers): the batch and
                # decode length must FIT the pool or the scheduler
                # preempts in a loop mid-bench (r3's "12 s stalls" were
                # exactly this thrash).  b16 is the r4 continuity shape;
                # the int8 KV cache (~0.5 MiB/token) doubles capacity,
                # so b48 is the headline config.
                ("llama_7b_int8_b16", dict(
                    shapes=LLAMA_7B, batch=16, k_steps=16, quant="int8",
                    timed_dispatches_cap=16)),
                # (no warm/prefill probes here: each one is a full 7B
                # rebuild — the restart story is measured once, at 1B)
                ("llama_7b_int8_kv8_b48", dict(
                    shapes=LLAMA_7B, batch=48, k_steps=16, quant="int8",
                    kv_dtype="int8", timed_dispatches_cap=16)),
                # MoE (the reference flagship family is MoE): ragged
                # sorted dispatch, single chip, int8 weights.
                ("moe_mixtral8x1b_int8_b32", dict(
                    shapes=MIXTRAL_8X1B, batch=32, k_steps=16,
                    quant="int8", model_kind="mixtral",
                    timed_dispatches_cap=16)),
            ]

    details = {}
    best_name, best = None, None
    warm_pending = True  # probe warm TTFT on the first SUCCESSFUL config
    # Every phase still runs after one fails (its numbers are worth
    # having), but anything recorded here makes the exit code non-zero.
    failures: list[str] = []
    for name, cfg in configs:
        try:
            det = _run_config(
                **cfg, timed_dispatches=timed,
                warm_engine_probe=warm_pending,
            )
        except Exception as e:  # noqa: BLE001 — reported via `failures`
            details[name] = {"error": f"{type(e).__name__}: {e}"}
            failures.append(name)
            continue
        warm_pending = False
        details[name] = det
        if (
            best is None
            or det["tokens_per_sec_p50"] > best["tokens_per_sec_p50"]
        ):
            best_name, best = name, det
    if best is None:
        raise RuntimeError(f"every bench config failed: {details}")

    # MoE dispatch-path ratio (VERDICT r4 #5): the headline config runs
    # the "auto" policy (dense-fused at bandwidth-bound decode — see
    # models/mixtral.py _mlp); rerun briefly with the ragged path
    # forced so the tradeoff is measured on the record every round.
    moe = details.get("moe_mixtral8x1b_int8_b32")
    # Skip (and don't clobber) when the user forced an impl themselves:
    # the comparison is only meaningful against the auto headline.
    user_impl = os.environ.get("VDT_MOE_IMPL")
    if moe and "error" not in moe and user_impl in (None, "auto"):
        from vllm_distributed_tpu.testing import MIXTRAL_8X1B

        os.environ["VDT_MOE_IMPL"] = "ragged"
        try:
            ragged = _run_config(
                shapes=MIXTRAL_8X1B, batch=32, k_steps=16, quant="int8",
                model_kind="mixtral", timed_dispatches=8,
            )
            moe["ragged_tokens_per_sec_p50"] = ragged[
                "tokens_per_sec_p50"
            ]
            moe["auto_vs_ragged_speedup"] = round(
                moe["tokens_per_sec_p50"]
                / max(ragged["tokens_per_sec_p50"], 1e-9),
                2,
            )
        except Exception as e:  # noqa: BLE001 — reported via `failures`
            moe["ragged_oracle_error"] = f"{type(e).__name__}: {e}"
            failures.append("moe_ragged_oracle")
        finally:
            if user_impl is None:
                os.environ.pop("VDT_MOE_IMPL", None)
            else:
                os.environ["VDT_MOE_IMPL"] = user_impl

    # Speculative-decoding gate (ISSUE 11) at 1B.
    spec_detail = None
    if os.environ.get("VDT_BENCH_SPEC", "1") == "1":
        try:
            spec_detail = _spec_probe()
        except Exception as e:  # noqa: BLE001 — reported via `failures`
            spec_detail = {"error": f"{type(e).__name__}: {e}"}
            failures.append("spec_decode")

    serve_detail = None
    if os.environ.get("VDT_BENCH_SERVE", "1") == "1":
        try:
            serve_detail = _serve_probe()
        except Exception as e:  # noqa: BLE001 — reported via `failures`
            serve_detail = {"error": f"{type(e).__name__}: {e}"}
            failures.append("serve_http")
        if serve_detail and "error" not in serve_detail:
            # Matched engine-direct comparison (VERDICT r4 #1 bar:
            # serve >= 50% of engine-direct at the same batch/quant/K).
            try:
                direct = _run_config(
                    shapes=LLAMA_1B, batch=16, k_steps=16, quant="int8",
                    timed_dispatches=8,
                )
                serve_detail["engine_direct_matched_tps"] = direct[
                    "tokens_per_sec"
                ]
                serve_detail["serve_frac_of_engine_direct"] = round(
                    serve_detail["output_tokens_per_s"]
                    / max(direct["tokens_per_sec"], 1e-9),
                    3,
                )
            except Exception as e:  # noqa: BLE001 — via `failures`
                serve_detail["engine_direct_error"] = (
                    f"{type(e).__name__}: {e}"
                )
                failures.append("serve_engine_direct")

    n_chips = jax.local_device_count()
    result = {
        # p50-dispatch-derived steady state (see tokens_per_sec_p50 note
        # in _measure); the wall-clock number is in the config detail.
        "metric": "decode_tokens_per_sec_per_chip_p50",
        "value": round(best["tokens_per_sec_p50"] / n_chips, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": 1.0,
        "detail": {
            "backend": jax.default_backend(),
            "device_kind": _hbm_bw()[0],
            "best_config": best_name,
            # r1/r2 reported wall-clock tokens_per_sec of the 1B bf16
            # b32 config; that continuity number is preserved here.
            "continuity_r2_equivalent_tps": details.get(
                "llama_1b_bf16_b32", {}
            ).get("tokens_per_sec"),
            "pallas_kernel_check": kernel_check,
            "spec_decode": spec_detail,
            "serve_http": serve_detail,
            "configs": details,
            "failures": failures,
        },
    }
    print(json.dumps(result))
    if failures:
        raise SystemExit(f"bench phases failed: {failures}")


if __name__ == "__main__":
    main()
