"""Prefix-caching ablation: same prompt set with caching on vs off.

Runs an identical repeated-prompt workload (a shared system-prefix chat
pattern) through two engines — one with ``--enable-prefix-caching``, one
without — and reports warm TTFT (a later round of the same prompts) and
the prefix-cache hit rate for each, plus a greedy-equivalence check that
the cached engine's outputs are bit-identical to the cold engine's.
Prints ONE JSON line, like bench.py.

Three rounds per engine: round 1 is cold (compiles + fills the cache),
round 2 warms the chunk shapes the cached run uses (its prefill token
buckets differ from the cold run's, so measuring it would charge the
cached engine an XLA compile the cold engine never pays), round 3 is the
measured warm round.

``--tiered`` (ISSUE 14) switches to the tiered-index ablation instead:
**flat vs radix vs radix+spill** warm TTFT at a CONSTRAINED page pool
(disjoint chains cycled one at a time, so by the time a chain returns
its pages have been evicted — discarded by the flat cache, spilled to
host DRAM by the tiered one), plus a **restore-vs-recompute crossover
sweep** over prompt lengths (the same workload with the restore path
forced on vs forced off) — the empirical basis for setting
``VDT_KV_SPILL_RESTORE_MIN_TOKENS``.

Invocation (CPU, synthetic weights — no checkpoint needed):

    JAX_PLATFORMS=cpu VDT_USE_PALLAS=reference python tools/prefix_cache_ablation.py
    JAX_PLATFORMS=cpu VDT_USE_PALLAS=reference python tools/prefix_cache_ablation.py --tiered

or against a real model / the TPU:

    python tools/prefix_cache_ablation.py --model meta-llama/Llama-2-7b-hf
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _build_prompts(n: int, prompt_len: int, shared_len: int) -> list[list[int]]:
    shared = [(13 * j) % 900 + 1 for j in range(shared_len)]
    return [
        shared + [(7 * i + 3 * j) % 900 + 1 for j in range(prompt_len - shared_len)]
        for i in range(n)
    ]


def _run_round(engine, prompts, tag: str, max_tokens: int):
    """Submit every prompt, drain, return (outputs, ttft list in s)."""
    from vllm_distributed_tpu.sampling_params import SamplingParams

    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens, ignore_eos=True)
    for i, p in enumerate(prompts):
        engine.add_request(f"{tag}{i}", prompt_token_ids=p, sampling_params=sp)
    done: dict[str, object] = {}
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out
    outs = [done[f"{tag}{i}"] for i in range(len(prompts))]
    ttfts = [o.metrics.ttft for o in outs if o.metrics.ttft is not None]
    cached = [o.metrics.cached_tokens for o in outs]
    return [list(o.outputs[0].token_ids) for o in outs], ttfts, cached


def _measure_mode(model: str, enable: bool, args) -> tuple[dict, list]:
    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine

    engine = LLMEngine.from_engine_args(
        EngineArgs(
            model=model,
            skip_tokenizer_init=True,
            load_format=args.load_format,
            num_kv_pages=args.num_kv_pages,
            page_size=args.page_size,
            max_num_seqs=args.num_prompts,
            max_model_len=args.prompt_len + args.max_tokens + 8,
            enable_prefix_caching=enable,
        )
    )
    prompts = _build_prompts(args.num_prompts, args.prompt_len, args.shared_prefix_len)
    t0 = time.perf_counter()
    outputs, cold_ttfts, _ = _run_round(engine, prompts, "c", args.max_tokens)
    cold_s = time.perf_counter() - t0
    _run_round(engine, prompts, "s", args.max_tokens)  # shape warmer
    t0 = time.perf_counter()
    warm_outputs, warm_ttfts, warm_cached = _run_round(
        engine, prompts, "w", args.max_tokens
    )
    warm_s = time.perf_counter() - t0
    assert warm_outputs == outputs, "warm round diverged from cold round"
    sched = engine.scheduler
    queries, hits = sched.prefix_cache_queries, sched.prefix_cache_hits
    rendered = engine.metrics.render().decode()
    metrics_hits = 0.0
    for line in rendered.splitlines():
        if line.startswith("vllm:prefix_cache_hits_total"):
            metrics_hits = float(line.rsplit(" ", 1)[1])
    detail = {
        "prefix_caching": enable,
        "cold_round_s": round(cold_s, 3),
        "warm_round_s": round(warm_s, 3),
        "ttft_cold_ms_mean": round(statistics.mean(cold_ttfts) * 1e3, 2),
        "ttft_warm_ms_mean": round(statistics.mean(warm_ttfts) * 1e3, 2),
        "ttft_warm_ms_p50": round(statistics.median(warm_ttfts) * 1e3, 2),
        "warm_cached_tokens_per_req": round(statistics.mean(warm_cached), 1),
        "prefix_cache_queries": queries,
        "prefix_cache_hits": hits,
        "prefix_cache_hit_rate": round(hits / queries, 4) if queries else 0.0,
        "metrics_endpoint_hits": metrics_hits,
    }
    engine.shutdown()
    return detail, outputs


def _build_engine(model: str, args, **kw):
    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine

    defaults = dict(
        model=model,
        skip_tokenizer_init=True,
        load_format=args.load_format,
        page_size=args.page_size,
        max_num_seqs=args.num_prompts,
        max_model_len=args.prompt_len + args.max_tokens + 8,
    )
    defaults.update(kw)
    return LLMEngine.from_engine_args(EngineArgs(**defaults))


def _cycle_disjoint(engine, prompts, tag, max_tokens, rounds=3):
    """Cycle disjoint chains ONE AT A TIME through a constrained pool
    (each comes back after the others evicted it); returns the final
    cycle's outputs and TTFTs (in seconds)."""
    outs, ttfts = [], []
    for rnd in range(rounds):
        outs, ttfts = [], []
        for i, p in enumerate(prompts):
            o, t, _ = _run_round(
                engine, [p], f"{tag}{rnd}-{i}", max_tokens
            )
            outs.append(o[0])
            ttfts.extend(t)
    return outs, ttfts


def _measure_tiered(model: str, args) -> dict:
    """flat vs radix vs radix+spill at a constrained pool, plus the
    restore-vs-recompute crossover sweep."""
    prompts = [
        [(101 * (i + 1) + 7 * j) % 900 + 1 for j in range(args.prompt_len)]
        for i in range(args.num_prompts)
    ]
    modes = {
        "flat": dict(
            enable_prefix_caching=True, prefix_cache_index="flat"
        ),
        "radix": dict(enable_prefix_caching=True),
        "radix_spill": dict(
            enable_prefix_caching=True,
            kv_spill_host_pages=args.host_pages,
            kv_spill_restore_min_tokens=args.page_size,
        ),
    }
    report: dict = {"modes": {}}
    baseline = None
    for name, kw in modes.items():
        engine = _build_engine(
            model, args, num_kv_pages=args.constrained_kv_pages, **kw
        )
        outs, ttfts = _cycle_disjoint(
            engine, prompts, name, args.max_tokens
        )
        sched = engine.scheduler
        report["modes"][name] = {
            "warm_ttft_ms_mean": round(statistics.mean(ttfts) * 1e3, 2),
            "warm_ttft_ms_p50": round(statistics.median(ttfts) * 1e3, 2),
            "prefix_cache_hits": sched.prefix_cache_hits,
            "prefix_cache_host_hits": getattr(
                sched, "prefix_cache_hits_host", 0
            ),
            "kv_spill_pages": getattr(sched, "kv_spill_pages", 0),
            "kv_restore_pages": getattr(sched, "kv_restore_pages", 0),
        }
        engine.shutdown()
        if baseline is None:
            baseline = outs
        elif outs != baseline:
            report["modes"][name]["outputs_bit_identical"] = False
    report["outputs_bit_identical"] = all(
        m.get("outputs_bit_identical", True)
        for m in report["modes"].values()
    )
    flat = report["modes"]["flat"]
    tier = report["modes"]["radix_spill"]
    report["gate"] = {
        "hit_tokens_radix_spill_gt_flat": (
            tier["prefix_cache_hits"] > flat["prefix_cache_hits"]
        ),
        "warm_ttft_radix_spill_lt_flat": (
            tier["warm_ttft_ms_mean"] < flat["warm_ttft_ms_mean"]
        ),
    }
    # Crossover sweep: same cycled workload per prompt length, restore
    # forced on (min=1 token) vs off (min > prompt) — where the curves
    # cross is the empirical VDT_KV_SPILL_RESTORE_MIN_TOKENS.
    sweep = []
    for plen in args.crossover_lens:
        row = {"prompt_len": plen}
        chains = [
            [(37 * (i + 3) + 11 * j) % 900 + 1 for j in range(plen)]
            for i in range(args.num_prompts)
        ]
        for policy, min_tokens in (
            ("restore", 1),
            ("recompute", plen + args.page_size),
        ):
            engine = _build_engine(
                model,
                args,
                num_kv_pages=args.constrained_kv_pages,
                max_model_len=plen + args.max_tokens + 8,
                enable_prefix_caching=True,
                kv_spill_host_pages=args.host_pages,
                kv_spill_restore_min_tokens=min_tokens,
            )
            _, ttfts = _cycle_disjoint(
                engine, chains, f"x{plen}{policy}", args.max_tokens
            )
            row[f"{policy}_ttft_ms_mean"] = round(
                statistics.mean(ttfts) * 1e3, 2
            )
            if policy == "restore":
                row["restored_pages"] = engine.scheduler.kv_restore_pages
            engine.shutdown()
        sweep.append(row)
    report["crossover"] = sweep
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default=None, help="default: tiny synthetic llama")
    ap.add_argument("--load-format", default=None, choices=["auto", "dummy"])
    ap.add_argument("--num-prompts", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=256)
    ap.add_argument(
        "--shared-prefix-len",
        type=int,
        default=192,
        help="leading tokens shared by every prompt (system-prompt pattern)",
    )
    ap.add_argument("--max-tokens", type=int, default=8)
    ap.add_argument("--num-kv-pages", type=int, default=1024)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument(
        "--tiered",
        action="store_true",
        help="ISSUE 14 ablation: flat vs radix vs radix+spill at a "
        "constrained pool + restore-vs-recompute crossover sweep",
    )
    ap.add_argument(
        "--constrained-kv-pages",
        type=int,
        default=None,
        help="pool size for the tiered ablation (default: enough for "
        "~half the cycled chains, forcing whole-chain eviction)",
    )
    ap.add_argument(
        "--host-pages",
        type=int,
        default=None,
        help="host-DRAM tier size for the radix+spill mode (default: "
        "enough for every cycled chain)",
    )
    ap.add_argument(
        "--crossover-lens",
        type=str,
        default=None,
        help="comma-separated prompt lengths for the restore-vs-"
        "recompute sweep (default: prompt_len/4, /2, x1, x2)",
    )
    args = ap.parse_args()

    model = args.model
    if model is None:
        from vllm_distributed_tpu.testing import write_llama_config

        model = write_llama_config()
        args.load_format = args.load_format or "dummy"
    args.load_format = args.load_format or "auto"

    if args.tiered:
        per_chain = (args.prompt_len + args.max_tokens) // args.page_size + 2
        if args.constrained_kv_pages is None:
            args.constrained_kv_pages = max(
                per_chain * max(args.num_prompts // 2, 1) + 1, 8
            )
        if args.host_pages is None:
            args.host_pages = per_chain * args.num_prompts
        if args.crossover_lens is None:
            base = args.prompt_len
            args.crossover_lens = sorted(
                {max(base // 4, args.page_size), base // 2, base, 2 * base}
            )
        else:
            args.crossover_lens = [
                int(x) for x in args.crossover_lens.split(",") if x
            ]
        result = {
            "bench": "prefix_cache_ablation",
            "mode": "tiered",
            "model": model,
            "num_prompts": args.num_prompts,
            "prompt_len": args.prompt_len,
            "constrained_kv_pages": args.constrained_kv_pages,
            "host_pages": args.host_pages,
            **_measure_tiered(model, args),
        }
        print(json.dumps(result))
        return

    off, outputs_off = _measure_mode(model, False, args)
    on, outputs_on = _measure_mode(model, True, args)
    result = {
        "bench": "prefix_cache_ablation",
        "model": model,
        "num_prompts": args.num_prompts,
        "prompt_len": args.prompt_len,
        "shared_prefix_len": args.shared_prefix_len,
        "off": off,
        "on": on,
        "warm_ttft_speedup": round(
            off["ttft_warm_ms_mean"] / max(on["ttft_warm_ms_mean"], 1e-9), 2
        ),
        "outputs_bit_identical": outputs_on == outputs_off,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
