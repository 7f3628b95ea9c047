"""Speculative-decoding ablation: same workload with spec on vs off.

Mirrors ``prefix_cache_ablation.py``: runs an identical
repetitive-suffix workload (prompts ending in a repeated pattern — the
extraction/code/quoting shape prompt-lookup targets) through two
engines — one with ``--speculative-ngram-k``, one without — and
reports decode tokens/s, the measured acceptance rate, and a
greedy-equivalence check that the speculative engine's outputs are
bit-identical to the baseline's.  Prints ONE JSON line, like bench.py.

Two rounds per engine: round 1 compiles (prefill buckets + the fused
decode scan / verify program), round 2 is the measured round.  The
headline is ``decode_speedup`` (spec tokens/s over baseline tokens/s on
the measured round) next to ``acceptance_rate`` — speculative decoding
is a bet that acceptance is high enough to beat the fused-decode scan,
and this tool prints both sides of the bet.

Invocation (CPU, synthetic weights — no checkpoint needed):

    JAX_PLATFORMS=cpu VDT_USE_PALLAS=reference python tools/spec_decode_ablation.py

or against a real model / the TPU:

    python tools/spec_decode_ablation.py --model meta-llama/Llama-2-7b-hf
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def build_prompts(
    n: int, prompt_len: int, pattern_len: int
) -> list[list[int]]:
    """Prompts with a distinct preamble and a repeated-pattern suffix:
    the proposer can look the continuation up, the preamble keeps the
    requests (and their KV) distinct."""
    prompts = []
    for i in range(n):
        pattern = [(17 * i + 3 * j) % 700 + 1 for j in range(pattern_len)]
        preamble_len = max(prompt_len - 2 * pattern_len, 0)
        preamble = [(11 * i + 7 * j) % 900 + 1 for j in range(preamble_len)]
        p = (preamble + pattern + pattern)[:prompt_len]
        prompts.append(p)
    return prompts


def _run_round(engine, prompts, tag: str, max_tokens: int):
    from vllm_distributed_tpu.sampling_params import SamplingParams

    sp = SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True
    )
    for i, p in enumerate(prompts):
        engine.add_request(
            f"{tag}{i}", prompt_token_ids=p, sampling_params=sp
        )
    done: dict[str, object] = {}
    first_token_at = None
    t0 = time.perf_counter()
    while engine.has_unfinished_requests():
        for out in engine.step():
            if first_token_at is None and out.outputs[0].token_ids:
                first_token_at = time.perf_counter()
            if out.finished:
                done[out.request_id] = out
    elapsed = time.perf_counter() - t0
    outs = [done[f"{tag}{i}"] for i in range(len(prompts))]
    tokens = sum(len(o.outputs[0].token_ids) for o in outs)
    # Decode throughput excludes prefill: measure from the first token.
    decode_s = (
        time.perf_counter() - first_token_at
        if first_token_at is not None
        else elapsed
    )
    return (
        [list(o.outputs[0].token_ids) for o in outs],
        tokens,
        elapsed,
        max(decode_s, 1e-9),
    )


def _measure_mode(model: str, spec_k: int, args) -> dict:
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
            num_decode_steps=args.num_decode_steps,
            speculative_ngram_k=spec_k,
        )
    )
    prompts = build_prompts(
        args.num_prompts, args.prompt_len, args.pattern_len
    )
    try:
        outputs, _, _, _ = _run_round(
            engine, prompts, "c", args.max_tokens
        )  # compile round
        sched = engine.scheduler
        drafted0, accepted0 = (
            sched.spec_drafted_tokens,
            sched.spec_accepted_tokens,
        )
        warm_outputs, tokens, elapsed, decode_s = _run_round(
            engine, prompts, "w", args.max_tokens
        )
        assert warm_outputs == outputs, "warm round diverged"
        drafted = sched.spec_drafted_tokens - drafted0
        accepted = sched.spec_accepted_tokens - accepted0
        return {
            "spec_ngram_k": spec_k,
            "output_tokens": tokens,
            "round_s": round(elapsed, 3),
            "decode_s": round(decode_s, 3),
            "tokens_per_sec": round(tokens / elapsed, 1),
            "decode_tokens_per_sec": round(tokens / decode_s, 1),
            "drafted_tokens": drafted,
            "accepted_tokens": accepted,
            "acceptance_rate": (
                round(accepted / drafted, 4) if drafted else 0.0
            ),
            "outputs": outputs,
        }
    finally:
        engine.shutdown()


def run_ablation(model: str, args) -> dict:
    """On/off comparison; importable by bench.py.  The returned dict's
    ``gate_pass`` field asserts the bet: with acceptance >= the
    ``--gate-acceptance`` floor the speculative engine must deliver
    >= ``--gate-speedup`` x decode tokens/s (below the floor the gate
    abstains — drafts that never match cannot win, and the fused-decode
    fallback keeps the loss bounded)."""
    off = _measure_mode(model, 0, args)
    on = _measure_mode(model, args.spec_k, args)
    identical = on.pop("outputs") == off.pop("outputs")
    speedup = round(
        on["decode_tokens_per_sec"]
        / max(off["decode_tokens_per_sec"], 1e-9),
        3,
    )
    gated = on["acceptance_rate"] >= args.gate_acceptance
    result = {
        "bench": "spec_decode_ablation",
        "model": model,
        "num_prompts": args.num_prompts,
        "prompt_len": args.prompt_len,
        "pattern_len": args.pattern_len,
        "max_tokens": args.max_tokens,
        "off": off,
        "on": on,
        "acceptance_rate": on["acceptance_rate"],
        "decode_speedup": speedup,
        "outputs_bit_identical": identical,
        "gate_applicable": gated,
        "gate_pass": bool(
            identical and (not gated or speedup >= args.gate_speedup)
        ),
    }
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--model", default=None, help="default: tiny synthetic llama"
    )
    ap.add_argument(
        "--load-format", default=None, choices=["auto", "dummy"]
    )
    ap.add_argument("--num-prompts", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument(
        "--pattern-len",
        type=int,
        default=24,
        help="repeated-suffix pattern length (the draftable tail)",
    )
    ap.add_argument("--max-tokens", type=int, default=48)
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--num-decode-steps", type=int, default=8)
    ap.add_argument("--num-kv-pages", type=int, default=1024)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument(
        "--gate-acceptance",
        type=float,
        default=0.5,
        help="acceptance floor below which the speedup gate abstains",
    )
    ap.add_argument(
        "--gate-speedup",
        type=float,
        default=1.3,
        help="required decode tokens/s multiple when the gate applies",
    )
    ap.add_argument(
        "--strict",
        action="store_true",
        help="exit 1 when the gate fails (bit-identity always fatal)",
    )
    args = ap.parse_args()

    model = args.model
    if model is None:
        from vllm_distributed_tpu.testing import write_llama_config

        model = write_llama_config()
        args.load_format = args.load_format or "dummy"
    args.load_format = args.load_format or "auto"

    result = run_ablation(model, args)
    print(json.dumps(result))
    if not result["outputs_bit_identical"]:
        sys.exit(2)
    if args.strict and not result["gate_pass"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
