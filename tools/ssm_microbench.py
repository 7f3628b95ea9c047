"""One layer's chunked state-space scan on the chip, the plain form
(``ops/ssm.ssd_chunk_scan``) against the kernel (``vdt_ssd_chunk_scan``,
ops/pallas/ssd_chunk_scan.py), by the step's token bucket, at the
Falcon-H1 cell's widths (32 heads of 128, 2 groups of 256, chunk 128,
bfloat16 activations and state) over 64 state rows.

A step of bucket ``t`` is the cell's mix: ``--decodes`` one-token rows
of running sequences first (the scheduler puts them ahead of the
prompts), then prompt chunks that fill ``--live`` of the bucket's rest
between them (one long chunk with a stored state, one fresh, one short
tail), every sequence on a state row of its own.  ``--mix prompt`` is
one fresh sequence over the whole bucket, ``--mix decodes`` 64
one-token rows through the chunked form.

Times are host-clock seconds over back-to-back calls that end in
``block_until_ready`` (the state is donated and fed back, as a step
does).  ``roofline_pct`` is ``benchmark/kernel_model_falcon_h1
.chunk_scan`` for one layer at the step's live tokens and state rows
over the kernel's time; ``walked`` is what ``ssm_tokens_tiled`` counts
on the kernel's path.

Usage: python tools/ssm_microbench.py [--tokens 256 512 1024 2048]
       python tools/ssm_microbench.py --cpu-rehearsal   (toy widths,
       interpreted kernel: counts nothing, proves the script runs)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import kernel_model_falcon_h1 as kernel_model
from vllm_distributed_tpu.ops import ssm
from vllm_distributed_tpu.ops.pallas import ssd_chunk_scan as kernel

CELL = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                    "falcon-h1-34b-cut-bf16.json")
PEAKS = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                     "peaks.json")


def _timed(fn, args, state, calls: int) -> float:
    for _ in range(3):
        y, state = fn(*args, state)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(calls):
        y, state = fn(*args, state)
    jax.block_until_ready((y, state))
    return (time.perf_counter() - t0) / calls


def step_lengths(t: int, mix: str, decodes: int, live: float) -> list[int]:
    """Tokens a sequence of the step, in batch order."""
    if mix == "prompt":
        return [t]
    if mix == "decodes":
        return [1] * min(decodes + 4, t)
    ones = min(decodes, t // 2)
    rest = max(int((t - ones) * live), 3)
    tail = max(rest // 16, 1)
    fresh = max(rest // 3, 1)
    return [1] * ones + [rest - fresh - tail, fresh, tail]


def step_inputs(lengths, t, s_pad, rows, cfg, dtype, seed=0):
    """The scan's arguments for a step of ``lengths``: the long prompt
    chunk (the first sequence of several tokens) continues from
    position 300, the other prompts are fresh, a one-token row is a
    sequence at a position past 0."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    rng = np.random.default_rng(seed)
    f32 = np.float32
    sid = np.full(t, s_pad, np.int32)
    pos = np.zeros(t, np.int32)
    slots = np.full(s_pad, rows, np.int32)
    last = np.zeros(s_pad, np.int32)
    starts = np.zeros(s_pad, np.int32)
    perm = rng.permutation(rows)
    lo, seen_prompt = 0, False
    for i, ln in enumerate(lengths):
        start = 300 + i if ln == 1 or not seen_prompt else 0
        seen_prompt |= ln > 1
        sid[lo:lo + ln] = i
        pos[lo:lo + ln] = np.arange(start, start + ln)
        slots[i], last[i], starts[i] = perm[i], lo + ln - 1, start
        lo += ln
    x = rng.standard_normal((t, h, p)).astype(f32)
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (t, h))).astype(f32)
    a = -rng.uniform(1, 16, h).astype(f32)
    b = rng.standard_normal((t, g, n)).astype(f32) * 0.1
    c = rng.standard_normal((t, g, n)).astype(f32) * 0.1
    args = (
        jnp.asarray(x, dtype), jnp.asarray(dt), jnp.asarray(a),
        jnp.asarray(b, dtype), jnp.asarray(c, dtype), jnp.ones((h,), f32),
    )
    kw = dict(
        seq_ids=jnp.asarray(sid), positions=jnp.asarray(pos),
        chunk_starts=jnp.asarray(starts), slots=jnp.asarray(slots),
        last_rows=jnp.asarray(last),
    )
    state = jnp.asarray(
        rng.standard_normal((rows, h, p, n)).astype(f32) * 0.1, dtype)
    return args, kw, state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, nargs="+",
                    default=[256, 512, 1024, 2048])
    ap.add_argument("--mix", choices=("cell", "prompt", "decodes"),
                    default="cell")
    ap.add_argument("--decodes", type=int, default=60)
    ap.add_argument("--live", type=float, default=0.85)
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--heads", type=int, default=None,
                    help="heads a grid step (the kernel's own choice)")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--skip-plain", action="store_true")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/ssm_microbench.jsonl")
    args = ap.parse_args()
    with open(CELL) as f:
        cfg = json.load(f)
    with open(PEAKS) as f:
        peaks = json.load(f)
    if args.cpu_rehearsal:
        cfg = dict(cfg, mamba_n_heads=4, mamba_d_state=128,
                   mamba_chunk_size=16)
        args.tokens, args.calls, args.decodes = [64], 1, 6
        dtype, interpret = jnp.float32, True
        peak = next(iter(peaks.values()))
    else:
        if jax.default_backend() != "tpu":
            sys.exit("ssm_microbench measures the chip; no TPU here "
                     "(--cpu-rehearsal runs the script at toy widths)")
        dtype, interpret = jnp.bfloat16, False
        peak = peaks[jax.devices()[0].device_kind]
    chunk = cfg["mamba_chunk_size"]
    device = jax.devices()[0]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    one_layer = dict(cfg, num_hidden_layers=1)

    for t in args.tokens:
        lengths = step_lengths(t, args.mix, args.decodes, args.live)
        s_pad = max(args.rows, len(lengths))
        inputs, kw, state = step_inputs(
            lengths, t, s_pad, s_pad + 1, cfg, dtype)

        def plain(*a):
            return ssm.ssd_chunk_scan(*a, chunk=chunk, **kw)

        def fused(*a):
            return kernel.ssd_chunk_scan(
                *a, chunk=chunk, heads=args.heads, interpret=interpret, **kw)

        live = sum(lengths)
        floor = kernel_model.chunk_scan(one_layer, live, len(lengths), peak)
        row = {
            "t": t, "mix": args.mix, "seqs": len(lengths),
            "one_token_rows": sum(1 for n in lengths if n == 1),
            "live_tokens": live,
            "walked": kernel.walked_tokens(lengths, chunk),
            "floor_ms": floor["seconds"] * 1e3, "binds": floor["binds"],
        }
        donate = jax.jit(fused, donate_argnums=(6,))
        row["kernel_ms"] = _timed(donate, inputs, state + 0, args.calls) * 1e3
        if not args.skip_plain:
            row["plain_ms"] = _timed(
                jax.jit(plain, donate_argnums=(6,)), inputs, state + 0,
                args.calls) * 1e3
            y0, s0 = jax.jit(plain)(*inputs, state)
            y1, s1 = jax.jit(fused)(*inputs, state)
            f32 = jnp.float32
            row["y_diff_max"] = float(jnp.abs(y0 - y1)[:live].max())
            row["y_abs_max"] = float(jnp.abs(y0)[:live].max())
            row["state_diff_max"] = float(
                jnp.abs(s0.astype(f32) - s1.astype(f32)).max())
            row["state_abs_max"] = float(jnp.abs(s0.astype(f32)).max())
            row["plain_over_kernel"] = row["plain_ms"] / row["kernel_ms"]
        if not args.cpu_rehearsal:
            row["roofline_pct"] = 100.0 * row["floor_ms"] / row["kernel_ms"]
        row["device"] = f"{device.platform}:{device.device_kind}"
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
