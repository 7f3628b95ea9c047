"""One MoE layer on the chip, dense einsum against the grouped int8
kernel, by the tokens in the step (PERF.md, PR 27: the sweep that fixed
``models/routed_experts.GROUPED_MIN_ROWS``); with ``--weights bf16``,
unquantized experts instead: the dense einsum against the sorted
dispatch through ``jax.lax.ragged_dot`` and through the grouped kernel
for unquantized experts (``grouped_matmul``), beside one dense SwiGLU FFN
over as many rows as the step routes (the same operations on one
matrix: the MXU share a routed form is held against; PERF.md, PR 38).

For each t it times ``MixtralForCausalLM._mlp_dense`` and
``_mlp_grouped`` on one layer of int8 experts at the given widths
(Mixtral-8x7B's by default), then the grouped path's two kernel calls
on their own: the fused up projection and the down projection (what is
left of ``grouped_ms`` is the sort, the gathers and the combine).
Times are host-clock seconds over back-to-back calls that end in
``block_until_ready`` (the device stays busy: the calls queue).  ``--live`` is the share of rows that are not a
step's padding.

Usage: python tools/moe_microbench.py [--tokens 64 128 ...] [--live 0.75]
       python tools/moe_microbench.py --weights bf16 --hidden 2048
           --intermediate 1792 --experts 32 --top-k 4   (LFM2's widths)
       python tools/moe_microbench.py --cpu-rehearsal   (toy widths,
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

from vllm_distributed_tpu.models.mixtral import MixtralForCausalLM
from vllm_distributed_tpu.models.routed_experts import experts_grouped
from vllm_distributed_tpu.ops.pallas import grouped_matmul as gm
from vllm_distributed_tpu.ops.quant import QuantizedTensor


def _timed(fn, *args, calls: int) -> float:
    for _ in range(3):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls


def _layer(
    key, e: int, h: int, im: int, dtype, stamp: str, weights: str = "int8"
) -> dict:
    keys = jax.random.split(key, 4)

    def qt(k, shape):
        if weights == "bf16":  # unquantized, in the model dtype
            return (jax.random.normal(k, shape, jnp.float32) * 0.02).astype(
                dtype)
        q = jax.random.randint(k, shape, -127, 128, jnp.int8)
        scale = jnp.full((shape[0], shape[2]), 0.02 / 127.0, jnp.float32)
        return QuantizedTensor(q, scale, 8, 0, shape, dtype, stamp)

    return {
        "router": jax.random.normal(keys[0], (h, e), dtype),
        "w1": qt(keys[1], (e, h, im)),
        "w3": qt(keys[2], (e, h, im)),
        "w2": qt(keys[3], (e, im, h)),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, nargs="+",
                    default=[64, 128, 256, 512, 1024, 2048])
    ap.add_argument("--hidden", type=int, default=4096)
    ap.add_argument("--intermediate", type=int, default=14336)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--live", type=float, default=1.0)
    ap.add_argument("--weights", choices=("int8", "bf16"), default="int8")
    ap.add_argument("--block-rows", type=int, default=gm.BLOCK_ROWS)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/moe_microbench.jsonl")
    args = ap.parse_args()
    if args.cpu_rehearsal:
        args.hidden, args.intermediate, args.calls = 128, 256, 2
        dtype, stamp = jnp.float32, "pallas_interpret"
    else:
        if jax.default_backend() != "tpu":
            sys.exit("moe_microbench measures the chip; no TPU here "
                     "(--cpu-rehearsal runs the script at toy widths)")
        dtype, stamp = jnp.bfloat16, "pallas"
    gm.BLOCK_ROWS = args.block_rows
    interpret = stamp == "pallas_interpret"

    model = MixtralForCausalLM.__new__(MixtralForCausalLM)
    model.num_experts, model.top_k = args.experts, args.top_k
    model.norm_topk = True
    e, k, hid, im = args.experts, args.top_k, args.hidden, args.intermediate
    layer = _layer(
        jax.random.PRNGKey(0), e, hid, im, dtype, stamp, args.weights)
    device = jax.devices()[0]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    if args.weights == "bf16":
        _unquantized(args, model, layer, dtype, device)
        return

    for t in args.tokens:
        h = jax.random.normal(jax.random.PRNGKey(t), (t, hid), dtype)
        n_live = max(int(round(t * args.live)), 1)
        live = jnp.arange(t) < n_live
        mp = gm.padded_rows(t * k, e, gm.BLOCK_ROWS)
        gs = model._sorted_dispatch(h, layer, live)[3]
        tiles = int((-(-gs // gm.BLOCK_ROWS)).sum())
        xs = jax.random.normal(jax.random.PRNGKey(1), (mp, hid), dtype)
        inner = jax.random.normal(jax.random.PRNGKey(2), (mp, im), dtype)

        # The layer goes in as an argument: a closed-over array would
        # be baked into each program as a 1.4 GB constant.
        def dense(layer, h):
            return model._mlp_dense(h, layer)

        def grouped(layer, h, live):
            return model._mlp_grouped(h, layer, live)

        def up(layer, xs, gs):
            w1, w3 = layer["w1"], layer["w3"]
            return gm.grouped_int8_matmul(
                xs, w1.q, w1.scale, gs, q_up=w3.q, scale_up=w3.scale,
                interpret=interpret)

        def down(layer, inner, gs):
            w2 = layer["w2"]
            return gm.grouped_int8_matmul(
                inner, w2.q, w2.scale, gs, interpret=interpret)

        calls = args.calls
        row = {
            "t": t, "live_rows": n_live * k, "tiles": tiles,
            "block_rows": gm.BLOCK_ROWS,
            "dense_ms": _timed(jax.jit(dense), layer, h, calls=calls),
            "grouped_ms": _timed(
                jax.jit(grouped), layer, h, live, calls=calls),
            "up_ms": _timed(jax.jit(up), layer, xs, gs, calls=calls),
            "down_ms": _timed(jax.jit(down), layer, inner, gs, calls=calls),
        }
        row = {key: v * 1e3 if key.endswith("_ms") else v
               for key, v in row.items()}
        flops = n_live * k * 3 * 2 * hid * im
        row["grouped_tflops_live_rows"] = flops / row["grouped_ms"] / 1e9
        row["kernels_tflops_live_rows"] = flops / (
            row["up_ms"] + row["down_ms"]) / 1e9
        row["device"] = f"{device.platform}:{device.device_kind}"
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")


def _unquantized(args, model, layer, dtype, device) -> None:
    """Unquantized experts: the dense einsum, the sorted dispatch
    through ``ragged_dot``, and one dense SwiGLU FFN of the expert's
    width over as many rows as the live tokens route."""
    k, hid, im = args.top_k, args.hidden, args.intermediate
    ffn = {name: layer[name][0] for name in ("w1", "w3", "w2")}

    def dense(layer, h):
        return model._mlp_dense(h, layer)

    def ragged(layer, h, live):
        return model._mlp_ragged(h, layer, live)

    interpret = args.cpu_rehearsal

    def grouped(layer, h, live):
        topw, topi = model._route(h, layer)
        return experts_grouped(
            h, topi, topw, layer["w1"], layer["w3"], layer["w2"], live,
            interpret)

    def one_ffn(w, x):
        return (jax.nn.silu(x @ w["w1"]) * (x @ w["w3"])) @ w["w2"]

    for t in args.tokens:
        h = jax.random.normal(jax.random.PRNGKey(t), (t, hid), dtype)
        n_live = max(int(round(t * args.live)), 1)
        live = jnp.arange(t) < n_live
        gs = model._sorted_dispatch(h, layer, live)[3]
        rows = jax.random.normal(
            jax.random.PRNGKey(1), (n_live * k, hid), dtype)
        row = {
            "t": t, "weights": "bf16", "live_rows": n_live * k,
            "busiest_expert_rows": int(gs.max()),
            "dense_ms": _timed(jax.jit(dense), layer, h, calls=args.calls),
            "ragged_ms": _timed(
                jax.jit(ragged), layer, h, live, calls=args.calls),
            "grouped_ms": _timed(
                jax.jit(grouped), layer, h, live, calls=args.calls),
            "ffn_same_rows_ms": _timed(
                jax.jit(one_ffn), ffn, rows, calls=args.calls),
        }
        row = {key: v * 1e3 if key.endswith("_ms") else v
               for key, v in row.items()}
        flops = n_live * k * 3 * 2 * hid * im
        row["ragged_tflops_live_rows"] = flops / row["ragged_ms"] / 1e9
        row["ffn_tflops"] = flops / row["ffn_same_rows_ms"] / 1e9
        row["grouped_tflops_live_rows"] = flops / row["grouped_ms"] / 1e9
        row["ragged_over_ffn"] = row["ragged_ms"] / row["ffn_same_rows_ms"]
        row["grouped_over_ffn"] = (
            row["grouped_ms"] / row["ffn_same_rows_ms"])
        row["device"] = f"{device.platform}:{device.device_kind}"
        print(json.dumps(row), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
