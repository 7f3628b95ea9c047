"""Mixtral / Qwen3-MoE sparse-MoE decoder (milestone config 5; the
reference's flagship deployment is a Qwen3-Coder MoE,
/root/reference/.env.server:11).

The attention block, forward loop, and weight plumbing are inherited from
the Llama decoder (models/llama.py) — only the MLP is swapped for a
top-k routed mixture of experts:

    router: logits = x @ Wg            [T, E]
    probs  = softmax(logits)           (float32, HF semantics)
    topw, topi = top_k(probs, k)       renormalized when norm_topk_prob

The default expert computation is a *sorted ragged dispatch*
(VDT_MOE_IMPL=ragged): the T×k token→expert assignments are flattened,
sorted by expert, and each projection is ONE grouped matmul
(jax.lax.ragged_dot) over the sorted rows — ~k/E of the dense FLOPs,
which is what makes a 160-expert/8-active flagship
(Qwen3-Coder-480B-A35B, the reference's deployment) servable.  Token drop/capacity
factors are never used — inference must match the reference exactly.

Int8 experts on one chip take the same sorted dispatch through a
Pallas kernel that streams the int8 tiles (ops/pallas/grouped_matmul)
in many-row steps; ``select_moe_impl`` below has the rule.

VDT_MOE_IMPL=dense keeps the GShard-style dense dispatch (every expert
on every token, a [T, E] combine matrix) as the correctness oracle:

    h1 = einsum('th,ehi->tei', x, W1); h3 = likewise W3
    y  = einsum('tei,eih,te->th', silu(h1)*h3, W2, combine)

Sharding: under EP the expert axis E is sharded over "tp" — each device
holds E/tp whole experts and, since activations are replicated over the
tp group, runs the grouped matmul over the full sorted row range with
rows outside its experts' contiguous slice folded into the edge groups
(keeping row offsets aligned with no weight copies) and masked from the
psum combine — an all-to-all-free EP layout.  Without EP each expert
splits over its intermediate dim exactly like the dense MLP.

Sliding-window attention (some Mixtral checkpoints set sliding_window) is
not applied; contexts are served full via the paged KV cache, matching
vLLM's default for Mixtral-8x7B (config ships null).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_ROUTER,
)
from vllm_distributed_tpu.models.llama import LlamaForCausalLM

# Routed rows (tokens × top_k) above which int8 experts on one chip run
# the grouped kernel instead of the dense einsum.  Below it the step is
# bound by reading the experts' weights, which both do once, and the
# grouped path pads every expert to a 128-row tile: nothing to win.
# Fixed from a one-layer sweep on the v5e at Mixtral's widths (PERF.md,
# PR 27).
GROUPED_MIN_ROWS = 256


def select_moe_impl(requested: str, rows: int, w1, on_mesh: bool) -> str:
    """"dense" | "ragged" | "grouped" for one expert layer, from
    VDT_MOE_IMPL (``requested``), the routed rows of the step being
    traced and the expert tensor itself.

    ``auto``: int8 experts stamped for the Pallas backend, on one chip,
    in a step of more than GROUPED_MIN_ROWS routed rows (prefill, a
    mixed step, a spec-verify step) take the grouped int8 kernel: such
    a step is compute-bound and the dense einsum spends E/k times the
    model's FLOPs there (step_experts_ms 53.97 -> 16.70, ledger, PR 27).
    Every other quantized case stays dense: decode is
    weight-BANDWIDTH-bound, where XLA fuses the dequant into the einsum
    so the compressed bytes stream once (decode_experts_roofline_pct
    92.6, ledger, PR 30), and ``ragged_dot`` cannot fuse a producer: it
    materializes the bf16 expert stack per call (pre-round; its cost is
    not measured on this tree) — a statement about ragged_dot, not about
    routing.  Unquantized experts route through ragged_dot on big row
    counts.  ``ragged`` on a tensor the grouped kernel takes means that
    kernel; ``dense`` is always the dense einsum."""
    from vllm_distributed_tpu.ops.pallas import grouped_matmul as gm
    from vllm_distributed_tpu.ops.quant import QuantizedTensor

    quantized = isinstance(w1, QuantizedTensor)
    grouped_ok = (
        quantized
        and w1.bits == 8
        and w1.matmul in ("pallas", "pallas_interpret")
        and not on_mesh
        and w1.mesh is None
        and (
            w1.matmul == "pallas_interpret"
            or gm.has_tiles(w1.shape[-2], w1.shape[-1])
        )
    )
    if requested == "auto":
        if grouped_ok and rows > GROUPED_MIN_ROWS:
            return "grouped"
        return "dense" if (quantized or rows <= 256) else "ragged"
    if requested == "ragged" and grouped_ok:
        return "grouped"
    return requested


class MixtralForCausalLM(LlamaForCausalLM):
    architectures = (
        "MixtralForCausalLM",
        "Qwen3MoeForCausalLM",
    )
    # Router stays unquantized (tiny and routing-decision-sensitive).
    QUANT_PARAMS = (
        LlamaForCausalLM.QUANT_PARAMS - {"gate", "up", "down"}
    ) | {"w1", "w2", "w3"}

    def __init__(self, model_config: Any) -> None:
        super().__init__(model_config)
        hf = model_config.hf_config
        self.qk_norm = self.model_type == "qwen3_moe"
        self.attn_bias = False
        # MoE shape: Mixtral uses num_local_experts/intermediate_size,
        # Qwen3-MoE num_experts/moe_intermediate_size.
        self.num_experts = int(
            getattr(hf, "num_local_experts", 0)
            or getattr(hf, "num_experts", 0)
        )
        if self.num_experts <= 0:
            raise ValueError(
                f"{self.architectures[0]} requires an expert count "
                "(num_local_experts/num_experts) in the HF config"
            )
        self.top_k = int(getattr(hf, "num_experts_per_tok", 2))
        self.moe_intermediate = int(
            getattr(hf, "moe_intermediate_size", 0)
            or getattr(hf, "intermediate_size", 0)
        )
        # Mixtral always renormalizes the top-k probs; Qwen3-MoE gates it.
        self.norm_topk = bool(getattr(hf, "norm_topk_prob", True))
        self.expert_parallel = bool(
            getattr(model_config, "enable_expert_parallel", False)
        )
        if getattr(hf, "mlp_only_layers", None):
            raise NotImplementedError(
                "mlp_only_layers (dense layers mixed into an MoE stack) "
                "is not supported yet"
            )
        if int(getattr(hf, "decoder_sparse_step", 1) or 1) != 1:
            raise NotImplementedError("decoder_sparse_step > 1 not supported")

    def validate_mesh(self, mesh) -> None:
        """Pre-placement check (called by the loader before any
        device_put): EP shards whole experts over the tp axis."""
        self._mesh = mesh  # the ragged dispatch shard_maps over it
        tp = mesh.shape.get("tp", 1)
        if self.expert_parallel and self.num_experts % tp:
            raise ValueError(
                f"expert parallelism needs num_experts "
                f"({self.num_experts}) divisible by tp ({tp})"
            )

    # ---- params ----
    def init_params(self, rng: jax.Array) -> dict:
        """Random init: Llama tree with the dense MLP swapped for
        router + stacked expert weights."""
        params = super().init_params(rng)
        e, h, im = self.num_experts, self.hidden_size, self.moe_intermediate

        def nrm(key, shape):
            return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(
                self.dtype
            )

        keys = iter(
            jax.random.split(jax.random.fold_in(rng, 1), 4 * self.num_layers)
        )
        for layer in params["layers"]:
            for dense in ("gate", "up", "down"):
                del layer[dense]
            layer["router"] = nrm(next(keys), (h, e))
            layer["w1"] = nrm(next(keys), (e, h, im))
            layer["w3"] = nrm(next(keys), (e, h, im))
            layer["w2"] = nrm(next(keys), (e, im, h))
        return params

    def map_hf_name(self, name: str):
        """MoE names (router + per-expert tensors) resolved here; the
        attention/embedding names fall through to the Llama table.

        Mixtral: model.layers.{i}.block_sparse_moe.gate.weight and
        .experts.{e}.w{1,2,3}.weight; Qwen3-MoE: mlp.gate.weight and
        mlp.experts.{e}.{gate,down,up}_proj.weight.  Per-expert tensors
        land at ("layers", i, wN, e) and are stacked to [E, ...] by
        finalize_params.
        """
        if name.startswith("model.layers."):
            parts = name.split(".")
            i = int(parts[2])
            rest = ".".join(parts[3:])
            if rest in ("block_sparse_moe.gate.weight", "mlp.gate.weight"):
                return ("layers", i, "router"), "T"
            for prefix in ("block_sparse_moe.experts.", "mlp.experts."):
                if rest.startswith(prefix):
                    eparts = rest[len(prefix) :].split(".")
                    e = int(eparts[0])
                    which = {
                        "w1.weight": "w1",
                        "w2.weight": "w2",
                        "w3.weight": "w3",
                        "gate_proj.weight": "w1",
                        "down_proj.weight": "w2",
                        "up_proj.weight": "w3",
                    }.get(".".join(eparts[1:]))
                    if which is None:
                        return None
                    return ("layers", i, which, e), "T"
        return super().map_hf_name(name)

    def _expert_specs(self) -> dict:
        """Final (stacked [E, ...]) specs for the expert tensors."""
        if self.expert_parallel:
            # Whole experts sharded over the tp axis: E % tp must hold.
            return {
                "w1": P("tp", None, None),
                "w3": P("tp", None, None),
                "w2": P("tp", None, None),
            }
        # Dense-MLP-style: split every expert over its intermediate dim.
        return {
            "w1": P(None, None, "tp"),
            "w3": P(None, None, "tp"),
            "w2": P(None, "tp", None),
        }

    def partition_specs(self) -> dict:
        specs = super().partition_specs()
        expert = self._expert_specs()
        for layer in specs["layers"]:
            for dense in ("gate", "up", "down"):
                del layer[dense]
            layer["router"] = P()
            layer.update(expert)
        return specs

    def load_specs(self) -> dict:
        """Per-tensor specs used DURING HF load, where expert tensors are
        still unstacked ({e: [h, im]} dicts).  Under EP an unstacked
        expert's final home is one device group, which NamedSharding
        cannot express for a single tensor — so in-flight experts shard
        over their input dim (bounded memory: tensor/tp per device) and
        finalize_params reshards the per-layer stack to the expert
        layout."""
        specs = self.partition_specs()
        if self.expert_parallel:
            per_expert = {
                "w1": P("tp", None),
                "w3": P("tp", None),
                "w2": P("tp", None),
            }
        else:
            per_expert = {
                "w1": P(None, "tp"),
                "w3": P(None, "tp"),
                "w2": P("tp", None),
            }
        for layer in specs["layers"]:
            for name, spec in per_expert.items():
                layer[name] = {e: spec for e in range(self.num_experts)}
        return specs

    def finalize_params(self, params: dict, mesh) -> dict:
        """Stack per-expert weight dicts into [E, ...] arrays with the
        final sharding (called by the loader after all tensors land).
        Quantized experts stack their q/scale parts.  Stacking runs
        under jit with explicit out_shardings so XLA reshards in-flight
        (input-dim shards -> expert shards) without a replicated
        transient — the per-layer peak stays O(layer/tp) per device."""
        from jax.sharding import NamedSharding

        from vllm_distributed_tpu.ops.quant import (
            QuantizedTensor,
            aligned_spec,
            quant_spec,
        )

        def stack_to(parts, spec):
            if mesh is None:
                return jnp.stack(parts)
            out = NamedSharding(
                mesh,
                aligned_spec(
                    spec, (len(parts), *parts[0].shape), mesh
                ),
            )
            return jax.jit(
                lambda *xs: jnp.stack(xs), out_shardings=out
            )(*parts)

        final = self._expert_specs()
        for layer in params["layers"]:
            for name in ("w1", "w2", "w3"):
                entry = layer.get(name)
                if not isinstance(entry, dict):
                    continue
                if sorted(entry) != list(range(self.num_experts)):
                    raise ValueError(
                        f"checkpoint is missing experts for {name}: "
                        f"have {sorted(entry)}, want 0..{self.num_experts - 1}"
                    )
                parts = [entry[e] for e in range(self.num_experts)]
                if isinstance(parts[0], QuantizedTensor):
                    qs = quant_spec(final[name], parts[0].bits)
                    layer[name] = QuantizedTensor(
                        q=stack_to([p.q for p in parts], qs.q),
                        scale=stack_to([p.scale for p in parts], qs.scale),
                        bits=parts[0].bits,
                        group=parts[0].group,
                        shape=(self.num_experts, *parts[0].shape),
                        dtype=parts[0].dtype,
                        matmul=parts[0].matmul,
                    )
                    continue
                layer[name] = stack_to(parts, final[name])
        return params

    # ---- forward (attention loop inherited; MLP is the routed MoE) ----
    def _route(self, h: jax.Array, layer: dict):
        """Router: top-k expert ids + (renormalized) weights per token."""
        logits = h @ layer["router"].astype(h.dtype)  # [T, E]
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        topw, topi = jax.lax.top_k(probs, self.top_k)  # [T, k]
        if self.norm_topk:
            topw = topw / topw.sum(axis=-1, keepdims=True)
        return topw, topi

    def _moe_impl(self) -> str:
        from vllm_distributed_tpu import envs

        return envs.VDT_MOE_IMPL

    def moe_impl(self, layer: dict, tokens: int) -> str:
        """What ``_mlp`` traces for a step of ``tokens`` rows over this
        layer's experts: "dense" | "ragged" | "grouped".  A function of
        the trace-time shapes and of what the tensor says of itself, so
        the runner can say which one a dispatched program holds."""
        return select_moe_impl(
            self._moe_impl(),
            tokens * self.top_k,
            layer["w1"],
            on_mesh=getattr(self, "_mesh", None) is not None,
        )

    def _mlp(
        self, h: jax.Array, layer: dict, live: jax.Array | None = None
    ) -> jax.Array:
        impl = self.moe_impl(layer, h.shape[0])
        if impl == "dense":
            return self._mlp_dense(h, layer)
        if impl == "grouped":
            return self._mlp_grouped(h, layer, live)
        return self._mlp_ragged(h, layer)

    def _mlp_dense(self, h: jax.Array, layer: dict) -> jax.Array:
        """GShard-style dense dispatch — every expert runs on every
        token, a [T, E] combine matrix (zeros outside the top-k) weights
        the results.  Exact and GSPMD-friendly; the correctness oracle
        for the routed paths and the fallback for shapes they reject."""
        from vllm_distributed_tpu.ops.quant import maybe_dequantize

        t = h.shape[0]
        with jax.named_scope(SCOPE_MOE_ROUTER):
            topw, topi = self._route(h, layer)
            combine = (
                jnp.zeros((t, self.num_experts), jnp.float32)
                .at[jnp.arange(t)[:, None], topi]
                .set(topw)
                .astype(h.dtype)
            )
        with jax.named_scope(SCOPE_MOE_EXPERTS):
            w1 = maybe_dequantize(layer["w1"], h.dtype)
            w3 = maybe_dequantize(layer["w3"], h.dtype)
            w2 = maybe_dequantize(layer["w2"], h.dtype)
            h1 = jnp.einsum("th,ehi->tei", h, w1)
            h3 = jnp.einsum("th,ehi->tei", h, w3)
            inner = jax.nn.silu(h1) * h3
            return jnp.einsum("tei,eih,te->th", inner, w2, combine)

    def _sorted_dispatch(
        self, h: jax.Array, layer: dict, live: jax.Array | None = None
    ):
        """The routed paths' shared first half: route, flatten the T×k
        assignments and sort them by expert.  Returns (topw [T, k],
        flat expert ids [T*k], order [T*k]: sorted row -> flat
        assignment, group sizes [E]).  Rows of tokens outside ``live``
        (a step's padding) take the id E: they sort last and join no
        group."""
        e, k = self.num_experts, self.top_k
        topw, topi = self._route(h, layer)
        flat_e = topi.reshape(-1).astype(jnp.int32)  # [T*k]
        if live is not None:
            flat_e = jnp.where(jnp.repeat(live, k), flat_e, e)
        order = jnp.argsort(flat_e)
        gs = (flat_e[:, None] == jnp.arange(e, dtype=jnp.int32)).sum(
            axis=0, dtype=jnp.int32
        )
        return topw, flat_e, order, gs

    def _mlp_ragged(self, h: jax.Array, layer: dict) -> jax.Array:
        """Sorted ragged dispatch (SURVEY §2.5's TPU plan; VERDICT r3
        #4): flatten the T×k assignments, sort rows by expert, run ONE
        grouped matmul per projection (jax.lax.ragged_dot), and
        scatter-add the weighted results back — ~k/E of the dense
        path's expert FLOPs, which is what makes a 160-expert/8-active
        flagship servable.

        Sharding: under EP each device holds E/tp whole experts; the
        activations are replicated over "tp", so instead of an
        all-to-all each shard runs the grouped matmul over the FULL
        sorted row range with its local expert stack — rows outside its
        experts' range fold into the edge groups (so group offsets stay
        aligned without padding/copying weights) and are masked from
        the psum-combined output.  Without EP, experts split over their
        intermediate dim like the dense MLP (partial products psum)."""
        from vllm_distributed_tpu.ops.quant import maybe_dequantize

        t = h.shape[0]
        k = self.top_k
        with jax.named_scope(SCOPE_MOE_ROUTER):
            topw, _, order, gs = self._sorted_dispatch(h, layer)
            tok = order // k
            xs = h[tok]  # [T*k, H] sorted by expert
            row_w = topw.reshape(-1)[order].astype(h.dtype)  # [T*k]

        with jax.named_scope(SCOPE_MOE_EXPERTS):
            w1 = maybe_dequantize(layer["w1"], h.dtype)
            w3 = maybe_dequantize(layer["w3"], h.dtype)
            w2 = maybe_dequantize(layer["w2"], h.dtype)

            mesh = getattr(self, "_mesh", None)
            tp = mesh.shape.get("tp", 1) if mesh is not None else 1
            if tp > 1 and self.expert_parallel:
                orows = self._ragged_ep(xs, gs, w1, w3, w2, mesh, tp)
            elif tp > 1:
                orows = self._ragged_tp(xs, gs, w1, w3, w2, mesh)
            else:
                h1 = jax.lax.ragged_dot(xs, w1, gs)
                h3 = jax.lax.ragged_dot(xs, w3, gs)
                inner = jax.nn.silu(h1) * h3
                orows = jax.lax.ragged_dot(inner, w2, gs)

        # f32 accumulation for the k-way combine: the dense oracle's
        # einsum promotes the combine matrix to f32, so a bf16
        # scatter-add here would drift from it (ADVICE r4 #1).
        y = jnp.zeros((t, h.shape[1]), jnp.float32)
        contrib = orows.astype(jnp.float32) * row_w.astype(jnp.float32)[
            :, None
        ]
        return y.at[tok].add(contrib).astype(h.dtype)

    def _mlp_grouped(
        self, h: jax.Array, layer: dict, live: jax.Array | None = None
    ) -> jax.Array:
        """The sorted dispatch over int8 experts on one chip: the rows
        go through ops/pallas/grouped_matmul, which streams the int8
        tiles themselves (no bf16 copy of the stack, which is what
        ``ragged_dot`` would need).  Each expert's group is padded to
        whole row tiles, so the gather writes that layout directly and
        the combine gathers back out of it (a token's k rows, weighted
        and summed in f32 like the other paths) instead of
        scatter-adding.  Tokens outside ``live`` join no group and
        come back as zeros."""
        from vllm_distributed_tpu.ops.pallas import grouped_matmul as gm

        t = h.shape[0]
        e, k = self.num_experts, self.top_k
        m = t * k
        w1, w3, w2 = layer["w1"], layer["w3"], layer["w2"]
        interpret = w1.matmul == "pallas_interpret"
        mp = gm.padded_rows(m, e, gm.BLOCK_ROWS)
        with jax.named_scope(SCOPE_MOE_ROUTER):
            topw, flat_e, order, gs = self._sorted_dispatch(h, layer, live)
            starts, tile_expert, _ = gm.group_layout(gs, mp, gm.BLOCK_ROWS)
            # sorted row r of expert e sits at padded row r + shift[e]
            shift = starts - (jnp.cumsum(gs) - gs)
            row_e = jnp.repeat(tile_expert, gm.BLOCK_ROWS)
            src = jnp.arange(mp, dtype=jnp.int32) - shift[row_e]
            xs = h[order[jnp.clip(src, 0, m - 1)] // k]  # [MP, H]
            # where each assignment's row went: its rank among the
            # sorted rows, moved into its expert's padded range
            rank = jnp.argsort(order).astype(jnp.int32)
            pos = rank + shift[jnp.minimum(flat_e, e - 1)]
            pos = jnp.minimum(pos, mp - 1).reshape(t, k)

        with jax.named_scope(SCOPE_MOE_EXPERTS):
            inner = gm.grouped_int8_matmul(
                xs, w1.q, w1.scale, gs, q_up=w3.q, scale_up=w3.scale,
                interpret=interpret,
            )
            orows = gm.grouped_int8_matmul(
                inner, w2.q, w2.scale, gs, interpret=interpret
            )

        w = topw.astype(h.dtype).astype(jnp.float32)[..., None]
        y = (orows[pos].astype(jnp.float32) * w).sum(axis=1)
        if live is not None:
            y = jnp.where(live[:, None], y, 0.0)
        return y.astype(h.dtype)

    def _ragged_ep(self, xs, gs, w1, w3, w2, mesh, tp):
        """EP shard_map: each device's local experts own a contiguous
        range of the sorted rows; out-of-range rows are folded into the
        first/last local group (keeping offsets aligned without weight
        copies), computed as garbage, masked, and psum-combined."""
        from jax.sharding import PartitionSpec as P

        e = self.num_experts
        e_local = e // tp
        m = xs.shape[0]

        def body(xs_, gs_, w1_, w3_, w2_):
            idx = jax.lax.axis_index("tp")
            cum = jnp.cumsum(gs_)
            lo_e = idx * e_local
            start = jnp.where(lo_e > 0, cum[jnp.maximum(lo_e - 1, 0)], 0)
            end = cum[lo_e + e_local - 1]
            gs_local = jax.lax.dynamic_slice(gs_, (lo_e,), (e_local,))
            # Fold the out-of-range rows into the edge groups.
            gs_fold = gs_local.at[0].add(start)
            gs_fold = gs_fold.at[e_local - 1].add(m - end)
            h1 = jax.lax.ragged_dot(xs_, w1_, gs_fold)
            h3 = jax.lax.ragged_dot(xs_, w3_, gs_fold)
            inner = jax.nn.silu(h1) * h3
            orows = jax.lax.ragged_dot(inner, w2_, gs_fold)
            rows = jnp.arange(m, dtype=jnp.int32)
            in_range = (rows >= start) & (rows < end)
            orows = jnp.where(in_range[:, None], orows, 0)
            return jax.lax.psum(orows, "tp")

        f = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(), P(),
                P("tp", None, None), P("tp", None, None),
                P("tp", None, None),
            ),
            out_specs=P(),
            check_vma=False,
        )
        return f(xs, gs, w1, w3, w2)

    def _ragged_tp(self, xs, gs, w1, w3, w2, mesh):
        """Non-EP tp: experts split over the intermediate dim (like the
        dense MLP); w2's partial products psum inside the region."""
        from jax.sharding import PartitionSpec as P

        def body(xs_, gs_, w1_, w3_, w2_):
            h1 = jax.lax.ragged_dot(xs_, w1_, gs_)
            h3 = jax.lax.ragged_dot(xs_, w3_, gs_)
            inner = jax.nn.silu(h1) * h3
            part = jax.lax.ragged_dot(inner, w2_, gs_)
            return jax.lax.psum(part, "tp")

        f = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(), P(),
                P(None, None, "tp"), P(None, None, "tp"),
                P(None, "tp", None),
            ),
            out_specs=P(),
            check_vma=False,
        )
        return f(xs, gs, w1, w3, w2)
