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

from vllm_distributed_tpu.models.common import SCOPE_MOE_ROUTER
from vllm_distributed_tpu.models.llama import LlamaForCausalLM
from vllm_distributed_tpu.models.routed_experts import (  # noqa: F401
    GROUPED_MIN_ROWS,
    RoutedLayerCounts,
    routed_experts,
    select_moe_impl,
    sorted_dispatch,
)


class MixtralForCausalLM(RoutedLayerCounts, LlamaForCausalLM):
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
        ``step_counts`` can say which one a dispatched program holds."""
        return select_moe_impl(
            self._moe_impl(),
            tokens * self.top_k,
            layer["w1"],
            on_mesh=getattr(self, "_mesh", None) is not None,
        )

    def _mlp(
        self, h: jax.Array, layer: dict, live: jax.Array | None = None
    ) -> jax.Array:
        return self._experts(self.moe_impl(layer, h.shape[0]), h, layer, live)

    def _experts(self, impl: str, h, layer: dict, live=None) -> jax.Array:
        """This model's router, then the shared routed layer
        (models/routed_experts.py) in the form ``impl``."""
        with jax.named_scope(SCOPE_MOE_ROUTER):
            topw, topi = self._route(h, layer)
        return routed_experts(
            impl, h, topi, topw, layer["w1"], layer["w3"], layer["w2"],
            live=live, mesh=getattr(self, "_mesh", None),
            expert_parallel=getattr(self, "expert_parallel", False),
        )

    # The three forms by name (tests, tools/moe_microbench.py).
    def _mlp_dense(self, h: jax.Array, layer: dict) -> jax.Array:
        return self._experts("dense", h, layer)

    def _mlp_ragged(self, h: jax.Array, layer: dict, live=None) -> jax.Array:
        return self._experts("ragged", h, layer, live)

    def _mlp_grouped(self, h: jax.Array, layer: dict, live=None) -> jax.Array:
        return self._experts("grouped", h, layer, live)

    def _sorted_dispatch(self, h: jax.Array, layer: dict, live=None):
        """(topw, flat expert ids, order, group sizes) of a step."""
        topw, topi = self._route(h, layer)
        return (topw, *sorted_dispatch(topi, self.num_experts, live))
