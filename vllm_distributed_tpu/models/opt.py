"""OPT decoder — parity config 1 (BASELINE.md: facebook/opt-125m, tp=1).

Learned positional embeddings (offset +2, the HF OPT quirk), pre-LN
blocks, ReLU MLP, biased projections, tied lm_head.  Supports
word_embed_proj_dim != hidden_size (opt-350m's project_in/out).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (
    SupportsQuantization,
    layer_norm,
    linear,
)
from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    paged_attention_reference,
    write_kv_pages,
)

_POS_OFFSET = 2  # HF OPT reserves the first two position rows.


class OPTForCausalLM(SupportsQuantization):
    architectures = ("OPTForCausalLM",)
    QUANT_PARAMS = frozenset({"wq", "wk", "wv", "wo", "fc1", "fc2"})

    def __init__(self, model_config: Any) -> None:
        hf = model_config.hf_config
        self._init_quant(model_config)
        self.num_layers = hf.num_hidden_layers
        self.hidden_size = hf.hidden_size
        self.num_heads = hf.num_attention_heads
        self.num_kv_heads = hf.num_attention_heads
        self.head_dim = self.hidden_size // self.num_heads
        self.ffn_dim = hf.ffn_dim
        self.vocab_size = hf.vocab_size
        self.max_positions = hf.max_position_embeddings
        self.word_embed_dim = getattr(
            hf, "word_embed_proj_dim", self.hidden_size
        )
        self.do_layer_norm_before = bool(
            getattr(hf, "do_layer_norm_before", True)
        )
        self.dtype = jnp.dtype(model_config.dtype)
        self.scale = self.head_dim**-0.5
        self.eps = 1e-5

    def init_params(self, rng: jax.Array) -> dict:
        h, d, f, v = self.hidden_size, self.head_dim, self.ffn_dim, self.vocab_size

        def nrm(key, shape):
            return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(
                self.dtype
            )

        keys = iter(jax.random.split(rng, 7 * self.num_layers + 4))
        layers = []
        for _ in range(self.num_layers):
            layers.append(
                {
                    "attn_ln_w": jnp.ones((h,), self.dtype),
                    "attn_ln_b": jnp.zeros((h,), self.dtype),
                    "wq": nrm(next(keys), (h, h)),
                    "bq": jnp.zeros((h,), self.dtype),
                    "wk": nrm(next(keys), (h, h)),
                    "bk": jnp.zeros((h,), self.dtype),
                    "wv": nrm(next(keys), (h, h)),
                    "bv": jnp.zeros((h,), self.dtype),
                    "wo": nrm(next(keys), (h, h)),
                    "bo": jnp.zeros((h,), self.dtype),
                    "final_ln_w": jnp.ones((h,), self.dtype),
                    "final_ln_b": jnp.zeros((h,), self.dtype),
                    "fc1": nrm(next(keys), (h, f)),
                    "fc1_b": jnp.zeros((f,), self.dtype),
                    "fc2": nrm(next(keys), (f, h)),
                    "fc2_b": jnp.zeros((h,), self.dtype),
                }
            )
        params = {
            "embed": nrm(next(keys), (v, self.word_embed_dim)),
            "embed_pos": nrm(
                next(keys), (self.max_positions + _POS_OFFSET, h)
            ),
            "layers": layers,
        }
        # HF OPT only has a decoder-level final LN when pre-LN (opt-350m
        # ships none and applies none).
        if self.do_layer_norm_before:
            params["final_ln_w"] = jnp.ones((h,), self.dtype)
            params["final_ln_b"] = jnp.zeros((h,), self.dtype)
        if self.word_embed_dim != h:
            params["project_in"] = nrm(next(keys), (self.word_embed_dim, h))
            params["project_out"] = nrm(next(keys), (h, self.word_embed_dim))
        return params

    def map_hf_name(self, name: str):
        # Some checkpoints use "model.decoder.", others "decoder.".
        if name.startswith("model."):
            name = name[len("model.") :]
        if name == "lm_head.weight":
            return None  # tied
        if not name.startswith("decoder."):
            return None
        name = name[len("decoder.") :]
        top = {
            "embed_tokens.weight": (("embed",), None),
            "embed_positions.weight": (("embed_pos",), None),
            "final_layer_norm.weight": (("final_ln_w",), None),
            "final_layer_norm.bias": (("final_ln_b",), None),
            "project_in.weight": (("project_in",), "T"),
            "project_out.weight": (("project_out",), "T"),
        }
        if name in top:
            return top[name]
        if not name.startswith("layers."):
            return None
        parts = name.split(".")
        i = int(parts[1])
        rest = ".".join(parts[2:])
        table = {
            "self_attn.q_proj.weight": ("wq", "T"),
            "self_attn.q_proj.bias": ("bq", None),
            "self_attn.k_proj.weight": ("wk", "T"),
            "self_attn.k_proj.bias": ("bk", None),
            "self_attn.v_proj.weight": ("wv", "T"),
            "self_attn.v_proj.bias": ("bv", None),
            "self_attn.out_proj.weight": ("wo", "T"),
            "self_attn.out_proj.bias": ("bo", None),
            "self_attn_layer_norm.weight": ("attn_ln_w", None),
            "self_attn_layer_norm.bias": ("attn_ln_b", None),
            "final_layer_norm.weight": ("final_ln_w", None),
            "final_layer_norm.bias": ("final_ln_b", None),
            "fc1.weight": ("fc1", "T"),
            "fc1.bias": ("fc1_b", None),
            "fc2.weight": ("fc2", "T"),
            "fc2.bias": ("fc2_b", None),
        }
        hit = table.get(rest)
        if hit is None:
            return None
        return ("layers", i, hit[0]), hit[1]

    def partition_specs(self) -> dict:
        layer = {
            "attn_ln_w": P(), "attn_ln_b": P(),
            "wq": P(None, "tp"), "bq": P("tp"),
            "wk": P(None, "tp"), "bk": P("tp"),
            "wv": P(None, "tp"), "bv": P("tp"),
            "wo": P("tp", None), "bo": P(),
            "final_ln_w": P(), "final_ln_b": P(),
            "fc1": P(None, "tp"), "fc1_b": P("tp"),
            "fc2": P("tp", None), "fc2_b": P(),
        }
        specs = {
            "embed": P(None, None),
            "embed_pos": P(),
            "layers": [dict(layer) for _ in range(self.num_layers)],
        }
        if self.do_layer_norm_before:
            specs["final_ln_w"] = P()
            specs["final_ln_b"] = P()
        if self.word_embed_dim != self.hidden_size:
            specs["project_in"] = P()
            specs["project_out"] = P()
        return specs

    def kv_cache_layout(self):
        from vllm_distributed_tpu.ops.attention import KVCacheLayout

        return KVCacheLayout.by_heads(
            self.num_layers, self.num_kv_heads, self.head_dim
        )

    def kv_cache_spec(self) -> P:
        """Combined KV pool [2, P, page, HD]: shard flat head lanes."""
        return P(None, None, None, "tp")

    def forward(
        self,
        params: dict,
        token_ids: jax.Array,
        kv_caches: list,
        meta: AttentionMetadata,
        attn_fn: Callable = paged_attention_reference,
        kv_write_fn: Callable = write_kv_pages,
        return_hidden: bool = False,
    ) -> tuple:
        t = token_ids.shape[0]
        x = params["embed"][token_ids].astype(self.dtype)
        if "project_in" in params:
            x = linear(x, params["project_in"])
        pos = params["embed_pos"][meta.q_positions + _POS_OFFSET].astype(
            self.dtype
        )
        x = x + pos
        new_kv = []
        for layer, kv_pages in zip(params["layers"], kv_caches):
            h = (
                layer_norm(x, layer["attn_ln_w"], layer["attn_ln_b"], self.eps)
                if self.do_layer_norm_before
                else x
            )
            q = linear(h, layer["wq"], layer["bq"]).reshape(
                t, self.num_heads, self.head_dim
            )
            k = linear(h, layer["wk"], layer["bk"]).reshape(
                t, self.num_kv_heads, self.head_dim
            )
            v = linear(h, layer["wv"], layer["bv"]).reshape(
                t, self.num_kv_heads, self.head_dim
            )
            kv_pages = kv_write_fn(kv_pages, k, v, meta.slot_mapping)
            new_kv.append(kv_pages)
            attn = attn_fn(
                q, kv_pages, meta,
                scale=self.scale, num_kv_heads=self.num_kv_heads,
            )
            x = x + linear(attn.reshape(t, -1), layer["wo"], layer["bo"])
            if not self.do_layer_norm_before:
                x = layer_norm(
                    x, layer["attn_ln_w"], layer["attn_ln_b"], self.eps
                )

            h = (
                layer_norm(
                    x, layer["final_ln_w"], layer["final_ln_b"], self.eps
                )
                if self.do_layer_norm_before
                else x
            )
            h = jax.nn.relu(linear(h, layer["fc1"], layer["fc1_b"]))
            h = linear(h, layer["fc2"], layer["fc2_b"])
            x = x + h
            if not self.do_layer_norm_before:
                x = layer_norm(
                    x, layer["final_ln_w"], layer["final_ln_b"], self.eps
                )

        if "final_ln_w" in params:
            x = layer_norm(
                x, params["final_ln_w"], params["final_ln_b"], self.eps
            )
        if "project_out" in params:
            x = linear(x, params["project_out"])
        sel = x[meta.logits_indices]
        logits = (sel @ params["embed"].T.astype(sel.dtype)).astype(
            jnp.float32
        )
        if return_hidden:
            return logits, new_kv, sel.astype(jnp.float32)
        return logits, new_kv
