"""Llama-family decoder (Llama 1/2/3, Mistral, Qwen2, Qwen3-dense).

The flagship model family of the parity configs (BASELINE.md: Llama-2-7B /
13B / 70B).  One implementation covers the variants via config switches:
GQA (num_key_value_heads), attention biases (Qwen2), per-head QK RMS-norm
(Qwen3), rope scaling (Llama-3), tied embeddings.

Functional style: ``init_params`` builds the pytree, ``forward`` is pure
and jit-safe.  Tensor parallelism is expressed purely as NamedSharding
partition specs over the mesh's "tp" axis (``partition_specs``); XLA/GSPMD
inserts the all-reduces after the row-parallel projections — the
TPU-native replacement for the reference's NCCL all-reduce inside vLLM
workers (SURVEY.md §2.2, §2.4).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.models.common import (
    SCOPE_ATTN,
    SCOPE_ATTN_OUT,
    SCOPE_ATTN_QKV,
    SCOPE_EMBED,
    SCOPE_KV_WRITE,
    SCOPE_LM_HEAD,
    SCOPE_MLP,
    SupportsQuantization,
    apply_rope,
    linear,
    rms_norm,
    rope_frequencies,
)
from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    paged_attention_reference,
    write_kv_pages,
)


class LlamaForCausalLM(SupportsQuantization):
    architectures = (
        "LlamaForCausalLM",
        "MistralForCausalLM",
        "Qwen2ForCausalLM",
        "Qwen3ForCausalLM",
    )
    # Weight-only quantization targets: the large matmuls.  Embeddings
    # (gathered, and possibly tied to lm_head), norms, and biases stay in
    # the model dtype.
    QUANT_PARAMS = frozenset(
        {"wq", "wk", "wv", "wo", "gate", "up", "down", "lm_head"}
    )

    def __init__(self, model_config: Any) -> None:
        hf = model_config.hf_config
        self.model_type = hf.model_type
        self.num_layers = model_config.get_num_layers()
        self.hidden_size = model_config.get_hidden_size()
        self.num_heads = model_config.get_num_attention_heads()
        self.num_kv_heads = model_config.get_num_kv_heads()
        self.head_dim = model_config.get_head_dim()
        self.intermediate_size = hf.intermediate_size
        self.vocab_size = hf.vocab_size
        self.rope_theta = float(getattr(hf, "rope_theta", 10000.0))
        self.rope_scaling = getattr(hf, "rope_scaling", None)
        self.rms_eps = float(getattr(hf, "rms_norm_eps", 1e-6))
        # Qwen2 carries q/k/v biases; Llama/Mistral/Qwen3 do not.
        self.attn_bias = bool(
            getattr(hf, "attention_bias", self.model_type == "qwen2")
        )
        self.qk_norm = self.model_type == "qwen3"
        self.tie_embeddings = bool(getattr(hf, "tie_word_embeddings", False))
        self.dtype = jnp.dtype(model_config.dtype)
        self.scale = self.head_dim**-0.5
        self._init_quant(model_config)

    # ---- params ----
    def init_params(self, rng: jax.Array) -> dict:
        """Random init (tests / --load-format dummy)."""
        h, nh, nkv, d, im, v = (
            self.hidden_size,
            self.num_heads,
            self.num_kv_heads,
            self.head_dim,
            self.intermediate_size,
            self.vocab_size,
        )

        def nrm(key, shape):
            return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(
                self.dtype
            )

        keys = iter(jax.random.split(rng, 7 * self.num_layers + 3))
        layers = []
        for _ in range(self.num_layers):
            layer = {
                "input_ln": jnp.ones((h,), self.dtype),
                "post_attn_ln": jnp.ones((h,), self.dtype),
                "wq": nrm(next(keys), (h, nh * d)),
                "wk": nrm(next(keys), (h, nkv * d)),
                "wv": nrm(next(keys), (h, nkv * d)),
                "wo": nrm(next(keys), (nh * d, h)),
                "gate": nrm(next(keys), (h, im)),
                "up": nrm(next(keys), (h, im)),
                "down": nrm(next(keys), (im, h)),
            }
            if self.attn_bias:
                layer["bq"] = jnp.zeros((nh * d,), self.dtype)
                layer["bk"] = jnp.zeros((nkv * d,), self.dtype)
                layer["bv"] = jnp.zeros((nkv * d,), self.dtype)
            if self.qk_norm:
                layer["q_norm"] = jnp.ones((d,), self.dtype)
                layer["k_norm"] = jnp.ones((d,), self.dtype)
            layers.append(layer)
        params = {
            "embed": nrm(next(keys), (v, h)),
            "layers": layers,
            "norm": jnp.ones((h,), self.dtype),
        }
        if not self.tie_embeddings:
            params["lm_head"] = nrm(next(keys), (h, v))
        return params

    def map_hf_name(self, name: str):
        """HF safetensors name -> (param path, 'T' to transpose) or None.

        HF reference layout: model.layers.{i}.self_attn.{q,k,v,o}_proj etc.
        """
        if name == "model.embed_tokens.weight":
            return ("embed",), None
        if name == "model.norm.weight":
            return ("norm",), None
        if name == "lm_head.weight":
            if self.tie_embeddings:
                return None
            return ("lm_head",), "T"
        if not name.startswith("model.layers."):
            return None
        parts = name.split(".")
        i = int(parts[2])
        rest = ".".join(parts[3:])
        table = {
            "self_attn.q_proj.weight": ("wq", "T"),
            "self_attn.k_proj.weight": ("wk", "T"),
            "self_attn.v_proj.weight": ("wv", "T"),
            "self_attn.o_proj.weight": ("wo", "T"),
            "self_attn.q_proj.bias": ("bq", None),
            "self_attn.k_proj.bias": ("bk", None),
            "self_attn.v_proj.bias": ("bv", None),
            "self_attn.q_norm.weight": ("q_norm", None),
            "self_attn.k_norm.weight": ("k_norm", None),
            "mlp.gate_proj.weight": ("gate", "T"),
            "mlp.up_proj.weight": ("up", "T"),
            "mlp.down_proj.weight": ("down", "T"),
            "input_layernorm.weight": ("input_ln", None),
            "post_attention_layernorm.weight": ("post_attn_ln", None),
        }
        hit = table.get(rest)
        if hit is None:
            return None
        return ("layers", i, hit[0]), hit[1]

    def partition_specs(self) -> dict:
        """PartitionSpecs mirroring the param tree, for the mesh "tp" axis.

        Column-parallel (out-dim sharded): wq/wk/wv/gate/up + lm_head;
        row-parallel (in-dim sharded): wo/down — GSPMD inserts the psum.
        """
        layer = {
            "input_ln": P(),
            "post_attn_ln": P(),
            "wq": P(None, "tp"),
            "wk": P(None, "tp"),
            "wv": P(None, "tp"),
            "wo": P("tp", None),
            "gate": P(None, "tp"),
            "up": P(None, "tp"),
            "down": P("tp", None),
        }
        if self.attn_bias:
            layer.update({"bq": P("tp"), "bk": P("tp"), "bv": P("tp")})
        if self.qk_norm:
            layer.update({"q_norm": P(), "k_norm": P()})
        specs = {
            "embed": P(None, "tp"),
            "layers": [dict(layer) for _ in range(self.num_layers)],
            "norm": P(),
        }
        if not self.tie_embeddings:
            specs["lm_head"] = P(None, "tp")
        return specs

    def kv_cache_layout(self):
        from vllm_distributed_tpu.ops.attention import KVCacheLayout

        return KVCacheLayout.by_heads(
            self.num_layers, self.num_kv_heads, self.head_dim
        )

    def kv_cache_spec(self) -> P:
        """Combined KV pool [2, P, page, HD]: shard the flat head×dim
        lanes over tp (heads are contiguous in HD, so this is per-kv-head
        sharding)."""
        return P(None, None, None, "tp")

    # ---- quantized projection fusion (single-chip fast path) ----
    _QKV_FUSE = ("wq", "wk", "wv")
    _GU_FUSE = ("gate", "up")

    def fuse_quantized_projections(self, params: dict) -> dict:
        """Concatenate the quantized Q|K|V and gate|up weights
        out-dim-wise so each layer issues one Pallas weight-streaming
        call instead of three/two (per-out-block computation is
        independent, so results are bit-identical to the unfused
        calls).  int8 concatenates q [in, out] and per-channel scales;
        int4 concatenates the packed q [in/2, out] and group scales
        [in/group, out] — both along the out dim, which preserves the
        packing and group layout exactly.  Only applies where every
        member is an eligible kernel-mode tensor of the same
        bits/group; called by the runner on the single-chip path after
        load."""
        from vllm_distributed_tpu.ops.quant import QuantizedTensor

        def fusable(layer, names):
            ws = [layer.get(n) for n in names]
            if not all(
                isinstance(w, QuantizedTensor)
                and w.bits in (8, 4)
                and w.q.ndim == 2
                and w.matmul in ("pallas", "pallas_interpret")
                # A tp-sharded concat along the out dim would interleave
                # shards of q|k|v instead of sharding the fused tensor:
                # fusion is a single-chip optimization only.
                and w.mesh is None
                for w in ws
            ):
                return None
            if len({(w.bits, w.group) for w in ws}) != 1:
                return None  # mixed schemes stay unfused
            if any(layer.get(f"b{n[-1]}") is not None for n in names
                   if n.startswith("w")):
                return None  # biased projections (qwen2) stay unfused
            return ws

        for layer in params.get("layers", []):
            for names, fused_name in (
                (self._QKV_FUSE, "wqkv"),
                (self._GU_FUSE, "wgu"),
            ):
                ws = fusable(layer, names)
                if ws is None:
                    continue
                layer[fused_name] = QuantizedTensor(
                    q=jnp.concatenate([w.q for w in ws], axis=-1),
                    scale=jnp.concatenate([w.scale for w in ws], axis=-1),
                    bits=ws[0].bits,
                    group=ws[0].group,
                    shape=(ws[0].shape[0], sum(w.shape[1] for w in ws)),
                    dtype=ws[0].dtype,
                    matmul=ws[0].matmul,
                )
                for n in names:
                    del layer[n]
        return params

    # ---- forward ----
    def _qkv(self, h: jax.Array, layer: dict, t: int):
        nh, nkv, d = self.num_heads, self.num_kv_heads, self.head_dim
        wqkv = layer.get("wqkv")
        if wqkv is not None:
            qkv = linear(h, wqkv)
            q = qkv[:, : nh * d]
            k = qkv[:, nh * d : (nh + nkv) * d]
            v = qkv[:, (nh + nkv) * d :]
        else:
            q = linear(h, layer["wq"], layer.get("bq"))
            k = linear(h, layer["wk"], layer.get("bk"))
            v = linear(h, layer["wv"], layer.get("bv"))
        return (
            q.reshape(t, nh, d),
            k.reshape(t, nkv, d),
            v.reshape(t, nkv, d),
        )

    def _mlp(
        self, h: jax.Array, layer: dict, live: jax.Array | None = None
    ) -> jax.Array:
        """Post-attention MLP for one layer (overridden by MoE models,
        which may skip the rows outside ``live``: a step's padding)."""
        wgu = layer.get("wgu")
        if wgu is not None:
            gu = linear(h, wgu)
            gate = gu[:, : self.intermediate_size]
            up = gu[:, self.intermediate_size :]
        else:
            gate, up = linear(h, layer["gate"]), linear(h, layer["up"])
        return linear(jax.nn.silu(gate) * up, layer["down"])

    def forward(
        self,
        params: dict,
        token_ids: jax.Array,  # [T]
        kv_caches: list,  # per layer combined kv_pages [2, P, page, HD]
        meta: AttentionMetadata,
        attn_fn: Callable = paged_attention_reference,
        kv_write_fn: Callable = write_kv_pages,
        return_hidden: bool = False,
    ) -> tuple:
        """Returns (logits [S, V] at meta.logits_indices, updated kv);
        with return_hidden also the final-norm hidden states [S, H]
        (embeddings / scoring, /v1/embeddings parity)."""
        with jax.named_scope(SCOPE_EMBED):
            x = params["embed"][token_ids].astype(self.dtype)
        inv_freq = rope_frequencies(
            self.head_dim, self.rope_theta, rope_scaling=self.rope_scaling
        )
        new_kv = []
        t = token_ids.shape[0]
        # Padding rows point one past the last sequence row (the
        # runner's convention); nothing reads what they compute.
        live = meta.q_seq_ids < meta.seq_lens.shape[0]
        for layer, kv_pages in zip(params["layers"], kv_caches):
            with jax.named_scope(SCOPE_ATTN_QKV):
                h = rms_norm(x, layer["input_ln"], self.rms_eps)
                q, k, v = self._qkv(h, layer, t)
                if self.qk_norm:
                    q = rms_norm(q, layer["q_norm"], self.rms_eps)
                    k = rms_norm(k, layer["k_norm"], self.rms_eps)
                q = apply_rope(q, meta.q_positions, inv_freq)
                k = apply_rope(k, meta.q_positions, inv_freq)
            with jax.named_scope(SCOPE_KV_WRITE):
                kv_pages = kv_write_fn(kv_pages, k, v, meta.slot_mapping)
            new_kv.append(kv_pages)
            with jax.named_scope(SCOPE_ATTN):
                attn = attn_fn(
                    q, kv_pages, meta,
                    scale=self.scale, num_kv_heads=self.num_kv_heads,
                )
            with jax.named_scope(SCOPE_ATTN_OUT):
                x = x + linear(attn.reshape(t, -1), layer["wo"])

            with jax.named_scope(SCOPE_MLP):
                h = rms_norm(x, layer["post_attn_ln"], self.rms_eps)
                x = x + self._mlp(h, layer, live)

        with jax.named_scope(SCOPE_LM_HEAD):
            x = rms_norm(x, params["norm"], self.rms_eps)
            sel = x[meta.logits_indices]  # [S, H]
            lm_head = params.get("lm_head")
            if lm_head is None:
                logits = sel @ params["embed"].T.astype(sel.dtype)
            else:
                from vllm_distributed_tpu.ops.quant import quant_matmul

                logits = quant_matmul(sel, lm_head)
        logits = logits.astype(jnp.float32)
        if return_hidden:
            return logits, new_kv, sel.astype(jnp.float32)
        return logits, new_kv
