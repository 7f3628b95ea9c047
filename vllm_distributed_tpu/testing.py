"""Synthetic model snapshots for tests, dryruns, and chip_smoke.py.

Zero-egress environments can't download checkpoints, so anything that
needs a model builds one: a config.json on disk (weights come from
--load-format dummy) shaped like the real family member it stands in for.
"""

from __future__ import annotations

import json
import os
import tempfile


def write_llama_config(
    dirname: str | None = None,
    *,
    vocab_size: int = 128,
    hidden: int = 64,
    intermediate: int = 128,
    layers: int = 2,
    heads: int = 8,
    kv_heads: int = 4,
    max_pos: int = 2048,
    dtype: str = "float32",
    tie_embeddings: bool = False,
) -> str:
    """Write a Llama-architecture config.json; returns the directory."""
    if dirname is None:
        dirname = tempfile.mkdtemp(prefix="vdt_tiny_llama_")
    cfg = {
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "hidden_size": hidden,
        "intermediate_size": intermediate,
        "num_hidden_layers": layers,
        "num_attention_heads": heads,
        "num_key_value_heads": kv_heads,
        "head_dim": hidden // heads,
        "vocab_size": vocab_size,
        "max_position_embeddings": max_pos,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "torch_dtype": dtype,
        "tie_word_embeddings": tie_embeddings,
        "hidden_act": "silu",
        "bos_token_id": 1,
        "eos_token_id": 2,
    }
    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, "config.json"), "w") as f:
        json.dump(cfg, f)
    return dirname


def write_mixtral_config(
    dirname: str | None = None,
    *,
    vocab_size: int = 128,
    hidden: int = 64,
    intermediate: int = 128,
    layers: int = 2,
    heads: int = 8,
    kv_heads: int = 4,
    num_experts: int = 4,
    top_k: int = 2,
    max_pos: int = 2048,
    dtype: str = "float32",
) -> str:
    """Write a Mixtral-architecture config.json; returns the directory."""
    if dirname is None:
        dirname = tempfile.mkdtemp(prefix="vdt_tiny_mixtral_")
    cfg = {
        "architectures": ["MixtralForCausalLM"],
        "model_type": "mixtral",
        "hidden_size": hidden,
        "intermediate_size": intermediate,
        "num_hidden_layers": layers,
        "num_attention_heads": heads,
        "num_key_value_heads": kv_heads,
        "head_dim": hidden // heads,
        "num_local_experts": num_experts,
        "num_experts_per_tok": top_k,
        "vocab_size": vocab_size,
        "max_position_embeddings": max_pos,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "torch_dtype": dtype,
        "tie_word_embeddings": False,
        "hidden_act": "silu",
        "sliding_window": None,
        "bos_token_id": 1,
        "eos_token_id": 2,
    }
    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, "config.json"), "w") as f:
        json.dump(cfg, f)
    return dirname


def longcat_config(
    *,
    vocab_size: int = 128,
    hidden: int = 64,
    ffn: int = 96,
    expert_ffn: int = 32,
    layers: int = 2,
    heads: int = 4,
    q_lora: int = 24,
    kv_lora: int = 16,
    nope: int = 8,
    rope: int = 8,
    v_dim: int = 8,
    held: int = 4,
    ep_size: int = 2,
    ep_rank: int = 0,
    zero: int = 4,
    top_k: int = 3,
    max_pos: int = 2048,
    dtype: str = "float32",
) -> dict:
    """A LongCat-Flash-architecture config (latent attention, double
    layers, a router over ``held * ep_size + zero`` outputs of which
    this chip holds ``held`` experts), keys as the published file
    spells them."""
    return {
        "architectures": ["LongcatFlashForCausalLM"],
        "model_type": "longcat_flash",
        "attention_bias": False,
        "attention_method": "MLA",
        "hidden_size": hidden,
        "ffn_hidden_size": ffn,
        "expert_ffn_hidden_size": expert_ffn,
        "num_layers": layers,
        "num_attention_heads": heads,
        "q_lora_rank": q_lora,
        "kv_lora_rank": kv_lora,
        "qk_nope_head_dim": nope,
        "qk_rope_head_dim": rope,
        "v_head_dim": v_dim,
        "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True,
        "n_routed_experts": held,
        "expert_parallel_size": ep_size,
        "expert_parallel_rank": ep_rank,
        "zero_expert_num": zero,
        "zero_expert_type": "identity",
        "moe_topk": top_k,
        "routed_scaling_factor": 6.0,
        "vocab_size": vocab_size,
        "max_position_embeddings": max_pos,
        "rms_norm_eps": 1e-5,
        "rope_theta": 10000000.0,
        "initializer_range": 0.02,
        "torch_dtype": dtype,
        "hidden_act": "silu",
        "bos_token_id": 1,
        "eos_token_id": 2,
    }


def write_longcat_config(dirname: str | None = None, **sizes) -> str:
    """Write ``longcat_config(**sizes)`` as a config.json; returns the
    directory."""
    if dirname is None:
        dirname = tempfile.mkdtemp(prefix="vdt_tiny_longcat_")
    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, "config.json"), "w") as f:
        json.dump(longcat_config(**sizes), f)
    return dirname


def deepseek_v3_config(
    *,
    vocab_size: int = 131,
    hidden: int = 64,
    ffn: int = 96,
    expert_ffn: int = 32,
    layers: int = 3,
    dense: int = 1,
    heads: int = 4,
    q_lora: int = 24,
    kv_lora: int = 16,
    nope: int = 8,
    rope: int = 8,
    v_dim: int = 12,
    held: int = 8,
    ep_size: int = 2,
    ep_rank: int = 0,
    shared: int = 1,
    n_group: int = 4,
    topk_group: int = 2,
    top_k: int = 3,
    yarn_factor: float = 4.0,
    yarn_original: int = 64,
    max_pos: int = 2048,
    dtype: str = "float32",
) -> dict:
    """A DeepSeek-V3-architecture config (one latent sublayer a layer
    with YaRN and values wider than keys, ``dense`` leading dense
    layers, a group-limited sigmoid router over ``held * ep_size``
    experts of which this chip holds ``held``, ``shared`` shared
    experts), keys as the published files spell them."""
    return {
        "architectures": ["DeepseekV3ForCausalLM"],
        "model_type": "deepseek_v3",
        "attention_bias": False,
        "hidden_size": hidden,
        "intermediate_size": ffn,
        "moe_intermediate_size": expert_ffn,
        "num_hidden_layers": layers,
        "num_nextn_predict_layers": 0,
        "first_k_dense_replace": dense,
        "moe_layer_freq": 1,
        "num_attention_heads": heads,
        "num_key_value_heads": heads,
        "q_lora_rank": q_lora,
        "kv_lora_rank": kv_lora,
        "qk_nope_head_dim": nope,
        "qk_rope_head_dim": rope,
        "v_head_dim": v_dim,
        "n_routed_experts": held,
        "expert_parallel_size": ep_size,
        "expert_parallel_rank": ep_rank,
        "n_shared_experts": shared,
        "n_group": n_group,
        "topk_group": topk_group,
        "num_experts_per_tok": top_k,
        "topk_method": "noaux_tc",
        "scoring_func": "sigmoid",
        "norm_topk_prob": True,
        "routed_scaling_factor": 2.5,
        "vocab_size": vocab_size,
        "max_position_embeddings": max_pos,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "rope_scaling": {
            "rope_type": "yarn",
            "factor": yarn_factor,
            "original_max_position_embeddings": yarn_original,
            "beta_fast": 32,
            "beta_slow": 1,
            "mscale": 1,
            "mscale_all_dim": 1,
        },
        "initializer_range": 0.02,
        "torch_dtype": dtype,
        "hidden_act": "silu",
        "tie_word_embeddings": False,
        "bos_token_id": 1,
        "eos_token_id": 2,
    }


def write_deepseek_v3_config(dirname: str | None = None, **sizes) -> str:
    """Write ``deepseek_v3_config(**sizes)`` as a config.json; returns
    the directory."""
    if dirname is None:
        dirname = tempfile.mkdtemp(prefix="vdt_tiny_deepseek_v3_")
    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, "config.json"), "w") as f:
        json.dump(deepseek_v3_config(**sizes), f)
    return dirname


def lfm2_config(
    *,
    vocab_size: int = 128,
    hidden: int = 64,
    intermediate: int = 96,
    moe_intermediate: int = 32,
    layer_types: tuple = ("conv", "conv", "full_attention", "conv"),
    dense_layers: int = 1,
    heads: int = 4,
    kv_heads: int = 2,
    experts: int = 8,
    top_k: int = 3,
    max_pos: int = 2048,
    dtype: str = "float32",
) -> dict:
    """An LFM2-MoE-architecture config (gated short convolutions with a
    few attention layers between them, leading dense layers, a sigmoid
    router with a choice-only bias), keys as the published file spells
    them (no ``head_dim``)."""
    return {
        "architectures": ["Lfm2MoeForCausalLM"],
        "model_type": "lfm2_moe",
        "hidden_size": hidden,
        "intermediate_size": intermediate,
        "moe_intermediate_size": moe_intermediate,
        "num_hidden_layers": len(layer_types),
        "layer_types": list(layer_types),
        "num_dense_layers": dense_layers,
        "num_attention_heads": heads,
        "num_key_value_heads": kv_heads,
        "num_experts": experts,
        "num_experts_per_tok": top_k,
        "norm_topk_prob": True,
        "use_expert_bias": True,
        "routed_scaling_factor": 1.0,
        "conv_L_cache": 3,
        "conv_bias": False,
        "vocab_size": vocab_size,
        "max_position_embeddings": max_pos,
        "norm_eps": 1e-5,
        "rope_theta": 1000000.0,
        "initializer_range": 0.02,
        "torch_dtype": dtype,
        "tie_word_embeddings": True,
        "hidden_act": "silu",
        "bos_token_id": 1,
        "eos_token_id": 2,
    }


def write_lfm2_config(dirname: str | None = None, **sizes) -> str:
    """Write ``lfm2_config(**sizes)`` as a config.json; returns the
    directory."""
    if dirname is None:
        dirname = tempfile.mkdtemp(prefix="vdt_tiny_lfm2_")
    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, "config.json"), "w") as f:
        json.dump(lfm2_config(**sizes), f)
    return dirname


def falcon_h1_config(
    *,
    vocab_size: int = 128,
    hidden: int = 64,
    intermediate: int = 96,
    layers: int = 2,
    heads: int = 5,
    kv_heads: int = 1,
    head_dim: int = 16,
    ssm_heads: int = 4,
    ssm_head_dim: int = 8,
    ssm_groups: int = 2,
    ssm_state: int = 16,
    chunk: int = 8,
    max_pos: int = 2048,
    dtype: str = "float32",
    **more,
) -> dict:
    """A Falcon-H1-architecture config (a Mamba-2 mixer beside
    grouped-query attention in every layer; a query group of 5, no
    power of two, as published), keys and multipliers as the published
    34B file spells them."""
    return {
        "architectures": ["FalconH1ForCausalLM"],
        "model_type": "falcon_h1",
        "attention_bias": False,
        "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375,
        "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381,
        "head_dim": head_dim,
        "hidden_act": "silu",
        "hidden_size": hidden,
        "intermediate_size": intermediate,
        "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125,
        "mamba_chunk_size": chunk,
        "mamba_conv_bias": True,
        "mamba_d_conv": 4,
        "mamba_d_head": ssm_head_dim,
        "mamba_d_ssm": ssm_heads * ssm_head_dim,
        "mamba_d_state": ssm_state,
        "mamba_expand": 2,
        "mamba_n_groups": ssm_groups,
        "mamba_n_heads": ssm_heads,
        "mamba_norm_before_gate": False,
        "mamba_proj_bias": False,
        "mamba_rms_norm": True,
        "mamba_use_mlp": True,
        "max_position_embeddings": max_pos,
        "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "num_attention_heads": heads,
        "num_hidden_layers": layers,
        "num_key_value_heads": kv_heads,
        "num_logits_to_keep": 1,
        "projectors_bias": False,
        "rms_norm_eps": 1e-5,
        "rope_scaling": None,
        "rope_theta": 100000000000,
        "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False,
        "vocab_size": vocab_size,
        "torch_dtype": dtype,
        "bos_token_id": 1,
        "eos_token_id": 2,
        **more,
    }


def write_falcon_h1_config(dirname: str | None = None, **sizes) -> str:
    """Write ``falcon_h1_config(**sizes)`` as a config.json; returns
    the directory."""
    if dirname is None:
        dirname = tempfile.mkdtemp(prefix="vdt_tiny_falcon_h1_")
    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, "config.json"), "w") as f:
        json.dump(falcon_h1_config(**sizes), f)
    return dirname


def full_attention_reference(q, k, v, scale, causal=True):
    """Dense (non-paged) attention oracle [T, H, D] with GQA repeat —
    the reference for ring attention (tests + multichip dryrun)."""
    import jax
    import jax.numpy as jnp

    t, hq, _ = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    logits = jnp.einsum("qhd,khd->hqk", q, k) * scale
    if causal:
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        logits = jnp.where(mask[None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


# Shapes of real family members, for chip_smoke.py's dummy-weight runs.
LLAMA_1B = dict(
    vocab_size=32000, hidden=2048, intermediate=8192, layers=16,
    heads=32, kv_heads=8, max_pos=4096, dtype="bfloat16",
)
LLAMA_7B = dict(
    vocab_size=32000, hidden=4096, intermediate=11008, layers=32,
    heads=32, kv_heads=32, max_pos=4096, dtype="bfloat16",
)
# Llama-2-13B (BASELINE milestone 3): 26 GB in bf16, so it only runs
# sharded — the four-chip shape of chip_smoke.py --tp 4.
LLAMA_13B = dict(
    vocab_size=32000, hidden=5120, intermediate=13824, layers=40,
    heads=40, kv_heads=40, max_pos=4096, dtype="bfloat16",
)
