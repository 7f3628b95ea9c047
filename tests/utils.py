"""Test helpers: synthesize tiny HF-format model snapshots on disk.

No network egress exists in CI, so every test builds its own miniature
checkpoint (config.json + model.safetensors with HF tensor names) and the
parity oracle is `transformers` running the same weights on torch CPU.
"""

from __future__ import annotations

import json
import os

import numpy as np


def _save_safetensors(path: str, tensors: dict[str, np.ndarray]) -> None:
    from safetensors.numpy import save_file

    save_file(tensors, path)


def make_tiny_llama(
    tmpdir: str,
    *,
    vocab_size: int = 128,
    hidden: int = 64,
    intermediate: int = 128,
    layers: int = 2,
    heads: int = 4,
    kv_heads: int = 2,
    max_pos: int = 512,
    tie_embeddings: bool = False,
    seed: int = 0,
    arch: str = "LlamaForCausalLM",
    model_type: str = "llama",
    attn_bias: bool = False,
    qk_norm: bool = False,
) -> str:
    """One builder for the whole llama family: Qwen2 = + q/k/v biases,
    Qwen3 dense = + per-head QK RMS-norm (the same flags the model code
    derives from model_type, models/llama.py)."""
    head_dim = hidden // heads
    cfg = {
        "architectures": [arch],
        "model_type": model_type,
        "hidden_size": hidden,
        "intermediate_size": intermediate,
        "num_hidden_layers": layers,
        "num_attention_heads": heads,
        "num_key_value_heads": kv_heads,
        "head_dim": head_dim,
        "vocab_size": vocab_size,
        "max_position_embeddings": max_pos,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "torch_dtype": "float32",
        "tie_word_embeddings": tie_embeddings,
        "hidden_act": "silu",
        "bos_token_id": 1,
        "eos_token_id": 2,
    }
    rng = np.random.default_rng(seed)

    def w(*shape, scale=0.05):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w(vocab_size, hidden),
        "model.norm.weight": np.ones(hidden, np.float32),
    }
    if not tie_embeddings:
        tensors["lm_head.weight"] = w(vocab_size, hidden)
    for i in range(layers):
        p = f"model.layers.{i}."
        tensors |= {
            p + "self_attn.q_proj.weight": w(heads * head_dim, hidden),
            p + "self_attn.k_proj.weight": w(kv_heads * head_dim, hidden),
            p + "self_attn.v_proj.weight": w(kv_heads * head_dim, hidden),
            p + "self_attn.o_proj.weight": w(hidden, heads * head_dim),
            p + "mlp.gate_proj.weight": w(intermediate, hidden),
            p + "mlp.up_proj.weight": w(intermediate, hidden),
            p + "mlp.down_proj.weight": w(hidden, intermediate),
            p + "input_layernorm.weight": np.ones(hidden, np.float32),
            p + "post_attention_layernorm.weight": np.ones(
                hidden, np.float32
            ),
        }
        if attn_bias:
            tensors |= {
                p + "self_attn.q_proj.bias": w(heads * head_dim, scale=0.02),
                p + "self_attn.k_proj.bias": w(
                    kv_heads * head_dim, scale=0.02
                ),
                p + "self_attn.v_proj.bias": w(
                    kv_heads * head_dim, scale=0.02
                ),
            }
        if qk_norm:
            tensors |= {
                p + "self_attn.q_norm.weight": 1.0 + w(head_dim, scale=0.1),
                p + "self_attn.k_norm.weight": 1.0 + w(head_dim, scale=0.1),
            }
    os.makedirs(tmpdir, exist_ok=True)
    with open(os.path.join(tmpdir, "config.json"), "w") as f:
        json.dump(cfg, f)
    _save_safetensors(os.path.join(tmpdir, "model.safetensors"), tensors)
    return tmpdir


def make_tiny_opt(
    tmpdir: str,
    *,
    vocab_size: int = 128,
    hidden: int = 64,
    ffn: int = 128,
    layers: int = 2,
    heads: int = 4,
    max_pos: int = 512,
    seed: int = 0,
) -> str:
    cfg = {
        "architectures": ["OPTForCausalLM"],
        "model_type": "opt",
        "hidden_size": hidden,
        "ffn_dim": ffn,
        "num_hidden_layers": layers,
        "num_attention_heads": heads,
        "vocab_size": vocab_size,
        "max_position_embeddings": max_pos,
        "word_embed_proj_dim": hidden,
        "do_layer_norm_before": True,
        "torch_dtype": "float32",
        "activation_function": "relu",
        "bos_token_id": 1,
        "eos_token_id": 2,
        "pad_token_id": 0,
    }
    rng = np.random.default_rng(seed)

    def w(*shape, scale=0.05):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    tensors = {
        "model.decoder.embed_tokens.weight": w(vocab_size, hidden),
        "model.decoder.embed_positions.weight": w(max_pos + 2, hidden),
        "model.decoder.final_layer_norm.weight": np.ones(hidden, np.float32),
        "model.decoder.final_layer_norm.bias": np.zeros(hidden, np.float32),
    }
    for i in range(layers):
        p = f"model.decoder.layers.{i}."
        tensors |= {
            p + "self_attn.q_proj.weight": w(hidden, hidden),
            p + "self_attn.q_proj.bias": np.zeros(hidden, np.float32),
            p + "self_attn.k_proj.weight": w(hidden, hidden),
            p + "self_attn.k_proj.bias": np.zeros(hidden, np.float32),
            p + "self_attn.v_proj.weight": w(hidden, hidden),
            p + "self_attn.v_proj.bias": np.zeros(hidden, np.float32),
            p + "self_attn.out_proj.weight": w(hidden, hidden),
            p + "self_attn.out_proj.bias": np.zeros(hidden, np.float32),
            p + "self_attn_layer_norm.weight": np.ones(hidden, np.float32),
            p + "self_attn_layer_norm.bias": np.zeros(hidden, np.float32),
            p + "final_layer_norm.weight": np.ones(hidden, np.float32),
            p + "final_layer_norm.bias": np.zeros(hidden, np.float32),
            p + "fc1.weight": w(ffn, hidden),
            p + "fc1.bias": np.zeros(ffn, np.float32),
            p + "fc2.weight": w(hidden, ffn),
            p + "fc2.bias": np.zeros(hidden, np.float32),
        }
    os.makedirs(tmpdir, exist_ok=True)
    with open(os.path.join(tmpdir, "config.json"), "w") as f:
        json.dump(cfg, f)
    _save_safetensors(os.path.join(tmpdir, "model.safetensors"), tensors)
    return tmpdir


def add_tiny_tokenizer(model_dir: str) -> str:
    """Attach a 30-word word-level tokenizer (ids < 30, safe for every
    tiny model here) loadable via AutoTokenizer, with a trivial chat
    template so apply_chat_template works."""
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    words = [
        "<unk>", "<s>", "</s>", "hello", "world", "the", "a", "cat",
        "dog", "sat", "on", "mat", "run", "jump", "stop", "go", "yes",
        "no", "maybe", "one", "two", "three", ".", ",", "!", "?", ":",
        "assistant", "user", "system",
    ]
    vocab = {w: i for i, w in enumerate(words)}
    tok = Tokenizer(WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = Whitespace()
    tok.save(os.path.join(model_dir, "tokenizer.json"))
    cfg = {
        "tokenizer_class": "PreTrainedTokenizerFast",
        "unk_token": "<unk>",
        "bos_token": "<s>",
        "eos_token": "</s>",
        "model_max_length": 512,
        "chat_template": (
            "{% for message in messages %}{{ message['role'] }} : "
            "{{ message['content'] }} {% endfor %}"
            "{% if add_generation_prompt %}assistant :{% endif %}"
        ),
    }
    with open(os.path.join(model_dir, "tokenizer_config.json"), "w") as f:
        json.dump(cfg, f)
    return model_dir


def make_tiny_mixtral(
    tmpdir: str,
    *,
    vocab_size: int = 128,
    hidden: int = 64,
    intermediate: int = 96,
    layers: int = 2,
    heads: int = 4,
    kv_heads: int = 2,
    num_experts: int = 4,
    top_k: int = 2,
    max_pos: int = 512,
    seed: int = 0,
) -> str:
    head_dim = hidden // heads
    cfg = {
        "architectures": ["MixtralForCausalLM"],
        "model_type": "mixtral",
        "hidden_size": hidden,
        "intermediate_size": intermediate,
        "num_hidden_layers": layers,
        "num_attention_heads": heads,
        "num_key_value_heads": kv_heads,
        "head_dim": head_dim,
        "num_local_experts": num_experts,
        "num_experts_per_tok": top_k,
        "vocab_size": vocab_size,
        "max_position_embeddings": max_pos,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "torch_dtype": "float32",
        "tie_word_embeddings": False,
        "hidden_act": "silu",
        "sliding_window": None,
        "bos_token_id": 1,
        "eos_token_id": 2,
    }
    rng = np.random.default_rng(seed)

    def w(*shape, scale=0.05):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w(vocab_size, hidden),
        "model.norm.weight": np.ones(hidden, np.float32),
        "lm_head.weight": w(vocab_size, hidden),
    }
    for i in range(layers):
        p = f"model.layers.{i}."
        tensors |= {
            p + "self_attn.q_proj.weight": w(heads * head_dim, hidden),
            p + "self_attn.k_proj.weight": w(kv_heads * head_dim, hidden),
            p + "self_attn.v_proj.weight": w(kv_heads * head_dim, hidden),
            p + "self_attn.o_proj.weight": w(hidden, heads * head_dim),
            p + "block_sparse_moe.gate.weight": w(num_experts, hidden, scale=0.3),
            p + "input_layernorm.weight": np.ones(hidden, np.float32),
            p + "post_attention_layernorm.weight": np.ones(hidden, np.float32),
        }
        for e in range(num_experts):
            ep = p + f"block_sparse_moe.experts.{e}."
            tensors |= {
                ep + "w1.weight": w(intermediate, hidden),
                ep + "w2.weight": w(hidden, intermediate),
                ep + "w3.weight": w(intermediate, hidden),
            }
    os.makedirs(tmpdir, exist_ok=True)
    with open(os.path.join(tmpdir, "config.json"), "w") as f:
        json.dump(cfg, f)
    _save_safetensors(os.path.join(tmpdir, "model.safetensors"), tensors)
    return tmpdir


def make_tiny_qwen3_moe(
    tmpdir: str,
    *,
    vocab_size: int = 128,
    hidden: int = 64,
    intermediate: int = 128,
    moe_intermediate: int = 48,
    layers: int = 2,
    heads: int = 4,
    kv_heads: int = 2,
    num_experts: int = 4,
    top_k: int = 2,
    max_pos: int = 512,
    seed: int = 1,
) -> str:
    head_dim = hidden // heads
    cfg = {
        "architectures": ["Qwen3MoeForCausalLM"],
        "model_type": "qwen3_moe",
        "hidden_size": hidden,
        "intermediate_size": intermediate,
        "moe_intermediate_size": moe_intermediate,
        "num_hidden_layers": layers,
        "num_attention_heads": heads,
        "num_key_value_heads": kv_heads,
        "head_dim": head_dim,
        "num_experts": num_experts,
        "num_experts_per_tok": top_k,
        "norm_topk_prob": True,
        "decoder_sparse_step": 1,
        "mlp_only_layers": [],
        "vocab_size": vocab_size,
        "max_position_embeddings": max_pos,
        "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0,
        "attention_bias": False,
        "torch_dtype": "float32",
        "tie_word_embeddings": False,
        "hidden_act": "silu",
        "bos_token_id": 1,
        "eos_token_id": 2,
    }
    rng = np.random.default_rng(seed)

    def w(*shape, scale=0.05):
        return (rng.standard_normal(shape) * scale).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": w(vocab_size, hidden),
        "model.norm.weight": np.ones(hidden, np.float32),
        "lm_head.weight": w(vocab_size, hidden),
    }
    for i in range(layers):
        p = f"model.layers.{i}."
        tensors |= {
            p + "self_attn.q_proj.weight": w(heads * head_dim, hidden),
            p + "self_attn.k_proj.weight": w(kv_heads * head_dim, hidden),
            p + "self_attn.v_proj.weight": w(kv_heads * head_dim, hidden),
            p + "self_attn.o_proj.weight": w(hidden, heads * head_dim),
            p + "self_attn.q_norm.weight": np.ones(head_dim, np.float32),
            p + "self_attn.k_norm.weight": np.ones(head_dim, np.float32),
            p + "mlp.gate.weight": w(num_experts, hidden, scale=0.3),
            p + "input_layernorm.weight": np.ones(hidden, np.float32),
            p + "post_attention_layernorm.weight": np.ones(hidden, np.float32),
        }
        for e in range(num_experts):
            ep = p + f"mlp.experts.{e}."
            tensors |= {
                ep + "gate_proj.weight": w(moe_intermediate, hidden),
                ep + "up_proj.weight": w(moe_intermediate, hidden),
                ep + "down_proj.weight": w(hidden, moe_intermediate),
            }
    os.makedirs(tmpdir, exist_ok=True)
    with open(os.path.join(tmpdir, "config.json"), "w") as f:
        json.dump(cfg, f)
    _save_safetensors(os.path.join(tmpdir, "model.safetensors"), tensors)
    return tmpdir


def hf_greedy_generate(model_dir: str, prompt_ids: list[int], max_new: int):
    """Oracle: greedy decode with transformers on torch CPU."""
    import torch
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        model_dir, torch_dtype=torch.float32
    )
    model.eval()
    ids = torch.tensor([prompt_ids])
    with torch.no_grad():
        out = model.generate(
            ids,
            max_new_tokens=max_new,
            do_sample=False,
            num_beams=1,
            pad_token_id=0,
        )
    return out[0, len(prompt_ids) :].tolist()


def hf_logits(model_dir: str, prompt_ids: list[int]):
    import torch
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        model_dir, torch_dtype=torch.float32
    )
    model.eval()
    with torch.no_grad():
        out = model(torch.tensor([prompt_ids]))
    return out.logits[0].numpy()


def make_tiny_qwen2(tmpdir: str, **kw) -> str:
    """Qwen2: the llama block plus q/k/v biases (attention_bias path)."""
    kw.setdefault("seed", 11)
    return make_tiny_llama(
        tmpdir, arch="Qwen2ForCausalLM", model_type="qwen2",
        attn_bias=True, **kw,
    )


def make_tiny_qwen3(tmpdir: str, **kw) -> str:
    """Qwen3 dense: per-head QK RMS-norm, no attention biases."""
    kw.setdefault("seed", 12)
    return make_tiny_llama(
        tmpdir, arch="Qwen3ForCausalLM", model_type="qwen3",
        qk_norm=True, **kw,
    )




class _HeldFuture:
    """A dispatch's future that says it is done only once its result
    is asked for: a device that is never early.  Whether a step finds
    the dispatch before it still in flight then no longer depends on
    how fast this machine ran it."""

    def __init__(self, fut) -> None:
        self._fut = fut

    def done(self) -> bool:
        return False

    def result(self, timeout=None):
        return self._fut.result(timeout)

    def add_done_callback(self, fn) -> None:
        self._fut.add_done_callback(fn)


def hold_results(engine):
    """Make every non-blocking dispatch of ``engine`` stay in flight
    until the engine blocks on it (``LLMEngine._finalize_one``), so
    tests of what overlaps are deterministic.  Returns the engine."""
    real = engine.executor.execute_model

    def execute_model(scheduler_output, non_block: bool = False):
        out = real(scheduler_output, non_block=non_block)
        return _HeldFuture(out) if non_block else out

    engine.executor.execute_model = execute_model
    return engine
