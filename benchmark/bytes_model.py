"""Bytes and operations a step must move, from a configuration's sizes
alone.  The yardstick's own arithmetic: nothing here reads the program.
"""

from __future__ import annotations


def experts_touched(cfg: dict, batch: float) -> float:
    """Distinct experts of one layer that ``batch`` decoding sequences
    reach between them when each picks ``num_experts_per_tok`` of
    ``num_local_experts`` evenly (seeded random weights route evenly):
    E * (1 - (1 - k/E) ** batch).  One sequence touches k; 31 sequences
    over 8 experts, top-2, touch 7.999 — every expert, every step."""
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** max(batch, 1.0))


def decode_weight_bytes(cfg: dict, batch: float = 1.0) -> float:
    """The weight bytes one decode micro-step of ``batch`` sequences
    must read.

    int8 matrices count one byte a weight and four a scale (one per
    output channel); the embedding is gathered, a row a sequence, and is
    left out.  For a mixture of experts each layer counts the experts
    its batch reaches (``experts_touched``): at ``batch`` 1 that is the
    least any routing can touch, at a full batch all of them — what a
    grouped kernel would still have to read, and what the dense
    all-experts implementation reads whatever the batch."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q_out = cfg["num_attention_heads"] * d
    kv_out = cfg["num_key_value_heads"] * d
    im = cfg["intermediate_size"]

    def mat(i, o):
        return i * o + 4 * o

    attn = mat(h, q_out) + 2 * mat(h, kv_out) + mat(q_out, h)
    mlp = 2 * mat(h, im) + mat(im, h)
    n_exp = cfg.get("num_local_experts")
    if n_exp:
        mlp = experts_touched(cfg, batch) * mlp + 2 * h * n_exp  # + router
    return cfg["num_hidden_layers"] * (attn + mlp) + mat(h, v)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """Bytes of KV cache one token holds: a key and a value of every
    KV head in every layer, bfloat16 unless stated."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * d
            * bytes_per_value)
