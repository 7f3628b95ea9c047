"""The least a kernel's work can cost on a chip, from a configuration's
sizes alone: the bytes it must move and the operations it must do, the
seconds each takes at the chip's peaks (``peaks.json``), and which of
the two binds.  Nothing here reads the program; ``bytes_model`` is the
same arithmetic for a whole decode micro-step.
"""

from __future__ import annotations

from benchmark import bytes_model


def _bound(nbytes: float, ops: float, peak: dict) -> dict:
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    by_ops = ops / peak["bf16_flops"]
    return {"bytes": nbytes, "ops": ops, "seconds": max(by_bytes, by_ops),
            "binds": "hbm" if by_bytes >= by_ops else "flops"}


def experts(cfg: dict, batch: float, peak: dict) -> dict:
    """The expert layers of one decode micro-step of ``batch``
    sequences: every layer reads the int8 matrices (one byte a weight,
    four a per-channel scale) of the experts the batch reaches between
    them (``bytes_model.experts_touched``), and each sequence runs
    ``num_experts_per_tok`` experts of three matrix products.  The
    products run in bfloat16 after the dequantization, hence that
    peak."""
    h, im = cfg["hidden_size"], cfg["intermediate_size"]
    layers, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    one_expert = 2 * (h * im + 4 * im) + (im * h + 4 * h)
    nbytes = layers * bytes_model.experts_touched(cfg, batch) * one_expert
    ops = layers * batch * k * 3 * 2 * h * im
    return _bound(nbytes, ops, peak)


def paged_attention(cfg: dict, kv_tokens: float, seqs: float,
                    peak: dict) -> dict:
    """Attention of one decode micro-step over ``kv_tokens`` cached
    tokens in all (``seqs`` sequences, one query token each): every
    layer reads each cached key and value once, the queries, and writes
    the outputs; each cached token costs a product with the query and
    one with the probabilities, for every query head."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    nbytes = (kv_tokens * bytes_model.kv_bytes_per_token(cfg)
              + layers * seqs * heads * d * 2 * 2)
    ops = layers * kv_tokens * heads * d * 2 * 2
    return _bound(nbytes, ops, peak)
