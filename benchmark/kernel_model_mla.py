"""The least the latent-attention kernel's work can cost on a chip
(``vdt_mla_attention``), from a configuration's sizes alone, beside
``kernel_model.py`` and in its terms: bytes, operations, the seconds
each takes at the chip's peaks, and which binds.  Nothing here reads
the program."""

from __future__ import annotations

from benchmark.kernel_model import _bound


def mla_attention(cfg: dict, kv_tokens: float, seqs: float,
                  peak: dict) -> dict:
    """Absorbed latent attention of one decode micro-step over
    ``kv_tokens`` cached tokens in all (``seqs`` sequences, one query
    token each), in every attention sublayer (two a layer): each cached
    row (``kv_lora_rank + qk_rope_head_dim`` bfloat16 values) is read
    once for all heads; the absorbed queries are read (a row's width a
    head) and the attended latents written (``kv_lora_rank`` a head).
    Each cached token costs, for every head, a product with the query
    over the row's whole width and one with the probabilities over its
    latent part."""
    heads, sublayers = cfg["num_attention_heads"], 2 * cfg["num_layers"]
    latent = cfg["kv_lora_rank"]
    width = latent + cfg["qk_rope_head_dim"]
    nbytes = sublayers * 2 * (kv_tokens * width
                              + seqs * heads * (width + latent))
    ops = sublayers * kv_tokens * heads * (width + latent) * 2
    return _bound(nbytes, ops, peak)
