"""The least a DeepSeek-V3-architecture block's own parts can cost on a
chip, from a configuration's sizes alone, beside ``kernel_model.py`` and
in its terms: bytes, operations, the seconds each takes at the chip's
peaks, and which binds.  One latent attention sublayer a layer
(``kernel_model_mla.py`` counts the double-layer family's two) and this
chip's share of the routed experts.  Nothing here reads the program."""

from __future__ import annotations

from benchmark.kernel_model import _bound
from benchmark.sizes import deepseek_v3 as sizes


def mla_attention(cfg: dict, kv_tokens: float, seqs: float,
                  peak: dict) -> dict:
    """Absorbed latent attention of one decode micro-step over
    ``kv_tokens`` cached tokens in all (``seqs`` sequences, one query
    token each), in every layer's one sublayer: each cached row
    (``kv_lora_rank + qk_rope_head_dim`` bfloat16 values) is read once
    for all heads; the absorbed queries are read (a row's width a head)
    and the attended latents written (``kv_lora_rank`` a head).  Each
    cached token costs, for every head, a product with the query over
    the row's whole width and one with the probabilities over its
    latent part.  Nothing here depends on ``v_head_dim``: the kernel
    works in the latent space."""
    heads, sublayers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    latent = cfg["kv_lora_rank"]
    width = latent + cfg["qk_rope_head_dim"]
    nbytes = sublayers * 2 * (kv_tokens * width
                              + seqs * heads * (width + latent))
    ops = sublayers * kv_tokens * heads * (width + latent) * 2
    return _bound(nbytes, ops, peak)


def held_experts(cfg: dict, batch: float, peak: dict) -> dict:
    """The routed experts of one decode micro-step of ``batch``
    sequences as this chip must compute its share: every routed layer
    reads the bfloat16 matrices of the held experts the batch reaches
    (``sizes.experts_touched``) and its router; each sequence makes
    ``num_experts_per_tok`` picks of which ``held / routed`` land here,
    three matrix products each."""
    layers, h = sizes.routed_layers(cfg), cfg["hidden_size"]
    share = cfg["n_routed_experts"] / sizes.routed_width(cfg)
    nbytes = 2.0 * layers * (
        sizes.experts_touched(cfg, batch) * sizes.expert_params(cfg)
        + h * sizes.routed_width(cfg))
    ops = (layers * batch * cfg["num_experts_per_tok"] * share * 2
           * sizes.expert_params(cfg))
    return _bound(nbytes, ops, peak)


def grouped_held_experts(cfg: dict, rows_here: float, peak: dict) -> dict:
    """The held experts' products of one single step whose live rows
    made ``rows_here`` picks of experts held here, summed over the
    routed layers (kernel ``vdt_grouped_matmul``; the step's
    ``moe_picks_held``): each such row runs three matrix products of
    one expert; the step reads the bfloat16 matrices of the held
    experts those rows reach (``rows_here / layers`` picks a layer,
    each over the ``n_routed_experts`` held evenly), each row's input
    and inner activations once a product, and writes its outputs.  The
    padding of an expert's group to whole row tiles, and the layout's
    rows for picks held elsewhere, are the kernel's cost, not work:
    they are not counted."""
    layers, held = sizes.routed_layers(cfg), cfg["n_routed_experts"]
    h, im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    a_layer = rows_here / max(layers, 1)
    reached = held * (1.0 - (1.0 - 1.0 / held) ** a_layer)
    nbytes = 2.0 * (layers * reached * sizes.expert_params(cfg)
                    + rows_here * (h + im + im + h))
    ops = rows_here * 2 * sizes.expert_params(cfg)
    return _bound(nbytes, ops, peak)
