"""The least the routed layers of an LFM2-MoE decode micro-step can
cost on a chip, from a configuration's sizes alone, beside
``kernel_model.py`` and in its terms.  Nothing here reads the
program."""

from __future__ import annotations

from benchmark.kernel_model import _bound
from benchmark.sizes import lfm2_moe as sizes


def experts(cfg: dict, batch: float, peak: dict) -> dict:
    """The routed layers of one decode micro-step of ``batch``
    sequences: every routed layer reads the bfloat16 matrices of the
    experts the batch reaches between them (``sizes.experts_touched``)
    and its router, and each sequence runs ``num_experts_per_tok``
    experts of three matrix products."""
    layers, h = sizes.routed_layers(cfg), cfg["hidden_size"]
    nbytes = 2.0 * layers * (
        sizes.experts_touched(cfg, batch) * sizes.expert_params(cfg)
        + h * cfg["num_experts"])
    ops = (layers * batch * cfg["num_experts_per_tok"] * 2
           * sizes.expert_params(cfg))
    return _bound(nbytes, ops, peak)


def grouped_experts(cfg: dict, tokens: float, peak: dict) -> dict:
    """The experts' products of one single step that routes ``tokens``
    live rows (kernel ``vdt_grouped_matmul``, all routed layers): each
    row runs ``num_experts_per_tok`` experts of three matrix products;
    every layer reads the bfloat16 matrices of the experts those rows
    reach (``sizes.experts_touched``), each row's input and inner
    activations once a product, and writes its outputs.  The padding of
    an expert's group to whole row tiles is the kernel's cost, not
    work: it is not counted."""
    layers, k = sizes.routed_layers(cfg), cfg["num_experts_per_tok"]
    h, im = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = tokens * k
    nbytes = 2.0 * layers * (
        sizes.experts_touched(cfg, tokens) * sizes.expert_params(cfg)
        + rows * (h + im + im + h))
    ops = layers * rows * 2 * sizes.expert_params(cfg)
    return _bound(nbytes, ops, peak)
