"""The least the state-space recurrence of a Falcon-H1 configuration
can cost on a chip, from the configuration's sizes alone, beside
``kernel_model.py`` and in its terms.  Nothing here reads the program.

Per layer the mixer has ``H`` = ``mamba_n_heads`` heads of ``P`` =
``mamba_d_head`` lanes, ``G`` = ``mamba_n_groups`` groups of ``N`` =
``mamba_d_state``; a sequence's state is ``H x P x N`` values a layer at
``ssm_state_dtype``."""

from __future__ import annotations

from benchmark.kernel_model import _bound
from benchmark.sizes import falcon_h1 as sizes


def _dims(cfg: dict) -> tuple:
    return (cfg["num_hidden_layers"], cfg["mamba_n_heads"],
            cfg["mamba_d_head"], cfg["mamba_n_groups"], cfg["mamba_d_state"])


def state_update(cfg: dict, seqs: float, peak: dict) -> dict:
    """The one-token update of ``seqs`` sequences, all layers: every
    sequence's state is read once and written once (the bytes; the
    token's own x, B, C, Δ and y are kilobytes a sequence and are
    counted too); five operations a state value (decay x state, Δx x B
    added, times C summed).  HBM-bound at any batch."""
    layers, h, p, g, n = _dims(cfg)
    per_token = layers * (2 * (h * p + 2 * g * n + h * p) + 4 * h)
    nbytes = seqs * (2.0 * sizes.ssm_state_bytes_per_seq(cfg) + per_token)
    ops = seqs * layers * h * p * n * 5.0
    return _bound(nbytes, ops, peak)


def chunk_scan(cfg: dict, tokens: float, state_rows: float,
               peak: dict) -> dict:
    """The chunked form over one single step of ``tokens`` live tokens
    in ``state_rows`` sequences, all layers, at ``mamba_chunk_size``:
    a token's products with the causal half of its tile (C Bᵀ over the
    groups, then the masked weights times Δx over the heads), its part
    of the tile's state (Bᵀ Δx) and its output from the incoming state
    (C S), two operations a multiply-add; the bytes of x, B, C (two
    bytes a value), Δ (four) in and y out, and every sequence's state
    in and out once.  The tiles' padding, the decays and the masks are
    the program's cost, not work: not counted."""
    layers, h, p, g, n = _dims(cfg)
    half = cfg["mamba_chunk_size"] / 2.0
    ops = layers * tokens * 2.0 * (
        half * g * n + half * h * p + 2 * h * p * n)
    nbytes = layers * tokens * (2 * (2 * h * p + 2 * g * n) + 4 * h) + (
        state_rows * 2.0 * sizes.ssm_state_bytes_per_seq(cfg))
    return _bound(nbytes, ops, peak)
