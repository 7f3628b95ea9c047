"""Share of the chip's roofline that latent attention reaches in a
decode micro-step of a model with one sublayer a layer: the least
seconds ``kernel_model_dsv3.mla_attention`` gives for the cached tokens
a fused scan reads (``kv_tokens`` and ``seqs`` of the
``runner.dispatch`` spans of decode programs, mean over the profile's
stretch, else over the window) over ``decode_mla1_ms``.  The count is
in the latent space and does not depend on ``v_head_dim``.  Fails the
run above 100% like its siblings.  Layer: kernels.  Moves
``tpot_p50_ms``."""

from benchmark import kernel_model_dsv3
from benchmark.layer_metrics import _named, decode_mla1_ms
from benchmark.layer_metrics.decode_attention_roofline_pct import (
    _decode_dispatches,
)


def read(ctx: dict):
    ms = decode_mla1_ms.read(ctx)
    spans = _decode_dispatches(ctx)
    if ms is None or not spans:
        return None
    kv_tokens = sum(s["kv_tokens"] for s in spans) / len(spans)
    seqs = sum(s["seqs"] for s in spans) / len(spans)
    return _named.roofline_pct(
        "decode_mla1_roofline_pct",
        kernel_model_dsv3.mla_attention(
            ctx["config"], kv_tokens, seqs, ctx["peak"]), ms)
