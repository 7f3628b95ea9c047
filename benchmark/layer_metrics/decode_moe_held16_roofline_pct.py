"""Share of the chip's roofline that the routed experts of a decode
micro-step reach on a chip that holds a share of them: the least
seconds ``kernel_model_dsv3.held_experts`` gives at the window's
``decode_batch_mean`` (HBM-bound: the bfloat16 matrices of the held
experts that many sequences' picks reach, whatever the program reads)
over ``decode_moe_held16_ms``.  Fails the run above 100% like its
siblings.  Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark import kernel_model_dsv3
from benchmark.layer_metrics import (
    _named, decode_batch_mean, decode_moe_held16_ms)


def read(ctx: dict):
    ms = decode_moe_held16_ms.read(ctx)
    batch = decode_batch_mean.read(ctx)
    if ms is None or batch is None:
        return None
    return _named.roofline_pct(
        "decode_moe_held16_roofline_pct",
        kernel_model_dsv3.held_experts(ctx["config"], batch, ctx["peak"]),
        ms)
