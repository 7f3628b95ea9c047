"""Share of the chip's roofline that the expert layers of a decode
micro-step reach: the least seconds ``kernel_model.experts`` gives at
the window's ``decode_batch_mean`` (HBM-bound at these sizes: the int8
matrices of every expert the batch reaches) over ``decode_experts_ms``.
Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark import kernel_model
from benchmark.layer_metrics import (
    _named, decode_batch_mean, decode_experts_ms)


def read(ctx: dict):
    ms = decode_experts_ms.read(ctx)
    batch = decode_batch_mean.read(ctx)
    if ms is None or batch is None:
        return None
    return _named.roofline_pct(
        "decode_experts_roofline_pct",
        kernel_model.experts(ctx["config"], batch, ctx["peak"]), ms)
