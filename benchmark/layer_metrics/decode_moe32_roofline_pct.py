"""Share of the chip's roofline that the routed layers of a decode
micro-step reach: the least seconds ``kernel_model_lfm2.experts`` gives
at the window's ``decode_batch_mean`` (HBM-bound at these sizes: the
bfloat16 matrices of every expert that many sequences' picks reach)
over ``decode_moe32_ms``.  Fails the run above 100% like its siblings.
``None`` where the configuration is not such a model (no
``moe_intermediate_size``) or the trace has no such scope.  Layer:
kernels.  Moves ``tpot_p50_ms``."""

from benchmark import kernel_model_lfm2
from benchmark.layer_metrics import (
    _named, decode_batch_mean, decode_moe32_ms)


def read(ctx: dict):
    if "moe_intermediate_size" not in ctx["config"]:
        return None
    ms = decode_moe32_ms.read(ctx)
    batch = decode_batch_mean.read(ctx)
    if ms is None or batch is None:
        return None
    return _named.roofline_pct(
        "decode_moe32_roofline_pct",
        kernel_model_lfm2.experts(ctx["config"], batch, ctx["peak"]), ms)
