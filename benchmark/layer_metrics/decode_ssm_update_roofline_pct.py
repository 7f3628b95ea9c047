"""Share of the chip's roofline that the one-token state update
reaches: the least seconds ``kernel_model_falcon_h1.state_update`` gives
at the window's ``decode_batch_mean`` (HBM-bound: that many sequences'
states read and written once at the file's state dtype) over
``decode_ssm_update_ms``.  Fails the run above 100%.  ``None`` where the
configuration has no such mixer or the trace no such scope.  Layer:
kernels.  Moves ``tpot_p50_ms``."""

from benchmark import kernel_model_falcon_h1
from benchmark.layer_metrics import (
    _falcon_h1, decode_batch_mean, decode_ssm_update_ms)


def read(ctx: dict):
    if not _falcon_h1.applies(ctx):
        return None
    ms = decode_ssm_update_ms.read(ctx)
    batch = decode_batch_mean.read(ctx)
    if ms is None or batch is None:
        return None
    return _falcon_h1.roofline_pct(
        "decode_ssm_update_roofline_pct",
        kernel_model_falcon_h1.state_update(ctx["config"], batch, ctx["peak"]),
        ms)
