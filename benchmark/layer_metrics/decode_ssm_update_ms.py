"""Device time of the one-token state update in one decode micro-step:
self time under scope ``ssm_scan`` (or of kernel ``vdt_ssm_update``)
inside the fused-decode programs, over executions times K, all layers:
every live sequence's state read, updated and written, and ``y = S C +
D x``.  ``None`` where the configuration has no such mixer or the trace
no such scope.  Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _falcon_h1


def read(ctx: dict):
    if not _falcon_h1.applies(ctx):
        return None
    return _falcon_h1.decode_ms(ctx, _falcon_h1.scan_seconds(ctx, "decode"))
