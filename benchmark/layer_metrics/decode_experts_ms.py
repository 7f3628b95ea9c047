"""Device time of the expert layers in one decode micro-step: self time
of the operations under scope ``moe_experts`` inside the fused-decode
programs (``jit_vdt_decode_*``), over executions times K, all layers.
Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _named


def read(ctx: dict):
    return _named.decode_ms(ctx, "scopes", "moe_experts")
