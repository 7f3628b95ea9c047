"""Device time of the routed layer in one decode micro-step, as this
chip computes its share of it: self time of the operations under scopes
``moe_router`` (the float32 router, the picks' places in the held
experts' buffers) and ``moe_experts`` (the held experts' products, the
identity experts' part) inside the fused-decode programs, over
executions times K, all layers.  ``None`` where the trace has neither.
Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _named


def read(ctx: dict):
    parts = [_named.decode_ms(ctx, "scopes", scope)
             for scope in ("moe_router", "moe_experts")]
    if all(p is None for p in parts):
        return None
    return sum(p for p in parts if p is not None)
