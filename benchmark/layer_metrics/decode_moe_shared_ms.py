"""Device time of the shared expert in one decode micro-step: self
time of the operations under scope ``moe_shared`` (the always-on SwiGLU
every chip of the expert-parallel group computes alike, and its sum
with the routed part) inside the fused-decode programs, over executions
times K, all routed layers.  ``None`` where the trace has no such scope
(a program without a shared expert, or a cell whose configuration does
not list the scope).  Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _named


def read(ctx: dict):
    return _named.decode_ms(ctx, "scopes", "moe_shared")
