"""Device time of the routed layers in one decode micro-step of a
model that holds every one of many small experts: self time of the
operations under scopes ``moe_router`` (the float32 sigmoid router, the
top-4, the combine matrix, the pick counts) and ``moe_experts`` (the
experts' products) inside the fused-decode programs, over executions
times K, all routed layers.  ``None`` where the trace has neither.
Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import decode_moe_held_ms

# the same two scopes as the latent configuration's reader, under this
# configuration's name
read = decode_moe_held_ms.read
