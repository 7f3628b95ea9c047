"""Device time of the routed experts in one decode micro-step, as this
chip computes its share of a group-limited router's picks: self time of
the operations under scopes ``moe_router`` (the float32 sigmoid router
over every routed expert of the deployment, the groups, the pick
counts, the combine matrix) and ``moe_experts`` (the held experts'
products) inside the fused-decode programs, over executions times K,
all routed layers.  ``None`` where the configuration is no such model
or the trace has neither.  Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _dsv3, decode_moe_held_ms


def read(ctx: dict):
    if not _dsv3.applies(ctx):
        return None
    return decode_moe_held_ms.read(ctx)
