"""Device time of the latent up-projections in one decode micro-step:
self time of the operations under scope ``mla_absorb`` (the queries
into the latent space, ``q_nope · W_uk``, and the attended latents out
of it, ``· W_uv``) inside the fused-decode programs, over executions
times K, all attention sublayers.  ``None`` where the trace has no such
scope.  Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _named


def read(ctx: dict):
    return _named.decode_ms(ctx, "scopes", "mla_absorb")
