"""Device time of paged attention in one decode micro-step: self time of
the kernel ``vdt_paged_attention`` inside the fused-decode programs
(``jit_vdt_decode_*``), over executions times K, all layers.  Layer:
kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _named


def read(ctx: dict):
    return _named.decode_ms(ctx, "kernels", "vdt_paged_attention")
