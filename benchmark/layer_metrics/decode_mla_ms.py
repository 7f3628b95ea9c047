"""Device time of latent attention in one decode micro-step: self time
of the kernel ``vdt_mla_attention`` inside the fused-decode programs
(``jit_vdt_decode_*``), over executions times K, all attention
sublayers (two a layer).  ``None`` where the trace has no such kernel
(a program without latent attention).  Layer: kernels.  Moves
``tpot_p50_ms``."""

from benchmark.layer_metrics import _named


def read(ctx: dict):
    return _named.decode_ms(ctx, "kernels", "vdt_mla_attention")
