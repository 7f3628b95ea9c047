"""Device time of latent attention in one decode micro-step of a model
with one attention sublayer a layer: self time of the kernel
``vdt_mla_attention`` inside the fused-decode programs
(``jit_vdt_decode_*``), over executions times K, all layers.  ``None``
where the configuration is no such model or the trace has no such
kernel.  Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _dsv3, _named


def read(ctx: dict):
    if not _dsv3.applies(ctx):
        return None
    return _named.decode_ms(ctx, "kernels", "vdt_mla_attention")
