"""Device time of the Mamba-2 mixers in one decode micro-step: self time
of the operations under scope ``ssm_mixer`` (the mixer whole: the
layer's input norm, ``in_proj``, the convolution and its state, the
gate, the grouped norm, ``out_proj``) with the recurrence nested in it
(scope ``ssm_scan``) inside the fused-decode programs
(``jit_vdt_decode_*``), over executions times K, all layers.  ``None``
where the configuration has no such mixer or the trace no such scope.
Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _falcon_h1


def read(ctx: dict):
    if not _falcon_h1.applies(ctx):
        return None
    return _falcon_h1.decode_ms(ctx, _falcon_h1.mixer_seconds(ctx, "decode"))
