"""Device time of the gated short convolutions in one decode
micro-step: self time of the operations under scope ``short_conv`` (the
operator whole: its norm, both projections, the gates, the three taps,
the sequence state's read and write) inside the fused-decode programs
(``jit_vdt_decode_*``), over executions times K, all convolution
layers.  ``None`` where the trace has no such scope (a program without
such a layer).  Layer: kernels.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _named


def read(ctx: dict):
    return _named.decode_ms(ctx, "scopes", "short_conv")
