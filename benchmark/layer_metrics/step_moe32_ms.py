"""Device time of the experts' products in one single step (a prefill
or mixed step) of a model that holds every one of many small experts:
self time of the operations under scope ``moe_experts`` inside the
single-step programs (``jit_vdt_step_*``), over their executions, all
routed layers.  The sorted dispatch through ``ragged_dot`` and the
dense einsum are both filed under that scope, so the number compares
them.  ``None`` where the trace has no such program or no such scope.
Layer: kernels.  Moves ``out_tok_s``."""

from benchmark.layer_metrics.step_conv_ms import step_scope_ms


def read(ctx: dict):
    return step_scope_ms(ctx, "moe_experts")
