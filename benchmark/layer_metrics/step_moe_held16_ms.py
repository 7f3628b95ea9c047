"""Device time of the held experts' products in one single step (a
prefill or mixed step) of a chip that holds a share of the routed
experts: self time of the operations under scope ``moe_experts`` inside
the single-step programs (``jit_vdt_step_*``), over their executions,
all routed layers: ``vdt_grouped_matmul`` in many-row steps, the dense
einsum in the others.  ``None`` where the configuration is no such
model or the trace has no such program or scope.  Layer: kernels.
Moves ``out_tok_s``."""

from benchmark.layer_metrics import _dsv3
from benchmark.layer_metrics.step_conv_ms import step_scope_ms


def read(ctx: dict):
    if not _dsv3.applies(ctx):
        return None
    return step_scope_ms(ctx, "moe_experts")
