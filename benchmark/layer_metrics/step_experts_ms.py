"""Device time of the expert layers in one single step (a prefill or
mixed step): self time of the operations under scope ``moe_experts``
inside the single-step programs (``jit_vdt_step_*``), over their
executions, all layers.  The dense einsum and the grouped kernel
``vdt_grouped_int8_matmul`` (PR 27) are both filed under that scope, so
the number compares them.  ``None`` where the trace has no such program
or no such scope (a program from before the names).
Layer: kernels.  Moves ``out_tok_s``."""

from benchmark.layer_metrics import _xplane


def read(ctx: dict):
    named = _xplane.load(ctx)
    step = (named or {}).get("programs", {}).get("step")
    if not step or not step["executions"]:
        return None
    if "moe_experts" not in step["scopes"]:
        return None
    return step["scopes"]["moe_experts"] * 1e3 / step["executions"]
