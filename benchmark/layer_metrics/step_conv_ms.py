"""Device time of the gated short convolutions in one single step (a
prefill or mixed step): self time of the operations under scope
``short_conv`` inside the single-step programs (``jit_vdt_step_*``),
over their executions, all convolution layers.  ``None`` where the
trace has no such program or no such scope.  Layer: kernels.  Moves
``out_tok_s``."""

from benchmark.layer_metrics import _xplane


def step_scope_ms(ctx: dict, scope: str):
    named = _xplane.load(ctx)
    step = (named or {}).get("programs", {}).get("step")
    if not step or not step["executions"] or scope not in step["scopes"]:
        return None
    return step["scopes"][scope] * 1e3 / step["executions"]


def read(ctx: dict):
    return step_scope_ms(ctx, "short_conv")
