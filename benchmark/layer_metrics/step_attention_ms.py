"""Device time of attention in one single step (a prefill or mixed
step): self time of the operations under scope ``attn`` inside the
single-step programs (``jit_vdt_step_*``), over their executions, all
layers.  The scope holds the kernel ``vdt_paged_attention`` and the
staging around it (the gathers into query tiles and back, the layout
copies), so the number falls with either (PR 30).  ``None`` where the
trace has no such program or no such scope (a program from before the
names).  Layer: kernels.  Moves ``out_tok_s``."""

from benchmark.layer_metrics import _xplane


def read(ctx: dict):
    named = _xplane.load(ctx)
    step = (named or {}).get("programs", {}).get("step")
    if not step or not step["executions"]:
        return None
    if "attn" not in step["scopes"]:
        return None
    return step["scopes"]["attn"] * 1e3 / step["executions"]
