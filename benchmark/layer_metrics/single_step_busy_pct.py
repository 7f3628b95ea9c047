"""Share of the device's busy time that single steps (prefill and mixed
steps, programs ``jit_vdt_step_*``) take: their seconds in the profile
over the busy seconds.  A single step yields one token a sequence where
a fused scan yields K.  Layer: step programs.  Moves ``out_tok_s``."""

from benchmark.layer_metrics import _xplane


def read(ctx: dict):
    named = _xplane.load(ctx)
    if named is None or "step" not in named["programs"]:
        return None
    return 100.0 * named["programs"]["step"]["seconds"] / named["busy_s"]
