"""Device time of the Mamba-2 mixers in one single step (a prefill or
mixed step): self time under scope ``ssm_mixer`` with the recurrence
nested in it (``ssm_scan``) inside the single-step programs
(``jit_vdt_step_*``), over their executions, all layers.  ``None`` where
the configuration has no such mixer or the trace no such program or
scope.  Layer: kernels.  Moves ``out_tok_s``."""

from benchmark.layer_metrics import _falcon_h1


def read(ctx: dict):
    if not _falcon_h1.applies(ctx):
        return None
    seconds = _falcon_h1.mixer_seconds(ctx, "step")
    if seconds is None:
        return None
    return seconds * 1e3 / _falcon_h1.program(ctx, "step")["executions"]
