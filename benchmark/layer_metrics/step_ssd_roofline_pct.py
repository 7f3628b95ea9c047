"""Share of the chip's roofline that the state-space recurrence reaches
in single steps: the least seconds the kernel model gives for each
single step inside the profile's stretch, summed, over the self time of
scope ``ssm_scan`` (or kernel ``vdt_ssd_chunk_scan``) in the single-step
programs of that stretch.  A step whose ``runner.dispatch`` span
carries ``ssm_tokens_live`` walked the chunked form
(``kernel_model_falcon_h1.chunk_scan`` over its ``tokens`` and
``state_rows``); one without it (every sequence has one token) ran the
one-token update over its ``state_rows`` (``state_update``).  Fails the
run above 100%.  ``None`` where the configuration has no such mixer, the
trace no such scope, or no ``profile.capture`` span says which steps the
trace holds.  Layer: kernels.  Moves ``out_tok_s``."""

from benchmark import kernel_model_falcon_h1 as km
from benchmark.layer_metrics import _falcon_h1


def read(ctx: dict):
    if not _falcon_h1.applies(ctx):
        return None
    seconds = _falcon_h1.scan_seconds(ctx, "step")
    steps = [s for s in _falcon_h1.captured_steps(ctx) if "state_rows" in s]
    if seconds is None or not steps:
        return None
    cfg, peak = ctx["config"], ctx["peak"]
    models = [
        km.chunk_scan(cfg, float(s["ssm_tokens_live"]),
                      float(s["state_rows"]), peak)
        if "ssm_tokens_live" in s
        else km.state_update(cfg, float(s["state_rows"]), peak)
        for s in steps]
    model = {key: sum(m[key] for m in models)
             for key in ("bytes", "ops", "seconds")}
    model["binds"] = "flops" if sum(
        m["binds"] == "flops" for m in models) * 2 >= len(models) else "hbm"
    return _falcon_h1.roofline_pct(
        "step_ssd_roofline_pct", model, seconds * 1e3)
