"""Share of the chip's roofline that the grouped kernel for unquantized
experts (``vdt_grouped_matmul``) reaches in single steps: the least
seconds ``kernel_model_lfm2.grouped_experts`` gives for the live tokens
of each single step whose experts were traced with that kernel (the
``runner.dispatch`` spans with ``moe`` = ``grouped`` that started inside
the profile's stretch: their ``tokens``), summed, over the kernel's
self time in the single-step programs of that stretch.  Work a step
must do in a measured device time cannot pass 100%: the run fails above
it.  ``None`` where the trace has no such kernel, no ``profile.capture``
span says which steps it holds, or the configuration has no such
experts.  Layer: kernels.  Moves ``out_tok_s``."""

from benchmark import kernel_model_lfm2
from benchmark.layer_metrics import _named, _spans, _xplane

KERNEL = "vdt_grouped_matmul"


def _grouped_steps(ctx: dict) -> list[dict]:
    spans = [s["attributes"] | {"start": s["start"]}
             for s in _spans.in_window(ctx, "runner.dispatch")
             if s["attributes"].get("program", "").startswith("vdt_step")
             and s["attributes"].get("moe") == "grouped"]
    for cap in _spans.in_window(ctx, "profile.capture"):
        a = cap["attributes"]
        inside = [s for s in spans
                  if a["begin_wall"] <= s["start"] < a["end_wall"]]
        if inside:
            return inside
    return []


def read(ctx: dict):
    if "moe_intermediate_size" not in ctx["config"]:
        return None
    named = _xplane.load(ctx)
    step = (named or {}).get("programs", {}).get("step")
    spans = _grouped_steps(ctx)
    if not step or not spans or KERNEL not in step["kernels"]:
        return None
    models = [kernel_model_lfm2.grouped_experts(
        ctx["config"], float(s["tokens"]), ctx["peak"]) for s in spans]
    model = {key: sum(m[key] for m in models)
             for key in ("bytes", "ops", "seconds")}
    model["binds"] = "flops" if sum(
        m["binds"] == "flops" for m in models) * 2 >= len(models) else "hbm"
    return _named.roofline_pct(
        "step_moe32_roofline_pct", model, step["kernels"][KERNEL] * 1e3)
