"""Share of the chip's roofline that the grouped kernel
(``vdt_grouped_matmul``) reaches in the single steps of a chip that
holds a share of the routed experts: the least seconds
``kernel_model_dsv3.grouped_held_experts`` gives for the rows routed
*here* in each single step whose experts were traced with that kernel
(the ``runner.dispatch`` spans with ``moe`` = ``grouped`` that started
inside the profile's stretch; each step's ``moe_picks_held`` from the
``runner.fetch`` span of the same ``step_id``: picks of experts held
here over all routed layers, not all the step's picks), summed, over
the kernel's self time in the single-step programs of that stretch.
The layout's rows for picks held elsewhere and each group's padding are
the kernel's cost, not work.  Fails the run above 100%.  ``None`` where
the configuration is no such model, the trace has no such kernel, no
``profile.capture`` span says which steps it holds, or no step's fetch
carries the count.  Layer: kernels.  Moves ``out_tok_s``."""

from benchmark import kernel_model_dsv3
from benchmark.layer_metrics import _dsv3, _named, _spans, _xplane
from benchmark.layer_metrics.step_moe32_roofline_pct import (
    KERNEL, _grouped_steps)


def rows_here(ctx: dict) -> list[int]:
    """``moe_picks_held`` of each grouped single step in the capture."""
    steps = {s.get("step_id") for s in _grouped_steps(ctx)}
    return [int(f["attributes"]["moe_picks_held"])
            for f in _spans.in_window(ctx, "runner.fetch")
            if f["attributes"].get("step_id") in steps
            and "moe_picks_held" in f["attributes"]]


def read(ctx: dict):
    if not _dsv3.applies(ctx):
        return None
    named = _xplane.load(ctx)
    step = (named or {}).get("programs", {}).get("step")
    if not step or KERNEL not in step["kernels"]:
        return None
    rows = rows_here(ctx)
    if not rows:
        return None
    models = [kernel_model_dsv3.grouped_held_experts(
        ctx["config"], float(n), ctx["peak"]) for n in rows]
    model = {key: sum(m[key] for m in models)
             for key in ("bytes", "ops", "seconds")}
    model["binds"] = "flops" if sum(
        m["binds"] == "flops" for m in models) * 2 >= len(models) else "hbm"
    return _named.roofline_pct(
        "step_moe_held16_roofline_pct", model, step["kernels"][KERNEL] * 1e3)
