"""Shared by the readers of a Falcon-H1 cell: whether the configuration
is such a model, the mixer's device time in one kind of step program,
and the single steps a profile's capture holds with what each span says
of its state-space work."""

from benchmark.layer_metrics import _named, _spans, _xplane

MIXER, SCAN = "ssm_mixer", "ssm_scan"
# a kernel of either name takes the scope's place once one is written
KERNELS = {"decode": "vdt_ssm_update", "step": "vdt_ssd_chunk_scan"}


def applies(ctx: dict) -> bool:
    return "mamba_d_state" in ctx["config"]


def program(ctx: dict, kind: str):
    named = _xplane.load(ctx)
    prog = (named or {}).get("programs", {}).get(kind)
    return prog if prog and prog["executions"] else None


def scan_seconds(ctx: dict, kind: str):
    """Self seconds of the recurrence alone in the programs of one kind
    ("decode" | "step"): scope ``ssm_scan`` and, once written, the
    kernel that replaces it.  ``None`` where the trace has neither."""
    prog = program(ctx, kind)
    if prog is None:
        return None
    found = [prog["scopes"].get(SCAN), prog["kernels"].get(KERNELS[kind])]
    found = [x for x in found if x is not None]
    return sum(found) if found else None


def mixer_seconds(ctx: dict, kind: str):
    """The whole mixer's self seconds: scope ``ssm_mixer`` (the layer's
    input norm to ``out_proj``) with the recurrence nested in it, which
    the named trace files apart (innermost scope first)."""
    prog = program(ctx, kind)
    if prog is None or MIXER not in prog["scopes"]:
        return None
    return prog["scopes"][MIXER] + (scan_seconds(ctx, kind) or 0.0)


def decode_ms(ctx: dict, seconds) -> float | None:
    """Seconds inside the fused-decode programs as milliseconds a
    micro-step (all layers)."""
    prog = program(ctx, "decode")
    if prog is None or seconds is None:
        return None
    steps = prog["executions"] * ctx["cell"].bench["num_decode_steps"]
    return seconds * 1e3 / steps


def captured_steps(ctx: dict) -> list[dict]:
    """The attributes of the single steps' ``runner.dispatch`` spans
    that started inside the profile's stretch."""
    spans = [s["attributes"] | {"start": s["start"]}
             for s in _spans.in_window(ctx, "runner.dispatch")
             if s["attributes"].get("program", "").startswith("vdt_step")]
    for cap in _spans.in_window(ctx, "profile.capture"):
        a = cap["attributes"]
        inside = [s for s in spans
                  if a["begin_wall"] <= s["start"] < a["end_wall"]]
        if inside:
            return inside
    return []


roofline_pct = _named.roofline_pct
