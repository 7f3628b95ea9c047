"""Share of the chip's roofline that paged attention reaches in a decode
micro-step: the least seconds ``kernel_model.paged_attention`` gives for
the cached tokens a fused scan reads (``kv_tokens`` and ``seqs`` of the
``runner.dispatch`` spans of decode programs, mean over the profile's
stretch, else over the window) over ``decode_attention_ms``.  Layer:
kernels.  Moves ``tpot_p50_ms``."""

from benchmark import kernel_model
from benchmark.layer_metrics import _named, _spans, decode_attention_ms


def _decode_dispatches(ctx: dict) -> list[dict]:
    spans = [s["attributes"] | {"start": s["start"]}
             for s in _spans.in_window(ctx, "runner.dispatch")
             if s["attributes"].get("program", "").startswith("vdt_decode")]
    for cap in _spans.in_window(ctx, "profile.capture"):
        a = cap["attributes"]
        inside = [s for s in spans
                  if a["begin_wall"] <= s["start"] < a["end_wall"]]
        if inside:
            return inside
    return spans


def read(ctx: dict):
    ms = decode_attention_ms.read(ctx)
    spans = _decode_dispatches(ctx)
    if ms is None or not spans:
        return None
    kv_tokens = sum(s["kv_tokens"] for s in spans) / len(spans)
    seqs = sum(s["seqs"] for s in spans) / len(spans)
    return _named.roofline_pct(
        "decode_attention_roofline_pct",
        kernel_model.paged_attention(
            ctx["config"], kv_tokens, seqs, ctx["peak"]), ms)
