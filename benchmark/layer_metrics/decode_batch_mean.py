"""Sequences per fused-decode dispatch: ``scheduler.schedule`` spans
with ``decode_steps`` > 1, mean of their cached-request counts over the
window.  Layer: engine.  Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics._spans import in_window


def read(ctx: dict):
    sizes = [
        s["attributes"]["num_cached"] + s["attributes"]["num_new"]
        for s in in_window(ctx, "scheduler.schedule")
        if s["attributes"].get("decode_steps", 1) > 1
    ]
    return sum(sizes) / len(sizes) if sizes else None
