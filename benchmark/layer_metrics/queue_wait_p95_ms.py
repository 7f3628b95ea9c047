"""Time a request waited in the engine's queue before its first
scheduling: ``engine.queue`` spans of ``/debug/traces``, 95th
percentile over the window.  Layer: engine.  Moves ``out_tok_s``."""

from benchmark import stats
from benchmark.layer_metrics._spans import in_window


def read(ctx: dict):
    spans = in_window(ctx, "engine.queue")
    return stats.percentile([s["duration"] * 1e3 for s in spans], 95)
