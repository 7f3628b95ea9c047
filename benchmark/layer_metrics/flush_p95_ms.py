"""One engine step's outputs from ``_dispatch_outputs``' entry on the
event loop to the last of them written: ``frontend.flush`` spans started
in the window, 95th percentile — what sits in front of a burst of chunks
besides the scan that made them.  ``None`` for a program without the
span.  Layer: front end.  Moves ``itl_p95_ms``."""

from benchmark import stats
from benchmark.layer_metrics._spans import in_window


def read(ctx: dict):
    spans = in_window(ctx, "frontend.flush")
    return stats.percentile([s["duration"] * 1e3 for s in spans], 95)
