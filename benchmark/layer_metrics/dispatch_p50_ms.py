"""Host time to hand one step program to the device: ``runner.dispatch``
spans of ``/debug/traces`` (around the call that enqueues the program,
AOT cache lookup included) that were not a program's first call, median
over the window.  Layer: step programs.  Moves ``tpot_p50_ms``."""

from benchmark import stats
from benchmark.layer_metrics._spans import in_window


def read(ctx: dict):
    spans = [s for s in in_window(ctx, "runner.dispatch")
             if not s["attributes"].get("first_call")]
    return stats.percentile([s["duration"] * 1e3 for s in spans], 50)
