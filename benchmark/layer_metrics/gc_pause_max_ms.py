"""The longest garbage collection of generation 1 or 2 inside the
window, a stop of every thread of the server: ``gc.collect`` spans of
``/debug/traces``' process trace, 0 when the window had none.  ``None``
for a program without the hook.  Layer: engine.  Moves ``itl_p95_ms``."""

from benchmark.layer_metrics import _host_timeline
from benchmark.layer_metrics._spans import in_window


def read(ctx: dict):
    if not _host_timeline.instrumented(ctx):
        return None
    return max((s["duration"] * 1e3 for s in in_window(ctx, "gc.collect")),
               default=0.0)
