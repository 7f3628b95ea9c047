"""XLA backend compiles inside the window, as JAX's own compile event
reports them: ``xla.compile`` spans with ``cache_hit`` false started in
the window.  Unlike ``window_compiles`` (first executions of a runner
key) it sees a jit that re-specialises under an unchanged key.  ``None``
for a program without the listener.  Layer: step programs.  Moves
``out_tok_s``."""

from benchmark.layer_metrics import _host_timeline
from benchmark.layer_metrics._spans import in_window


def read(ctx: dict):
    if not _host_timeline.instrumented(ctx):
        return None
    return sum(1 for s in in_window(ctx, "xla.compile")
               if not s["attributes"].get("cache_hit"))
