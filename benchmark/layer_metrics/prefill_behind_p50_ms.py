"""From a request's first schedule to the instant the dispatch in front
of its first dispatch resolved (0 where the chip was free): ``behind_ms``
of the ``engine.prefill`` spans started in the window, median — the part
of ``engine.prefill`` that is waiting, not the request's own step(s).
``None`` for a program whose spans lack the attribute.  Layer: engine.
Moves ``ttft_p50_ms``."""

from benchmark.layer_metrics import _host_timeline


def read(ctx: dict):
    return _host_timeline.median_ms(ctx, "engine.prefill", "behind_ms")
