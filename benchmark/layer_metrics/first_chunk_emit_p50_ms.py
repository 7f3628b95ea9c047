"""A request's first chunk on its way out, from where ``engine.prefill``
ends (the first token's stamp) to ``response.write`` returned — the rest
of the engine's step, the hop to the event loop, serialising and the
write: ``api.emit`` spans started in the window, median.  ``None`` for a
program without the span.  Layer: front end.  Moves ``ttft_p50_ms``."""

from benchmark.layer_metrics import _host_timeline


def read(ctx: dict):
    return _host_timeline.median_ms(ctx, "api.emit")
