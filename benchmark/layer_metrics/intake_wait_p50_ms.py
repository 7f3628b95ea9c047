"""A request's wait in ``AsyncLLM``'s intake, from ``generate()``'s put
to the engine thread's ``add_request`` — the time the late schedule (PR
32) spends on purpose while the device allows it, which ``engine.queue``
cannot see: ``engine.intake`` spans started in the window, median.
``None`` for a program without the span.  Layer: front end.  Moves
``ttft_p50_ms``."""

from benchmark.layer_metrics import _host_timeline


def read(ctx: dict):
    return _host_timeline.median_ms(ctx, "engine.intake")
