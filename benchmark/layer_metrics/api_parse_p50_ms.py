"""A request from the handler's entry to its first ``engine.generate()``
call (body read, JSON, validation, sampling parameters, tokenizing, a
streamed response's headers): ``api.parse`` spans of ``/debug/traces``
started in the window, median.  ``None`` for a program without the span.
Layer: front end.  Moves ``ttft_p50_ms``."""

from benchmark.layer_metrics import _host_timeline


def read(ctx: dict):
    return _host_timeline.median_ms(ctx, "api.parse")
