"""The longest stretch inside the window with no output: between the
ends of two consecutive ``engine.process`` spans with ``outputs`` > 0.
``_host_timeline.output_gap`` has the spans that overlap it (each step's
``since_prev_ms`` / ``slack_ms`` / ``intake_ms`` among them) and leaves
them in ``host_timeline.json``.  ``None`` with fewer than two such spans.
Layer: engine.  Moves ``out_tok_s``."""

from benchmark.layer_metrics import _host_timeline


def read(ctx: dict):
    _host_timeline.report(ctx)
    gap = _host_timeline.output_gap(ctx)
    return None if gap is None else gap["ms"]
