"""Share of the traced stretch in which the device was idle and
the engine's thread was in ``scheduler.schedule``: the gaps
between device operations laid over the annotations the spans leave in
the profile's host plane, on the profiler's clock.  The five
``idle_in_*`` shares add up to ``device_idle_pct``.  Layer: engine.
Moves ``tpot_p50_ms``."""

from benchmark.layer_metrics import _named


def read(ctx: dict):
    return _named.idle_pct(ctx, "schedule")
