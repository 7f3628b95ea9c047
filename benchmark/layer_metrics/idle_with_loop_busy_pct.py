"""Share of the traced stretch in which the device was idle while
another thread than the engine's was inside an ``api.*`` or
``gc.collect`` annotation (the event loop serialising and writing
chunks, a collection): the gaps between device operations laid over
those annotations in the profile's host plane, on the profiler's clock.
It overlaps the ``idle_in_*`` shares (which say where the engine's
thread was), it does not add to them.  ``None`` without a device plane
or for a program without the annotations.  Layer: front end.  Moves
``tpot_p50_ms``."""

from benchmark.layer_metrics import _host_timeline


def read(ctx: dict):
    if not _host_timeline.instrumented(ctx):
        return None
    found = _host_timeline.profile_threads(ctx)
    if found is None:
        return None
    return 100.0 * found["idle_with_loop_busy_s"] / found["window_s"]
