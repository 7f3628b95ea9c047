"""Share of the traced stretch of the window in which no operation ran
on the device: 1 - union of device-op intervals over the stretch.
Layer: device.  Moves ``tpot_p50_ms``."""


def read(ctx: dict):
    t = ctx["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
