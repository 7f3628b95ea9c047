"""Share of the router's picks that went to experts this chip holds,
over the window: held over held + absent, from the counts the step
programs make on the device (``_dsv3.picks``).  An even router reads
``n_routed_experts`` over the router's width (6.25 at 16 of 256); the
groups and the seeded bias move it.  It scales the rows the held
experts see.  ``None`` where no span carries the counts or the
configuration is no such model.  Layer: engine.  Moves ``out_tok_s``."""

from benchmark.layer_metrics import _dsv3


def read(ctx: dict):
    total = _dsv3.picks(ctx)
    if not total or total["held"] + total["absent"] <= 0:
        return None
    return 100.0 * total["held"] / (total["held"] + total["absent"])
