"""How uneven the routing over the held experts is, over the window:
``n_routed_experts`` (held) times the busiest held expert's picks over
all picks of held experts (1.0 = even; each routed layer's busiest,
summed over the layers and the window's steps: ``_dsv3.picks``).  It is
what a grouped kernel that pads every group towards the busiest's pays
over the rows routed here.  ``None`` where no span carries the counts
or the configuration is no such model.  Layer: engine.  Moves
``out_tok_s``."""

from benchmark.layer_metrics import _dsv3


def read(ctx: dict):
    total = _dsv3.picks(ctx)
    if not total or total["held"] <= 0:
        return None
    return ctx["config"]["n_routed_experts"] * total["busiest"] / total["held"]
