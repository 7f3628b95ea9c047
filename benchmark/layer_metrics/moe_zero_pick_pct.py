"""Share of the router's picks that went to identity (zero-compute)
experts over the window: the step programs count their picks by kind on
the device (zero | held | absent) and the runner puts each fetched
step's counts on ``vllm:moe_picks_total{kind=...}`` and, as
``moe_picks_<kind>``, on the step's ``runner.fetch`` span; this reads
the spans that started inside the window (``ctx`` carries
``/debug/traces``, not the scrapes of ``/metrics``), zero over all
kinds.  An even router reads zero_expert_num over the router's width
(33.3 at 256 of 768; ``held`` 2.08 at 16).  ``None`` where no span
carries the counts (a program without such a router).  Layer: engine.
Moves ``out_tok_s``."""

from benchmark.layer_metrics._spans import in_window

KINDS = ("zero", "held", "absent")


def picks(ctx: dict) -> dict | None:
    """Picks by kind over the window's fetched steps."""
    total = dict.fromkeys(KINDS, 0)
    seen = False
    for s in in_window(ctx, "runner.fetch"):
        a = s["attributes"]
        if "moe_picks_zero" in a:
            seen = True
            for kind in KINDS:
                total[kind] += int(a.get("moe_picks_" + kind, 0))
    return total if seen else None


def read(ctx: dict):
    total = picks(ctx)
    if not total or sum(total.values()) <= 0:
        return None
    return 100.0 * total["zero"] / sum(total.values())
