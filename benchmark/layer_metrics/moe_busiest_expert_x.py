"""How uneven the routing is over the window: ``num_experts`` times the
busiest expert's picks over all picks (1.0 = even).  The step programs
count, on the device and over a step's live rows, every pick and each
routed layer's busiest expert's picks (summed over the layers); the
runner puts them on ``vllm:moe_expert_picks_total{kind="all"|
"busiest"}`` and, as ``moe_picks_all`` / ``moe_picks_busiest``, on the
step's ``runner.fetch`` span; this reads the spans that started inside
the window.  It is what a grouped kernel that pads every expert's group
to the busiest's would pay over the routed rows.  ``None`` where no
span carries the counts.  Layer: engine.  Moves ``out_tok_s``."""

from benchmark.layer_metrics._spans import in_window


def read(ctx: dict):
    picked = busiest = 0
    for s in in_window(ctx, "runner.fetch"):
        a = s["attributes"]
        if "moe_picks_busiest" in a:
            picked += int(a.get("moe_picks_all", 0))
            busiest += int(a["moe_picks_busiest"])
    experts = ctx["config"].get("num_experts")
    if picked <= 0 or not experts:
        return None
    return experts * busiest / picked
