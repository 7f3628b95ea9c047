"""Shared by the readers of the DeepSeek-V3-architecture cell: whether a
run's configuration is such a model (one latent sublayer a layer,
leading dense layers, a chip's share of the routed experts), and its
router's pick counts over the window."""

from benchmark.layer_metrics._spans import in_window

KINDS = ("held", "absent", "busiest")


def applies(ctx: dict) -> bool:
    """The published keys only this family has together."""
    cfg = ctx["config"]
    return "first_k_dense_replace" in cfg and "kv_lora_rank" in cfg


def picks(ctx: dict) -> dict | None:
    """Picks by kind over the window's fetched steps: the step programs
    count, on the device and over a step's live rows, the picks of
    experts held here, of experts held elsewhere, and each routed
    layer's busiest held expert's picks (summed over the layers); the
    runner puts them on ``vllm:moe_picks_total{kind}`` and, as
    ``moe_picks_<kind>``, on the step's ``runner.fetch`` span."""
    if not applies(ctx):
        return None
    total = dict.fromkeys(KINDS, 0)
    seen = False
    for s in in_window(ctx, "runner.fetch"):
        a = s["attributes"]
        if "moe_picks_busiest" in a and "moe_picks_held" in a:
            seen = True
            for kind in KINDS:
                total[kind] += int(a.get("moe_picks_" + kind, 0))
    return total if seen else None
