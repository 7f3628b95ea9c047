"""Shared by the readers of ``/debug/traces``: the spans of one name
that started inside the window.  Span starts are wall-clock seconds;
``wall_offset`` maps the benchmark's monotonic clock onto it."""


def in_window(ctx: dict, name: str) -> list[dict]:
    lo = ctx["t0"] + ctx["wall_offset"]
    hi = ctx["t1"] + ctx["wall_offset"]
    return [
        s for tr in (ctx["spans"] or []) for s in tr["spans"]
        if s["name"] == name and lo <= s["start"] < hi
        and s["duration"] is not None
    ]


def step_shapes(ctx: dict) -> set:
    """(token bucket, longest-chunk bucket, sequences) of every single
    step that ``scheduler.schedule`` recorded inside the window, by the
    server's rule of powers of two — to be held against the buckets the
    warm-up's sweep played (``lattice.buckets``)."""
    from benchmark.lattice import next_power_of_2

    floor = ctx["cell"].mix["warmup"].get("sweep", {}).get(
        "min_token_bucket", 16)
    shapes = set()
    for s in in_window(ctx, "scheduler.schedule"):
        a = s["attributes"]
        if not a.get("batch") or a.get("decode_steps", 1) > 1:
            continue
        chunks = [int(x.rsplit(":", 1)[1]) for x in a["batch"].split(",")]
        shapes.add((max(next_power_of_2(sum(chunks)), floor),
                    next_power_of_2(max(chunks)), len(chunks)))
    return shapes
