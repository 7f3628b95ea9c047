"""How late the load generator ran: send time - due time, 95th
percentile over the window's requests.  Layer: load generator.  Moves
``out_tok_s`` (a starved generator reads as a slow server)."""


def read(ctx: dict):
    return ctx["window"]["loadgen_late_p95_ms"]
