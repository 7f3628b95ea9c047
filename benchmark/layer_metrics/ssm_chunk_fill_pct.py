"""How full the chunked scan's tiles are: the single steps' live tokens
over the tokens their scans walked (tile length times tiles: every tile
of the step's token bucket, padding or not), over the window.  The
runner puts both on ``vllm:ssm_chunk_tokens_total{kind="live"|"tiled"}``
and, as ``ssm_tokens_live`` / ``ssm_tokens_tiled``, on the step's
``runner.dispatch`` span; this reads the spans that started inside the
window.  What a scan that skipped dead tiles would save is the rest.
``None`` where no span carries the counts.  Layer: step programs.  Moves
``out_tok_s``."""

from benchmark.layer_metrics._spans import in_window


def read(ctx: dict):
    live = tiled = 0
    for s in in_window(ctx, "runner.dispatch"):
        a = s["attributes"]
        if "ssm_tokens_tiled" in a:
            live += int(a.get("ssm_tokens_live", 0))
            tiled += int(a["ssm_tokens_tiled"])
    if tiled <= 0:
        return None
    return 100.0 * live / tiled
