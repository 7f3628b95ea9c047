"""Step programs first met inside the window: the rise of
``vllm:xla_compiles_total`` from the window's start to its end.
Layer: step programs.  Moves ``out_tok_s`` (each is a stall of the whole engine)."""


def read(ctx: dict):
    return ctx["window_compiles"]
