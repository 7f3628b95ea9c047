"""Step programs met during set-up (``vllm:xla_compiles_total`` at the
window's start).  Layer: step programs.  Moves ``setup_s``."""


def read(ctx: dict):
    return ctx["programs_compiled"]
