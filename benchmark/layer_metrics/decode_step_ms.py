"""Device time of one decode micro-step: median duration of the
fused-decode program in the profiler's trace, divided by the K steps it
fuses.  Layer: step programs.  Moves ``tpot_p50_ms``."""

from benchmark import trace_reduce


def read(ctx: dict):
    bench = ctx["cell"].bench
    ms = trace_reduce.module_median_ms(
        ctx["trace"], bench["trace"]["decode_module"])
    return None if ms is None else ms / bench["num_decode_steps"]
