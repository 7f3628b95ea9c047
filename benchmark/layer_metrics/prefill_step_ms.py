"""Device time of one single-step (prefill or mixed) program: median
over its executions in the profiler's trace.  Layer: step programs.
Moves ``out_tok_s`` (mixed steps take about half of the device's time)."""

from benchmark import trace_reduce


def read(ctx: dict):
    return trace_reduce.module_median_ms(
        ctx["trace"], ctx["cell"].bench["trace"]["prefill_module"])
