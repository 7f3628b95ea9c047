"""Share of the device's busy time in operations that carry neither a
kernel's name (``vdt_*``) nor one of the program's scopes: time the
per-scope and per-kernel metrics cannot see.  Read it before a roofline
share: a fusion filed under no scope, or under a neighbour's, makes a
kernel look faster than it is.  The operations that make it up are on
an earlier line of the run.  Layer: step programs.  Moves
``tpot_p50_ms``."""

from benchmark.layer_metrics import _xplane


def read(ctx: dict):
    named = _xplane.load(ctx)
    if named is None:
        return None
    if not any(p["scopes"] or p["kernels"]
               for p in named["programs"].values()):
        return None  # a program that names nothing: nothing to hold it to
    return 100.0 * sum(named["unattributed"].values()) / named["busy_s"]
