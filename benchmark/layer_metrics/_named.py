"""Shared by the readers of the named trace (``_xplane``): the decode
programs' time under one scope or kernel per micro-step, and a share of
a roofline that fails the run above 100%."""

from benchmark.layer_metrics import _xplane


def decode_ms(ctx: dict, table: str, key: str):
    """Self milliseconds of one scope (``table`` "scopes") or kernel
    ("kernels") inside the fused-decode programs, per micro-step (all
    layers): its seconds over executions times the K steps a scan
    fuses.  ``None`` where the trace has no such name."""
    named = _xplane.load(ctx)
    decode = (named or {}).get("programs", {}).get("decode")
    if not decode or key not in decode[table] or not decode["executions"]:
        return None
    steps = decode["executions"] * ctx["cell"].bench["num_decode_steps"]
    return decode[table][key] * 1e3 / steps


def roofline_pct(name: str, model: dict, measured_ms: float) -> float:
    """The least seconds the chip could take over the measured ones.
    Work a step must do in a measured device time cannot pass 100%: a
    reading above it means time of this kernel was filed elsewhere
    (read ``device_unattributed_pct`` first) or the model counts too
    much, and the run fails."""
    pct = 100.0 * model["seconds"] / (measured_ms / 1e3)
    if pct > 100.0:
        raise ValueError(
            f"{name} reads {pct:.1f}%: {model['bytes']:.0f} bytes and "
            f"{model['ops']:.0f} operations ({model['binds']}-bound, "
            f"{model['seconds'] * 1e3:.3f} ms at the chip's peak) in "
            f"{measured_ms:.3f} ms measured")
    return pct


def idle_pct(ctx: dict, kind: str):
    """One of ``_xplane.IDLE_KINDS`` as a share of the traced stretch;
    ``None`` where the host plane has no span of the step loop (a
    program from before them: every gap would read as "other")."""
    named = _xplane.load(ctx)
    if named is None or not named["host"]:
        return None
    return 100.0 * named["idle_s"][kind] / named["window_s"]
