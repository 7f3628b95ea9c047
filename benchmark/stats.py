"""The benchmark's arithmetic: percentiles and the reduction from a
window's request records to its end-to-end metrics.

A request record is a dict (see ``loadgen.Record.to_dict``): ``due``,
``sent``, ``status``, ``error``, ``want`` (tokens asked for), ``chunks``
(a list of ``[arrival time, tokens in the chunk]``).  All times are the
host's monotonic clock, in seconds.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile (q in 0..100) of a non-empty
    list; ``None`` for an empty one."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def token_times(rec: dict) -> list[float]:
    """Arrival time of every output token: a chunk's tokens all take
    the chunk's arrival time."""
    out: list[float] = []
    for t, n in rec["chunks"]:
        out.extend([t] * n)
    return out


def ok(rec: dict) -> bool:
    """A request counts as served when it answered 200, raised no
    error and delivered every token it asked for."""
    return (
        rec["status"] == 200
        and not rec["error"]
        and sum(n for _, n in rec["chunks"]) == rec["want"]
    )


def ttft_ms(rec: dict) -> float:
    return (rec["chunks"][0][0] - rec["due"]) * 1e3


def tpot_ms(rec: dict) -> float | None:
    """(last token time - first token time) / (tokens - 1)."""
    times = token_times(rec)
    if len(times) < 2:
        return None
    return (times[-1] - times[0]) / (len(times) - 1) * 1e3


def gaps_ms(rec: dict) -> list[float]:
    times = token_times(rec)
    return [(b - a) * 1e3 for a, b in zip(times, times[1:])]


def window_metrics(records: list[dict], t0: float, t1: float) -> dict:
    """End-to-end numbers of one window.  ``counted`` are the requests
    due in [t0, t1); a failed, shed or short one is in ``failed`` and
    in no timing (it has missed every limit).  ``out_tok_s`` counts
    every output token that arrived inside the window, whichever
    request it belongs to, over the window's whole length."""
    counted = [r for r in records if t0 <= r["due"] < t1]
    good = [r for r in counted if ok(r)]
    ttft = [ttft_ms(r) for r in good]
    tpot = [x for x in (tpot_ms(r) for r in good) if x is not None]
    gaps = [g for r in good for g in gaps_ms(r)]
    tokens_in_window = sum(
        n for r in records for t, n in r["chunks"] if t0 <= t < t1
    )
    late = [(r["sent"] - r["due"]) * 1e3 for r in counted
            if r["sent"] is not None]
    return {
        "attempted": len(counted),
        "failed": len(counted) - len(good),
        "samples": {"ttft": len(ttft), "tpot": len(tpot), "gaps": len(gaps)},
        "ttft_p95_ms": percentile(ttft, 95),
        "ttft_p50_ms": percentile(ttft, 50),
        "tpot_p50_ms": percentile(tpot, 50),
        "itl_p95_ms": percentile(gaps, 95),
        "out_tok_s": tokens_in_window / (t1 - t0),
        "loadgen_late_p95_ms": percentile(late, 95),
        "in_tok": sum(r["prompt_len"] for r in counted),
    }
