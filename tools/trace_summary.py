"""Offline per-stage latency summary of a /debug/traces dump.

The command-line companion to Perfetto (ISSUE 5 satellite): point it at
a saved ``/debug/traces`` JSON dump — or straight at a live server's
endpoint URL — and it prints a per-stage p50/p90/p99 table, so "where
did the latency go" is answerable from a terminal without loading a
trace UI.

    python tools/trace_summary.py traces.json
    python tools/trace_summary.py http://localhost:8000/debug/traces

Pure stdlib; the input is the ``{"traces": [...]}`` shape served by the
API server (tracing.Tracer.snapshot).
"""

from __future__ import annotations

import argparse
import json
import sys
import urllib.request

# Stages printed first, in pipeline order; any other span names found in
# the dump follow alphabetically.  The router hop (ISSUE 10) sits above
# the replica's api.request: the router forwards its trace context in
# X-VDT-Trace-Id, so a dump merged from the router's and the replica's
# /debug/traces shows the whole path under one trace id.
_STAGE_ORDER = [
    "router.request",
    "router.handoff",
    "api.request",
    # The request's way in (PR 40): parse, the wait for the engine
    # thread to take it in, then the engine's own stages.
    "api.parse",
    "engine.intake",
    "engine.queue",
    "engine.kv_restore",
    "engine.kv_handoff",
    "engine.prefill",
    # ... and its first chunk's way out.
    "api.emit",
    "engine.decode",
    # One trace per engine step (PR 26): the root and the host's phases.
    "engine.step",
    "scheduler.schedule",
    "executor.dispatch",
    "executor.gather",
    "worker.execute",
    "worker.serialize",
    "runner.prepare",
    "runner.dispatch",
    "runner.fetch",
    "engine.process",
    "frontend.flush",
    # Causes that belong to no request (the process trace).
    "gc.collect",
    "xla.compile",
    "profile.capture",
]


def load_traces(source: str) -> list[dict]:
    """Read a dump from a file path or an http(s) URL."""
    if source.startswith(("http://", "https://")):
        with urllib.request.urlopen(source, timeout=30) as resp:
            payload = json.load(resp)
    elif source == "-":
        payload = json.load(sys.stdin)
    else:
        with open(source) as f:
            payload = json.load(f)
    if isinstance(payload, dict):
        return payload.get("traces", [])
    return payload


def percentile(sorted_values: list[float], q: float) -> float:
    """Nearest-rank percentile on an already-sorted list."""
    if not sorted_values:
        return 0.0
    idx = min(
        len(sorted_values) - 1, max(0, round(q * (len(sorted_values) - 1)))
    )
    return sorted_values[idx]


def summarize(traces: list[dict]) -> dict[str, dict[str, float]]:
    """Aggregate span durations by name: count/p50/p90/p99/max (s)."""
    by_name: dict[str, list[float]] = {}
    marker_counts: dict[str, int] = {}
    for trace in traces:
        for span in trace.get("spans", []):
            name = span["name"]
            duration = span.get("duration")
            if duration is None:
                # Instant event.  Pipeline stages recorded as markers
                # (router.handoff is an event on the router's request
                # span, not a timed child) still get a count-only row —
                # a stage listed in _STAGE_ORDER must never silently
                # vanish from the table.
                if name in _STAGE_ORDER:
                    marker_counts[name] = marker_counts.get(name, 0) + 1
                continue
            by_name.setdefault(name, []).append(float(duration))
    stats: dict[str, dict[str, float]] = {}
    for name, durations in by_name.items():
        durations.sort()
        stats[name] = {
            "count": len(durations),
            "p50": percentile(durations, 0.50),
            "p90": percentile(durations, 0.90),
            "p99": percentile(durations, 0.99),
            "max": durations[-1],
        }
    for name, count in marker_counts.items():
        if name not in stats:
            stats[name] = {
                "count": count,
                "p50": None,
                "p90": None,
                "p99": None,
                "max": None,
            }
    return stats


def overlap_summary(traces: list[dict]) -> dict | None:
    """Dispatch-pipeline overlap view (ISSUE 7): for each pair of
    consecutively dispatched steps on the same host, the idle gap
    between the end of ``executor.gather`` N and the start of
    ``executor.dispatch`` N+1.  A positive gap is a **stall window** —
    the driver sat waiting for results before it had the next step on
    the wire; the overlapped scheduler exists to make every gap
    negative (dispatch N+1 in flight before gather N lands).

    Returns ``{"steps", "stall_windows", "gap_p50", "gap_p90",
    "gap_max"}`` (seconds; gaps can be negative), or None when the dump
    has no step-stamped dispatch/gather spans (tracing predates the
    overlap protocol, or no steps ran).
    """
    # host -> step_id -> {"dispatch": start, "gather_end": end}
    by_host: dict[str, dict[int, dict[str, float]]] = {}
    for trace in traces:
        for span in trace.get("spans", []):
            name = span.get("name")
            if name not in ("executor.dispatch", "executor.gather"):
                continue
            attrs = span.get("attributes") or {}
            step_id = attrs.get("step_id")
            host = attrs.get("target_host")
            if step_id is None or host is None:
                continue
            steps = by_host.setdefault(host, {})
            entry = steps.setdefault(int(step_id), {})
            entry["trace_id"] = trace.get("trace_id")
            start = float(span.get("start") or 0.0)
            duration = float(span.get("duration") or 0.0)
            if name == "executor.dispatch":
                # First dispatch span wins (a step is dispatched once
                # per host; retries would only widen the gap).
                entry.setdefault("dispatch", start)
            else:
                entry["gather_end"] = max(
                    entry.get("gather_end", 0.0), start + duration
                )
    gaps: list[float] = []
    stall_windows = 0
    pairs = 0
    for steps in by_host.values():
        ordered = sorted(steps)
        for prev, nxt in zip(ordered, ordered[1:]):
            if steps[prev].get("trace_id") != steps[nxt].get("trace_id"):
                # Steps from different traces: idle time between
                # unrelated requests (or a ring-evicted trace), not a
                # pipeline gap.  Within one trace, non-adjacent ids are
                # still a real pair — empty schedules consume a step id
                # without dispatching, exactly the stall-prone window.
                continue
            gather_end = steps[prev].get("gather_end")
            dispatch = steps[nxt].get("dispatch")
            if gather_end is None or dispatch is None:
                continue
            pairs += 1
            gap = dispatch - gather_end
            gaps.append(gap)
            if gap > 0:
                stall_windows += 1
    if not pairs:
        return None
    gaps.sort()
    return {
        "steps": pairs,
        "stall_windows": stall_windows,
        "gap_p50": percentile(gaps, 0.50),
        "gap_p90": percentile(gaps, 0.90),
        "gap_max": gaps[-1],
    }


def spec_summary(traces: list[dict]) -> dict | None:
    """Speculative-decoding acceptance view (ISSUE 11): aggregate the
    ``engine.spec_decode`` instant events the engine emits per verify
    step (attributes: drafted, accepted).  Returns ``{"verify_steps",
    "drafted", "accepted", "acceptance_rate"}`` or None when the dump
    has no spec events (spec decode off, or tracing predates it)."""
    steps = drafted = accepted = 0
    for trace in traces:
        for span in trace.get("spans", []):
            if span.get("name") != "engine.spec_decode":
                continue
            attrs = span.get("attributes") or {}
            if "drafted" not in attrs:
                continue
            steps += 1
            drafted += int(attrs.get("drafted", 0))
            accepted += int(attrs.get("accepted", 0))
    if not steps:
        return None
    return {
        "verify_steps": steps,
        "drafted": drafted,
        "accepted": accepted,
        "acceptance_rate": (accepted / drafted) if drafted else 0.0,
    }


def format_spec(spec: dict) -> str:
    return "\n".join(
        [
            "speculative decoding (greedy n-gram verify)",
            f"  verify steps   : {spec['verify_steps']}",
            f"  drafted tokens : {spec['drafted']}",
            f"  accepted tokens: {spec['accepted']}",
            f"  acceptance rate: {spec['acceptance_rate']:.3f}",
        ]
    )


def format_overlap(overlap: dict) -> str:
    lines = [
        "dispatch overlap (gap = dispatch N+1 start - gather N end; "
        "negative = overlapped)",
        f"  step pairs     : {overlap['steps']}",
        f"  stall_windows  : {overlap['stall_windows']}",
        f"  gap p50 (ms)   : {overlap['gap_p50'] * 1e3:+.2f}",
        f"  gap p90 (ms)   : {overlap['gap_p90'] * 1e3:+.2f}",
        f"  gap max (ms)   : {overlap['gap_max'] * 1e3:+.2f}",
    ]
    return "\n".join(lines)


def format_table(stats: dict[str, dict[str, float]]) -> str:
    names = [n for n in _STAGE_ORDER if n in stats]
    names += sorted(set(stats) - set(_STAGE_ORDER))
    header = (
        f"{'stage':<22} {'count':>7} {'p50(ms)':>10} {'p90(ms)':>10} "
        f"{'p99(ms)':>10} {'max(ms)':>10}"
    )
    lines = [header, "-" * len(header)]
    for name in names:
        s = stats[name]
        if s["p50"] is None:  # count-only marker stage
            dash = f"{'-':>10}"
            lines.append(
                f"{name:<22} {int(s['count']):>7} {dash} {dash} "
                f"{dash} {dash}"
            )
            continue
        lines.append(
            f"{name:<22} {int(s['count']):>7} {s['p50'] * 1e3:>10.2f} "
            f"{s['p90'] * 1e3:>10.2f} {s['p99'] * 1e3:>10.2f} "
            f"{s['max'] * 1e3:>10.2f}"
        )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        description="per-stage p50/p90/p99 from a /debug/traces dump"
    )
    parser.add_argument(
        "source",
        help="dump file, '-' for stdin, or a /debug/traces URL",
    )
    args = parser.parse_args(argv)
    traces = load_traces(args.source)
    if not traces:
        print("no traces in dump (is tracing enabled on the server?)")
        return 1
    stats = summarize(traces)
    print(f"{len(traces)} trace(s)")
    print(format_table(stats))
    overlap = overlap_summary(traces)
    if overlap is not None:
        print()
        print(format_overlap(overlap))
    spec = spec_summary(traces)
    if spec is not None:
        print()
        print(format_spec(spec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
