"""Shared by the readers of the host's time line (PR 40): the spans that
close a request's way from the socket to the engine thread and back
(``api.parse``, ``engine.intake``, ``engine.queue``, ``engine.prefill``,
``api.emit``, the root's ``first_chunk_ms``), the engine thread's time
between two steps (``since_prev_ms`` / ``slack_ms`` / ``intake_ms`` on
each ``engine.step`` root), the causes that belong to no thread's phases
(``gc.collect``, ``xla.compile``), and, from the profile, what the other
threads were doing in the device's gaps.

A program from before these spans (the parent of PR 40) has none of
them: ``instrumented`` is then false, the readers that would read 0
from silence return ``None``, and nothing here raises.  The first reader
to come by leaves ``host_timeline.json`` beside the run's files: the
split of the first token's time, the longest stretch with no output and
the spans over it, the ring's reach — what ``PERF.md`` §5 quotes.
"""

from __future__ import annotations

import json
import os
import re

from benchmark import stats, trace_reduce
from benchmark.layer_metrics import _xplane
from benchmark.layer_metrics._spans import in_window

# A request's way, stage by stage; they add up to ``first_chunk_ms``.
STAGES = ("api.parse", "engine.intake", "engine.queue", "engine.prefill",
          "api.emit")
# Annotations that say another thread than the engine's held the GIL:
# the synchronous ones.  ``api.request`` is not among them: a root span
# on the event loop stays entered across every ``await`` of a request's
# life, so its annotation says nothing of who was running.
LOOP_BUSY = ("api.write", "api.dispatch", "gc.collect")


def all_spans(ctx: dict, name: str) -> list[dict]:
    return [s for tr in (ctx["spans"] or []) for s in tr["spans"]
            if s["name"] == name and s["duration"] is not None]


def instrumented(ctx: dict) -> bool:
    """Whether the program writes PR 40's spans at all: every
    ``engine.step`` root of such a program says ``since_prev_ms``."""
    if "_instrumented" not in ctx:
        ctx["_instrumented"] = any(
            "since_prev_ms" in s["attributes"]
            for s in all_spans(ctx, "engine.step"))
    return ctx["_instrumented"]


def _mean(values: list):
    return sum(values) / len(values) if values else None


def median_ms(ctx: dict, name: str, attribute: str | None = None):
    """Median over the window of a span's milliseconds, or of one of
    its attributes (which is in milliseconds already)."""
    spans = in_window(ctx, name)
    if attribute is None:
        return stats.percentile([s["duration"] * 1e3 for s in spans], 50)
    return stats.percentile(
        [s["attributes"][attribute] for s in spans
         if attribute in s["attributes"]], 50)


def output_gap(ctx: dict) -> dict | None:
    """The longest stretch inside the window between the ends of two
    consecutive ``engine.process`` spans with ``outputs`` > 0, and the
    spans that overlap it: {"ms", "start", "end" (wall seconds),
    "spans": [{name, step_id, ms, and for an ``engine.step`` root its
    since_prev_ms / slack_ms / intake_ms}]}.  ``None`` with fewer than
    two such ends inside the window."""
    if "_output_gap" in ctx:
        return ctx["_output_gap"]
    lo = ctx["t0"] + ctx["wall_offset"]
    hi = ctx["t1"] + ctx["wall_offset"]
    ends = sorted(
        s["start"] + s["duration"] for s in all_spans(ctx, "engine.process")
        if s["attributes"].get("outputs", 0) > 0
        and lo <= s["start"] + s["duration"] < hi)
    gap = None
    if len(ends) >= 2:
        a, b = max(zip(ends, ends[1:]), key=lambda p: p[1] - p[0])
        over = []
        for tr in ctx["spans"]:
            for s in tr["spans"]:
                if s["duration"] is None:
                    continue
                if s["start"] < b and s["start"] + s["duration"] > a:
                    row = {"name": s["name"],
                           "step_id": s["attributes"].get("step_id"),
                           "ms": s["duration"] * 1e3,
                           "at_ms": (s["start"] - a) * 1e3}
                    for key in ("since_prev_ms", "slack_ms", "idle_ms",
                                "intake_ms", "generation", "thread",
                                "seconds"):
                        if key in s["attributes"]:
                            row[key] = s["attributes"][key]
                    over.append(row)
        over.sort(key=lambda r: r["at_ms"])
        # a request's whole life (and a capture's) overlaps every gap:
        # the steps and the process's own spans are what name a cause
        keep = [r for r in over if not r["name"].startswith(
            ("api.request", "engine.decode", "engine.queue",
             "engine.prefill", "engine.intake", "api.parse", "api.emit",
             "profile.capture"))]
        gap = {"ms": (b - a) * 1e3, "start": a, "end": b,
               "spans": keep[:60]}
    ctx["_output_gap"] = gap
    return gap


# ---- the profile: what the other threads did in the device's gaps -------
def load_threads(xplane_path: str) -> dict:
    """{"ops": [(start, end)] of the first device plane that ran
    anything, "engine": [(start, end)] of ``engine.step``, "busy":
    [(name, start, end)] of ``LOOP_BUSY`` annotations on the other
    threads, "gc_engine": the same for ``gc.collect`` on the engine's},
    nanoseconds on the trace's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    ops: list = []
    threads: list = []
    for plane in data.planes:
        if re.match(r"/device:(TPU|GPU):\d+$", plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE and not ops:
                    ops = [(int(ev.start_ns),
                            int(ev.start_ns) + int(ev.duration_ns))
                           for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                found = [(ev.name, int(ev.start_ns),
                          int(ev.start_ns) + int(ev.duration_ns))
                         for ev in line.events
                         if ev.name == "engine.step"
                         or ev.name in LOOP_BUSY]
                if found:
                    threads.append(found)
    engine = [t for t in threads if any(n == "engine.step" for n, *_ in t)]
    rest = [t for t in threads if t not in engine]
    return {
        "ops": ops,
        "engine": [(s, e) for t in engine for n, s, e in t
                   if n == "engine.step"],
        "gc_engine": [(n, s, e) for t in engine for n, s, e in t
                      if n != "engine.step"],
        "busy": [ev for t in rest for ev in t],
    }


def loop_busy(threads: dict) -> dict | None:
    """``None`` without a device plane.  Else the captured stretch
    (first operation's start to the last's end), its gaps, how much of
    them lay under a ``LOOP_BUSY`` annotation of another thread than
    the engine's, and the largest gap with what lay over it."""
    if not threads["ops"]:
        return None
    busy_dev = _xplane._merged(threads["ops"])
    lo, hi = busy_dev[0][0], busy_dev[-1][1]
    gaps = _xplane._minus([(lo, hi)], busy_dev)
    under = _xplane._merged([(s, e) for _, s, e in threads["busy"]])
    out = {"window_s": (hi - lo) / 1e9,
           "idle_s": sum(e - s for s, e in gaps) / 1e9,
           "idle_with_loop_busy_s": _xplane._overlap(gaps, under) / 1e9,
           "annotations": len(threads["busy"])}
    if gaps:
        s, e = max(gaps, key=lambda g: g[1] - g[0])
        over: dict = {}
        for name, a, b in threads["busy"] + [
                (n + " (engine thread)", a, b)
                for n, a, b in threads["gc_engine"]]:
            ov = min(e, b) - max(s, a)
            if ov > 0:
                over[name] = over.get(name, 0.0) + ov / 1e6
        out["largest_gap"] = {
            "ms": (e - s) / 1e6, "at_ms": (s - lo) / 1e6,
            "under_ms": {k: round(v, 3) for k, v in sorted(
                over.items(), key=lambda kv: -kv[1])}}
    return out


def profile_threads(ctx: dict) -> dict | None:
    """``loop_busy`` of this run's profile, read once; ``None`` where
    the run left no profile or it has no device plane."""
    if "_loop_busy" not in ctx:
        ctx["_loop_busy"] = None
        out_dir = os.path.join(
            _xplane.REPO, "chiprun_out", "benchmark", ctx["cell"].name)
        try:
            path = trace_reduce.find_xplane(os.path.join(out_dir, "profile"))
        except FileNotFoundError:
            return None
        ctx["_loop_busy"] = loop_busy(load_threads(path))
    return ctx["_loop_busy"]


# ---- what PERF.md quotes -------------------------------------------------
def report(ctx: dict) -> dict:
    """The host's time line of this run in one table, computed once,
    printed on earlier lines and left beside the run's files."""
    if "_host_timeline" in ctx:
        return ctx["_host_timeline"]
    lo = ctx["t0"] + ctx["wall_offset"]
    split = {name: median_ms(ctx, name) for name in STAGES}
    first = [s["attributes"]["first_chunk_ms"]
             for s in in_window(ctx, "api.request")
             if "first_chunk_ms" in s["attributes"]]
    # Medians do not add (a long wait in the queue goes with a short
    # one behind the dispatch); means do, and each request's own sum is
    # exact: how far the worst is from its root's ``first_chunk_ms``.
    hi = ctx["t1"] + ctx["wall_offset"]
    sums, errors = [], []
    for tr in ctx["spans"] or []:
        root = next((s for s in tr["spans"] if s["name"] == "api.request"
                     and "first_chunk_ms" in s["attributes"]
                     and lo <= s["start"] < hi), None)
        stages = {s["name"]: s["duration"] * 1e3 for s in tr["spans"]
                  if s["name"] in STAGES and s["duration"] is not None}
        if root is not None and len(stages) == len(STAGES):
            sums.append(sum(stages.values()))
            errors.append(abs(sums[-1] - root["attributes"]["first_chunk_ms"]))
    phases: dict = {}
    for s in in_window(ctx, "engine.intake"):
        p = s["attributes"].get("phase", "?")
        phases[p] = phases.get(p, 0) + 1
    steps = in_window(ctx, "engine.step")
    starts = [s["start"] for tr in (ctx["spans"] or [])
              if tr.get("root_span_id") for s in tr["spans"]
              if s["span_id"] == tr["root_span_id"]]
    out = {
        "instrumented": instrumented(ctx),
        "split_p50_ms": split,
        "split_sum_ms": (sum(split.values())
                         if None not in split.values() else None),
        "first_chunk_p50_ms": stats.percentile(first, 50),
        "first_chunk_mean_ms": _mean(first),
        "split_mean_ms": {
            name: _mean([s["duration"] * 1e3 for s in in_window(ctx, name)])
            for name in STAGES},
        "closure": {"requests": len(sums),
                    "sum_p50_ms": stats.percentile(sums, 50),
                    "max_error_ms": max(errors, default=None)},
        "client_ttft_p50_ms": ctx["window"].get("ttft_p50_ms"),
        "emit_parts_p50_ms": {
            k: median_ms(ctx, "api.emit", k)
            for k in ("held_ms", "hop_ms", "write_ms")},
        "prefill_p50": {k: median_ms(ctx, "engine.prefill", k)
                        for k in ("behind_ms", "steps")},
        "intake_phase": phases,
        "between_steps_p50_ms": {
            k: stats.percentile(
                [s["attributes"][k] for s in steps
                 if k in s["attributes"]], 50)
            for k in ("since_prev_ms", "slack_ms", "idle_ms", "intake_ms")},
        "flush_ms": {
            "p50": median_ms(ctx, "frontend.flush"),
            "p95": stats.percentile(
                [s["duration"] * 1e3
                 for s in in_window(ctx, "frontend.flush")], 95)},
        "gc": [{"at_s": s["start"] - lo, "ms": s["duration"] * 1e3,
                "generation": s["attributes"].get("generation"),
                "thread": s["attributes"].get("thread")}
               for s in in_window(ctx, "gc.collect")],
        "xla_compiles": [dict(s["attributes"], at_s=s["start"] - lo)
                         for s in in_window(ctx, "xla.compile")],
        "output_gap": output_gap(ctx),
        "profile": profile_threads(ctx),
        # the ring: how far back the fetched traces reach (run.py keeps
        # the traces alone, so ``evicted`` itself is in the server's log)
        "ring": {"traces": len(ctx["spans"] or []),
                 "oldest_root_after_t0_s": (min(starts) - lo
                                            if starts else None)},
    }
    ctx["_host_timeline"] = out
    gap = out["output_gap"]
    print(f"[host timeline] first token, medians in ms: {split}; sum "
          f"{out['split_sum_ms']}, first_chunk_ms {out['first_chunk_p50_ms']},"
          f" client ttft {out['client_ttft_p50_ms']}; means "
          f"{out['split_mean_ms']} against {out['first_chunk_mean_ms']}; "
          f"each request's own sum {out['closure']}", flush=True)
    print(f"[host timeline] emit parts {out['emit_parts_p50_ms']}; prefill "
          f"{out['prefill_p50']}; intake by phase {phases}; between steps "
          f"{out['between_steps_p50_ms']}; flush {out['flush_ms']}",
          flush=True)
    print(f"[host timeline] {len(out['gc'])} collections in the window, "
          f"{len(out['xla_compiles'])} backend compiles; longest output "
          f"gap {gap and round(gap['ms'], 2)} ms; profile {out['profile']}; "
          f"ring {out['ring']}", flush=True)
    out_dir = os.path.join(
        _xplane.REPO, "chiprun_out", "benchmark", ctx["cell"].name)
    if os.path.isdir(out_dir):
        with open(os.path.join(out_dir, "host_timeline.json"), "w") as f:
            json.dump(out, f, indent=1)
    return out
