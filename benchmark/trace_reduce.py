"""From a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy seconds, the time of each XLA module
(step program), the operations that took most time, the longest idle
gaps.

Two stages, so that the arithmetic can be tested without a profiler:
``load_events`` reads the device planes into plain tuples, ``reduce``
works on those.  A TPU's plane is ``/device:TPU:<n>``; its line ``XLA
Modules`` has one event per executed program and its line ``XLA Ops`` one
per operation.  Times are nanoseconds on the trace's own clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(profile_dir: str) -> str:
    hits = sorted(glob.glob(
        os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {profile_dir}")
    return hits[-1]


def load_events(xplane_path: str) -> dict:
    """{"devices": {plane name: {line name: [(name, start_ns, dur_ns)]}},
    "lines": {plane: [line names]}} for the device planes of a trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices: dict = {}
    listing: dict = {}
    for plane in data.planes:
        listing[plane.name] = [line.name for line in plane.lines]
        if not re.match(r"/device:(TPU|GPU):\d+$", plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines[line.name] = [
                    (op_name(ev.name), int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events
                ]
        devices[plane.name] = lines
    return {"devices": devices, "lines": listing}


def op_name(text: str) -> str:
    """An operation's event carries its whole HLO line (``%fusion.12 =
    bf16[...] fusion(...)``); its name is what stands before `` = ``."""
    return text.split(" = ", 1)[0].lstrip("%")[:120]


def op_kind(name: str) -> str:
    """``while.12`` -> ``while``; ``slice_bitcast-convert_fusion.1`` ->
    ``slice_bitcast-convert_fusion``."""
    return re.sub(r"\.\d+$", "", name)


def union_ns(intervals: list[tuple[int, int]]) -> tuple[int, list]:
    """Total covered nanoseconds of (start, end) intervals, and the gaps
    between the merged intervals as (start, end)."""
    total, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def nest(ops: list) -> list[tuple]:
    """(name, start, dur, depth, self_ns) for each operation event.  A
    ``while`` or a call holds the operations of its body inside its own
    interval, on the same line: ``self_ns`` is an event's duration less
    its direct children's, so that summing it counts each nanosecond of
    device time once."""
    out: list[list] = []
    stack: list[int] = []  # indices into out
    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and s >= out[stack[-1]][1] + out[stack[-1]][2]:
            stack.pop()
        if stack:
            out[stack[-1]][4] -= d
        out.append([name, s, d, len(stack), d])
        stack.append(len(out) - 1)
    return [tuple(o) for o in out]


def module_base(name: str) -> str:
    """``jit__jit_decode_steps(123)`` -> ``jit__jit_decode_steps``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(events: dict, top: int = 10) -> dict:
    """Busy seconds (union of operation intervals, averaged over the
    device planes), the traced stretch, per-module durations and the
    breakdown the result line carries (device time by kind of
    operation, each nanosecond counted once; the longest idle gaps by
    the program that ran next)."""
    devices = events["devices"]
    if not devices:
        raise ValueError(
            f"no device plane in the trace; planes: {list(events['lines'])}")
    busy, spans = [], []
    op_time: dict[str, float] = {}
    modules: dict[str, list[float]] = {}
    executions: list[dict] = []
    gap_rows: list[tuple[str, float]] = []
    for lines in devices.values():
        ops = lines.get(OPS_LINE, [])
        mods = lines.get(MODULES_LINE, [])
        basis = ops or mods
        if not basis:
            continue
        ivals = [(s, s + d) for _, s, d in basis]
        b, gaps = union_ns(ivals)
        busy.append(b / 1e9)
        spans.append((min(s for s, _ in ivals), max(e for _, e in ivals)))
        for name, _, _, _, self_ns in nest(ops):
            kind = op_kind(name)
            op_time[kind] = op_time.get(kind, 0.0) + max(self_ns, 0) / 1e9
        for name, _, d in mods:
            modules.setdefault(module_base(name), []).append(d / 1e9)
        executions += module_executions(mods, ops)
        starts = sorted((s, module_base(n)) for n, s, _ in mods)
        for gs, ge in gaps:
            nxt = next((n for s, n in starts if s >= ge - 1000), "end")
            gap_rows.append((f"before {nxt}", (ge - gs) / 1e9))
    if not busy:
        raise ValueError("the device planes hold no operation")
    window_s = max(e for _, e in spans) - min(s for s, _ in spans)
    gap_rows.sort(key=lambda r: -r[1])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s / 1e9,
        "modules": {
            k: {"n": len(v), "total_s": sum(v),
                "median_ms": sorted(v)[len(v) // 2] * 1e3}
            for k, v in modules.items()
        },
        "executions": executions,
        "breakdown": {
            "device_ops": [
                [k, v] for k, v in sorted(
                    op_time.items(), key=lambda kv: -kv[1])[:top]
            ],
            "idle_gaps": [[k, v] for k, v in gap_rows[:top]],
        },
    }


def module_executions(mods: list, ops: list) -> list[dict]:
    """One record per executed program: its name, its seconds, and the
    kinds of operation that ran inside its interval.  Programs have no
    stable names yet (every AOT-loaded step program is ``jit_call``), so
    a reader tells them apart by what they contain: the fused decode
    scan is the one with a ``while``."""
    ops = sorted(ops, key=lambda e: e[1])
    starts = [e[1] for e in ops]
    out = []
    for name, s, d in mods:
        lo = bisect.bisect_left(starts, s)
        hi = bisect.bisect_right(starts, s + d)
        kinds = sorted({op_kind(ops[i][0]) for i in range(lo, hi)})
        out.append({"name": module_base(name), "seconds": d / 1e9,
                    "op_kinds": kinds})
    return out


def reduce_profile(profile_dir: str, sample: int = 12) -> dict:
    """Reduce the newest trace under ``profile_dir``; a sample of its
    events is kept beside the result, small enough to look at by hand
    (and to keep as a test's recorded trace)."""
    events = load_events(find_xplane(profile_dir))
    out = reduce(events)
    out["sample"] = sample_events(events, sample)
    return out


def sample_events(events: dict, modules: int = 12) -> dict:
    """The program executions up to the end of the first ``modules`` of
    them that took a millisecond or more, with their outermost
    operations."""
    devices = {}
    for plane, lines in events["devices"].items():
        mods = lines.get(MODULES_LINE, [])
        big = sorted(m[1] for m in mods if m[2] >= 1_000_000)[:modules]
        end = max((s + d for _, s, d in mods if s in big), default=0)
        devices[plane] = {
            MODULES_LINE: [m for m in mods if m[1] < end],
            OPS_LINE: [
                (n, s, d) for n, s, d, depth, _ in nest(
                    [o for o in lines.get(OPS_LINE, []) if o[1] < end])
                if depth == 0
            ],
        }
    return {"lines": events["lines"], "devices": devices}


def module_median_ms(trace: dict | None, select: dict) -> float | None:
    """Median duration, in ms, of the program executions that ``select``
    picks: ``name`` (substring of the module's name), ``contains_op`` /
    ``lacks_op`` (an operation kind that ran, or did not run, inside
    it), ``min_ms`` (shorter executions are helpers, not steps).
    ``None`` if the trace has none."""
    if trace is None:
        return None
    xs = sorted(
        e["seconds"] for e in trace["executions"]
        if select.get("name", "") in e["name"]
        and ("contains_op" not in select
             or select["contains_op"] in e["op_kinds"])
        and ("lacks_op" not in select
             or select["lacks_op"] not in e["op_kinds"])
        and e["seconds"] * 1e3 >= select.get("min_ms", 0.0)
    )
    if not xs:
        return None
    mid = len(xs) // 2
    med = xs[mid] if len(xs) % 2 else (xs[mid - 1] + xs[mid]) / 2
    return med * 1e3
