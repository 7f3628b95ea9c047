"""The traced run's profile, read once for all the readers that need
names: which step program, which kernel and which part of a layer each
device operation belongs to, and what the host's threads were doing in
every gap between operations.

It reads what the program writes into a ``jax.profiler`` trace since PR
26 and nothing else of the program: XLA modules called
``jit_vdt_<kind>_<sizes>``, Pallas kernels called ``vdt_<kernel>``, an
``op_name`` on every operation (in the programs' HloProtos, which the
trace carries) whose path holds one of ``SCOPES`` (``jax.named_scope``),
and on the host's threads one annotation per span
of the step loop (``PHASES``).  A program without them (the parent of PR
26) gives a trace in which nothing has a name: every reader over this
file then returns ``None``.

Two stages, as ``trace_reduce``: ``load_events`` reads the ``.xplane.pb``
into plain lists, ``reduce`` does the arithmetic, so that the arithmetic
is tested on a recorded file (``benchmark/testdata``).  Times are
nanoseconds on the trace's own clock, which the device plane and the
host plane share: the only clock a gap can be attributed on.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import time

from benchmark import trace_reduce
from benchmark.trace_reduce import find_xplane, nest, op_name, union_ns

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# ``vllm_distributed_tpu.models.common.SCOPES``, written out: the
# yardstick does not import the program (a test holds the two together).
SCOPES = ("embed", "attn_qkv", "kv_write", "attn", "attn_out",
          "moe_router", "moe_experts", "mlp", "lm_head", "sampler")
# The spans of the step loop, by where an idle device is filed while
# the engine's thread is inside one.
PHASES = {"scheduler.schedule": "schedule", "runner.prepare": "runner",
          "runner.dispatch": "runner", "runner.fetch": "fetch",
          "engine.process": "process", "engine.step": None,
          "engine.wait": None}
IDLE_KINDS = ("schedule", "runner", "fetch", "process", "other")
KERNEL = re.compile(r"^vdt_[a-z0-9_]+?(?=\.\d+$|$)")
PROGRAM = re.compile(r"^jit_vdt_(decode|step|spec)_")


# ---- stage 1: the file --------------------------------------------------
# A device event is an HLO instruction's text without its metadata, and
# ``ProfileData`` shows no event metadata.  The ``op_name`` (the scope's
# carrier) is in the trace all the same: the plane ``/host:metadata``
# holds the optimised HloProto of every program that ran, which is what
# XProf's own op profile reads.  ``ProfileData`` does not expose it, so
# the few fields needed are walked in the protobuf wire format: XSpace
# .planes=1; XPlane .name=2 .event_metadata=4 (map: value=2)
# .stat_metadata=5; XEventMetadata .name=2 .stats=5; XStat .metadata_id=1
# .bytes_value=6; HloProto .hlo_module=1; HloModuleProto .computations=3;
# HloComputationProto .instructions=2; HloInstructionProto .name=1
# .metadata=7; OpMetadata .op_name=2.  A plane's lines (field 3, nearly
# all of the file) are stepped over by their length.
def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _first(buf, number: int, default=None):
    return next((v for f, v in _fields(buf) if f == number), default)


def hlo_op_names(xplane_path: str) -> dict:
    """{program as the trace names it (``jit_vdt_step_…(<id>)``):
    {instruction: op_name}} from the HloProtos of ``/host:metadata``;
    empty where the trace holds none."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for number, plane in _fields(space):
        if number != 1 or bytes(_first(plane, 2, b"")) != b"/host:metadata":
            continue
        for number, entry in _fields(plane):
            if number != 4:
                continue
            meta = _first(entry, 2)
            if meta is None:
                continue
            program = bytes(_first(meta, 2, b"")).decode()
            names = out.setdefault(program, {})
            for number, stat in _fields(meta):
                proto = _first(stat, 6) if number == 5 else None
                module = _first(proto, 1) if proto is not None else None
                if module is None:
                    continue
                for number, comp in _fields(module):
                    if number != 3:
                        continue
                    for number, inst in _fields(comp):
                        if number != 2:
                            continue
                        op_meta = _first(inst, 7)
                        path = _first(op_meta, 2) if op_meta is not None else None
                        if path is not None:
                            names[bytes(_first(inst, 1)).decode()] = (
                                bytes(path).decode())
    return out


def load_events(xplane_path: str) -> dict:
    """{"devices": {plane: {"modules": [[name, start, dur]], "ops":
    [[name, start, dur, op_name path]]}}, "host": [[[span, start, dur,
    step_id]] per thread that ran any span of ``PHASES``], "lines":
    {plane: [line names]}}.  An operation's path is looked up under the
    program that was running when it started."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    op_names = hlo_op_names(xplane_path)
    devices: dict = {}
    host: list = []
    listing: dict = {}
    for plane in data.planes:
        listing[plane.name] = [line.name for line in plane.lines]
        if re.match(r"/device:(TPU|GPU):\d+$", plane.name):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == trace_reduce.MODULES_LINE:
                    mods = sorted(
                        (int(ev.start_ns), int(ev.duration_ns), ev.name)
                        for ev in line.events)
                elif line.name == trace_reduce.OPS_LINE:
                    ops = [(op_name(ev.name), int(ev.start_ns),
                            int(ev.duration_ns)) for ev in line.events]
            starts = [m[0] for m in mods]
            found = []
            for name, start, dur in ops:
                i = bisect.bisect_right(starts, start) - 1
                table = op_names.get(mods[i][2], {}) if i >= 0 else {}
                found.append([name, start, dur, table.get(name, "")])
            devices[plane.name] = {
                "modules": [[trace_reduce.module_base(n), s, d]
                            for s, d, n in mods],
                "ops": found}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    if ev.name in PHASES:
                        step = dict(ev.stats).get("step_id")
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns), step])
                if spans:
                    host.append(spans)
    return {"devices": devices, "host": host, "lines": listing}


# ---- stage 2: the arithmetic --------------------------------------------
def scope_of(path: str) -> str | None:
    """The innermost of ``SCOPES`` on an ``op_name`` path."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


def kernel_of(name: str) -> str | None:
    m = KERNEL.match(name)
    return m.group(0) if m else None


def program_kind(module: str) -> str:
    m = PROGRAM.match(module)
    return m.group(1) if m else "other"


def _merged(intervals: list) -> list:
    """Disjoint sorted (start, end) covering the same nanoseconds."""
    if not intervals:
        return []
    _, gaps = union_ns(intervals)
    edges = [min(s for s, _ in intervals)]
    for s, e in gaps:
        edges += [s, e]
    edges.append(max(e for _, e in intervals))
    return list(zip(edges[::2], edges[1::2]))


def _minus(a: list, b: list) -> list:
    """What of the disjoint sorted intervals ``a`` no interval of the
    disjoint sorted ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def _overlap(a: list, b: list) -> int:
    return sum(e - s for s, e in a) - sum(e - s for s, e in _minus(a, b))


def _threads(host: list) -> tuple[list, list]:
    """(the engine's thread, every other thread): the engine's is the
    one that ran ``engine.step``."""
    engine = [t for t in host if any(s[0] == "engine.step" for s in t)]
    rest = [t for t in host if t not in engine]
    return [s for t in engine for s in t], [s for t in rest for s in t]


def _program() -> dict:
    return {"executions": 0, "seconds": 0.0, "scopes": {}, "kernels": {},
            "names": {}}


def _add(table: dict, key: str, ns: int) -> None:
    table[key] = table.get(key, 0.0) + ns / 1e9


def reduce(events: dict, top: int = 10) -> dict | None:
    """``None`` for a trace without a device plane.  Else ``busy_s`` and
    ``window_s`` as ``trace_reduce`` has them; ``programs`` (by kind of
    step program: executions, their seconds, their names, and self
    seconds by scope and by kernel, each nanosecond counted once);
    ``unattributed`` (self seconds of the operations that carry neither
    a kernel's name nor a scope, by kind of operation); ``idle_s`` by
    ``IDLE_KINDS``, which add up to ``window_s - busy_s``; ``gaps`` (the
    longest, each with the host's phases in it, the program before and
    the program after); ``host`` (annotations found, by span)."""
    planes = [p for p in events["devices"].values() if p["ops"]]
    if not planes:
        return None
    plane = planes[0]  # a cell on four chips reads its first
    ops = plane["ops"]
    mods = sorted(plane["modules"], key=lambda m: m[1])
    starts = [m[1] for m in mods]

    def module_at(t: int) -> int | None:
        i = bisect.bisect_right(starts, t) - 1
        return i if i >= 0 and t < mods[i][1] + mods[i][2] else None

    programs: dict = {}
    for name, _, dur in mods:
        p = programs.setdefault(program_kind(name), _program())
        p["executions"] += 1
        p["seconds"] += dur / 1e9
        p["names"][name] = p["names"].get(name, 0) + 1
    unattributed: dict = {}
    for i, start, _, _, self_ns in nest(
            [(i, o[1], o[2]) for i, o in enumerate(ops)]):
        self_ns = max(self_ns, 0)
        m = module_at(start)
        kind = program_kind(mods[m][0]) if m is not None else "other"
        p = programs.get(kind) or programs.setdefault(kind, _program())
        kernel, scope = kernel_of(ops[i][0]), scope_of(ops[i][3])
        if kernel:
            _add(p["kernels"], kernel, self_ns)
        if scope:
            _add(p["scopes"], scope, self_ns)
        if not kernel and not scope:
            _add(unattributed, trace_reduce.op_kind(ops[i][0]), self_ns)

    ivals = [(o[1], o[1] + o[2]) for o in ops]
    busy = _merged(ivals)
    busy_ns = sum(e - s for s, e in busy)
    lo, hi = busy[0][0], busy[-1][1]
    gaps = _minus([(lo, hi)], busy)

    engine, others = _threads(events["host"])

    def spans_of(thread: list, kind: str) -> list:
        return _merged([(s, s + d) for name, s, d, _ in thread
                        if PHASES.get(name) == kind])

    # Where the engine's thread was, phase by phase (they do not
    # overlap: one thread, siblings under engine.step); a fetch on
    # another thread (the resolver's, for a pipelined scan) counts only
    # where the engine's thread was in no phase of its own.
    on_engine = {k: spans_of(engine, k)
                 for k in ("schedule", "runner", "process", "fetch")}
    covered = _merged([iv for v in on_engine.values() for iv in v])
    fetch_elsewhere = _minus(spans_of(others, "fetch"), covered)
    idle = {k: _overlap(gaps, on_engine[k])
            for k in ("schedule", "runner", "process")}
    idle["fetch"] = (_overlap(gaps, on_engine["fetch"])
                     + _overlap(gaps, fetch_elsewhere))
    idle["other"] = sum(e - s for s, e in gaps) - sum(idle.values())

    def phases_in(s: int, e: int) -> dict:
        out = {}
        for name, a, d, _ in engine + others:
            ov = min(e, a + d) - max(s, a)
            if ov > 0 and PHASES.get(name):
                out[name] = out.get(name, 0.0) + ov / 1e6
        return {k: round(v, 3) for k, v in sorted(
            out.items(), key=lambda kv: -kv[1])}

    def program_near(t: int, before: bool) -> str:
        i = bisect.bisect_right(starts, t) - (1 if before else 0)
        if before:
            return mods[i][0] if i >= 0 else "start"
        i = bisect.bisect_left(starts, t - 1000)
        return mods[i][0] if i < len(mods) else "end"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "programs": programs,
        "unattributed": dict(sorted(
            unattributed.items(), key=lambda kv: -kv[1])),
        "idle_s": {k: idle[k] / 1e9 for k in IDLE_KINDS},
        "gaps": [{"ms": (e - s) / 1e6, "at_ms": (s - lo) / 1e6,
                  "host": phases_in(s, e),
                  "after": program_near(s, True),
                  "before": program_near(e, False)} for s, e in longest],
        "host": host_annotations(events),
    }


def host_annotations(events: dict) -> dict:
    """Annotations of the step loop's spans found in the host plane, by
    span name (what a trace without a device plane still has)."""
    out: dict = {}
    for thread in events["host"]:
        for name, *_ in thread:
            out[name] = out.get(name, 0) + 1
    return out


def sample_events(events: dict, executions: int = 2) -> dict:
    """The events up to the end of the first ``executions`` of each kind
    of step program, names and paths written once (operations are
    [name index, start, duration, path index]): small enough to look at
    by hand and to keep as a test's recorded trace (``expand`` undoes
    it)."""
    end = 0
    for found in events["devices"].values():
        seen: dict = {}
        for name, s, d in sorted(found["modules"], key=lambda m: m[1]):
            kind = program_kind(name)
            if kind != "other" and seen.get(kind, 0) < executions:
                seen[kind] = seen.get(kind, 0) + 1
                end = max(end, s + d)
    kept = {plane: [o for o in found["ops"] if o[1] < end]
            for plane, found in events["devices"].items()}
    names = sorted({o[0] for ops in kept.values() for o in ops})
    paths = sorted({o[3] for ops in kept.values() for o in ops})
    name_at = {n: i for i, n in enumerate(names)}
    path_at = {p: i for i, p in enumerate(paths)}
    return {
        "lines": events["lines"], "names": names, "paths": paths,
        "devices": {plane: {
            "modules": [m for m in found["modules"] if m[1] < end],
            "ops": [[name_at[n], s, d, path_at[p]]
                    for n, s, d, p in kept[plane]]}
            for plane, found in events["devices"].items()},
        "host": [[s for s in t if s[1] < end] for t in events["host"]]}


def expand(sample: dict) -> dict:
    """The events of a ``sample_events`` file, as ``load_events`` gives
    them."""
    names, paths = sample["names"], sample["paths"]
    return {
        "lines": sample["lines"], "host": sample["host"],
        "devices": {plane: {
            "modules": found["modules"],
            "ops": [[names[n], s, d, paths[p]]
                    for n, s, d, p in found["ops"]]}
            for plane, found in sample["devices"].items()}}


def say_table(named: dict, seconds: float) -> None:
    """The earlier lines of a traced run that the named metrics are
    taken from."""
    def rows(table: dict) -> str:
        return ", ".join(f"{k} {v:.4f}" for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])) or "none"

    print(f"[named trace] read in {seconds:.1f}s: busy "
          f"{named['busy_s']:.3f}s of {named['window_s']:.3f}s; host "
          f"annotations {named['host']}", flush=True)
    for kind, p in sorted(named["programs"].items()):
        print(f"[named trace] {kind}: {p['executions']} executions, "
              f"{p['seconds']:.3f}s, {len(p['names'])} programs; seconds "
              f"by scope: {rows(p['scopes'])}; by kernel: "
              f"{rows(p['kernels'])}", flush=True)
    print(f"[named trace] neither kernel nor scope: "
          f"{rows(named['unattributed'])}", flush=True)
    print(f"[named trace] idle seconds by host phase: "
          f"{rows(named['idle_s'])}", flush=True)
    for g in named["gaps"]:
        print(f"[named trace] gap {g['ms']:.2f} ms at {g['at_ms']:.1f} ms "
              f"after {g['after']} before {g['before']}: host "
              f"{g['host'] or 'in no phase'}", flush=True)


def load(ctx: dict) -> dict | None:
    """The reduced named trace of this run, read at the first call and
    kept in ``ctx``; ``None`` where the run left no profile or the
    profile has no device plane.  The first call prints the table and
    leaves it, with a sample of the events, beside the run's files."""
    if "_named_trace" in ctx:
        return ctx["_named_trace"]
    ctx["_named_trace"] = None
    out_dir = os.path.join(REPO, "chiprun_out", "benchmark", ctx["cell"].name)
    try:
        path = find_xplane(os.path.join(out_dir, "profile"))
    except FileNotFoundError:
        return None
    t0 = time.monotonic()
    events = load_events(path)
    named = reduce(events)
    if named is None:
        print(f"[named trace] no device plane; host annotations "
              f"{host_annotations(events)}", flush=True)
        return None
    say_table(named, time.monotonic() - t0)
    with open(os.path.join(out_dir, "named_trace.json"), "w") as f:
        json.dump(named, f, indent=1)
    with open(os.path.join(out_dir, "named_trace_sample.json"), "w") as f:
        json.dump(sample_events(events), f)
    ctx["_named_trace"] = named
    return named
