"""End-to-end request tracing (ISSUE 5 tentpole).

A zero-hard-dependency tracer answering "where did this request's
800 ms go?": W3C-style 128-bit trace ids, parent/child spans, and a
bounded ring buffer of completed traces, threaded through every layer a
request crosses (API server → engine → scheduler → executor → RPC →
worker).  Upstream vLLM ships per-request OpenTelemetry traces next to
its Prometheus metrics for the same reason (Kwon et al. 2023); Llumnix
(Sun et al. 2024) shows per-request latency telemetry is the raw input
any scheduling/migration layer needs.

Design rules:

- **No-op fast path.**  With tracing disabled (the default), ``span()``
  returns a module-level singleton and ``record_span``/``event`` return
  immediately — the hot loop allocates nothing.
- **No hard deps.**  Pure stdlib (jax only while a profile captures).
- **Spans cross the RPC boundary.**  ``distributed/rpc.py`` embeds the
  current trace context in apply frames and ships the worker-side spans
  back inside the reply frame; ``adopt()`` merges them into the local
  trace, shifting timestamps by the per-host clock offset the executor
  estimates from heartbeat RTTs.
- **Wall clock only for span starts.**  Durations come from
  ``time.monotonic()`` deltas, so an NTP step can skew where a trace
  sits on the absolute timeline but never the shape of the spans.
- **Metrics feed from span data.**  A single sink (EngineMetrics) sees
  every completed local span, so the per-stage Prometheus histograms
  and the traces can never disagree.

- **Spans on the profiler's clock.**  While ``POST /debug/profile``
  captures (it sets ``Tracer.profiling``), entering a span also enters
  a ``jax.profiler.TraceAnnotation`` of the same name, so the span is
  in the trace's host plane beside the device plane: the one clock on
  which a device gap can be laid against what the host was doing.
- **Causes that belong to no request.**  While tracing is on, a
  ``gc.callbacks`` hook records every collection of generation 1 and 2
  as a ``gc.collect`` span (a stop-the-world pause under the GIL), and
  the worker's compile listener records every XLA backend compile as an
  ``xla.compile`` span.  Spans with no trace to go to are kept in one
  bounded *process trace*, served first in ``/debug/traces``.

Config: ``ObservabilityConfig.enable_tracing`` (CLI ``--enable-tracing``)
or ``VDT_TRACING=1``; ring size via ``VDT_TRACE_RING_SIZE``.
"""

from __future__ import annotations

import contextvars
import gc
import json
import os
import threading
import time
from collections import deque
from typing import Any, Callable

from vllm_distributed_tpu.logger import init_logger

logger = init_logger(__name__)

# (trace_id, span_id) — the wire-format trace context.  Plain tuples so
# they pickle into RPC frames and dataclasses without ceremony.
TraceContext = tuple  # tuple[str, str]

# Active span context of the current thread/task; read by the RPC layer
# when building apply frames, set by Span.__enter__.
_current: contextvars.ContextVar[TraceContext | None] = contextvars.ContextVar(
    "vdt_trace_ctx", default=None
)


def current_ctx() -> TraceContext | None:
    return _current.get()


def new_trace_id() -> str:
    return os.urandom(16).hex()  # 128-bit, W3C trace-id sized


def new_span_id() -> str:
    return os.urandom(8).hex()


class _NoopSpan:
    """Shared do-nothing span: the disabled-tracing fast path returns
    this singleton, so opening a span allocates nothing."""

    __slots__ = ()
    ctx = None

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_attribute(self, key: str, value: Any) -> None:
        pass

    def end(self) -> None:
        pass

    def to_wire(self) -> dict:
        return {}


NOOP_SPAN = _NoopSpan()


class Span:
    """One timed operation.  Use as a context manager (``with
    tracer.span(...)``); the code-hygiene suite bans orphanable manual
    ``start_span`` calls outside a ``with``."""

    __slots__ = (
        "name",
        "trace_id",
        "span_id",
        "parent_id",
        "start",
        "duration",
        "host",
        "attributes",
        "_tracer",
        "_t0",
        "_token",
        "_record",
        "_annotation",
    )

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        trace_id: str,
        parent_id: str | None,
        host: str,
        attributes: dict,
        record: bool,
    ) -> None:
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_id = parent_id
        self.host = host
        self.attributes = attributes
        self.start = time.time()
        self.duration: float | None = None
        self._tracer = tracer
        self._t0 = time.monotonic()
        self._token: contextvars.Token | None = None
        self._record = record
        self._annotation = None

    @property
    def ctx(self) -> TraceContext:
        return (self.trace_id, self.span_id)

    @property
    def start_mono(self) -> float:
        """``start`` on the monotonic clock, for intervals that begin
        where this span began."""
        return self._t0

    def set_attribute(self, key: str, value: Any) -> None:
        self.attributes[key] = value

    def __enter__(self) -> "Span":
        self._token = _current.set(self.ctx)
        if self._tracer.profiling:
            self._annotation = _annotate(
                self.name, self.attributes.get("step_id")
            )
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.attributes["error"] = exc_type.__name__
        self.end()
        return False

    def end(self) -> None:
        if self.duration is not None:
            return  # already ended
        self.duration = time.monotonic() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        self._tracer._close(self)

    def to_wire(self) -> dict:
        """Serializable form, shipped inside RPC reply frames and served
        from /debug/traces."""
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "host": self.host,
            "start": self.start,
            "duration": self.duration,
            "attributes": self.attributes,
        }


def _annotate(name: str, step_id):
    """Enter a ``jax.profiler.TraceAnnotation`` (only ever reached
    while a capture runs, so jax is imported already)."""
    from jax.profiler import TraceAnnotation

    annotation = (
        TraceAnnotation(name)
        if step_id is None
        else TraceAnnotation(name, step_id=step_id)
    )
    annotation.__enter__()
    return annotation


class _Trace:
    """All spans of one trace, accumulated until the root span closes."""

    __slots__ = ("trace_id", "spans", "root_span_id", "done")

    def __init__(self, trace_id: str) -> None:
        self.trace_id = trace_id
        self.spans: list[dict] = []
        self.root_span_id: str | None = None
        self.done = False

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "root_span_id": self.root_span_id,
            "complete": self.done,
            "spans": list(self.spans),
        }


class Tracer:
    """Process-global tracer.  Thread-safe: spans are opened/closed on
    the event loop, the engine thread, executor loop and gather pool."""

    def __init__(self) -> None:
        self.enabled = False
        # True while /debug/profile captures: spans then also enter a
        # TraceAnnotation (see Span.__enter__).
        self.profiling = False
        self.host = "driver"
        self._lock = threading.Lock()
        self._active: dict[str, _Trace] = {}
        self._ring: deque[_Trace] = deque(maxlen=256)
        self._finished: dict[str, _Trace] = {}
        self._open_spans = 0
        # host -> (wall-clock offset vs this process, rtt of the sample)
        self._clock_offsets: dict[str, tuple[float, float]] = {}
        self._metrics_sink: Callable[[str, float], None] | None = None
        # Traces the ring has dropped since start (or reset()).
        self._evicted = 0
        # The process trace: spans that belong to no request and no
        # step (a collection, a compile outside any span).  Bounded
        # like the ring, and never in it.
        self._process_trace_id = new_trace_id()
        self._loose: deque[dict] = deque(maxlen=4096)
        # The collection under way: (wall start, monotonic start, its
        # annotation while profiling).  One at a time, under the GIL.
        self._gc_open: tuple | None = None
        # generation -> [collections, seconds paused] since the hook
        # went in; ``/metrics`` reads it at scrape time (the hook itself
        # touches no lock: a collection can start inside any of them).
        self.gc_totals: dict[int, list] = {}

    # ---- configuration ----
    def configure(
        self,
        enabled: bool,
        ring_size: int | None = None,
        host: str | None = None,
    ) -> "Tracer":
        self.enabled = enabled
        if host is not None:
            self.host = host
        # The collection hook: the process's tracer alone, and only
        # while tracing is on.
        hooked = self._on_gc in gc.callbacks
        if enabled and not hooked and self is _tracer:
            gc.callbacks.append(self._on_gc)
        elif hooked and not enabled:
            gc.callbacks.remove(self._on_gc)
            self._gc_open = None
        if ring_size is not None and ring_size != self._ring.maxlen:
            with self._lock:
                self._evicted += max(len(self._ring) - max(1, ring_size), 0)
                self._ring = deque(self._ring, maxlen=max(1, ring_size))
                # Shrinking evicts the oldest traces from the deque; the
                # id index must follow or get_trace() keeps resurrecting
                # (and retaining) traces snapshot() no longer lists.
                self._finished = {t.trace_id: t for t in self._ring}
        return self

    def set_metrics_sink(
        self, sink: Callable[[str, float], None] | None
    ) -> None:
        """Single slot (not a list): the engine re-registers the same
        EngineMetrics across supervisor rebuilds without stacking."""
        self._metrics_sink = sink

    def clear_metrics_sink(self, sink: Callable[[str, float], None]) -> None:
        """Detach ``sink`` if it is the installed one (engine shutdown
        must not keep its EngineMetrics alive through the global tracer);
        a newer engine's sink is left in place."""
        if self._metrics_sink == sink:
            self._metrics_sink = None

    def reset(self) -> None:
        with self._lock:
            self._active.clear()
            self._finished.clear()
            self._ring.clear()
            self._open_spans = 0
            self._clock_offsets.clear()
            self._loose.clear()
            self._evicted = 0

    # ---- span creation ----
    def span(
        self,
        name: str,
        parent: TraceContext | None = None,
        trace_root: bool = False,
        record: bool = True,
        **attributes: Any,
    ):
        """Open a span as a context manager.  ``parent`` is an explicit
        (trace_id, span_id); None inherits the calling context.  A span
        with neither a parent nor ``trace_root`` is dropped (no-op) —
        untraced work stays untraced.  ``record=False`` spans are not
        stored locally (the worker side ships them back to the driver
        instead of accumulating orphan traces)."""
        if not self.enabled:
            return NOOP_SPAN
        if parent is None and not trace_root:
            parent = _current.get()
            if parent is None:
                return NOOP_SPAN
        trace_id = new_trace_id() if parent is None else parent[0]
        parent_id = None if parent is None else parent[1]
        span = Span(
            self, name, trace_id, parent_id, self.host, attributes, record
        )
        with self._lock:
            self._open_spans += 1
        return span

    def annotation(self, name: str):
        """A ``TraceAnnotation`` of ``name`` while a profile captures,
        else the no-op span: for stretches that are counted, not kept
        as spans (the engine thread's waits, the event loop's writes),
        yet belong in the profile's host plane."""
        if not self.profiling:
            return NOOP_SPAN
        from jax.profiler import TraceAnnotation

        return TraceAnnotation(name)

    # Manual open; must be paired with .end() under try/finally.  The
    # code-hygiene AST check bans calls outside a `with` so spans cannot
    # leak open — prefer span().
    start_span = span

    def record_span(
        self,
        name: str,
        start: float,
        duration: float,
        parent: TraceContext | None = None,
        **attributes: Any,
    ) -> None:
        """Record an already-measured interval (start: wall clock,
        duration: monotonic delta).  Never 'open', so it cannot leak.
        Feeds the metrics sink even without a trace context, so stage
        histograms populate for untraced engine-level callers too."""
        if not self.enabled:
            return
        self._sink(name, duration)
        if parent is None:
            return
        self._store(self._wire(name, start, duration, parent, attributes))

    def record_loose(
        self, name: str, start: float, duration: float, **attributes: Any
    ) -> None:
        """Record a measured interval that belongs to no trace (a
        collection, a compile outside any span) in the process trace."""
        if not self.enabled:
            return
        self._loose.append(
            self._wire(
                name, start, duration, (self._process_trace_id, None),
                attributes,
            )
        )

    def _wire(
        self,
        name: str,
        start: float,
        duration: float | None,
        parent: TraceContext,
        attributes: dict,
    ) -> dict:
        return {
            "name": name,
            "trace_id": parent[0],
            "span_id": new_span_id(),
            "parent_id": parent[1],
            "host": self.host,
            "start": start,
            "duration": duration,
            "attributes": attributes,
        }

    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook (installed while tracing is on): a
        collection of generation 1 or 2 stops every thread for its
        length, so it is a ``gc.collect`` span in the process trace, an
        annotation on the collecting thread while a profile captures,
        and a count and a pause by generation for ``/metrics``.  It takes
        no lock: the collecting thread may hold any of them."""
        generation = info["generation"]
        if generation < 1:
            return
        if phase == "start":
            annotation = self.annotation("gc.collect")
            annotation.__enter__()
            self._gc_open = (time.time(), time.monotonic(), annotation)
            return
        opened, self._gc_open = self._gc_open, None
        if opened is None:
            return
        start, t0, annotation = opened
        duration = time.monotonic() - t0
        annotation.__exit__(None, None, None)
        self.record_loose(
            "gc.collect",
            start,
            duration,
            generation=generation,
            collected=info.get("collected", 0),
            # whose allocation started it: a finalizer that lets the GIL
            # go stops that thread alone for the rest
            thread=threading.current_thread().name,
        )
        total = self.gc_totals.setdefault(generation, [0, 0.0])
        total[0] += 1
        total[1] += duration

    def event(
        self, ctx: TraceContext | None, name: str, **attributes: Any
    ) -> None:
        """Instant event (zero-duration span) on an existing trace."""
        if not self.enabled or ctx is None:
            return
        self._store(self.stamp(name, ctx, **attributes))

    def stamp(
        self, name: str, parent: TraceContext, **attributes: Any
    ) -> dict:
        """Build (without storing) an instant-span dict — used for the
        worker-side reply marker shipped inside the RPC result frame."""
        return self._wire(name, time.time(), None, parent, attributes)

    # ---- cross-host ----
    def set_clock_offset(self, host: str, offset: float, rtt: float) -> None:
        """Record one (remote wall − local wall) sample.  Low-RTT samples
        are the trustworthy ones; a stored sample slowly decays so a
        fresh estimate eventually wins even if its RTT is worse."""
        with self._lock:
            cur = self._clock_offsets.get(host)
            if cur is None or rtt <= cur[1] * 1.25:
                self._clock_offsets[host] = (offset, rtt)
            else:
                self._clock_offsets[host] = (cur[0], cur[1] * 1.05)

    def clock_offset(self, host: str) -> float:
        with self._lock:
            cur = self._clock_offsets.get(host)
        return 0.0 if cur is None else cur[0]

    def adopt(self, spans: list[dict]) -> None:
        """Merge spans recorded on another host (shipped back inside an
        RPC reply) into their trace, mapping remote wall clocks onto the
        local timeline via the estimated per-host offset."""
        if not self.enabled:
            return
        for span in spans:
            if not isinstance(span, dict) or "trace_id" not in span:
                continue
            offset = self.clock_offset(span.get("host", ""))
            if offset:
                span = dict(span)
                span["start"] = span["start"] - offset
            self._store(span)

    # ---- storage ----
    def _close(self, span: Span) -> None:
        with self._lock:
            self._open_spans = max(self._open_spans - 1, 0)
        self._sink(span.name, span.duration or 0.0)
        if not span._record:
            return
        wire = span.to_wire()
        with self._lock:
            trace = self._trace_for(span.trace_id)
            trace.spans.append(wire)
            if span.parent_id is None:
                trace.root_span_id = span.span_id
                self._finalize(trace)

    def _store(self, wire: dict) -> None:
        with self._lock:
            self._trace_for(wire["trace_id"]).spans.append(wire)

    def _trace_for(self, trace_id: str) -> _Trace:
        """Lock held.  Finished traces still accept late spans (a
        pipelined gather can outlive the request's root span)."""
        trace = self._finished.get(trace_id)
        if trace is not None:
            return trace
        trace = self._active.get(trace_id)
        if trace is None:
            trace = _Trace(trace_id)
            self._active[trace_id] = trace
            # Bound the active set: a trace whose root never closes
            # (engine-level caller, crashed request) must not leak.
            while len(self._active) > max(self._ring.maxlen or 1, 64):
                _, oldest = next(iter(self._active.items()))
                del self._active[oldest.trace_id]
                self._finalize(oldest)
        return trace

    def _finalize(self, trace: _Trace) -> None:
        """Lock held: move a trace to the completed ring.  Idempotent:
        a trace force-evicted from the active set (overflow) whose root
        span closes later must not enter the ring twice."""
        if self._finished.get(trace.trace_id) is trace:
            trace.done = trace.done or trace.root_span_id is not None
            return
        trace.done = trace.root_span_id is not None
        self._active.pop(trace.trace_id, None)
        if len(self._ring) == self._ring.maxlen:
            evicted = self._ring[0]
            self._finished.pop(evicted.trace_id, None)
            self._evicted += 1
        self._ring.append(trace)
        self._finished[trace.trace_id] = trace

    def _sink(self, name: str, duration: float) -> None:
        sink = self._metrics_sink
        if sink is not None:
            try:
                sink(name, duration)
            except Exception as e:  # noqa: BLE001 — telemetry only
                logger.debug("metrics sink failed for %s: %s", name, e)

    # ---- introspection ----
    @property
    def num_open_spans(self) -> int:
        return self._open_spans

    def snapshot(
        self, limit: int | None = None, process: bool = False
    ) -> list[dict]:
        """Recent completed traces, oldest first; with ``process``,
        the process trace before them (where it holds anything)."""
        with self._lock:
            traces = list(self._ring)
        if limit is not None:
            traces = traces[-limit:]
        out = [t.to_dict() for t in traces]
        loose = list(self._loose) if process else None
        if loose:
            out.insert(
                0,
                {
                    "trace_id": self._process_trace_id,
                    "root_span_id": None,
                    "complete": False,
                    "spans": loose,
                },
            )
        return out

    def ring_state(self) -> dict:
        """What the ring has lost: traces dropped since start, and the
        start of the oldest span it still holds (None while empty) — a
        reader of a window that began before it sees a shortened one."""
        with self._lock:
            evicted = self._evicted
            oldest = list(self._ring[0].spans) if self._ring else []
        return {
            "evicted": evicted,
            "oldest_start": min((s["start"] for s in oldest), default=None),
        }

    def get_trace(self, trace_id: str) -> dict | None:
        with self._lock:
            trace = self._finished.get(trace_id) or self._active.get(
                trace_id
            )
            return None if trace is None else trace.to_dict()

    def to_chrome(self, limit: int | None = None) -> dict:
        """Chrome trace-event format (loads directly in Perfetto /
        chrome://tracing): complete events ('X') for spans, instant
        events ('i') for zero-duration markers, with process-name
        metadata mapping pids to hosts."""
        events: list[dict] = []
        hosts: dict[str, int] = {}
        for trace in self.snapshot(limit, process=True):
            tid = int(trace["trace_id"][:8], 16) & 0x7FFFFFFF
            for span in trace["spans"]:
                pid = hosts.setdefault(span["host"], len(hosts) + 1)
                args = dict(span["attributes"])
                args.update(
                    trace_id=span["trace_id"],
                    span_id=span["span_id"],
                    parent_id=span["parent_id"],
                )
                event = {
                    "name": span["name"],
                    "cat": "vdt",
                    "pid": pid,
                    "tid": tid,
                    "ts": span["start"] * 1e6,
                    "args": args,
                }
                if span["duration"] is None:
                    event.update(ph="i", s="t")
                else:
                    event.update(ph="X", dur=span["duration"] * 1e6)
                events.append(event)
        for host, pid in hosts.items():
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": 0,
                    "args": {"name": host},
                }
            )
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def to_chrome_json(self, limit: int | None = None) -> str:
        return json.dumps(self.to_chrome(limit))


_tracer = Tracer()


def get_tracer() -> Tracer:
    return _tracer


def configure_from_env(host: str | None = None) -> Tracer:
    """Configure the global tracer from VDT_TRACING/VDT_TRACE_RING_SIZE
    (worker agents call this after the driver replicates its env)."""
    from vllm_distributed_tpu import envs

    return _tracer.configure(
        enabled=envs.VDT_TRACING,
        ring_size=envs.VDT_TRACE_RING_SIZE,
        host=host,
    )
