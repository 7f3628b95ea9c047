"""Worker-side XLA/device telemetry (ISSUE 12 tentpole, part 2).

PR 11's bucketed spec-decode shapes made compile behavior load-bearing:
a shape bucket that escapes warmup costs a multi-second mid-serve XLA
compile, and nothing surfaced it in steady state — only offline benches
noticed.  ``DeviceTelemetry`` is the worker-local ledger the model
runner writes:

- **compile events**: every first execution of a distinct (kind,
  static shape) jit key, timed, tagged with the triggering bucket kind
  (``prefill``/``decode``/``spec``): a recompile storm shows up as a
  climbing ``vllm:xla_compiles_total`` instead of mystery latency spikes;
- **HBM**: live/limit bytes from the runtime's ``memory_stats`` so
  memory creep is a gauge, not an OOM post-mortem;
- **step roofline**: estimated bytes-touched / step-time over the
  device's peak HBM bandwidth (host wall time, not a device
  reading);
- **counters**: one store of totals by label behind one ``record``,
  a key a row of ``metrics.WORKER_COUNTERS`` (which says what each
  counts, and under what name it reaches ``/metrics``).  What a step
  counts is said by its model and its attention function
  (``step_counts``); the runner passes their increments on unread.
  Three rows are JAX's own events for this process (the persistent
  cache's lookups, hits and writes; backend compiles and their seconds
  by whether XLA compiled the module or the cache held it: what the
  compile events above cannot see, a jit that re-specialises under an
  unchanged key).  With tracing on each backend compile is an
  ``xla.compile`` span too.

The driver pulls snapshots over the existing ``collective_rpc`` path
(``get_device_telemetry``) on ``/metrics`` scrapes; compile events
carry monotonically increasing sequence numbers so the engine folds
each event into its Prometheus instruments exactly once.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from vllm_distributed_tpu import tracing
from vllm_distributed_tpu.metrics import WORKER_COUNTERS

# Peak HBM bandwidth (bytes/s) by device-kind prefix, for the roofline
# gauge.  Rough public numbers; an unknown kind reports frac 0.0 (the
# gauge is a trend signal, not a benchmark).
_PEAK_BW_BY_KIND = (
    ("TPU v6", 1640e9),
    ("TPU v5p", 2765e9),
    ("TPU v5e", 819e9),
    ("TPU v5", 819e9),
    ("TPU v4", 1228e9),
    ("TPU v3", 900e9),
)


# JAX's persistent-compilation-cache events (jax/_src/compiler.py).  A
# program that took under jax_persistent_cache_min_compile_time_secs to
# compile is looked up but never written, so "writes" counts exactly the
# step-program-sized misses.
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "lookups",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
# JAX's duration events (jax/_src/dispatch.py, compiler.py; JAX 0.9):
# the first is recorded around compile_or_get_cached with the module's
# ``fun_name``, whether XLA compiled or the persistent cache answered;
# the second, just before it and on the same thread, only where the
# cache answered.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# The events are process-wide, so the tally is too: one listener of each
# kind, installed on first use, shared by every DeviceTelemetry in the
# process.
_cache_counts = dict.fromkeys(_CACHE_EVENTS.values(), 0)
# source (compiled | cache) -> [compiles, seconds]
_backend_compiles = {"compiled": [0, 0.0], "cache": [0, 0.0]}
_retrieved = threading.local()
_cache_lock = threading.Lock()
_cache_listener_installed = False


def _on_jax_event(event: str, **_kwargs) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        with _cache_lock:
            _cache_counts[name] += 1


def _on_jax_duration(event: str, duration: float, **kwargs) -> None:
    if event == _CACHE_RETRIEVAL_EVENT:
        _retrieved.hit = True
        return
    if event != _BACKEND_COMPILE_EVENT:
        return
    cache_hit = getattr(_retrieved, "hit", False)
    _retrieved.hit = False
    with _cache_lock:
        total = _backend_compiles["cache" if cache_hit else "compiled"]
        total[0] += 1
        total[1] += duration
    tracer = tracing.get_tracer()
    if not tracer.enabled:
        return
    attributes = dict(
        seconds=duration,
        cache_hit=cache_hit,
        module=str(kwargs.get("fun_name", "")),
    )
    start = time.time() - duration
    # Under whatever span is current on the driver; a worker host keeps
    # no trace of the driver's, so there it is a span of the process.
    parent = tracing.current_ctx() if tracer.host == "driver" else None
    if parent is None:
        tracer.record_loose("xla.compile", start, duration, **attributes)
    else:
        tracer.record_span(
            "xla.compile", start, duration, parent=parent, **attributes
        )


def _install_cache_listener() -> None:
    global _cache_listener_installed
    with _cache_lock:
        if _cache_listener_installed:
            return
        _cache_listener_installed = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_jax_event)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def peak_hbm_bandwidth(device_kind: str) -> float:
    for prefix, bw in _PEAK_BW_BY_KIND:
        if device_kind.startswith(prefix):
            return bw
    return 0.0


def local_memory_stats() -> list[dict]:
    """``memory_stats()`` of every local device, in device order; empty
    when the backend reports none (the CPU backend returns None)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    return [s for s in stats if s]


def persistent_cache_counts() -> dict[str, int]:
    with _cache_lock:
        return dict(_cache_counts)


def backend_compile_totals() -> tuple[dict[str, int], dict[str, float]]:
    """(compiles, seconds) of this process's XLA backend compiles, each
    by source (compiled | cache)."""
    with _cache_lock:
        return (
            {k: v[0] for k, v in _backend_compiles.items()},
            {k: v[1] for k, v in _backend_compiles.items()},
        )


class DeviceTelemetry:
    """Thread-safe ledger of compile/memory/bandwidth observations on
    one worker.  All record paths are O(1); ``snapshot`` is called only
    on the (rare) driver pull."""

    def __init__(self, max_events: int = 256) -> None:
        self._lock = threading.Lock()
        self._seq = 0
        # (seq, kind, seconds, shape_key) — bounded; the cumulative
        # totals below survive ring eviction.
        self.compile_events: deque[tuple] = deque(maxlen=max_events)
        self.compile_seconds_total = 0.0
        self.last_step_seconds = 0.0
        self.last_step_bytes = 0
        self.roofline_frac = 0.0
        # totals by label, a key a row of WORKER_COUNTERS (the three
        # rows of the process's own events are read at snapshot time)
        self.counters: dict[str, dict[str, int]] = {
            key: {} for key in WORKER_COUNTERS
        }
        _install_cache_listener()

    def record(self, counter: str, counts: dict[str, int]) -> None:
        """``counts`` by label onto ``counter``, a row of
        ``WORKER_COUNTERS`` (anything else is a KeyError: no series is
        made by a typo); a gauge row is set to them."""
        gauge = WORKER_COUNTERS[counter].gauge
        with self._lock:
            totals = self.counters[counter]
            for label, n in counts.items():
                totals[label] = n if gauge else totals.get(label, 0) + n

    def record_fallback(self, kind: str) -> None:
        self.record("fallbacks", {kind: 1})

    def record_compile(self, kind: str, seconds: float, key: str) -> None:
        with self._lock:
            self._seq += 1
            self.compile_events.append((self._seq, kind, seconds, key))
            self.compile_seconds_total += seconds
        self.record("compiles", {kind: 1})

    def record_step(
        self, seconds: float, est_bytes: int, peak_bw: float
    ) -> None:
        """One executed step: achieved-vs-roofline bandwidth."""
        if seconds <= 0:
            return
        with self._lock:
            self.last_step_seconds = seconds
            self.last_step_bytes = est_bytes
            self.roofline_frac = (
                (est_bytes / seconds) / peak_bw if peak_bw > 0 else 0.0
            )

    def _memory_stats(self) -> tuple[int, int]:
        """(live_bytes, limit_bytes) of the fullest local device; (0, 0)
        when the backend exposes none (CPU tests, mock workers)."""
        stats = local_memory_stats()
        if not stats:
            return 0, 0
        return (
            max(int(s.get("bytes_in_use", 0)) for s in stats),
            min(int(s.get("bytes_limit", 0)) for s in stats),
        )

    def snapshot(self, probe_memory: bool = True) -> dict:
        live, limit = self._memory_stats() if probe_memory else (0, 0)
        backend_compiles, backend_compile_seconds = backend_compile_totals()
        with self._lock:
            return {
                **{k: dict(v) for k, v in self.counters.items()},
                "compile_events": [list(e) for e in self.compile_events],
                "compile_seconds_total": self.compile_seconds_total,
                "hbm_live_bytes": live,
                "hbm_limit_bytes": limit,
                "last_step_seconds": self.last_step_seconds,
                "last_step_bytes": self.last_step_bytes,
                "roofline_frac": self.roofline_frac,
                "persistent_cache": persistent_cache_counts(),
                "backend_compiles": backend_compiles,
                "backend_compile_seconds": backend_compile_seconds,
            }
