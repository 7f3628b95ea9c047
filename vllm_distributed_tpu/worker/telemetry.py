"""Worker-side XLA/device telemetry (ISSUE 12 tentpole, part 2).

PR 11's bucketed spec-decode shapes made compile behavior load-bearing:
a shape bucket that escapes warmup costs a multi-second mid-serve XLA
compile, and nothing surfaced it in steady state — only offline benches
noticed.  ``DeviceTelemetry`` is the worker-local ledger the model
runner writes:

- **compiles**: every first execution of a distinct (kind, static
  shape) jit key is counted and timed, tagged with the triggering
  bucket kind (``prefill``/``decode``/``spec``) — a recompile storm
  shows up as a climbing ``vllm:xla_compiles_total`` instead of
  mystery latency spikes;
- **HBM**: live/limit bytes from the runtime's ``memory_stats`` so
  memory creep is a gauge, not an OOM post-mortem;
- **step roofline**: estimated bytes-touched / step-time over the
  device's peak HBM bandwidth (host wall time, not a device
  reading);
- **fallbacks**: every time the worker carried on without something it
  was asked to use (an unusable or unexportable AOT artifact, a compile
  cache directory it could not create), by kind — semantically safe,
  but a run that claims a warm start must show zero;
- **grouped MoE steps**: step programs dispatched whose int8 experts
  were traced with the grouped kernel (models/routed_experts.select_moe_impl),
  so how often the routed path engages is a counter, not an inference;
- **router picks**: of a model that routes over more experts than
  this chip holds, the picks that went to identity (zero-compute)
  experts, to experts held here and to experts held elsewhere, counted
  on the device and fetched with the step's tokens;
- **expert picks**: of a model that holds every expert, all picks and
  each routed layer's busiest expert's picks (what a grouped kernel
  pads the other experts' groups to), counted the same way;
- **sequence-state rows**: of a model that keeps state a sequence
  beside its pages, the sequences each dispatched step computed, by
  whether they started at position 0 (``fresh``: nothing of the row is
  read) or later (``carried``);
- **persistent cache**: JAX's own compilation-cache events for this
  process (lookups, hits, and entries written after a miss), so a warm
  restart is judged by what the cache reports, not by a time threshold;
- **backend compiles**: JAX's own backend-compile event for this
  process, counted and timed by whether XLA compiled the module or the
  persistent cache held it — what ``compiles`` above cannot see: a jit
  that re-specialises under an unchanged (kind, statics) key.  With
  tracing on each is an ``xla.compile`` span too.

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
        self.compiles: dict[str, int] = {}
        self.compile_seconds_total = 0.0
        self.last_step_seconds = 0.0
        self.last_step_bytes = 0
        self.roofline_frac = 0.0
        self.fallbacks: dict[str, int] = {}
        self.moe_grouped_steps = 0
        self.moe_picks: dict[str, int] = {}
        self.moe_expert_picks: dict[str, int] = {}
        self.seq_state_rows: dict[str, int] = {}
        # bytes of sequence state allocated, by kind (set once, when
        # the runner allocates its KV pool)
        self.seq_state_bytes: dict[str, int] = {}
        self.ssm_chunk_tokens: dict[str, int] = {}
        self.mla_chunk_tokens: dict[str, int] = {}
        self.mla_kv_rows: dict[str, int] = {}
        _install_cache_listener()

    def record_fallback(self, kind: str) -> None:
        with self._lock:
            self.fallbacks[kind] = self.fallbacks.get(kind, 0) + 1

    def record_moe_grouped_step(self) -> None:
        """One dispatched step program whose int8 experts run the
        grouped (routed) kernel; a fused decode scan never is one."""
        with self._lock:
            self.moe_grouped_steps += 1

    def record_moe_picks(
        self, counts: dict[str, int], counter: str = "moe_picks"
    ) -> None:
        """Router picks of one fetched step by kind, as the step program
        counted them on the device: ``moe_picks`` (zero | held | absent,
        which add up to all picks) or ``moe_expert_picks`` (all |
        busiest, which do not)."""
        self._add(getattr(self, counter), counts)

    def record_seq_state_rows(self, counts: dict[str, int]) -> None:
        """Sequences of one dispatched step whose state rows it reads
        and rewrites, by kind (fresh | carried)."""
        self._add(self.seq_state_rows, counts)

    def record_ssm_chunk_tokens(self, counts: dict[str, int]) -> None:
        """Tokens of one dispatched single step of a model with a
        chunked state-space scan: live (the step's own tokens) | tiled
        (chunk size × the chunk tiles its scans walked)."""
        self._add(self.ssm_chunk_tokens, counts)

    def record_mla_chunk_tokens(self, counts: dict[str, int]) -> None:
        """Tokens of one dispatched chunked step's multi-token chunks by
        the form their latent attention took (materialised: a fresh
        chunk | absorbed: a chunk with a cached prefix)."""
        self._add(self.mla_chunk_tokens, counts)

    def record_mla_kv_rows(self, counts: dict[str, int]) -> None:
        """Cached rows of one dispatched fused scan, a sublayer call's
        worth: live (the sequences' pool-resident tokens) | fetched
        (what the latent kernel's DMAs move for them)."""
        self._add(self.mla_kv_rows, counts)

    def _add(self, totals: dict[str, int], counts: dict[str, int]) -> None:
        with self._lock:
            for label, n in counts.items():
                totals[label] = totals.get(label, 0) + n

    def record_compile(self, kind: str, seconds: float, key: str) -> None:
        with self._lock:
            self._seq += 1
            self.compile_events.append((self._seq, kind, seconds, key))
            self.compiles[kind] = self.compiles.get(kind, 0) + 1
            self.compile_seconds_total += seconds

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
                "compile_events": [list(e) for e in self.compile_events],
                "compiles": dict(self.compiles),
                "compile_seconds_total": self.compile_seconds_total,
                "hbm_live_bytes": live,
                "hbm_limit_bytes": limit,
                "last_step_seconds": self.last_step_seconds,
                "last_step_bytes": self.last_step_bytes,
                "roofline_frac": self.roofline_frac,
                "fallbacks": dict(self.fallbacks),
                "moe_grouped_steps": self.moe_grouped_steps,
                "moe_picks": dict(self.moe_picks),
                "moe_expert_picks": dict(self.moe_expert_picks),
                "seq_state_rows": dict(self.seq_state_rows),
                "seq_state_bytes": dict(self.seq_state_bytes),
                "ssm_chunk_tokens": dict(self.ssm_chunk_tokens),
                "mla_chunk_tokens": dict(self.mla_chunk_tokens),
                "mla_kv_rows": dict(self.mla_kv_rows),
                "persistent_cache": persistent_cache_counts(),
                "backend_compiles": backend_compiles,
                "backend_compile_seconds": backend_compile_seconds,
            }
