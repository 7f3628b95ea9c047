"""Prometheus instrumentation for the engine loop (SURVEY.md §5.5).

The reference serves vLLM's Prometheus metrics through `build_app`
(/root/reference/src/launch.py:429-432); this is the TPU-native engine's
equivalent, using vLLM's metric names (prefix ``vllm:``) so existing
dashboards/alerts keep working after a backend swap.

One ``EngineMetrics`` per engine with its own CollectorRegistry (no
global-registry collisions across engines/tests).  Disabled via
``--disable-log-stats`` (ObservabilityConfig.collect_metrics=False), in
which case every record call is a no-op and /metrics reports only
process defaults.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

from vllm_distributed_tpu.tracing import get_tracer

_TTFT_BUCKETS = (
    0.001, 0.005, 0.01, 0.02, 0.04, 0.06, 0.08, 0.1, 0.25, 0.5,
    0.75, 1.0, 2.5, 5.0, 7.5, 10.0, 20.0, 40.0, 80.0,
)
_ITL_BUCKETS = (
    0.0005, 0.001, 0.005, 0.01, 0.02, 0.04, 0.06, 0.08, 0.1, 0.25,
    0.5, 0.75, 1.0, 2.5,
)
_E2E_BUCKETS = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 40.0, 80.0, 160.0,
)
# Heartbeat RTTs live on the control plane (DCN), not the data plane:
# sub-millisecond on loopback, a few ms cross-host, anything near the
# ping interval is a miss in the making.
_HEARTBEAT_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0,
)
# One recovery = teardown + backoff + agent redial + executor rebuild +
# replay; sub-second with warm AOT caches on mocks, tens of seconds on a
# real pod slice (device init + weight load dominate).
_RECOVERY_BUCKETS = (
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 40.0, 80.0, 160.0, 320.0,
)
# Request stages (queue wait, prefill, decode) span the TTFT..e2e range.
_STAGE_BUCKETS = (
    0.001, 0.005, 0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
    20.0, 40.0, 80.0, 160.0,
)
# Emitted tokens per spec-decode verify window: 1 (full reject) up to
# K+1 (full accept); integer buckets up to the largest sane K.
_SPEC_ACCEPT_BUCKETS = (1, 2, 3, 4, 6, 8, 12, 16, 24, 33)
# Step phases (schedule on host CPU, dispatch fan-out, gather wait):
# schedule/dispatch are sub-millisecond, gather bounds device time.
_STEP_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
)
# Per-SLO-class latency histograms (ISSUE 12), in MILLISECONDS to match
# the VDT_SLO_*_MS target units.  Coarser than the engine's log-bucket
# histograms (engine/slo.py) — the fleet-exact merge runs over those;
# these exist so ordinary Prometheus dashboards get per-class curves.
_SLO_TTFT_MS_BUCKETS = (
    1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0,
    2500.0, 5000.0, 10000.0, 30000.0, 60000.0, 120000.0,
)
_SLO_ITL_MS_BUCKETS = (
    0.5, 1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0,
)
# XLA compile wall time (trace+lower+compile+first run): sub-second on
# warm AOT/disk caches, tens of seconds cold on a pod slice.
_COMPILE_BUCKETS = (
    0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 40.0,
    80.0, 160.0, 320.0,
)
# Host-tier restore batches (ISSUE 14): a few pages over PCIe/DMA —
# sub-millisecond on loopback mocks, milliseconds for real page spans;
# anything approaching prefill time means the crossover is set wrong.
_KV_RESTORE_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)

# Span name (tracing.py) -> per-stage histogram attribute.  The tracer's
# metrics sink feeds these, so the Prometheus histograms and the traces
# derive from the SAME measurements and can never disagree.
SPAN_METRIC_MAP = {
    "engine.queue": "queue_time",
    "engine.prefill": "prefill_time",
    "engine.decode": "decode_time",
    "scheduler.schedule": "step_schedule_time",
    "executor.dispatch": "step_dispatch_time",
    "executor.gather": "step_gather_time",
}

@dataclass(frozen=True)
class WorkerCounter:
    """One row of ``WORKER_COUNTERS``: how a total the worker keeps by
    label reaches ``/metrics``."""

    metric: str  # the family's name, without a counter's ``_total``
    label: str | None  # the label its keys are values of; None: one series
    help: str
    gauge: bool = False  # the worker's value is set, not added up

    @property
    def family(self) -> str:
        return self.metric if self.gauge else self.metric + "_total"


# The worker's labelled counters: key in ``DeviceTelemetry``'s store and
# snapshot -> family on /metrics.  The only list of them:
# ``DeviceTelemetry.record`` refuses a key that is no row, the engine
# folds a snapshot row by row (``LLMEngine.refresh_device_telemetry``),
# ``EngineMetrics`` builds one instrument a row, and a row's help text
# is the one description of what it counts.  All are the reply-rank
# worker's (one representative host).
WORKER_COUNTERS: dict[str, WorkerCounter] = {
    "compiles": WorkerCounter(
        "vllm:xla_compiles", "kind",
        "jit compiles observed on the reply-rank worker (the first "
        "execution of each distinct (kind, static shape) key, counted "
        "from cumulative totals so a storm that overflows the event "
        "ring between scrapes still counts exactly), by the triggering "
        "bucket-shape kind (prefill | decode | spec); a climbing "
        "counter in steady state is a recompile storm",
    ),
    "fallbacks": WorkerCounter(
        "vllm:worker_fallbacks", "kind",
        "Times the reply-rank worker carried on without something "
        "it was asked to use (aot_load | aot_export | "
        "compile_cache_setup); a warm start must show none",
    ),
    "persistent_cache": WorkerCounter(
        "vllm:xla_persistent_cache_events", "event",
        "JAX persistent-compilation-cache events in the reply-rank "
        "worker's process: lookups, hits, and writes (a miss whose "
        "compile was long enough to be stored)",
    ),
    "moe_grouped_steps": WorkerCounter(
        "vllm:moe_grouped_steps", None,
        "Step programs dispatched on the reply-rank worker whose "
        "int8 experts ran the grouped (routed) kernel: many-row "
        "steps of an MoE model on one chip; a fused decode scan "
        "never counts",
    ),
    "moe_picks": WorkerCounter(
        "vllm:moe_picks", "kind",
        "Router picks on the reply-rank worker by kind, counted on the "
        "device and fetched with the step's tokens: zero "
        "(identity experts: no compute), held (experts this chip "
        "holds), absent (experts held by other chips: nothing is "
        "computed for them here); a model without identity experts "
        "counts busiest instead of zero (each routed layer's "
        "most-picked held expert's picks, summed: not a part of "
        "the others' sum) and skipped (no pick: held experts whose "
        "conditional did not run in a program of the branch form)",
    ),
    "moe_expert_picks": WorkerCounter(
        "vllm:moe_expert_picks", "kind",
        "Router picks on the reply-rank worker of a model that "
        "holds every expert, over all routed layers: all, and "
        "busiest (each layer's most-picked expert's picks, summed: "
        "experts x busiest / all is 1.0 when the routing is even)",
    ),
    "seq_state_rows": WorkerCounter(
        "vllm:seq_state_rows", "kind",
        "Sequences in the steps the reply-rank worker dispatched "
        "whose per-sequence state (a short convolution's last rows) "
        "the step reads and rewrites: fresh (the sequence starts at "
        "position 0 and reads nothing of its row), carried",
    ),
    "seq_state_bytes": WorkerCounter(
        "vllm:seq_state_bytes", "kind",
        "Bytes of per-sequence state the reply-rank worker "
        "allocated beside its paged caches, at its sequence bucket, "
        "by kind of state (conv: the rows a short convolution looks "
        "back on; ssm: a state-space state)",
        gauge=True,
    ),
    "ssm_chunk_tokens": WorkerCounter(
        "vllm:ssm_chunk_tokens", "kind",
        "Tokens of the single steps the reply-rank worker "
        "dispatched for a model with a chunked state-space scan: "
        "live (the steps' own tokens), tiled (the token slots the "
        "scans' matrix products ran over: the bucket's tiles in the plain "
        "form, each sequence's windows in the kernel); live / tiled: fill",
    ),
    "mla_chunk_tokens": WorkerCounter(
        "vllm:mla_chunk_tokens", "form",
        "Tokens of multi-token chunks in the chunked steps the "
        "reply-rank worker dispatched, by the form their latent "
        "(MLA) attention took: materialised (a fresh chunk: per-head "
        "keys and values over the step's own rows), absorbed (a "
        "chunk with a cached prefix: the latent cache's rows)",
    ),
    "mla_kv_rows": WorkerCounter(
        "vllm:mla_kv_rows", "kind",
        "Cached rows of the fused decode scans the reply-rank worker "
        "dispatched, one latent (MLA) attention call's worth a scan: "
        "live (the sequences' pool-resident tokens), fetched (the "
        "rows the kernel's DMAs move for them: whole sub-blocks); "
        "live / fetched is the fill",
    ),
    # ---- causes outside any thread's phases (ISSUE 40) ----
    "backend_compiles": WorkerCounter(
        "vllm:xla_backend_compiles", "source",
        "XLA backend compiles in the reply-rank worker's process, as "
        "JAX's own compile event reports them, by source: compiled "
        "(XLA compiled the module: a jit re-specialised by a new "
        "shape counts, which vllm:xla_compiles_total cannot see), "
        "cache (the module came from the persistent cache)",
    ),
    "backend_compile_seconds": WorkerCounter(
        "vllm:xla_backend_compile_seconds", "source",
        "Seconds inside the XLA backend compiles counted by "
        "vllm:xla_backend_compiles_total, by source",
    ),
}


# Every vllm:* metric family this engine documents (README
# "Observability"), as rendered in `# TYPE` lines (counters carry the
# `_total` suffix there).  tests/test_metrics.py asserts render()
# exposes exactly this set — additions/removals must update both.
DOCUMENTED_METRICS = (
    "vllm:num_requests_running",
    "vllm:num_requests_waiting",
    "vllm:prompt_tokens_total",
    "vllm:generation_tokens_total",
    "vllm:num_preemptions_total",
    "vllm:prefix_cache_queries_total",
    "vllm:prefix_cache_hits_total",
    # ---- tiered KV cache (ISSUE 14) ----
    "vllm:kv_spill_pages_total",
    "vllm:kv_restore_pages_total",
    "vllm:kv_restore_seconds",
    "vllm:host_kv_bytes",
    # ---- disaggregated prefill/decode hand-off (ISSUE 15) ----
    "vllm:kv_transfer_pages_total",
    "vllm:kv_transfer_bytes_total",
    "vllm:kv_transfer_seconds",
    "vllm:spec_decode_draft_tokens_total",
    "vllm:spec_decode_accepted_tokens_total",
    "vllm:spec_decode_acceptance_length",
    "vllm:gpu_cache_usage_perc",
    "vllm:time_to_first_token_seconds",
    "vllm:time_per_output_token_seconds",
    "vllm:e2e_request_latency_seconds",
    "vllm:request_queue_time_seconds",
    "vllm:request_prefill_time_seconds",
    "vllm:request_decode_time_seconds",
    "vllm:step_schedule_time_seconds",
    "vllm:step_dispatch_time_seconds",
    "vllm:step_gather_time_seconds",
    "vllm:request_success_total",
    "vllm:pipeline_breaks_total",
    "vllm:dispatches_total",
    "vllm:requests_rejected_total",
    "vllm:engine_drain_state",
    "vllm:admission_queued_tokens",
    "vllm:replica_info",
    "vllm:host_up",
    "vllm:heartbeat_latency_seconds",
    "vllm:engine_dead_info",
    "vllm:engine_restarts_total",
    "vllm:requests_replayed_total",
    "vllm:engine_recovery_seconds",
    # ---- SLO/goodput accounting (ISSUE 12) ----
    "vllm:slo_requests_total",
    "vllm:slo_ttft_attained_total",
    "vllm:slo_itl_attained_total",
    "vllm:goodput_requests_total",
    "vllm:slo_ttft_ms",
    "vllm:slo_itl_ms",
    # ---- XLA/device telemetry (ISSUE 12) ----
    "vllm:xla_compile_seconds",
    "vllm:hbm_live_bytes",
    "vllm:step_roofline_frac",
    *(row.family for row in WORKER_COUNTERS.values()),
    # ---- causes outside any thread's phases (ISSUE 40) ----
    "vllm:gc_collections_total",
    "vllm:gc_pause_seconds_total",
    # ---- fleet sentinel (ISSUE 20) ----
    "vllm:slo_burn_rate",
    "vllm:itl_p99_ms",
)


# Why the engine drained its in-flight dispatches before a step
# (LLMEngine._drain_reason / _sync_reason): the ``reason`` label of
# vllm:pipeline_breaks_total.
PIPELINE_BREAK_REASONS = (
    "logprobs", "penalties", "spec", "pages", "kv_tier", "kv_transfer",
)


class EngineMetrics:
    """Engine-loop instruments; every method is a no-op when disabled."""

    def __init__(self, model_name: str, enabled: bool = True) -> None:
        from vllm_distributed_tpu.engine.sentinel import (
            BurnRateTracker,
            SentinelLog,
        )

        self.enabled = enabled
        self.registry = None
        # Fleet sentinel (ISSUE 20): the engine's slice of the unified
        # event timeline (served at /debug/events) and its own
        # multi-window SLO burn tracker.  Both live even when the
        # prometheus exposition is disabled — events are not metrics.
        self.events = SentinelLog("engine")
        self.burn = BurnRateTracker()
        if not enabled:
            return
        try:
            from prometheus_client import (
                CollectorRegistry,
                Counter,
                Gauge,
                Histogram,
            )
        except ImportError:
            # Degrade to disabled rather than failing engine startup on
            # an install without the (optional) prometheus_client.
            import logging

            logging.getLogger(__name__).warning(
                "prometheus_client not installed; metrics disabled"
            )
            self.enabled = False
            return

        self.registry = CollectorRegistry()
        label = {"model_name": model_name}

        def counter(name, doc):
            return Counter(
                name, doc, ["model_name"], registry=self.registry
            ).labels(**label)

        def gauge(name, doc):
            return Gauge(
                name, doc, ["model_name"], registry=self.registry
            ).labels(**label)

        def histogram(name, doc, buckets):
            return Histogram(
                name,
                doc,
                ["model_name"],
                buckets=buckets,
                registry=self.registry,
            ).labels(**label)

        self.num_running = gauge(
            "vllm:num_requests_running",
            "Requests currently executing on the device",
        )
        self.num_waiting = gauge(
            "vllm:num_requests_waiting", "Requests queued for admission"
        )
        self.prompt_tokens = counter(
            "vllm:prompt_tokens", "Prefill tokens processed"
        )
        self.generation_tokens = counter(
            "vllm:generation_tokens", "Tokens generated"
        )
        self.preemptions = counter(
            "vllm:num_preemptions", "Requests preempted by the scheduler"
        )
        self.prefix_cache_queries = counter(
            "vllm:prefix_cache_queries",
            "Tokens looked up in the prefix cache at (re-)admission "
            "(includes preemption-resume lookups)",
        )
        # Split per tier (ISSUE 14): tier="hbm" counts resident hits,
        # tier="host" tokens restored from the host-DRAM spill tier.
        # Sum across tiers for the pre-tiering total.
        self._prefix_cache_hits = Counter(
            "vllm:prefix_cache_hits",
            "Tokens served from cached KV pages instead of prefill "
            "(cross-request prefix reuse and preemption-resume "
            'recovery), per cache tier: "hbm" resident pages, "host" '
            "pages restored from the host-DRAM spill tier",
            ["model_name", "tier"],
            registry=self.registry,
        )
        self.prefix_cache_hits_hbm = self._prefix_cache_hits.labels(
            model_name=model_name, tier="hbm"
        )
        self.prefix_cache_hits_host = self._prefix_cache_hits.labels(
            model_name=model_name, tier="host"
        )
        # ---- tiered KV cache (ISSUE 14) ----
        self.kv_spill_pages = counter(
            "vllm:kv_spill_pages",
            "KV pages spilled from the HBM pool to the host-DRAM tier "
            "on eviction (worker-side device_get batches)",
        )
        self.kv_restore_pages = counter(
            "vllm:kv_restore_pages",
            "KV pages streamed back from the host-DRAM tier into "
            "freshly allocated HBM pages ahead of a prefill resume",
        )
        self.kv_restore_seconds = histogram(
            "vllm:kv_restore_seconds",
            "Worker wall time applying a restore-bearing step's KV-tier "
            "spans (the restore stall the engine.kv_restore span traces)",
            _KV_RESTORE_BUCKETS,
        )
        self.host_kv_bytes = gauge(
            "vllm:host_kv_bytes",
            "Bytes of KV held in the host-DRAM spill tier "
            "(slots in use x per-page pool bytes)",
        )
        # ---- disaggregated prefill/decode hand-off (ISSUE 15) ----
        # direction="out": page-layer chunks exported to another
        # replica; direction="in": chunks imported and committed here.
        self._kv_transfer_pages = Counter(
            "vllm:kv_transfer_pages",
            "KV page-layer chunks moved over DCN for prefill/decode "
            'hand-offs (pages x layers), by direction: "out" exported '
            'from this replica\'s held prefills, "in" imported and '
            "committed into the local prefix index",
            ["model_name", "direction"],
            registry=self.registry,
        )
        self._kv_transfer_bytes = Counter(
            "vllm:kv_transfer_bytes",
            "KV bytes moved over DCN for prefill/decode hand-offs, by "
            "direction (pre-base64 wire payload)",
            ["model_name", "direction"],
            registry=self.registry,
        )
        self.kv_transfer_pages_out = self._kv_transfer_pages.labels(
            model_name=model_name, direction="out"
        )
        self.kv_transfer_pages_in = self._kv_transfer_pages.labels(
            model_name=model_name, direction="in"
        )
        self.kv_transfer_bytes_out = self._kv_transfer_bytes.labels(
            model_name=model_name, direction="out"
        )
        self.kv_transfer_bytes_in = self._kv_transfer_bytes.labels(
            model_name=model_name, direction="in"
        )
        self.kv_transfer_seconds = histogram(
            "vllm:kv_transfer_seconds",
            "Wall seconds per KV hand-off transfer on this replica "
            "(export: hold creation to release; import: begin to "
            "commit)",
            _KV_RESTORE_BUCKETS,
        )
        # ---- speculative decoding (ISSUE 11) ----
        self.spec_draft_tokens = counter(
            "vllm:spec_decode_draft_tokens",
            "Tokens drafted by the n-gram prompt-lookup proposer into "
            "fused verify passes",
        )
        self.spec_accepted_tokens = counter(
            "vllm:spec_decode_accepted_tokens",
            "Drafted tokens accepted by greedy verification (bonus "
            "tokens not counted; acceptance rate = accepted / draft)",
        )
        self.spec_acceptance_length = histogram(
            "vllm:spec_decode_acceptance_length",
            "Tokens emitted per verified request window (1 + accepted "
            "drafts; 1 = full reject, K+1 = full accept)",
            _SPEC_ACCEPT_BUCKETS,
        )
        self.kv_cache_usage = gauge(
            "vllm:gpu_cache_usage_perc",  # vLLM's name, kept for dashboards
            "Fraction of usable KV pages held by live requests "
            "(evictable cached pages count as free)",
        )
        self.ttft = histogram(
            "vllm:time_to_first_token_seconds",
            "Time from request arrival to first generated token",
            _TTFT_BUCKETS,
        )
        self.itl = histogram(
            "vllm:time_per_output_token_seconds",
            "Inter-token latency (per generated token after the first)",
            _ITL_BUCKETS,
        )
        self.e2e_latency = histogram(
            "vllm:e2e_request_latency_seconds",
            "Request end-to-end latency",
            _E2E_BUCKETS,
        )
        # ---- per-stage latencies, fed from span data (tracing.py) via
        # observe_span so dashboards and traces can never disagree.
        # Populated only while tracing is enabled.
        self.queue_time = histogram(
            "vllm:request_queue_time_seconds",
            "Arrival to first schedule (admission queue wait)",
            _STAGE_BUCKETS,
        )
        self.prefill_time = histogram(
            "vllm:request_prefill_time_seconds",
            "First schedule to first token ((chunked) prefill)",
            _STAGE_BUCKETS,
        )
        self.decode_time = histogram(
            "vllm:request_decode_time_seconds",
            "First token to finish (decode)",
            _STAGE_BUCKETS,
        )
        self.step_schedule_time = histogram(
            "vllm:step_schedule_time_seconds",
            "Scheduler time per engine step",
            _STEP_BUCKETS,
        )
        self.step_dispatch_time = histogram(
            "vllm:step_dispatch_time_seconds",
            "Per-host RPC dispatch fan-out time per step",
            _STEP_BUCKETS,
        )
        self.step_gather_time = histogram(
            "vllm:step_gather_time_seconds",
            "Per-host reply wait per step (bounds device time + DCN)",
            _STEP_BUCKETS,
        )
        self._pipeline_breaks = Counter(
            "vllm:pipeline_breaks",
            "Async-scheduling reconciliation drains: the dispatches in "
            "flight had to land before the next step, by what needed "
            "settled host state: logprobs | penalties | spec | pages | "
            "kv_tier | kv_transfer",
            ["model_name", "reason"],
            registry=self.registry,
        )
        self._dispatches = Counter(
            "vllm:dispatches",
            "Step programs issued, by kind (decode = fused scan, step = "
            "single prefill/mixed step, spec = verify pass) and whether "
            "another dispatch was in flight when this one was issued",
            ["model_name", "kind", "overlapped"],
            registry=self.registry,
        )
        # Every series exists from the start, at 0: a scrape's delta
        # never has to tell "no sample yet" from "none happened".
        for reason in PIPELINE_BREAK_REASONS:
            self._pipeline_breaks.labels(
                model_name=model_name, reason=reason
            )
        for kind in ("decode", "step", "spec"):
            for overlapped in ("true", "false"):
                self._dispatches.labels(
                    model_name=model_name, kind=kind, overlapped=overlapped
                )
        self._success = Counter(
            "vllm:request_success",
            "Finished requests by finish reason",
            ["model_name", "finished_reason"],
            registry=self.registry,
        )
        # ---- overload resilience (ISSUE 8) ----
        self._rejected = Counter(
            "vllm:requests_rejected",
            "Admission rejections (HTTP 429) by reason: queue_full | "
            "queued_tokens | kv_pressure | draining",
            ["model_name", "reason"],
            registry=self.registry,
        )
        self.drain_state = gauge(
            "vllm:engine_drain_state",
            "0 serving, 1 draining (admission stopped, in-flight work "
            "finishing), 2 drained (unfinished work journaled/aborted)",
        )
        self.admission_queued_tokens = gauge(
            "vllm:admission_queued_tokens",
            "Prompt tokens queued for admission (waiting requests "
            "awaiting (re-)prefill)",
        )
        # ---- multi-replica identity (ISSUE 10 satellite) ----
        self._replica_info = Gauge(
            "vllm:replica_info",
            "Constant 1; the replica_id label is this serving "
            "replica's stable identity (VDT_REPLICA_ID, default "
            "host:port) so multi-replica dashboards can attribute "
            "series per replica",
            ["model_name", "replica_id"],
            registry=self.registry,
        )
        # ---- control-plane liveness ----
        self._host_up = Gauge(
            "vllm:host_up",
            "1 while the host answers heartbeats, 0 once marked dead",
            ["model_name", "host_rank"],
            registry=self.registry,
        )
        self.heartbeat_latency = histogram(
            "vllm:heartbeat_latency_seconds",
            "Control-plane heartbeat round-trip time per remote host",
            _HEARTBEAT_BUCKETS,
        )
        self._engine_dead = Gauge(
            "vllm:engine_dead_info",
            "1 when the engine is dead; labels carry the failure "
            "attribution (lifecycle phase + offending host)",
            ["model_name", "phase", "host_rank"],
            registry=self.registry,
        )
        # ---- supervised recovery (engine/supervisor.py).  These live in
        # the same EngineMetrics instance, which is carried ACROSS engine
        # rebuilds — counters must not reset when the engine recovers.
        self.engine_restarts = counter(
            "vllm:engine_restarts_total",
            "In-process engine recovery attempts started by the "
            "supervisor (teardown + executor rebuild)",
        )
        self.requests_replayed = counter(
            "vllm:requests_replayed_total",
            "Interrupted requests re-admitted from the request journal "
            "after an engine recovery",
        )
        self.recovery_seconds = histogram(
            "vllm:engine_recovery_seconds",
            "Engine death to recovered-and-replayed, per successful "
            "recovery cycle",
            _RECOVERY_BUCKETS,
        )
        # ---- SLO/goodput accounting (ISSUE 12).  slo_class is a
        # BOUNDED label: SloAccounting sanitizes and caps the class set
        # (overflow folds into "other"), so client-controlled names can
        # never explode series cardinality (vdt-lint VDT009).
        self._slo_requests = Counter(
            "vllm:slo_requests",
            "Finished requests per SLO class (attainment denominator)",
            ["model_name", "slo_class"],
            registry=self.registry,
        )
        self._slo_ttft_attained = Counter(
            "vllm:slo_ttft_attained",
            "Finished requests whose TTFT met the class target "
            "(VDT_SLO_TTFT_MS; no target = trivially attained)",
            ["model_name", "slo_class"],
            registry=self.registry,
        )
        self._slo_itl_attained = Counter(
            "vllm:slo_itl_attained",
            "Finished requests whose WORST inter-token latency met the "
            "class target (VDT_SLO_ITL_MS; no target or single-token "
            "output = trivially attained)",
            ["model_name", "slo_class"],
            registry=self.registry,
        )
        self._goodput_requests = Counter(
            "vllm:goodput_requests",
            "DistServe goodput: requests that completed (stop/length) "
            "within BOTH their TTFT and ITL SLO targets",
            ["model_name", "slo_class"],
            registry=self.registry,
        )
        self._slo_ttft_ms = Histogram(
            "vllm:slo_ttft_ms",
            "TTFT per SLO class, milliseconds (per-class dashboard "
            "view; the fleet-exact merge runs over the /slo log-bucket "
            "histograms)",
            ["model_name", "slo_class"],
            buckets=_SLO_TTFT_MS_BUCKETS,
            registry=self.registry,
        )
        self._slo_itl_ms = Histogram(
            "vllm:slo_itl_ms",
            "Inter-token latency per SLO class, milliseconds",
            ["model_name", "slo_class"],
            buckets=_SLO_ITL_MS_BUCKETS,
            registry=self.registry,
        )
        # ---- XLA/device telemetry (ISSUE 12), fed by the driver's
        # pull of worker DeviceTelemetry snapshots (one representative
        # host: the executor's reply rank).
        self._worker_counters = {
            key: (Gauge if row.gauge else Counter)(
                row.metric,
                row.help,
                ["model_name", *([row.label] if row.label else [])],
                registry=self.registry,
            )
            for key, row in WORKER_COUNTERS.items()
        }
        for key, row in WORKER_COUNTERS.items():
            if row.label is None:  # its one series reads 0 from the start
                self._worker_counters[key].labels(**label)
        self.xla_compile_seconds = histogram(
            "vllm:xla_compile_seconds",
            "Wall time of each observed jit compile "
            "(trace+lower+compile+first run)",
            _COMPILE_BUCKETS,
        )
        self.hbm_live_bytes = gauge(
            "vllm:hbm_live_bytes",
            "Live HBM bytes on the fullest local device of the reply-rank "
            "worker (memory creep is a gauge, not an OOM post-mortem)",
        )
        self.step_roofline_frac = gauge(
            "vllm:step_roofline_frac",
            "Last step's estimated bytes-touched/second over the "
            "device's peak HBM bandwidth (0 when unknown)",
        )
        # ---- causes outside any thread's phases (ISSUE 40) ----
        self._gc_collections = Counter(
            "vllm:gc_collections",
            "Garbage collections of generation 1 and 2 in the server's "
            "process since tracing was enabled (the hook is installed "
            "only then), by generation",
            ["model_name", "generation"],
            registry=self.registry,
        )
        self._gc_pause_seconds = Counter(
            "vllm:gc_pause_seconds",
            "Seconds every thread of the server's process stood still "
            "in the collections counted by vllm:gc_collections_total",
            ["model_name", "generation"],
            registry=self.registry,
        )
        self._gc_seen: dict[int, tuple] = {}
        # ---- fleet sentinel (ISSUE 20) ----
        self._slo_burn = Gauge(
            "vllm:slo_burn_rate",
            "SLO error-budget burn rate per class and window "
            "(error_rate / (1 - VDT_SLO_OBJECTIVE)); refreshed on "
            "every /metrics render",
            ["model_name", "slo_class", "window"],
            registry=self.registry,
        )
        self.itl_p99_ms = gauge(
            "vllm:itl_p99_ms",
            "p99 inter-token latency across all SLO classes (merged "
            "log-bucket histograms, bucket-representative ms) — the "
            "router's sentinel scrapes this as a per-replica condition "
            "signal",
        )
        from vllm_distributed_tpu.engine.slo import SloAccounting

        self.slo = SloAccounting()
        self._dead_labels: tuple[str, str] | None = None
        self._model_name = model_name

    # ---- engine-loop hooks ----
    def record_queues(
        self, running: int, waiting: int, waiting_tokens: int | None = None
    ) -> None:
        if not self.enabled:
            return
        self.num_running.set(running)
        self.num_waiting.set(waiting)
        if waiting_tokens is not None:
            self.admission_queued_tokens.set(waiting_tokens)

    def record_rejected(self, reason: str) -> None:
        """One admission rejection (typed EngineOverloadedError -> 429)."""
        if self.enabled:
            self._rejected.labels(
                model_name=self._model_name, reason=reason
            ).inc()

    def record_drain_state(self, state: int) -> None:
        if self.enabled:
            self.drain_state.set(state)

    def record_preemptions(self, n: int) -> None:
        if self.enabled and n:
            self.preemptions.inc(n)

    def record_pipeline_break(self, reason: str) -> None:
        if self.enabled:
            self._pipeline_breaks.labels(
                model_name=self._model_name, reason=reason
            ).inc()

    def record_dispatch(self, kind: str, overlapped: bool) -> None:
        if self.enabled:
            self._dispatches.labels(
                model_name=self._model_name,
                kind=kind,
                overlapped="true" if overlapped else "false",
            ).inc()

    def record_prompt_tokens(self, n: int) -> None:
        if self.enabled and n:
            self.prompt_tokens.inc(n)

    def record_prefix_cache(
        self, queries: int, hits: int, host_hits: int = 0
    ) -> None:
        """``hits`` is the TOTAL across tiers; ``host_hits`` the
        host-restored share of it (tier="hbm" gets the remainder)."""
        if not self.enabled:
            return
        if queries:
            self.prefix_cache_queries.inc(queries)
        if hits - host_hits > 0:
            self.prefix_cache_hits_hbm.inc(hits - host_hits)
        if host_hits:
            self.prefix_cache_hits_host.inc(host_hits)

    def record_kv_tier(
        self, spilled: int, restored: int, host_bytes: int | None = None
    ) -> None:
        """Page deltas from one step's tier spans + the current host
        occupancy (None leaves the gauge untouched)."""
        if not self.enabled:
            return
        if spilled:
            self.kv_spill_pages.inc(spilled)
        if restored:
            self.kv_restore_pages.inc(restored)
        if host_bytes is not None:
            self.host_kv_bytes.set(host_bytes)

    def record_kv_transfer(
        self, direction: str, pages: int, nbytes: int
    ) -> None:
        """One hand-off chunk batch (ISSUE 15): page-layer count and
        wire bytes, by direction ("out" export / "in" import)."""
        if not self.enabled:
            return
        if direction == "out":
            self.kv_transfer_pages_out.inc(pages)
            self.kv_transfer_bytes_out.inc(nbytes)
        else:
            self.kv_transfer_pages_in.inc(pages)
            self.kv_transfer_bytes_in.inc(nbytes)

    def record_kv_transfer_seconds(self, seconds: float) -> None:
        if self.enabled:
            self.kv_transfer_seconds.observe(max(seconds, 0.0))

    def record_kv_restore_seconds(self, seconds: float) -> None:
        if self.enabled:
            self.kv_restore_seconds.observe(max(seconds, 0.0))

    def record_kv_cache_usage(self, frac: float) -> None:
        if self.enabled:
            self.kv_cache_usage.set(frac)

    def record_spec_decode(self, drafted: int, accepted: int) -> None:
        """Token deltas from one speculative verify step."""
        if not self.enabled:
            return
        if drafted:
            self.spec_draft_tokens.inc(drafted)
        if accepted:
            self.spec_accepted_tokens.inc(accepted)

    def record_spec_acceptance_length(self, num_emitted: int) -> None:
        """Tokens emitted by one request's verify window (1 + accepted)."""
        if self.enabled:
            self.spec_acceptance_length.observe(num_emitted)

    def _slo_class(self, req_metrics) -> str:
        """Resolve (and cache) the request's bounded SLO-class label."""
        cls = req_metrics.slo_class_resolved
        if cls is None:
            cls = self.slo.resolve(req_metrics.slo_class)
            req_metrics.slo_class_resolved = cls
        return cls

    def record_new_tokens(self, req_metrics, n: int, now: float | None = None) -> None:
        """n new tokens for one request: TTFT on the first, ITL after.
        ``now`` and every interval endpoint are MONOTONIC clock reads
        (the *_mono RequestMetrics fields) — an NTP step must never
        produce a negative/garbage TTFT, ITL, or e2e observation."""
        if not self.enabled or n <= 0:
            return
        now = now if now is not None else time.monotonic()
        self.generation_tokens.inc(n)
        cls = self._slo_class(req_metrics)
        last = req_metrics.last_token_time_mono
        if req_metrics.first_token_time_mono is not None and last is None:
            # first batch of tokens for this request
            ttft = (
                req_metrics.first_token_time_mono
                - req_metrics.arrival_time_mono
            )
            self.ttft.observe(ttft)
            self.slo.record_ttft(cls, ttft)
            self._slo_ttft_ms.labels(
                model_name=self._model_name, slo_class=cls
            ).observe(max(ttft, 0.0) * 1000.0)
            n_after_first = n - 1
            # A fused dispatch can deliver the first token WITH its
            # successors: their intervals start at the first token.
            last = req_metrics.first_token_time_mono
        else:
            n_after_first = n
        if last is not None and n_after_first > 0:
            per_tok = max(now - last, 0.0) / n_after_first
            for _ in range(n_after_first):
                self.itl.observe(per_tok)
            # SLO accounting (ISSUE 12): class histogram + the request's
            # own per-bucket tally (what the fleet merge is recomputable
            # from) + worst-interval tracking for the ITL attainment.
            idx = self.slo.record_itl(cls, per_tok, n_after_first)
            buckets = req_metrics.slo_itl_buckets
            if buckets is None:
                buckets = req_metrics.slo_itl_buckets = {}
            buckets[idx] = buckets.get(idx, 0) + n_after_first
            if (
                req_metrics.slo_itl_max_s is None
                or per_tok > req_metrics.slo_itl_max_s
            ):
                req_metrics.slo_itl_max_s = per_tok
            itl_ms = self._slo_itl_ms.labels(
                model_name=self._model_name, slo_class=cls
            )
            for _ in range(n_after_first):
                itl_ms.observe(per_tok * 1000.0)
        req_metrics.last_token_time_mono = now

    def record_replica_info(self, replica_id: str) -> None:
        """Publish this replica's stable identity (API-server boot)."""
        if self.enabled and replica_id:
            self._replica_info.labels(
                model_name=self._model_name, replica_id=replica_id
            ).set(1)

    # ---- control-plane liveness hooks (called from the executor's
    # heartbeat loop and the engine failure callback; every caller
    # tolerates a disabled/None metrics object) ----
    def record_heartbeat(self, host_rank: int, latency: float) -> None:
        if not self.enabled:
            return
        self._host_up.labels(
            model_name=self._model_name, host_rank=str(host_rank)
        ).set(1)
        self.heartbeat_latency.observe(latency)

    def record_host_down(self, host_rank: int) -> None:
        if not self.enabled:
            return
        self._host_up.labels(
            model_name=self._model_name, host_rank=str(host_rank)
        ).set(0)

    def record_engine_dead(self, failure) -> None:
        """`failure` is a HostFailure or None (non-control-plane death)."""
        if not self.enabled:
            return
        phase = failure.phase if failure is not None else "unknown"
        host = str(failure.host_rank) if failure is not None else ""
        self._dead_labels = (phase, host)
        self._engine_dead.labels(
            model_name=self._model_name, phase=phase, host_rank=host
        ).set(1)

    # ---- supervised recovery hooks ----
    def record_restart(self) -> None:
        if self.enabled:
            self.engine_restarts.inc()

    def record_replayed(self, n: int) -> None:
        if self.enabled and n:
            self.requests_replayed.inc(n)

    def record_recovery_seconds(self, seconds: float) -> None:
        if self.enabled:
            self.recovery_seconds.observe(seconds)

    def record_engine_recovered(self) -> None:
        """Clear the dead gauge set by record_engine_dead (same label
        set, so dashboards see the incident close, not a new series)."""
        if not self.enabled or self._dead_labels is None:
            return
        phase, host = self._dead_labels
        self._engine_dead.labels(
            model_name=self._model_name, phase=phase, host_rank=host
        ).set(0)

    def record_finished(self, req_metrics, reason: str | None) -> None:
        if not self.enabled:
            return
        if req_metrics.finished_time_mono is not None:
            # Monotonic interval: immune to wall-clock (NTP) steps.
            self.e2e_latency.observe(
                req_metrics.finished_time_mono
                - req_metrics.arrival_time_mono
            )
        elif req_metrics.finished_time is not None:
            self.e2e_latency.observe(
                max(req_metrics.finished_time - req_metrics.arrival_time, 0.0)
            )
        self._success.labels(
            model_name=self._model_name, finished_reason=reason or "unknown"
        ).inc()
        # SLO/goodput accounting (ISSUE 12): attainment of this
        # request's class targets, from the same monotonic stamps.
        cls = self._slo_class(req_metrics)
        ttft_s = req_metrics.ttft
        ttft_ok, itl_ok, good = self.slo.record_finished(
            cls,
            ttft_s,
            req_metrics.slo_itl_max_s,
            req_metrics.slo_itl_buckets,
            reason,
        )
        labels = dict(model_name=self._model_name, slo_class=cls)
        self._slo_requests.labels(**labels).inc()
        if ttft_ok:
            self._slo_ttft_attained.labels(**labels).inc()
        if itl_ok:
            self._slo_itl_attained.labels(**labels).inc()
        if good:
            self._goodput_requests.labels(**labels).inc()
        # Fleet sentinel (ISSUE 20): cumulative (requests, goodput)
        # feeds the multi-window burn tracker; a paired-window breach
        # enters the timeline.
        requests, goodput = self.slo.class_counts(cls)
        for fired in self.burn.observe(cls, requests, goodput):
            self.events.emit("alert_slo_burn", **fired)

    # ---- XLA/device telemetry hooks (ISSUE 12), fed by
    # LLMEngine.refresh_device_telemetry from worker snapshots ----
    def record_worker_counter(self, key: str, label: str, n: float) -> None:
        """``n`` more of the worker counter ``key`` (a row of
        ``WORKER_COUNTERS``) under ``label``; a gauge row is set to it."""
        if not self.enabled or n <= 0:
            return
        row = WORKER_COUNTERS[key]
        labels = {"model_name": self._model_name}
        if row.label:
            labels[row.label] = label
        series = self._worker_counters[key].labels(**labels)
        if row.gauge:
            series.set(n)
        else:
            series.inc(n)

    def record_xla_compile_seconds(self, seconds: float) -> None:
        """Histogram fed from individual timed events (best-effort: the
        event ring is bounded, the counter above is the exact tally)."""
        if self.enabled:
            self.xla_compile_seconds.observe(max(seconds, 0.0))

    def record_device_snapshot(self, snap: dict) -> None:
        """Gauges from one worker DeviceTelemetry snapshot (compile
        events are folded separately so each is counted exactly once)."""
        if not self.enabled:
            return
        self.hbm_live_bytes.set(snap.get("hbm_live_bytes", 0) or 0)
        self.step_roofline_frac.set(snap.get("roofline_frac", 0.0) or 0.0)

    def slo_snapshot(self, include_timelines: bool = True) -> dict | None:
        """Replica /slo view (None while metrics are disabled)."""
        if not self.enabled:
            return None
        return self.slo.snapshot(include_timelines=include_timelines)

    def observe_span(self, name: str, duration: float) -> None:
        """Tracer metrics sink (tracing.Tracer.set_metrics_sink): every
        completed local span whose name maps to a per-stage histogram
        feeds it, so /metrics and /debug/traces share one measurement."""
        if not self.enabled:
            return
        attr = SPAN_METRIC_MAP.get(name)
        if attr is not None:
            getattr(self, attr).observe(max(duration, 0.0))

    def render(self) -> bytes:
        """Prometheus text exposition of this engine's registry."""
        if self.registry is None:
            return b"# metrics disabled (--disable-log-stats)\n"
        from prometheus_client import generate_latest

        # Sentinel gauges (ISSUE 20) are scrape-time views of the SLO
        # accounting, not event-driven — refresh them per render so the
        # burn decays as the windows slide even with no new requests.
        p99 = self.slo.itl_p99_ms()
        if p99 is not None:
            self.itl_p99_ms.set(p99)
        # The tracer's collection hook keeps plain totals (it may run
        # inside any lock, this registry's included); the counters
        # follow them here.
        for gen, (n, seconds) in list(get_tracer().gc_totals.items()):
            seen_n, seen_s = self._gc_seen.get(gen, (0, 0.0))
            labels = dict(model_name=self._model_name, generation=str(gen))
            self._gc_collections.labels(**labels).inc(max(n - seen_n, 0))
            self._gc_pause_seconds.labels(**labels).inc(
                max(seconds - seen_s, 0.0)
            )
            self._gc_seen[gen] = (n, seconds)
        for cls, rates in self.burn.snapshot().items():
            for window, value in rates.items():
                self._slo_burn.labels(
                    model_name=self._model_name,
                    slo_class=cls,
                    window=window,
                ).set(value)
        return generate_latest(self.registry)
