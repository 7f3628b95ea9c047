"""Request/step output types.

``ModelRunnerOutput`` is the per-step contract returned from workers to the
executor (the analog of vLLM's ModelRunnerOutput consumed at launch.py:46,
326).  ``RequestOutput``/``CompletionOutput`` are the user-facing results
streamed by the engine.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any


@dataclass
class ModelRunnerOutput:
    """What a worker returns from one execute_model step.

    Only the designated reply rank returns a populated instance; all other
    ranks return None (reference: launch.py:536-538).
    """

    # req_id -> newly sampled token ids this step (usually length 1).
    sampled_token_ids: dict[str, list[int]] = field(default_factory=dict)
    # req_id -> list of (token_id -> logprob) dicts, parallel to sampled ids.
    logprobs: dict[str, list[dict[int, float]]] = field(default_factory=dict)
    # req_id -> number of prompt tokens processed this step (chunked prefill).
    num_prompt_tokens_processed: dict[str, int] = field(default_factory=dict)
    # KV-connector progress (disaggregated prefill, SURVEY.md §3.4):
    # requests whose KV finished moving on THIS worker this step; the
    # executor-side KVOutputAggregator intersects across the world.
    kv_finished_sending: set[str] = field(default_factory=set)
    kv_finished_recving: set[str] = field(default_factory=set)
    # Tiered KV cache (ISSUE 14): wall seconds this worker spent
    # applying the step's spill/restore spans (device_get/device_put
    # batches) before executing — feeds vllm:kv_restore_seconds and the
    # engine.kv_restore trace span on restore-bearing steps.
    kv_tier_seconds: float = 0.0


@dataclass
class CompletionOutput:
    index: int
    text: str
    token_ids: list[int]
    cumulative_logprob: float | None = None
    logprobs: list[dict[int, float]] | None = None
    finish_reason: str | None = None  # "stop" | "length" | "abort"
    stop_reason: int | str | None = None

    @property
    def finished(self) -> bool:
        return self.finish_reason is not None


@dataclass
class RequestOutput:
    request_id: str
    prompt: str | None
    prompt_token_ids: list[int]
    outputs: list[CompletionOutput]
    finished: bool
    metrics: "RequestMetrics | None" = None
    # Set by AsyncLLM while tracing is on: the countdown of the engine
    # step's outputs this one left the engine with (``frontend.flush``).
    flush: Any = None
    # This output holds its request's first token (the engine stamped
    # ``first_token_time`` while building it): AsyncLLM hands such
    # outputs to their streams ahead of the rest of the step's.
    first_token: bool = False


@dataclass
class RequestMetrics:
    """Per-request timing.  Wall-clock stamps (`*_time`) exist for span
    start timestamps and human display; every INTERVAL (TTFT, ITL, e2e,
    stage durations) is computed from the `*_time_mono` monotonic
    counterparts so an NTP step can never produce negative or garbage
    latency observations."""

    arrival_time: float = 0.0
    first_scheduled_time: float | None = None
    first_token_time: float | None = None
    finished_time: float | None = None
    # Monotonic counterparts, used for all interval math.
    arrival_time_mono: float = 0.0
    first_scheduled_time_mono: float | None = None
    first_token_time_mono: float | None = None
    finished_time_mono: float | None = None
    last_token_time_mono: float | None = None
    # Prompt tokens already reported to vllm:prompt_tokens (prefill
    # progress is counted per processed step, remainder at first token).
    prompt_tokens_counted: int = 0
    # Tokens served from the prefix cache at the last admission
    # (0 unless --enable-prefix-caching hit; RequestOutput-visible).
    cached_tokens: int = 0
    # ---- SLO accounting (ISSUE 12, engine/slo.py) ----
    # Raw client-supplied class (slo_class sampling param / header) and
    # its sanitized, cardinality-bounded form (cached by EngineMetrics).
    slo_class: str = "default"
    slo_class_resolved: str | None = None
    # Worst observed inter-token interval (monotonic), and the
    # request's own log-bucket ITL tally — the per-request timeline the
    # fleet histogram merge is bit-recomputable from.
    slo_itl_max_s: float | None = None
    slo_itl_buckets: dict[int, int] | None = None

    @property
    def ttft(self) -> float | None:
        if self.first_token_time_mono is not None and self.arrival_time_mono:
            return self.first_token_time_mono - self.arrival_time_mono
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time
