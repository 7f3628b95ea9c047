"""Per-request state tracked by the scheduler/engine."""

from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field

from vllm_distributed_tpu.outputs import RequestMetrics
from vllm_distributed_tpu.sampling_params import SamplingParams


class RequestStatus(enum.Enum):
    WAITING = enum.auto()
    RUNNING = enum.auto()
    PREEMPTED = enum.auto()
    FINISHED_STOPPED = enum.auto()
    FINISHED_LENGTH = enum.auto()
    FINISHED_ABORTED = enum.auto()
    # Deadline expired: shed from waiting before prefill, or stopped
    # mid-decode with partial output (ISSUE 8).
    FINISHED_TIMEOUT = enum.auto()
    # Shed by the sustained-pressure preempt-to-shed policy: repeated
    # preemption under load degrades to a rejection, not allocator
    # thrash (ISSUE 8).
    FINISHED_SHED = enum.auto()

    @property
    def is_finished(self) -> bool:
        return self in (
            RequestStatus.FINISHED_STOPPED,
            RequestStatus.FINISHED_LENGTH,
            RequestStatus.FINISHED_ABORTED,
            RequestStatus.FINISHED_TIMEOUT,
            RequestStatus.FINISHED_SHED,
        )


FINISH_REASON = {
    RequestStatus.FINISHED_STOPPED: "stop",
    RequestStatus.FINISHED_LENGTH: "length",
    RequestStatus.FINISHED_ABORTED: "abort",
    RequestStatus.FINISHED_TIMEOUT: "timeout",
    RequestStatus.FINISHED_SHED: "overloaded",
}


@dataclass(eq=False)
class Request:
    request_id: str
    prompt_token_ids: list[int]
    sampling_params: SamplingParams
    prompt: str | None = None
    eos_token_id: int | None = None
    arrival_time: float = field(default_factory=time.monotonic)

    status: RequestStatus = RequestStatus.WAITING
    # All tokens = prompt + generated output.
    output_token_ids: list[int] = field(default_factory=list)
    # How many tokens have had their KV computed (chunked prefill cursor).
    num_computed_tokens: int = 0
    # Tokens scheduled to the device whose results have not been applied
    # yet, decode rows and prefill chunks alike (engine pipelining:
    # dispatch N+1 is issued before N's tokens arrive; a device carry
    # holds each row's last sampled token).
    num_inflight_tokens: int = 0
    # Page ids owned by this request, in order.
    page_ids: list[int] = field(default_factory=list)
    # After preemption-resume, KV for already-generated tokens must be
    # recomputed too; this is the token count to re-prefill up to.
    resume_target: int = 0
    metrics: RequestMetrics = field(default_factory=RequestMetrics)
    stop_reason: int | str | None = None
    # Cumulative logprobs bookkeeping (filled only when requested).
    logprobs: list[dict[int, float]] | None = None
    cumulative_logprob: float = 0.0
    # (trace_id, span_id) of the caller's root span (tracing.py); the
    # engine parents this request's queue/prefill/decode spans and
    # preemption/replay events to it.  None = untraced.
    trace_ctx: tuple | None = None
    # Monotonic instant this request must be finished by (ISSUE 8);
    # None = no deadline.  Set by LLMEngine.add_request from the
    # client's deadline_ms or the server default; checked cheaply at
    # schedule time.
    deadline_mono: float | None = None
    # Times this request was preempted (the preempt-to-shed policy's
    # thrash signal).
    num_preemptions: int = 0
    # Incrementally-maintained prompt+output concat (token_history);
    # None until first use.
    _hist: list[int] | None = field(default=None, repr=False)

    def __post_init__(self) -> None:
        self.metrics.arrival_time = time.time()
        self.metrics.arrival_time_mono = self.arrival_time
        # SLO class rides on RequestMetrics so the metrics layer can key
        # its per-class accounting without reaching back into params.
        self.metrics.slo_class = self.sampling_params.slo_class
        if self.sampling_params.logprobs is not None:
            self.logprobs = []

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def num_output_tokens(self) -> int:
        return len(self.output_token_ids)

    @property
    def num_tokens(self) -> int:
        return self.num_prompt_tokens + self.num_output_tokens

    @property
    def all_token_ids(self) -> list[int]:
        return self.prompt_token_ids + self.output_token_ids

    def token_history(self) -> list[int]:
        """Prompt+output as ONE list, maintained incrementally.

        The spec-decode proposer scans this every all-decode schedule;
        rebuilding the ``all_token_ids`` concat per request per step
        would put an O(context) copy on the scheduler hot path.  The
        cache extends by the appended delta (outputs only append
        between calls) and rebuilds outright when the output shrank
        (stop-string truncation).  Callers must treat the result as
        read-only."""
        want = self.num_prompt_tokens + self.num_output_tokens
        h = self._hist
        if h is None or len(h) > want:
            h = self.prompt_token_ids + self.output_token_ids
            self._hist = h
        elif len(h) < want:
            h.extend(
                self.output_token_ids[len(h) - self.num_prompt_tokens :]
            )
        return h

    @property
    def prefill_target(self) -> int:
        """Tokens whose KV is recomputed in (chunked) prefill before decode
        resumes: the prompt, or everything known at preemption time."""
        return max(self.num_prompt_tokens, self.resume_target)

    @property
    def is_prefill(self) -> bool:
        return self.num_computed_tokens < self.prefill_target

    @property
    def num_dispatched_tokens(self) -> int:
        """Tokens whose KV is computed or on its way: what the workers
        count as computed when the next dispatch is built."""
        return self.num_computed_tokens + self.num_inflight_tokens

    @property
    def prefill_dispatched(self) -> bool:
        """The whole prompt has been dispatched (settled or in flight):
        the next dispatch runs this request as a decode row."""
        return self.num_dispatched_tokens >= self.prefill_target

    @property
    def num_tokens_with_inflight(self) -> int:
        """``num_tokens`` once everything in flight has landed: every
        decode token in flight samples one; a prefill chunk brings none
        until it reaches the prompt's end, where it samples the first,
        and what was dispatched past that is decode."""
        if not self.is_prefill:
            return self.num_tokens + self.num_inflight_tokens
        past = self.num_dispatched_tokens - self.prefill_target
        return self.num_tokens + (past + 1 if past >= 0 else 0)

    @property
    def max_total_tokens(self) -> int:
        mt = self.sampling_params.max_tokens
        if mt is None:
            return 1 << 60
        return self.num_prompt_tokens + mt

    def set_deadline(self, default_deadline_ms: int) -> None:
        """Resolve the effective deadline: the client's deadline_ms
        sampling param, else the server default (0 = none).  Anchored
        to the monotonic arrival instant so NTP steps can't expire (or
        resurrect) a request."""
        ms = self.sampling_params.deadline_ms
        if ms is None and default_deadline_ms > 0:
            ms = default_deadline_ms
        if ms is not None:
            self.deadline_mono = self.arrival_time + ms / 1000.0

    def expired(self, now_mono: float) -> bool:
        return self.deadline_mono is not None and now_mono >= self.deadline_mono

    def append_output_token(self, token_id: int) -> None:
        self.output_token_ids.append(token_id)

    def check_stop(self, max_model_len: int) -> RequestStatus | None:
        """Returns a finished status if the request should stop, else None.
        Stop-string checking happens in the detokenizer, not here."""
        sp = self.sampling_params
        if self.num_output_tokens >= sp.min_tokens:
            last = self.output_token_ids[-1] if self.output_token_ids else None
            if (
                not sp.ignore_eos
                and self.eos_token_id is not None
                and last == self.eos_token_id
            ):
                self.stop_reason = None
                return RequestStatus.FINISHED_STOPPED
            if last is not None and last in sp.stop_token_ids:
                self.stop_reason = last
                return RequestStatus.FINISHED_STOPPED
        if self.num_tokens >= min(self.max_total_tokens, max_model_len):
            return RequestStatus.FINISHED_LENGTH
        return None
