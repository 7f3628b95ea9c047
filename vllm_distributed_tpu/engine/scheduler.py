"""Continuous-batching scheduler.

The engine-side capability the reference delegates to vLLM v1's scheduler
(SURVEY.md §2.3; the scheduler's product, `SchedulerOutput`, is exactly
what CustomExecutor.execute_model receives at launch.py:322).  Design:

- Single token budget per step (`max_num_batched_tokens`), shared by
  prefill and decode; chunked prefill lets long prompts trickle through
  without starving decodes (TPU-friendly: step shapes stay bounded, so the
  number of distinct compiled programs stays small).
- Workers mirror request state, so `SchedulerOutput` carries full data only
  for newly-scheduled requests and deltas for cached ones — matching the
  reference's control-plane economy (only small control messages cross
  hosts per step, SURVEY.md §2.5).
- Preemption by eviction: when KV pages run out, the lowest-priority
  running request is stopped, its pages freed, and it re-enters the
  waiting queue for full recompute (same policy family as vLLM's
  recompute preemption).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

from vllm_distributed_tpu.config import CacheConfig, SchedulerConfig
from vllm_distributed_tpu.engine.block_manager import (
    NoFreePagesError,
    PageAllocator,
    PrefixCachingAllocator,
    RadixPrefixCachingAllocator,
)
from vllm_distributed_tpu.engine.qos import QosRegistry
from vllm_distributed_tpu.engine.request import Request, RequestStatus
from vllm_distributed_tpu.engine.spec_decode import spec_eligible
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.tracing import get_tracer

logger = init_logger(__name__)

# Spec-decode pipelining hysteresis (ISSUE 11): after this many
# consecutive draftless spec-eligible schedules the engine resumes
# async dispatch pipelining (spec dormant)...
_SPEC_DRY_LIMIT = 4
# ...and drains it for one probing schedule every this many pipelined
# schedules, so a workload that turns repetitive re-engages spec.
_SPEC_PROBE_INTERVAL = 16


@dataclass
class NewRequestData:
    req_id: str
    # On preemption-resume this includes previously generated tokens (the
    # worker re-prefills them); num_prompt_tokens marks the true
    # prompt/output boundary so penalties stay correct across preemption.
    prompt_token_ids: list[int]
    num_prompt_tokens: int
    page_ids: list[int]
    num_computed_tokens: int
    num_new_tokens: int
    sampling_params: SamplingParams


@dataclass
class CachedRequestData:
    req_id: str
    new_page_ids: list[int]
    num_computed_tokens: int
    num_new_tokens: int


@dataclass
class SchedulerOutput:
    """One step's worth of work, shipped to every worker."""

    step_id: int
    new_requests: list[NewRequestData] = field(default_factory=list)
    cached_requests: list[CachedRequestData] = field(default_factory=list)
    # req_id -> num tokens to run this step (prefill chunk len, or the
    # decode_steps fused this dispatch).
    num_scheduled_tokens: dict[str, int] = field(default_factory=dict)
    total_num_scheduled_tokens: int = 0
    finished_req_ids: list[str] = field(default_factory=list)
    preempted_req_ids: list[str] = field(default_factory=list)
    # >1 = every scheduled request is a decode and the worker runs this
    # many fused decode micro-steps on device (one sampled token each).
    decode_steps: int = 1
    # Speculative decoding (ISSUE 11): req_id -> drafted tokens to
    # verify this step.  Non-empty marks a spec verify step: every
    # scheduled request is a decode, its num_scheduled_tokens is
    # 1 + len(drafts) (the step's input token + the drafts), the worker
    # verifies all drafts in ONE fused pass, and the ACTUAL per-request
    # advance (1 + accepted drafts) is reconciled in update_from_output
    # from the emitted token count.  decode_steps is always 1 here.
    draft_token_ids: dict[str, list[int]] = field(default_factory=dict)
    # Tiered KV cache (ISSUE 14): (hbm_page, host_slot) spans whose KV
    # the workers copy out to host DRAM, and (host_slot, hbm_page)
    # spans they stream back, BEFORE executing this step — spills must
    # land before the evicted page is overwritten, restores before the
    # restored pages are read.  Applied in order: all spills, then all
    # restores.
    kv_spill_ops: list[tuple[int, int]] = field(default_factory=list)
    kv_restore_ops: list[tuple[int, int]] = field(default_factory=list)
    # Trace context of the first scheduled traced request, if any: the
    # parent for this step's schedule/dispatch/gather spans (a step
    # serves a batch, so one trace adopts the step; the others link via
    # the schedule span's batch attributes).
    trace_ctx: tuple | None = None

    @property
    def is_empty(self) -> bool:
        return self.total_num_scheduled_tokens == 0


class Scheduler:
    def __init__(
        self,
        scheduler_config: SchedulerConfig,
        cache_config: CacheConfig,
        num_pages: int,
    ) -> None:
        self.config = scheduler_config
        self.page_size = cache_config.page_size
        # Prefix caching swaps the allocator behind the same interface;
        # with the flag off the seed allocator (and behaviour) is
        # untouched.  The radix index (ISSUE 14) is the default cache;
        # "flat" keeps the PR 1 hash-chain as the tests' reference index.
        self.enable_prefix_caching = cache_config.enable_prefix_caching
        if not self.enable_prefix_caching:
            self.allocator = PageAllocator(num_pages, cache_config.page_size)
        elif cache_config.prefix_cache_index == "flat":
            self.allocator = PrefixCachingAllocator(
                num_pages, cache_config.page_size
            )
        else:
            self.allocator = RadixPrefixCachingAllocator(
                num_pages,
                cache_config.page_size,
                host_pages=cache_config.kv_spill_host_pages,
                restore_min_tokens=cache_config.kv_spill_restore_min_tokens,
            )
        self._tiered = isinstance(
            self.allocator, RadixPrefixCachingAllocator
        )
        # Bounded upstream by the AdmissionController caps when
        # configured (engine/overload.py); unbounded growth is the
        # operator's explicit choice via max_waiting_requests=0.
        # vdt-lint: disable=unbounded-queue — bound enforced at admission
        self.waiting: deque[Request] = deque()
        # Prompt tokens awaiting (re-)prefill across self.waiting — an
        # integer mirror maintained at every waiting mutation so the
        # event-loop admission check reads one int instead of iterating
        # a deque the engine thread mutates (ISSUE 8).
        self.num_waiting_tokens = 0
        self.running: list[Request] = []
        self.requests: dict[str, Request] = {}
        self._step_id = 0
        # Finished/preempted since last step, to notify workers.
        self._finished_since_last: list[str] = []
        # Notices that rode an EMPTY SchedulerOutput: the engine never
        # dispatches empty steps, so without holding them here the
        # workers would silently keep mirrored state for finished/
        # preempted requests forever (and the step-delta encoder would
        # desynchronize from the worker mirrors).
        self._held_notices: tuple[list[str], list[str]] | None = None
        # Requests that finished/aborted while LATER steps containing
        # them were still in flight on the device: their KV pages are
        # freed only once every in-flight step has drained, so the
        # device can never be writing into pages the allocator has
        # already handed to another request (async-scheduling
        # reconciliation, ISSUE 7).
        self._deferred_frees: dict[str, Request] = {}
        # Cumulative preemption count (metrics, SURVEY.md §5.5).
        self.num_preemptions = 0
        # Cumulative prefix-cache token counters (metrics): tokens
        # eligible for lookup at admission vs tokens served from cache.
        # `prefix_cache_hits` is the TOTAL across tiers;
        # `prefix_cache_hits_host` is the host-restored share of it.
        self.prefix_cache_queries = 0
        self.prefix_cache_hits = 0
        self.prefix_cache_hits_host = 0
        # Cumulative tier-traffic counters (ISSUE 14 metrics).
        self.kv_spill_pages = 0
        self.kv_restore_pages = 0
        # Tier-op spans produced by a schedule whose output came up
        # EMPTY (e.g. the triggering admission rolled back): held for
        # the next step that actually reaches the workers, exactly like
        # _held_notices — a spill must still beat any later reuse of
        # its source page.
        self._held_tier_ops: tuple[list, list] | None = None
        # Requests finished OUTSIDE update_from_output (deadline sheds,
        # preempt-to-shed): the engine drains this after each schedule
        # and emits their final RequestOutputs (ISSUE 8).
        self._finished_out_of_band: list[Request] = []
        # Cumulative overload counters (metrics).
        self.num_timeouts = 0
        self.num_sheds = 0
        # True while any live request carries a deadline (sticky; reset
        # when the scheduler empties) so deadline enforcement costs one
        # attribute read per step when unused.
        self._has_deadlines = False
        # Speculative decoding (ISSUE 11): the n-gram prompt-lookup
        # proposer, built only when --speculative-ngram-k > 0 so the
        # default path pays one attribute read per step.
        self.spec = None
        if scheduler_config.spec_ngram_k > 0:
            from vllm_distributed_tpu.engine.spec_decode import (
                NgramProposer,
            )

            self.spec = NgramProposer(
                scheduler_config.spec_ngram_k,
                min_n=scheduler_config.spec_ngram_min,
                max_n=scheduler_config.spec_ngram_max,
            )
        # Cumulative spec-decode token counters (metrics): tokens
        # drafted into verify passes vs drafts accepted by them.
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        # Pipelining hysteresis state (see spec_wants_sync).
        self._spec_dry_streak = 0
        self._spec_pipeline_steps = 0
        # Disaggregated prefill (ISSUE 15): installed by LLMEngine when
        # the engine builds its KVTransferManager.  None = the finish
        # path below never holds pages (seed behavior); while idle the
        # manager costs one attribute read per schedule.
        self.kv_transfer = None
        # QoS control plane (ISSUE 16): class registry driving priority
        # admission order, class-weighted preemption, and the per-class
        # waiting mirrors the admission shares read.  Disabled (the
        # default) keeps every decision on the seed code path.
        self.qos = QosRegistry.parse(scheduler_config.qos_classes)
        # Per-class mirrors of len(waiting)/num_waiting_tokens, keyed by
        # the RESOLVED class bucket (unknown names fold into "default",
        # so the keyspace is capped by the registry).  Maintained only
        # while QoS is enabled.
        self.waiting_by_class: dict[str, int] = {}
        self.waiting_tokens_by_class: dict[str, int] = {}
        # Cumulative per-class preempt/shed counters (QoS only): the
        # acceptance evidence that evictions land on the lowest class.
        self.preemptions_by_class: dict[str, int] = {}
        self.sheds_by_class: dict[str, int] = {}

    # ---- QoS lookups (ISSUE 16) ----
    def _qos_priority(self, req: Request) -> int:
        return self.qos.resolve(req.sampling_params.slo_class).priority

    def _qos_bucket(self, req: Request) -> str:
        return self.qos.resolve(req.sampling_params.slo_class).name

    # ---- waiting-queue mutation (ALL of it goes through these three
    # helpers so num_waiting_tokens can never drift from the deque) ----
    def _waiting_push(self, req: Request, left: bool = False) -> None:
        if left:
            self.waiting.appendleft(req)
        else:
            self.waiting.append(req)
        tokens = req.prefill_target - req.num_computed_tokens
        self.num_waiting_tokens += tokens
        if self.qos.enabled:
            cls = self._qos_bucket(req)
            self.waiting_by_class[cls] = (
                self.waiting_by_class.get(cls, 0) + 1
            )
            self.waiting_tokens_by_class[cls] = (
                self.waiting_tokens_by_class.get(cls, 0) + tokens
            )

    def _waiting_pop(self, req: Request, popleft: bool = False) -> None:
        if popleft:
            self.waiting.popleft()
        else:
            self.waiting.remove(req)
        tokens = req.prefill_target - req.num_computed_tokens
        self.num_waiting_tokens = max(
            self.num_waiting_tokens - tokens, 0
        )
        if self.qos.enabled:
            cls = self._qos_bucket(req)
            self.waiting_by_class[cls] = max(
                self.waiting_by_class.get(cls, 0) - 1, 0
            )
            self.waiting_tokens_by_class[cls] = max(
                self.waiting_tokens_by_class.get(cls, 0) - tokens, 0
            )

    # ---- intake ----
    def add_request(self, req: Request) -> None:
        # A request that can never fit in the page pool would wait forever;
        # reject it up front. +1 covers the first sampled token's slot.
        usable_pages = self.allocator.num_pages - 1
        max_len = min(req.max_total_tokens, self.config.max_model_len)
        if self.allocator.num_pages_needed(max_len) > usable_pages:
            raise ValueError(
                f"request {req.request_id} needs up to {max_len} KV slots "
                f"({self.allocator.num_pages_needed(max_len)} pages) but the "
                f"cache holds only {usable_pages} pages of "
                f"{self.page_size} slots"
            )
        if req.num_prompt_tokens >= self.config.max_model_len:
            raise ValueError(
                f"prompt of request {req.request_id} has "
                f"{req.num_prompt_tokens} tokens, exceeding max_model_len "
                f"{self.config.max_model_len}"
            )
        if req.num_prompt_tokens == 0:
            raise ValueError(f"request {req.request_id} has an empty prompt")
        if (
            not self.config.enable_chunked_prefill
            and req.num_prompt_tokens > self.config.max_num_batched_tokens
        ):
            raise ValueError(
                f"prompt of request {req.request_id} has "
                f"{req.num_prompt_tokens} tokens but chunked prefill is "
                f"disabled and the step budget is "
                f"{self.config.max_num_batched_tokens}"
            )
        self.requests[req.request_id] = req
        if req.deadline_mono is not None:
            self._has_deadlines = True
        self._waiting_push(req)

    def abort_request(self, req_id: str) -> None:
        req = self.requests.get(req_id)
        if req is None or req.status.is_finished:
            return
        req.status = RequestStatus.FINISHED_ABORTED
        if req in self.running:
            self.running.remove(req)
            self._finished_since_last.append(req_id)
        elif req in self.waiting:
            self._waiting_pop(req)
        self._release_or_defer(req)
        del self.requests[req_id]

    def _release_or_defer(self, req: Request) -> None:
        """Free a finished request's pages — unless a later in-flight
        step still references them (pipelined scheduling ran ahead of
        this finish), in which case the free waits for those steps to
        drain (``update_from_output`` settles the debt).  A finishing
        prefill-only request (ISSUE 15) instead HOLDS its pages for the
        router's KV-page export; the hold's release (or TTL expiry)
        comes back through ``release_hold_pages``."""
        if (
            self.kv_transfer is not None
            and req.num_inflight_tokens == 0
            and self.kv_transfer.wants_hold(req)
        ):
            self.kv_transfer.hold(req)
            return
        if req.num_inflight_tokens > 0:
            self._deferred_frees[req.request_id] = req
        else:
            self.allocator.free(req)

    def release_hold_pages(self, req: Request) -> None:
        """Free a KV-export hold's pages (engine/kv_transfer.py calls
        this on release/expiry — the deferred path mirrors
        _release_or_defer for safety, though a held prefill never has
        steps in flight)."""
        if req.num_inflight_tokens > 0:
            self._deferred_frees[req.request_id] = req
        else:
            self.allocator.free(req)

    @property
    def num_unfinished(self) -> int:
        return len(self.waiting) + len(self.running)

    @property
    def kv_cache_usage(self) -> float:
        """Fraction of usable KV pages held by live requests (cached
        pages awaiting reuse count as free — they are evictable)."""
        usable = self.allocator.num_pages - 1  # page 0 reserved
        return 1.0 - self.allocator.num_free_pages / max(usable, 1)

    def has_unfinished_requests(self) -> bool:
        return self.num_unfinished > 0

    # ---- deadlines + load shedding (ISSUE 8) ----
    def _shed_expired(self, now_mono: float) -> None:
        """Enforce per-request deadlines at schedule time (the cheap
        place: one monotonic read, two short scans).  Expired WAITING
        requests are shed before any prefill is spent on them — the
        workers never knew them (or already dropped them at preemption),
        so no notice is emitted.  Expired RUNNING requests finish with
        finish_reason="timeout" and whatever partial output they have;
        their finish notice rides this step's output like any other
        finish."""
        for req in [r for r in self.waiting if r.expired(now_mono)]:
            self._waiting_pop(req)
            req.status = RequestStatus.FINISHED_TIMEOUT
            self._release_or_defer(req)
            del self.requests[req.request_id]
            self._finished_out_of_band.append(req)
            self.num_timeouts += 1
            get_tracer().event(
                req.trace_ctx,
                "engine.deadline_shed",
                request_id=req.request_id,
                stage="waiting",
            )
        for req in [r for r in self.running if r.expired(now_mono)]:
            self.running.remove(req)
            req.status = RequestStatus.FINISHED_TIMEOUT
            self._finished_since_last.append(req.request_id)
            self._release_or_defer(req)
            del self.requests[req.request_id]
            self._finished_out_of_band.append(req)
            self.num_timeouts += 1
            get_tracer().event(
                req.trace_ctx,
                "engine.deadline_shed",
                request_id=req.request_id,
                stage="running",
                num_output_tokens=req.num_output_tokens,
            )

    def take_finished_out_of_band(self) -> list[Request]:
        """Drain requests finished outside update_from_output (deadline
        sheds, preempt-to-shed) so the engine can emit their final
        outputs."""
        if not self._finished_out_of_band:
            return []
        out, self._finished_out_of_band = self._finished_out_of_band, []
        return out

    # ---- the step ----
    def schedule(self) -> SchedulerOutput:
        # Sticky flag, not a per-step scan: with no deadlines anywhere
        # (the default) this is one attribute read per step.
        if self._has_deadlines:
            if self.requests:
                self._shed_expired(time.monotonic())
            else:
                self._has_deadlines = False
        if self.kv_transfer is not None and self.kv_transfer.active:
            # Disagg TTL guard (ISSUE 15): expired export holds and
            # orphaned imports free their pages here — a router that
            # died mid-hand-off can never leak pool capacity.
            self.kv_transfer.sweep(time.monotonic())
        out = SchedulerOutput(step_id=self._step_id)
        self._step_id += 1
        out.finished_req_ids = self._finished_since_last
        self._finished_since_last = []

        token_budget = self.config.max_num_batched_tokens

        # Chunked-prefill fairness budget (ISSUE 16): while any
        # decode-bound request of higher-or-equal class is running,
        # prefill chunks collectively take at most qos_prefill_share of
        # the step budget, so a 32k-token prefill can no longer starve
        # decode ITL on a mixed replica.  Work-conserving: with no
        # qualifying decode running, prefill fills whatever budget is
        # left — exactly the seed policy.  Off (share=0, the default)
        # this whole block is two config reads.
        prefill_cap: int | None = None
        max_decode_prio = 0
        if (
            self.config.enable_chunked_prefill
            and 0.0 < self.config.qos_prefill_share < 1.0
        ):
            decode_prios = [
                self._qos_priority(r) if self.qos.enabled else 0
                for r in self.running
                if r.prefill_dispatched
            ]
            if decode_prios:
                max_decode_prio = max(decode_prios)
                prefill_cap = max(
                    int(
                        self.config.qos_prefill_share
                        * self.config.max_num_batched_tokens
                    ),
                    1,
                )
        prefill_used = 0

        # Multi-step decode: when the whole batch is decoding and nothing
        # is waiting to be admitted, fuse K decode steps into one device
        # dispatch.  K is UNIFORM (the configured value, clamped only by
        # the shared token budget): a request whose remaining length
        # budget is under K is scheduled with num_new < K and the worker
        # masks its trailing micro-steps on device.  This keeps ONE
        # compiled scan length per config — the r4 design derived K from
        # min(remaining room), so every request tail walked K down
        # through 8/4/2/1 and compiled a fresh multi-second program
        # mid-serve (measured 14-23 s each on v5e).  Logprobs force K=1
        # (per-step [S, V] logprob fetches don't amortize).
        #
        # Speculative decoding (ISSUE 11) takes precedence on the same
        # all-decode precondition: when the n-gram proposer drafts for
        # at least one request, the step becomes a single-dispatch
        # verify pass (decode_steps=1, per-request num_new = 1+drafts)
        # instead of a K-step scan — one HBM pass for up to K+1 tokens
        # rather than one per token.  Steps where nothing drafts fall
        # back to the fused scan, so non-repetitive stretches keep the
        # fused-decode throughput.
        # In-flight work counts (pipelining): a request whose last prompt
        # chunk is on the device is a decode row of this schedule, its
        # input token taken from the device carry.
        decode_only = bool(
            self.running
            and not self.waiting
            and all(r.prefill_dispatched for r in self.running)
            and all(
                r.sampling_params.logprobs is None for r in self.running
            )
        )
        spec_drafts = self._propose_drafts() if decode_only else {}
        k = 1
        if (
            not spec_drafts
            and decode_only
            and self.config.num_decode_steps > 1
        ):
            k = self.config.fused_decode_steps()
        out.decode_steps = k

        # 1) decodes + in-flight chunked prefills, in arrival order.
        #    Iterate over a copy: preemption mutates self.running.
        scheduled_running: list[Request] = []
        preempted: set[str] = set()
        for req in list(self.running):
            if req.request_id in preempted:
                continue
            if token_budget <= 0:
                break
            drafts = None
            is_prefill = not req.prefill_dispatched
            if is_prefill:
                remaining = req.prefill_target - req.num_dispatched_tokens
                chunk = min(remaining, token_budget)
                if (
                    prefill_cap is not None
                    and self._qos_priority(req) <= max_decode_prio
                ):
                    chunk = min(chunk, prefill_cap - prefill_used)
                    if chunk <= 0:
                        continue
                if not self.config.enable_chunked_prefill and chunk < remaining:
                    continue
                num_new = chunk
            else:
                # Skip decodes that already have their whole remaining
                # budget in flight (pipelining: results not applied yet).
                room = self.decode_room(req)
                if room <= 0:
                    continue
                drafts = spec_drafts.get(req.request_id)
                if drafts is not None and token_budget <= len(drafts):
                    # The shared budget cuts this verify window short;
                    # trim drafts rather than overrun the step budget.
                    drafts = drafts[: token_budget - 1] or None
                if drafts is not None:
                    # Spec verify window: the input token + the drafts
                    # (already room-capped at proposal time).
                    num_new = 1 + len(drafts)
                else:
                    # Under-K tails are masked on device, not given
                    # their own scan length (see the K comment above).
                    num_new = min(k, room)
            got = self._allocate_or_preempt(
                req,
                req.num_inflight_tokens + num_new,
                preempted,
                scheduled_running,
            )
            if not got:
                continue
            new_pages = got[1]
            out.num_scheduled_tokens[req.request_id] = num_new
            out.total_num_scheduled_tokens += num_new
            token_budget -= num_new
            if out.trace_ctx is None:
                out.trace_ctx = req.trace_ctx
            out.cached_requests.append(
                CachedRequestData(
                    req_id=req.request_id,
                    new_page_ids=new_pages,
                    # The worker's view of "computed" at dispatch time
                    # includes tokens still in flight on the device.
                    num_computed_tokens=req.num_dispatched_tokens,
                    num_new_tokens=num_new,
                )
            )
            if drafts is not None:
                out.draft_token_ids[req.request_id] = drafts
                self.spec_drafted_tokens += len(drafts)
            if is_prefill:
                prefill_used += num_new
            req.num_inflight_tokens += num_new
            scheduled_running.append(req)

        # 2) admit waiting requests while budget and seats remain.
        while (
            self.waiting
            and token_budget > 0
            and len(self.running) < self.config.max_num_seqs
        ):
            req = self.waiting[0]
            if self.qos.enabled:
                # Priority admission (ISSUE 16): highest class first,
                # FIFO within a class (the deque IS arrival order).
                # Strict: a blocked high-class head blocks lower
                # classes too — borrowing happens at admission control,
                # not by reordering around a starved guarantee.
                req = self._pick_waiting()
            if req.request_id in preempted:
                break  # do not resume a request preempted this same step
            # Prefix cache: a request without pages resumes after the
            # longest cached page chain matching its tokens (pure query;
            # state changes only on actual admission below).  Covers
            # preemption-resume too — content addressing makes a
            # request's own earlier pages an ordinary hit.  With the
            # tiered radix index the hit may extend into the host-DRAM
            # tier: a host run at/above the restore crossover is
            # streamed back into fresh pages and counted as computed;
            # below it those tokens are simply recomputed.
            hit_tokens, hit_pages, plan, restore = 0, [], None, False
            if (
                self.enable_prefix_caching
                and req.num_computed_tokens == 0
                and not req.page_ids
            ):
                if self._tiered:
                    plan = self.allocator.plan_prefix(req)
                    restore = (
                        plan.host_tokens > 0
                        and plan.host_tokens
                        >= self.allocator.restore_min_tokens
                    )
                    hit_tokens = plan.resident_tokens + (
                        plan.host_tokens if restore else 0
                    )
                else:
                    hit_tokens, hit_pages = self.allocator.query_prefix(req)
            remaining_prompt = (
                req.prefill_target - req.num_computed_tokens - hit_tokens
            )
            num_new = min(remaining_prompt, token_budget)
            if (
                prefill_cap is not None
                and self._qos_priority(req) <= max_decode_prio
            ):
                num_new = min(num_new, prefill_cap - prefill_used)
            if num_new <= 0:
                break
            if not self.config.enable_chunked_prefill:
                if remaining_prompt > token_budget:
                    break
                num_new = remaining_prompt
            # Admission: don't preempt running requests for new ones.
            if plan is not None and hit_tokens:
                ok = self.allocator.can_admit_plan(plan, num_new, restore)
            elif hit_pages:
                ok = self.allocator.can_allocate_with_prefix(
                    hit_pages, hit_tokens + num_new
                )
            else:
                ok = self.allocator.can_allocate(req, num_new)
            if not ok:
                break
            self._waiting_pop(req, popleft=req is self.waiting[0])
            host_hit = 0
            try:
                if self.enable_prefix_caching and hit_tokens:
                    if plan is not None:
                        restored = self.allocator.attach_plan(
                            req, plan, restore
                        )
                        host_hit = restored * self.page_size
                        self.kv_restore_pages += restored
                    else:
                        self.allocator.attach_prefix(req, hit_pages)
                    # The chunked-prefill path resumes from here, so the
                    # model runner gets the partial prefill for free.
                    req.num_computed_tokens = hit_tokens
                new_pages = self.allocator.allocate(req, num_new)
            except NoFreePagesError:
                # The admission estimate can over-count free capacity in
                # a rare radix corner (an unreffed interior above a
                # reffed duplicate-content chain).  Roll back cleanly:
                # the request re-queues untouched and this schedule
                # stops admitting.
                self.allocator.free(req)
                req.num_computed_tokens = 0
                self._waiting_push(req, left=True)
                break
            if self.enable_prefix_caching:
                self.prefix_cache_queries += req.prefill_target
                self.prefix_cache_hits += hit_tokens
                self.prefix_cache_hits_host += host_hit
                req.metrics.cached_tokens = hit_tokens
            if req.status == RequestStatus.WAITING:
                req.metrics.first_scheduled_time = time.time()
                req.metrics.first_scheduled_time_mono = time.monotonic()
            resumed = req.status == RequestStatus.PREEMPTED
            req.status = RequestStatus.RUNNING
            self.running.append(req)
            out.num_scheduled_tokens[req.request_id] = num_new
            out.total_num_scheduled_tokens += num_new
            token_budget -= num_new
            if out.trace_ctx is None:
                out.trace_ctx = req.trace_ctx
            out.new_requests.append(
                NewRequestData(
                    req_id=req.request_id,
                    # On preemption-resume the worker state was dropped, so
                    # resend everything incl. generated tokens.
                    prompt_token_ids=req.all_token_ids
                    if resumed
                    else req.prompt_token_ids,
                    num_prompt_tokens=req.num_prompt_tokens,
                    page_ids=list(req.page_ids),
                    num_computed_tokens=req.num_computed_tokens,
                    num_new_tokens=num_new,
                    sampling_params=req.sampling_params,
                )
            )
            prefill_used += num_new
            req.num_inflight_tokens += num_new

        # Tiered KV (ISSUE 14): ship the spill/restore spans this
        # schedule produced (evictions during allocate, restores during
        # attach) on this step — ahead of the step's own KV writes.
        if self._tiered:
            spill_ops, restore_ops = self.allocator.take_tier_ops()
            self.kv_spill_pages += len(spill_ops)
            if self._held_tier_ops is not None:
                held_s, held_r = self._held_tier_ops
                self._held_tier_ops = None
                spill_ops = held_s + spill_ops
                restore_ops = held_r + restore_ops
            if spill_ops or restore_ops:
                if out.is_empty:
                    # Empty outputs are never dispatched — hold the
                    # spans for the next step that reaches the workers.
                    self._held_tier_ops = (spill_ops, restore_ops)
                else:
                    out.kv_spill_ops = spill_ops
                    out.kv_restore_ops = restore_ops
                    # Slots consumed by shipped restores become
                    # reusable for FUTURE spill batches only.
                    self.allocator.release_shipped_slots()

        out.preempted_req_ids = sorted(preempted)
        if self._held_notices is not None:
            held_fin, held_pre = self._held_notices
            self._held_notices = None
            out.finished_req_ids = held_fin + out.finished_req_ids
            out.preempted_req_ids = held_pre + [
                p for p in out.preempted_req_ids if p not in held_pre
            ]
        if out.is_empty and (out.finished_req_ids or out.preempted_req_ids):
            # Empty outputs are never dispatched — hold the notices for
            # the next step that actually reaches the workers.
            self._held_notices = (
                out.finished_req_ids, out.preempted_req_ids
            )
            out.finished_req_ids = []
            out.preempted_req_ids = []
        return out

    def decode_room(self, req: Request) -> int:
        """Tokens ``req`` may still be given, what is in flight counted
        as spent: 0 or less once its whole budget is on the device."""
        return (
            min(req.max_total_tokens, self.config.max_model_len)
            - req.num_tokens_with_inflight
        )

    def _propose_drafts(self) -> dict[str, list[int]]:
        """N-gram prompt-lookup proposals for an all-decode step
        (ISSUE 11).  Returns {} unless spec decode is enabled, every
        running request is spec-eligible (greedy, no penalties — the
        gate is batch-wide so one compiled verify program serves the
        step), the pipeline is drained (the proposer and the verify
        input need the host-current last token), and at least one
        request found a draftable tail n-gram."""
        if self.spec is None:
            return {}
        if any(
            not spec_eligible(r.sampling_params) for r in self.running
        ):
            return {}
        if any(r.num_inflight_tokens > 0 for r in self.running):
            # Pipelined continuation (spec dormant): host tokens are
            # stale, so no proposals — count toward the probe cadence.
            self._spec_pipeline_steps += 1
            return {}
        self._spec_pipeline_steps = 0
        drafts: dict[str, list[int]] = {}
        for r in self.running:
            room = (
                min(r.max_total_tokens, self.config.max_model_len)
                - r.num_tokens
            )
            if room <= 1:
                continue  # no space for a draft beyond the bonus token
            d = self.spec.propose(r.token_history(), room - 1)
            if d:
                drafts[r.request_id] = d
        if drafts:
            self._spec_dry_streak = 0
        else:
            self._spec_dry_streak += 1
        return drafts

    def spec_wants_sync(self) -> bool:
        """Pipelining hysteresis (ISSUE 11): True while the engine
        should drain dispatches before each schedule so the proposer
        sees host-current tokens.  While prompt-lookup keeps drafting,
        the verify pass is the latency hider and every step runs
        synchronously; after ``_SPEC_DRY_LIMIT`` consecutive draftless
        eligible schedules the engine resumes the async dispatch
        pipeline (spec dormant — non-repetitive greedy traffic keeps
        the PR 6 overlap instead of silently regressing below the
        spec-off baseline), draining once every
        ``_SPEC_PROBE_INTERVAL`` pipelined schedules to re-probe for
        drafts.  Pure read: call sites may invoke it multiple times
        per step."""
        if self._spec_dry_streak < _SPEC_DRY_LIMIT:
            return True
        return self._spec_pipeline_steps >= _SPEC_PROBE_INTERVAL

    def _pick_waiting(self) -> Request:
        """QoS admission order (ISSUE 16): the highest-priority class's
        oldest waiting request.  A forward scan keeping the FIRST max
        preserves FIFO within each class, so equal-priority traffic
        behaves exactly like the seed deque."""
        best = self.waiting[0]
        best_prio = self._qos_priority(best)
        for cand in self.waiting:
            p = self._qos_priority(cand)
            if p > best_prio:
                best, best_prio = cand, p
        return best

    def _allocate_or_preempt(
        self,
        req: Request,
        num_new: int,
        preempted: set[str],
        scheduled_this_step: list[Request],
    ) -> tuple[bool, list[int]] | None:
        """Allocate pages for req, evicting lower-priority running requests
        if needed. Returns (True, new_pages) or None if req itself could not
        be scheduled (it was preempted).

        A request already scheduled this step must never be chosen as the
        victim: its page ids are already baked into the SchedulerOutput, so
        freeing them would hand the same pages to two requests.
        """
        while True:
            try:
                return True, self.allocator.allocate(req, num_new)
            except NoFreePagesError:
                victim = self._pick_victim(
                    req, preempted, scheduled_this_step
                )
                if victim is None:
                    # Preempt req itself.
                    self._preempt(req, preempted)
                    return None
                self._preempt(victim, preempted)

    def _pick_victim(
        self,
        req: Request,
        preempted: set[str],
        scheduled_this_step: list[Request],
    ) -> Request | None:
        """Eviction victim for req's allocation.  Seed policy: the most
        recently admitted eligible request.  Under QoS (ISSUE 16) the
        LOWEST class goes first (recency breaks ties within a class),
        and a victim of strictly higher class than the requester is
        never evicted — the requester yields instead, so low-class
        pressure can't thrash high-class decodes."""
        if not self.qos.enabled:
            for cand in reversed(self.running):
                if (
                    cand is not req
                    and cand.request_id not in preempted
                    and cand not in scheduled_this_step
                ):
                    return cand
            return None
        victim = None
        victim_prio = 0
        for idx, cand in enumerate(self.running):
            if (
                cand is req
                or cand.request_id in preempted
                or cand in scheduled_this_step
            ):
                continue
            p = self._qos_priority(cand)
            if victim is None or p < victim_prio:
                victim, victim_prio = cand, p
            elif p == victim_prio:
                victim = cand  # later index: recency within the class
        if victim is not None and victim_prio > self._qos_priority(req):
            return None
        return victim

    def _preempt(self, req: Request, preempted: set[str]) -> None:
        logger.debug("preempting request %s", req.request_id)
        self.num_preemptions += 1
        req.num_preemptions += 1
        get_tracer().event(
            req.trace_ctx,
            "engine.preempted",
            request_id=req.request_id,
            num_tokens=req.num_tokens,
        )
        self.allocator.free(req)
        req.num_computed_tokens = 0
        # In-flight sampled tokens are lost on preemption; the request
        # re-prefills to what the host has and regenerates (same PRNG
        # stream position, so seeded sampling is unaffected).
        req.num_inflight_tokens = 0
        req.resume_target = req.num_tokens
        if req in self.running:
            self.running.remove(req)
        # Workers drop state on preempted_req_ids in this step's output;
        # no entry in _finished_since_last (it would collide with the
        # request's own resume in a later step's new_requests).
        preempted.add(req.request_id)
        if self.qos.enabled:
            cls = self._qos_bucket(req)
            self.preemptions_by_class[cls] = (
                self.preemptions_by_class.get(cls, 0) + 1
            )
        shed_after = self.config.preempt_shed_threshold
        if shed_after > 0 and self.qos.enabled:
            # Preemption weight scales the shed budget: a 0.5-weight
            # class degrades to rejection after half the evictions, a
            # 2.0-weight class rides out twice as many.
            shed_after = max(
                int(
                    round(
                        shed_after
                        * self.qos.resolve(
                            req.sampling_params.slo_class
                        ).preemption_weight
                    )
                ),
                1,
            )
        if shed_after > 0 and req.num_preemptions > shed_after:
            # Sustained-pressure preempt-to-shed (ISSUE 8): this request
            # has been evicted-and-recomputed past the policy budget —
            # another resume would just thrash the allocator.  Degrade
            # to a rejection: finish with finish_reason="overloaded"
            # and partial output instead of re-queueing.  The worker
            # drop-notice already rides preempted_req_ids above.
            req.status = RequestStatus.FINISHED_SHED
            self.requests.pop(req.request_id, None)
            self._finished_out_of_band.append(req)
            self.num_sheds += 1
            if self.qos.enabled:
                cls = self._qos_bucket(req)
                self.sheds_by_class[cls] = (
                    self.sheds_by_class.get(cls, 0) + 1
                )
            get_tracer().event(
                req.trace_ctx,
                "engine.preempt_shed",
                request_id=req.request_id,
                num_preemptions=req.num_preemptions,
                num_output_tokens=req.num_output_tokens,
            )
            return
        req.status = RequestStatus.PREEMPTED
        self._waiting_push(req, left=True)

    # ---- post-step bookkeeping ----
    def update_from_output(
        self,
        scheduler_output: SchedulerOutput,
        sampled_token_ids: dict[str, list[int]],
    ) -> list[Request]:
        """Advance request states given the tokens the workers sampled.
        Returns requests that finished this step."""
        finished: list[Request] = []
        for req_id, num in scheduler_output.num_scheduled_tokens.items():
            deferred = self._deferred_frees.get(req_id)
            if deferred is not None:
                # A step scheduled before this request finished is
                # draining: settle its in-flight debt, free the pages
                # once the last such step lands.
                deferred.num_inflight_tokens = max(
                    deferred.num_inflight_tokens - num, 0
                )
                if deferred.num_inflight_tokens == 0:
                    del self._deferred_frees[req_id]
                    self.allocator.free(deferred)
                continue
            req = self.requests.get(req_id)
            if req is None or req.status != RequestStatus.RUNNING:
                continue  # aborted mid-step
            new_tokens = sampled_token_ids.get(req_id, [])
            if req_id in scheduler_output.draft_token_ids:
                # Spec verify pass (ISSUE 11): the window was scheduled
                # at its full width (input + all drafts) but KV is only
                # valid through the accepted prefix — advance by the
                # EMITTED count (1 + accepted drafts); the rejected-draft
                # rows are garbage the next window overwrites in place
                # (block_manager.register_computed never reaches them).
                num_adv = len(new_tokens)
                self.spec_accepted_tokens += max(len(new_tokens) - 1, 0)
            else:
                num_adv = num
            req.num_computed_tokens += num_adv
            req.num_inflight_tokens = max(req.num_inflight_tokens - num, 0)
            for tok in new_tokens:
                req.append_output_token(tok)
                status = req.check_stop(self.config.max_model_len)
                if status is not None:
                    req.status = status
                    break
            if self.enable_prefix_caching:
                # Pages fully covered by computed tokens now hold valid
                # KV: register them (before any free below, so a
                # finishing request's pages enter the LRU registered).
                self.allocator.register_computed(req)
            if req.status.is_finished:
                self.running.remove(req)
                self._release_or_defer(req)
                self._finished_since_last.append(req_id)
                finished.append(req)
                del self.requests[req_id]
        return finished

    def finish_request(self, req: Request, status: RequestStatus) -> None:
        req.status = status
        if req in self.running:
            self.running.remove(req)
            self._finished_since_last.append(req.request_id)
        if req in self.waiting:
            self._waiting_pop(req)
        self._release_or_defer(req)
        self.requests.pop(req.request_id, None)
