"""Synchronous engine: scheduler + executor + detokenization loop.

The TPU-native rebuild of the vLLM engine core the reference consumes via
`build_async_engine_client_from_engine_args` (launch.py:33, 407; SURVEY.md
§2.3).  One `step()` = schedule → executor.execute_model (one fused device
program per worker) → update request state → detokenize/stream.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any

from vllm_distributed_tpu.config import EngineArgs, EngineConfig
from vllm_distributed_tpu.engine.request import (
    FINISH_REASON,
    Request,
    RequestStatus,
)
from vllm_distributed_tpu.engine.scheduler import Scheduler
from vllm_distributed_tpu.engine.spec_decode import spec_eligible
from vllm_distributed_tpu.executor.abstract import Executor
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.metrics import WORKER_COUNTERS
from vllm_distributed_tpu.outputs import CompletionOutput, RequestOutput
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.tokenizer import (
    IncrementalDetokenizer,
    get_tokenizer,
)
from vllm_distributed_tpu.tracing import (
    NOOP_SPAN,
    get_tracer,
    new_span_id,
)

logger = init_logger(__name__)


class LLMEngine:
    def __init__(
        self,
        config: EngineConfig,
        executor_class: type[Executor] | None = None,
        metrics=None,
    ) -> None:
        self.config = config
        executor_class = executor_class or Executor.get_class(config)
        self.executor = executor_class(config)
        try:
            self._init_engine(config, metrics)
        except Exception:
            # A half-built engine must not leak its executor (listener
            # socket, loop thread, pools) — the supervisor's crash-loop
            # rebuild attempts would otherwise pile them up.
            self.executor.shutdown()
            raise

    def _init_engine(self, config: EngineConfig, metrics) -> None:
        num_pages = self.executor.determine_num_pages()
        self.executor.initialize_cache(num_pages)
        if config.scheduler_config.warmup_decode:
            self.executor.warmup_decode()
        if config.scheduler_config.warmup_prefill:
            self.executor.warmup_prefill()
        self.scheduler = Scheduler(
            config.scheduler_config, config.cache_config, num_pages
        )

        if metrics is None:
            from vllm_distributed_tpu.metrics import EngineMetrics

            metrics = EngineMetrics(
                config.model_config.model,
                enabled=config.observability_config.collect_metrics,
            )
        # A rebuilt engine (engine/supervisor.py) inherits the previous
        # engine's EngineMetrics so counters/histograms span restarts.
        self.metrics = metrics
        # Tracing (tracing.py): the global tracer is configured from
        # ObservabilityConfig; with tracing off every call below is the
        # allocation-free no-op path.  The metrics sink is a single slot,
        # so supervisor rebuilds re-register the same EngineMetrics
        # without stacking.
        obs = config.observability_config
        self.tracer = get_tracer().configure(
            enabled=obs.enable_tracing, ring_size=obs.trace_ring_size
        )
        self.tracer.set_metrics_sink(self.metrics.observe_span)
        # Liveness instruments (host_up, heartbeat latency) are emitted
        # from the executor's heartbeat loop.
        self.executor.metrics = self.metrics
        self._preemptions_seen = 0
        # (queries, hits, host_hits) already recorded.
        self._prefix_cache_seen = (0, 0, 0)
        self._spec_seen = (0, 0)  # (drafted, accepted) already recorded
        # Tiered KV cache (ISSUE 14): (spilled, restored, slots-used)
        # already recorded, and the per-page pool byte size for the
        # vllm:host_kv_bytes gauge — pulled once from the reply-rank
        # worker over the new kv-tier RPC (best-effort: 0 leaves the
        # gauge at 0, never fails boot).
        self._kv_tier_seen = (0, 0, 0)
        self._kv_page_bytes = 0
        if (
            config.cache_config.enable_prefix_caching
            and config.cache_config.kv_spill_host_pages > 0
        ):
            try:
                info = self.executor.collective_rpc(
                    "get_kv_tier_info",
                    unique_reply_rank=self.executor.output_rank,
                    timeout=30.0,
                )
                self._kv_page_bytes = int((info or {}).get("page_bytes", 0))
            except Exception as e:  # noqa: BLE001 — telemetry only
                logger.debug("kv-tier info pull failed: %s", e)
        # Flight recorder (ISSUE 12): always-on bounded ring of per-step
        # records, dumped on HostFailure/recovery/drain and served at
        # /debug/flightrecorder.
        from vllm_distributed_tpu.engine.flight_recorder import (
            FlightRecorder,
            resilience_state,
        )

        self.flight_recorder = FlightRecorder(
            size=obs.flight_recorder_size
        )
        # Unified timeline (ISSUE 20): dumps become structured events
        # on the engine's sentinel log; step records sample the
        # registered resilience provider (if any shares the process).
        self.flight_recorder.sentinel = self.metrics.events
        self._resilience_state = resilience_state
        # Device-telemetry pull cursors: event-ring position (timing
        # histogram) and cumulative per-kind compile totals already
        # counted (exact even when the bounded event ring overflows
        # between scrapes — the recompile-storm case).
        self._telemetry_seq = 0
        # worker counter (a row of metrics.WORKER_COUNTERS) -> label ->
        # the total already counted
        self._telemetry_seen: dict[str, dict[str, float]] = {}

        # Disaggregated prefill/decode hand-off (ISSUE 15): export holds
        # + inbound transfers.  Always constructed (cheap, idle costs
        # one attribute read per schedule); the scheduler hook makes the
        # finish path hold pages only for prefill_only requests.
        from vllm_distributed_tpu.engine.kv_transfer import (
            KVTransferManager,
        )

        self.kv_transfer = KVTransferManager(
            self.scheduler, self.executor, self.metrics, self.tracer
        )
        self.scheduler.kv_transfer = self.kv_transfer

        self.tokenizer = None
        if not config.model_config.skip_tokenizer_init:
            self.tokenizer = get_tokenizer(
                config.model_config.tokenizer,
                config.model_config.trust_remote_code,
            )
        self.detokenizers: dict[str, IncrementalDetokenizer] = {}
        self._failed = False
        self.failure_info = None  # HostFailure from the executor, if any
        self.executor.register_failure_callback(self._on_failure)
        # Pipelining: dispatched-but-unapplied steps, fused scans and
        # single steps alike (at most one between step() calls, two
        # briefly within a call) — the engine-side realization of the
        # reference's in-flight batches (max_concurrent_batches,
        # launch.py:298-302).
        # Entries: (SchedulerOutput, future, shape key, time issued).
        self._pending: list[tuple] = []
        # Async-scheduling reconciliation count: times the pipeline had
        # to fully drain because the next step needed settled host state
        # (logprobs, penalties, a spec verify pass, page pressure, KV
        # tier or transfer work).  0 in steady state, admissions and
        # budget ends included; surfaced by bench-serve as its
        # stall_windows field.
        self.pipeline_breaks = 0
        # The same count by what forced the drain (logprobs, penalties,
        # spec, pages, kv_tier, kv_transfer), and every dispatch issued
        # by (kind, overlapped): overlapped = issued while another was
        # in flight.  Mirrors of vllm:pipeline_breaks_total{reason} and
        # vllm:dispatches_total{kind,overlapped}.
        self.pipeline_break_reasons: dict[str, int] = {}
        self.dispatch_counts: dict[tuple[str, bool], int] = {}
        # What schedule_slack() reads: the shortest of the last few
        # device times of each shape of dispatch (kept by the threads
        # that resolve them, under the lock), when the last dispatch
        # ended, and how long the host takes to issue one (its lead).
        self._device_seconds: dict[tuple, deque] = {}
        self._device_estimate: dict[tuple, float] = {}
        self._done_lock = threading.Lock()
        self._last_done = 0.0
        self._issue_seconds = 0.005
        # Kept only while tracing is on.  Of each traced request still
        # in prefill: [step_id of its first dispatch, dispatches that
        # carried its prompt's chunks, step_id of the dispatch in front
        # of the first (None: the chip was free)]; when each of the last
        # few dispatches resolved (the resolver's own stamp); and what
        # the next ``engine.step`` root says of the time since the last:
        # when that one ended, and the seconds the caller's loop then
        # spent waiting in slack, idle and in its intake.
        self._prefill_trace: dict[str, list] = {}
        self._done_at: dict[int, float] = {}
        self._step_ended: float | None = None
        self.between_steps = {"slack": 0.0, "idle": 0.0, "intake": 0.0}
        # (trace context, step_id) of the last ``engine.step`` span, for
        # the caller's ``frontend.flush``; None with tracing off.
        self.last_step: tuple | None = None

    @classmethod
    def from_engine_args(cls, engine_args: EngineArgs) -> "LLMEngine":
        return cls(engine_args.create_engine_config())

    def _on_failure(self) -> None:
        self._failed = True
        self.failure_info = getattr(self.executor, "failure_info", None)
        detail = (
            f": {self.failure_info.describe()}"
            if self.failure_info is not None
            else ""
        )
        logger.error("executor reported failure; engine is dead%s", detail)
        self.metrics.record_engine_dead(self.failure_info)
        # Capture the last N steps before the incident while the state
        # is fresh — the artifact the post-mortem starts from.
        self.flight_recorder.dump(
            "host_failure",
            extra=(
                self.failure_info.to_dict()
                if self.failure_info is not None
                else None
            ),
        )

    @property
    def errored(self) -> bool:
        """Executor failure observed — the next step() (or the AsyncLLM
        loop's idle check) turns this into engine death."""
        return self._failed

    def _dead_message(self) -> str:
        if self.failure_info is not None:
            return f"Engine executor failed: {self.failure_info.describe()}"
        return "Engine executor failed."

    # ---- intake ----
    def add_request(
        self,
        request_id: str,
        prompt: str | None = None,
        sampling_params: SamplingParams | None = None,
        prompt_token_ids: list[int] | None = None,
        arrival_time: float | None = None,
        trace_ctx: tuple | None = None,
    ) -> None:
        sampling_params = sampling_params or SamplingParams()
        if prompt_token_ids is None:
            if prompt is None:
                raise ValueError("need prompt or prompt_token_ids")
            if self.tokenizer is None:
                raise ValueError("tokenizer not initialized")
            prompt_token_ids = self.tokenizer.encode(prompt)
        eos = None
        if not sampling_params.ignore_eos:
            if self.tokenizer is not None:
                eos = self.tokenizer.eos_token_id
            else:
                eos = getattr(
                    self.config.model_config.hf_config, "eos_token_id", None
                )
                if isinstance(eos, list):
                    eos = eos[0] if eos else None
        req = Request(
            request_id=request_id,
            prompt_token_ids=prompt_token_ids,
            sampling_params=sampling_params,
            prompt=prompt,
            eos_token_id=eos,
            trace_ctx=trace_ctx,
            # the monotonic instant the caller took it in, where given
            **({} if arrival_time is None else {"arrival_time": arrival_time}),
        )
        # Client deadline (deadline_ms param) or server default, anchored
        # to the monotonic arrival instant; enforced at schedule time.
        req.set_deadline(self.config.scheduler_config.default_deadline_ms)
        self.scheduler.add_request(req)
        if (
            sampling_params.detokenize
            and self.tokenizer is not None
        ):
            self.detokenizers[request_id] = IncrementalDetokenizer(
                self.tokenizer,
                prompt_token_ids,
                stop=sampling_params.stop,
                include_stop_str_in_output=(
                    sampling_params.include_stop_str_in_output
                ),
                min_tokens=sampling_params.min_tokens,
            )

    def abort_request(self, request_id: str) -> None:
        self.scheduler.abort_request(request_id)
        self.detokenizers.pop(request_id, None)
        self._prefill_trace.pop(request_id, None)
        if self.config.kv_transfer_config is not None:
            self.executor.kv_output_aggregator.forget(request_id)

    def has_unfinished_requests(self) -> bool:
        return self.scheduler.has_unfinished_requests()

    # ---- the loop ----
    def _drain_reason(self) -> str | None:
        """Why the dispatches in flight must land before the next
        schedule() may run, or None: it needs settled host state only
        where the schedule itself reads what a dispatch in flight will
        change.  A waiting request, a running prefill, a request whose
        budget ends in flight and a batch whose rows moved are none of
        that: the scheduler counts in-flight work of both kinds and the
        runner reads the decode rows' tokens from the device carry."""
        s = self.scheduler
        if (
            s.spec is not None
            and s.running
            and s.spec_wants_sync()
            and all(spec_eligible(r.sampling_params) for r in s.running)
        ):
            # Speculative decoding runs synchronous verify passes: the
            # proposer and the verify input both need the host-current
            # last token, so while a batch that COULD draft (all
            # greedy, no penalties/logprobs) keeps drafting, every
            # dispatch resolves before the next schedule — the verify
            # pass itself is the latency hider, one HBM pass per
            # accepted window instead of per token.  Spec-impossible
            # batches (any sampled request) and draftless stretches
            # (spec_wants_sync hysteresis) keep the async dispatch
            # pipeline; the periodic probe drain re-engages spec when
            # the text turns repetitive.
            return "spec"
        # No preemption of in-flight work: schedule() may evict a
        # running request only when nothing of it is on the device, so
        # the pool must hold the worst case of the step to come (the
        # pages of what is in flight were taken when it was scheduled).
        k = s.config.fused_decode_steps()
        budget = s.config.max_num_batched_tokens
        worst = 0
        for r in s.running:
            if r.prefill_dispatched:
                n = k
            else:
                n = min(r.prefill_target - r.num_dispatched_tokens, budget)
            worst += n // s.page_size + 1
        if s.allocator.num_free_pages < worst:
            return "pages"
        return None

    def _sync_reason(self, so) -> str | None:
        """Why this step's results must be back before anything else is
        scheduled (it is run blocking, on settled host state), or None:
        it may be issued behind whatever is in flight."""
        if self.config.kv_transfer_config is not None:
            # The KV-connector path fans every step out to all workers
            # and aggregates their replies inline (executor/abstract.py).
            return "kv_transfer"
        if so.kv_restore_ops or so.kv_spill_ops:
            # Tier copies run eagerly on the pool, outside the step
            # program, and the restore stall is read off this step.
            return "kv_tier"
        if so.draft_token_ids:
            return "spec"
        requests = self.scheduler.requests
        for req_id in so.num_scheduled_tokens:
            req = requests.get(req_id)
            if req is None:
                continue
            sp = req.sampling_params
            if sp.logprobs is not None:
                # Per-token logprob rows are fetched and attached step
                # by step (host token feedback).
                return "logprobs"
            if (
                sp.repetition_penalty != 1.0
                or sp.presence_penalty != 0.0
                or sp.frequency_penalty != 0.0
            ):
                # The penalty inputs are built from the host's token
                # history.
                return "penalties"
        return None

    def _break_pipeline(self, reason: str) -> list[RequestOutput]:
        """Reconciliation: drain so that what follows sees settled
        state.  Deferred page frees settle in the same drain."""
        self.pipeline_breaks += 1
        self.pipeline_break_reasons[reason] = (
            self.pipeline_break_reasons.get(reason, 0) + 1
        )
        self.metrics.record_pipeline_break(reason)
        return self._drain_pending()

    def _count_dispatch(self, so, overlapped: bool) -> None:
        kind = (
            "spec"
            if so.draft_token_ids
            else "decode" if so.decode_steps > 1 else "step"
        )
        key = (kind, overlapped)
        self.dispatch_counts[key] = self.dispatch_counts.get(key, 0) + 1
        self.metrics.record_dispatch(kind, overlapped)

    @staticmethod
    def _shape_key(so) -> tuple:
        """What decides a dispatch's device time, as far as the engine
        can see it: the step program's kind and token buckets."""
        tokens = so.total_num_scheduled_tokens
        longest = max(so.num_scheduled_tokens.values(), default=1)
        return (
            so.decode_steps,
            1 << (tokens - 1).bit_length(),
            1 << (longest - 1).bit_length(),
        )

    def _issue(self, so, t_step0: float) -> None:
        """Issue ``so`` without blocking and note what the next
        schedule_slack() needs of it."""
        fut = self.executor.execute_model(so, non_block=True)
        key = self._shape_key(so)
        t_issued = time.monotonic()
        took = t_issued - t_step0
        if took < 0.05:
            # A first call compiles or loads its program for far longer
            # and is no lead: averaged in, it moves the next schedules
            # from just before a dispatch's end to just after its start,
            # which is when a closed loop's next requests arrive.
            self._issue_seconds += 0.2 * (took - self._issue_seconds)

        def done(_fut) -> None:
            # Resolver thread.  On the device the dispatch began when it
            # was issued or when the one before it ended, if later.
            now = time.monotonic()
            with self._done_lock:
                seen = self._device_seconds.setdefault(key, deque(maxlen=8))
                seen.append(now - max(t_issued, self._last_done))
                self._device_estimate[key] = min(seen)
                self._last_done = now
                if self.tracer.enabled:
                    # For ``engine.prefill``'s ``behind_ms`` of the
                    # requests whose first dispatch queued behind this.
                    self._done_at[so.step_id] = now
                    if len(self._done_at) > 64:
                        del self._done_at[next(iter(self._done_at))]

        fut.add_done_callback(done)
        self._pending.append((so, fut, key, t_issued))

    def schedule_slack(self) -> float:
        """Seconds the caller may still wait for arrivals before the
        next ``step()`` has to be called to keep the device fed.

        A step issued behind a dispatch in flight queues behind it, and
        whatever arrives after it was scheduled queues behind both: so
        the schedule is best made as late as the device allows, not as
        soon as the host is free.  The dispatches in flight are
        expected to end when dispatches of their shape did lately (the
        shortest of the last few, so an estimate errs early), and the
        host needs its own lead to schedule, prepare and issue (twice
        its recent mean).  0.0 where nothing is in flight, a shape has
        not been seen yet, or the time is up."""
        if not self._pending:
            return 0.0
        end = self._last_done
        for _, fut, key, t_issued in self._pending:
            expected = self._device_estimate.get(key)
            if expected is None or fut.done():
                return 0.0
            end = max(end, t_issued) + expected
        return max(end - 2.0 * self._issue_seconds - time.monotonic(), 0.0)

    def _finalize_one(self) -> list[RequestOutput]:
        scheduler_output, result = self._pending.pop(0)[:2]
        if hasattr(result, "result"):  # Future
            # The wait is the resolver thread's ``runner.fetch``.
            result = result.result()
        return self._process(scheduler_output, result)

    def _drain_pending(self) -> list[RequestOutput]:
        outputs: list[RequestOutput] = []
        while self._pending:
            outputs.extend(self._finalize_one())
        return outputs

    def _finalize_done(self) -> list[RequestOutput]:
        """Finalize in-flight dispatches whose results are already
        available, WITHOUT blocking: tokens stream to the caller as each
        dispatch completes instead of surfacing only when the pipeline
        drains (r4's held-until-drain delivery was the dominant
        serving-latency artifact, VERDICT r4 weak #1)."""
        outputs: list[RequestOutput] = []
        while self._pending:
            result = self._pending[0][1]
            if hasattr(result, "done") and not result.done():
                break
            outputs.extend(self._finalize_one())
        return outputs

    def step(self) -> list[RequestOutput]:
        """One engine step, a trace of its own: the root ``engine.step``
        and a child for each phase the host goes through
        (``scheduler.schedule``, ``runner.prepare`` / ``.dispatch`` /
        ``.fetch``, ``engine.process``), each with the ``step_id`` of
        the dispatch it works for — a step also finalizes dispatches
        that earlier steps put in flight."""
        if self._failed:
            raise RuntimeError(self._dead_message())
        with self.tracer.span("engine.step", trace_root=True) as span:
            if span is NOOP_SPAN:
                self.last_step = None
                return self._step(span)
            # The thread's time between two steps, on this step's root:
            # no trace of its own for a wait (the ring is per trace).
            between, ended = self.between_steps, self._step_ended
            span.attributes.update(
                since_prev_ms=(
                    0.0 if ended is None
                    else (span.start_mono - ended) * 1e3
                ),
                slack_ms=between["slack"] * 1e3,
                idle_ms=between["idle"] * 1e3,
                intake_ms=between["intake"] * 1e3,
            )
            between.update(slack=0.0, idle=0.0, intake=0.0)
            try:
                return self._step(span)
            finally:
                self.last_step = (span.ctx, span.attributes.get("step_id"))
                self._step_ended = time.monotonic()

    def _step(self, span) -> list[RequestOutput]:
        t_step0 = time.monotonic()
        outputs: list[RequestOutput] = []
        outputs.extend(self._finalize_done())
        # One dispatch of any kind may be issued behind another: with
        # fused decode on, the host's work for step N+1 (fetch and
        # process of N-1, schedule, prepare, dispatch) runs while N is
        # on the device.  The blocking path is the fallback for what
        # needs settled host state, not a mode.
        overlap = self.scheduler.config.num_decode_steps > 1
        reason = self._drain_reason() if self._pending else None
        if reason is not None:
            outputs.extend(self._break_pipeline(reason))
        scheduler_output = self._schedule()
        sync_reason = (
            self._sync_reason(scheduler_output) if overlap else None
        )
        if span is not NOOP_SPAN:
            span.attributes.update(
                step_id=scheduler_output.step_id,
                decode_steps=scheduler_output.decode_steps,
                pending=len(self._pending),
                pipeline_break=reason is not None
                or bool(self._pending and sync_reason),
            )
            self._note_prefill_dispatch(scheduler_output)
        if self.flight_recorder.enabled:
            self._record_flight(scheduler_output)
        # Deadline sheds and preempt-to-sheds finish OUTSIDE
        # update_from_output; emit their final (partial) outputs now so
        # clients see finish_reason="timeout"/"overloaded" promptly.
        outputs.extend(self._finish_out_of_band())
        if scheduler_output.is_empty:
            # Typically every request's remaining budget is in flight:
            # block on the HEAD dispatch only, so tokens keep streaming
            # per dispatch while the tail of the pipeline drains.
            if self._pending:
                outputs.extend(self._finalize_one())
            return outputs
        if overlap and sync_reason is None:
            self._count_dispatch(scheduler_output, bool(self._pending))
            self._issue(scheduler_output, t_step0)
            depth = self.config.scheduler_config.max_concurrent_dispatches
            while len(self._pending) > depth - 1:
                outputs.extend(self._finalize_one())
            return outputs
        if self._pending:
            outputs.extend(self._break_pipeline(sync_reason))
        self._count_dispatch(scheduler_output, False)
        runner_output = self.executor.execute_model(scheduler_output)
        outputs.extend(self._process(scheduler_output, runner_output))
        return outputs

    def _note_prefill_dispatch(self, so) -> None:
        """Tracing on: which dispatch first carried each traced request,
        what was in front of it on the device, and how many dispatches
        carried its prompt — ``engine.prefill``'s ``first_step_id``,
        ``behind_ms`` and ``steps``."""
        requests = self.scheduler.requests
        front = self._pending[-1][0].step_id if self._pending else None
        for new in so.new_requests:
            req = requests.get(new.req_id)
            if req is None or req.trace_ctx is None:
                continue
            track = self._prefill_trace.get(new.req_id)
            if track is None:
                self._prefill_trace[new.req_id] = [so.step_id, 1, front]
            else:  # resumed after a preemption: its prompt again
                track[1] += 1
        if not self._prefill_trace:
            return
        for cached in so.cached_requests:
            track = self._prefill_trace.get(cached.req_id)
            if track is None:
                continue
            req = requests.get(cached.req_id)
            if (
                req is not None
                and cached.num_computed_tokens < req.prefill_target
            ):
                track[1] += 1

    def _prefill_attrs(self, req: Request, first_sched: float) -> dict:
        """What ``_note_prefill_dispatch`` kept of ``req``, as the
        attributes of its ``engine.prefill`` span."""
        track = self._prefill_trace.pop(req.request_id, None)
        if track is None:
            return {}
        first_step_id, steps, front = track
        resolved = self._done_at.get(front) if front is not None else None
        return dict(
            steps=steps,
            first_step_id=first_step_id,
            behind_ms=(
                0.0 if resolved is None
                else max(resolved - first_sched, 0.0) * 1e3
            ),
        )

    def _record_flight(self, so) -> None:
        """One flight-recorder record per scheduled step (positional, in
        flight_recorder.FIELDS order — tuple pack + deque append)."""
        s = self.scheduler
        open_breakers, retry_balance = self._resilience_state()
        self.flight_recorder.record_step(
            so.step_id,
            time.time(),
            time.monotonic(),
            len(s.running),
            len(s.waiting),
            so.total_num_scheduled_tokens,
            so.decode_steps,
            len(so.new_requests),
            len(so.cached_requests),
            len(so.preempted_req_ids),
            len(so.finished_req_ids),
            sum(len(d) for d in so.draft_token_ids.values()),
            len(self._pending),
            self.pipeline_breaks,
            s.allocator.num_free_pages,
            open_breakers,
            retry_balance,
        )

    def refresh_device_telemetry(self) -> dict | None:
        """Pull one DeviceTelemetry snapshot from the reply-rank worker
        and fold it into the Prometheus instruments: compile events past
        the cursor are counted exactly once, gauges take the latest
        value.  Called on /metrics scrapes (via the AsyncLLM aux path,
        so the collective stays ordered with step dispatches) and
        directly by engine-level tests.  Best-effort: a dead executor
        just leaves the previous values standing."""
        try:
            snap = self.executor.collective_rpc(
                "get_device_telemetry",
                unique_reply_rank=self.executor.output_rank,
                # Short: this runs between step dispatches on the engine
                # thread — a slow host must cost a missed scrape, never
                # a long decode stall.
                timeout=5.0,
            )
        except Exception as e:  # noqa: BLE001 — telemetry only
            logger.debug("device-telemetry pull failed: %s", e)
            return None
        if not isinstance(snap, dict):
            return None
        # Timing histogram from the (bounded) event ring; the COUNTER
        # uses the cumulative totals below so it stays exact even when
        # more compiles happened between scrapes than the ring holds.
        for event in snap.get("compile_events", ()):
            try:
                seq, seconds = event[0], event[2]
            except (IndexError, TypeError):
                continue
            if seq > self._telemetry_seq:
                self._telemetry_seq = seq
                self.metrics.record_xla_compile_seconds(float(seconds))
        # Cumulative worker totals -> counter increments, each counted
        # exactly once.
        for key, row in WORKER_COUNTERS.items():
            seen = self._telemetry_seen.setdefault(key, {})
            for label, total in (snap.get(key) or {}).items():
                new = total if row.gauge else total - seen.get(label, 0)
                if new > 0:
                    self.metrics.record_worker_counter(key, str(label), new)
                    seen[label] = total
        self.metrics.record_device_snapshot(snap)
        return snap

    def _finish_out_of_band(self) -> list[RequestOutput]:
        """Final outputs for requests the scheduler finished outside
        update_from_output (deadline sheds, preempt-to-shed, ISSUE 8):
        partial tokens/text, finish_reason from the status, metrics and
        spans recorded like any other finish."""
        reqs = self.scheduler.take_finished_out_of_band()
        if not reqs:
            return []
        now = time.time()
        now_mono = time.monotonic()
        # QoS sheds enter the unified timeline (ISSUE 20): one event
        # per drain with the reason tally, not one per request.
        shed_reasons: dict[str, int] = {}
        for req in reqs:
            reason = FINISH_REASON.get(req.status) or "unknown"
            shed_reasons[reason] = shed_reasons.get(reason, 0) + 1
        self.metrics.events.emit(
            "qos_shed", count=len(reqs), reasons=shed_reasons
        )
        outputs: list[RequestOutput] = []
        for req in reqs:
            req.metrics.finished_time = now
            req.metrics.finished_time_mono = now_mono
            if self.tracer.enabled:
                self._record_request_spans(req, now_mono, True)
            detok = self.detokenizers.pop(req.request_id, None)
            outputs.append(self._make_output(req, detok))
            self.metrics.record_finished(
                req.metrics, FINISH_REASON.get(req.status)
            )
            if self.config.kv_transfer_config is not None:
                self.executor.kv_output_aggregator.forget(req.request_id)
        return outputs

    def _schedule(self):
        """One scheduler pass, wrapped in a per-step schedule span with
        the batch composition attached: a child of the step, and stored
        again under the first traced request in the batch (tracing off =
        plain call)."""
        if not self.tracer.enabled:
            return self.scheduler.schedule()
        with self.tracer.span("scheduler.schedule") as span:
            scheduler_output = self.scheduler.schedule()
            if span is NOOP_SPAN:  # no step around it: nothing to parent
                return scheduler_output
            span.attributes.update(
                step_id=scheduler_output.step_id,
                num_new=len(scheduler_output.new_requests),
                num_cached=len(scheduler_output.cached_requests),
                num_preempted=len(scheduler_output.preempted_req_ids),
                decode_steps=scheduler_output.decode_steps,
                total_tokens=scheduler_output.total_num_scheduled_tokens,
                batch=",".join(
                    f"{rid}:{n}"
                    for rid, n in (
                        scheduler_output.num_scheduled_tokens.items()
                    )
                ),
            )
        if scheduler_output.trace_ctx is not None:
            # The same interval in the trace of the first traced request
            # of the batch, where a request's own chain
            # (queue → schedule → dispatch → worker) is read.  Stored,
            # not recorded: the span above has fed the metrics sink.
            self.tracer.adopt(
                [
                    dict(
                        span.to_wire(),
                        trace_id=scheduler_output.trace_ctx[0],
                        parent_id=scheduler_output.trace_ctx[1],
                        span_id=new_span_id(),
                    )
                ]
            )
        return scheduler_output

    def _record_stage(
        self,
        req: Request,
        name: str,
        start_mono: float,
        end_mono: float,
        **attributes,
    ) -> None:
        """Synthesize one request-stage span from monotonic stamps.  The
        wall-clock start is derived from the arrival anchor + monotonic
        delta, so span starts are NTP-consistent with the durations."""
        m = req.metrics
        self.tracer.record_span(
            name,
            m.arrival_time + (start_mono - m.arrival_time_mono),
            max(end_mono - start_mono, 0.0),
            parent=req.trace_ctx,
            request_id=req.request_id,
            **attributes,
        )

    def _record_request_spans(
        self, req: Request, now_mono: float, finished: bool
    ) -> None:
        """Stage spans at the two request milestones: queue+prefill when
        the first token lands, decode at finish.  A request finishing
        without ever producing a token (e.g. stop-string truncation to
        zero) still gets its earlier stages recorded at finish."""
        m = req.metrics
        first_sched = (
            m.first_scheduled_time_mono
            if m.first_scheduled_time_mono is not None
            else now_mono
        )

        def queue_and_prefill(end_mono: float) -> None:
            self._record_stage(
                req, "engine.queue", m.arrival_time_mono, first_sched
            )
            self._record_stage(
                req,
                "engine.prefill",
                first_sched,
                end_mono,
                **self._prefill_attrs(req, first_sched),
            )

        if m.first_token_time_mono == now_mono and not finished:
            queue_and_prefill(now_mono)
            return
        if not finished:
            return
        if m.first_token_time_mono is None:
            queue_and_prefill(now_mono)
        else:
            if m.first_token_time_mono == now_mono:
                # First token and finish in the same step.
                queue_and_prefill(m.first_token_time_mono)
            self._record_stage(
                req, "engine.decode", m.first_token_time_mono, now_mono
            )
        self.tracer.event(
            req.trace_ctx,
            "engine.finished",
            request_id=req.request_id,
            finish_reason=FINISH_REASON.get(req.status, "?"),
            num_output_tokens=req.num_output_tokens,
            # Joins traces to the per-class SLO accounting (ISSUE 12):
            # "which class were the slow traces in" becomes greppable.
            slo_class=req.sampling_params.slo_class,
        )

    def _process(
        self, scheduler_output, runner_output
    ) -> list[RequestOutput]:
        with self.tracer.span(
            "engine.process", step_id=scheduler_output.step_id
        ) as span:
            outputs = self._process_outputs(scheduler_output, runner_output)
            span.set_attribute("outputs", len(outputs))
            return outputs

    def _process_outputs(
        self, scheduler_output, runner_output
    ) -> list[RequestOutput]:
        finished = self.scheduler.update_from_output(
            scheduler_output, runner_output.sampled_token_ids
        )
        now = time.time()
        now_mono = time.monotonic()
        self.metrics.record_queues(
            len(self.scheduler.running),
            len(self.scheduler.waiting),
            self.scheduler.num_waiting_tokens,
        )
        self.metrics.record_preemptions(
            self.scheduler.num_preemptions - self._preemptions_seen
        )
        self._preemptions_seen = self.scheduler.num_preemptions
        pc = (
            self.scheduler.prefix_cache_queries,
            self.scheduler.prefix_cache_hits,
            self.scheduler.prefix_cache_hits_host,
        )
        self.metrics.record_prefix_cache(
            pc[0] - self._prefix_cache_seen[0],
            pc[1] - self._prefix_cache_seen[1],
            pc[2] - self._prefix_cache_seen[2],
        )
        self._prefix_cache_seen = pc
        self.metrics.record_kv_cache_usage(self.scheduler.kv_cache_usage)
        # Tiered KV cache (ISSUE 14): tier-traffic deltas, host
        # occupancy, and the restore-stall observables on steps that
        # carried restore spans.
        slots = getattr(
            self.scheduler.allocator, "host_slots_used", 0
        )
        kt = (
            self.scheduler.kv_spill_pages,
            self.scheduler.kv_restore_pages,
            # Occupancy moves without tier traffic too (promotes and
            # subtree prunes release slots) — the gauge must follow.
            slots,
        )
        if kt != self._kv_tier_seen:
            self.metrics.record_kv_tier(
                kt[0] - self._kv_tier_seen[0],
                kt[1] - self._kv_tier_seen[1],
                host_bytes=slots * self._kv_page_bytes,
            )
            self._kv_tier_seen = kt
        if scheduler_output.kv_restore_ops:
            stall = runner_output.kv_tier_seconds
            self.metrics.record_kv_restore_seconds(stall)
            self.metrics.events.emit(
                "kv_restore",
                pages=len(scheduler_output.kv_restore_ops),
                stall_s=round(stall, 6),
                step_id=scheduler_output.step_id,
            )
            if self.tracer.enabled:
                self.tracer.record_span(
                    "engine.kv_restore",
                    now - stall,
                    stall,
                    parent=scheduler_output.trace_ctx,
                    step_id=scheduler_output.step_id,
                    pages=len(scheduler_output.kv_restore_ops),
                    spilled_pages=len(scheduler_output.kv_spill_ops),
                )
        if scheduler_output.draft_token_ids:
            sd = (
                self.scheduler.spec_drafted_tokens,
                self.scheduler.spec_accepted_tokens,
            )
            drafted = sd[0] - self._spec_seen[0]
            accepted = sd[1] - self._spec_seen[1]
            self.metrics.record_spec_decode(drafted, accepted)
            self._spec_seen = sd
            for req_id in scheduler_output.draft_token_ids:
                emitted = runner_output.sampled_token_ids.get(req_id)
                if emitted:
                    self.metrics.record_spec_acceptance_length(
                        len(emitted)
                    )
            if self.tracer.enabled:
                self.tracer.event(
                    scheduler_output.trace_ctx,
                    "engine.spec_decode",
                    step_id=scheduler_output.step_id,
                    drafted=drafted,
                    accepted=accepted,
                )

        outputs: list[RequestOutput] = []
        for req_id in scheduler_output.num_scheduled_tokens:
            req = self.scheduler.requests.get(req_id)
            if req is None:  # finished this step; look in finished list
                req = next(
                    (r for r in finished if r.request_id == req_id), None
                )
                if req is None:
                    continue
            new_tokens = runner_output.sampled_token_ids.get(req_id, [])
            # Prompt tokens count as PROCESSED (per prefill step), not on
            # first-token arrival — aborted/preempted prefills contribute
            # like vLLM's accounting.
            n_prefill = runner_output.num_prompt_tokens_processed.get(
                req_id, 0
            )
            if n_prefill:
                req.metrics.prompt_tokens_counted += n_prefill
                self.metrics.record_prompt_tokens(n_prefill)
            first_token = (
                bool(new_tokens) and req.metrics.first_token_time is None
            )
            if first_token:
                req.metrics.first_token_time = now
                req.metrics.first_token_time_mono = now_mono
                # The final prefill chunk samples a token and reports no
                # num_prompt_tokens_processed: count the remainder here.
                rest = req.num_prompt_tokens - req.metrics.prompt_tokens_counted
                if rest > 0:
                    req.metrics.prompt_tokens_counted += rest
                    self.metrics.record_prompt_tokens(rest)
            self.metrics.record_new_tokens(
                req.metrics, len(new_tokens), now_mono
            )
            if req_id in runner_output.logprobs and req.logprobs is not None:
                lps = runner_output.logprobs[req_id]
                req.logprobs.extend(lps)
                for tok, lp in zip(new_tokens, lps):
                    req.cumulative_logprob += lp.get(tok, 0.0)

            detok = self.detokenizers.get(req_id)
            if detok is not None and new_tokens:
                detok.append(new_tokens)
                if detok.stopped_on is not None and not req.status.is_finished:
                    # Truncate tokens generated past the stop string so
                    # token_ids/logprobs/usage agree with the text.
                    keep = detok.stop_token_count
                    dropped = req.output_token_ids[keep:]
                    if dropped:
                        del req.output_token_ids[keep:]
                        if req.logprobs is not None and len(req.logprobs) > keep:
                            for tok, lp in zip(dropped, req.logprobs[keep:]):
                                req.cumulative_logprob -= lp.get(tok, 0.0)
                            del req.logprobs[keep:]
                    self.scheduler.finish_request(
                        req, RequestStatus.FINISHED_STOPPED
                    )
                    req.stop_reason = detok.stopped_on
                    finished.append(req)

            if req.status.is_finished:
                req.metrics.finished_time = now
                req.metrics.finished_time_mono = now_mono
            if self.tracer.enabled:
                self._record_request_spans(
                    req, now_mono, req.status.is_finished
                )
            outputs.append(self._make_output(req, detok, first_token))

        for req in finished:
            self.metrics.record_finished(
                req.metrics, FINISH_REASON.get(req.status)
            )
            self.detokenizers.pop(req.request_id, None)
            if self.config.kv_transfer_config is not None:
                self.executor.kv_output_aggregator.forget(req.request_id)
        return outputs

    def _make_output(
        self,
        req: Request,
        detok: IncrementalDetokenizer | None,
        first_token: bool = False,
    ) -> RequestOutput:
        finish_reason = FINISH_REASON.get(req.status)
        completion = CompletionOutput(
            index=0,
            text=detok.output_text if detok is not None else "",
            token_ids=list(req.output_token_ids),
            cumulative_logprob=(
                req.cumulative_logprob if req.logprobs is not None else None
            ),
            logprobs=req.logprobs,
            finish_reason=finish_reason,
            stop_reason=req.stop_reason,
        )
        return RequestOutput(
            request_id=req.request_id,
            prompt=req.prompt,
            prompt_token_ids=req.prompt_token_ids,
            outputs=[completion],
            finished=req.status.is_finished,
            metrics=req.metrics,
            first_token=first_token,
        )

    def embed(self, prompt_token_ids: list[int]) -> list[float]:
        """Pooled embedding of a prompt (/v1/embeddings)."""
        return self.executor.collective_rpc(
            "embed",
            (prompt_token_ids,),
            unique_reply_rank=self.executor.output_rank,
        )

    def score(self, prompt_token_ids: list[int]) -> list[float | None]:
        """Prompt logprobs (completions echo+logprobs)."""
        return self.executor.collective_rpc(
            "score",
            (prompt_token_ids,),
            unique_reply_rank=self.executor.output_rank,
        )

    def shutdown(self) -> None:
        self.tracer.clear_metrics_sink(self.metrics.observe_span)
        self.executor.shutdown()

    # Introspection used by the API layer.
    def get_model_config(self):
        return self.config.model_config
