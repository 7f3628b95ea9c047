"""Async engine client: the surface the HTTP layer consumes.

The rebuild of the EngineClient protocol + AsyncLLM the reference drives
through build_async_engine_client_from_engine_args (launch.py:30-33,
395-407; SURVEY.md §2.3).  The engine's blocking step loop runs on a
dedicated thread (device work must not block the server's event loop).

The event loop NEVER takes a lock shared with the engine thread: intake
(add/abort) goes through a thread-safe command queue the engine thread
drains between steps, so a multi-second prefill can't freeze /health or
other SSE streams (ADVICE r1 #1 / VERDICT r2 weak #3).  Results stream
to per-request asyncio queues via call_soon_threadsafe.

Engine death is no longer always terminal: a control-plane HostFailure
hands the engine thread to the EngineSupervisor (engine/supervisor.py),
which tears down the dead executor, waits for the agents to redial,
rebuilds the engine, and replays interrupted requests from the request
journal — in-flight generate() streams keep yielding across the blip.
Only when the restart policy is exhausted (or the death is not a
control-plane failure) does the engine reach the terminal dead state:
every queued/in-flight/new request gets a typed EngineDeadError.
"""

from __future__ import annotations

import asyncio
import json
import os
import queue as _queue
import threading
import time
from typing import AsyncIterator

from vllm_distributed_tpu import envs
from vllm_distributed_tpu.config import EngineArgs, EngineConfig
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.engine.overload import (
    DRAIN_DRAINED,
    DRAIN_DRAINING,
    AdmissionController,
    EngineOverloadedError,
    estimate_prompt_tokens,
)
from vllm_distributed_tpu.engine.supervisor import (
    EngineSupervisor,
    JournalEntry,
)
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.outputs import RequestOutput
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.tracing import get_tracer

logger = init_logger(__name__)


class EngineDeadError(RuntimeError):
    """The engine can no longer serve.  ``failure`` carries the
    structured per-host attribution (HostFailure) when the death came
    from the multihost control plane, None otherwise."""

    def __init__(self, message: str, failure=None) -> None:
        super().__init__(message)
        self.failure = failure


class EngineRecoveringError(RuntimeError):
    """The engine died but the supervisor is rebuilding it in-process.
    Distinct from EngineDeadError so /health can answer 503 with the
    RECOVERING state and a Retry-After derived from the backoff."""

    def __init__(self, message: str, failure=None, retry_after: int = 1):
        super().__init__(message)
        self.failure = failure
        self.retry_after = retry_after


class _Flush:
    """One engine step's outputs on their way out, made only while
    tracing is on: the ``frontend.flush`` span of that step's trace, from
    ``_dispatch_outputs``' entry to the last of the batch consumed
    (written, for a streamed request), and what ``api.emit`` needs of
    the hand-over.  Counted down on the event loop alone."""

    __slots__ = (
        "ctx", "step_id", "outputs", "first", "left", "handed", "t0", "wall"
    )

    def __init__(self, ctx: tuple, step_id, outputs: int) -> None:
        self.ctx = ctx
        self.step_id = step_id
        self.outputs = self.left = outputs
        self.first = 0  # how many of them went ahead: first tokens
        # engine thread: the step's outputs handed to the loop
        self.handed = time.monotonic()
        self.t0 = self.wall = 0.0

    def enter(self) -> None:
        self.t0 = time.monotonic()
        self.wall = time.time()

    @property
    def through(self) -> int:
        """How many of the step's outputs are through: for the one a
        handler holds, its 0-based place in the hand-over."""
        return self.outputs - self.left

    def done(self) -> None:
        self.left -= 1
        if self.left == 0:
            get_tracer().record_span(
                "frontend.flush",
                self.wall,
                time.monotonic() - self.t0,
                parent=self.ctx,
                step_id=self.step_id,
                outputs=self.outputs,
                first_outputs=self.first,
                hop_ms=(self.t0 - self.handed) * 1e3,
            )


class AsyncLLM:
    # How long shutdown() waits for the engine thread before concluding
    # it is wedged and skipping the device teardown it still owns.
    SHUTDOWN_JOIN_SECONDS = 5.0

    def __init__(self, config: EngineConfig) -> None:
        self.config = config
        self._loop: asyncio.AbstractEventLoop | None = None
        self._queues: dict[str, asyncio.Queue] = {}
        # Request journal (engine/supervisor.py): per live request, the
        # prompt, params, and client-visible cumulative output — what a
        # recovery replays.  Written on the event loop, snapshotted by
        # the supervisor on the engine thread after a flush barrier.
        self._journal: dict[str, JournalEntry] = {}
        # Thread-safe intake: ("add", kwargs) / ("abort", request_id) /
        # ("resume", JournalEntry) / ("aux", ...), applied by the engine
        # thread between steps.  "add" producers are bounded by the
        # AdmissionController caps; abort/aux are 1:1 with live HTTP
        # handlers, which the server's connection limits bound.
        # vdt-lint: disable=unbounded-queue — bound enforced at admission
        self._intake: _queue.SimpleQueue = _queue.SimpleQueue()
        self._wake = threading.Event()
        self._dead: BaseException | None = None
        self._shutdown = False
        # Coarse engine-thread location, for the stuck-shutdown warning:
        # boot | intake | idle | step | recovering | dead | stopped.
        self._phase = "boot"
        self.engine = LLMEngine(config)
        self.supervisor = EngineSupervisor(self)
        # Overload resilience (ISSUE 8): bounded admission + drain state.
        # Caps live in SchedulerConfig (default 0 = seed behavior).
        self._admission = AdmissionController(
            config.scheduler_config,
            retry_after=envs.VDT_OVERLOAD_RETRY_AFTER_SECONDS,
        )
        self._admission.attach_scheduler(self.engine.scheduler)
        self._drain_journal_path = envs.VDT_DRAIN_JOURNAL_PATH or None
        # Last scrape-triggered device-telemetry pull (monotonic); see
        # refresh_device_telemetry.
        self._telemetry_refreshed = float("-inf")
        # Requests journaled by a previous process's drain: re-admitted
        # (with their emitted tokens restored) when a client re-attaches
        # via generate() with the same request id.
        self._resumable: dict[str, JournalEntry] = self._load_drain_journal()
        self._thread = threading.Thread(
            target=self._run_engine_loop, daemon=True, name="vdt-engine"
        )
        self._thread.start()

    @classmethod
    def from_engine_args(cls, engine_args: EngineArgs) -> "AsyncLLM":
        return cls(engine_args.create_engine_config())

    # ---- the background loop ----
    def _drain_intake(self) -> None:
        """Apply queued add/abort/aux commands (engine thread only).
        With tracing on, the time it takes counts toward the next
        ``engine.step``'s ``intake_ms``."""
        if self._intake.empty():
            return
        if not get_tracer().enabled:
            return self._apply_intake()
        t0 = time.monotonic()
        try:
            self._apply_intake()
        finally:
            self.engine.between_steps["intake"] += time.monotonic() - t0

    def _apply_intake(self) -> None:
        while True:
            try:
                op, payload = self._intake.get_nowait()
            except _queue.Empty:
                return
            if op == "add":
                # The reservation moves from "intake-pending" to
                # scheduler state (counted there) the moment the add is
                # consumed — even on error, the tokens never reach the
                # waiting queue.
                est = payload.pop("_est_tokens", 0)
                self._admission.consumed(est, payload.pop("_est_class", None))
                request_id = payload["request_id"]
                entry = self._journal.get(request_id)
                if entry is not None:
                    # Consumed from the intake: from here on, recovery
                    # must replay this request (the op won't re-run).
                    entry.admitted = True
                # generate() stamps a traced request's put: the wait in
                # the intake is its ``engine.intake`` span, ending where
                # ``engine.queue`` begins.
                put = payload.pop("_put", None)
                if put is not None:
                    # one stamp ends the intake and begins the queue
                    payload["arrival_time"] = now = time.monotonic()
                    get_tracer().record_span(
                        "engine.intake",
                        time.time() - (now - put[0]),
                        now - put[0],
                        parent=payload["trace_ctx"],
                        request_id=request_id,
                        phase=put[1],
                    )
                try:
                    self.engine.add_request(**payload)
                except Exception as e:  # noqa: BLE001 — per-request error
                    # Surface intake errors (too-long prompt, bad params)
                    # on the request's own stream, preserving the type so
                    # the API layer can map e.g. ValueError -> 400.
                    self._to_request_queue(request_id, e)
            elif op == "resume":
                # Drain-journal replay (ISSUE 8): re-admit a request a
                # previous process drained, with its delivered tokens
                # restored as output state (preemption-resume
                # semantics, engine/supervisor.py JournalEntry).
                entry = payload
                entry.admitted = True
                try:
                    entry.replay_into(self.engine)
                except Exception as e:  # noqa: BLE001 — per-request error
                    self._to_request_queue(entry.request_id, e)
            elif op == "aux":
                # Auxiliary device work (embed/score) runs HERE so its
                # collective dispatch is totally ordered with step
                # dispatches — on a multihost mesh, racing callers would
                # otherwise enqueue mismatched programs across hosts.
                fn, args, fut = payload
                try:
                    result = fn(*args)
                    err = None
                except Exception as e:  # noqa: BLE001
                    result, err = None, e
                if self._loop is not None:
                    self._loop.call_soon_threadsafe(
                        self._resolve_aux, fut, result, err
                    )
            else:  # "abort"
                self.engine.abort_request(payload)

    @staticmethod
    def _resolve_aux(fut, result, err) -> None:
        if fut.cancelled():
            return
        if fut.done():
            return  # already failed by a concurrent sweep
        if err is not None:
            fut.set_exception(err)
        else:
            fut.set_result(result)

    async def _run_aux(self, fn, *args):
        if self._dead is not None:
            raise self._dead_error()
        loop = asyncio.get_running_loop()
        self._loop = loop
        fut = loop.create_future()
        self._intake.put(("aux", (fn, args, fut)))
        self._wake.set()
        # Death-race fix (ISSUE 4 satellite): an aux enqueued after the
        # engine thread's post-death/post-shutdown intake sweep would
        # otherwise await forever.  The terminal sweep now also runs from
        # _fail_all_queues (event-loop side), and this re-check covers
        # a put that lands after BOTH sweeps.
        if self._shutdown and not fut.done():
            raise EngineDeadError("AsyncLLM is shutting down")
        if self._dead is not None and not fut.done():
            raise self._dead_error()
        return await fut

    def _to_request_queue(self, request_id: str, item) -> None:
        if self._loop is None:
            return
        self._loop.call_soon_threadsafe(
            lambda: self._dispatch_item(request_id, item)
        )

    def _dispatch_item(self, request_id: str, item) -> None:
        q = self._queues.get(request_id)
        if q is not None:
            q.put_nowait(item)

    def _run_engine_loop(self) -> None:
        while True:
            try:
                self._serve_until_shutdown()
            except BaseException as e:  # noqa: BLE001
                logger.exception("engine loop died")
                try:
                    recovered = (
                        not self._shutdown and self.supervisor.recover(e)
                    )
                except BaseException:  # noqa: BLE001
                    # A recovery-cycle bug must still land in the
                    # terminal drain below — never a silent thread death
                    # that leaves every stream hanging.
                    logger.exception("engine recovery itself failed")
                    recovered = False
                if recovered:
                    continue  # fresh engine installed; keep serving
                self._phase = "dead"
                self._dead = e
                if self._loop is not None:
                    self._loop.call_soon_threadsafe(
                        self._fail_all_queues, self._dead_error()
                    )
                # Belt and braces: _fail_all_queues sweeps the intake on
                # the event loop, but if the loop is gone (or a put races
                # both sweeps) resolve from here too.
                self._sweep_intake(self._dead_error())
                return
            # Clean shutdown: anything still queued (aux futures in
            # particular) must not leave its caller hanging.
            self._phase = "stopped"
            self._sweep_intake(
                EngineDeadError("AsyncLLM is shutting down")
            )
            return

    def _serve_until_shutdown(self) -> None:
        while not self._shutdown:
            self._phase = "intake"
            self._drain_intake()
            if self.engine.errored:
                # An idle deployment with a dead executor must not
                # look healthy: heartbeat/disconnect failures are
                # surfaced here even when no request is in flight
                # (step() would never run to notice them).
                raise RuntimeError(self.engine._dead_message())
            if not self.engine.has_unfinished_requests():
                self._phase = "idle"
                self._wait("engine.wait", "idle", 0.2)
                continue
            # The next step queues behind what is on the device, and
            # what arrives after its schedule queues behind both: wait
            # for arrivals while the device allows it (an arrival wakes
            # the loop, which takes it in and asks again; so does the
            # idle loop's own bound, so that a dead executor or a
            # shutdown is seen as soon as when nothing is running).
            slack = self.engine.schedule_slack()
            if slack > 0.0:
                self._phase = "slack"
                self._wait("engine.slack", "slack", min(slack, 0.2))
                continue
            self._phase = "step"
            outputs = self.engine.step()
            if outputs and self._loop is not None:
                last = self.engine.last_step
                self._loop.call_soon_threadsafe(
                    self._dispatch_outputs,
                    outputs,
                    None if last is None else _Flush(*last, len(outputs)),
                )

    def _wait(self, name: str, kind: str, timeout: float) -> None:
        """Wait to be woken, between two steps.  With tracing on the
        wait counts toward the next ``engine.step`` root's ``slack_ms``
        or ``idle_ms``; while a profile captures it is an annotation of
        ``name`` in the host plane besides, which tells waiting from
        the other things an engine thread outside a step can be doing."""
        tracer = get_tracer()
        if not tracer.enabled:
            self._wake.wait(timeout=timeout)
            self._wake.clear()
            return
        t0 = time.monotonic()
        with tracer.annotation(name):
            self._wake.wait(timeout=timeout)
        self._wake.clear()
        self.engine.between_steps[kind] += time.monotonic() - t0

    def _sweep_intake(self, error: BaseException) -> None:
        """Fail work still sitting in the intake: aux futures (callers
        await them and nothing else will ever resolve them) and "add"
        ops (a generate() racing shutdown would otherwise await its
        queue forever — on terminal death _fail_all_queues also covers
        it, but clean shutdown has no fail-all pass)."""
        while True:
            try:
                op, payload = self._intake.get_nowait()
            except _queue.Empty:
                return
            if op == "add":
                # Release the admission reservation even when the loop
                # is gone — the counters must not leak on shutdown.
                self._admission.consumed(
                    payload.pop("_est_tokens", 0),
                    payload.pop("_est_class", None),
                )
            if self._loop is None:
                continue
            try:
                if op == "aux":
                    self._loop.call_soon_threadsafe(
                        self._resolve_aux, payload[2], None, error
                    )
                elif op == "add":
                    self._to_request_queue(payload["request_id"], error)
                elif op == "resume":
                    self._to_request_queue(payload.request_id, error)
            except RuntimeError:
                return  # event loop already closed; nobody awaits

    def _dispatch_outputs(
        self, outputs: list[RequestOutput], flush: _Flush | None = None
    ) -> None:
        """Hand one step's outputs to their streams (event loop).  The
        outputs that hold their request's first token go first, the
        others after them, each group in the engine's order: handlers
        wake and write in the order of the hand-over, and a first token
        is what a client waits for, while a running stream's chunk loses
        nothing by leaving a few places later in the same flush."""
        if flush is not None:
            flush.enter()  # ``frontend.flush`` starts here: the split is its
        first = [out for out in outputs if out.first_token]
        if first:
            outputs = first + [out for out in outputs if not out.first_token]
        if flush is not None:
            flush.first = len(first)
        with get_tracer().annotation("api.dispatch"):
            for out in outputs:
                entry = self._journal.get(out.request_id)
                if entry is not None:
                    # Journal what the client is about to see — what a
                    # recovery would restore as output state on replay.
                    entry.observe(out)
                q = self._queues.get(out.request_id)
                if q is not None:
                    if flush is not None:
                        out.flush = flush
                    q.put_nowait(out)
                elif flush is not None:
                    flush.done()  # nobody is waiting for this one

    def _fail_all_queues(self, e: BaseException) -> None:
        for q in self._queues.values():
            q.put_nowait(e)
        # Satellite fix: sweep the intake from the event loop too — an
        # aux future enqueued after the engine thread's own post-death
        # sweep must still be resolved, never left hanging.
        self._sweep_intake(e)

    def _dead_error(self) -> EngineDeadError:
        """Typed death with the structured HostFailure attached (drain
        contract: every in-flight/queued/new request gets THIS, never a
        hang)."""
        return EngineDeadError(
            str(self._dead) if self._dead is not None
            else self.engine._dead_message(),
            failure=self.failure_info,
        )

    # ---- EngineClient surface ----
    @property
    def is_running(self) -> bool:
        return self._dead is None and self._thread.is_alive()

    @property
    def errored(self) -> bool:
        return self._dead is not None or self.engine.errored

    @property
    def failure_info(self):
        """Structured HostFailure from the control plane, if any.  After
        a failed recovery the current engine may be a half-built one, so
        fall back to the supervisor's originating failure."""
        return (
            getattr(self.engine, "failure_info", None)
            or self.supervisor.last_failure
        )

    def _recovery_pending(self) -> bool:
        """True while the supervisor is (or is about to start)
        rebuilding: the engine errored but the death will be absorbed,
        so callers should wait/503-with-Retry-After, not fail."""
        sup = self.supervisor
        if sup.recovering:
            return True
        return (
            self._dead is None
            and self.engine.errored
            and sup.can_recover(getattr(self.engine, "failure_info", None))
        )

    async def check_health(self) -> None:
        if self._dead is not None:
            raise self._dead_error()
        if self._recovery_pending():
            failure = self.failure_info
            raise EngineRecoveringError(
                "engine is recovering"
                + (f": {failure.describe()}" if failure is not None else ""),
                failure=failure,
                retry_after=self.supervisor.retry_after_seconds(),
            )
        if self.engine.errored:
            raise self._dead_error()

    def _deadline_mono(self, params: SamplingParams) -> float | None:
        """Effective deadline for journaling: the client's deadline_ms
        or the server default, anchored now (the journal mirrors what
        the engine will compute at add time)."""
        ms = params.deadline_ms
        if ms is None:
            default = self.config.scheduler_config.default_deadline_ms
            ms = default if default > 0 else None
        return time.monotonic() + ms / 1000.0 if ms is not None else None

    @property
    def _journaling_enabled(self) -> bool:
        """Journaling exists for replay: in-process recovery
        (supervisor) or cross-process drain hand-off.  With neither
        configured the per-output cumulative copies are skipped."""
        if self._drain_journal_path:
            return True
        return self.supervisor.policy.max_restarts > 0 and getattr(
            self.engine.executor, "supports_recovery", False
        )

    def check_admission(
        self,
        num_requests: int = 1,
        est_tokens: int = 0,
        prompt_token_ids: list[int] | None = None,
        slo_class: str | None = None,
    ) -> None:
        """Pure admission pre-check for the HTTP layer (no
        reservation): raises EngineOverloadedError so rejects become
        429 responses before any SSE stream opens.  generate() runs the
        authoritative reserving check."""
        try:
            self._admission.check(
                num_requests, est_tokens, prompt_token_ids, slo_class
            )
        except EngineOverloadedError as e:
            self.engine.metrics.record_rejected(e.reason)
            raise

    async def generate(
        self,
        request_id: str,
        prompt: str | None = None,
        prompt_token_ids: list[int] | None = None,
        sampling_params: SamplingParams | None = None,
        trace_ctx: tuple | None = None,
        parsed_at: float | None = None,
    ) -> AsyncIterator[RequestOutput]:
        """Feed a request and yield cumulative RequestOutputs until
        finished.  Cancellation (client disconnect) aborts the request.
        ``parsed_at`` (a traced caller's monotonic stamp of where its
        ``api.parse`` ended) is where ``engine.intake`` begins; without
        it, at the put.
        A request submitted while the engine is RECOVERING waits in the
        intake and is admitted by the rebuilt engine.  A request id
        journaled by a previous process's drain resumes instead: the
        journaled prompt/params are re-admitted with the already
        delivered tokens restored, and outputs stay cumulative across
        the hand-off."""
        if self._dead is not None or (
            self.engine.errored and not self._recovery_pending()
        ):
            raise self._dead_error()
        self._loop = asyncio.get_running_loop()
        # Drain-journal resume: bypass admission caps — this is
        # previously ADMITTED work being handed back (losing it would
        # violate the drain contract), not new load.
        resume_entry = self._resumable.pop(request_id, None)
        est = 0
        slo = (
            sampling_params.slo_class
            if sampling_params is not None
            else None
        )
        if resume_entry is None:
            est = estimate_prompt_tokens(prompt, prompt_token_ids)
            try:
                # Bounded admission (ISSUE 8): caps + KV watermark +
                # drain state.  Default-off knobs make this a single
                # flag read in the seed configuration.  The class rides
                # along so per-class shares (ISSUE 16) bill the right
                # bucket.
                self._admission.reserve(est, prompt_token_ids, slo)
            except EngineOverloadedError as e:
                self.engine.metrics.record_rejected(e.reason)
                get_tracer().event(
                    trace_ctx,
                    "engine.rejected",
                    request_id=request_id,
                    reason=e.reason,
                )
                raise
        # Drained by this handler's own iteration below; bounded by the
        # request's max_tokens worth of outputs.
        # vdt-lint: disable=unbounded-queue — consumer is this handler
        q: asyncio.Queue = asyncio.Queue()
        self._queues[request_id] = q
        if resume_entry is not None:
            # Keep journaling the resumed request so a later drain (or
            # recovery) can hand it off again.
            self._journal[request_id] = resume_entry
        elif self._journaling_enabled:
            params = sampling_params or SamplingParams()
            self._journal[request_id] = JournalEntry(
                request_id=request_id,
                prompt=prompt,
                prompt_token_ids=(
                    list(prompt_token_ids)
                    if prompt_token_ids is not None
                    else None
                ),
                sampling_params=params.clone(),
                trace_ctx=trace_ctx,
                deadline_mono=self._deadline_mono(params),
            )
        try:
            if self._dead is not None:
                # Raced the death after the check above: the fail-all
                # sweep may have already run without seeing our queue.
                if resume_entry is None:
                    self._admission.release(est, slo)
                raise self._dead_error()
            if resume_entry is not None:
                self._intake.put(("resume", resume_entry))
            else:
                payload = dict(
                    request_id=request_id,
                    prompt=prompt,
                    prompt_token_ids=prompt_token_ids,
                    sampling_params=sampling_params,
                    trace_ctx=trace_ctx,
                    _est_tokens=est,
                    _est_class=slo,
                )
                if trace_ctx is not None:
                    # For ``engine.intake``: when it was put, and what
                    # the engine thread was doing then.
                    payload["_put"] = (
                        time.monotonic() if parsed_at is None else parsed_at,
                        self._phase,
                    )
                self._intake.put(("add", payload))
            self._wake.set()
            if self._shutdown:
                # Raced shutdown(): the engine thread's final sweep may
                # have run before our put (mirror of the _run_aux
                # re-check — never leave the stream awaiting forever).
                raise EngineDeadError("AsyncLLM is shutting down")
            while True:
                item = await q.get()
                if isinstance(item, BaseException):
                    raise item
                if item.flush is None:
                    yield item
                else:
                    # Tracing on.  The consumer's work on the output (a
                    # streaming handler's delta, dump and write) is an
                    # ``api.write`` annotation while a profile captures,
                    # so the event loop's busy time lies in the host
                    # plane beside the engine thread's; back here it is
                    # through with it: one less of its step's flush.
                    with get_tracer().annotation("api.write"):
                        yield item
                    item.flush.done()
                if item.finished:
                    return
        finally:
            # A resume takeover (api_server.internal_resume replaying
            # an id after a router crash, ISSUE 17) may have replaced
            # this handler's queue with a fresh one; tearing down here
            # would abort the successor's engine-side request.  Only
            # clean up what is still ours.
            if self._queues.get(request_id) is q:
                self._queues.pop(request_id, None)
                self._journal.pop(request_id, None)
                self._intake.put(("abort", request_id))
                self._wake.set()

    async def abort(self, request_id: str) -> None:
        self._intake.put(("abort", request_id))
        self._wake.set()
        self._queues.pop(request_id, None)
        self._journal.pop(request_id, None)

    async def intake_barrier(self) -> None:
        """Resolve once every intake op enqueued before this call has
        been applied by the engine thread.  The takeover fence for a
        replayed /internal/resume (ISSUE 17): after ``abort(rid)`` +
        ``intake_barrier()``, the engine holds no request ``rid`` and no
        stale output of the old incarnation can contaminate a successor
        — outputs dispatched before the barrier resolved found no queue
        or journal registered under the id and were dropped (output
        dispatch and barrier resolution are FIFO on the event loop)."""
        await self._run_aux(lambda: None)

    # ---- graceful drain (ISSUE 8) ----
    @property
    def draining(self) -> bool:
        return self._admission.draining

    @property
    def drain_state_name(self) -> str:
        return self._admission.drain_state_name

    def register_resumable(self, entry: JournalEntry) -> None:
        """Live-migration intake (router/): register a journal entry
        another replica (or the router's own journal) handed off, so the
        next ``generate()`` with the same request id resumes it with the
        already-delivered tokens restored as output state — the same
        preemption-resume path a drain-journal pickup takes.  Bypasses
        admission caps: migrated work was already admitted somewhere,
        and dropping it would violate the zero-lost-work contract."""
        self._resumable[entry.request_id] = entry

    def resumable_request_ids(self) -> list[str]:
        """Request ids a previous process drained into the journal; a
        router (ROADMAP item 1) re-drives each through generate() to
        finish it here."""
        return list(self._resumable)

    async def drain(self, timeout: float | None = None) -> dict:
        """Stop admission, let in-flight work finish for up to
        ``timeout`` seconds, then journal what remains so a restarted
        engine (or another replica) replays it with zero lost admitted
        work — the hand-off primitive a multi-replica router calls
        before taking this backend out of rotation (Llumnix,
        PAPERS.md).

        New requests 429 with reason="draining" from the moment this is
        called; /health reports the drain state.  Requests still live
        at the deadline are journaled to VDT_DRAIN_JOURNAL_PATH (when
        set), then their streams are terminated with a typed
        EngineOverloadedError and the engine-side work is aborted.
        Idempotent: a second call just waits again."""
        if timeout is None:
            timeout = envs.VDT_DRAIN_TIMEOUT_SECONDS
        t0 = time.monotonic()
        self._admission.begin_drain()
        self.engine.metrics.record_drain_state(DRAIN_DRAINING)
        logger.warning(
            "drain started: admission stopped, waiting up to %.1fs for "
            "%d live request(s)",
            timeout,
            len(self._queues),
        )
        deadline = t0 + timeout
        while time.monotonic() < deadline:
            if self._dead is not None:
                break
            if (
                not self.engine.has_unfinished_requests()
                and self._admission.pending()[0] == 0
                and not any(
                    not e.finished for e in self._journal.values()
                )
            ):
                break
            await asyncio.sleep(0.05)
        # Journal whatever is still live, then cut its streams.  The
        # abort sweep covers EVERY live stream (journaling may be
        # disabled); the journal covers what can be replayed.
        leftover = [
            e for e in self._journal.values() if not e.finished
        ]
        journaled = 0
        if leftover and self._drain_journal_path:
            journaled = self._write_drain_journal(leftover)
        journaled_ids = {e.request_id for e in leftover} if journaled else set()
        aborted = []
        for request_id in list(self._queues):
            aborted.append(request_id)
            self._dispatch_item(
                request_id,
                EngineOverloadedError(
                    "engine drained: request journaled for replay"
                    if request_id in journaled_ids
                    else "engine drained: request aborted",
                    reason="draining",
                    retry_after=envs.VDT_RETRY_AFTER_SECONDS,
                ),
            )
        self._admission.finish_drain()
        self.engine.metrics.record_drain_state(DRAIN_DRAINED)
        # Flight-recorder artifact for the hand-off post-mortem trail
        # (ISSUE 12): what the engine was doing up to the drain.
        self.engine.flight_recorder.dump("drain")
        result = {
            "status": "drained",
            "waited_s": round(time.monotonic() - t0, 3),
            "journaled": journaled,
            "aborted": len(aborted),
            "journal_path": (
                self._drain_journal_path if journaled else None
            ),
        }
        logger.warning("drain finished: %s", result)
        return result

    def _write_drain_journal(self, entries: list[JournalEntry]) -> int:
        """Persist unfinished requests for a future process.  Atomic
        write (tmp + rename) so a crash mid-drain never leaves a
        half-journal a restarted engine would trip over."""
        payload = {
            "version": 1,
            "requests": [e.to_dict() for e in entries],
        }
        path = self._drain_journal_path
        tmp = f"{path}.tmp"
        # vdt-lint: disable=async-blocking — drain is a shutdown path,
        # one small local write; the loop is not serving admissions.
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        return len(entries)

    def _load_drain_journal(self) -> dict[str, JournalEntry]:
        """Boot-time pickup of a previous process's drain journal.  The
        file is renamed away immediately so a crash loop can't replay
        the same work twice."""
        path = self._drain_journal_path
        if not path or not os.path.exists(path):
            return {}
        try:
            with open(path) as f:
                payload = json.load(f)
            os.replace(path, f"{path}.consumed")
        except (OSError, ValueError) as e:
            logger.error("drain journal %s unreadable: %s", path, e)
            return {}
        entries = {}
        for item in payload.get("requests", ()):
            try:
                entry = JournalEntry.from_dict(item)
            except (KeyError, TypeError, ValueError) as e:
                logger.error(
                    "drain journal entry %r malformed: %s",
                    item.get("request_id", "?"),
                    e,
                )
                continue
            entries[entry.request_id] = entry
        if entries:
            logger.warning(
                "loaded drain journal %s: %d request(s) resumable via "
                "generate() with the same request id",
                path,
                len(entries),
            )
        return entries

    async def embed(self, prompt_token_ids: list[int]) -> list[float]:
        """Runs on the engine thread between steps (_drain_intake), so
        the aux collective is ordered with step dispatches mesh-wide."""
        return await self._run_aux(
            lambda ids: self.engine.embed(ids), prompt_token_ids
        )

    # Scrape-triggered telemetry pulls are coalesced to this interval:
    # the aux RPC runs on the engine thread between steps, and the
    # /metrics endpoint is unauthenticated — without a floor, a scrape
    # storm (or several scrapers: Prometheus + the router's merged
    # view) would stall token generation behind back-to-back RPCs.
    TELEMETRY_MIN_INTERVAL_SECONDS = 2.0

    async def refresh_device_telemetry(self) -> dict | None:
        """Pull worker XLA/HBM telemetry into the metrics (ISSUE 12).
        Rides the aux path so the collective is ordered with step
        dispatches; /metrics calls this best-effort per scrape, rate-
        limited so concurrent/frequent scrapers coalesce onto one pull
        per interval (the skipped ones serve the last-pulled values)."""
        now = time.monotonic()
        if (
            now - self._telemetry_refreshed
            < self.TELEMETRY_MIN_INTERVAL_SECONDS
        ):
            return None
        # Stamp BEFORE awaiting: scrapers arriving while the pull is in
        # flight skip instead of queueing their own RPCs.
        self._telemetry_refreshed = now
        return await self._run_aux(
            lambda: self.engine.refresh_device_telemetry()
        )

    async def score(self, prompt_token_ids: list[int]) -> list:
        return await self._run_aux(
            lambda ids: self.engine.score(ids), prompt_token_ids
        )

    # ---- KV-page hand-off (disaggregated prefill, ISSUE 15) ----
    # All ride the aux path: allocator mutation happens on the engine
    # thread (serialized with the scheduler) and the export/import
    # collectives stay ordered with step dispatches mesh-wide.
    async def kv_export(
        self, handle: str, layer_start: int, layer_count: int
    ) -> dict:
        return await self._run_aux(
            lambda: self.engine.kv_transfer.export(
                handle, layer_start, layer_count
            )
        )

    async def kv_release(self, handle: str) -> bool:
        return await self._run_aux(
            lambda: self.engine.kv_transfer.release(handle)
        )

    async def kv_import_begin(
        self, token_ids: list[int], resume_from: str | None = None
    ) -> dict:
        return await self._run_aux(
            lambda: self.engine.kv_transfer.begin_import(
                token_ids, resume_from=resume_from
            )
        )

    async def kv_import_chunk(
        self, transfer_id: str, layers: list[dict]
    ) -> dict:
        return await self._run_aux(
            lambda: self.engine.kv_transfer.apply_chunk(
                transfer_id, layers
            )
        )

    async def kv_import_commit(self, transfer_id: str) -> dict:
        return await self._run_aux(
            lambda: self.engine.kv_transfer.commit_import(transfer_id)
        )

    async def kv_import_abort(self, transfer_id: str) -> bool:
        return await self._run_aux(
            lambda: self.engine.kv_transfer.abort_import(transfer_id)
        )

    # Introspection for the API layer.
    @property
    def metrics(self):
        return self.engine.metrics

    def get_model_config(self):
        return self.config.model_config

    @property
    def tokenizer(self):
        return self.engine.tokenizer

    def shutdown(self) -> None:
        self._shutdown = True
        self._wake.set()
        self.supervisor.interrupt()
        self._thread.join(timeout=self.SHUTDOWN_JOIN_SECONDS)
        if self._thread.is_alive():
            # Satellite fix: a failed join used to fall through into
            # engine.shutdown(), racing the stuck thread for the device.
            logger.warning(
                "engine thread did not exit within %.0fs (stuck in phase "
                "%r); skipping engine teardown — the stuck thread still "
                "owns the device",
                self.SHUTDOWN_JOIN_SECONDS,
                self._phase,
            )
            return
        self.engine.shutdown()
