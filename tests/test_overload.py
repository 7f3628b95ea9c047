"""Overload-resilience suite (ISSUE 8): bounded admission, per-request
deadlines, KV backpressure, preempt-to-shed, and graceful drain.

Layered like the feature: scheduler-level unit tests for the shed
policies, AdmissionController unit tests for the caps, AsyncLLM
end-to-end tests on a uniproc CPU engine (step slowed where queue
pressure must build deterministically), HTTP-level 429/Retry-After and
/drain contract tests, and two mock 2-host deployment tests for the
acceptance criteria: drain→restart→replay is bit-identical (greedy,
VDT_MOCK_TOKEN_SEQ), and ≥5× sustained offered load sheds with bounded
queues/memory instead of falling over.

Everything here is default-off in the engine: seed behavior is
unchanged unless the caps/deadlines are configured, which is exactly
what these tests opt into.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import multiprocessing
import os
import random
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from tests.mock_worker import MockWorker  # noqa: F401 (import check)
from tools.chaos_soak import RespawningAgent
from vllm_distributed_tpu.config import (
    CacheConfig,
    EngineArgs,
    SchedulerConfig,
)
from vllm_distributed_tpu.engine.async_llm import AsyncLLM
from vllm_distributed_tpu.engine.overload import (
    AdmissionController,
    EngineOverloadedError,
)
from vllm_distributed_tpu.engine.request import Request, RequestStatus
from vllm_distributed_tpu.engine.scheduler import Scheduler
from vllm_distributed_tpu.engine.supervisor import (
    EngineSupervisor,
    JournalEntry,
    RestartPolicy,
)
from vllm_distributed_tpu.entrypoints.openai.api_server import (
    build_app,
    init_app_state,
    serve_http,
)
from vllm_distributed_tpu.executor.multihost import MultiHostExecutor
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.testing import write_llama_config
from vllm_distributed_tpu.utils import get_open_port

pytestmark = pytest.mark.overload


# ---------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------
def _sp(**kw) -> SamplingParams:
    kw.setdefault("temperature", 0.0)
    kw.setdefault("ignore_eos", True)
    return SamplingParams(**kw)


def _req(rid: str, prompt=(1, 2), **sp_kw) -> Request:
    return Request(
        request_id=rid,
        prompt_token_ids=list(prompt),
        sampling_params=_sp(**sp_kw),
    )


def _mk_engine(tmp_path, name: str, **engine_kw) -> AsyncLLM:
    """Uniproc CPU engine with dummy weights (no safetensors load; the
    overload machinery never looks at weight values)."""
    kw = dict(
        model=write_llama_config(str(tmp_path / name)),
        skip_tokenizer_init=True,
        load_format="dummy",
        num_kv_pages=64,
        max_model_len=128,
        num_decode_steps=1,
    )
    kw.update(engine_kw)
    return AsyncLLM.from_engine_args(EngineArgs(**kw))


@contextlib.contextmanager
def _slowed(engine: AsyncLLM, delay: float):
    """Slow the engine step so queue pressure builds deterministically
    (the pattern test_async_llm uses for loop-isolation tests)."""
    real = engine.engine.step

    def slow_step():
        time.sleep(delay)
        return real()

    engine.engine.step = slow_step
    try:
        yield
    finally:
        engine.engine.step = real


async def _consume(agen):
    last = None
    async for item in agen:
        last = item
    return last


def _run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def _rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return 0.0


# ---------------------------------------------------------------------
# scheduler-level: deadline shed + preempt-to-shed + token accounting
# ---------------------------------------------------------------------
def _mk_sched(num_pages=8, page_size=2, **cfg_kw) -> Scheduler:
    cfg_kw.setdefault("max_num_seqs", 4)
    cfg_kw.setdefault("max_num_batched_tokens", 64)
    cfg_kw.setdefault("max_model_len", 64)
    cfg_kw.setdefault("num_decode_steps", 1)
    return Scheduler(
        SchedulerConfig(**cfg_kw),
        CacheConfig(page_size=page_size),
        num_pages,
    )


def test_waiting_token_counter_tracks_queue():
    sched = _mk_sched()
    a, b = _req("a", [1, 2, 3], max_tokens=4), _req("b", [4, 5], max_tokens=4)
    sched.add_request(a)
    sched.add_request(b)
    assert sched.num_waiting_tokens == 5
    sched.abort_request("b")
    assert sched.num_waiting_tokens == 3
    sched.schedule()  # admits a
    assert sched.num_waiting_tokens == 0
    assert len(sched.waiting) == 0


def test_expired_waiting_request_is_shed_before_prefill():
    sched = _mk_sched()
    req = _req("late", [1, 2], max_tokens=4)
    req.deadline_mono = time.monotonic() - 0.01  # already expired
    sched.add_request(req)
    out = sched.schedule()
    # Never scheduled: no prefill spent, no worker notice (they never
    # saw it), finished out of band with the timeout status.
    assert "late" not in out.num_scheduled_tokens
    assert out.finished_req_ids == []
    shed = sched.take_finished_out_of_band()
    assert [r.request_id for r in shed] == ["late"]
    assert shed[0].status == RequestStatus.FINISHED_TIMEOUT
    assert sched.num_waiting_tokens == 0
    assert not sched.has_unfinished_requests()
    assert sched.num_timeouts == 1


def test_expired_running_request_finishes_with_partial_output():
    sched = _mk_sched()
    req = _req("mid", [1, 2], max_tokens=8, deadline_ms=100_000)
    req.set_deadline(0)  # what LLMEngine.add_request does
    sched.add_request(req)
    # A second live request keeps the post-shed step non-empty, so the
    # finish notice can ride it (empty outputs are never dispatched;
    # notices on them are held for the next real step).
    other = _req("other", [3, 4], max_tokens=8)
    sched.add_request(other)
    out = sched.schedule()
    sched.update_from_output(out, {"mid": [7], "other": [9]})
    assert req.status == RequestStatus.RUNNING
    req.deadline_mono = time.monotonic() - 0.01  # expire mid-decode
    out2 = sched.schedule()
    # The finish notice rides the step like any other finish, so the
    # workers drop their mirrored state.
    assert "mid" in out2.finished_req_ids
    assert "mid" not in out2.num_scheduled_tokens
    assert "other" in out2.num_scheduled_tokens
    shed = sched.take_finished_out_of_band()
    assert [r.request_id for r in shed] == ["mid"]
    assert shed[0].status == RequestStatus.FINISHED_TIMEOUT
    assert shed[0].output_token_ids == [7]  # partial output survives


def test_preempt_shed_policy_threshold():
    sched = _mk_sched(preempt_shed_threshold=1)
    req = _req("thrash", [1, 2], max_tokens=8)
    sched.add_request(req)
    sched.schedule()
    assert req.status == RequestStatus.RUNNING
    # First preemption: under threshold, requeued as usual.
    sched._preempt(req, set())
    assert req.status == RequestStatus.PREEMPTED
    assert req in sched.waiting
    assert sched.take_finished_out_of_band() == []
    sched.schedule()  # resume
    assert req.status == RequestStatus.RUNNING
    # Second preemption crosses the threshold: shed, not requeued.
    sched._preempt(req, set())
    assert req.status == RequestStatus.FINISHED_SHED
    assert req not in sched.waiting
    assert "thrash" not in sched.requests
    shed = sched.take_finished_out_of_band()
    assert [r.request_id for r in shed] == ["thrash"]
    assert sched.num_sheds == 1


def test_preempt_shed_disabled_by_default():
    sched = _mk_sched()  # threshold 0 = seed behavior
    req = _req("resilient", [1, 2], max_tokens=8)
    sched.add_request(req)
    for _ in range(5):
        sched.schedule()
        assert req.status == RequestStatus.RUNNING
        sched._preempt(req, set())
        assert req.status == RequestStatus.PREEMPTED
    assert sched.take_finished_out_of_band() == []
    assert req.num_preemptions == 5


# ---------------------------------------------------------------------
# AdmissionController unit tests
# ---------------------------------------------------------------------
class _FakeAllocator:
    def __init__(self, num_pages=17, page_size=16, free=None):
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_free_pages = free if free is not None else num_pages - 1

    def estimate_cached_tokens(self, token_ids):
        return 0


class _FakeScheduler:
    def __init__(self, waiting=0, waiting_tokens=0, **alloc_kw):
        self.waiting = [None] * waiting
        self.num_waiting_tokens = waiting_tokens
        self.allocator = _FakeAllocator(**alloc_kw)


def _controller(sched=None, **cfg_kw) -> AdmissionController:
    cfg_kw.setdefault("max_num_seqs", 4)
    cfg_kw.setdefault("max_num_batched_tokens", 64)
    ctl = AdmissionController(SchedulerConfig(**cfg_kw), retry_after=7)
    ctl.attach_scheduler(sched or _FakeScheduler())
    return ctl


def test_admission_defaults_are_wide_open():
    ctl = _controller(_FakeScheduler(waiting=10_000, waiting_tokens=1 << 20))
    ctl.check(1, 1 << 16)  # no caps configured: anything goes


def test_admission_queue_cap():
    ctl = _controller(_FakeScheduler(waiting=2), max_waiting_requests=3)
    ctl.reserve(5)  # depth 2 + pending 1 = 3 == cap: admitted
    with pytest.raises(EngineOverloadedError) as ei:
        ctl.reserve(5)
    assert ei.value.reason == "queue_full"
    assert ei.value.retry_after == 7
    # Consumption frees the pending slot.
    ctl.consumed(5)
    assert ctl.pending() == (0, 0)


def test_admission_token_cap():
    ctl = _controller(
        _FakeScheduler(waiting_tokens=6), max_queued_tokens=10
    )
    ctl.reserve(4)
    with pytest.raises(EngineOverloadedError) as ei:
        ctl.reserve(1)
    assert ei.value.reason == "queued_tokens"
    ctl.release(4)
    ctl.reserve(1)  # released capacity is reusable


def test_admission_kv_watermark():
    # usable = 16, watermark 0.5 -> keep 8 free.  120-token prompt
    # needs ceil(120/16)+1 = 9 pages; 16 - 9 = 7 < 8 -> reject.
    sched = _FakeScheduler(num_pages=17, page_size=16)
    ctl = _controller(sched, kv_admission_watermark=0.5)
    with pytest.raises(EngineOverloadedError) as ei:
        ctl.check(1, 120, list(range(120)))
    assert ei.value.reason == "kv_pressure"
    ctl.check(1, 16, list(range(16)))  # 2 pages: plenty left


def test_admission_drain_state():
    ctl = _controller()
    ctl.begin_drain()
    with pytest.raises(EngineOverloadedError) as ei:
        ctl.check()
    assert ei.value.reason == "draining"
    assert ctl.drain_state_name == "draining"
    ctl.finish_drain()
    assert ctl.drain_state_name == "drained"


# ---------------------------------------------------------------------
# AsyncLLM end-to-end on a uniproc CPU engine
# ---------------------------------------------------------------------
def test_queue_cap_rejects_burst(tmp_path, monkeypatch):
    monkeypatch.setenv("VDT_MAX_WAITING_REQUESTS", "2")
    engine = _mk_engine(tmp_path, "qcap", max_num_seqs=1)
    try:
        with _slowed(engine, 0.3):

            async def go():
                outcomes = {"completed": 0}
                rejects = []

                async def one(i):
                    try:
                        out = await _consume(
                            engine.generate(
                                f"q{i}",
                                prompt_token_ids=[1, 2, 3],
                                sampling_params=_sp(max_tokens=2),
                            )
                        )
                        assert out.finished
                        outcomes["completed"] += 1
                    except EngineOverloadedError as e:
                        rejects.append(e)

                # Warm one request into RUNNING (max_num_seqs=1), then
                # burst: the waiting queue caps at 2, the rest 429.
                first = asyncio.create_task(one(0))
                await asyncio.sleep(0.15)
                await asyncio.gather(*(one(i) for i in range(1, 6)))
                await first
                return outcomes, rejects

            outcomes, rejects = _run(go())
        assert rejects, "cap never triggered"
        assert all(e.reason == "queue_full" for e in rejects)
        assert outcomes["completed"] + len(rejects) == 6
        # The warm request may still occupy a waiting slot when the
        # burst lands (slow step delays its first schedule), so the
        # admitted count is 2 or 3 depending on that race — but the
        # cap itself is exact: everyone past it was rejected.
        assert outcomes["completed"] >= 2
        # The rejection counter observed every shed.
        rendered = engine.metrics.render().decode()
        assert 'vllm:requests_rejected_total{model_name' in rendered
    finally:
        engine.shutdown()


def test_queued_token_cap_rejects(tmp_path, monkeypatch):
    monkeypatch.setenv("VDT_MAX_QUEUED_TOKENS", "8")
    engine = _mk_engine(tmp_path, "tcap", max_num_seqs=1)
    try:
        with _slowed(engine, 0.3):

            async def go():
                completed, rejects = 0, []

                async def one(i):
                    nonlocal completed
                    try:
                        await _consume(
                            engine.generate(
                                f"t{i}",
                                prompt_token_ids=[1, 2, 3, 4, 5],
                                sampling_params=_sp(max_tokens=2),
                            )
                        )
                        completed += 1
                    except EngineOverloadedError as e:
                        rejects.append(e)

                first = asyncio.create_task(one(0))
                await asyncio.sleep(0.15)
                await asyncio.gather(*(one(i) for i in range(1, 4)))
                await first
                return completed, rejects

            completed, rejects = _run(go())
        assert rejects, "token cap never triggered"
        assert all(e.reason == "queued_tokens" for e in rejects)
        assert completed + len(rejects) == 4
    finally:
        engine.shutdown()


def test_kv_watermark_rejects_long_prompt(tmp_path, monkeypatch):
    monkeypatch.setenv("VDT_KV_ADMISSION_WATERMARK", "0.5")
    engine = _mk_engine(
        tmp_path, "wm", num_kv_pages=17, max_model_len=256
    )
    try:

        async def go():
            # 120-token prompt: ~9 pages against 16 usable with a
            # keep-8-free watermark -> rejected before any prefill.
            with pytest.raises(EngineOverloadedError) as ei:
                await _consume(
                    engine.generate(
                        "long",
                        prompt_token_ids=list(range(1, 121)),
                        sampling_params=_sp(max_tokens=2),
                    )
                )
            assert ei.value.reason == "kv_pressure"
            # A short prompt sails through the same watermark.
            out = await _consume(
                engine.generate(
                    "short",
                    prompt_token_ids=list(range(1, 17)),
                    sampling_params=_sp(max_tokens=2),
                )
            )
            assert out.finished

        _run(go())
    finally:
        engine.shutdown()


def test_deadline_waiting_request_times_out(tmp_path):
    engine = _mk_engine(tmp_path, "dls", max_num_seqs=1)
    try:
        with _slowed(engine, 0.25):

            async def go():
                hog = asyncio.create_task(
                    _consume(
                        engine.generate(
                            "hog",
                            prompt_token_ids=[1, 2, 3],
                            sampling_params=_sp(max_tokens=8),
                        )
                    )
                )
                await asyncio.sleep(0.1)
                late = await _consume(
                    engine.generate(
                        "late",
                        prompt_token_ids=[4, 5],
                        sampling_params=_sp(max_tokens=4, deadline_ms=300),
                    )
                )
                return await hog, late

            hog, late = _run(go())
        assert hog.finished
        assert len(hog.outputs[0].token_ids) == 8  # hog is unaffected
        assert late.finished
        assert late.outputs[0].finish_reason == "timeout"
        assert late.outputs[0].token_ids == []  # shed before prefill
    finally:
        engine.shutdown()


def test_deadline_running_request_partial_output(tmp_path):
    engine = _mk_engine(tmp_path, "dlr")
    try:
        with _slowed(engine, 0.15):

            async def go():
                return await _consume(
                    engine.generate(
                        "slowpoke",
                        prompt_token_ids=[1, 2, 3],
                        sampling_params=_sp(
                            max_tokens=50, deadline_ms=500
                        ),
                    )
                )

            out = _run(go())
        assert out.finished
        assert out.outputs[0].finish_reason == "timeout"
        # Partial output: started decoding, stopped at the deadline.
        assert 0 < len(out.outputs[0].token_ids) < 50
    finally:
        engine.shutdown()


def test_server_default_deadline_env(tmp_path, monkeypatch):
    monkeypatch.setenv("VDT_DEFAULT_DEADLINE_MS", "500")
    engine = _mk_engine(tmp_path, "dld")
    try:
        with _slowed(engine, 0.15):
            out = _run(
                _consume(
                    engine.generate(
                        "default-dl",
                        prompt_token_ids=[1, 2, 3],
                        sampling_params=_sp(max_tokens=50),
                    )
                )
            )
        assert out.outputs[0].finish_reason == "timeout"
        assert len(out.outputs[0].token_ids) < 50
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------
# supervisor: an expired request is never replayed
# ---------------------------------------------------------------------
class _StubScheduler:
    def __init__(self):
        self.requests = {}


class _StubEngine:
    def __init__(self):
        self.scheduler = _StubScheduler()
        self.detokenizers = {}
        self.added = []

    def add_request(
        self,
        request_id,
        prompt=None,
        prompt_token_ids=None,
        sampling_params=None,
        trace_ctx=None,
    ):
        req = Request(
            request_id=request_id,
            prompt_token_ids=list(prompt_token_ids or [1]),
            sampling_params=sampling_params or SamplingParams(),
        )
        self.scheduler.requests[request_id] = req
        self.added.append(request_id)


class _StubLLM:
    def __init__(self):
        self._journal = {}
        self.delivered = []

    def _to_request_queue(self, request_id, item):
        self.delivered.append((request_id, item))


def test_replay_skips_expired_entry():
    llm = _StubLLM()
    sup = EngineSupervisor(
        llm, policy=RestartPolicy(1, 0.1, 1.0, 60.0)
    )
    expired = JournalEntry(
        request_id="expired",
        prompt=None,
        prompt_token_ids=[1, 2],
        sampling_params=_sp(max_tokens=8),
        admitted=True,
        deadline_mono=time.monotonic() - 1.0,
        emitted_token_ids=[5, 6],
    )
    live = JournalEntry(
        request_id="live",
        prompt=None,
        prompt_token_ids=[3, 4],
        sampling_params=_sp(max_tokens=8),
        admitted=True,
        deadline_mono=time.monotonic() + 60.0,
        emitted_token_ids=[7],
    )
    llm._journal = {"expired": expired, "live": live}
    engine = _StubEngine()
    replayed = sup._replay(engine)
    assert replayed == 1
    assert engine.added == ["live"]  # the expired one never re-admitted
    # The expired request's client got a finished timeout output with
    # what was already delivered.
    assert len(llm.delivered) == 1
    rid, out = llm.delivered[0]
    assert rid == "expired"
    assert out.finished
    assert out.outputs[0].finish_reason == "timeout"
    assert out.outputs[0].token_ids == [5, 6]
    assert expired.finished
    # The live replay preserved its ORIGINAL deadline.
    req = engine.scheduler.requests["live"]
    assert req.deadline_mono == live.deadline_mono


def test_journal_entry_drain_round_trip():
    entry = JournalEntry(
        request_id="rt",
        prompt="hi",
        prompt_token_ids=[1, 2, 3],
        sampling_params=_sp(max_tokens=9, deadline_ms=1000),
        emitted_token_ids=[4, 5],
        emitted_logprobs=[{4: -0.5}, {5: -0.25}],
        emitted_cumulative_logprob=-0.75,
    )
    back = JournalEntry.from_dict(
        json.loads(json.dumps(entry.to_dict()))
    )
    assert back.request_id == "rt"
    assert back.prompt_token_ids == [1, 2, 3]
    assert back.sampling_params.max_tokens == 9
    assert back.emitted_token_ids == [4, 5]
    assert back.emitted_logprobs == [{4: -0.5}, {5: -0.25}]
    assert back.deadline_mono is None  # never crosses processes


# ---------------------------------------------------------------------
# HTTP: 429 + Retry-After, deadline header, /drain, /health states
# ---------------------------------------------------------------------
def _client_call(app, coro_fn):
    async def go():
        server = TestServer(app)
        client = TestClient(server)
        await client.start_server()
        try:
            return await coro_fn(client)
        finally:
            await client.close()

    return _run(go())


def test_http_429_retry_after_and_drain(tmp_path, monkeypatch):
    monkeypatch.setenv("VDT_MAX_WAITING_REQUESTS", "1")
    monkeypatch.setenv("VDT_OVERLOAD_RETRY_AFTER_SECONDS", "3")
    engine = _mk_engine(tmp_path, "http", max_num_seqs=1)
    state = init_app_state(engine, served_model_name="ov")

    async def go(client):
        body = {
            "prompt": [1, 2, 3],
            "max_tokens": 2,
            "temperature": 0.0,
            "ignore_eos": True,
        }
        with _slowed(engine, 0.3):
            responses = await asyncio.gather(
                *(
                    client.post("/v1/completions", json=body)
                    for _ in range(5)
                )
            )
            by_status = {}
            for r in responses:
                by_status.setdefault(r.status, []).append(r)
            assert 429 in by_status, {
                s: len(v) for s, v in by_status.items()
            }
            rejected = by_status[429][0]
            assert rejected.headers["Retry-After"] == "3"
            payload = await rejected.json()
            assert payload["type"] == "overloaded_error"
            assert payload["reason"] == "queue_full"
            assert 200 in by_status  # the admitted ones served fine
        # Malformed deadline header is a 400, not a surprise.
        r = await client.post(
            "/v1/completions",
            json=body,
            headers={"X-VDT-Deadline-Ms": "soon"},
        )
        assert r.status == 400
        # A generous header deadline passes through harmlessly.
        r = await client.post(
            "/v1/completions",
            json=body,
            headers={"X-VDT-Deadline-Ms": "60000"},
        )
        assert r.status == 200
        # The server's own 429 counter observed the sheds.
        metrics_text = await (await client.get("/metrics")).text()
        rejected_lines = [
            line
            for line in metrics_text.splitlines()
            if line.startswith("vllm:requests_rejected_total{")
            and 'reason="queue_full"' in line
        ]
        assert rejected_lines and float(
            rejected_lines[0].rsplit(" ", 1)[1]
        ) >= 1
        # ---- drain: stop admission, report state, 429 new work ----
        r = await client.post("/drain", json={})
        drained = await r.json()
        assert r.status == 200
        assert drained["status"] == "drained"
        assert drained["aborted"] == 0  # nothing was in flight
        health = await client.get("/health")
        assert health.status == 503
        assert (await health.json())["status"] == "drained"
        r = await client.post("/v1/completions", json=body)
        assert r.status == 429
        assert (await r.json())["reason"] == "draining"
        metrics_text = await (await client.get("/metrics")).text()
        assert "vllm:engine_drain_state" in metrics_text

    try:
        _client_call(build_app(state), go)
    finally:
        engine.shutdown()


def test_nonstreaming_client_disconnect_aborts(tmp_path):
    """ISSUE 8 satellite: a non-streaming completion whose client hangs
    up must stop generating server-side (handler_cancellation in
    serve_http; streaming already aborted via its failing writes)."""
    engine = _mk_engine(tmp_path, "disc")
    port = get_open_port()

    async def go():
        state = init_app_state(engine, served_model_name="d")
        runner = await serve_http(
            build_app(state), host="127.0.0.1", port=port
        )
        try:
            with _slowed(engine, 0.15):
                body = json.dumps(
                    {
                        "prompt": [1, 2, 3],
                        "max_tokens": 100,  # ~15s if left running
                        "temperature": 0.0,
                        "ignore_eos": True,
                    }
                ).encode()
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port
                )
                writer.write(
                    b"POST /v1/completions HTTP/1.1\r\n"
                    b"Host: t\r\nContent-Type: application/json\r\n"
                    b"Content-Length: %d\r\n\r\n" % len(body) + body
                )
                await writer.drain()
                # Let the request get admitted and start decoding...
                t0 = time.monotonic()
                while (
                    not engine.engine.scheduler.has_unfinished_requests()
                    and time.monotonic() - t0 < 5
                ):
                    await asyncio.sleep(0.05)
                assert engine.engine.scheduler.has_unfinished_requests()
                # ...then vanish.
                writer.close()
                t0 = time.monotonic()
                while engine.engine.scheduler.has_unfinished_requests():
                    assert time.monotonic() - t0 < 6, (
                        "request kept generating after client disconnect"
                    )
                    await asyncio.sleep(0.1)
        finally:
            await runner.cleanup()

    try:
        _run(go())
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------
# mock 2-host deployment: the two acceptance tests
# ---------------------------------------------------------------------
class OverloadMultiHostExecutor(MultiHostExecutor):
    worker_cls = "tests.mock_worker.MockWorker"


def _agent_with_env(port, env):
    for k, v in (env or {}).items():
        os.environ[k] = v
    from vllm_distributed_tpu.distributed.agent import remote_main

    remote_main("127.0.0.1", port)


def _spawn_agent(port, extra_env=None):
    env = {
        "VDT_ADVERTISE_NUM_CHIPS": "4",
        "VDT_ADVERTISE_PLATFORM": "cpu",
        "VDT_MOCK_TOKEN_SEQ": "1",
        "VDT_MOCK_EXECUTE_SLEEP_SECONDS": "0.05",
        **(extra_env or {}),
    }
    proc = multiprocessing.Process(
        target=_agent_with_env, args=(port, env), daemon=True
    )
    proc.start()
    return proc


def _deployment_env(monkeypatch, tmp_path, port):
    monkeypatch.setenv("VDT_SERVER_PORT", str(port))
    monkeypatch.setenv("VDT_CONNECT_TIMEOUT_SECONDS", "30")
    monkeypatch.setenv("VDT_HEARTBEAT_INTERVAL_SECONDS", "0.5")
    monkeypatch.setenv("VDT_HEARTBEAT_MISS_THRESHOLD", "3")
    monkeypatch.setenv("VDT_EXECUTE_MODEL_TIMEOUT_SECONDS", "10")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    monkeypatch.setenv("VDT_MOCK_TOKEN_SEQ", "1")
    monkeypatch.setenv("VDT_MOCK_EXECUTE_SLEEP_SECONDS", "0.05")


def _deployment_args(tmp_path, **kw):
    return EngineArgs(
        model=write_llama_config(str(tmp_path / "m")),
        skip_tokenizer_init=True,
        load_format="dummy",
        num_hosts=2,
        num_decode_steps=1,
        max_model_len=512,
        distributed_executor_backend=OverloadMultiHostExecutor,
        **kw,
    )


def test_drain_restart_replay_bit_identical(tmp_path, monkeypatch):
    """Acceptance: /drain under live streaming traffic → restart →
    journal replay loses zero admitted requests and finishes them
    bit-identically (greedy, VDT_MOCK_TOKEN_SEQ)."""
    port = get_open_port()
    journal = tmp_path / "drain.json"
    _deployment_env(monkeypatch, tmp_path, port)
    monkeypatch.setenv("VDT_DRAIN_JOURNAL_PATH", str(journal))
    agents = RespawningAgent(port, spawn=_spawn_agent)
    engine = AsyncLLM.from_engine_args(_deployment_args(tmp_path))
    prompt = [1, 2, 3]
    max_tokens = 20
    # Mock seq mode: token i == absolute position, so the uninterrupted
    # greedy run is exactly 3..22 — the drain+restart+replay run must
    # produce the SAME sequence.
    expected = list(range(3, 3 + max_tokens))

    async def phase_one():
        tokens_seen: list[int] = []
        cut = asyncio.Event()

        async def victim():
            try:
                async for out in engine.generate(
                    "handoff",
                    prompt_token_ids=list(prompt),
                    sampling_params=_sp(max_tokens=max_tokens),
                ):
                    tokens_seen[:] = list(out.outputs[0].token_ids)
                pytest.fail("victim finished before the drain cut it")
            except EngineOverloadedError as e:
                assert e.reason == "draining"
                cut.set()

        vt = asyncio.create_task(victim())
        t0 = time.monotonic()
        while len(tokens_seen) < 2:
            assert time.monotonic() - t0 < 20
            await asyncio.sleep(0.02)
        result = await engine.drain(timeout=0.2)
        await asyncio.wait_for(vt, timeout=5)
        assert cut.is_set()
        assert result["journaled"] == 1
        assert result["aborted"] == 1
        assert result["journal_path"] == str(journal)
        # /health surfaces the drain state.
        assert engine.drain_state_name == "drained"
        return list(tokens_seen)

    try:
        tokens_before = _run(phase_one())
        assert tokens_before == expected[: len(tokens_before)]
        assert len(tokens_before) < max_tokens  # genuinely mid-stream
    finally:
        engine.shutdown()
    assert journal.exists()

    # "Restart": a fresh AsyncLLM in the same environment picks the
    # journal up and finishes the drained request when the client
    # re-attaches under the same request id.
    engine2 = AsyncLLM.from_engine_args(_deployment_args(tmp_path))
    try:
        assert engine2.resumable_request_ids() == ["handoff"]

        async def phase_two():
            return await _consume(engine2.generate("handoff"))

        final = _run(phase_two())
        assert final.finished
        assert final.outputs[0].finish_reason == "length"
        # Zero lost admitted work, bit-identical greedy output.
        assert list(final.outputs[0].token_ids) == expected
        # The journal was consumed: a crash loop can't double-replay.
        assert not journal.exists()
        assert engine2.resumable_request_ids() == []
    finally:
        engine2.shutdown()
        agents.stop()


def test_overload_5x_sheds_and_stays_bounded(tmp_path, monkeypatch):
    """Acceptance: ≥5× sustained offered load on the mock 2-host
    deployment sheds with typed rejections, keeps admitted-request ITL
    p99 bounded, and the waiting queue + RSS plateau."""
    port = get_open_port()
    _deployment_env(monkeypatch, tmp_path, port)
    monkeypatch.setenv("VDT_MAX_WAITING_REQUESTS", "8")
    baseline_threads = {
        t for t in threading.enumerate() if t.name.startswith("vdt-")
    }
    agent = _spawn_agent(port)
    engine = AsyncLLM.from_engine_args(
        _deployment_args(tmp_path, max_num_seqs=4)
    )
    # Capacity ceiling: 4 seats × (1 token / 0.05 s step) = 80 tok/s →
    # at 5 output tokens/request, ≤16 req/s.  Offer 80 req/s = ≥5×.
    offered_rps = 80.0
    duration_s = 2.5
    stats = {"completed": 0, "rejected": 0, "errors": 0}
    itls: list[float] = []
    max_waiting = 0

    async def one(i: int):
        last = None
        try:
            async for out in engine.generate(
                f"ov-{i}",
                prompt_token_ids=[1, 2, 3],
                sampling_params=_sp(max_tokens=5),
            ):
                now = time.monotonic()
                if last is not None:
                    itls.append(now - last)
                last = now
            stats["completed"] += 1
        except EngineOverloadedError:
            stats["rejected"] += 1
        except Exception:  # noqa: BLE001 — accounted and asserted == 0
            stats["errors"] += 1

    async def go():
        nonlocal max_waiting
        rng = random.Random(5)
        rss0 = _rss_mb()
        tasks = []
        end = time.monotonic() + duration_s
        i = 0
        while time.monotonic() < end:
            tasks.append(asyncio.create_task(one(i)))
            i += 1
            max_waiting = max(
                max_waiting, len(engine.engine.scheduler.waiting)
            )
            await asyncio.sleep(rng.expovariate(offered_rps))
        await asyncio.gather(*tasks)
        return rss0, _rss_mb(), i

    try:
        rss0, rss1, offered = _run(go())
    finally:
        engine.shutdown()
        if agent.is_alive():
            agent.terminate()
        agent.join(timeout=5)

    assert stats["errors"] == 0, stats
    assert stats["completed"] + stats["rejected"] == offered
    # Load genuinely exceeded capacity and the engine SHED rather than
    # queued: most offered work was rejected with the typed 429 error.
    assert offered >= duration_s * 40, f"arrival loop too slow: {offered}"
    assert stats["rejected"] > stats["completed"], stats
    assert stats["completed"] > 0
    # Bounded admission held: the waiting queue never exceeded the cap.
    assert max_waiting <= 8, max_waiting
    # Admitted-request ITL stayed bounded (sheds can't pollute this:
    # rejected requests never produce tokens).
    if itls:
        p99 = sorted(itls)[min(len(itls) - 1, int(0.99 * len(itls)))]
        assert p99 < 2.0, f"ITL p99 {p99:.2f}s under overload"
    # Memory plateaued: shedding, not queue growth.
    assert rss1 - rss0 < 150, f"RSS grew {rss1 - rss0:.0f} MiB"
    # No leaked engine threads after shutdown.
    t0 = time.monotonic()
    while time.monotonic() - t0 < 8:
        extra = [
            t
            for t in threading.enumerate()
            if t.name.startswith("vdt-") and t not in baseline_threads
        ]
        if not extra:
            break
        time.sleep(0.1)
    assert not extra, f"leaked threads: {[t.name for t in extra]}"


def test_chaos_soak_overload_smoke(tmp_path):
    """Satellite: the chaos-soak overload phase holds its bounded-memory
    contract across kill→recover cycles (1-cycle smoke; longer loops
    stay behind the soak marker)."""
    from tools.chaos_soak import run_soak

    report = run_soak(
        cycles=1,
        model_dir=write_llama_config(str(tmp_path / "soak")),
        max_tokens=10,
        kill_after_tokens=3,
        overload_rps=40.0,
        overload_cap=6,
    )
    assert report["replay_failures"] == 0
    overload = report["overload"]
    assert overload["offered"] > 0
    assert overload["rejected"] > 0, overload
    assert overload["max_waiting_depth"] <= 6, overload
    assert overload["bounded"], overload
