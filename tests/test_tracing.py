"""End-to-end request tracing (ISSUE 5).

Unit level: tracer semantics (ids, nesting, ring bound, clock-offset
adoption, the allocation-free no-op path) and trace integrity (children
nest inside parents, per-host monotonic timestamps, no span leaks open).

E2E level (the acceptance scenario): an OpenAI-API request served by an
AsyncLLM over the mocked 2-host MultiHostExecutor produces ONE trace
containing api → queue → prefill → decode → rpc-dispatch →
worker-execute spans with consistent parent/child links across the RPC
boundary; /debug/traces serves it as JSON and as Chrome trace-event
format; the trace id is echoed in a response header; the per-stage
Prometheus histograms are fed from the same spans.  With VDT_TRACING
unset the engine loop runs the no-op tracer and /debug/traces is 404.
"""

import asyncio
import json
import multiprocessing
import os
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from tests.mock_worker import MockWorker  # noqa: F401 (import check)
from tests.utils import make_tiny_llama
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.distributed.agent import remote_main
from vllm_distributed_tpu.engine.async_llm import AsyncLLM
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.entrypoints.openai.api_server import (
    TRACE_HEADER,
    ServerState,
    build_app,
    init_app_state,
)
from vllm_distributed_tpu.executor.multihost import MultiHostExecutor
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.testing import write_llama_config
from vllm_distributed_tpu.tracing import (
    NOOP_SPAN,
    Tracer,
    get_tracer,
)
from vllm_distributed_tpu.utils import get_open_port

EPS = 0.1  # interval-nesting tolerance (separate wall-clock reads)


# ---------------------------------------------------------------------
# tracer unit tests
# ---------------------------------------------------------------------
def test_disabled_tracer_is_allocation_free_noop():
    t = Tracer()
    assert not t.enabled
    # Every open returns the SAME shared object: no per-call allocation.
    assert t.span("a") is NOOP_SPAN
    assert t.span("b", trace_root=True) is NOOP_SPAN
    with t.span("c") as sp:
        sp.set_attribute("k", "v")  # all no-ops
    t.record_span("d", 0.0, 1.0, parent=("t", "s"))
    t.event(("t", "s"), "e")
    assert t.snapshot() == []
    assert t.num_open_spans == 0


def test_ids_are_w3c_sized():
    t = Tracer().configure(True)
    with t.span("root", trace_root=True) as root:
        pass
    assert len(root.trace_id) == 32  # 128-bit hex
    assert len(root.span_id) == 16  # 64-bit hex
    int(root.trace_id, 16), int(root.span_id, 16)


def test_context_nesting_and_finalize():
    t = Tracer().configure(True, ring_size=8)
    with t.span("root", trace_root=True) as root:
        with t.span("child") as child:
            with t.span("grandchild") as grand:
                pass
        # Sibling opened after child closed inherits root again.
        with t.span("sibling") as sib:
            pass
    assert child.parent_id == root.span_id
    assert grand.parent_id == child.span_id
    assert sib.parent_id == root.span_id
    snap = t.snapshot()
    assert len(snap) == 1 and snap[0]["complete"]
    assert snap[0]["root_span_id"] == root.span_id
    assert t.num_open_spans == 0


def test_span_without_context_is_dropped():
    """Untraced work stays untraced: a child span with no parent and no
    root flag is the no-op singleton, not an orphan trace."""
    t = Tracer().configure(True)
    assert t.span("orphan") is NOOP_SPAN
    assert t.snapshot() == []


def test_ring_buffer_bounds_completed_traces():
    t = Tracer().configure(True, ring_size=4)
    ids = []
    for _ in range(10):
        with t.span("root", trace_root=True) as root:
            pass
        ids.append(root.trace_id)
    snap = t.snapshot()
    assert len(snap) == 4
    assert [tr["trace_id"] for tr in snap] == ids[-4:]  # oldest evicted
    assert t.get_trace(ids[0]) is None


def test_ring_shrink_reindexes_finished_traces():
    """Reconfiguring to a smaller ring must evict from the id index too:
    get_trace() and snapshot() stay in sync and dropped traces are freed."""
    t = Tracer().configure(True, ring_size=8)
    ids = []
    for _ in range(8):
        with t.span("root", trace_root=True) as root:
            pass
        ids.append(root.trace_id)
    t.configure(True, ring_size=2)
    assert [tr["trace_id"] for tr in t.snapshot()] == ids[-2:]
    for tid in ids[:-2]:
        assert t.get_trace(tid) is None
    for tid in ids[-2:]:
        assert t.get_trace(tid) is not None


def test_metrics_sink_cleared_only_for_owner():
    """clear_metrics_sink detaches the caller's sink but never a newer
    engine's: the slot must not outlive the engine that installed it."""
    t = Tracer()
    sink_a: list = []
    sink_b: list = []
    t.set_metrics_sink(sink_a.append)
    t.clear_metrics_sink(sink_b.append)
    assert t._metrics_sink is not None  # someone else's sink survives
    t.clear_metrics_sink(sink_a.append)
    assert t._metrics_sink is None


def test_overflow_evicted_trace_not_duplicated_on_root_close():
    """A trace force-evicted from the active set (too many in flight)
    whose root span closes afterwards must not enter the ring twice or
    desync the trace_id index."""
    t = Tracer().configure(True, ring_size=4)
    # The active set caps at max(ring_size, 64): hold 70 roots open.
    roots = [t.span(f"root{i}", trace_root=True) for i in range(70)]
    for r in roots:
        r.__enter__()
    for r in reversed(roots):
        r.__exit__(None, None, None)
    snap = t.snapshot()
    ids = [tr["trace_id"] for tr in snap]
    assert len(ids) == len(set(ids)) == 4  # no duplicates, ring bound
    for tid in ids:
        assert t.get_trace(tid) is not None  # index consistent
    assert t.num_open_spans == 0


def test_adopt_applies_clock_offset():
    t = Tracer().configure(True)
    with t.span("root", trace_root=True) as root:
        pass
    # Remote host's clock runs 5s ahead; a low-RTT sample established it.
    t.set_clock_offset("host1", 5.0, rtt=0.001)
    t.adopt(
        [
            {
                "name": "worker.execute",
                "trace_id": root.trace_id,
                "span_id": "aa" * 8,
                "parent_id": root.span_id,
                "host": "host1",
                "start": root.start + 5.0 + 0.01,
                "duration": 0.002,
                "attributes": {},
            }
        ]
    )
    trace = t.get_trace(root.trace_id)
    adopted = [s for s in trace["spans"] if s["name"] == "worker.execute"]
    assert len(adopted) == 1
    # Mapped back onto the local timeline: ~10ms after root start.
    assert abs(adopted[0]["start"] - (root.start + 0.01)) < 1e-6


def test_clock_offset_prefers_low_rtt_samples():
    t = Tracer().configure(True)
    t.set_clock_offset("h", 1.0, rtt=0.001)
    t.set_clock_offset("h", 99.0, rtt=0.5)  # congested sample: rejected
    assert t.clock_offset("h") == 1.0
    t.set_clock_offset("h", 2.0, rtt=0.0009)  # better sample: accepted
    assert t.clock_offset("h") == 2.0


def test_metrics_sink_fed_from_spans():
    observed = []
    t = Tracer().configure(True)
    t.set_metrics_sink(lambda name, dur: observed.append((name, dur)))
    with t.span("root", trace_root=True):
        pass
    t.record_span("scheduler.schedule", 0.0, 0.25, parent=None)
    t.set_metrics_sink(None)
    names = [n for n, _ in observed]
    assert "root" in names
    # record_span without a trace context still feeds the sink (stage
    # histograms populate even for untraced engine-level callers).
    assert ("scheduler.schedule", 0.25) in observed


def test_chrome_export_is_valid_trace_event_json():
    t = Tracer().configure(True)
    with t.span("root", trace_root=True, rid="r1") as root:
        with t.span("child"):
            pass
        t.event(root.ctx, "engine.preempted", request_id="r1")
    chrome = json.loads(t.to_chrome_json())
    events = chrome["traceEvents"]
    assert isinstance(events, list) and events
    complete = [e for e in events if e.get("ph") == "X"]
    instants = [e for e in events if e.get("ph") == "i"]
    meta = [e for e in events if e.get("ph") == "M"]
    assert len(complete) == 2 and len(instants) == 1 and meta
    for e in complete:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["args"]["trace_id"] == root.trace_id
    assert any(
        m["args"]["name"] == "driver"
        for m in meta
        if m["name"] == "process_name"
    )


def test_open_span_accounting_survives_errors():
    """A raise inside a with-span must close it (no leaked open span) —
    the property the code-hygiene start_span lint protects."""
    t = Tracer().configure(True)
    with pytest.raises(ValueError):
        with t.span("root", trace_root=True):
            with t.span("child"):
                raise ValueError("boom")
    assert t.num_open_spans == 0
    snap = t.snapshot()
    child = next(
        s for s in snap[0]["spans"] if s["name"] == "child"
    )
    assert child["attributes"]["error"] == "ValueError"


# ---------------------------------------------------------------------
# trace_summary CLI
# ---------------------------------------------------------------------
def test_trace_summary_cli(tmp_path, capsys):
    from tools.trace_summary import main, summarize

    t = Tracer().configure(True)
    for _ in range(3):
        with t.span("root", trace_root=True) as root:
            t.record_span(
                "engine.queue", root.start, 0.01, parent=root.ctx
            )
            t.record_span(
                "engine.decode", root.start, 0.10, parent=root.ctx
            )
            t.event(root.ctx, "engine.preempted")  # instant: excluded
    traces = t.snapshot()
    stats = summarize(traces)
    assert stats["engine.queue"]["count"] == 3
    assert abs(stats["engine.decode"]["p50"] - 0.10) < 1e-9
    dump = tmp_path / "traces.json"
    dump.write_text(json.dumps({"traces": traces}))
    assert main([str(dump)]) == 0
    out = capsys.readouterr().out
    assert "engine.queue" in out and "p99(ms)" in out
    assert "3 trace(s)" in out


def test_trace_summary_overlap_view(tmp_path, capsys):
    """The overlap view pairs gather N with dispatch N+1 per host and
    counts positive gaps as stall windows."""
    from tools.trace_summary import main, overlap_summary

    def span(name, step_id, start, duration, host="host0"):
        return {
            "name": name,
            "start": start,
            "duration": duration,
            "attributes": {"step_id": step_id, "target_host": host},
        }

    # Step 0: gather ends at t=1.0.  Step 1 dispatched at t=0.9 →
    # overlapped (negative gap).  Step 1's gather ends at 2.0; step 2
    # dispatched at 2.25 → one 250ms stall window.
    spans = [
        span("executor.dispatch", 0, 0.0, 0.01),
        span("executor.gather", 0, 0.5, 0.5),
        span("executor.dispatch", 1, 0.9, 0.01),
        span("executor.gather", 1, 1.5, 0.5),
        span("executor.dispatch", 2, 2.25, 0.01),
        span("executor.gather", 2, 2.5, 0.5),
    ]
    traces = [{"trace_id": "t0", "spans": spans}]
    overlap = overlap_summary(traces)
    assert overlap is not None
    assert overlap["steps"] == 2
    assert overlap["stall_windows"] == 1
    assert abs(overlap["gap_max"] - 0.25) < 1e-9
    assert overlap["gap_p50"] < 0.25  # the overlapped pair is negative
    # Spans without step ids (pre-overlap dumps) → no overlap section.
    legacy = [{"trace_id": "t1", "spans": [
        {"name": "executor.dispatch", "start": 0.0, "duration": 0.01,
         "attributes": {"target_host": "host0"}},
    ]}]
    assert overlap_summary(legacy) is None
    dump = tmp_path / "traces.json"
    dump.write_text(json.dumps({"traces": traces}))
    assert main([str(dump)]) == 0
    out = capsys.readouterr().out
    assert "stall_windows  : 1" in out
    assert "dispatch overlap" in out


def test_stage_order_covers_every_pipeline_span():
    """Regression (ISSUE 20 satellite): every pipeline-stage span the
    code records must appear in trace_summary's ``_STAGE_ORDER``, so a
    new span kind cannot silently fall off (or to the bottom of) the
    latency table.  ``engine.kv_handoff`` and ``router.handoff`` did
    exactly that.  Scans the package by AST for literal names passed
    to ``span()`` / ``record_span()`` / ``_record_stage()``."""
    import ast
    import pathlib

    from tools.trace_summary import _STAGE_ORDER

    # Control-plane spans that are deliberately not in the
    # request-pipeline table (they still print, alphabetically).
    non_pipeline = {"router.reconnect"}

    pkg = pathlib.Path(__file__).resolve().parents[1] / "vllm_distributed_tpu"
    recorded: set[str] = set()
    for path in pkg.rglob("*.py"):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            attr = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None
            )
            if attr in ("span", "record_span"):
                arg_idx = 0
            elif attr == "_record_stage":
                arg_idx = 1  # (req, name, ...)
            else:
                continue
            if len(node.args) <= arg_idx:
                continue
            arg = node.args[arg_idx]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                if "." in arg.value:
                    recorded.add(arg.value)
    # Marker stages recorded as instant events on a parent span.
    recorded.add("router.handoff")
    assert recorded, "AST scan found no recorded spans — scanner broken?"
    missing = recorded - set(_STAGE_ORDER) - non_pipeline
    assert not missing, (
        f"span kinds missing from trace_summary._STAGE_ORDER: "
        f"{sorted(missing)} — add them (or to the non_pipeline "
        "allowlist if they are not request-pipeline stages)"
    )
    assert "engine.kv_handoff" in _STAGE_ORDER
    assert "router.handoff" in _STAGE_ORDER


def test_trace_summary_marker_stage_rows():
    """Stages recorded as instant events (router.handoff) get a
    count-only row instead of vanishing."""
    from tools.trace_summary import format_table, summarize

    traces = [{
        "trace_id": "t0",
        "spans": [
            {"name": "router.request", "start": 0.0, "duration": 0.2},
            {"name": "router.handoff", "start": 0.1, "duration": None},
            {"name": "engine.preempted", "start": 0.1, "duration": None},
        ],
    }]
    stats = summarize(traces)
    assert stats["router.handoff"]["count"] == 1
    assert stats["router.handoff"]["p50"] is None
    # Non-stage markers stay excluded, as before.
    assert "engine.preempted" not in stats
    table = format_table(stats)
    assert "router.handoff" in table
    # The marker row renders dashes, ordered right after router.request.
    lines = table.splitlines()
    assert lines.index(
        next(ln for ln in lines if ln.startswith("router.handoff"))
    ) == lines.index(
        next(ln for ln in lines if ln.startswith("router.request"))
    ) + 1


# ---------------------------------------------------------------------
# engine no-op path + /debug/traces while disabled
# ---------------------------------------------------------------------
def test_engine_step_loop_runs_noop_tracer_when_unset(tmp_path, monkeypatch):
    monkeypatch.delenv("VDT_TRACING", raising=False)
    engine = LLMEngine.from_engine_args(
        EngineArgs(
            model=make_tiny_llama(str(tmp_path / "m")),
            skip_tokenizer_init=True,
            num_kv_pages=64,
            max_model_len=128,
        )
    )
    tracer = get_tracer()
    assert engine.tracer is tracer and not tracer.enabled
    tracer.reset()
    engine.add_request(
        "r0",
        prompt_token_ids=[1, 5, 9],
        sampling_params=SamplingParams(
            temperature=0.0, max_tokens=4, ignore_eos=True
        ),
    )
    while engine.has_unfinished_requests():
        engine.step()
    engine.shutdown()
    # The whole run went through the no-op path: same singleton span,
    # nothing recorded, nothing open.
    assert tracer.span("x") is NOOP_SPAN
    assert tracer.snapshot() == []
    assert tracer.num_open_spans == 0


def test_debug_traces_404_when_disabled():
    get_tracer().configure(False)
    state = ServerState(engine=None, model_name="x", max_model_len=1)

    async def run():
        server = TestServer(build_app(state))
        client = TestClient(server)
        await client.start_server()
        try:
            r = await client.get("/debug/traces")
            assert r.status == 404
            body = await r.json()
            assert "VDT_TRACING" in body["message"]
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(run())


def test_debug_traces_rejects_negative_limit():
    tracer = get_tracer().configure(True)
    tracer.reset()
    state = ServerState(engine=None, model_name="x", max_model_len=1)

    async def run():
        server = TestServer(build_app(state))
        client = TestClient(server)
        await client.start_server()
        try:
            r = await client.get("/debug/traces?limit=-1")
            assert r.status == 400
            body = await r.json()
            assert "non-negative" in body["message"]
        finally:
            await client.close()

    try:
        asyncio.new_event_loop().run_until_complete(run())
    finally:
        tracer.configure(False)


# ---------------------------------------------------------------------
# E2E acceptance: api → queue → prefill → decode → dispatch → worker
# ---------------------------------------------------------------------
class TracedMultiHostExecutor(MultiHostExecutor):
    worker_cls = "tests.mock_worker.MockWorker"


def _agent_with_env(port, env):
    for k, v in (env or {}).items():
        os.environ[k] = v
    remote_main("127.0.0.1", port)


@pytest.fixture
def traced_app(tmp_path, monkeypatch):
    """OpenAI app over AsyncLLM over the mocked 2-host executor with
    tracing on; VDT_TRACING reaches the agent via env replication."""
    port = get_open_port()
    monkeypatch.setenv("VDT_SERVER_PORT", str(port))
    monkeypatch.setenv("VDT_TRACING", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    get_tracer().reset()
    agent = multiprocessing.Process(
        target=_agent_with_env,
        args=(
            port,
            {"VDT_ADVERTISE_NUM_CHIPS": "4", "VDT_ADVERTISE_PLATFORM": "cpu"},
        ),
        daemon=True,
    )
    agent.start()
    engine = AsyncLLM.from_engine_args(
        EngineArgs(
            model=write_llama_config(str(tmp_path / "m")),
            skip_tokenizer_init=True,
            load_format="dummy",
            num_hosts=2,
            num_decode_steps=1,
            max_model_len=512,
            distributed_executor_backend=TracedMultiHostExecutor,
        )
    )
    state = init_app_state(engine, served_model_name="tiny")
    yield lambda: build_app(state)
    engine.shutdown()
    if agent.is_alive():
        agent.terminate()
    agent.join(timeout=5)
    # Don't leak an enabled global tracer into later test files.
    get_tracer().configure(False)
    get_tracer().set_metrics_sink(None)
    get_tracer().reset()


def _span_index(trace):
    by_name = {}
    for span in trace["spans"]:
        by_name.setdefault(span["name"], []).append(span)
    return by_name


def test_request_produces_one_linked_trace(traced_app):
    async def go(client):
        r = await client.post(
            "/v1/completions",
            json={"model": "tiny", "prompt": [1, 5, 9], "max_tokens": 4},
        )
        assert r.status == 200
        trace_id = r.headers.get(TRACE_HEADER)
        assert trace_id and len(trace_id) == 32

        r = await client.get("/debug/traces")
        assert r.status == 200
        traces = (await r.json())["traces"]
        trace = next(
            t for t in traces if t["trace_id"] == trace_id
        )
        assert trace["complete"]
        by_name = _span_index(trace)

        # The acceptance chain, all in ONE trace.
        for name in (
            "api.request",
            "engine.queue",
            "engine.prefill",
            "engine.decode",
            "scheduler.schedule",
            "executor.dispatch",
            "executor.gather",
            "worker.execute",
        ):
            assert name in by_name, (name, sorted(by_name))

        root = by_name["api.request"][0]
        assert root["parent_id"] is None
        assert root["span_id"] == trace["root_span_id"]
        span_ids = {s["span_id"] for s in trace["spans"]}

        # queue/prefill/decode parent to the root; stages tile the
        # request: queue ends where prefill starts, prefill where
        # decode starts, all inside the root interval.
        stages = {}
        for name in ("engine.queue", "engine.prefill", "engine.decode"):
            (span,) = by_name[name]
            assert span["parent_id"] == root["span_id"]
            stages[name] = span
        q, p, d = (
            stages["engine.queue"],
            stages["engine.prefill"],
            stages["engine.decode"],
        )
        assert abs(q["start"] + q["duration"] - p["start"]) < EPS
        assert abs(p["start"] + p["duration"] - d["start"]) < EPS
        root_end = root["start"] + root["duration"]
        for s in (q, p, d):
            assert s["start"] >= root["start"] - EPS
            assert s["start"] + s["duration"] <= root_end + EPS

        # Cross-RPC links: every worker-side span's parent is a
        # driver-side dispatch span in this same trace.
        dispatch_ids = {
            s["span_id"] for s in by_name["executor.dispatch"]
        }
        workers = by_name["worker.execute"]
        assert all(w["host"] == "host1" for w in workers)
        assert all(w["parent_id"] in dispatch_ids for w in workers)
        # Worker replies also landed (serialize + reply marker).
        assert "worker.serialize" in by_name
        assert "worker.reply" in by_name

        # Step spans parent to the root (and dispatch carries the
        # control-message payload size).
        for s in by_name["scheduler.schedule"]:
            assert s["parent_id"] == root["span_id"]
            assert s["trace_id"] == trace_id
        assert any(
            s["attributes"].get("payload_bytes", 0) > 0
            for s in by_name["executor.dispatch"]
        )

        # Timestamps are monotonic per host: sorting any host's spans
        # by start gives non-negative durations and ordered starts.
        for host in {s["host"] for s in trace["spans"]}:
            spans = sorted(
                (s for s in trace["spans"] if s["host"] == host),
                key=lambda s: s["start"],
            )
            assert all((s["duration"] or 0.0) >= 0.0 for s in spans)

        # Every span id referenced as a parent exists in the trace
        # (except the root's None) — no dangling links across the
        # RPC boundary.
        for s in trace["spans"]:
            if s["parent_id"] is not None and s["name"] not in (
                "api.request",
            ):
                assert s["parent_id"] in span_ids, s

        # Chrome export: valid trace-event JSON with both hosts.
        r = await client.get("/debug/traces?format=chrome")
        assert r.status == 200
        chrome = json.loads(await r.text())
        assert chrome["traceEvents"]
        process_names = {
            e["args"]["name"]
            for e in chrome["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert {"driver", "host1"} <= process_names

        # Per-stage Prometheus histograms fed from the same spans.
        r = await client.get("/metrics")
        text = await r.text()
        for family in (
            "vllm:request_queue_time_seconds_count",
            "vllm:request_prefill_time_seconds_count",
            "vllm:request_decode_time_seconds_count",
            "vllm:step_schedule_time_seconds_count",
            "vllm:step_dispatch_time_seconds_count",
            "vllm:step_gather_time_seconds_count",
        ):
            line = next(
                ln for ln in text.splitlines() if ln.startswith(family)
            )
            assert float(line.split()[-1]) > 0, line

        # No span leaked open once the request finished.
        assert get_tracer().num_open_spans == 0

    async def run():
        server = TestServer(traced_app())
        client = TestClient(server)
        await client.start_server()
        try:
            await go(client)
        finally:
            await client.close()

    asyncio.new_event_loop().run_until_complete(run())


# ---------------------------------------------------------------------
# PR 40: the host's time line closed at both ends
# ---------------------------------------------------------------------
def _with_client(app_factory, go):
    async def run():
        client = TestClient(TestServer(app_factory()))
        await client.start_server()
        try:
            return await go(client)
        finally:
            await client.close()

    return asyncio.new_event_loop().run_until_complete(run())


async def _stream(client, **body):
    r = await client.post(
        "/v1/completions",
        json=dict(model="tiny", prompt=[1, 5, 9], stream=True, **body),
    )
    assert r.status == 200
    chunks = [ln async for ln in r.content if ln.startswith(b"data: ")]
    assert chunks[-1].strip() == b"data: [DONE]"
    return r.headers.get(TRACE_HEADER), chunks


def test_first_chunk_time_is_the_sum_of_the_spans_on_its_way(traced_app):
    """Closure: between the socket and the first byte back no host time
    is outside a span — parse + intake + queue + prefill + emit is the
    root's ``first_chunk_ms`` — and between two steps none is outside
    the step root's ``since_prev_ms`` account."""

    async def go(client):
        ids = [(await _stream(client, max_tokens=6))[0] for _ in range(3)]
        # A step's trace closes on the engine thread, which a busy
        # machine may run after the stream's last byte: wait for the
        # steps read below, ten seconds at most.
        deadline = time.monotonic() + 10.0
        while True:
            payload = await (await client.get("/debug/traces")).json()
            closed = sum(
                s["name"] == "engine.step"
                for t in payload["traces"] for s in t["spans"])
            have = {t["trace_id"] for t in payload["traces"]}
            if (closed >= 6 and have.issuperset(ids)) or (
                    time.monotonic() > deadline):
                return ids, payload
            await asyncio.sleep(0.05)

    ids, payload = _with_client(traced_app, go)
    traces = {t["trace_id"]: t for t in payload["traces"]}
    for trace_id in ids:
        by_name = _span_index(traces[trace_id])
        (root,) = by_name["api.request"]
        way = [by_name[n][0] for n in (
            "api.parse", "engine.intake", "engine.queue", "engine.prefill",
            "api.emit")]
        assert all(s["parent_id"] == root["span_id"] for s in way)
        total_ms = sum(s["duration"] for s in way) * 1e3
        assert abs(total_ms - root["attributes"]["first_chunk_ms"]) < 0.01
        # each stage begins where the one before it ended
        for a, b in zip(way, way[1:]):
            # (span starts are wall-clock anchors taken beside the
            # monotonic stamps, not the stamps: loosely)
            assert abs(a["start"] + a["duration"] - b["start"]) < 0.02
        parse, intake, _, prefill, emit = (s["attributes"] for s in way)
        assert parse == {"prompt_tokens": 3, "stream": True}
        assert intake["phase"] in ("idle", "slack", "step", "intake")
        assert prefill["steps"] == 1 and prefill["behind_ms"] == 0.0
        assert isinstance(prefill["first_step_id"], int)
        parts = emit["held_ms"] + emit["hop_ms"] + emit["write_ms"]
        assert abs(parts - way[-1]["duration"] * 1e3) < 1e-6
        assert len(by_name["api.emit"]) == 1  # the first chunk only
        # one stream at a time: its first token is its step's one output
        assert emit["position"] == 0
    steps = sorted(
        (s for t in payload["traces"] for s in t["spans"]
         if s["name"] == "engine.step"), key=lambda s: s["start"])
    assert len(steps) >= 6
    for prev, step in zip(steps, steps[1:]):
        a = step["attributes"]
        assert a["since_prev_ms"] + 1e-6 >= (
            a["slack_ms"] + a["idle_ms"] + a["intake_ms"])
        between = step["start"] - (prev["start"] + prev["duration"])
        assert abs(between * 1e3 - a["since_prev_ms"]) < 20.0  # wall
    # an idle engine waited for the second and third request
    assert max(s["attributes"]["idle_ms"] for s in steps) > 0
    # every step that gave outputs has its flush, a child of its trace
    flushes = [s for t in payload["traces"] for s in t["spans"]
               if s["name"] == "frontend.flush"]
    assert flushes
    for f in flushes:
        (root,) = [s for s in traces[f["trace_id"]]["spans"]
                   if s["parent_id"] is None]
        assert root["name"] == "engine.step"
        assert f["parent_id"] == root["span_id"]
        assert f["attributes"]["step_id"] == root["attributes"]["step_id"]
        assert f["attributes"]["outputs"] >= 1
        assert 0 <= f["attributes"]["first_outputs"] <= 1
    # each stream's first token went ahead in the flush of its step
    assert sum(f["attributes"]["first_outputs"] for f in flushes) == 3
    # the ring says what it lost
    assert payload["evicted"] == 0
    # (the ring's oldest trace: where a collection or a compile left a
    # span, the process trace stands before it and is no part of the ring)
    ring = [t for t in payload["traces"] if t["root_span_id"] is not None]
    assert payload["oldest_start"] == min(
        s["start"] for s in ring[0]["spans"] if s["name"] != "gc.collect")


def test_a_collection_is_a_span_of_the_process_trace(traced_app):
    import gc

    async def go(client):
        await _stream(client, max_tokens=2)
        gc.collect(2)
        payload = await (await client.get("/debug/traces")).json()
        return payload, await (await client.get("/metrics")).text()

    assert get_tracer()._on_gc in gc.callbacks
    payload, text = _with_client(traced_app, go)
    process = payload["traces"][0]
    assert process["root_span_id"] is None
    found = [s for s in process["spans"] if s["name"] == "gc.collect"
             and s["attributes"]["generation"] == 2]
    assert found and all(s["duration"] > 0 for s in found)
    assert all("collected" in s["attributes"] for s in found)
    assert found[-1]["attributes"]["thread"] == "MainThread"
    # on the tracer's clock: wall seconds, like every other span's start
    newest = max(s["start"] for t in payload["traces"][1:]
                 for s in t["spans"])
    assert abs(found[-1]["start"] - newest) < 60
    line = next(ln for ln in text.splitlines() if ln.startswith(
        "vllm:gc_collections_total") and 'generation="2"' in ln)
    assert float(line.split()[-1]) >= 1
    assert any(ln.startswith("vllm:gc_pause_seconds_total")
               and 'generation="2"' in ln and float(ln.split()[-1]) > 0
               for ln in text.splitlines())


def test_tracing_off_stamps_counts_and_hooks_nothing(tmp_path, monkeypatch):
    """Off is off: no stamp in the intake's payload, no countdown object,
    no collection hook, the no-op span."""
    import gc

    from vllm_distributed_tpu.engine import async_llm

    tracer = get_tracer().configure(False)
    made = []
    monkeypatch.setattr(
        async_llm, "_Flush", lambda *a: made.append(a) or None)
    engine = AsyncLLM.from_engine_args(EngineArgs(
        model=make_tiny_llama(str(tmp_path / "m")),
        skip_tokenizer_init=True, num_kv_pages=64, max_model_len=128,
        max_num_seqs=8, num_decode_steps=4))
    puts = []

    class Spy(type(engine._intake)):
        def put(self, item):
            puts.append(item)
            super().put(item)

    engine._intake = Spy()
    state = init_app_state(engine, served_model_name="tiny")
    try:
        _, chunks = _with_client(
            lambda: build_app(state),
            lambda client: _stream(client, max_tokens=6, ignore_eos=True))
    finally:
        engine.shutdown()
    assert len(chunks) >= 2
    adds = [payload for op, payload in puts if op == "add"]
    assert adds and all("_put" not in payload for payload in adds)
    assert made == []
    assert engine.engine.last_step is None
    assert engine.engine.between_steps == {
        "slack": 0.0, "idle": 0.0, "intake": 0.0}
    assert engine.engine._prefill_trace == {} and engine.engine._done_at == {}
    assert tracer._on_gc not in gc.callbacks
    assert tracer.span("api.parse", trace_root=True) is NOOP_SPAN
    assert tracer.annotation("api.write") is NOOP_SPAN
    assert tracer.snapshot(process=True) == []


def test_closure_holds_behind_a_dispatch_in_flight(tmp_path):
    """The same sum with the dispatch pipeline on and streams arriving
    beside one another: whatever a first dispatch queued behind is
    inside ``engine.prefill`` (its ``behind_ms``), not outside a span."""
    tracer = get_tracer()
    tracer.reset()
    engine = AsyncLLM.from_engine_args(EngineArgs(
        model=make_tiny_llama(str(tmp_path / "m")),
        skip_tokenizer_init=True, num_kv_pages=64, max_model_len=128,
        max_num_seqs=8, num_decode_steps=4, enable_tracing=True))
    state = init_app_state(engine, served_model_name="tiny")

    async def go(client):
        await _stream(client, max_tokens=4, ignore_eos=True)  # first calls
        got = await asyncio.gather(*(
            _stream(client, max_tokens=16, ignore_eos=True)
            for _ in range(6)))
        traces = (await (await client.get("/debug/traces")).json())["traces"]
        return [trace_id for trace_id, _ in got], traces

    try:
        ids, traces = _with_client(lambda: build_app(state), go)
    finally:
        engine.shutdown()
        tracer.configure(False)
        tracer.reset()
    traces = {t["trace_id"]: t for t in traces}
    for trace_id in ids:
        by_name = _span_index(traces[trace_id])
        (root,) = by_name["api.request"]
        total_ms = 1e3 * sum(by_name[n][0]["duration"] for n in (
            "api.parse", "engine.intake", "engine.queue", "engine.prefill",
            "api.emit"))
        assert abs(total_ms - root["attributes"]["first_chunk_ms"]) < 0.01
        prefill = by_name["engine.prefill"][0]
        a = prefill["attributes"]
        assert a["steps"] >= 1 and a["behind_ms"] >= 0.0
        assert a["behind_ms"] <= prefill["duration"] * 1e3 + 1e-6
        # six first tokens at most in a step, and they go first
        assert 0 <= by_name["api.emit"][0]["attributes"]["position"] <= 5
    assert engine.engine._prefill_trace == {}


def test_behind_ms_is_first_schedule_to_the_front_dispatch_resolving(
        tmp_path):
    engine = LLMEngine.from_engine_args(EngineArgs(
        model=make_tiny_llama(str(tmp_path / "m")),
        skip_tokenizer_init=True, num_kv_pages=64, max_model_len=128))
    try:
        req = type("R", (), {"request_id": "r"})()
        engine._done_at.update({4: 100.030, 6: 99.0})
        for track, want in (
            ([5, 2, 4], dict(steps=2, first_step_id=5, behind_ms=30.0)),
            # the chip was free, or the one in front had resolved already
            ([5, 1, None], dict(steps=1, first_step_id=5, behind_ms=0.0)),
            ([7, 3, 6], dict(steps=3, first_step_id=7, behind_ms=0.0)),
        ):
            engine._prefill_trace["r"] = track
            got = engine._prefill_attrs(req, 100.0)
            assert got["behind_ms"] == pytest.approx(want.pop("behind_ms"))
            assert {k: got[k] for k in want} == want
        assert engine._prefill_attrs(req, 100.0) == {}  # kept once
    finally:
        engine.shutdown()


def test_compile_listener_counts_a_jit_that_respecialises_under_one_key():
    """``vllm:xla_compiles_total`` counts the first execution of a runner
    key; the same key met with a new shape compiles again and only JAX's
    own backend-compile event sees it."""
    import jax
    import jax.numpy as jnp

    from vllm_distributed_tpu.worker import telemetry

    ledger = telemetry.DeviceTelemetry()  # installs the listeners
    tracer = get_tracer().configure(True)
    tracer.reset()
    try:
        fn = jax.jit(lambda x: (x * 3 + 1).sum())
        before = sum(telemetry.backend_compile_totals()[0].values())
        with tracer.span("engine.step", trace_root=True) as step:
            with tracer.span("runner.dispatch") as dispatch:
                fn(jnp.ones((3, 5))).block_until_ready()
                ledger.record_compile("step", 0.1, "step:fn:[]")
                fn(jnp.ones((7, 5))).block_until_ready()  # same key
        counts, seconds = telemetry.backend_compile_totals()
        assert sum(counts.values()) - before >= 2
        assert sum(ledger.snapshot(probe_memory=False)[
            "compiles"].values()) == 1
        snap = ledger.snapshot(probe_memory=False)
        assert snap["backend_compiles"] == counts
        assert snap["backend_compile_seconds"] == seconds
        assert all(v >= 0 for v in seconds.values())
        (trace,) = [t for t in tracer.snapshot()
                    if t["trace_id"] == step.trace_id]
        found = [s for s in trace["spans"] if s["name"] == "xla.compile"]
        assert len(found) >= 2
        for s in found:  # under whatever span was current
            assert s["parent_id"] == dispatch.span_id
            assert set(s["attributes"]) == {"seconds", "cache_hit", "module"}
            assert s["attributes"]["seconds"] == s["duration"]
        # outside any span it is a span of the process
        fn(jnp.ones((2, 2))).block_until_ready()
        (process,) = [t for t in tracer.snapshot(process=True)
                      if t["root_span_id"] is None]
        assert any(s["name"] == "xla.compile" for s in process["spans"])
    finally:
        tracer.configure(False)
        tracer.reset()


def test_engine_folds_backend_compiles_into_the_new_families(tmp_path):
    engine = LLMEngine.from_engine_args(EngineArgs(
        model=make_tiny_llama(str(tmp_path / "m")),
        skip_tokenizer_init=True, num_kv_pages=64, max_model_len=128))
    try:
        snap = engine.refresh_device_telemetry()
        assert set(snap["backend_compiles"]) == {"compiled", "cache"}
        text = engine.metrics.render().decode()
        total = sum(snap["backend_compiles"].values())
        assert total >= 1  # loading the model compiled something
        counted = sum(
            float(ln.split()[-1]) for ln in text.splitlines()
            if ln.startswith("vllm:xla_backend_compiles_total{"))
        assert counted == total
        engine.refresh_device_telemetry()  # each counted once
        text = engine.metrics.render().decode()
        assert counted <= sum(
            float(ln.split()[-1]) for ln in text.splitlines()
            if ln.startswith("vllm:xla_backend_compiles_total{")
        ) <= counted + 2
        assert "vllm:xla_backend_compile_seconds_total{" in text
    finally:
        engine.shutdown()


def test_ring_counts_what_it_drops():
    t = Tracer().configure(True, ring_size=2)
    assert t.ring_state() == {"evicted": 0, "oldest_start": None}
    starts = []
    for _ in range(5):
        with t.span("root", trace_root=True) as s:
            starts.append(s.start)
    assert t.ring_state() == {"evicted": 3, "oldest_start": starts[3]}
    t.configure(True, ring_size=1)
    assert t.ring_state() == {"evicted": 4, "oldest_start": starts[4]}
    t.record_loose("gc.collect", 1.0, 0.5, generation=2, collected=0)
    assert t.snapshot() == t.snapshot(process=True)[1:]
    (loose,) = t.snapshot(process=True)[0]["spans"]
    assert loose["name"] == "gc.collect" and loose["parent_id"] is None
    t.reset()
    assert t.ring_state()["evicted"] == 0
    assert t.snapshot(process=True) == []
