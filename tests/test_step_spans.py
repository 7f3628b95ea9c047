"""The step loop's spans (PR 26, part B): one ``engine.step`` trace per
engine step with a child for every phase the host goes through; the
attributes the benchmark's readers take from them; a
``jax.profiler.TraceAnnotation`` per span only while a capture runs;
nothing at all with tracing off."""

import pytest

from tests.utils import hold_results, make_tiny_llama
from vllm_distributed_tpu import tracing
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.tracing import NOOP_SPAN, Tracer, get_tracer

PHASES = ("scheduler.schedule", "runner.prepare", "runner.dispatch",
          "runner.fetch", "engine.process")
NEW_SPANS = ("engine.step", "runner.prepare", "runner.dispatch",
             "runner.fetch", "engine.process", "engine.wait",
             "profile.capture")


@pytest.fixture
def annotations(monkeypatch):
    """Stands in for jax.profiler.TraceAnnotation: what was entered,
    and what was left."""
    log = []

    class Fake:
        def __init__(self, name, step_id):
            self.entry = (name, step_id)
            log.append(("enter", *self.entry))

        def __exit__(self, *exc):
            log.append(("exit", *self.entry))

    monkeypatch.setattr(tracing, "_annotate", Fake)
    return log


def test_a_span_is_an_annotation_only_while_a_capture_runs(annotations):
    t = Tracer().configure(True)
    with t.span("engine.step", trace_root=True):
        pass
    assert annotations == []
    t.profiling = True
    with t.span("engine.step", trace_root=True):
        with t.span("runner.prepare", step_id=7):
            assert annotations[-1] == ("enter", "runner.prepare", 7)
        # left before its parent, on the thread that entered it
        assert annotations[-1] == ("exit", "runner.prepare", 7)
    t.profiling = False
    assert annotations == [
        ("enter", "engine.step", None), ("enter", "runner.prepare", 7),
        ("exit", "runner.prepare", 7), ("exit", "engine.step", None)]
    with t.span("engine.step", trace_root=True):
        pass
    assert len(annotations) == 4
    assert t.num_open_spans == 0


def test_a_span_opened_during_a_capture_leaves_its_annotation_after_it(
        annotations):
    t = Tracer().configure(True)
    t.profiling = True
    span = t.span("engine.step", trace_root=True)
    with span:
        t.profiling = False  # the capture ended inside the span
    assert annotations == [
        ("enter", "engine.step", None), ("exit", "engine.step", None)]


@pytest.mark.parametrize("name", NEW_SPANS)
def test_disabled_tracer_gives_the_noop_singleton_for_every_new_span(
        name, annotations):
    t = Tracer()
    t.profiling = True  # even a capture allocates nothing with tracing off
    assert t.span(name, trace_root=True, step_id=1) is NOOP_SPAN
    assert t.span(name, parent=("a" * 32, "b" * 16)) is NOOP_SPAN
    assert annotations == []


def test_real_annotation_enters_and_leaves_without_a_capture():
    """The real thing, outside any profile: entering and leaving is
    legal and leaves nothing behind."""
    t = Tracer().configure(True)
    t.profiling = True
    with t.span("runner.dispatch", trace_root=True, step_id=3) as span:
        assert span._annotation is not None
    assert span._annotation is None


def _engine(tmp_path, **kw):
    return LLMEngine.from_engine_args(EngineArgs(
        model=make_tiny_llama(str(tmp_path / "m")),
        skip_tokenizer_init=True, num_kv_pages=64, max_model_len=128,
        max_num_seqs=8, **kw))


def _serve(engine, n=2, max_tokens=6):
    for i in range(n):
        engine.add_request(
            f"r{i}", prompt_token_ids=[1, 5, 9, 23 + i],
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=max_tokens, ignore_eos=True))
    while engine.has_unfinished_requests():
        engine.step()


@pytest.fixture
def traced_engine(tmp_path):
    tracer = get_tracer()
    tracer.reset()
    engine = _engine(tmp_path, num_decode_steps=1, enable_tracing=True)
    try:
        yield engine
    finally:
        engine.shutdown()
        tracer.configure(False)
        tracer.reset()


def _step_traces(tracer):
    out = []
    for trace in tracer.snapshot():
        root = [s for s in trace["spans"] if s["parent_id"] is None]
        if root and root[0]["name"] == "engine.step":
            # the phases: a compile nests under whichever of them met it
            out.append((root[0], [s for s in trace["spans"]
                                  if s["parent_id"] is not None
                                  and s["name"] != "xla.compile"]))
    return out


def test_engine_step_has_every_phase_as_a_child_with_one_step_id(
        traced_engine):
    _serve(traced_engine)
    steps = _step_traces(get_tracer())
    assert len(steps) >= 6
    for root, children in steps:
        assert {"step_id", "decode_steps", "pending",
                "pipeline_break"} <= set(root["attributes"])
        names = [c["name"] for c in children]
        assert set(PHASES) <= set(names), names
        for c in children:
            assert c["parent_id"] == root["span_id"], c
            assert c["attributes"]["step_id"] == root["attributes"][
                "step_id"], (c["name"], root["attributes"])
            # children lie inside the step
            assert c["start"] >= root["start"] - 0.05
    # step ids rise by one a step: no step went untraced
    ids = [root["attributes"]["step_id"] for root, _ in steps]
    assert ids == list(range(ids[0], ids[0] + len(ids)))
    assert get_tracer().num_open_spans == 0


def test_schedule_span_keeps_the_attributes_the_readers_use(traced_engine):
    """``decode_batch_mean`` reads num_cached + num_new and decode_steps,
    ``_spans.step_shapes`` the batch's ``<request>:<tokens>`` list."""
    _serve(traced_engine)
    spans = [c for _, children in _step_traces(get_tracer())
             for c in children if c["name"] == "scheduler.schedule"]
    assert spans
    for s in spans:
        a = s["attributes"]
        assert {"num_cached", "num_new", "decode_steps", "batch",
                "step_id", "total_tokens", "num_preempted"} <= set(a)
        chunks = [int(x.rsplit(":", 1)[1]) for x in a["batch"].split(",")]
        assert len(chunks) == a["num_cached"] + a["num_new"]
        assert sum(chunks) == a["total_tokens"]
    first = spans[0]["attributes"]
    assert first["num_new"] == 2 and first["decode_steps"] == 1


def test_dispatch_span_names_the_program_and_counts_its_work(traced_engine):
    _serve(traced_engine)
    spans = [c for _, children in _step_traces(get_tracer())
             for c in children if c["name"] == "runner.dispatch"]
    prefill, decode = spans[0]["attributes"], spans[-1]["attributes"]
    assert prefill["program"].startswith("vdt_step_t16_q4_s8_p")
    assert prefill["first_call"] is True
    assert (prefill["seqs"], prefill["tokens"], prefill["kv_tokens"]) == (
        2, 8, 8)
    assert decode["first_call"] is False
    assert (decode["seqs"], decode["tokens"]) == (2, 2)
    assert decode["kv_tokens"] > prefill["kv_tokens"]


def test_a_pipelined_scan_is_fetched_under_the_step_that_dispatched_it(
        tmp_path):
    tracer = get_tracer()
    tracer.reset()
    engine = _engine(tmp_path, num_decode_steps=4, enable_tracing=True)
    try:
        _serve(engine, n=2, max_tokens=24)
        by_step: dict = {}
        for root, children in _step_traces(tracer):
            for c in children:
                by_step.setdefault(c["attributes"]["step_id"], {})[
                    c["name"]] = root["attributes"]["step_id"]
        fused = [sid for sid, names in by_step.items()
                 if names.get("runner.dispatch") is not None
                 and names.get("engine.process") is not None
                 and names["engine.process"] != names["runner.dispatch"]]
        # some scan was finalized by a later step than dispatched it …
        assert fused, by_step
        for sid in fused:
            names = by_step[sid]
            # … and its fetch stayed with the dispatching step
            assert names["runner.fetch"] == names["runner.dispatch"] == sid
    finally:
        engine.shutdown()
        tracer.configure(False)
        tracer.reset()


def test_a_mixed_step_issued_behind_a_scan_is_fetched_under_its_own_step(
        tmp_path):
    """A request arrives while a scan is in flight: the single step
    that admits it is issued without blocking (``overlapped``), its
    ``runner.fetch`` runs on the resolver thread under the step that
    dispatched it, and a later step processes it.  The attributes the
    benchmark's readers take from ``runner.dispatch`` are all there."""
    tracer = get_tracer()
    tracer.reset()
    engine = hold_results(
        _engine(tmp_path, num_decode_steps=4, enable_tracing=True))
    try:
        for i in range(2):
            engine.add_request(
                f"r{i}", prompt_token_ids=[1, 5, 9, 23 + i],
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=24, ignore_eos=True))
        while not engine._pending:
            engine.step()
        engine.add_request(
            "late", prompt_token_ids=[2, 4, 6, 8, 10],
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=6, ignore_eos=True))
        while engine.has_unfinished_requests():
            engine.step()
        assert engine.pipeline_breaks == 0
        by_step: dict = {}
        dispatches = {}
        for root, children in _step_traces(tracer):
            assert {"step_id", "decode_steps", "pending",
                    "pipeline_break"} <= set(root["attributes"])
            assert root["attributes"]["pipeline_break"] is False
            for c in children:
                sid = c["attributes"]["step_id"]
                by_step.setdefault(sid, {})[c["name"]] = root[
                    "attributes"]["step_id"]
                if c["name"] == "runner.dispatch":
                    dispatches[sid] = c["attributes"]
        for a in dispatches.values():
            assert {"step_id", "program", "first_call", "overlapped",
                    "seqs", "tokens", "kv_tokens"} <= set(a)
        mixed = [sid for sid, a in dispatches.items()
                 if a["program"].startswith("vdt_step_") and a["overlapped"]]
        assert mixed, dispatches
        for sid in mixed:
            names = by_step[sid]
            assert names["runner.prepare"] == names["runner.dispatch"] == sid
            assert names["runner.fetch"] == sid
            assert names["engine.process"] != sid
        # the first dispatch of all had nothing in flight before it
        assert dispatches[min(dispatches)]["overlapped"] is False
        assert any(a["overlapped"] for a in dispatches.values()
                   if a["program"].startswith("vdt_decode_"))
    finally:
        engine.shutdown()
        tracer.configure(False)
        tracer.reset()


def test_with_tracing_off_a_served_request_records_nothing(tmp_path):
    tracer = get_tracer()
    tracer.configure(False)
    tracer.reset()
    engine = _engine(tmp_path, num_decode_steps=4)
    try:
        assert not tracer.enabled
        _serve(engine)
        assert tracer.snapshot() == [] and tracer.num_open_spans == 0
    finally:
        engine.shutdown()


def test_debug_profile_puts_the_spans_on_the_profilers_clock(tmp_path):
    """POST /debug/profile sets the tracer's flag around the capture,
    returns both host clocks at its two ends and keeps them as a
    ``profile.capture`` trace in the ring; the steps served meanwhile
    are annotations in the profile's host plane."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from benchmark.layer_metrics import _xplane
    from vllm_distributed_tpu.engine.async_llm import AsyncLLM
    from vllm_distributed_tpu.entrypoints.openai.api_server import (
        build_app,
        init_app_state,
    )

    tracer = get_tracer()
    tracer.reset()
    engine = AsyncLLM.from_engine_args(EngineArgs(
        model=make_tiny_llama(str(tmp_path / "m")),
        skip_tokenizer_init=True, num_kv_pages=64, max_model_len=128,
        max_num_seqs=8, num_decode_steps=4, enable_tracing=True,
        profile_dir=str(tmp_path / "prof")))
    state = init_app_state(engine, served_model_name="tiny")

    async def go():
        client = TestClient(TestServer(build_app(state)))
        await client.start_server()
        try:
            async def complete():
                await asyncio.sleep(0.2)
                r = await client.post("/v1/completions", json={
                    "model": "tiny", "prompt": [1, 5, 9], "max_tokens": 24,
                    "ignore_eos": True})
                assert r.status == 200

            profile, _ = await asyncio.gather(
                client.post("/debug/profile?seconds=1.5"), complete())
            assert profile.status == 200, await profile.text()
            traces = (await (await client.get("/debug/traces")).json())[
                "traces"]
            return await profile.json(), traces
        finally:
            await client.close()

    loop = asyncio.new_event_loop()
    try:
        body, traces = loop.run_until_complete(go())
    finally:
        loop.close()
        engine.shutdown()
        tracer.configure(False)
        tracer.reset()
    assert tracer.profiling is False
    assert 1.4 < body["end_mono"] - body["begin_mono"] < 10
    assert abs((body["end_wall"] - body["begin_wall"])
               - (body["end_mono"] - body["begin_mono"])) < 0.5
    (capture,) = [s for t in traces for s in t["spans"]
                  if s["name"] == "profile.capture"]
    assert capture["attributes"]["begin_wall"] == body["begin_wall"]
    assert capture["attributes"]["end_mono"] == body["end_mono"]
    found = _xplane.host_annotations(_xplane.load_events(
        _xplane.find_xplane(body["path"])))
    assert set(PHASES) | {"engine.step"} <= set(found), found
    # an engine with nothing to do waits, and says so while profiled
    assert found.get("engine.wait", 0) >= 1, found
