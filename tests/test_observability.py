"""Flight recorder + XLA/device telemetry + profiling surface
(ISSUE 12): the always-on per-step ring and its dumps, the
/debug/flightrecorder and gated /debug/profile endpoints, and the
induced shape-bucket recompile observed through
vllm:xla_compiles_total on the mock runner."""

from __future__ import annotations

import asyncio
import json
import os

import pytest
from aiohttp.test_utils import TestClient, TestServer

from tests.mock_worker import MockUniProcExecutor
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.async_llm import AsyncLLM
from vllm_distributed_tpu.engine.flight_recorder import (
    FIELDS,
    FlightRecorder,
)
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.entrypoints.openai.api_server import (
    build_app,
    init_app_state,
)
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.testing import write_llama_config


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _engine_args(model_dir: str, **kw) -> EngineArgs:
    args = dict(
        model=model_dir,
        skip_tokenizer_init=True,
        load_format="dummy",
        num_kv_pages=64,
        max_model_len=128,
        num_decode_steps=1,
        distributed_executor_backend=MockUniProcExecutor,
    )
    args.update(kw)
    return EngineArgs(**args)


@pytest.fixture
def model_dir(tmp_path):
    return write_llama_config(str(tmp_path / "m"))


# ---------------------------------------------------------------------
# flight recorder unit behavior
# ---------------------------------------------------------------------
def test_ring_is_bounded_and_dump_prunes(tmp_path):
    fr = FlightRecorder(size=8, dump_dir=str(tmp_path))
    for i in range(50):
        fr.record_step(*([i] * len(FIELDS)))
    snap = fr.snapshot()
    assert len(snap["steps"]) == 8
    assert snap["steps"][-1][0] == 49
    assert snap["fields"] == list(FIELDS)
    paths = [fr.dump(f"r{i}") for i in range(20)]
    assert all(p is not None for p in paths)
    dumps = [f for f in os.listdir(tmp_path) if f.startswith("flight")]
    assert len(dumps) <= 16  # pruned to the newest artifacts
    with open(paths[-1]) as f:
        payload = json.load(f)
    assert payload["reason"] == "r19"
    assert len(payload["steps"]) == 8


def test_disabled_recorder_is_noop(tmp_path):
    fr = FlightRecorder(size=0, dump_dir=str(tmp_path))
    fr.record_step(*([0] * len(FIELDS)))
    assert fr.dump("x") is None
    assert not os.listdir(tmp_path)


def test_engine_records_steps_and_dump_has_composition(
    model_dir, tmp_path, monkeypatch
):
    monkeypatch.setenv("VDT_FLIGHT_RECORDER_DIR", str(tmp_path / "fr"))
    engine = LLMEngine.from_engine_args(_engine_args(model_dir))
    try:
        engine.add_request(
            "r0",
            prompt_token_ids=[1, 2, 3],
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=6, ignore_eos=True
            ),
        )
        while engine.has_unfinished_requests():
            engine.step()
        snap = engine.flight_recorder.snapshot()
        assert snap["steps"], "no step records"
        by_field = [
            dict(zip(FIELDS, step)) for step in snap["steps"]
        ]
        assert any(s["num_new"] == 1 for s in by_field)  # admission
        assert any(s["scheduled_tokens"] > 0 for s in by_field)
        assert all(s["kv_free_pages"] > 0 for s in by_field)
        path = engine.flight_recorder.dump("test")
        assert path is not None and os.path.exists(path)
        # Bounded size: ring-limited records keep the artifact small.
        assert os.path.getsize(path) < 1 << 20
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------
# induced shape-bucket recompile observed via vllm:xla_compiles_total
# ---------------------------------------------------------------------
def test_mock_recompile_observed_in_metrics(model_dir):
    engine = LLMEngine.from_engine_args(_engine_args(model_dir))
    try:
        sp = SamplingParams(
            temperature=0.0, max_tokens=2, ignore_eos=True
        )
        engine.add_request(
            "small", prompt_token_ids=[1, 2, 3], sampling_params=sp
        )
        while engine.has_unfinished_requests():
            engine.step()
        engine.refresh_device_telemetry()
        text = engine.metrics.render().decode()
        assert 'vllm:xla_compiles_total{kind="prefill"' in text

        def compiles(t: str) -> float:
            for line in t.splitlines():
                if line.startswith('vllm:xla_compiles_total{kind="prefill"'):
                    return float(line.rsplit(" ", 1)[1])
            return 0.0

        before = compiles(text)
        assert before >= 1.0
        # A much longer prompt lands in a new power-of-2 token bucket:
        # the mock runner records a fresh compile, the pull observes it.
        engine.add_request(
            "big",
            prompt_token_ids=list(range(1, 50)),
            sampling_params=sp.clone(),
        )
        while engine.has_unfinished_requests():
            engine.step()
        snap = engine.refresh_device_telemetry()
        assert snap is not None and snap["compiles"]["prefill"] >= 2
        after = compiles(engine.metrics.render().decode())
        assert after >= before + 1.0, (before, after)
        # Re-running the SAME bucket must not count again.
        engine.add_request(
            "again",
            prompt_token_ids=list(range(1, 50)),
            sampling_params=sp.clone(),
        )
        while engine.has_unfinished_requests():
            engine.step()
        engine.refresh_device_telemetry()
        assert compiles(engine.metrics.render().decode()) == after
        # Gauges landed too.
        text = engine.metrics.render().decode()
        assert "vllm:hbm_live_bytes" in text
        assert "vllm:step_roofline_frac" in text
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------
# HTTP surfaces: /debug/flightrecorder, /metrics pull, /debug/profile
# ---------------------------------------------------------------------
def test_a_worker_counter_is_a_row_of_the_table_or_an_error():
    """One store behind one ``record``: a row of
    ``metrics.WORKER_COUNTERS`` adds up by label (a gauge row is set),
    every row is a key of the snapshot, and a name that is no row is
    refused where it is recorded, not served as a new series."""
    from vllm_distributed_tpu.metrics import WORKER_COUNTERS, EngineMetrics
    from vllm_distributed_tpu.worker.telemetry import DeviceTelemetry

    ledger = DeviceTelemetry()
    ledger.record("moe_picks", {"held": 3, "absent": 1})
    ledger.record("moe_picks", {"held": 2})
    ledger.record("seq_state_bytes", {"conv": 10})
    ledger.record("seq_state_bytes", {"conv": 7})
    ledger.record_fallback("aot_load")
    snap = ledger.snapshot(probe_memory=False)
    assert set(WORKER_COUNTERS) <= set(snap)
    assert snap["moe_picks"] == {"held": 5, "absent": 1}
    assert snap["seq_state_bytes"] == {"conv": 7}
    assert snap["fallbacks"] == {"aot_load": 1}
    with pytest.raises(KeyError):
        ledger.record("moe_pickz", {"held": 1})
    with pytest.raises(KeyError):
        EngineMetrics("m").record_worker_counter("moe_pickz", "held", 1)


def test_http_observability_surfaces(model_dir, tmp_path, monkeypatch):
    monkeypatch.setenv("VDT_FLIGHT_RECORDER_DIR", str(tmp_path / "fr"))
    engine = AsyncLLM.from_engine_args(_engine_args(model_dir))
    state = init_app_state(engine, served_model_name="obs")

    async def go():
        server = TestServer(build_app(state))
        client = TestClient(server)
        await client.start_server()
        try:
            r = await client.post(
                "/v1/completions",
                json={
                    "prompt": [1, 2, 3],
                    "max_tokens": 4,
                    "temperature": 0.0,
                    "ignore_eos": True,
                    "slo_class": "chat",
                },
            )
            assert r.status == 200
            # /metrics pulls device telemetry (compile counter present).
            text = await (await client.get("/metrics")).text()
            assert 'vllm:xla_compiles_total{kind="prefill"' in text
            # /slo serves the per-class view.
            slo = await (await client.get("/slo")).json()
            assert slo["classes"]["chat"]["requests"] == 1
            assert slo["timelines"]
            lean = await (
                await client.get("/slo?timelines=0")
            ).json()
            assert "timelines" not in lean
            # /debug/flightrecorder serves the ring; ?dump=1 writes.
            fr = await (await client.get("/debug/flightrecorder")).json()
            assert fr["steps"]
            fr = await (
                await client.get("/debug/flightrecorder?dump=1")
            ).json()
            assert fr["path"] and os.path.exists(fr["path"])
            # /debug/profile is gated: 404 while unconfigured.
            r = await client.post("/debug/profile?seconds=0.05")
            assert r.status == 404
        finally:
            await client.close()

    try:
        _run(go())
    finally:
        engine.shutdown()


def test_debug_profile_enabled_returns_artifact(
    model_dir, tmp_path, monkeypatch
):
    profile_dir = str(tmp_path / "prof")
    engine = AsyncLLM.from_engine_args(
        _engine_args(model_dir, profile_dir=profile_dir)
    )
    state = init_app_state(engine, served_model_name="prof")

    async def go():
        server = TestServer(build_app(state))
        client = TestClient(server)
        await client.start_server()
        try:
            r = await client.post("/debug/profile?seconds=not-a-number")
            assert r.status == 400
            r = await client.post("/debug/profile?seconds=0")
            assert r.status == 400
            r = await client.post("/debug/profile?seconds=0.05")
            assert r.status == 200, await r.text()
            body = await r.json()
            assert body["path"].startswith(profile_dir)
            assert os.path.isdir(body["path"])
        finally:
            await client.close()

    try:
        _run(go())
    finally:
        engine.shutdown()
