"""Fault-injection suite for the control plane (ISSUE 2 tentpole).

Each test injects ONE deterministic fault — via the transport-level
``FaultInjector`` (frames dropped/corrupted/delayed), the mock-worker
hooks (hang/die mid-execute), raw process kills, or connect delays — and
asserts the three-part contract:

1. bounded detection time (never "wait for a request to time out",
   never a hang);
2. a ``HostFailure`` with the right lifecycle phase and the offending
   host named;
3. the degraded surface: ``/health`` → 503 with the structured cause and
   ``Retry-After``, new work rejected with a typed error, and no leaked
   vdt threads or pending RPC futures afterwards.

Tier-1 (not `slow`): everything here runs on loopback with mock workers
and sub-second heartbeat intervals.
"""

import asyncio
import multiprocessing
import os
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from tests.mock_worker import MockWorker  # noqa: F401 (import check)
from tools.chaos_soak import RespawningAgent, run_soak
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.distributed.agent import (
    reconnect_delay,
    remote_main,
    server_silence_watchdog,
)
from vllm_distributed_tpu.distributed.rpc_transport import FaultInjector
from vllm_distributed_tpu.engine.async_llm import AsyncLLM, EngineDeadError
from vllm_distributed_tpu.engine.scheduler import SchedulerOutput
from vllm_distributed_tpu.entrypoints.openai.api_server import (
    build_app,
    init_app_state,
)
from vllm_distributed_tpu.executor.multihost import MultiHostExecutor
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.testing import write_llama_config
from vllm_distributed_tpu.utils import get_open_port

pytestmark = pytest.mark.fault

# Fast liveness so detection bounds are test-sized: miss budget is
# HB_INTERVAL * HB_THRESHOLD = 1.5 s.
HB_INTERVAL = 0.5
HB_THRESHOLD = 3
EXECUTE_TIMEOUT = 3.0
# CI slack on top of the theoretical detection deadline.
SLACK = 3.0


class FaultMultiHostExecutor(MultiHostExecutor):
    worker_cls = "tests.mock_worker.MockWorker"


def _agent_with_env(port, env):
    for k, v in (env or {}).items():
        os.environ[k] = v
    remote_main("127.0.0.1", port)


def _spawn_agent(port, extra_env=None):
    env = {
        "VDT_ADVERTISE_NUM_CHIPS": "4",
        "VDT_ADVERTISE_PLATFORM": "cpu",
        "VDT_FAULT_INJECTION": "1",
        **(extra_env or {}),
    }
    proc = multiprocessing.Process(
        target=_agent_with_env, args=(port, env), daemon=True
    )
    proc.start()
    return proc


def _vdt_threads():
    return {t for t in threading.enumerate() if t.name.startswith("vdt-")}


def _assert_no_new_vdt_threads(baseline, deadline=8.0):
    """Every vdt-* thread created since `baseline` must exit: heartbeat
    tasks cancelled, executor loop stopped, pools drained."""
    end = time.monotonic() + deadline
    extra = []
    while time.monotonic() < end:
        extra = [t for t in _vdt_threads() if t not in baseline]
        if not extra:
            return
        time.sleep(0.1)
    raise AssertionError(f"leaked threads: {[t.name for t in extra]}")


def _wait_for(predicate, deadline, what):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline:
        if predicate():
            return time.monotonic() - t0
        time.sleep(0.05)
    raise AssertionError(f"{what} not observed within {deadline:.1f}s")


def _fault_env(monkeypatch, tmp_path, port):
    monkeypatch.setenv("VDT_SERVER_PORT", str(port))
    monkeypatch.setenv(
        "VDT_EXECUTE_MODEL_TIMEOUT_SECONDS", str(int(EXECUTE_TIMEOUT))
    )
    monkeypatch.setenv("VDT_HEARTBEAT_INTERVAL_SECONDS", str(HB_INTERVAL))
    monkeypatch.setenv("VDT_HEARTBEAT_MISS_THRESHOLD", str(HB_THRESHOLD))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    # These tests assert the TERMINAL death contract (drain + reject);
    # disable the in-process supervisor so a HostFailure stays fatal.
    # The recovery suite below re-enables it with its own knobs.
    monkeypatch.setenv("VDT_MAX_ENGINE_RESTARTS", "0")


def _recovery_env(monkeypatch, tmp_path, port):
    """Supervised-recovery flavor: fast restart policy, deterministic
    mock token sequences, and execute pacing slow enough to kill a
    stream mid-generation."""
    _fault_env(monkeypatch, tmp_path, port)
    monkeypatch.setenv("VDT_MAX_ENGINE_RESTARTS", "3")
    monkeypatch.setenv("VDT_ENGINE_RESTART_BACKOFF_SECONDS", "0.2")
    monkeypatch.setenv("VDT_ENGINE_RESTART_BACKOFF_CAP_SECONDS", "2")
    monkeypatch.setenv("VDT_CRASH_LOOP_WINDOW_SECONDS", "60")
    monkeypatch.setenv("VDT_CONNECT_TIMEOUT_SECONDS", "30")
    monkeypatch.setenv("VDT_MOCK_TOKEN_SEQ", "1")
    monkeypatch.setenv("VDT_MOCK_EXECUTE_SLEEP_SECONDS", "0.05")


RECOVERY_AGENT_ENV = {
    "VDT_MOCK_TOKEN_SEQ": "1",
    "VDT_MOCK_EXECUTE_SLEEP_SECONDS": "0.05",
}


def _engine_args(tmp_path, **kw):
    model_dir = write_llama_config(str(tmp_path / "m"))
    return EngineArgs(
        model=model_dir,
        skip_tokenizer_init=True,
        load_format="dummy",
        num_hosts=2,
        **kw,
    )


@pytest.fixture
def fault_deployment(tmp_path, monkeypatch):
    """Executor-level 2-host mocked deployment with injection armed."""
    port = get_open_port()
    _fault_env(monkeypatch, tmp_path, port)
    baseline = _vdt_threads()
    agent = _spawn_agent(port)
    executor = FaultMultiHostExecutor(
        _engine_args(tmp_path).create_engine_config()
    )
    yield executor, agent, baseline
    executor.shutdown()
    if agent.is_alive():
        agent.terminate()
    agent.join(timeout=5)


@pytest.fixture
def engine_deployment(tmp_path, monkeypatch):
    """Full AsyncLLM over the mocked multihost executor, for /health and
    drain/reject assertions."""
    port = get_open_port()
    _fault_env(monkeypatch, tmp_path, port)
    baseline = _vdt_threads()
    agent = _spawn_agent(port)
    engine = AsyncLLM.from_engine_args(
        _engine_args(
            tmp_path,
            num_decode_steps=1,  # blocking step path: no mock device sleep
            max_model_len=512,  # fits the mock worker's 100-page cache
            distributed_executor_backend=FaultMultiHostExecutor,
        )
    )
    yield engine, agent, baseline
    engine.shutdown()
    if agent.is_alive():
        agent.terminate()
    agent.join(timeout=5)


def _so(step=0, req="r1"):
    return SchedulerOutput(
        step_id=step,
        num_scheduled_tokens={req: 1},
        total_num_scheduled_tokens=1,
    )


def _arm(executor, name, value=1.0, after_writes=2):
    """Arm a fault on the remote worker.  after_writes=2 lets the arming
    RPC's own reply (plus at most one in-flight pong) escape before the
    fault engages."""
    replies = executor.collective_rpc(
        "inject_fault", (name, value, after_writes)
    )
    assert "armed" in replies, replies


# ---------------------------------------------------------------------
# fault 1: stalled heartbeat (wedged host, socket open, NO traffic)
# ---------------------------------------------------------------------
def test_heartbeat_detects_wedged_host_without_requests(fault_deployment):
    """A host that silently stops answering (one-way partition: our
    frames arrive, its frames vanish) is detected by heartbeats alone —
    this test never calls execute_model, the deployment is idle."""
    executor, agent, baseline = fault_deployment
    _arm(executor, "blackhole_writes")
    budget = HB_INTERVAL * (HB_THRESHOLD + 3) + SLACK
    detect = _wait_for(
        lambda: executor.is_failed, budget, "heartbeat failure"
    )
    assert detect < budget
    failure = executor.failure_info
    assert failure is not None
    assert failure.phase == "heartbeat"
    assert failure.host_rank == 1
    assert "heartbeats missed" in failure.message
    # The orphaned agent fail-fasts once the driver drops the peer,
    # releasing its (pretend) TPU devices.
    agent.join(timeout=10)
    assert agent.exitcode not in (None, 0)
    executor.shutdown()
    _assert_no_new_vdt_threads(baseline)


# ---------------------------------------------------------------------
# fault 2: a single dropped frame must NOT kill the deployment
# ---------------------------------------------------------------------
def test_single_dropped_frame_recovers(fault_deployment):
    """One lost pong = one missed heartbeat, then recovery; the pending
    RPC slot for the lost reply is reclaimed (no future leak) and the
    deployment keeps serving."""
    executor, agent, _ = fault_deployment
    _arm(executor, "drop_writes", value=1)
    time.sleep(HB_INTERVAL * (HB_THRESHOLD + 2))
    assert not executor.is_failed
    out = executor.execute_model(_so())
    assert out.sampled_token_ids == {"r1": [42]}
    peer = executor._remote_hosts[0].peer
    _wait_for(
        lambda: len(peer._pending) == 0,
        HB_INTERVAL * 4,
        "pending-map drain (lost-pong slot reclaimed)",
    )
    assert not executor.is_failed


# ---------------------------------------------------------------------
# fault 3: hung execute (device program wedged, control plane healthy)
# ---------------------------------------------------------------------
def test_hung_execute_attributes_offending_host(fault_deployment):
    """The remote worker hangs mid-execute while its agent keeps
    answering heartbeats: the execute deadline trips, and the timeout
    error names WHICH host missed it (satellite: no more bare
    TimeoutError from _gather)."""
    executor, agent, baseline = fault_deployment
    _arm(executor, "hang_execute")
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="Executor failed") as ei:
        executor.execute_model(_so())
    detect = time.monotonic() - t0
    assert detect < EXECUTE_TIMEOUT + SLACK
    assert "rank 1" in str(ei.value)  # offending host named in the error
    failure = executor.failure_info
    assert failure.phase == "execute"
    assert failure.host_rank == 1
    assert failure.address  # host address captured for the operator
    executor.shutdown()
    _assert_no_new_vdt_threads(baseline)


# ---------------------------------------------------------------------
# fault 4: agent killed mid-execute
# ---------------------------------------------------------------------
def test_agent_killed_mid_execute(fault_deployment):
    """The agent process dies inside execute_model: detection is
    EOF-fast (no waiting out the execute deadline), and the failure
    names host 1 in whichever phase won the race (the in-flight
    collective or the connection-loss path)."""
    executor, agent, baseline = fault_deployment
    _arm(executor, "die_in_execute")
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="Executor failed"):
        executor.execute_model(_so())
    detect = time.monotonic() - t0
    assert detect < EXECUTE_TIMEOUT  # faster than the timeout budget
    failure = executor.failure_info
    assert failure.phase in ("execute", "connect")
    assert failure.host_rank == 1
    agent.join(timeout=10)
    assert agent.exitcode == 17
    executor.shutdown()
    _assert_no_new_vdt_threads(baseline)


# ---------------------------------------------------------------------
# fault 5: agent killed between steps (idle connection loss)
# ---------------------------------------------------------------------
def test_agent_killed_between_steps(fault_deployment):
    executor, agent, baseline = fault_deployment
    out = executor.execute_model(_so())  # healthy step first
    assert out.sampled_token_ids == {"r1": [42]}
    agent.terminate()
    t0 = time.monotonic()
    detect = _wait_for(
        lambda: executor.is_failed, 10.0, "disconnect failure"
    )
    assert detect < 10.0
    failure = executor.failure_info
    assert failure.phase == "connect"
    assert failure.host_rank == 1
    assert "connection to agent lost" in failure.message
    with pytest.raises(RuntimeError, match="Executor failed"):
        executor.collective_rpc("check_health")
    executor.shutdown()
    _assert_no_new_vdt_threads(baseline)


# ---------------------------------------------------------------------
# fault 6: corrupted frame
# ---------------------------------------------------------------------
def test_corrupted_frame_kills_connection(fault_deployment):
    """A corrupted pong fails the driver's unpickle, which tears the
    connection down — attribution is connection-phase with host 1."""
    executor, agent, baseline = fault_deployment
    _arm(executor, "corrupt_writes", value=1)
    budget = HB_INTERVAL * 4 + SLACK
    detect = _wait_for(
        lambda: executor.is_failed, budget, "corrupt-frame failure"
    )
    assert detect < budget
    failure = executor.failure_info
    assert failure.phase == "connect"
    assert failure.host_rank == 1
    executor.shutdown()
    _assert_no_new_vdt_threads(baseline)


# ---------------------------------------------------------------------
# fault 7: delayed connect
# ---------------------------------------------------------------------
def test_delayed_connect_within_budget_boots(tmp_path, monkeypatch):
    """An agent that dials in late (but inside VDT_CONNECT_TIMEOUT) costs
    boot latency, nothing else."""
    port = get_open_port()
    _fault_env(monkeypatch, tmp_path, port)
    monkeypatch.setenv("VDT_CONNECT_TIMEOUT_SECONDS", "30")
    agent = _spawn_agent(
        port, {"VDT_FAULT_CONNECT_DELAY_SECONDS": "1.5"}
    )
    t0 = time.monotonic()
    executor = FaultMultiHostExecutor(
        _engine_args(tmp_path).create_engine_config()
    )
    try:
        assert time.monotonic() - t0 >= 1.0  # the delay actually applied
        assert not executor.is_failed
        out = executor.execute_model(_so())
        assert out.sampled_token_ids == {"r1": [42]}
    finally:
        executor.shutdown()
        if agent.is_alive():
            agent.terminate()
        agent.join(timeout=5)


def test_delayed_connect_beyond_budget_fails_boot(tmp_path, monkeypatch):
    """An agent delayed past the connect deadline fails boot in bounded
    time with a connect-phase attribution — and the half-booted executor
    leaks nothing."""
    port = get_open_port()
    _fault_env(monkeypatch, tmp_path, port)
    monkeypatch.setenv("VDT_CONNECT_TIMEOUT_SECONDS", "2")
    baseline = _vdt_threads()
    agent = _spawn_agent(
        port, {"VDT_FAULT_CONNECT_DELAY_SECONDS": "60"}
    )
    t0 = time.monotonic()
    try:
        with pytest.raises(RuntimeError, match="Executor failed") as ei:
            FaultMultiHostExecutor(
                _engine_args(tmp_path).create_engine_config()
            )
        elapsed = time.monotonic() - t0
        assert elapsed < 2 + SLACK + 2  # bounded by the connect deadline
        assert "[connect]" in str(ei.value)
        assert "0/1 agent(s)" in str(ei.value)
        _assert_no_new_vdt_threads(baseline)
    finally:
        if agent.is_alive():
            agent.terminate()
        agent.join(timeout=5)


# ---------------------------------------------------------------------
# full-engine degradation: /health 503 + structured cause, drain/reject
# ---------------------------------------------------------------------
def _serve(engine, coro_fn):
    state = init_app_state(engine, served_model_name="fault-test")

    async def go():
        server = TestServer(build_app(state))
        client = TestClient(server)
        await client.start_server()
        try:
            return await coro_fn(client)
        finally:
            await client.close()

    return asyncio.new_event_loop().run_until_complete(go())


def test_agent_killed_mid_generate_drains_and_rejects(engine_deployment):
    """Satellite: the existing kill path, end to end — agent dies while
    a generate() streams; the pending request gets a typed error (never
    a hang), /health flips to 503 with the per-host cause, new requests
    are rejected 503 + Retry-After, and the death is on /metrics."""
    engine, agent, baseline = engine_deployment
    sp = SamplingParams(temperature=0.0, max_tokens=100_000, ignore_eos=True)

    async def go(client):
        outs = 0
        t_kill = None
        with pytest.raises(EngineDeadError) as ei:
            async for _ in engine.generate(
                "victim", prompt_token_ids=[1, 2, 3], sampling_params=sp
            ):
                outs += 1
                if outs == 2:
                    agent.terminate()
                    t_kill = time.monotonic()
        detect = time.monotonic() - t_kill
        assert outs >= 2  # it WAS streaming before the kill
        assert detect < 10.0  # EOF-fast, not execute-timeout-slow
        failure = ei.value.failure
        assert failure is not None
        assert failure.host_rank == 1
        assert failure.phase in ("execute", "connect")

        # /health: 503 + structured cause + Retry-After.
        r = await client.get("/health")
        assert r.status == 503
        assert int(r.headers["Retry-After"]) > 0
        body = await r.json()
        assert body["failure"]["host_rank"] == 1
        assert body["failure"]["phase"] in ("execute", "connect")

        # New engine-level work: immediate typed rejection.
        with pytest.raises(EngineDeadError):
            async for _ in engine.generate(
                "after", prompt_token_ids=[1], sampling_params=sp
            ):
                pass

        # New HTTP work: 503 + Retry-After (retryable, not a 500).
        r = await client.post(
            "/v1/completions",
            json={"model": "m", "prompt": [1, 2], "max_tokens": 4},
        )
        assert r.status == 503
        assert "Retry-After" in r.headers

        # The death reaches Prometheus with its attribution labels.
        r = await client.get("/metrics")
        text = await r.text()
        assert "vllm:engine_dead_info" in text
        assert 'host_rank="1"' in text

    _serve(engine, go)
    engine.shutdown()
    _assert_no_new_vdt_threads(baseline)


def test_wedged_host_fails_idle_engine_health(engine_deployment):
    """The ISSUE's motivating scenario: an IDLE engine (no request ever
    submitted, execute_model never called) over a wedged host must not
    look healthy forever — heartbeats trip engine death and /health
    reports the heartbeat-phase cause."""
    engine, agent, baseline = engine_deployment
    executor = engine.engine.executor
    executor.collective_rpc("inject_fault", ("blackhole_writes", 1.0, 2))
    t0 = time.monotonic()
    budget = HB_INTERVAL * (HB_THRESHOLD + 3) + SLACK

    async def go(client):
        while not engine.errored:
            assert time.monotonic() - t0 < budget, (
                "idle wedged host not detected"
            )
            await asyncio.sleep(0.05)
        r = await client.get("/health")
        assert r.status == 503
        body = await r.json()
        assert body["failure"]["phase"] == "heartbeat"
        assert body["failure"]["host_rank"] == 1
        with pytest.raises(EngineDeadError) as ei:
            async for _ in engine.generate(
                "rejected",
                prompt_token_ids=[1],
                sampling_params=SamplingParams(max_tokens=1),
            ):
                pass
        assert ei.value.failure.phase == "heartbeat"

    _serve(engine, go)
    # Liveness gauge present with the host labeled.
    rendered = engine.metrics.render().decode()
    assert "vllm:host_up" in rendered and 'host_rank="1"' in rendered
    engine.shutdown()
    _assert_no_new_vdt_threads(baseline)


# ---------------------------------------------------------------------
# agent-side symmetry + unit pieces
# ---------------------------------------------------------------------
def test_server_silence_watchdog(monkeypatch):
    """Deployed agent, silent driver → the watchdog returns (→ exit) in
    bounded time; refreshed contact keeps it quiet."""
    monkeypatch.setenv("VDT_HEARTBEAT_INTERVAL_SECONDS", "0.1")
    monkeypatch.setenv("VDT_HEARTBEAT_MISS_THRESHOLD", "2")

    async def silent():
        hb = {"last_contact": time.monotonic()}
        t0 = time.monotonic()
        await asyncio.wait_for(server_silence_watchdog(hb), timeout=5)
        return time.monotonic() - t0

    elapsed = asyncio.new_event_loop().run_until_complete(silent())
    # Budget is interval * (threshold + 1) = 0.3 s; bounded well under 5.
    assert 0.2 <= elapsed < 3.0

    async def refreshed():
        hb = {"last_contact": time.monotonic()}

        async def keepalive():
            while True:
                hb["last_contact"] = time.monotonic()
                await asyncio.sleep(0.05)

        ka = asyncio.ensure_future(keepalive())
        try:
            with pytest.raises(asyncio.TimeoutError):
                await asyncio.wait_for(server_silence_watchdog(hb), 1.0)
        finally:
            ka.cancel()

    asyncio.new_event_loop().run_until_complete(refreshed())


def test_reconnect_backoff_is_jittered_and_capped():
    for attempt in range(16):
        for _ in range(4):
            d = reconnect_delay(attempt)
            assert 0 < d <= 30.0
    assert reconnect_delay(0) <= 1.0
    assert all(15.0 <= reconnect_delay(10) <= 30.0 for _ in range(6))
    # full jitter: repeated draws at one attempt differ
    assert len({reconnect_delay(5) for _ in range(8)}) > 1


def test_flight_recorder_dump_on_host_failure(tmp_path, monkeypatch):
    """ISSUE 12: an injected HostFailure makes the engine dump its
    flight-recorder ring automatically — a bounded JSON artifact with
    the last N step records and the failure attribution attached."""
    import json as _json

    from tests.mock_worker import MockUniProcExecutor
    from vllm_distributed_tpu.distributed.failure import (
        PHASE_EXECUTE,
        HostFailure,
    )
    from vllm_distributed_tpu.engine.flight_recorder import FIELDS
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine

    fr_dir = tmp_path / "fr"
    monkeypatch.setenv("VDT_FLIGHT_RECORDER_DIR", str(fr_dir))
    monkeypatch.setenv("VDT_FLIGHT_RECORDER_SIZE", "32")
    model_dir = write_llama_config(str(tmp_path / "frm"))
    engine = LLMEngine.from_engine_args(
        EngineArgs(
            model=model_dir,
            skip_tokenizer_init=True,
            load_format="dummy",
            num_kv_pages=64,
            max_model_len=512,
            num_decode_steps=1,
            distributed_executor_backend=MockUniProcExecutor,
        )
    )
    try:
        sp = SamplingParams(
            temperature=0.0, max_tokens=3, ignore_eos=True
        )
        # More steps than the ring holds: the dump must stay bounded.
        for i in range(60):
            engine.add_request(
                f"fr-{i}", prompt_token_ids=[1, 2, 3], sampling_params=sp
            )
            while engine.has_unfinished_requests():
                engine.step()
        engine.executor._notify_failure(
            HostFailure(
                host_rank=1,
                address="10.0.0.2:30044",
                phase=PHASE_EXECUTE,
                message="injected for the flight-recorder contract",
            )
        )
        dumps = sorted(fr_dir.glob("flightrecorder-host_failure-*.json"))
        assert dumps, "HostFailure produced no flight-recorder artifact"
        payload = _json.loads(dumps[-1].read_text())
        assert payload["reason"] == "host_failure"
        assert payload["extra"]["host_rank"] == 1
        assert payload["extra"]["phase"] == PHASE_EXECUTE
        assert payload["fields"] == list(FIELDS)
        # Bounded: ring-limited records, not one per executed step.
        assert 0 < len(payload["steps"]) <= 32
        assert dumps[-1].stat().st_size < 1 << 20
    finally:
        engine.shutdown()


def test_fault_injector_unit():
    async def go():
        inj = FaultInjector()
        # pass-through when disarmed
        assert await inj.on_write(0, b"x") == (0, b"x")
        # drop honors after_writes then counts down
        inj.arm("drop", 2, after_writes=1)
        assert await inj.on_write(0, b"skip") == (0, b"skip")
        assert await inj.on_write(0, b"a") is None
        assert await inj.on_write(0, b"b") is None
        assert await inj.on_write(0, b"c") == (0, b"c")  # auto-disarm
        assert inj.frames_dropped == 2
        # corrupt flips bytes, preserves length
        inj.arm("corrupt", 1)
        kind, payload = await inj.on_write(1, b"\x00\xff")
        assert (kind, payload) == (1, b"\xff\x00")
        assert await inj.on_write(1, b"ok") == (1, b"ok")
        # blackhole swallows everything until disarmed
        inj.arm("blackhole")
        assert await inj.on_write(0, b"gone") is None
        assert await inj.on_write(0, b"gone2") is None
        inj.disarm()
        assert await inj.on_write(0, b"back") == (0, b"back")

    asyncio.new_event_loop().run_until_complete(go())

# ---------------------------------------------------------------------
# supervised recovery (ISSUE 4): kill → RECOVERING → rebuild → replay
# ---------------------------------------------------------------------
@pytest.fixture
def recovery_deployment(tmp_path, monkeypatch):
    """AsyncLLM over the mocked multihost executor with the supervisor
    armed and a compose-style agent respawner, so a killed host redials
    and the deployment can re-form in-process."""
    port = get_open_port()
    _recovery_env(monkeypatch, tmp_path, port)
    baseline = _vdt_threads()
    agents = RespawningAgent(port, RECOVERY_AGENT_ENV, spawn=_spawn_agent)
    engine = AsyncLLM.from_engine_args(
        _engine_args(
            tmp_path,
            num_decode_steps=1,
            max_model_len=512,
            distributed_executor_backend=FaultMultiHostExecutor,
        )
    )
    yield engine, agents, baseline
    engine.shutdown()
    agents.stop()


def _metric_value(engine, name):
    for line in engine.metrics.render().decode().splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    return None


def test_kill_mid_stream_recovers_and_replays(recovery_deployment):
    """The tentpole contract end to end: kill the remote host while a
    greedy stream is mid-generation → /health reports RECOVERING (503 +
    Retry-After from the backoff schedule, body carries the originating
    HostFailure), the respawned agent re-forms the deployment, and the
    interrupted request completes with output bit-identical to an
    uninterrupted run — the client stream never observes an error."""
    engine, agents, baseline = recovery_deployment
    sp = SamplingParams(temperature=0.0, max_tokens=12, ignore_eos=True)
    prompt = [1, 2, 3]
    # Mock seq mode: token i == absolute position, so an uninterrupted
    # greedy run of 12 tokens from a 3-token prompt is exactly 3..14.
    expected = list(range(3, 15))

    async def go(client):
        health_states = []

        async def poll_health():
            while True:
                r = await client.get("/health")
                body = {} if r.status == 200 else await r.json()
                health_states.append(
                    (r.status, body, r.headers.get("Retry-After"))
                )
                await asyncio.sleep(0.05)

        poller = asyncio.create_task(poll_health())
        tokens = []
        killed = False
        async for out in engine.generate(
            "victim", prompt_token_ids=prompt, sampling_params=sp
        ):
            tokens = list(out.outputs[0].token_ids)
            if not killed and len(tokens) >= 3:
                agents.kill_current()
                killed = True
        poller.cancel()
        assert killed
        assert out.finished
        # Replay determinism: bit-identical to the uninterrupted run.
        assert tokens == expected, f"{tokens} != {expected}"
        # The RECOVERING state was observable on /health mid-blip.
        recovering = [
            s for s in health_states
            if s[0] == 503 and s[1].get("status") == "recovering"
        ]
        assert recovering, (
            f"RECOVERING never observed on /health: {health_states}"
        )
        _, body, retry_after = recovering[0]
        assert body["failure"]["host_rank"] == 1
        assert body["failure"]["phase"] in (
            "execute", "connect", "heartbeat"
        )
        # Retry-After derives from the backoff schedule (base 0.2s,
        # cap 2s -> ceil in [1, 2]).
        assert 1 <= int(retry_after) <= 2
        # Recovered: healthy again.
        r = await client.get("/health")
        assert r.status == 200

    _serve(engine, go)
    assert engine.supervisor.restarts_total >= 1
    assert _metric_value(engine, "vllm:engine_restarts_total") >= 1
    assert _metric_value(engine, "vllm:requests_replayed_total") >= 1
    # The dead-info gauge closed the incident (back to 0).
    assert _metric_value(engine, "vllm:engine_dead_info") == 0
    engine.shutdown()
    _assert_no_new_vdt_threads(baseline)


def test_kill_with_steps_queued_in_stream_recovers(tmp_path, monkeypatch):
    """ISSUE 7 fault interplay: with the overlapped dispatch pipeline
    active (step streams + fused async scheduling, two steps in
    flight), kill the remote host while steps are queued in its stream
    — the in-flight/queued frames die with the host, the supervisor
    rebuild still replays the journaled request, the continuation is
    bit-identical, and nothing (loop threads, stream runners, futures)
    leaks."""
    port = get_open_port()
    _recovery_env(monkeypatch, tmp_path, port)
    # Pipelined protocol knobs: fused windows through the two-phase
    # stream path, device slow enough that the driver's two-in-flight
    # discipline keeps the remote inbox non-empty at kill time.
    monkeypatch.setenv("VDT_STEP_STREAMS", "1")
    monkeypatch.setenv("VDT_MOCK_STEP_SECONDS", "0.1")
    agent_env = {
        **RECOVERY_AGENT_ENV,
        "VDT_STEP_STREAMS": "1",
        "VDT_MOCK_STEP_SECONDS": "0.1",
    }
    baseline = _vdt_threads()
    agents = RespawningAgent(port, agent_env, spawn=_spawn_agent)
    engine = AsyncLLM.from_engine_args(
        _engine_args(
            tmp_path,
            num_decode_steps=4,  # fused windows -> non_block pipeline
            max_model_len=512,
            distributed_executor_backend=FaultMultiHostExecutor,
        )
    )
    try:
        prompt = [1, 2, 3]
        max_tokens = 24
        expected = list(range(3, 3 + max_tokens))
        sp = SamplingParams(
            temperature=0.0, max_tokens=max_tokens, ignore_eos=True
        )

        async def go(client):
            tokens = []
            killed = False
            async for out in engine.generate(
                "queued-victim",
                prompt_token_ids=list(prompt),
                sampling_params=sp,
            ):
                tokens = list(out.outputs[0].token_ids)
                if not killed and len(tokens) >= 4:
                    # First fused window delivered: the pipeline is
                    # full — step N+1 is executing and N+2 is queued
                    # in the stream when the host dies.
                    agents.kill_current()
                    killed = True
            assert killed and out.finished
            assert tokens == expected, f"{tokens} != {expected}"
            r = await client.get("/health")
            assert r.status == 200

        _serve(engine, go)
        assert engine.supervisor.restarts_total >= 1
        assert _metric_value(engine, "vllm:requests_replayed_total") >= 1
    finally:
        engine.shutdown()
        agents.stop()
    _assert_no_new_vdt_threads(baseline)


def test_restart_policy_exhaustion_goes_terminal(tmp_path, monkeypatch):
    """Exceeding VDT_MAX_ENGINE_RESTARTS within the crash-loop window
    lands in the pre-supervisor terminal state: typed EngineDeadError
    with attribution, 503 dead (not recovering), new work rejected, and
    no leaked threads (the PR 2 leak assertions)."""
    port = get_open_port()
    _recovery_env(monkeypatch, tmp_path, port)
    monkeypatch.setenv("VDT_MAX_ENGINE_RESTARTS", "2")
    # Nobody respawns the agent, so every rebuild times out fast.
    monkeypatch.setenv("VDT_CONNECT_TIMEOUT_SECONDS", "1")
    baseline = _vdt_threads()
    agent = _spawn_agent(port, RECOVERY_AGENT_ENV)
    engine = AsyncLLM.from_engine_args(
        _engine_args(
            tmp_path,
            num_decode_steps=1,
            max_model_len=512,
            distributed_executor_backend=FaultMultiHostExecutor,
        )
    )
    sp = SamplingParams(temperature=0.0, max_tokens=50, ignore_eos=True)

    async def go(client):
        outs = 0
        with pytest.raises(EngineDeadError) as ei:
            async for _ in engine.generate(
                "victim", prompt_token_ids=[1, 2, 3], sampling_params=sp
            ):
                outs += 1
                if outs == 2:
                    agent.terminate()
        assert outs >= 2
        failure = ei.value.failure
        assert failure is not None
        assert failure.host_rank == 1
        # Terminal, not recovering: /health says dead with attribution.
        r = await client.get("/health")
        assert r.status == 503
        body = await r.json()
        assert body["status"] == "dead"
        assert body["failure"]["host_rank"] == 1
        # New work: immediate typed rejection.
        with pytest.raises(EngineDeadError):
            async for _ in engine.generate(
                "after", prompt_token_ids=[1], sampling_params=sp
            ):
                pass

    try:
        _serve(engine, go)
        # Both restart attempts were spent before giving up.
        assert engine.supervisor.restarts_total == 2
        assert _metric_value(engine, "vllm:engine_restarts_total") == 2
        engine.shutdown()
        _assert_no_new_vdt_threads(baseline)
    finally:
        if agent.is_alive():
            agent.terminate()
        agent.join(timeout=5)


def test_request_submitted_during_recovery_waits_and_completes(
    recovery_deployment,
):
    """A request that arrives while the engine is RECOVERING queues in
    the intake and is served by the rebuilt engine — accepted work waits
    out the blip instead of failing."""
    engine, agents, baseline = recovery_deployment
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True)

    async def go(client):
        # Kill mid-stream, then immediately submit new work while the
        # supervisor is still rebuilding.
        first_tokens = []
        late = None
        killed = False
        async for out in engine.generate(
            "victim", prompt_token_ids=[1, 2, 3], sampling_params=sp
        ):
            first_tokens = list(out.outputs[0].token_ids)
            if not killed and len(first_tokens) >= 2:
                agents.kill_current()
                killed = True
                late = asyncio.create_task(
                    _collect_gen(
                        engine.generate(
                            "late",
                            prompt_token_ids=[7, 7, 7, 7],
                            sampling_params=sp.clone(),
                        )
                    )
                )
        assert first_tokens == list(range(3, 9))
        late_out = await asyncio.wait_for(late, timeout=30)
        assert late_out.finished
        # Position-deterministic: 4-token prompt -> tokens 4..9.
        assert list(late_out.outputs[0].token_ids) == list(range(4, 10))

    _serve(engine, go)
    engine.shutdown()
    _assert_no_new_vdt_threads(baseline)


async def _collect_gen(gen):
    last = None
    async for out in gen:
        last = out
    return last


# ---------------------------------------------------------------------
# chaos soak (CI satellite): a 2-cycle smoke runs in the fault suite;
# longer loops carry the `soak` marker and stay out of tier-1.
# ---------------------------------------------------------------------
def test_chaos_soak_smoke(tmp_path):
    from vllm_distributed_tpu.testing import write_llama_config as _wlc

    report = run_soak(
        cycles=2, model_dir=_wlc(str(tmp_path / "soak-m"))
    )
    assert report["cycles"] == 2
    assert report["replay_failures"] == 0
    assert report["restarts_total"] >= 2
    assert report["recovery_seconds"]["max"] > 0
    # ISSUE 12: every kill→recover cycle leaves flight-recorder
    # artifacts behind (host_failure and/or recovery dumps).
    assert report["flightrecorder_dumps"] >= 1


@pytest.mark.soak
@pytest.mark.slow
def test_chaos_soak_long(tmp_path):
    from vllm_distributed_tpu.testing import write_llama_config as _wlc

    report = run_soak(
        cycles=10, model_dir=_wlc(str(tmp_path / "soak-m"))
    )
    assert report["replay_failures"] == 0
