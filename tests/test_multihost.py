"""Multi-host executor over loopback with mock workers (SURVEY.md §4
item 4: the reference's own topology is fully exercisable on one machine;
cf. launch.py:549 connecting over loopback)."""

import multiprocessing
import os
import time

import pytest

from tests.mock_worker import MockWorker  # noqa: F401 (import check)
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.distributed.agent import remote_main
from vllm_distributed_tpu.engine.scheduler import SchedulerOutput
from vllm_distributed_tpu.executor.multihost import MultiHostExecutor
from vllm_distributed_tpu.testing import write_llama_config
from vllm_distributed_tpu.utils import get_open_port


class MockedMultiHostExecutor(MultiHostExecutor):
    worker_cls = "tests.mock_worker.MockWorker"


def _agent_with_env(port, env):
    for k, v in (env or {}).items():
        os.environ[k] = v
    remote_main("127.0.0.1", port)


def _spawn_agent(port, env=None):
    proc = multiprocessing.Process(
        target=_agent_with_env, args=(port, env or {}), daemon=True
    )
    proc.start()
    return proc


@pytest.fixture
def deployment(tmp_path, monkeypatch):
    """A 2-host mocked deployment: executor (host 0) + one agent proc."""
    port = get_open_port()
    monkeypatch.setenv("VDT_SERVER_PORT", str(port))
    monkeypatch.setenv("VDT_EXECUTE_MODEL_TIMEOUT_SECONDS", "20")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    # Pin the advertisement so mocked boots skip the jax chip probe.
    agent = _spawn_agent(
        port,
        {"VDT_ADVERTISE_NUM_CHIPS": "4", "VDT_ADVERTISE_PLATFORM": "cpu"},
    )
    model_dir = write_llama_config(str(tmp_path / "m"))
    config = EngineArgs(
        model=model_dir,
        skip_tokenizer_init=True,
        load_format="dummy",
        num_hosts=2,
    ).create_engine_config()
    executor = MockedMultiHostExecutor(config)
    yield executor, agent
    executor.shutdown()
    if agent.is_alive():
        agent.terminate()
    agent.join(timeout=5)


def test_boot_and_lifecycle_order(deployment):
    executor, _ = deployment
    # Local + remote both ran init_device then load_model, in order.
    lifecycles = executor.collective_rpc("get_lifecycle")
    assert len(lifecycles) == 2
    for lc in lifecycles:
        assert lc == ["init_device", "load_model"]


def test_num_pages_min_aggregation(deployment):
    executor, _ = deployment
    # host0 reports 100, host1 reports 101 → min wins.
    assert executor.determine_num_pages() == 100


def test_env_replication(deployment, monkeypatch):
    executor, _ = deployment
    # VDT_EXECUTE_MODEL_TIMEOUT_SECONDS was set pre-boot and is in the
    # registry → must exist on the remote host; ranks must be 0 and 1.
    replies = executor.collective_rpc(
        "get_rank_and_env", ("VDT_EXECUTE_MODEL_TIMEOUT_SECONDS",)
    )
    assert sorted(r[0] for r in replies) == [0, 1]
    for rank, value in replies:
        assert value == "20", f"rank {rank} missing replicated env"


def test_execute_model_replies_from_host0_only(deployment):
    executor, _ = deployment
    so = SchedulerOutput(
        step_id=0,
        num_scheduled_tokens={"r1": 1},
        total_num_scheduled_tokens=1,
    )
    out = executor.execute_model(so)
    assert out.sampled_token_ids == {"r1": [42]}
    # Fan-out to all, reply only from designated rank:
    replies = executor.collective_rpc("execute_model", (so,))
    assert replies[0] is not None and replies[1] is None


def test_pipelined_dispatch_overlaps_cross_rpc(deployment):
    """Two in-flight dispatches across RPC (VERDICT r2 weak #4): the
    remote worker must receive dispatch N+1 BEFORE fetch N completes —
    i.e. multihost steady state overlaps the DCN round trip with device
    time instead of serializing dispatch-then-resolve."""
    executor, _ = deployment

    def so(step):
        return SchedulerOutput(
            step_id=step,
            num_scheduled_tokens={f"r{step}": 1},
            total_num_scheduled_tokens=1,
        )

    t0 = time.monotonic()
    fut_a = executor.execute_model(so(0), non_block=True)
    fut_b = executor.execute_model(so(1), non_block=True)
    out_a = fut_a.result(timeout=15)
    out_b = fut_b.result(timeout=15)
    elapsed = time.monotonic() - t0
    assert out_a.sampled_token_ids == {"r0": [42]}
    assert out_b.sampled_token_ids == {"r1": [42]}

    # Both workers (local + remote) saw dispatch(1) before fetch_done(0).
    for timeline in executor.collective_rpc("get_timeline"):
        events = {(e, s): t for e, s, t in timeline}
        assert events[("dispatch", 1)] < events[("fetch_done", 0)], timeline
    # And the engine-visible latency amortizes: ~2 x step time when the
    # round trips overlap, far under the serialized 2 x (rtt + step).
    from tests.mock_worker import MOCK_STEP_SECONDS

    assert elapsed < 2 * MOCK_STEP_SECONDS + 1.0


# Three greedy requests as (prompt tokens, max_tokens).  Staggered
# lengths finish on different steps, forcing mid-window finishes
# (reconciliation) and held notices.
_STAGGERED = [(3 + 2 * i, 9 + i) for i in range(3)]


def _engine_run(tmp_path, monkeypatch, *, streams: str,
                decode_steps: int, spec_k: int = 0, seq: str = "1",
                requests=_STAGGERED, step_seconds: str = "0.01",
                step_stats: list | None = None,
                late: dict | None = None, probe: dict | None = None):
    """Boot a full LLMEngine over the mocked 2-host deployment and run
    ``requests`` to completion; returns req_id -> tokens.  With
    ``step_stats`` the executor's ``step_stream_stats()`` after every
    engine step is appended to it.  ``late`` maps an engine step to
    (prompt tokens, max_tokens) requests added before it; ``probe``
    receives the engine's dispatch and drain counts."""
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    from vllm_distributed_tpu.sampling_params import SamplingParams

    port = get_open_port()
    monkeypatch.setenv("VDT_SERVER_PORT", str(port))
    monkeypatch.setenv("VDT_STEP_STREAMS", streams)
    monkeypatch.setenv("VDT_MOCK_TOKEN_SEQ", seq)
    monkeypatch.setenv("VDT_MOCK_STEP_SECONDS", step_seconds)
    monkeypatch.setenv("VDT_EXECUTE_MODEL_TIMEOUT_SECONDS", "30")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    agent = _spawn_agent(
        port,
        {
            "VDT_ADVERTISE_NUM_CHIPS": "4",
            "VDT_ADVERTISE_PLATFORM": "cpu",
            "VDT_MOCK_TOKEN_SEQ": seq,
            "VDT_MOCK_STEP_SECONDS": step_seconds,
            "VDT_STEP_STREAMS": streams,
        },
    )
    engine = None
    try:
        engine = LLMEngine.from_engine_args(
            EngineArgs(
                model=write_llama_config(
                    str(tmp_path / f"m-{streams}-{decode_steps}-{spec_k}")
                ),
                skip_tokenizer_init=True,
                load_format="dummy",
                num_hosts=2,
                num_decode_steps=decode_steps,
                speculative_ngram_k=spec_k,
                max_model_len=512,
                distributed_executor_backend=MockedMultiHostExecutor,
            )
        )
        if probe is not None:
            from tests.utils import hold_results

            hold_results(engine)  # what overlaps: not this machine's speed
        for i, (prompt_len, max_tokens) in enumerate(requests):
            engine.add_request(
                f"r{i}",
                prompt_token_ids=list(range(1, prompt_len + 1)),
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=max_tokens, ignore_eos=True
                ),
            )
        tokens: dict[str, list[int]] = {}
        late = dict(late or {})
        step = 0
        while late or engine.has_unfinished_requests():
            for j, (prompt_len, max_tokens) in enumerate(late.pop(step, ())):
                engine.add_request(
                    f"late{step}-{j}",
                    prompt_token_ids=list(range(1, prompt_len + 1)),
                    sampling_params=SamplingParams(
                        temperature=0.0, max_tokens=max_tokens,
                        ignore_eos=True,
                    ),
                )
            step += 1
            if not engine.has_unfinished_requests():
                continue
            for out in engine.step():
                tokens[out.request_id] = list(out.outputs[0].token_ids)
            if step_stats is not None:
                step_stats.append(engine.executor.step_stream_stats())
        if probe is not None:
            probe.update(
                dispatches=dict(engine.dispatch_counts),
                breaks=dict(engine.pipeline_break_reasons),
            )
        return tokens
    finally:
        if engine is not None:
            engine.shutdown()
        if agent.is_alive():
            agent.terminate()
        agent.join(timeout=5)


def test_pipelined_vs_blocking_engine_outputs_bit_identical(
    tmp_path, monkeypatch
):
    """ISSUE 7: the overlapped protocol (step streams + async fused
    scheduling, two steps in flight) must be invisible in the outputs —
    greedy tokens bit-identical to the blocking per-step RPC path —
    and, while the batch is steady, neither host's dispatch thread may
    ever wait for a frame with nothing in flight on its device."""
    blocking = _engine_run(
        tmp_path, monkeypatch, streams="0", decode_steps=1
    )
    overlapped = _engine_run(
        tmp_path, monkeypatch, streams="1", decode_steps=4
    )
    # Mock seq mode: token i == absolute position, so a request of
    # (prompt p, max_tokens m) must be exactly range(p, p + m) — both
    # protocols are checked against the ORACLE, not just each other.
    expected = {
        f"r{i}": list(range(p, p + m)) for i, (p, m) in enumerate(_STAGGERED)
    }
    assert blocking == expected
    assert overlapped == expected

    # Steady state: three equal requests decode together for a dozen
    # fused windows.  A stall window is counted at the prefill->decode
    # boundary and at the final drain; every step between them (pipeline
    # full: something in flight on each host when the engine returns)
    # must add none.  Device time is long against the host's work here
    # so a loaded test machine cannot starve the pipeline.
    uniform = [(5, 49)] * 3
    stats: list[dict] = []
    steady = _engine_run(
        tmp_path, monkeypatch, streams="1", decode_steps=4,
        requests=uniform, step_seconds="0.05", step_stats=stats,
    )
    assert steady == {f"r{i}": list(range(5, 5 + 49)) for i in range(3)}
    full = [
        s for s in stats
        if set(s) == {"host0", "host1"}
        and all(h["inflight"] >= 1 for h in s.values())
    ]
    assert len(full) >= 8, stats
    for host in ("host0", "host1"):
        assert full[-1][host]["dispatched"] > full[0][host]["dispatched"]
        assert full[-1][host]["stalls"] == full[0][host]["stalls"], stats


@pytest.mark.parametrize("streams", ["1", "0"])
def test_single_steps_behind_scans_over_two_hosts(
    tmp_path, monkeypatch, streams
):
    """PR 32: arrivals while a scan is in flight are admitted by a
    single step issued WITHOUT blocking, over the step streams (a
    non-blocking frame, two-phase on every host) and over the legacy
    dispatch_model / fetch_results pairs alike; the multi-host executor
    takes the same path as the single process.  Tokens are the
    deterministic stream's, nothing drains, and both kinds of dispatch
    were issued behind another."""
    late = {3: [(6, 11)], 4: [(2, 7), (9, 5)], 8: [(4, 6)]}
    probe: dict = {}
    got = _engine_run(
        tmp_path, monkeypatch, streams=streams, decode_steps=4,
        requests=[(5, 40), (3, 33)], late=late, probe=probe,
    )
    expected = {"r0": list(range(5, 45)), "r1": list(range(3, 36))}
    for step, reqs in late.items():
        for j, (p, m) in enumerate(reqs):
            expected[f"late{step}-{j}"] = list(range(p, p + m))
    assert got == expected
    assert probe["breaks"] == {}
    assert probe["dispatches"].get(("step", True), 0) >= 1, probe
    assert probe["dispatches"].get(("decode", True), 0) >= 1, probe


def test_spec_decode_over_step_streams_bit_identical(
    tmp_path, monkeypatch
):
    """ISSUE 11: speculative verify frames (per-request drafts out,
    realized spec_advance back) over the REAL persistent step-stream
    protocol against a mocked 2-host deployment — outputs must match
    the non-speculative run and the deterministic stream oracle, and
    drafts must actually be accepted (the mirrors stayed in lockstep
    through variable-advance windows or decode would have diverged)."""
    seq = "seq:5,6,7,8"
    base = _engine_run(
        tmp_path, monkeypatch, streams="1", decode_steps=4, seq=seq
    )
    spec = _engine_run(
        tmp_path, monkeypatch, streams="1", decode_steps=4, spec_k=3,
        seq=seq,
    )
    expected = {
        f"r{i}": [
            (5, 6, 7, 8)[p % 4]
            for p in range(3 + 2 * i, 3 + 2 * i + 9 + i)
        ]
        for i in range(3)
    }
    assert base == expected
    assert spec == expected


def test_short_host_rejected(tmp_path, monkeypatch):
    """A TPU host advertising fewer chips than the deployment needs per
    host is skipped with a warning (reference: launch.py:226-231); a
    healthy agent then fills the slot and boot completes."""
    port = get_open_port()
    monkeypatch.setenv("VDT_SERVER_PORT", str(port))
    monkeypatch.setenv("VDT_EXECUTE_MODEL_TIMEOUT_SECONDS", "20")

    # A "TPU host" with zero chips: must be rejected, never fill a slot.
    bad = _spawn_agent(
        port,
        {"VDT_ADVERTISE_NUM_CHIPS": "0", "VDT_ADVERTISE_PLATFORM": "tpu"},
    )
    good = None
    model_dir = write_llama_config(str(tmp_path / "m"))
    config = EngineArgs(
        model=model_dir,
        skip_tokenizer_init=True,
        load_format="dummy",
        num_hosts=2,
    ).create_engine_config()
    try:
        import threading

        boot: dict = {}

        def build():
            try:
                boot["executor"] = MockedMultiHostExecutor(config)
            except Exception as e:  # noqa: BLE001
                boot["error"] = e

        t = threading.Thread(target=build, daemon=True)
        t.start()
        time.sleep(3)
        # Bad agent alone must not complete boot.
        assert "executor" not in boot, "zero-chip host was accepted"
        good = _spawn_agent(
            port,
            {"VDT_ADVERTISE_NUM_CHIPS": "4", "VDT_ADVERTISE_PLATFORM": "tpu"},
        )
        t.join(timeout=30)
        assert "executor" in boot, boot.get("error")
        boot["executor"].shutdown()
    finally:
        for proc in (bad, good):
            if proc is not None and proc.is_alive():
                proc.terminate()
                proc.join(timeout=5)


def test_real_model_two_process_world(tmp_path):
    """SURVEY §4 item 4 at full depth (VERDICT r2 weak #5): a REAL tiny
    Llama served by MultiHostExecutor + agent over loopback — real
    StreamRpcTransport, real Worker on both sides, and a real 2-process
    jax.distributed CPU world (tp=2, one device per process).  Output
    must match the single-process uniproc run bit-for-bit."""
    import subprocess
    import sys

    from tests.utils import make_tiny_llama

    model_dir = write_llama_config(str(tmp_path / "m"), heads=8, kv_heads=4)
    port = get_open_port()
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
        VDT_SERVER_PORT=str(port),
        VDT_HOST_IP="127.0.0.1",
        VDT_EXECUTE_MODEL_TIMEOUT_SECONDS="60",
        PYTHONPATH=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    repo = env["PYTHONPATH"]
    driver = subprocess.Popen(
        [sys.executable, os.path.join(repo, "tests", "multihost_driver.py"),
         model_dir],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    # Agent output goes to a file, not a PIPE: nobody drains the pipe
    # concurrently, and XLA's chatty stderr would fill it and deadlock.
    agent_log = open(tmp_path / "agent.log", "w")
    agent = subprocess.Popen(
        [sys.executable, "-c",
         "from vllm_distributed_tpu.distributed.agent import remote_main; "
         f"remote_main('127.0.0.1', {port})"],
        env=env,
        stdout=agent_log,
        stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        # Generous timeout: two jax processes compiling concurrently on
        # a small CI box are slow (observed ~330s on one core).
        out, _ = driver.communicate(timeout=570)
    finally:
        agent.terminate()
        if driver.poll() is None:
            driver.kill()
        agent_log.close()
    assert driver.returncode == 0, out[-4000:]
    line = [l for l in out.splitlines() if l.startswith("TOKENS=")]
    assert line, out[-4000:]
    import json as _json

    got = _json.loads(line[0][len("TOKENS="):])

    # Single-process oracle on the same dummy weights.
    from vllm_distributed_tpu.config import EngineArgs as EA
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    from vllm_distributed_tpu.sampling_params import SamplingParams

    eng = LLMEngine.from_engine_args(
        EA(
            model=model_dir,
            skip_tokenizer_init=True,
            load_format="dummy",
            num_kv_pages=32,
            max_model_len=64,
            num_decode_steps=4,
        )
    )
    eng.add_request(
        "x",
        prompt_token_ids=[1, 5, 9],
        sampling_params=SamplingParams(
            temperature=0.0, max_tokens=6, ignore_eos=True
        ),
    )
    want = None
    while eng.has_unfinished_requests():
        for o in eng.step():
            want = o.outputs[0].token_ids
    assert got == want, (got, want)


def test_agent_loss_fails_executor(deployment):
    executor, agent = deployment
    failed = []
    executor.register_failure_callback(lambda: failed.append(True))
    agent.terminate()
    agent.join(timeout=5)
    deadline = time.time() + 10
    while not executor.is_failed and time.time() < deadline:
        time.sleep(0.1)
    assert executor.is_failed
    assert failed == [True]
    with pytest.raises(RuntimeError, match="Executor failed"):
        executor.collective_rpc("check_health")
