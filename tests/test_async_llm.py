"""AsyncLLM event-loop isolation (VERDICT r2 weak #3 / ADVICE r1 #1):
a slow engine step (multi-second prefill on a big model) must not freeze
the server's event loop — intake goes through a thread-safe queue, and
no lock is shared between the event loop and the engine thread."""

import asyncio
import time

import pytest

from tests.utils import make_tiny_llama
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.async_llm import AsyncLLM
from vllm_distributed_tpu.sampling_params import SamplingParams


@pytest.fixture()
def engine(tmp_path):
    eng = AsyncLLM.from_engine_args(
        EngineArgs(
            model=make_tiny_llama(str(tmp_path / "m")),
            skip_tokenizer_init=True,
            num_kv_pages=64,
            max_model_len=128,
        )
    )
    yield eng
    eng.shutdown()


async def _consume(agen):
    out = None
    async for item in agen:
        out = item
    return out


def test_event_loop_responsive_during_slow_step(engine):
    """Submissions + health stay <100ms while a 400ms step is mid-flight
    (the old shared lock serialized them behind the step)."""
    real_step = engine.engine.step

    def slow_step():
        time.sleep(0.4)
        return real_step()

    engine.engine.step = slow_step

    async def go():
        sp = SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)
        t1 = asyncio.create_task(
            _consume(engine.generate("a", prompt_token_ids=[1, 2, 3],
                                     sampling_params=sp))
        )
        await asyncio.sleep(0.1)  # engine thread is now inside slow_step
        # Event-loop responsiveness probes while the step blocks.
        worst = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            await engine.check_health()
            await asyncio.sleep(0.01)
            worst = max(worst, time.perf_counter() - t0)
        # Submitting a second request must not block either.
        t0 = time.perf_counter()
        t2 = asyncio.create_task(
            _consume(engine.generate("b", prompt_token_ids=[4, 5],
                                     sampling_params=sp))
        )
        await asyncio.sleep(0)
        submit_latency = time.perf_counter() - t0
        r1, r2 = await asyncio.gather(t1, t2)
        return worst, submit_latency, r1, r2

    worst, submit_latency, r1, r2 = asyncio.new_event_loop().run_until_complete(go())
    assert worst < 0.1, f"event loop stalled {worst:.3f}s behind the step"
    assert submit_latency < 0.1
    assert r1.finished and len(r1.outputs[0].token_ids) == 4
    assert r2.finished and len(r2.outputs[0].token_ids) == 4


def test_intake_error_surfaces_with_type(engine):
    """A too-long prompt raises ValueError out of generate() (the API
    layer maps ValueError -> 400), not a generic engine error."""

    async def go():
        sp = SamplingParams(temperature=0.0, max_tokens=4)
        with pytest.raises(ValueError):
            await _consume(
                engine.generate(
                    "big", prompt_token_ids=list(range(500)),
                    sampling_params=sp,
                )
            )

    asyncio.new_event_loop().run_until_complete(go())


def test_cancel_aborts_request(engine):
    async def go():
        sp = SamplingParams(temperature=0.0, max_tokens=64, ignore_eos=True)
        task = asyncio.create_task(
            _consume(engine.generate("c", prompt_token_ids=[1, 2],
                                     sampling_params=sp))
        )
        await asyncio.sleep(0.3)
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        # The abort drains through intake; the engine ends up idle.
        for _ in range(50):
            if not engine.engine.has_unfinished_requests():
                break
            await asyncio.sleep(0.05)
        assert not engine.engine.has_unfinished_requests()

    asyncio.new_event_loop().run_until_complete(go())


def test_loop_waits_out_the_slack_and_takes_arrivals_in(tmp_path):
    """PR 32: with a dispatch in flight the loop does not schedule the
    next step at once but waits while ``schedule_slack()`` allows; a
    request that arrives meanwhile wakes it, is taken in and is in the
    next schedule.  Tokens are those of an engine that never waits."""
    def build(name, **kw):
        return AsyncLLM.from_engine_args(EngineArgs(
            model=make_tiny_llama(str(tmp_path / name)),
            skip_tokenizer_init=True, num_kv_pages=64, max_model_len=128,
            **kw))

    async def go(eng):
        sp = SamplingParams(temperature=0.0, max_tokens=30, ignore_eos=True)
        t1 = asyncio.create_task(_consume(eng.generate(
            "a", prompt_token_ids=[1, 2, 3], sampling_params=sp)))
        await asyncio.sleep(0.15)
        t2 = asyncio.create_task(_consume(eng.generate(
            "b", prompt_token_ids=[4, 5, 6, 7], sampling_params=sp)))
        return await asyncio.gather(t1, t2)

    eng = build("m", num_decode_steps=4)
    waits = []
    real = eng.engine.schedule_slack

    def slack():
        real()  # the real arithmetic runs; the CPU's steps are too short
        if eng.engine._pending and len(waits) < 40:
            waits.append(time.monotonic())
            return 0.02
        return 0.0

    eng.engine.schedule_slack = slack
    try:
        got = asyncio.new_event_loop().run_until_complete(go(eng))
    finally:
        eng.shutdown()
    ref = build("r", num_decode_steps=1)
    try:
        want = asyncio.new_event_loop().run_until_complete(go(ref))
    finally:
        ref.shutdown()
    assert len(waits) >= 5
    assert [r.outputs[0].token_ids for r in got] == [
        r.outputs[0].token_ids for r in want]
    assert all(len(r.outputs[0].token_ids) == 30 for r in got)
