"""A step's flush on the host alone: the real ``AsyncLLM._dispatch_outputs``
-> ``generate()`` -> ``_stream_completion`` path over a stub engine (no
model, no device program), N closed-loop streams from a client in a
process of its own, over loopback.  For working on the event loop's
share of a first token's time and of a chunk without the chip (PERF.md,
PR 42); no cell of the benchmark runs it, and what it prints is this
machine's CPU, never a number of the chip's host.

The stub stands where ``LLMEngine`` stands: its ``step()`` sleeps
``--step-ms`` (the device's time, the GIL let go), then gives every
running request ``--burst`` more tokens (a fused scan's one chunk a
stream) and every request admitted since the step before its first
token, the running ones first and the new ones after them, as
``Scheduler.schedule`` orders a step.  Tracing is on in this process, so
the spans the benchmark's readers use say what the loop did:

- ``us_a_chunk``: the flushes' seconds over their outputs (flushes of
  16 outputs and more), and ``flush_ms`` p50 / p95;
- ``first_chunk``: ``api.emit``'s ``hop_ms`` by ``position`` (the
  output's 0-based place in its step's hand-over), and the client's
  first-chunk time from the send;
- with ``--profile-loop S``: a ``cProfile`` over S seconds in the middle
  of the run, switched on from a callback of the event loop, top
  functions by own time.  Python 3.12's profiler sees every thread, so
  the stub's ``step`` and its ``time.sleep`` are the engine thread's
  rows and the rest is the loop's; it costs more than what it measures:
  read shares, not times.

``--engine-order`` leaves ``first_token`` unset on the stub's outputs, so
the hand-over keeps the engine's order: what a server did before PR 42.

Usage: python tools/flush_microbench.py [--streams 64] [--seconds 8]
           [--step-ms 40] [--burst 8] [--engine-order] [--profile-loop 3]
"""

from __future__ import annotations

import argparse
import asyncio
import cProfile
import io
import json
import multiprocessing
import os
import pstats
import random
import statistics
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ROUTER_HEADER = {"X-VDT-Router": "1"}  # as benchmark/loadgen.py sends
PROFILE_TOP = 30  # lines of the loop profile printed, by own time


# ---- the client: a process of its own ----------------------------------
async def _client(url: str, streams: int, seconds: float) -> dict:
    import aiohttp

    first_ms: list[float] = []
    chunks = requests = 0
    deadline = time.monotonic() + seconds

    async def one(session, rng) -> None:
        nonlocal chunks, requests
        while time.monotonic() < deadline:
            body = {
                "prompt": [rng.randrange(1, 100) for _ in range(8)],
                "max_tokens": rng.randrange(16, 385),
                "stream": True,
            }
            sent = time.monotonic()
            first = None
            async with session.post(
                url, json=body, headers=ROUTER_HEADER
            ) as r:
                async for line in r.content:
                    if not line.startswith(b"data: {"):
                        continue
                    chunks += 1
                    if first is None:
                        first = time.monotonic()
            requests += 1
            if first is not None:
                first_ms.append((first - sent) * 1e3)

    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        await asyncio.gather(*(
            one(session, random.Random(i))
            for i in range(streams)))
    return {"requests": requests, "chunks": chunks,
            "first_chunk_ms_p50": _pct(first_ms, 50),
            "first_chunk_ms_p95": _pct(first_ms, 95)}


def _client_main(url, streams, seconds, conn) -> None:
    conn.send(asyncio.run(_client(url, streams, seconds)))
    conn.close()


# ---- the stub engine ----------------------------------------------------
class StubEngine:
    """What ``AsyncLLM`` asks of an ``LLMEngine``, and no more."""

    def __init__(self, config, step_s: float, burst: int, mark_first: bool):
        from vllm_distributed_tpu.metrics import EngineMetrics
        from vllm_distributed_tpu.tracing import get_tracer

        self.config = config
        self.step_s, self.burst, self.mark_first = step_s, burst, mark_first
        self.tracer = get_tracer()
        self.metrics = EngineMetrics("stub", enabled=False)
        self.scheduler = None  # admission: caps only, none set
        self.tokenizer = None
        self.executor = None
        self.errored = False
        self.last_step: tuple | None = None
        self.between_steps = {"slack": 0.0, "idle": 0.0, "intake": 0.0}
        self._running: dict[str, list] = {}  # id -> [ids, max, metrics, out]
        self._new: dict[str, list] = {}
        self._step_id = 0

    def add_request(self, request_id, prompt_token_ids, sampling_params,
                    **kwargs) -> None:
        from vllm_distributed_tpu.outputs import RequestMetrics

        self._new[request_id] = [
            list(prompt_token_ids), sampling_params.max_tokens,
            RequestMetrics(), []]

    def abort_request(self, request_id) -> None:
        self._running.pop(request_id, None)
        self._new.pop(request_id, None)

    def has_unfinished_requests(self) -> bool:
        return bool(self._running or self._new)

    def schedule_slack(self) -> float:
        return 0.0

    def step(self) -> list:
        from vllm_distributed_tpu.outputs import (
            CompletionOutput,
            RequestOutput,
        )

        self._step_id += 1
        time.sleep(self.step_s)  # the device's time: the GIL is let go
        with self.tracer.span(
            "engine.step", trace_root=True, step_id=self._step_id
        ) as span:
            now = time.monotonic()
            admitted, self._new = self._new, {}
            outputs = []
            for group, grow in ((self._running, self.burst), (admitted, 1)):
                for rid, (prompt, cap, metrics, out) in list(group.items()):
                    first = not out
                    out.extend(range(len(out), min(len(out) + grow, cap)))
                    if first:
                        metrics.first_token_time = time.time()
                        metrics.first_token_time_mono = now
                    done = len(out) >= cap
                    outputs.append(RequestOutput(
                        request_id=rid, prompt=None, prompt_token_ids=prompt,
                        outputs=[CompletionOutput(
                            index=0, text="", token_ids=list(out),
                            finish_reason="length" if done else None)],
                        finished=done, metrics=metrics,
                        first_token=first and self.mark_first))
                    if done:
                        group.pop(rid)
            self._running.update(admitted)
        self.last_step = (span.ctx, self._step_id)
        return outputs

    def shutdown(self) -> None:
        pass


# ---- the server side: this process --------------------------------------
def _pct(values, p):
    if not values:
        return None
    values = sorted(values)
    return values[min(len(values) - 1, int(p / 100 * len(values)))]


# a first chunk's place in its step's hand-over, by its upper bound
_PLACES = ((0, "0"), (3, "1-3"), (15, "4-15"), (31, "16-31"),
           (float("inf"), "32+"))


def _place(position: int) -> str:
    return next(label for top, label in _PLACES if position <= top)


def _report(traces: list[dict]) -> dict:
    spans = [s for t in traces for s in t["spans"]]
    flushes = [s for s in spans if s["name"] == "frontend.flush"]
    emits = [s["attributes"] for s in spans if s["name"] == "api.emit"]
    big = [s for s in flushes if s["attributes"]["outputs"] >= 16]
    by_place: dict[str, list] = {label: [] for _, label in _PLACES}
    for a in emits:
        by_place[_place(a["position"])].append(a["hop_ms"])
    ms = [s["duration"] * 1e3 for s in flushes]
    return {
        "flushes": len(flushes),
        "outputs_a_flush_p50": _pct(
            [s["attributes"]["outputs"] for s in flushes], 50),
        "first_outputs_a_flush_mean": statistics.fmean(
            s["attributes"]["first_outputs"] for s in flushes
        ) if flushes else None,
        "us_a_chunk": 1e6 * sum(s["duration"] for s in big) / max(
            1, sum(s["attributes"]["outputs"] for s in big)),
        "flush_ms": {"p50": _pct(ms, 50), "p95": _pct(ms, 95)},
        "first_chunk": {
            "n": len(emits),
            "position_p50": _pct([a["position"] for a in emits], 50),
            "held_ms_p50": _pct([a["held_ms"] for a in emits], 50),
            "hop_ms_p50": _pct([a["hop_ms"] for a in emits], 50),
            "write_ms_p50": _pct([a["write_ms"] for a in emits], 50),
            "hop_ms_by_position": {
                k: {"n": len(v), "p50": _pct(v, 50), "p95": _pct(v, 95)}
                for k, v in by_place.items() if v},
        },
    }


async def _serve(args) -> dict:
    from aiohttp import web

    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.engine import async_llm
    from vllm_distributed_tpu.entrypoints.openai.api_server import (
        build_app,
        init_app_state,
    )
    from vllm_distributed_tpu.testing import write_llama_config
    from vllm_distributed_tpu.tracing import get_tracer

    tracer = get_tracer().configure(True, ring_size=1 << 16)
    config = EngineArgs(
        model=write_llama_config(), skip_tokenizer_init=True,
        load_format="dummy", max_model_len=1024,
    ).create_engine_config()

    def stub(cfg):
        return StubEngine(
            cfg, args.step_ms / 1e3, args.burst, not args.engine_order)

    with mock.patch.object(async_llm, "LLMEngine", stub):
        engine = async_llm.AsyncLLM(config)
    runner = web.AppRunner(build_app(
        init_app_state(engine, served_model_name="stub")))
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    ctx = multiprocessing.get_context("spawn")
    here, there = ctx.Pipe(duplex=False)
    client = ctx.Process(
        target=_client_main,
        args=(f"http://127.0.0.1:{port}/v1/completions", args.streams,
              args.seconds, there),
        daemon=True)
    client.start()
    loop = asyncio.get_running_loop()
    profile = None
    if args.profile_loop:
        profile = cProfile.Profile()
        start = max(0.0, (args.seconds - args.profile_loop) / 2) + 1.0
        # enabled from a callback: on the loop's own thread
        loop.call_later(start, profile.enable)
        loop.call_later(start + args.profile_loop, profile.disable)
    try:
        got = await loop.run_in_executor(
            None, lambda: here.recv() if here.poll(args.seconds + 60) else None)
        if got is None:
            raise RuntimeError("the client process gave no result")
        report = _report(tracer.snapshot())
    finally:
        client.join(timeout=10)
        if client.is_alive():
            client.terminate()
        await runner.cleanup()
        engine.shutdown()
        tracer.configure(False)
        tracer.reset()
    report["client"] = got
    report["chunks_a_second"] = got["chunks"] / args.seconds
    if profile is not None:
        text = io.StringIO()
        pstats.Stats(profile, stream=text).sort_stats("tottime").print_stats(
            PROFILE_TOP)
        report["loop_profile"] = text.getvalue()
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--streams", type=int, default=64)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--step-ms", type=float, default=40.0,
                    help="the stub's device time a step")
    ap.add_argument("--burst", type=int, default=8,
                    help="tokens a running stream gains a step")
    ap.add_argument("--engine-order", action="store_true",
                    help="no output is marked as a first token: the "
                    "hand-over keeps the engine's order")
    ap.add_argument("--profile-loop", type=float, default=0.0,
                    metavar="S", help="cProfile S seconds of the run")
    args = ap.parse_args()
    report = asyncio.run(_serve(args))
    profile = report.pop("loop_profile", None)
    print(json.dumps({"args": vars(args), **report}, indent=1))
    if profile:
        print(profile)
    return 0


if __name__ == "__main__":
    sys.exit(main())
