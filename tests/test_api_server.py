"""OpenAI API server integration tests over a real AsyncLLM engine on CPU
(SURVEY.md §4 item 3: serve a tiny model and hit the OpenAI API)."""

import asyncio
import json

import pytest
from aiohttp.test_utils import TestClient, TestServer

from tests.utils import add_tiny_tokenizer, make_tiny_llama
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.async_llm import AsyncLLM
from vllm_distributed_tpu.entrypoints.openai.api_server import (
    build_app,
    init_app_state,
)


@pytest.fixture(scope="module")
def served_app(tmp_path_factory):
    """Shared engine/state; a FRESH app per call (TestServer freezes the
    app it serves, so apps are single-use)."""
    model_dir = make_tiny_llama(str(tmp_path_factory.mktemp("srv")))
    add_tiny_tokenizer(model_dir)
    engine = AsyncLLM.from_engine_args(
        EngineArgs(
            model=model_dir,
            num_kv_pages=128,
            max_model_len=256,
            max_num_seqs=8,
        )
    )
    state = init_app_state(
        engine,
        served_model_name="tiny-llama",
        tool_call_parser="hermes",
    )
    yield lambda: build_app(state)
    engine.shutdown()


def _client_call(make_app, coro_fn):
    async def go():
        server = TestServer(make_app())
        client = TestClient(server)
        await client.start_server()
        try:
            return await coro_fn(client)
        finally:
            await client.close()

    return asyncio.new_event_loop().run_until_complete(go())


def test_health_version_models(served_app):
    async def go(client):
        r = await client.get("/health")
        assert r.status == 200
        r = await client.get("/version")
        assert "version" in await r.json()
        r = await client.get("/v1/models")
        data = await r.json()
        assert data["data"][0]["id"] == "tiny-llama"
        assert data["data"][0]["max_model_len"] == 256
        r = await client.get("/metrics")
        assert r.status == 200

    _client_call(served_app, go)


def test_tokenize_roundtrip(served_app):
    async def go(client):
        r = await client.post(
            "/tokenize", json={"prompt": "hello world the cat"}
        )
        data = await r.json()
        assert data["count"] == 4
        r = await client.post(
            "/detokenize", json={"tokens": data["tokens"]}
        )
        text = (await r.json())["prompt"]
        assert "hello" in text and "cat" in text

    _client_call(served_app, go)


def test_completions_basic(served_app):
    async def go(client):
        r = await client.post(
            "/v1/completions",
            json={
                "prompt": "hello world the cat sat",
                "max_tokens": 6,
                "temperature": 0,
                "ignore_eos": True,
            },
        )
        assert r.status == 200, await r.text()
        data = await r.json()
        assert data["object"] == "text_completion"
        assert data["usage"]["completion_tokens"] == 6
        assert data["choices"][0]["finish_reason"] == "length"
        assert isinstance(data["choices"][0]["text"], str)
        return data

    _client_call(served_app, go)


def test_completions_token_ids_and_n(served_app):
    async def go(client):
        r = await client.post(
            "/v1/completions",
            json={
                "prompt": [3, 4, 5],
                "n": 2,
                "max_tokens": 4,
                "temperature": 0,
                "ignore_eos": True,
            },
        )
        data = await r.json()
        assert len(data["choices"]) == 2
        # Greedy: both samples identical.
        assert data["choices"][0]["text"] == data["choices"][1]["text"]

    _client_call(served_app, go)


def test_completions_streaming(served_app):
    async def go(client):
        r = await client.post(
            "/v1/completions",
            json={
                "prompt": "hello world",
                "max_tokens": 5,
                "temperature": 0,
                "ignore_eos": True,
                "stream": True,
            },
        )
        assert r.headers["Content-Type"].startswith("text/event-stream")
        body = await r.text()
        events = [
            line[len("data: ") :]
            for line in body.splitlines()
            if line.startswith("data: ")
        ]
        assert events[-1] == "[DONE]"
        finals = [json.loads(e) for e in events[:-1]]
        assert any(
            c["finish_reason"] == "length"
            for e in finals
            for c in e["choices"]
        )

    _client_call(served_app, go)


def test_chat_completions_and_streaming(served_app):
    async def go(client):
        r = await client.post(
            "/v1/chat/completions",
            json={
                "messages": [
                    {"role": "system", "content": "the cat"},
                    {"role": "user", "content": "hello world"},
                ],
                "max_tokens": 5,
                "temperature": 0,
                "ignore_eos": True,
            },
        )
        assert r.status == 200, await r.text()
        data = await r.json()
        assert data["object"] == "chat.completion"
        msg = data["choices"][0]["message"]
        assert msg["role"] == "assistant"
        non_stream_text = msg["content"]

        r = await client.post(
            "/v1/chat/completions",
            json={
                "messages": [
                    {"role": "system", "content": "the cat"},
                    {"role": "user", "content": "hello world"},
                ],
                "max_tokens": 5,
                "temperature": 0,
                "ignore_eos": True,
                "stream": True,
                "stream_options": {"include_usage": True},
            },
        )
        body = await r.text()
        events = [
            line[len("data: ") :]
            for line in body.splitlines()
            if line.startswith("data: ")
        ]
        assert events[-1] == "[DONE]"
        chunks = [json.loads(e) for e in events[:-1]]
        streamed = "".join(
            c["choices"][0]["delta"].get("content") or ""
            for c in chunks
            if c["choices"]
        )
        assert streamed == non_stream_text
        usage_chunks = [c for c in chunks if c.get("usage")]
        assert usage_chunks and usage_chunks[-1]["usage"]["completion_tokens"] == 5

    _client_call(served_app, go)


def test_prompt_too_long_rejected(served_app):
    async def go(client):
        r = await client.post(
            "/v1/completions",
            json={"prompt": [3] * 300, "max_tokens": 4},
        )
        assert r.status == 400
        assert "max_model_len" in (await r.json())["message"]

    _client_call(served_app, go)


def test_stop_string(served_app):
    async def go(client):
        # Find what greedy produces, then stop on its first word.
        r = await client.post(
            "/v1/completions",
            json={
                "prompt": "hello world the cat sat",
                "max_tokens": 8,
                "temperature": 0,
                "ignore_eos": True,
            },
        )
        full = (await r.json())["choices"][0]["text"]
        first_word = full.split()[0] if full.split() else None
        if first_word is None:
            return
        r = await client.post(
            "/v1/completions",
            json={
                "prompt": "hello world the cat sat",
                "max_tokens": 8,
                "temperature": 0,
                "ignore_eos": True,
                "stop": [first_word],
            },
        )
        data = await r.json()
        assert data["choices"][0]["finish_reason"] == "stop"
        assert first_word not in data["choices"][0]["text"]

    _client_call(served_app, go)


def test_chat_streaming_tool_call_deltas(served_app, monkeypatch):
    """Tool-call fragments must stream in SSE chunks AS the text
    arrives (VERDICT r4 missing #3), not only after the request
    finishes: with a stubbed generation that emits qwen3_coder tool
    syntax across several outputs, tool_calls deltas appear in chunks
    BEFORE the final one, and the reassembled arguments match."""
    from vllm_distributed_tpu.entrypoints.openai import api_server
    from vllm_distributed_tpu.outputs import (
        CompletionOutput,
        RequestOutput,
    )

    pieces = [
        "checking ",
        "<tool_call>\n<function=get_weather>\n",
        "<parameter=city>SF</parameter>\n",
        "</function>\n</tool_call>",
    ]

    async def fake_generate(request_id, **kw):
        text = ""
        for j, piece in enumerate(pieces):
            text += piece
            finished = j == len(pieces) - 1
            yield RequestOutput(
                request_id=request_id,
                prompt=None,
                prompt_token_ids=[1, 2],
                outputs=[
                    CompletionOutput(
                        index=0,
                        text=text,
                        token_ids=list(range(j + 1)),
                        finish_reason="stop" if finished else None,
                    )
                ],
                finished=finished,
            )

    async def go(client):
        state = client.server.app["state"]
        monkeypatch.setattr(state, "tool_call_parser", "qwen3_coder")
        monkeypatch.setattr(state, "enable_auto_tool_choice", True)
        monkeypatch.setattr(
            type(state.engine), "generate", lambda self, rid, **kw:
            fake_generate(rid, **kw),
        )
        r = await client.post(
            "/v1/chat/completions",
            json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "hi"}],
                "stream": True,
                "max_tokens": 8,
            },
        )
        assert r.status == 200
        chunks = []
        async for line in r.content:
            line = line.decode().strip()
            if line.startswith("data:") and line[5:].strip() != "[DONE]":
                chunks.append(json.loads(line[5:]))
        return chunks

    chunks = _client_call(served_app, go)
    tool_chunks = [
        (n, c)
        for n, c in enumerate(chunks)
        if c["choices"] and c["choices"][0]["delta"].get("tool_calls")
    ]
    assert tool_chunks, chunks
    # Fragments arrived before the final chunk (true streaming).
    assert tool_chunks[0][0] < len(chunks) - 1
    args = ""
    name = None
    for _, c in tool_chunks:
        for frag in c["choices"][0]["delta"]["tool_calls"]:
            fn = frag.get("function", {})
            name = fn.get("name", name)
            args += fn.get("arguments", "")
    assert name == "get_weather"
    assert json.loads(args) == {"city": "SF"}
    finals = [
        c for c in chunks
        if c["choices"] and c["choices"][0].get("finish_reason")
    ]
    assert finals and finals[-1]["choices"][0]["finish_reason"] == (
        "tool_calls"
    )


# ---------------------------------------------------------------------
# PR 42: a streamed completion chunk is bytes from a template a stream;
# parsed, it is the dict the pydantic form gives
# ---------------------------------------------------------------------
_CREATED = 1_700_000_000
_PROMPT = [3, 1, 4]


def _scripted_output(request_id, text, ids, finish):
    from vllm_distributed_tpu.outputs import CompletionOutput, RequestOutput

    return RequestOutput(
        request_id=request_id,
        prompt=None,
        prompt_token_ids=_PROMPT,
        outputs=[CompletionOutput(
            index=0, text=text, token_ids=list(ids), finish_reason=finish)],
        finished=finish is not None,
    )


class _ScriptedEngine:
    """What ``_stream_completion`` needs of an engine: every choice's
    stream plays ``script``, a list of (cumulative text, cumulative
    token ids, finish reason or None)."""

    def __init__(self, script, tokenizer):
        self.script = script
        self.tokenizer = tokenizer
        self.params = []

    def check_admission(self, **kwargs):
        pass

    async def generate(self, request_id, sampling_params=None, **kwargs):
        self.params.append(sampling_params)
        for text, ids, finish in self.script:
            yield _scripted_output(request_id, text, ids, finish)


def _pydantic_chunks(script, index, router_meta, no_tokenizer, prefill_only):
    """The chunks of one choice as the handler built them before: two
    pydantic models, a dump, the router's keys put into the dict."""
    from vllm_distributed_tpu.entrypoints.openai.protocol import (
        CompletionChoice,
        CompletionResponse,
    )

    chunks, sent, sent_toks, first = [], 0, 0, True
    for text, ids, finish in script:
        delta, new_ids = text[sent:], list(ids[sent_toks:])
        sent, sent_toks = len(text), len(ids)
        if not (delta or finish or (
                new_ids and (no_tokenizer or router_meta))):
            continue
        chunk = CompletionResponse(
            id="cmpl-0",
            created=_CREATED,
            model="tiny",
            choices=[CompletionChoice(
                index=index, text=delta, finish_reason=finish)],
        ).model_dump(exclude_none=True)
        if router_meta:
            chunk["choices"][0]["vdt_token_ids"] = new_ids
            if first:
                chunk["choices"][0]["vdt_prompt_token_ids"] = list(_PROMPT)
            if prefill_only:
                chunk["choices"][0]["vdt_kv_handle"] = f"cmpl-0-{index}"
        first = False
        chunks.append(chunk)
    return chunks


_TEXT = [("He", [5], None), ("He", [5], None), ("Hello", [5, 6], None),
         ("Hello \"w\"\n✓", [5, 6, 7], None),
         ("Hello \"w\"\n✓!", [5, 6, 7, 8], "stop")]
_IDS_ONLY = [("", [], None), ("", [5], None), ("", [5, 6, 7], None),
             ("", [5, 6, 7, 8], "length")]
_CHUNK_CASES = {
    # name: (script, tokenizer?, router header, disagg header, body)
    "text-delta": (_TEXT, True, False, False, {}),
    "text-delta-router": (_TEXT, True, True, False, {}),
    "empty-delta-with-ids": (_IDS_ONLY, False, False, False, {}),
    "empty-delta-with-ids-router": (_IDS_ONLY, False, True, False, {}),
    "ids-behind-a-tokenizer-router": (_IDS_ONLY, True, True, False, {}),
    "ids-behind-a-tokenizer": (_IDS_ONLY, True, False, False, {}),
    "first-chunk-is-the-last": (
        [("", [], None), ("x", [9], "length")], True, True, False, {}),
    "prefill-only": (
        [("", [], None), ("", [9], "length")], False, True, True, {}),
    "prefill-only-without-the-router": (
        [("", [9], "length")], False, False, True, {}),
    "n2": (_TEXT, True, True, False, {"n": 2}),
    "n2-prefill-header-ignored": (_IDS_ONLY, False, True, True, {"n": 2}),
    "include-usage": (
        _IDS_ONLY, False, True, False,
        {"stream_options": {"include_usage": True}}),
    "include-usage-n2": (
        _TEXT, True, False, False,
        {"n": 2, "stream_options": {"include_usage": True}}),
    **{f"finish-{reason}": (
        [("a", [5], None), ("ab", [5, 6], reason)], True, True, False, {})
       for reason in ("stop", "length", "abort", "timeout", "overloaded")},
}


@pytest.mark.parametrize("case", sorted(_CHUNK_CASES))
def test_streamed_completion_chunks_parse_to_the_pydantic_form(
    case, monkeypatch
):
    """Key for key.  The one stated difference: ``created`` is the
    second the stream began, in every chunk of a request (the clock is
    pinned, so the pydantic form is given the same second)."""
    import time
    from types import SimpleNamespace

    from vllm_distributed_tpu.entrypoints.openai import api_server
    from vllm_distributed_tpu.entrypoints.openai.protocol import (
        CompletionResponse,
        UsageInfo,
    )

    script, tokenizer, router, disagg, body = _CHUNK_CASES[case]
    monkeypatch.setattr(api_server, "time", SimpleNamespace(
        time=lambda: _CREATED + 0.9, monotonic=time.monotonic))
    engine = _ScriptedEngine(script, object() if tokenizer else None)
    state = api_server.ServerState(
        engine=engine, model_name="tiny", max_model_len=64)
    headers = {}
    if router:
        headers[api_server.ROUTER_HEADER] = "1"
    if disagg:
        headers[api_server.DISAGG_HEADER] = "prefill"

    async def go(client):
        r = await client.post(
            "/v1/completions", headers=headers,
            json={"prompt": _PROMPT, "stream": True, "max_tokens": 8, **body})
        assert r.status == 200
        return await r.read()

    raw = _client_call(lambda: build_app(state), go)
    frames = raw.split(b"\n\n")
    assert frames.pop() == b"" and frames.pop() == b"data: [DONE]"
    assert all(f.startswith(b"data: {") and b"\n" not in f for f in frames)
    chunks = [json.loads(f[len(b"data: "):]) for f in frames]
    n = body.get("n", 1)
    prefill_only = engine.params[0].prefill_only
    assert prefill_only == (disagg and n == 1)
    if "stream_options" in body:
        done = sum(len(script[-1][1]) for _ in range(n))
        assert chunks.pop() == CompletionResponse(
            id="cmpl-0", created=_CREATED, model="tiny", choices=[],
            usage=UsageInfo(
                prompt_tokens=n * len(_PROMPT), completion_tokens=done,
                total_tokens=n * len(_PROMPT) + done),
        ).model_dump(exclude_none=True)
    for index in range(n):
        got = [c for c in chunks if c["choices"][0]["index"] == index]
        want = _pydantic_chunks(
            script, index, router, not tokenizer, prefill_only)
        assert got == want
        # ... in the pydantic form's key order too
        assert [json.dumps(c) for c in got] == [json.dumps(c) for c in want]
    assert len(chunks) == n * len(want) and want
