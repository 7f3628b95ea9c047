"""OpenAI-compatible HTTP server (aiohttp).

The rebuild of the serving layer the reference assembles from vLLM's
entrypoints (setup_server/build_app/init_app_state/serve_http,
launch.py:413-457; SURVEY.md §2 C7): chat completions, completions,
models, tokenize/detokenize, health, version, Prometheus /metrics, SSE
streaming, keep-alive timeout (VDT_HTTP_TIMEOUT_KEEP_ALIVE ≈
VLLM_HTTP_TIMEOUT_KEEP_ALIVE, launch.py:445), and the tool-parser hook
(--tool-call-parser, .env.server:11).
"""

from __future__ import annotations

import asyncio
import hmac
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any

from aiohttp import web

from vllm_distributed_tpu import envs
from vllm_distributed_tpu.engine.async_llm import (
    AsyncLLM,
    EngineDeadError,
    EngineRecoveringError,
)
from vllm_distributed_tpu.engine.overload import EngineOverloadedError
from vllm_distributed_tpu.entrypoints.openai.protocol import (
    EmbeddingData,
    EmbeddingRequest,
    EmbeddingResponse,
    ChatChoice,
    ChatCompletionRequest,
    ChatCompletionResponse,
    ChatCompletionStreamResponse,
    ChatDelta,
    ChatMessage,
    ChatResponseMessage,
    ChatStreamChoice,
    CompletionChoice,
    CompletionRequest,
    CompletionResponse,
    DetokenizeRequest,
    DetokenizeResponse,
    ErrorResponse,
    ModelCard,
    ModelList,
    TokenizeRequest,
    TokenizeResponse,
    ToolCall,
    UsageInfo,
)
from vllm_distributed_tpu.entrypoints.openai.tool_parsers import (
    ToolParserManager,
)
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.outputs import RequestOutput
from vllm_distributed_tpu.tracing import get_tracer
from vllm_distributed_tpu.utils import Counter
from vllm_distributed_tpu.version import __version__

logger = init_logger(__name__)

# Response header echoing the request's 128-bit trace id; look it up in
# /debug/traces to see where the latency went.
TRACE_HEADER = "X-VDT-Trace-Id"

# Request header carrying the client's deadline in milliseconds from
# arrival (the deadline_ms body field wins when both are present).
DEADLINE_HEADER = "X-VDT-Deadline-Ms"

# Request header naming the request's SLO class for goodput accounting
# (ISSUE 12; the slo_class body field wins when both are present).
# Sanitized and cardinality-bounded server-side (engine/slo.py) before
# it becomes a metric label.
SLO_CLASS_HEADER = "X-VDT-SLO-Class"

# Stable identity of this serving replica (VDT_REPLICA_ID, default
# host:port), stamped on every response so a router/bench/log reader can
# attribute behavior per replica (ISSUE 10 satellite).
REPLICA_HEADER = "X-VDT-Replica-Id"

# Internal hop marker set by the multi-replica router (router/): when
# present, streaming chunks carry per-choice ``vdt_token_ids`` (and
# ``vdt_prompt_token_ids`` on the first chunk) so the router can journal
# emitted tokens for live migration and feed its prefix-affinity index.
# The router strips these fields before the client sees them.
ROUTER_HEADER = "X-VDT-Router"

# Disaggregated prefill/decode (ISSUE 15): the router marks the
# prefill-pool hop with ``X-VDT-Disagg: prefill``, and this replica runs
# the request as prefill-only — prefill plus the first sampled token,
# then finish with the KV pages HELD for export (engine/kv_transfer.py).
# Streaming chunks then carry ``vdt_kv_handle`` (the engine request id)
# so the router can drive /internal/kv/export and /internal/kv/release.
DISAGG_HEADER = "X-VDT-Disagg"


@dataclass
class ServerState:
    engine: AsyncLLM
    model_name: str
    max_model_len: int
    tool_call_parser: str | None = None
    enable_auto_tool_choice: bool = False
    chat_template: str | None = None
    api_key: str | None = None
    replica_id: str = ""
    # Disaggregation role this replica announces in /health (ISSUE 15):
    # "prefill" | "decode" | "mixed".  Pure advertisement — the router
    # reads it from the health probe and places accordingly; the
    # replica itself serves whatever arrives.
    role: str = "mixed"
    request_counter: Counter = field(default_factory=Counter)
    metrics: Any = None
    # Live /internal/resume handler task per request id (ISSUE 17): a
    # router replaying a resume for an id it already resumed (it
    # crashed mid-hand-off and cannot know whether the first attempt
    # landed) takes over from the stale handler instead of deadlocking
    # behind its registration.
    resume_takeovers: dict = field(default_factory=dict)


# Endpoints that stay open without an API key (probes + scrapers), the
# same split vLLM's build_app auth middleware makes.  /slo is a scraper
# surface like /metrics (the router's fleet merge pulls it).
_UNAUTHENTICATED = {
    "/health", "/ping", "/version", "/metrics", "/slo",
    # The router's timeline merge scrapes this like /slo and /metrics:
    # same operational-telemetry sensitivity, same auth posture.
    "/debug/events",
}

# Probe/scrape endpoints never open a root span (they would drown the
# trace ring in noise and trace nothing request-shaped).  /drain can
# block for the full drain timeout — a span that long is noise too.
_UNTRACED = {
    "/health", "/ping", "/version", "/metrics", "/slo", "/debug/traces",
    "/debug/flightrecorder", "/debug/events", "/debug/profile", "/drain",
}


@web.middleware
async def auth_middleware(request: web.Request, handler):
    state: ServerState = request.app["state"]
    if state.api_key and request.path not in _UNAUTHENTICATED:
        header = request.headers.get("Authorization", "")
        expect = f"Bearer {state.api_key}".encode()
        got = header.encode("utf-8", "surrogateescape")
        if not hmac.compare_digest(got, expect):
            return _error("invalid or missing API key", 401)
    return await handler(request)


@web.middleware
async def replica_middleware(request: web.Request, handler):
    """Stamp X-VDT-Replica-Id on every unprepared response (streamed
    responses add it to their own headers before prepare())."""
    response = await handler(request)
    state: ServerState = request.app["state"]
    if state.replica_id and not response.prepared:
        response.headers.setdefault(REPLICA_HEADER, state.replica_id)
    return response


def _parent_ctx(request: web.Request) -> tuple | None:
    """Incoming trace context from the router hop (ISSUE 10 satellite):
    the router forwards ``X-VDT-Trace-Id: <trace_id>-<span_id>`` so this
    replica's spans parent under the router's root span and the whole
    request shares one trace id across processes."""
    header = request.headers.get(TRACE_HEADER)
    if not header:
        return None
    trace_id, _, span_id = header.partition("-")
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(trace_id, 16)
        int(span_id, 16)
    except ValueError:
        return None
    return (trace_id, span_id)


@web.middleware
async def trace_middleware(request: web.Request, handler):
    """Root span per API request (tracing.py).  The trace id is echoed
    in the X-VDT-Trace-Id response header; handlers pick the context up
    from ``request['trace_ctx']`` and thread it through the engine so
    queue/prefill/decode/RPC spans share the trace.  A request arriving
    from the router carries a parent context in the same header, and the
    span parents under it instead of rooting a new trace.  With tracing
    off this is one attribute read per request."""
    tracer = get_tracer()
    if not tracer.enabled or request.path in _UNTRACED:
        return await handler(request)
    parent = _parent_ctx(request)
    with tracer.span(
        "api.request",
        parent=parent,
        trace_root=parent is None,
        method=request.method,
        path=request.path,
    ) as span:
        request["trace_ctx"] = span.ctx
        request["trace_span"] = span
        response = await handler(request)
        span.set_attribute("status", response.status)
    if not response.prepared:
        # Streamed (SSE) responses set the header themselves before
        # prepare(); everything else gets it stamped here.
        response.headers[TRACE_HEADER] = span.ctx[0]
    return response


# ---- the request's way in and out, as spans (tracing on) ----
def _record_parse(
    request: web.Request, root, prompt_tokens: int, stream: bool
) -> float:
    """``api.parse``, child of the root ``api.request``: from the root's
    start (the handler's entry) to the first ``engine.generate()`` call,
    which this call stands before — the body read, JSON, validation,
    sampling parameters, tokenizing a text prompt, a streamed response's
    headers.  Once a request (n > 1 comes here per choice).  Returns
    where it ended, which ``generate(parsed_at=)`` takes as the start of
    ``engine.intake``: one stamp, no time between the two spans."""
    if "trace_parsed" not in request:
        request["trace_parsed"] = now = time.monotonic()
        get_tracer().record_span(
            "api.parse",
            root.start,
            now - root.start_mono,
            parent=root.ctx,
            prompt_tokens=prompt_tokens,
            stream=stream,
        )
    return request["trace_parsed"]


def _record_emit(root, out: RequestOutput, got: float) -> None:
    """``api.emit``, for a request's first chunk only (a streaming
    handler calls it once, after the write of the first output that
    carries a token, which it took from ``generate()`` at ``got``): from where
    ``engine.prefill`` ended (the first token's stamp, inside
    ``engine.process``) to ``response.write`` returned (now), in three
    parts — ``held_ms`` (the rest of the engine's step, until it hands
    its outputs to the loop), ``hop_ms`` (``call_soon_threadsafe`` and
    the request's queue, until the handler has it at ``got``: the
    loop's entry plus the chunks of the ``position`` outputs of its step
    that were through before it) and ``write_ms``.  The root gets
    ``first_chunk_ms``, the server-side first-token time: parse + intake
    + queue + prefill + emit."""
    flush, stamped = out.flush, out.metrics.first_token_time_mono
    if flush is None or stamped is None:
        return
    if "first_chunk_ms" in root.attributes:  # n > 1: the first wins
        return
    now = time.monotonic()
    root.set_attribute("first_chunk_ms", (now - root.start_mono) * 1e3)
    get_tracer().record_span(
        "api.emit",
        root.start + (stamped - root.start_mono),
        now - stamped,
        parent=root.ctx,
        request_id=out.request_id,
        held_ms=(flush.handed - stamped) * 1e3,
        hop_ms=(got - flush.handed) * 1e3,
        write_ms=(now - got) * 1e3,
        position=flush.through,
    )


# ---- helpers ----
def _error(message: str, status: int = 400) -> web.Response:
    return web.json_response(
        ErrorResponse(message=message, code=status).model_dump(),
        status=status,
    )


def _engine_dead_response(e: EngineDeadError) -> web.Response:
    """Degraded-mode rejection: 503 (not 500 — the deployment supervisor
    is restarting the backend, the request is retryable elsewhere/later)
    with Retry-After and the structured per-host attribution."""
    body = ErrorResponse(message=str(e), code=503).model_dump()
    failure = getattr(e, "failure", None)
    if failure is not None:
        body["failure"] = failure.to_dict()
    return web.json_response(
        body,
        status=503,
        headers={"Retry-After": str(envs.VDT_RETRY_AFTER_SECONDS)},
    )


def _overloaded_response(e: EngineOverloadedError) -> web.Response:
    """Load-shed rejection: 429 + Retry-After (ISSUE 8), deliberately
    DISTINCT from the dead/recovering 503s — this backend is healthy
    but full, so a load balancer should retry it soon, not eject it."""
    body = ErrorResponse(
        message=str(e), type="overloaded_error", code=429
    ).model_dump()
    body["reason"] = getattr(e, "reason", "overloaded")
    return web.json_response(
        body,
        status=429,
        headers={"Retry-After": str(getattr(e, "retry_after", 1))},
    )


def _request_error(e: Exception) -> web.Response:
    if isinstance(e, EngineOverloadedError):
        return _overloaded_response(e)
    if isinstance(e, EngineDeadError):
        return _engine_dead_response(e)
    return _error(str(e), 400)


def _apply_deadline(request: web.Request, params) -> web.Response | None:
    """Fold the X-VDT-Deadline-Ms header into the sampling params (the
    body field wins).  Returns an error response for a malformed
    header, else None."""
    header = request.headers.get(DEADLINE_HEADER)
    if header is None or params.deadline_ms is not None:
        return None
    try:
        ms = int(header)
        if ms < 1:
            raise ValueError
    except ValueError:
        return _error(
            f"{DEADLINE_HEADER} must be a positive integer, got "
            f"{header!r}"
        )
    params.deadline_ms = ms
    return None


def _apply_slo_class(request: web.Request, req_model, params) -> None:
    """Fold the X-VDT-SLO-Class header into the sampling params.  An
    EXPLICIT body field wins (req_model.slo_class is None only when the
    body omitted it, so a client naming "default" beats the header).
    Never rejects: the class is telemetry, and engine/slo.py sanitizes
    + bounds whatever arrives."""
    header = request.headers.get(SLO_CLASS_HEADER)
    if header and req_model.slo_class is None:
        params.slo_class = header


def _apply_disagg_prefill(
    request: web.Request, params, req_model, num_prompts: int = 1
) -> None:
    """Fold the router's ``X-VDT-Disagg: prefill`` hop marker into the
    sampling params (ISSUE 15): the request runs prefill plus ONE
    sampled token, then finishes with its pages held for export.  Only
    single-choice streaming requests qualify (the router never plans a
    hand-off for anything else); everything else ignores the header."""
    if request.headers.get(DISAGG_HEADER) != "prefill":
        return
    if not req_model.stream or req_model.n != 1 or num_prompts != 1:
        return
    params.prefill_only = True
    params.max_tokens = 1


def _apply_chat_template(state: ServerState, req: ChatCompletionRequest) -> str:
    tokenizer = state.engine.tokenizer
    conversation = [
        m.model_dump(exclude_none=True) for m in req.messages
    ]
    template = req.chat_template or state.chat_template
    kwargs = req.chat_template_kwargs or {}
    if tokenizer is not None and (
        template or getattr(tokenizer, "chat_template", None)
    ):
        return tokenizer.apply_chat_template(
            conversation,
            tokenize=False,
            add_generation_prompt=req.add_generation_prompt,
            chat_template=template,
            tools=req.tools,
            **kwargs,
        )
    # No template available: a plain readable fallback.
    lines = [
        f"{m.get('role')}: {m.get('content') or ''}" for m in conversation
    ]
    lines.append("assistant:")
    return "\n".join(lines)


def _logprobs_dict(out, chat: bool) -> dict | None:
    comp = out.outputs[0]
    if comp.logprobs is None:
        return None
    if chat:
        content = []
        for tok, lp in zip(comp.token_ids, comp.logprobs):
            entry = {
                "token": str(tok),
                "logprob": lp.get(tok, 0.0),
                "top_logprobs": [
                    {"token": str(t), "logprob": v}
                    for t, v in sorted(lp.items(), key=lambda kv: -kv[1])
                ],
            }
            content.append(entry)
        return {"content": content}
    return {
        "tokens": [str(t) for t in comp.token_ids],
        "token_logprobs": [
            lp.get(t, 0.0) for t, lp in zip(comp.token_ids, comp.logprobs)
        ],
        "top_logprobs": [
            {str(t): v for t, v in lp.items()} for lp in comp.logprobs
        ],
    }


async def _collect(gen) -> RequestOutput:
    last = None
    async for out in gen:
        last = out
    return last


def _shed_response(outs: list[RequestOutput]) -> web.Response | None:
    """Map engine-side preempt-to-shed finishes to HTTP 429 on the
    non-streaming path (ISSUE 8): an admitted request the scheduler
    shed under sustained pressure IS a rejection, even though it
    carries partial output.  Streaming responses instead deliver
    finish_reason="overloaded" in the final chunk (headers are long
    gone)."""
    if any(
        out.outputs[0].finish_reason == "overloaded" for out in outs
    ):
        return _overloaded_response(
            EngineOverloadedError(
                "request shed under sustained KV pressure "
                "(preempt-to-shed); retry later",
                reason="overloaded",
                retry_after=envs.VDT_OVERLOAD_RETRY_AFTER_SECONDS,
            )
        )
    return None


# ---- route handlers ----
async def health(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    try:
        await state.engine.check_health()
    except EngineRecoveringError as e:
        # Third engine state: the supervisor is rebuilding in-process.
        # Still 503 (don't route new traffic here yet), but the body
        # says RECOVERING and Retry-After tracks the backoff schedule,
        # so a load balancer knows this backend is coming back.
        body = {"status": "recovering", "error": str(e)}
        failure = getattr(e, "failure", None)
        if failure is not None:
            # The originating HostFailure that triggered the recovery.
            body["failure"] = failure.to_dict()
        return web.json_response(
            body,
            status=503,
            headers={"Retry-After": str(e.retry_after)},
        )
    except EngineDeadError as e:
        body = {"status": "dead", "error": str(e)}
        failure = getattr(e, "failure", None)
        if failure is not None:
            # Per-host attribution verbatim from the control plane:
            # which host, which lifecycle phase, and the cause chain.
            body["failure"] = failure.to_dict()
        return web.json_response(
            body,
            status=503,
            headers={"Retry-After": str(envs.VDT_RETRY_AFTER_SECONDS)},
        )
    if state.engine.draining:
        # Fourth engine state (ISSUE 8): healthy but not admitting —
        # in-flight work is finishing (draining) or has been journaled
        # for hand-off (drained).  503 takes this backend out of LB
        # rotation; the body says why.
        return web.json_response(
            {"status": state.engine.drain_state_name},
            status=503,
            headers={"Retry-After": str(envs.VDT_RETRY_AFTER_SECONDS)},
        )
    # Wall-clock in the body (ISSUE 20): the router pairs it with its
    # own send/recv stamps to estimate this replica's clock offset for
    # /router/timeline correction (heartbeat-RTT style, ISSUE 4).
    body = {"status": "ok", "now": time.time()}
    if state.replica_id:
        body["replica_id"] = state.replica_id
    if state.role and state.role != "mixed":
        body["role"] = state.role
    return web.json_response(body)


async def version(request: web.Request) -> web.Response:
    return web.json_response({"version": __version__})


async def drain(request: web.Request) -> web.Response:
    """Graceful drain (ISSUE 8): stop admission (new requests 429,
    /health reports the drain state), let in-flight requests finish for
    up to ``?timeout=<seconds>`` (default VDT_DRAIN_TIMEOUT_SECONDS),
    then journal what remains to VDT_DRAIN_JOURNAL_PATH so a restarted
    engine — or another replica — replays it with zero lost admitted
    work.  The SIGTERM handler calls the same path."""
    state: ServerState = request.app["state"]
    timeout = None
    raw = request.query.get("timeout")
    if raw is not None:
        try:
            timeout = float(raw)
            if timeout < 0:
                raise ValueError
        except ValueError:
            return _error(f"timeout must be a non-negative number, got {raw!r}")
    result = await state.engine.drain(timeout)
    return web.json_response(result)


async def list_models(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    card = ModelCard(id=state.model_name, max_model_len=state.max_model_len)
    return web.json_response(ModelList(data=[card]).model_dump())


async def tokenize(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    req = TokenizeRequest(**await request.json())
    tokenizer = state.engine.tokenizer
    if tokenizer is None:
        return _error("tokenizer unavailable", 400)
    ids = tokenizer.encode(
        req.prompt, add_special_tokens=req.add_special_tokens
    )
    return web.json_response(
        TokenizeResponse(
            tokens=ids, count=len(ids), max_model_len=state.max_model_len
        ).model_dump()
    )


async def detokenize(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    req = DetokenizeRequest(**await request.json())
    tokenizer = state.engine.tokenizer
    if tokenizer is None:
        return _error("tokenizer unavailable", 400)
    return web.json_response(
        DetokenizeResponse(prompt=tokenizer.decode(req.tokens)).model_dump()
    )


async def chat_completions(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    try:
        req = ChatCompletionRequest(**await request.json())
    except Exception as e:  # noqa: BLE001
        return _error(f"invalid request: {e}")
    request_id = f"chatcmpl-{next(state.request_counter)}"

    prompt = _apply_chat_template(state, req)
    tokenizer = state.engine.tokenizer
    prompt_ids = tokenizer.encode(prompt) if tokenizer else None
    if prompt_ids is not None and len(prompt_ids) >= state.max_model_len:
        return _error(
            f"prompt has {len(prompt_ids)} tokens, exceeding "
            f"max_model_len {state.max_model_len}"
        )
    default_max = state.max_model_len - (
        len(prompt_ids) if prompt_ids else 0
    ) - 1
    try:
        params = req.to_sampling_params(default_max, is_chat=True)
    except ValueError as e:
        return _error(str(e))
    err = _apply_deadline(request, params)
    if err is not None:
        return err
    _apply_slo_class(request, req, params)
    _apply_disagg_prefill(request, params, req)

    # Admission pre-check (no reservation): overload rejects become
    # proper 429s HERE, before any SSE stream opens; generate() runs
    # the authoritative reserving check per choice.
    try:
        state.engine.check_admission(
            num_requests=req.n,
            est_tokens=(len(prompt_ids) if prompt_ids else 0) * req.n,
            prompt_token_ids=prompt_ids,
            slo_class=params.slo_class,
        )
    except EngineOverloadedError as e:
        return _overloaded_response(e)

    if req.stream:
        return await _stream_chat(request, state, req, request_id, prompt_ids, prompt, params)

    root = request.get("trace_span")
    if root is not None:
        _record_parse(
            request, root, len(prompt_ids) if prompt_ids else 0, False
        )
    try:
        outs = await asyncio.gather(
            *(
                _collect(
                    state.engine.generate(
                        f"{request_id}-{i}",
                        prompt=None if prompt_ids else prompt,
                        prompt_token_ids=prompt_ids,
                        sampling_params=params.clone(),
                        trace_ctx=request.get("trace_ctx"),
                    )
                )
                for i in range(req.n)
            )
        )
    except (EngineOverloadedError, EngineDeadError, ValueError) as e:
        return _request_error(e)
    shed = _shed_response(outs)
    if shed is not None:
        return shed

    choices = []
    usage = UsageInfo()
    for i, out in enumerate(outs):
        comp = out.outputs[0]
        content, tool_calls = comp.text, []
        if state.tool_call_parser and (req.tools or state.enable_auto_tool_choice):
            parser = ToolParserManager.get(state.tool_call_parser)
            content, tool_calls = parser.extract(comp.text)
        finish = comp.finish_reason
        if tool_calls:
            finish = "tool_calls"
        choices.append(
            ChatChoice(
                index=i,
                message=ChatResponseMessage(
                    content=content,
                    tool_calls=[ToolCall(**tc) for tc in tool_calls] or None,
                ),
                logprobs=_logprobs_dict(out, chat=True),
                finish_reason=finish,
            )
        )
        usage.prompt_tokens += len(out.prompt_token_ids)
        usage.completion_tokens += len(comp.token_ids)
    usage.total_tokens = usage.prompt_tokens + usage.completion_tokens
    resp = ChatCompletionResponse(
        id=request_id, model=state.model_name, choices=choices, usage=usage
    )
    return web.json_response(resp.model_dump(exclude_none=True))


async def _stream_chat(
    request, state, req, request_id, prompt_ids, prompt, params
) -> web.StreamResponse:
    headers = {
        "Content-Type": "text/event-stream",
        "Cache-Control": "no-cache",
        "Connection": "keep-alive",
    }
    trace_ctx = request.get("trace_ctx")
    if trace_ctx is not None:
        headers[TRACE_HEADER] = trace_ctx[0]
    if state.replica_id:
        headers[REPLICA_HEADER] = state.replica_id
    response = web.StreamResponse(headers=headers)
    await response.prepare(request)

    async def send(obj) -> None:
        payload = obj if isinstance(obj, str) else json.dumps(
            obj.model_dump(exclude_none=True)
        )
        await response.write(f"data: {payload}\n\n".encode())

    # Router hop (ISSUE 10): chunks carry vdt_token_ids metadata so the
    # router can journal emitted tokens for live migration.
    router_meta = request.headers.get(ROUTER_HEADER) == "1"
    include_usage = bool(
        (req.stream_options or {}).get("include_usage", False)
    )
    usage = UsageInfo()
    # Streaming tool-call parsing (the reference's flagship COMMAND is
    # an agent workload: --enable-auto-tool-choice --tool-call-parser
    # qwen3_coder, .env.server:11): tool-call fragments stream in SSE
    # chunks as the text arrives, not after the request finishes.
    use_tools = bool(
        state.tool_call_parser
        and (req.tools or state.enable_auto_tool_choice)
    )

    async def stream_one(i: int) -> None:
        first = True
        sent = 0
        sent_toks = 0
        finish = None
        stream_parser = (
            ToolParserManager.get(state.tool_call_parser).streaming()
            if use_tools
            else None
        )
        sent_tool_deltas = False
        root, parsed_at = request.get("trace_span"), None
        if root is not None:
            parsed_at = _record_parse(
                request, root, len(prompt_ids) if prompt_ids else 0, True
            )

        async def emit(
            delta: ChatDelta, finish_reason=None, meta: dict | None = None
        ) -> None:
            payload = ChatCompletionStreamResponse(
                id=request_id,
                model=state.model_name,
                choices=[
                    ChatStreamChoice(
                        index=i, delta=delta,
                        finish_reason=finish_reason,
                    )
                ],
            ).model_dump(exclude_none=True)
            if meta:
                payload["choices"][0].update(meta)
            await send(json.dumps(payload))

        async for out in state.engine.generate(
            f"{request_id}-{i}",
            prompt=None if prompt_ids else prompt,
            prompt_token_ids=prompt_ids,
            sampling_params=params.clone(),
            trace_ctx=trace_ctx,
            parsed_at=parsed_at,
        ):
            comp = out.outputs[0]
            if root is not None and comp.token_ids:
                got = time.monotonic()  # first tokens, tracing on
            delta_text = comp.text[sent:]
            sent = len(comp.text)
            new_ids = list(comp.token_ids[sent_toks:])
            sent_toks = len(comp.token_ids)
            finish = comp.finish_reason
            tool_deltas: list[dict] = []
            if stream_parser is not None:
                delta_text, tool_deltas = stream_parser.push(delta_text)
                if comp.finished:
                    tail_text, tail_tools = stream_parser.finish()
                    delta_text += tail_text
                    tool_deltas += tail_tools
                sent_tool_deltas |= bool(tool_deltas)
            if comp.finished and sent_tool_deltas:
                finish = "tool_calls"
            if first or delta_text or tool_deltas or comp.finished or (
                router_meta and new_ids
            ):
                delta = ChatDelta(
                    role="assistant" if first else None,
                    content=delta_text or ("" if first else None),
                    tool_calls=tool_deltas or None,
                )
                meta = None
                if router_meta:
                    meta = {"vdt_token_ids": new_ids}
                    if first:
                        meta["vdt_prompt_token_ids"] = list(
                            out.prompt_token_ids
                        )
                    if params.prefill_only:
                        # The export handle the router drives
                        # /internal/kv/export with (ISSUE 15).
                        meta["vdt_kv_handle"] = f"{request_id}-{i}"
                first = False
                await emit(
                    delta, finish if comp.finished else None, meta
                )
                if root is not None and comp.token_ids:
                    _record_emit(root, out, got)
                    root = None  # the first chunk only
            if comp.finished:
                usage.prompt_tokens += len(out.prompt_token_ids)
                usage.completion_tokens += len(comp.token_ids)

    try:
        await asyncio.gather(*(stream_one(i) for i in range(req.n)))
        if include_usage:
            usage.total_tokens = usage.prompt_tokens + usage.completion_tokens
            await send(
                ChatCompletionStreamResponse(
                    id=request_id,
                    model=state.model_name,
                    choices=[],
                    usage=usage,
                )
            )
        await send("[DONE]")
    except EngineOverloadedError as e:
        # Mid-stream shed/drain: headers are long sent, so the reject
        # rides the stream as a typed error frame with the 429 code.
        await send(
            json.dumps(
                {"error": str(e), "code": 429, "reason": e.reason}
            )
        )
    except (EngineDeadError, ValueError) as e:
        # The code tells a fronting router whether this is migratable
        # (503: the backend died, replay elsewhere) or final (400).
        await send(
            json.dumps(
                {
                    "error": str(e),
                    "code": 503 if isinstance(e, EngineDeadError) else 400,
                }
            )
        )
    except (ConnectionResetError, asyncio.CancelledError):
        logger.info("client disconnected from %s", request_id)
    await response.write_eof()
    return response


async def completions(request: web.Request) -> web.Response:
    state: ServerState = request.app["state"]
    try:
        req = CompletionRequest(**await request.json())
    except Exception as e:  # noqa: BLE001
        return _error(f"invalid request: {e}")
    request_id = f"cmpl-{next(state.request_counter)}"
    tokenizer = state.engine.tokenizer

    # Normalize prompt forms: str | [str] | [int] | [[int]].
    prompts: list[tuple[str | None, list[int] | None]] = []
    p = req.prompt
    if isinstance(p, str):
        prompts = [(p, None)]
    elif isinstance(p, list) and p and isinstance(p[0], int):
        prompts = [(None, p)]
    elif isinstance(p, list) and p and isinstance(p[0], str):
        prompts = [(s, None) for s in p]
    elif isinstance(p, list) and p and isinstance(p[0], list):
        prompts = [(None, ids) for ids in p]
    else:
        return _error("invalid prompt")

    resolved: list[tuple[str | None, list[int]]] = []
    for text, ids in prompts:
        if ids is None:
            if tokenizer is None:
                return _error("tokenizer unavailable for text prompts")
            ids = tokenizer.encode(text)
        resolved.append((text, ids))

    longest = max(len(ids) for _, ids in resolved)
    if longest >= state.max_model_len:
        return _error(
            f"prompt has {longest} tokens, exceeding max_model_len "
            f"{state.max_model_len}"
        )
    default_max = state.max_model_len - longest - 1
    try:
        params = req.to_sampling_params(default_max, is_chat=False)
    except ValueError as e:
        return _error(str(e))
    err = _apply_deadline(request, params)
    if err is not None:
        return err
    _apply_slo_class(request, req, params)
    _apply_disagg_prefill(request, params, req, num_prompts=len(resolved))

    try:
        state.engine.check_admission(
            num_requests=len(resolved) * req.n,
            est_tokens=sum(len(ids) for _, ids in resolved) * req.n,
            prompt_token_ids=resolved[0][1],
            slo_class=params.slo_class,
        )
    except EngineOverloadedError as e:
        return _overloaded_response(e)

    if req.stream:
        return await _stream_completion(
            request, state, req, request_id, resolved, params
        )

    root = request.get("trace_span")
    if root is not None:
        _record_parse(
            request, root, sum(len(ids) for _, ids in resolved), False
        )
    gens = []
    for pi, (text, ids) in enumerate(resolved):
        for i in range(req.n):
            gens.append(
                _collect(
                    state.engine.generate(
                        f"{request_id}-{pi}-{i}",
                        prompt=text,
                        prompt_token_ids=ids,
                        sampling_params=params.clone(),
                        trace_ctx=request.get("trace_ctx"),
                    )
                )
            )
    try:
        outs = await asyncio.gather(*gens)
    except (EngineOverloadedError, EngineDeadError, ValueError) as e:
        return _request_error(e)
    shed = _shed_response(outs)
    if shed is not None:
        return shed

    choices = []
    usage = UsageInfo()
    score_cache: dict[tuple, list] = {}  # n choices share one prompt
    for idx, out in enumerate(outs):
        comp = out.outputs[0]
        text = comp.text
        lp_dict = _logprobs_dict(out, chat=False)
        if req.echo:
            prefix = out.prompt or (
                tokenizer.decode(out.prompt_token_ids) if tokenizer else ""
            )
            text = prefix + text
            if lp_dict is not None:
                # Echoed prompts report prompt logprobs too (vLLM's
                # prompt_logprobs surface): a teacher-forced scoring
                # pass off the hot path (model_runner.score).
                key = tuple(out.prompt_token_ids)
                try:
                    if key not in score_cache:
                        score_cache[key] = await state.engine.score(
                            out.prompt_token_ids
                        )
                    prompt_lps = score_cache[key]
                except EngineDeadError as e:
                    return _engine_dead_response(e)
                lp_dict = {
                    "tokens": [str(t) for t in out.prompt_token_ids]
                    + lp_dict["tokens"],
                    "token_logprobs": prompt_lps
                    + lp_dict["token_logprobs"],
                    "top_logprobs": [None] * len(out.prompt_token_ids)
                    + lp_dict["top_logprobs"],
                }
        choices.append(
            CompletionChoice(
                index=idx,
                text=text,
                logprobs=lp_dict,
                finish_reason=comp.finish_reason,
            )
        )
        usage.prompt_tokens += len(out.prompt_token_ids)
        usage.completion_tokens += len(comp.token_ids)
    usage.total_tokens = usage.prompt_tokens + usage.completion_tokens
    resp = CompletionResponse(
        id=request_id, model=state.model_name, choices=choices, usage=usage
    )
    return web.json_response(resp.model_dump(exclude_none=True))


def _completion_chunk_frame(
    request_id: str, model: str, created: int, index: int
) -> tuple[str, str]:
    """A streamed completion chunk's SSE frame either side of what
    varies from chunk to chunk: everything up to the choice's ``text``
    value, and everything after the choice's last key.  Made once a
    stream from the pydantic form itself, so id, object, created, model,
    index and the zero usage of every chunk are what
    ``CompletionResponse`` gives, in its key order; a chunk is then
    ``head + json.dumps(text) + [further keys of the choice] + tail``."""
    frame = json.dumps(
        CompletionResponse(
            id=request_id,
            created=created,
            model=model,
            choices=[CompletionChoice(index=index, text="")],
        ).model_dump(exclude_none=True)
    )
    # A quote inside id or model is escaped, so only the choice's own
    # key can read ``"text": ""``.
    head, hole, tail = frame.partition('"text": ""')
    if not hole:
        raise RuntimeError(f"no text key to split at in {frame!r}")
    return f'data: {head}"text": ', f"{tail}\n\n"


async def _stream_completion(
    request, state, req, request_id, resolved, params
) -> web.StreamResponse:
    headers = {
        "Content-Type": "text/event-stream",
        "Cache-Control": "no-cache",
    }
    trace_ctx = request.get("trace_ctx")
    if trace_ctx is not None:
        headers[TRACE_HEADER] = trace_ctx[0]
    if state.replica_id:
        headers[REPLICA_HEADER] = state.replica_id
    # The second the stream began: the same in every chunk of a request.
    created = int(time.time())
    response = web.StreamResponse(headers=headers)
    await response.prepare(request)
    write = response.write

    async def send_json(payload: str) -> None:
        await write(f"data: {payload}\n\n".encode())

    no_tokenizer = state.engine.tokenizer is None
    router_meta = request.headers.get(ROUTER_HEADER) == "1"
    include_usage = bool(
        (req.stream_options or {}).get("include_usage", False)
    )
    usage = UsageInfo()
    dumps = json.dumps

    async def stream_one(choice_idx: int, text, ids) -> None:
        sent = 0
        sent_toks = 0
        first = True
        head, tail = _completion_chunk_frame(
            request_id, state.model_name, created, choice_idx
        )
        # The export handle the router drives /internal/kv/export with
        # (ISSUE 15).
        kv_handle = (
            dumps(f"{request_id}-{choice_idx}")
            if params.prefill_only
            else None
        )
        root, parsed_at = request.get("trace_span"), None
        if root is not None:
            parsed_at = _record_parse(
                request, root, sum(len(ids) for _, ids in resolved), True
            )
        async for out in state.engine.generate(
            f"{request_id}-{choice_idx}",
            prompt=text,
            prompt_token_ids=ids,
            sampling_params=params.clone(),
            trace_ctx=trace_ctx,
            parsed_at=parsed_at,
        ):
            comp = out.outputs[0]
            token_ids = comp.token_ids
            if root is not None and token_ids:
                got = time.monotonic()  # first tokens, tracing on
            delta = comp.text[sent:]
            sent = len(comp.text)
            new_ids = token_ids[sent_toks:]
            sent_toks = len(token_ids)
            finished = comp.finished
            if finished:
                usage.prompt_tokens += len(out.prompt_token_ids)
                usage.completion_tokens += sent_toks
            # Without a tokenizer (dummy-weight serving/benches) there is
            # no text to delta — stream empty chunks on token arrival so
            # SSE timing still reflects token delivery.
            if delta or finished or (
                new_ids and (no_tokenizer or router_meta)
            ):
                # The choice's keys in the pydantic form's order: text,
                # finish_reason when there is one, then the router's.
                body = dumps(delta)
                if finished:
                    body += f', "finish_reason": {dumps(comp.finish_reason)}'
                if router_meta:
                    body += f', "vdt_token_ids": {dumps(new_ids)}'
                    if first:
                        body += (
                            ', "vdt_prompt_token_ids": '
                            f"{dumps(out.prompt_token_ids)}"
                        )
                    if kv_handle is not None:
                        body += f', "vdt_kv_handle": {kv_handle}'
                first = False
                await write(f"{head}{body}{tail}".encode())
                if root is not None and token_ids:
                    _record_emit(root, out, got)
                    root = None  # the first chunk only

    try:
        tasks = []
        idx = 0
        for text, ids in resolved:
            for _ in range(req.n):
                tasks.append(stream_one(idx, text, ids))
                idx += 1
        await asyncio.gather(*tasks)
        if include_usage:
            usage.total_tokens = (
                usage.prompt_tokens + usage.completion_tokens
            )
            final = CompletionResponse(
                id=request_id,
                created=created,
                model=state.model_name,
                choices=[],
                usage=usage,
            )
            await send_json(json.dumps(final.model_dump(exclude_none=True)))
        await send_json("[DONE]")
    except EngineOverloadedError as e:
        await send_json(
            json.dumps(
                {"error": str(e), "code": 429, "reason": e.reason}
            )
        )
    except (EngineDeadError, ValueError) as e:
        # 503 = backend death (a router live-migrates), 400 = final.
        await send_json(
            json.dumps(
                {
                    "error": str(e),
                    "code": 503 if isinstance(e, EngineDeadError) else 400,
                }
            )
        )
    except (ConnectionResetError, asyncio.CancelledError):
        logger.info("client disconnected from %s", request_id)
    await response.write_eof()
    return response


async def metrics(request: web.Request) -> web.Response:
    """Engine-loop Prometheus instruments (TTFT/ITL/throughput/queues —
    the reference serves vLLM's via build_app, launch.py:429-432).
    Each scrape also pulls the worker-side XLA/HBM telemetry snapshot
    (ISSUE 12) so compile counters and memory gauges stay current in
    steady state — best-effort: a dead/recovering engine just serves
    the previous values."""
    state: ServerState = request.app["state"]
    try:
        await state.engine.refresh_device_telemetry()
    except Exception as e:  # noqa: BLE001 — scrape must answer anyway
        logger.debug("device-telemetry refresh failed: %s", e)
    return web.Response(
        body=state.engine.metrics.render(), content_type="text/plain"
    )


async def slo(request: web.Request) -> web.Response:
    """Per-class SLO/goodput view (ISSUE 12, engine/slo.py): attainment
    counters, mergeable log-bucket TTFT/ITL histograms, and the bounded
    ring of raw per-request timelines.  The router's /router/slo merges
    N replicas' views associatively into the fleet picture; the
    ``timelines`` ring is what the merge is bit-recomputable from
    (``?timelines=0`` omits it for cheap scrapes)."""
    state: ServerState = request.app["state"]
    include = request.query.get("timelines", "1") not in ("0", "false")
    snap = state.engine.metrics.slo_snapshot(include_timelines=include)
    if snap is None:
        return _error(
            "SLO accounting disabled (--disable-log-stats)", 404
        )
    if state.replica_id:
        snap["replica_id"] = state.replica_id
    return web.json_response(snap)


async def debug_flightrecorder(request: web.Request) -> web.Response:
    """The engine flight recorder's bounded per-step ring (ISSUE 12),
    on demand.  ``?dump=1`` also writes the JSON artifact (same format
    as the automatic HostFailure/recovery/drain dumps) and returns its
    path."""
    state: ServerState = request.app["state"]
    recorder = state.engine.engine.flight_recorder
    if not recorder.enabled:
        return _error(
            "flight recorder disabled (VDT_FLIGHT_RECORDER_SIZE=0)", 404
        )
    body = recorder.snapshot()
    if request.query.get("dump") in ("1", "true"):
        body["path"] = recorder.dump("on_demand")
    return web.json_response(body)


async def debug_events(request: web.Request) -> web.Response:
    """This replica's slice of the unified event timeline (ISSUE 20):
    the engine's bounded SentinelLog (flight-recorder dumps, recovery
    transitions, QoS sheds, KV hand-off/restore outcomes), each event
    carrying both monotonic and wall stamps so the router can merge it
    fleet-wide at /router/timeline with clock-offset correction."""
    state: ServerState = request.app["state"]
    log = state.engine.engine.metrics.events
    if not log.enabled:
        return _error(
            "event timeline disabled (VDT_SENTINEL_EVENTS_SIZE=0)", 404
        )
    body = {
        "source": log.source,
        "now_wall": time.time(),
        "now_mono": time.monotonic(),
        "events": log.snapshot(),
    }
    if state.replica_id:
        body["replica_id"] = state.replica_id
    return web.json_response(body)


async def debug_profile(request: web.Request) -> web.Response:
    """Gated server-side jax.profiler capture (ISSUE 12):
    ``POST /debug/profile?seconds=N`` records a trace into the
    configured profile directory (--profile-dir / VDT_PROFILE_DIR) and
    returns the artifact path.  404 while unconfigured — profiling is
    an operator opt-in, like /debug/traces.  One capture at a time."""
    state: ServerState = request.app["state"]
    profile_dir = (
        state.engine.config.observability_config.profile_dir
    )
    if not profile_dir:
        return _error(
            "profiling disabled: start with --profile-dir (or "
            "VDT_PROFILE_DIR) to enable POST /debug/profile",
            404,
        )
    try:
        seconds = float(request.query.get("seconds", "1"))
    except ValueError:
        return _error("seconds must be a number")
    if not 0 < seconds <= 120:
        return _error("seconds must be in (0, 120]")
    if request.app.get("_profiling"):
        return _error("a profile capture is already running", 409)
    path = os.path.join(
        profile_dir, f"profile-{int(time.time() * 1000)}"
    )

    tracer = get_tracer()

    def capture() -> dict:
        # Runs on an executor thread: the sleep must not block the
        # event loop for the capture window.
        import jax

        os.makedirs(path, exist_ok=True)
        # The capture's own span (a trace of its own in the ring) holds
        # both host clocks at its two ends: span starts elsewhere are
        # wall clock, the profile's events are on the profiler's.
        # No Python tracer: its hook on every call of every thread made
        # the host's phases several times longer than they are (the
        # engine's output processing 30 ms where it takes 1.4 untraced,
        # PERF.md) and the capture's end a 40 s stall.  The host plane
        # keeps the runtime's own events and the spans' annotations.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        with tracer.span("profile.capture", trace_root=True) as span:
            jax.profiler.start_trace(path, profiler_options=options)
            # From here every span is also a TraceAnnotation in the
            # profile's host plane (tracing.Span.__enter__).
            tracer.profiling = True
            clocks = {
                "begin_wall": time.time(),
                "begin_mono": time.monotonic(),
            }
            try:
                time.sleep(seconds)
            finally:
                clocks["end_wall"] = time.time()
                clocks["end_mono"] = time.monotonic()
                tracer.profiling = False
                jax.profiler.stop_trace()
            for key, value in clocks.items():
                span.set_attribute(key, value)
        return clocks

    request.app["_profiling"] = True
    try:
        clocks = await asyncio.get_running_loop().run_in_executor(
            None, capture
        )
    except Exception as e:  # noqa: BLE001 — surface, don't 500-crash
        return _error(f"profile capture failed: {e}", 503)
    finally:
        request.app["_profiling"] = False
    return web.json_response({"path": path, "seconds": seconds, **clocks})


async def debug_traces(request: web.Request) -> web.Response:
    """Recent completed request traces (tracing.py ring buffer).

    ``?format=chrome`` returns Chrome trace-event JSON that loads
    directly in Perfetto (https://ui.perfetto.dev) or chrome://tracing;
    the default JSON form is what tools/trace_summary.py consumes.
    ``?trace_id=<id>`` fetches one trace; ``?limit=N`` bounds the dump.
    404 with a documented body while tracing is disabled."""
    tracer = get_tracer()
    if not tracer.enabled:
        return _error(
            "tracing disabled: start with --enable-tracing or "
            "VDT_TRACING=1 to populate /debug/traces",
            404,
        )
    trace_id = request.query.get("trace_id")
    if trace_id:
        trace = tracer.get_trace(trace_id)
        if trace is None:
            return _error(f"trace {trace_id!r} not found", 404)
        return web.json_response({"traces": [trace]})
    try:
        limit = int(request.query.get("limit", "0")) or None
    except ValueError:
        return _error("limit must be an integer")
    if limit is not None and limit < 0:
        return _error("limit must be a non-negative integer")
    if request.query.get("format") == "chrome":
        return web.json_response(tracer.to_chrome(limit))
    # What the ring has lost since start rides along (and goes to the
    # log): a reader of a window that began before ``oldest_start``
    # sees a shortened one.
    state = tracer.ring_state()
    traces = tracer.snapshot(limit, process=True)
    logger.info(
        "/debug/traces: %d traces, %d evicted since start, oldest_start %s",
        len(traces), state["evicted"], state["oldest_start"],
    )
    return web.json_response({"traces": traces, **state})


async def embeddings(request: web.Request) -> web.Response:
    """Pooled (mean, L2-normalized) final-hidden-state embeddings — the
    causal-LM pooling path the reference inherits via vLLM's app
    (launch.py:429; SURVEY.md §2.3 build_app row)."""
    state: ServerState = request.app["state"]
    try:
        req = EmbeddingRequest(**await request.json())
    except Exception as e:  # noqa: BLE001
        return _error(f"invalid request: {e}")
    if req.encoding_format not in ("float", "base64"):
        return _error(
            f"unsupported encoding_format {req.encoding_format!r}"
        )
    tokenizer = state.engine.tokenizer

    try:
        p = req.input
        if isinstance(p, str):
            p = [p]
        if isinstance(p, list) and p and isinstance(p[0], int):
            items = [[int(t) for t in p]]  # single token list
        elif isinstance(p, list) and p and isinstance(p[0], str):
            if tokenizer is None:
                return _error("tokenizer unavailable for text input")
            items = [tokenizer.encode(str(s)) for s in p]
        elif isinstance(p, list) and p and isinstance(p[0], list):
            items = [[int(t) for t in ids] for ids in p]
        else:
            return _error("invalid input")
    except (TypeError, ValueError) as e:
        return _error(f"invalid input: {e}")
    if any(not ids for ids in items):
        return _error("input must contain at least one token")
    longest = max(len(ids) for ids in items)
    # `>` not `>=`: embeddings generate nothing, so no headroom needed.
    if longest > state.max_model_len:
        return _error(
            f"input has {longest} tokens, exceeding max_model_len "
            f"{state.max_model_len}"
        )
    try:
        vectors = await asyncio.gather(
            *(state.engine.embed(ids) for ids in items)
        )
    except EngineDeadError as e:
        return _engine_dead_response(e)
    if req.encoding_format == "base64":
        import base64
        import struct

        vectors = [
            base64.b64encode(
                struct.pack(f"<{len(v)}f", *v)
            ).decode("ascii")
            for v in vectors
        ]
    usage = UsageInfo(prompt_tokens=sum(len(i) for i in items))
    usage.total_tokens = usage.prompt_tokens
    resp = EmbeddingResponse(
        model=state.model_name,
        data=[
            EmbeddingData(index=i, embedding=v)
            for i, v in enumerate(vectors)
        ],
        usage=usage,
    )
    return web.json_response(resp.model_dump())


async def tokenizer_info(request: web.Request) -> web.Response:
    """Tokenizer metadata (the reference registers vLLM's
    maybe_register_tokenizer_info_endpoint, launch.py:34, 428)."""
    state: ServerState = request.app["state"]
    tok = state.engine.tokenizer
    if tok is None:
        return _error("tokenizer unavailable")
    info = {
        "tokenizer_class": type(tok).__name__,
        "vocab_size": getattr(tok, "vocab_size", None),
        "model_max_length": getattr(tok, "model_max_length", None),
        "bos_token": getattr(tok, "bos_token", None),
        "eos_token": getattr(tok, "eos_token", None),
        "pad_token": getattr(tok, "pad_token", None),
        "chat_template": state.chat_template
        or getattr(tok, "chat_template", None),
    }
    return web.json_response(info)


async def internal_resume(request: web.Request) -> web.Response:
    """Live-migration hand-off target (ISSUE 10, router/).  The router
    posts one journaled in-flight request — original OpenAI body (for
    sampling-param parity with the first admission), prompt token ids,
    and the tokens already delivered to the client — and this replica
    re-admits it with the emitted tokens restored as OUTPUT tokens (the
    ``engine/supervisor.py`` JournalEntry preemption-resume semantics,
    via AsyncLLM.register_resumable), so the continuation's greedy
    tokens are bit-identical to an unmigrated run.

    The reply is an internal SSE stream, one JSON frame per output:
    cumulative ``text``, the NEW ``token_ids`` beyond the restored ones
    (the first frame also carries ``prompt_token_ids``), a final frame
    with ``finish_reason`` + ``usage``, then ``[DONE]``.  The router
    converts frames back into client-facing OpenAI chunks.  Logprobs
    are not journaled or restored — a non-issue for streams (the SSE
    chunk format never carries logprobs) and non-streaming requests
    are resubmitted whole, regenerating them."""
    from vllm_distributed_tpu.engine.supervisor import JournalEntry

    state: ServerState = request.app["state"]
    try:
        d = await request.json()
        kind = d.get("kind", "completions")
        rid = str(d["request_id"])
        emitted = [int(t) for t in d.get("emitted_token_ids") or ()]
        body = d.get("body") or {}
    except Exception as e:  # noqa: BLE001
        return _error(f"invalid resume payload: {e}")
    engine = state.engine
    if engine.draining:
        # A draining replica is leaving rotation: accepting a migration
        # here would just migrate it again moments later.
        return web.json_response(
            ErrorResponse(
                message="replica is draining; not accepting migrations",
                code=503,
            ).model_dump(),
            status=503,
            headers={"Retry-After": str(envs.VDT_RETRY_AFTER_SECONDS)},
        )
    try:
        if kind == "chat":
            req = ChatCompletionRequest(**body)
        else:
            req = CompletionRequest(**body)
    except Exception as e:  # noqa: BLE001
        return _error(f"invalid resume body: {e}")
    prompt_ids = d.get("prompt_token_ids")
    if prompt_ids is None:
        # No ids learned from the dead replica's metadata: re-derive
        # them locally (deterministic given the shared model/template).
        tokenizer = engine.tokenizer
        prompt_text = d.get("prompt")
        if kind == "chat":
            prompt_text = _apply_chat_template(state, req)
        if prompt_text is None or tokenizer is None:
            return _error(
                "resume payload carries neither prompt_token_ids nor a "
                "tokenizable prompt"
            )
        prompt_ids = tokenizer.encode(prompt_text)
    prompt_ids = [int(t) for t in prompt_ids]
    if len(prompt_ids) >= state.max_model_len:
        return _error(
            f"prompt has {len(prompt_ids)} tokens, exceeding "
            f"max_model_len {state.max_model_len}"
        )
    default_max = state.max_model_len - len(prompt_ids) - 1
    try:
        params = req.to_sampling_params(default_max, kind == "chat")
    except ValueError as e:
        return _error(str(e))
    err = _apply_deadline(request, params)
    if err is not None:
        return err
    _apply_slo_class(request, req, params)
    # Migrated requests keep their QoS standing (ISSUE 16): the router
    # journals the original class and sends it top-level, covering the
    # case where the client set it via header (not in the body we
    # replay) — the destination replica must bill the same bucket.
    # Precedence mirrors _apply_slo_class: an explicit body field wins,
    # then a header, then the journaled class.  (params.slo_class is
    # already coerced to "default" by to_sampling_params, so the guard
    # must look at the REQUEST model's field, which is None only when
    # the body omitted it.)
    resumed_class = d.get("slo_class")
    if (
        resumed_class
        and req.slo_class is None
        and not request.headers.get(SLO_CLASS_HEADER)
    ):
        params.slo_class = str(resumed_class)
    # Idempotent replay (ISSUE 17 satellite): a router that crashed
    # mid-hand-off replays the same journaled request id, and the
    # second POST must win cleanly.  Cancel the stale handler and wait
    # for its teardown — its generate() finally aborts the engine-side
    # request through the FIFO intake, so the abort is ordered BEFORE
    # the ("resume", ...) our fresh generate() enqueues below — then
    # belt-and-braces abort in case the stale handler died outside its
    # generate loop and never reached that finally.
    prior = state.resume_takeovers.get(rid)
    if prior is not None and prior is not asyncio.current_task():
        prior.cancel()
        try:
            await asyncio.wait_for(
                asyncio.gather(prior, return_exceptions=True), timeout=5
            )
        except asyncio.TimeoutError:
            return _error(
                f"stale resume handler for {rid} did not exit", 503
            )
        await engine.abort(rid)
        # Fence: the old engine-side request keeps stepping until the
        # abort is consumed, and its outputs would land in OUR queue
        # (same id) once we register below — duplicating tokens in the
        # replayed stream.  The barrier resolves only after the abort
        # applied and every stale output dispatch has run (and dropped,
        # nothing being registered under the id right now).
        try:
            await asyncio.wait_for(engine.intake_barrier(), timeout=5)
        except asyncio.TimeoutError:
            return _error(
                f"engine did not quiesce {rid} for takeover", 503
            )
    state.resume_takeovers[rid] = asyncio.current_task()
    engine.register_resumable(
        JournalEntry(
            request_id=rid,
            prompt=None,
            prompt_token_ids=prompt_ids,
            sampling_params=params,
            emitted_token_ids=emitted,
            trace_ctx=request.get("trace_ctx"),
        )
    )

    headers = {
        "Content-Type": "text/event-stream",
        "Cache-Control": "no-cache",
    }
    trace_ctx = request.get("trace_ctx")
    if trace_ctx is not None:
        headers[TRACE_HEADER] = trace_ctx[0]
    if state.replica_id:
        headers[REPLICA_HEADER] = state.replica_id
    response = web.StreamResponse(headers=headers)
    await response.prepare(request)

    async def send_frame(obj: dict) -> None:
        await response.write(f"data: {json.dumps(obj)}\n\n".encode())

    sent_toks = len(emitted)
    first = True
    try:
        async for out in engine.generate(rid, trace_ctx=trace_ctx):
            comp = out.outputs[0]
            new_ids = list(comp.token_ids[sent_toks:])
            sent_toks = len(comp.token_ids)
            if not (new_ids or comp.finished or first):
                continue
            frame: dict = {
                "text": comp.text,
                "token_ids": new_ids,
                "finish_reason": (
                    comp.finish_reason if comp.finished else None
                ),
            }
            if first:
                frame["prompt_token_ids"] = list(out.prompt_token_ids)
                first = False
            if comp.finished:
                frame["usage"] = {
                    "prompt_tokens": len(out.prompt_token_ids),
                    "completion_tokens": len(comp.token_ids),
                }
            await send_frame(frame)
        await response.write(b"data: [DONE]\n\n")
    except EngineOverloadedError as e:
        await send_frame(
            {"error": str(e), "code": 429, "reason": e.reason}
        )
    except (EngineDeadError, ValueError) as e:
        await send_frame({"error": str(e), "code": 503})
    except (ConnectionResetError, asyncio.CancelledError):
        logger.info("router disconnected from resumed %s", rid)
    finally:
        # Only drop the registration if it is still ours — a takeover
        # that cancelled this handler has already installed itself.
        if state.resume_takeovers.get(rid) is asyncio.current_task():
            state.resume_takeovers.pop(rid, None)
    await response.write_eof()
    return response


def _kv_transfer_error(e: Exception) -> web.Response | None:
    """Map typed hand-off failures to responses the router treats as
    'abort and fall back to recompute-resume' (ISSUE 15)."""
    from vllm_distributed_tpu.engine.kv_transfer import KVTransferError

    if isinstance(e, KVTransferError):
        return _error(str(e), 409)
    if isinstance(e, EngineDeadError):
        return _engine_dead_response(e)
    return None


async def internal_kv_export(request: web.Request) -> web.Response:
    """One per-layer chunk of a held prefill's KV pages (ISSUE 15).
    Body: ``{"handle", "layer_start", "layer_count"}``; the handle is
    the ``vdt_kv_handle`` the prefill-only stream carried.  The reply
    carries base64 layer payloads with sha256 checksums plus the chain
    metadata (token ids, page/layer counts) the decode side needs."""
    import base64

    state: ServerState = request.app["state"]
    try:
        d = await request.json()
        handle = str(d["handle"])
        layer_start = int(d.get("layer_start", 0))
        layer_count = int(d.get("layer_count", 1))
    except Exception as e:  # noqa: BLE001
        return _error(f"invalid export payload: {e}")
    try:
        out = await state.engine.kv_export(handle, layer_start, layer_count)
    except Exception as e:  # noqa: BLE001 — typed mapping below; anything else is a 500-worthy bug
        resp = _kv_transfer_error(e)
        if resp is not None:
            return resp
        raise
    for layer in out.get("layers") or ():
        layer["data"] = base64.b64encode(layer["data"]).decode("ascii")
    return web.json_response(out)


async def internal_kv_release(request: web.Request) -> web.Response:
    """Release a prefill export hold's pages (hand-off finished or
    abandoned).  Idempotent — the TTL sweep covers a router that never
    calls this."""
    state: ServerState = request.app["state"]
    try:
        d = await request.json()
        handle = str(d["handle"])
    except Exception as e:  # noqa: BLE001
        return _error(f"invalid release payload: {e}")
    try:
        released = await state.engine.kv_release(handle)
    except EngineDeadError as e:
        return _engine_dead_response(e)
    return web.json_response({"released": bool(released)})


async def internal_kv(request: web.Request) -> web.Response:
    """KV-page import surface of the decode replica (ISSUE 15): the
    router streams a prefill replica's exported pages here in per-layer
    chunks, then commits, and the next ``/internal/resume`` admission
    attaches them as computed through the PR 14 plan/attach path.

    Frames (one POST each):
    - ``{"op": "begin", "prompt_token_ids": [...]}`` →
      ``{"transfer_id", "num_pages"}`` (transfer_id null = nothing
      importable here; skip to resume, recompute is always correct)
    - ``{"op": "chunk", "transfer_id", "layers": [{index, num_layers,
      shape?, data (base64), checksum}, ...]}``
    - ``{"op": "commit", "transfer_id"}`` → ``{"adopted_tokens"}``
    - ``{"op": "abort", "transfer_id"}``

    A ``begin`` may carry ``resume_from`` (ISSUE 19): the router lost a
    chunk round-trip and asks for the still-live reservation plus its
    ``received`` layer indices, then re-pulls only the missing ones.

    A checksum mismatch or incomplete transfer answers 409 and the
    reserved pages are freed — garbage KV can never be indexed."""
    import base64

    state: ServerState = request.app["state"]
    engine = state.engine
    max_frame = envs.VDT_KV_MAX_FRAME_BYTES
    if max_frame > 0 and (request.content_length or 0) > max_frame:
        # Typed bound checked against Content-Length BEFORE buffering
        # the body: an oversized (or hostile) frame costs one header
        # read, not VDT_KV_MAX_FRAME_BYTES of router-side memory.
        return web.json_response(
            ErrorResponse(
                message=(
                    f"kv frame of {request.content_length} bytes "
                    f"exceeds VDT_KV_MAX_FRAME_BYTES={max_frame}"
                ),
                code=413,
            ).model_dump(),
            status=413,
        )
    try:
        d = await request.json()
        op = str(d.get("op") or "")
    except Exception as e:  # noqa: BLE001
        return _error(f"invalid kv frame: {e}")
    try:
        if op == "begin":
            if engine.draining:
                # A draining replica is leaving rotation: importing KV
                # it will never decode just burns the transfer.
                return web.json_response(
                    ErrorResponse(
                        message="replica is draining; not accepting "
                        "kv transfers",
                        code=503,
                    ).model_dump(),
                    status=503,
                    headers={
                        "Retry-After": str(envs.VDT_RETRY_AFTER_SECONDS)
                    },
                )
            token_ids = [int(t) for t in d.get("prompt_token_ids") or ()]
            resume_from = d.get("resume_from")
            return web.json_response(
                await engine.kv_import_begin(
                    token_ids,
                    resume_from=(
                        str(resume_from)
                        if resume_from is not None
                        else None
                    ),
                )
            )
        if op == "chunk":
            tid = str(d["transfer_id"])
            layers = []
            for layer in d.get("layers") or ():
                layers.append(
                    {
                        **layer,
                        "data": base64.b64decode(layer["data"]),
                    }
                )
            return web.json_response(
                await engine.kv_import_chunk(tid, layers)
            )
        if op == "commit":
            return web.json_response(
                await engine.kv_import_commit(str(d["transfer_id"]))
            )
        if op == "abort":
            return web.json_response(
                {
                    "aborted": bool(
                        await engine.kv_import_abort(
                            str(d["transfer_id"])
                        )
                    )
                }
            )
    except KeyError as e:
        return _error(f"kv frame missing field: {e}")
    except Exception as e:  # noqa: BLE001 — typed mapping below; anything else is a 500-worthy bug
        resp = _kv_transfer_error(e)
        if resp is not None:
            return resp
        raise
    return _error(f"unknown kv frame op {op!r}")


# ---- app assembly ----
def build_app(state: ServerState) -> web.Application:
    app = web.Application(
        client_max_size=64 * 2**20,
        middlewares=[replica_middleware, auth_middleware, trace_middleware],
    )
    app["state"] = state
    app.router.add_get("/health", health)
    app.router.add_get("/ping", health)
    app.router.add_get("/version", version)
    app.router.add_post("/drain", drain)
    app.router.add_get("/v1/models", list_models)
    app.router.add_post("/tokenize", tokenize)
    app.router.add_post("/detokenize", detokenize)
    app.router.add_get("/get_tokenizer_info", tokenizer_info)
    app.router.add_post("/v1/chat/completions", chat_completions)
    app.router.add_post("/v1/completions", completions)
    app.router.add_post("/v1/embeddings", embeddings)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/slo", slo)
    app.router.add_get("/debug/traces", debug_traces)
    app.router.add_get("/debug/flightrecorder", debug_flightrecorder)
    app.router.add_get("/debug/events", debug_events)
    app.router.add_post("/debug/profile", debug_profile)
    app.router.add_post("/internal/resume", internal_resume)
    app.router.add_post("/internal/kv", internal_kv)
    app.router.add_post("/internal/kv/export", internal_kv_export)
    app.router.add_post("/internal/kv/release", internal_kv_release)
    return app


def init_app_state(
    engine: AsyncLLM,
    *,
    served_model_name: str | None = None,
    tool_call_parser: str | None = None,
    enable_auto_tool_choice: bool = False,
    chat_template: str | None = None,
    api_key: str | None = None,
    replica_id: str | None = None,
    role: str | None = None,
) -> ServerState:
    model_config = engine.get_model_config()
    if replica_id is None:
        replica_id = envs.VDT_REPLICA_ID
    if replica_id:
        engine.metrics.record_replica_info(replica_id)
    if role is None:
        role = envs.VDT_ROUTER_ROLE
    if role not in ("prefill", "decode", "mixed"):
        raise ValueError(
            f"unknown replica role {role!r}; want prefill | decode | mixed"
        )
    return ServerState(
        engine=engine,
        model_name=served_model_name or model_config.model,
        max_model_len=model_config.max_model_len,
        tool_call_parser=tool_call_parser,
        enable_auto_tool_choice=enable_auto_tool_choice,
        chat_template=chat_template,
        api_key=api_key,
        replica_id=replica_id,
        role=role,
    )


async def serve_http(
    app: web.Application,
    host: str = "0.0.0.0",
    port: int = 8000,
    ssl_certfile: str | None = None,
    ssl_keyfile: str | None = None,
    shutdown_timeout: float | None = None,
) -> web.AppRunner:
    """Start serving; returns the runner (caller owns shutdown).
    ``shutdown_timeout`` caps how long cleanup() waits for in-flight
    requests — the router tests/chaos harness pass a tiny value so
    'kill a replica' means connections actually die mid-stream."""
    ssl_context = None
    if ssl_certfile:
        import ssl as ssl_mod

        ssl_context = ssl_mod.SSLContext(ssl_mod.PROTOCOL_TLS_SERVER)
        ssl_context.load_cert_chain(ssl_certfile, ssl_keyfile)
    runner_kwargs = {}
    if shutdown_timeout is not None:
        runner_kwargs["shutdown_timeout"] = shutdown_timeout
    runner = web.AppRunner(
        app,
        keepalive_timeout=envs.VDT_HTTP_TIMEOUT_KEEP_ALIVE,
        # Cancel handler tasks when the client disconnects (aiohttp
        # disables this by default since 3.9): a non-streaming
        # completion whose client hung up must not keep generating —
        # the cancelled handler's generate() iterators abort their
        # engine-side requests (ISSUE 8 satellite; the streaming path
        # already aborted via its write failing).
        handler_cancellation=True,
        **runner_kwargs,
    )
    await runner.setup()
    site = web.TCPSite(runner, host, port, ssl_context=ssl_context)
    await site.start()
    logger.info("API server listening on %s:%d", host, port)
    return runner
