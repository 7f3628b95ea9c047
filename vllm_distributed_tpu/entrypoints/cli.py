"""CLI — `vdt` / `python -m vllm_distributed_tpu`.

The rebuild of the reference's launcher surface (launch.py:668-679 +
the vLLM CLI families it mounts, launch.py:21-25, 465-507; SURVEY.md §2
C7): ``serve`` boots the engine + OpenAI server, ``remote <server_ip>``
turns this host into a worker agent, plus ``bench``, ``collect-env``,
``run-batch``, and client-side ``chat``/``complete``.  ``${VAR}`` tokens
in argv are env-expanded (FlexibleArgumentParser parity).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.version import __version__

logger = init_logger(__name__)


def _expand_env(argv: list[str]) -> list[str]:
    return [os.path.expandvars(a) for a in argv]


def _add_server_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument("--host", type=str, default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--ssl-certfile", type=str, default=None)
    parser.add_argument("--ssl-keyfile", type=str, default=None)
    parser.add_argument("--served-model-name", type=str, default=None)
    parser.add_argument("--chat-template", type=str, default=None)
    parser.add_argument("--tool-call-parser", type=str, default=None)
    parser.add_argument("--tool-parser-plugin", type=str, default=None)
    parser.add_argument(
        "--enable-auto-tool-choice", action="store_true", default=False
    )
    parser.add_argument("--disable-log-requests", action="store_true")
    parser.add_argument(
        "--api-key",
        type=str,
        default=None,
        help="require 'Authorization: Bearer <key>' on API endpoints",
    )
    parser.add_argument(
        "--log-config",
        type=str,
        default=None,
        help="JSON logging-config file applied via logging.config."
        "dictConfig (the reference's load_log_config, launch.py:34,423)",
    )


def make_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="vdt",
        description="TPU-native distributed LLM serving",
    )
    parser.add_argument("--version", action="version", version=__version__)
    sub = parser.add_subparsers(dest="command", required=True)

    serve = sub.add_parser("serve", help="start the OpenAI API server")
    serve.add_argument("model_tag", type=str, nargs="?", default=None)
    _add_server_args(serve)
    EngineArgs.add_cli_args(serve)

    remote = sub.add_parser(
        "remote", help="offer this host's chips to a server"
    )
    remote.add_argument("server_ip", type=str)
    remote.add_argument("--server-port", type=int, default=None)

    router = sub.add_parser(
        "router",
        help="multi-replica front-end: cache-affinity placement + live "
        "request migration over N `vdt serve` replicas",
    )
    router.add_argument("--host", type=str, default="0.0.0.0")
    router.add_argument("--port", type=int, default=8080)
    router.add_argument(
        "--api-key",
        type=str,
        default=None,
        help="require 'Authorization: Bearer <key>' on API endpoints "
        "(forwarded verbatim to replicas)",
    )
    from vllm_distributed_tpu.config import RouterArgs

    RouterArgs.add_cli_args(router)

    bench = sub.add_parser(
        "bench",
        help="latency/throughput bench (offline) or serve (live HTTP)",
    )
    bench.add_argument(
        "mode", choices=["latency", "throughput", "serve"],
        default="throughput", nargs="?",
    )
    bench.add_argument("--input-len", type=int, default=32)
    bench.add_argument("--output-len", type=int, default=64)
    bench.add_argument("--num-prompts", type=int, default=32)
    # serve mode: drives a LIVE server over HTTP/SSE (the reference's
    # `vllm bench serve`, launch.py:21-25) — engine args unused.
    bench.add_argument("--url", default="http://localhost:8000")
    bench.add_argument("--concurrency", type=int, default=8)
    bench.add_argument(
        "--request-rate",
        type=float,
        default=None,
        help="serve mode: OPEN-LOOP Poisson arrivals at this rate "
        "(req/s) instead of closed-loop concurrency — set it above "
        "capacity to measure overload shedding; rejected (429) and "
        "timed-out requests are accounted separately and never "
        "pollute the latency percentiles",
    )
    bench.add_argument(
        "--ramp",
        type=str,
        default=None,
        metavar="R1:S1,R2:S2,...",
        help="serve mode: piecewise OPEN-LOOP Poisson arrival schedule "
        "— run at R1 req/s for S1 seconds, then R2 for S2, ... "
        "(sweep the rate up and down to exercise an autoscaled "
        "fleet).  Client p50/p99 and rejected/timed-out counts are "
        "reported per segment; --num-prompts is ignored",
    )
    bench.add_argument(
        "--deadline-ms",
        type=int,
        default=None,
        help="serve mode: per-request deadline sent with every request",
    )
    bench.add_argument(
        "--slo-class",
        dest="slo_classes",
        action="append",
        default=None,
        metavar="NAME[:WEIGHT]",
        help="serve mode: send requests under this SLO class "
        "(repeatable; an integer weight sets the mix, e.g. "
        "--slo-class interactive:3 --slo-class batch:1).  Client "
        "TTFT/ITL p50/p99 are reported per class, plus per-class "
        "goodput deltas scraped from the server's "
        "vllm:goodput_requests_total counters — so scheduler changes "
        "are judged on SLO attainment, not just tokens/s",
    )
    # --seed comes from EngineArgs.add_cli_args below (shared with the
    # engine modes); serve mode reuses it for the open-loop arrival
    # processes and tenant length distributions, and records it in the
    # results JSON so multi-tenant A/B runs are reproducible.
    bench.add_argument(
        "--tenant",
        dest="tenants",
        action="append",
        default=None,
        metavar="NAME:key=val,...",
        help="serve mode: MULTI-TENANT load — repeatable named traffic "
        'profiles, e.g. --tenant "chat:class=interactive,arrival='
        'bursty,rate=8,burst=4,input=16-64,output=32-128" --tenant '
        '"bulk:class=batch,arrival=closed,concurrency=16".  Keys: '
        "class (SLO class sent with every request, default NAME), "
        "arrival (poisson|bursty|closed), rate (req/s for the "
        "open-loop arrivals), burst (arrivals per burst epoch), "
        "concurrency (closed-loop streams), input/output (token "
        "lengths, INT or LO-HI sampled uniformly per request).  All "
        "tenants run concurrently for --tenant-seconds; the report "
        "carries per-tenant client percentiles and shed counts plus "
        "per-class server goodput deltas — the instrument every QoS "
        "scheduling change is judged with",
    )
    bench.add_argument(
        "--tenant-seconds",
        type=float,
        default=10.0,
        help="multi-tenant mode: wall-clock duration of the run",
    )
    bench.add_argument(
        "--disagg",
        action="store_true",
        default=False,
        help="serve mode: run the prefill/decode INTERFERENCE scenario "
        "instead of the uniform load — a steady batch of decode "
        "streams with one long-prompt stream injected mid-run, "
        "reporting the decode ITL p99 before vs during the long "
        "prefill and the long prompt's TTFT.  Run it once against a "
        "mixed-pool router and once against a role-separated one "
        "(--fleet-prefill/--fleet-decode or VDT_ROUTER_ROLE "
        "replicas): role separation should hold the decode p99 flat "
        "(the ISSUE 15 A/B)",
    )
    bench.add_argument(
        "--disagg-prompt-len",
        type=int,
        default=1024,
        help="interference scenario: long-prompt length in tokens",
    )
    bench.add_argument(
        "--disagg-decode-streams",
        type=int,
        default=4,
        help="interference scenario: steady decode streams",
    )
    bench.add_argument(
        "--shared-prefix-len",
        type=int,
        default=0,
        help="serve mode: prepend this many SHARED prompt tokens to "
        "every request (router affinity A/B workload: with "
        "--enable-prefix-caching replicas, affinity routing should "
        "show a higher vllm:prefix_cache_hits rate than round_robin)",
    )
    EngineArgs.add_cli_args(bench)

    sub.add_parser("collect-env", help="print environment diagnostics")

    run_batch = sub.add_parser(
        "run-batch", help="run a JSONL batch file offline"
    )
    run_batch.add_argument("-i", "--input-file", required=True)
    run_batch.add_argument("-o", "--output-file", required=True)
    EngineArgs.add_cli_args(run_batch)

    for name in ("chat", "complete"):
        client = sub.add_parser(name, help=f"{name} against a server")
        client.add_argument("--url", default="http://localhost:8000")
        client.add_argument("--model", default=None)
        client.add_argument("prompt", nargs="?", default=None)

    return parser


# ---- serve ----
async def _serve_async(args: argparse.Namespace) -> None:
    from vllm_distributed_tpu.engine.async_llm import AsyncLLM
    from vllm_distributed_tpu.entrypoints.openai.api_server import (
        build_app,
        init_app_state,
        serve_http,
    )
    from vllm_distributed_tpu.entrypoints.openai.tool_parsers import (
        ToolParserManager,
    )

    if args.model_tag:
        args.model = args.model_tag
    if getattr(args, "log_config", None):
        import logging.config

        # vdt-lint: disable=async-blocking — one-shot startup read
        # before the loop serves any traffic.
        with open(args.log_config) as f:
            logging.config.dictConfig(json.load(f))
    if args.tool_parser_plugin:
        ToolParserManager.import_tool_parser(args.tool_parser_plugin)
    engine_args = EngineArgs.from_cli_args(args)
    if engine_args.num_hosts > 1:
        engine_args.distributed_executor_backend = "multihost"
    loop = asyncio.get_running_loop()
    engine = await loop.run_in_executor(
        None, lambda: AsyncLLM.from_engine_args(engine_args)
    )
    chat_template = None
    if args.chat_template:
        if os.path.exists(args.chat_template):
            # vdt-lint: disable=async-blocking — one-shot startup read
            # before the loop serves any traffic.
            with open(args.chat_template) as f:
                chat_template = f.read()
        else:
            chat_template = args.chat_template
    from vllm_distributed_tpu import envs

    state = init_app_state(
        engine,
        served_model_name=args.served_model_name,
        tool_call_parser=args.tool_call_parser,
        enable_auto_tool_choice=args.enable_auto_tool_choice,
        chat_template=chat_template,
        api_key=args.api_key,
        # Stable replica identity (ISSUE 10 satellite): operator-pinned
        # via VDT_REPLICA_ID, else this server's host:port.
        replica_id=envs.VDT_REPLICA_ID or f"{args.host}:{args.port}",
    )
    app = build_app(state)
    runner = await serve_http(
        app,
        host=args.host,
        port=args.port,
        ssl_certfile=args.ssl_certfile,
        ssl_keyfile=args.ssl_keyfile,
    )
    # Graceful drain on SIGTERM (ISSUE 8): stop admission (429 + drain
    # state in /health), let in-flight requests finish under the drain
    # deadline, journal the rest to VDT_DRAIN_JOURNAL_PATH for the
    # restarted process to replay, THEN exit.  A second SIGTERM (or
    # SIGINT) skips the wait.
    stop = asyncio.Event()
    sigterm_seen = False

    def _on_sigterm() -> None:
        nonlocal sigterm_seen
        if stop.is_set():
            return
        if sigterm_seen:
            stop.set()  # second signal: exit now
            return
        sigterm_seen = True

        async def _drain_and_stop() -> None:
            try:
                if state.engine.draining:
                    # An HTTP-initiated /drain is already in progress
                    # (or finished): wait it out instead of re-draining
                    # — stopping now would cancel its journal write.
                    while state.engine.drain_state_name == "draining":
                        await asyncio.sleep(0.1)
                else:
                    await state.engine.drain()
            except Exception:  # noqa: BLE001 — drain is best-effort
                logger.exception("drain on SIGTERM failed")
            finally:
                stop.set()

        logger.warning("SIGTERM: draining before shutdown")
        asyncio.get_running_loop().create_task(_drain_and_stop())

    import signal

    try:
        loop.add_signal_handler(signal.SIGTERM, _on_sigterm)
    except (NotImplementedError, RuntimeError):
        pass  # non-unix platforms / nested loops: plain kill semantics
    try:
        await stop.wait()  # serve until drained + stopped (or killed)
    finally:
        await runner.cleanup()
        engine.shutdown()


def cmd_serve(args: argparse.Namespace) -> None:
    asyncio.run(_serve_async(args))


# ---- remote ----
def cmd_remote(args: argparse.Namespace) -> None:
    from vllm_distributed_tpu.distributed.agent import remote_main

    remote_main(args.server_ip, args.server_port)


# ---- router ----
async def _router_async(args: argparse.Namespace) -> None:
    from vllm_distributed_tpu.config import RouterArgs
    from vllm_distributed_tpu.entrypoints.openai.api_server import (
        serve_http,
    )
    from vllm_distributed_tpu.router.app import (
        RouterState,
        build_router_app,
    )
    from vllm_distributed_tpu.tracing import configure_from_env

    configure_from_env(host="router")
    router_args = RouterArgs.from_cli_args(args)
    urls = router_args.resolved_replicas()
    from vllm_distributed_tpu import envs

    fleet_on = (
        router_args.fleet_size > 0
        or router_args.fleet_prefill > 0
        or router_args.fleet_decode > 0
        or router_args.autoscale
    )
    if not urls and not fleet_on:
        raise SystemExit(
            "router needs replicas: pass --replica URL (repeatable), "
            "set VDT_ROUTER_REPLICAS, or enable the managed fleet "
            "(--fleet-size/--autoscale with --fleet-cmd)"
        )
    state = RouterState(
        urls,
        policy=router_args.policy,
        max_migrations=router_args.max_migrations,
        affinity_block_tokens=router_args.affinity_block_tokens,
        affinity_capacity=router_args.affinity_capacity,
        affinity_min_tokens=router_args.affinity_min_tokens,
        health_interval=router_args.health_interval,
        connect_timeout=router_args.connect_timeout,
        read_timeout=router_args.read_timeout,
        api_key=args.api_key,
        allow_empty_pool=fleet_on,
    )
    # Crash-safe state (ISSUE 17; default off): open the WAL and
    # recover whatever the previous incarnation left — fleet membership
    # to re-adopt, in-flight journals to replay on reconnect.
    persist_log = None
    recovered = None
    state_dir = router_args.resolved_state_dir()
    if state_dir:
        from vllm_distributed_tpu.router.persist import RouterStateLog

        persist_log = RouterStateLog(state_dir)
        recovered = persist_log.open()
        state.attach_persist(persist_log, recovered)
        logger.info(
            "router durable state at %s: recovered %d replica "
            "record(s), %d in-flight journal(s)",
            state_dir,
            len(recovered.replicas),
            len(recovered.journals),
        )
    if fleet_on:
        # Elastic fleet (ISSUE 13): the router owns `vdt serve`
        # replicas as supervised children, optionally resized by the
        # autoscaler control loop.
        from vllm_distributed_tpu.router.app import _fleet_slo
        from vllm_distributed_tpu.router.fleet import (
            Autoscaler,
            AutoscalerConfig,
            CommandLauncher,
            ReplicaManager,
        )

        template = router_args.fleet_cmd or envs.VDT_FLEET_CMD
        if not template:
            raise SystemExit(
                "fleet mode needs a replica command template: pass "
                "--fleet-cmd 'vdt serve ... --port {port}' or set "
                "VDT_FLEET_CMD"
            )
        autoscaler = None
        cfg = None
        if router_args.autoscale:
            cfg = AutoscalerConfig.from_env()
            if router_args.autoscale_min is not None:
                cfg.min_replicas = router_args.autoscale_min
            if router_args.autoscale_max is not None:
                cfg.max_replicas = router_args.autoscale_max
        target = router_args.fleet_size or (
            cfg.min_replicas if cfg is not None else 0
        )
        manager = ReplicaManager(
            state.pool,
            state.metrics,
            CommandLauncher(template),
            target=target,
            # Disaggregated pools (ISSUE 15): fixed per-role counts
            # spawned from the same template with VDT_ROUTER_ROLE set.
            role_targets={
                "prefill": router_args.fleet_prefill,
                "decode": router_args.fleet_decode,
            },
            # Durable membership (ISSUE 17): spawn/retire events land in
            # the WAL so the next incarnation can re-adopt live children.
            persist=persist_log,
        )
        # Recovered scale targets win over the CLI defaults: a crash
        # between a scale-up and its convergence must not revert the
        # fleet (the first reconcile tick would retire the extras the
        # previous incarnation just spawned).
        if recovered is not None and recovered.fleet_target is not None:
            if recovered.fleet_target != manager.target:
                logger.info(
                    "restoring recovered fleet target %d "
                    "(CLI default was %d)",
                    recovered.fleet_target,
                    manager.target,
                )
            manager.target = recovered.fleet_target
            for role, n in (recovered.fleet_role_targets or {}).items():
                if role in manager.role_targets:
                    manager.role_targets[role] = int(n)
        manager.persist_targets()
        if cfg is not None:

            async def _slo_classes() -> dict:
                return (await _fleet_slo(state)).get("classes", {})

            autoscaler = Autoscaler(
                manager,
                state.pool,
                state.metrics,
                cfg,
                slo_probe=_slo_classes,
                # Long-prompt arrival EWMA observed by the proxy path;
                # drives the per-role prefill-pool target (ISSUE 16).
                prefill_demand=state.prefill_demand,
            )
        state.attach_fleet(manager, autoscaler)
    app = build_router_app(state)
    runner = await serve_http(app, host=args.host, port=args.port)
    if fleet_on:
        logger.info(
            "router managing a fleet of %d replica(s)%s (template: %s)",
            state.manager.target,
            " with autoscaling" if state.autoscaler is not None else "",
            router_args.fleet_cmd or envs.VDT_FLEET_CMD,
        )
    if urls:
        logger.info(
            "router fronting %d replica(s) with policy=%s: %s",
            len(urls),
            state.policy,
            ", ".join(urls),
        )
    stop = asyncio.Event()
    sigterm_seen = False

    def _on_sigterm() -> None:
        # Graceful fleet drain on SIGTERM (ISSUE 13 satellite, parity
        # with the replica-side SIGTERM drain from ISSUE 8): drain
        # every MANAGED replica (bounded by
        # VDT_FLEET_DRAIN_TIMEOUT_SECONDS) and reap every child before
        # exit, so a router kill never leaks `vdt serve` processes.  A
        # second SIGTERM (or SIGINT) skips the wait; children are still
        # reaped by the runner cleanup below.
        nonlocal sigterm_seen
        if stop.is_set():
            return
        if sigterm_seen or state.manager is None:
            stop.set()
            return
        sigterm_seen = True

        async def _drain_and_stop() -> None:
            try:
                await state.manager.stop(drain=True)
            except Exception:  # noqa: BLE001 — drain is best-effort; cleanup still reaps
                logger.exception("fleet drain on SIGTERM failed")
            finally:
                stop.set()

        logger.warning("SIGTERM: draining managed fleet before shutdown")
        asyncio.get_running_loop().create_task(_drain_and_stop())

    def _on_sigint() -> None:
        stop.set()

    import signal

    loop = asyncio.get_running_loop()
    for sig, handler in (
        (signal.SIGTERM, _on_sigterm),
        (signal.SIGINT, _on_sigint),
    ):
        try:
            loop.add_signal_handler(sig, handler)
        except (NotImplementedError, RuntimeError):
            pass
    try:
        await stop.wait()
    finally:
        # _on_cleanup stops the autoscaler and the manager (idempotent
        # if SIGTERM already drained) — all children reaped either way.
        await runner.cleanup()


def cmd_router(args: argparse.Namespace) -> None:
    asyncio.run(_router_async(args))


# ---- bench ----
def parse_ramp(spec: str) -> list[tuple[float, float]]:
    """Parse a piecewise arrival schedule: ``"r1:s1,r2:s2,..."`` →
    ``[(rate_rps, seconds), ...]``.  A zero rate is an idle dwell
    (useful as the settle tail of an autoscale-down assertion).  Shared
    by bench-serve ``--ramp`` and the chaos ramp harness
    (tools/chaos_soak.py)."""
    segments: list[tuple[float, float]] = []
    for piece in spec.split(","):
        piece = piece.strip()
        if not piece:
            continue
        rate_s, sep, dur_s = piece.partition(":")
        try:
            if not sep:
                raise ValueError
            rate, dur = float(rate_s), float(dur_s)
            if rate < 0 or dur <= 0:
                raise ValueError
        except ValueError:
            raise SystemExit(
                f"bad --ramp segment {piece!r}: want RATE:SECONDS with "
                "RATE >= 0 and SECONDS > 0"
            )
        segments.append((rate, dur))
    if not segments:
        raise SystemExit("--ramp needs at least one RATE:SECONDS segment")
    return segments


TENANT_ARRIVALS = ("poisson", "bursty", "closed")


def parse_len_range(spec: str, what: str) -> tuple[int, int]:
    """``"8"`` → (8, 8); ``"32-128"`` → (32, 128) (uniform bounds)."""
    lo_s, sep, hi_s = spec.partition("-")
    try:
        lo = int(lo_s)
        hi = int(hi_s) if sep else lo
        if lo <= 0 or hi < lo:
            raise ValueError
    except ValueError:
        raise SystemExit(
            f"bad tenant {what} length {spec!r}: want INT or LO-HI "
            "with 0 < LO <= HI"
        )
    return lo, hi


def parse_tenants(specs: list[str]) -> list[dict]:
    """Parse repeatable ``--tenant "NAME:key=val,..."`` profiles
    (ISSUE 16's multi-tenant load generator).

    Each profile is an independent traffic source with its own SLO
    class, arrival process, and prompt/output length distributions —
    e.g. interactive chat (bursty short prompts), long-context
    summarization (Poisson long prompts), bulk batch (closed-loop).
    """
    tenants: list[dict] = []
    seen: set[str] = set()
    for spec in specs:
        name, sep, rest = spec.partition(":")
        name = name.strip()
        if not name or not sep:
            raise SystemExit(
                f"bad --tenant {spec!r}: want NAME:key=val,..."
            )
        if name in seen:
            raise SystemExit(f"duplicate --tenant name {name!r}")
        seen.add(name)
        profile = {
            "name": name,
            "slo_class": name,
            "arrival": "poisson",
            "rate": 4.0,
            "burst": 4,
            "concurrency": 4,
            "input": (32, 32),
            "output": (64, 64),
        }
        for kv in rest.split(","):
            kv = kv.strip()
            if not kv:
                continue
            key, sep2, val = kv.partition("=")
            key, val = key.strip(), val.strip()
            if not sep2 or not val:
                raise SystemExit(
                    f"bad --tenant {name!r} entry {kv!r}: want key=val"
                )
            try:
                if key == "class":
                    profile["slo_class"] = val
                elif key == "arrival":
                    if val not in TENANT_ARRIVALS:
                        raise ValueError
                    profile["arrival"] = val
                elif key == "rate":
                    profile["rate"] = float(val)
                    if profile["rate"] <= 0:
                        raise ValueError
                elif key == "burst":
                    profile["burst"] = int(val)
                    if profile["burst"] < 1:
                        raise ValueError
                elif key == "concurrency":
                    profile["concurrency"] = int(val)
                    if profile["concurrency"] < 1:
                        raise ValueError
                elif key in ("input", "output"):
                    profile[key] = parse_len_range(val, key)
                else:
                    raise SystemExit(
                        f"unknown --tenant key {key!r} (want class/"
                        "arrival/rate/burst/concurrency/input/output)"
                    )
            except ValueError:
                raise SystemExit(
                    f"bad --tenant {name!r} value for {key!r}: {val!r}"
                )
        tenants.append(profile)
    return tenants


def _percentiles(xs: list[float]) -> dict:
    xs = sorted(xs)

    def pct(p):
        return round(xs[min(int(len(xs) * p), len(xs) - 1)], 4)

    return {"p50": pct(0.5), "p90": pct(0.9), "p99": pct(0.99)}


async def _bench_disagg_interference(args: argparse.Namespace) -> dict:
    """The ISSUE 15 interference scenario: steady decode streams with
    one long-prompt stream injected once they are warm.  Reports the
    decode streams' client ITL p99 split into before-vs-during the long
    prefill, plus the long prompt's TTFT — the numbers that judge
    mixed vs role-separated pools.  The deployment under test is
    whatever --url fronts; the A/B is two runs against two routers."""
    import aiohttp

    url = args.url.rstrip("/")
    long_len = args.disagg_prompt_len
    n_decode = args.disagg_decode_streams
    arrivals: list[list[float]] = [[] for _ in range(n_decode)]
    long_marks: dict[str, float] = {}
    errors = {"decode": 0, "long": 0}

    async def stream(session, body, on_chunk) -> None:
        async with session.post(
            f"{url}/v1/completions", json=body
        ) as resp:
            resp.raise_for_status()
            async for raw in resp.content:
                line = raw.decode().strip()
                if not line.startswith("data:"):
                    continue
                payload = line[5:].strip()
                if payload == "[DONE]":
                    break
                chunk = json.loads(payload)
                if chunk.get("choices"):
                    on_chunk()

    async def decode_stream(session, i: int) -> None:
        body = {
            "model": args.model or "bench",
            "prompt": [(13 * i + j) % 900 + 1 for j in range(args.input_len)],
            "max_tokens": args.output_len,
            "temperature": 0.0,
            "ignore_eos": True,
            "stream": True,
        }
        try:
            await stream(
                session, body,
                lambda: arrivals[i].append(time.perf_counter()),
            )
        except Exception:  # noqa: BLE001 — bench client: count, move on
            errors["decode"] += 1

    async def long_stream(session) -> None:
        body = {
            "model": args.model or "bench",
            "prompt": [(17 + j) % 900 + 1 for j in range(long_len)],
            "max_tokens": 8,
            "temperature": 0.0,
            "ignore_eos": True,
            "stream": True,
        }

        def first() -> None:
            long_marks.setdefault("first", time.perf_counter())

        long_marks["start"] = time.perf_counter()
        try:
            await stream(session, body, first)
        except Exception:  # noqa: BLE001 — bench client: count, move on
            errors["long"] += 1
        long_marks["end"] = time.perf_counter()

    timeout = aiohttp.ClientTimeout(total=None, sock_read=600)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        decode_tasks = [
            asyncio.create_task(decode_stream(session, i))
            for i in range(n_decode)
        ]
        # Warm: every decode stream steadily producing before the long
        # prompt lands (bounded wait; slow deployments just inject).
        deadline = time.perf_counter() + 30
        while time.perf_counter() < deadline:
            if all(len(a) >= 4 for a in arrivals):
                break
            await asyncio.sleep(0.02)
        await long_stream(session)
        await asyncio.gather(*decode_tasks)

    start = long_marks.get("start", 0.0)
    first = long_marks.get("first")
    end = long_marks.get("end", start)
    window_end = first if first is not None else end
    before: list[float] = []
    during: list[float] = []
    for a in arrivals:
        for prev, cur in zip(a, a[1:]):
            itl = cur - prev
            if cur <= start:
                before.append(itl)
            elif prev >= start and cur <= window_end:
                during.append(itl)
    if len(during) < 3:
        # The prefill window was too short to straddle samples (the
        # role-separated happy case): widen to the whole long stream.
        during = [
            cur - prev
            for a in arrivals
            for prev, cur in zip(a, a[1:])
            if prev >= start and cur <= end
        ] or during
    return {
        "mode": "serve",
        "scenario": "disagg_interference",
        "url": url,
        "decode_streams": n_decode,
        "long_prompt_len": long_len,
        "long_ttft_s": (
            round(first - start, 4) if first is not None else None
        ),
        "decode_itl_ms": {
            "before": (
                {
                    k: round(v * 1e3, 3)
                    for k, v in _percentiles(before).items()
                }
                if before
                else None
            ),
            "during_long_prefill": (
                {
                    k: round(v * 1e3, 3)
                    for k, v in _percentiles(during).items()
                }
                if during
                else None
            ),
        },
        "errors": dict(errors),
    }


async def _bench_serve_async(args: argparse.Namespace) -> dict:
    """Drive a LIVE server with concurrent streaming completions and
    measure TTFT/ITL/throughput as the CLIENT sees them over SSE, then
    cross-check against the server's own /metrics histograms (the
    serving metrics BASELINE.md tracks are HTTP-path numbers, not
    engine-loop numbers)."""
    import aiohttp

    if getattr(args, "disagg", False):
        return await _bench_disagg_interference(args)

    url = args.url.rstrip("/")
    # The closed-loop semaphore is unused by the tenant path (each
    # profile carries its own concurrency), so tenant-only invocations
    # may omit --concurrency entirely.
    sem = asyncio.Semaphore(getattr(args, "concurrency", None) or 1)
    ttfts: list[float] = []
    itls: list[float] = []
    out_tokens = 0
    # Overload accounting (ISSUE 8): sheds are OUTCOMES, not latency
    # samples — a 429'd or timed-out request must never pollute the
    # percentiles of the requests the server actually served.
    request_rate = getattr(args, "request_rate", None)
    # "unavailable" = 503s (breaker rejections / exhausted retry
    # budget with no live target) — a distinct outcome from generic
    # transport errors so a resilience A/B can read them apart.
    counts = {
        "completed": 0,
        "rejected": 0,
        "timed_out": 0,
        "unavailable": 0,
        "errors": 0,
    }

    # Piecewise rate sweep (ISSUE 13): open-loop segments with
    # per-segment accounting, the workload an autoscaler acceptance run
    # (and the chaos ramp harness) is judged against.
    ramp = getattr(args, "ramp", None)
    ramp_segments = parse_ramp(ramp) if ramp else None
    if ramp_segments and request_rate is not None:
        raise SystemExit("--ramp and --request-rate are mutually exclusive")

    # Reproducible stochastic load (ISSUE 16 satellite): ONE seed
    # drives every arrival process and length distribution, and is
    # recorded in the results JSON, so two A/B runs offer the same
    # workload down to the per-request token counts.
    import random

    seed = int(getattr(args, "seed", None) or 12345)

    # Multi-tenant profiles (ISSUE 16): independent concurrent traffic
    # sources, each with its own class/arrivals/length distributions.
    tenant_specs = getattr(args, "tenants", None)
    tenants = parse_tenants(tenant_specs) if tenant_specs else None
    if tenants and (request_rate is not None or ramp_segments):
        raise SystemExit(
            "--tenant is mutually exclusive with --request-rate/--ramp"
        )
    tenant_seconds = float(getattr(args, "tenant_seconds", None) or 10.0)
    tenant_runs: list[dict] = [
        {
            "profile": p,
            # Per-tenant NAMED streams: adding or reordering a tenant
            # can't shift another tenant's arrival or length draws.
            "arr_rng": random.Random(f"{seed}:{p['name']}:arrival"),
            "len_rng": random.Random(f"{seed}:{p['name']}:length"),
            "offered": 0,
            "completed": 0,
            "rejected": 0,
            "timed_out": 0,
            "unavailable": 0,
            "errors": 0,
            "ttfts": [],
            "itls": [],
        }
        for p in (tenants or ())
    ]
    seg_stats: list[dict] = [
        {
            "rate_rps": rate,
            "seconds": dur,
            "offered": 0,
            "completed": 0,
            "rejected": 0,
            "timed_out": 0,
            "unavailable": 0,
            "errors": 0,
            "ttfts": [],
            "itls": [],
        }
        for rate, dur in (ramp_segments or ())
    ]

    # Per-class request mix (ISSUE 12): "name[:weight]" entries expand
    # into a deterministic assignment pattern so the same command line
    # always produces the same mix.
    class_pattern: list[str] = []
    for entry in getattr(args, "slo_classes", None) or ():
        name, _, weight = entry.partition(":")
        try:
            w = max(int(weight), 1) if weight else 1
        except ValueError:
            raise SystemExit(
                f"--slo-class weight must be an integer: {entry!r}"
            )
        class_pattern.extend([name] * w)
    per_class: dict[str, dict] = {
        cls: {"ttfts": [], "itls": [], "completed": 0, "shed": 0}
        for cls in class_pattern
    }
    for t in tenant_runs:
        # Tenant classes join the per-class readout (several tenants
        # may share one SLO class — the server judges by class).
        per_class.setdefault(
            t["profile"]["slo_class"],
            {"ttfts": [], "itls": [], "completed": 0, "shed": 0},
        )

    def class_for(i: int) -> str | None:
        if not class_pattern:
            return None
        return class_pattern[i % len(class_pattern)]

    def parse_summed_metrics(text: str) -> dict:
        want = {
            "vllm:time_to_first_token_seconds_sum",
            "vllm:time_to_first_token_seconds_count",
            "vllm:time_per_output_token_seconds_sum",
            "vllm:time_per_output_token_seconds_count",
            "vllm:generation_tokens_total",
            "vllm:pipeline_breaks_total",
            "vllm:requests_rejected_total",
            # Router affinity A/B (ISSUE 10): the hit-rate delta between
            # --shared-prefix-len runs under affinity vs round_robin
            # routing is the placement-quality signal.  Scraping the
            # router sums these across replicas (the merged exposition
            # keeps per-replica labels; the sum is what A/B needs).
            "vllm:prefix_cache_queries_total",
            "vllm:prefix_cache_hits_total",
            # Fleet sentinel (ISSUE 20): alert count summed across
            # kinds, plus the burn-rate high-water gauge.
            "vdt_router:alerts_total",
            "vdt_router:fleet_slo_burn_rate_peak",
        }
        # Router resilience counters (ISSUE 19): kept split by outcome
        # label so retries granted/denied and hedge outcomes report as
        # separate columns.
        labeled = {
            "vdt_router:retries_total",
            "vdt_router:hedges_total",
            "vdt_router:breaker_rejections_total",
        }
        import re

        out = {}
        for line in text.splitlines():
            if line.startswith("#"):
                continue
            parts = line.split()
            if len(parts) != 2:
                continue
            key = parts[0].split("{")[0]
            if key in want:
                out[key] = out.get(key, 0.0) + float(parts[1])
            elif key in labeled:
                m = re.search(r'outcome="([^"]*)"', parts[0])
                k = f"{key}|{m.group(1)}" if m else key
                out[k] = out.get(k, 0.0) + float(parts[1])
        return out

    # Per-class server counters (ISSUE 12): deltas of the labeled SLO
    # families over the run window give server-judged attainment; the
    # merged router exposition sums replicas per class, which is what
    # the fleet readout needs.
    _SLO_FAMILIES = {
        "vllm:slo_requests_total",
        "vllm:goodput_requests_total",
        "vllm:slo_ttft_attained_total",
        "vllm:slo_itl_attained_total",
    }

    def parse_slo_metrics(text: str) -> dict:
        import re

        out: dict[str, dict[str, float]] = {}
        for line in text.splitlines():
            if line.startswith("#"):
                continue
            parts = line.split()
            if len(parts) != 2 or parts[0].split("{")[0] not in _SLO_FAMILIES:
                continue
            m = re.search(r'slo_class="([^"]*)"', parts[0])
            cls = m.group(1) if m else "default"
            fam = out.setdefault(parts[0].split("{")[0], {})
            fam[cls] = fam.get(cls, 0.0) + float(parts[1])
        return out

    async def scrape_metrics(session) -> tuple[dict, dict]:
        """ONE /metrics fetch parsed for both the summed throughput
        families and the per-class SLO families — a second fetch would
        double the scrape load the bench puts on the server it is
        measuring."""
        try:
            async with session.get(f"{url}/metrics") as r:
                text = await r.text()
        except Exception:  # noqa: BLE001 — metrics are optional
            return {}, {}
        return (
            parse_summed_metrics(text),
            parse_slo_metrics(text) if per_class else {},
        )

    shared_prefix_len = getattr(args, "shared_prefix_len", 0) or 0
    shared_prefix = [(7 * j) % 900 + 1 for j in range(shared_prefix_len)]

    async def drive_one(
        session,
        i: int,
        seg: dict | None = None,
        ten: dict | None = None,
    ) -> None:
        nonlocal out_tokens
        if ten is not None:
            p = ten["profile"]
            input_len = ten["len_rng"].randint(*p["input"])
            output_len = ten["len_rng"].randint(*p["output"])
            slo_class = p["slo_class"]
        else:
            input_len, output_len = args.input_len, args.output_len
            slo_class = class_for(i)
        prompt = shared_prefix + [
            (13 * i + j) % 900 + 1 for j in range(input_len)
        ]
        body = {
            "model": args.model or "bench",
            "prompt": prompt,
            "max_tokens": output_len,
            "temperature": 0.0,
            "ignore_eos": True,
            "stream": True,
            # Final chunk carries usage: tokens are counted from the
            # stream itself, not assumed = output_len (a truncated or
            # errored stream must not overstate throughput).
            "stream_options": {"include_usage": True},
        }
        if getattr(args, "deadline_ms", None):
            body["deadline_ms"] = args.deadline_ms
        if slo_class is not None:
            body["slo_class"] = slo_class
        t0 = time.perf_counter()
        chunk_times: list[float] = []
        got_tokens = 0
        finish_reason = None
        try:
            async with session.post(
                f"{url}/v1/completions", json=body
            ) as resp:
                if resp.status == 429:
                    # Load shed: an accounted outcome, not an error and
                    # not a latency sample.
                    counts["rejected"] += 1
                    if seg is not None:
                        seg["rejected"] += 1
                    if ten is not None:
                        ten["rejected"] += 1
                    await resp.read()
                    return
                if resp.status == 503:
                    # Breaker rejection / no routable replica (ISSUE
                    # 19): its own outcome column, apart from generic
                    # transport errors.
                    counts["unavailable"] += 1
                    if seg is not None:
                        seg["unavailable"] += 1
                    if ten is not None:
                        ten["unavailable"] += 1
                    await resp.read()
                    return
                resp.raise_for_status()
                async for raw in resp.content:
                    line = raw.decode().strip()
                    if not line.startswith("data:"):
                        continue
                    payload = line[5:].strip()
                    if payload == "[DONE]":
                        break
                    chunk = json.loads(payload)
                    usage = chunk.get("usage")
                    if usage:
                        got_tokens = usage.get(
                            "completion_tokens", got_tokens
                        )
                    choices = chunk.get("choices") or []
                    choice = choices[0] if choices else None
                    if choice is not None and choice.get("finish_reason"):
                        finish_reason = choice["finish_reason"]
                    # Token-bearing chunks: anything before the finish
                    # marker ("text" may be empty when the server runs
                    # without a tokenizer, e.g. dummy-weight benches).
                    # A request whose whole completion lands in ONE
                    # finish-bearing chunk (stream starved while the
                    # engine raced ahead) still delivered its first
                    # token THEN — count it, or cold requests silently
                    # vanish from the client TTFT distribution.
                    if choice is not None and (
                        not choice.get("finish_reason")
                        or not chunk_times
                    ):
                        chunk_times.append(time.perf_counter())
        except Exception:  # noqa: BLE001 — bench client: count, move on
            counts["errors"] += 1
            if seg is not None:
                seg["errors"] += 1
            if ten is not None:
                ten["errors"] += 1
            return
        if finish_reason in ("timeout", "overloaded"):
            # Deadline/pressure shed mid-generation: partial output —
            # keep it out of the completed-latency distribution too.
            counts["timed_out"] += 1
            if seg is not None:
                seg["timed_out"] += 1
            if ten is not None:
                ten["timed_out"] += 1
            if slo_class is not None:
                per_class[slo_class]["shed"] += 1
            return
        counts["completed"] += 1
        if seg is not None:
            seg["completed"] += 1
        if ten is not None:
            ten["completed"] += 1
        if slo_class is not None:
            per_class[slo_class]["completed"] += 1
        if chunk_times:
            ttft = chunk_times[0] - t0
            ttfts.append(ttft)
            out_tokens += got_tokens
            itl = None
            if got_tokens > 1:
                # Client-side per-token interval: tokens arrive in fused
                # bursts, so spread the span over the tokens after the
                # first (the serving ITL definition).
                span = chunk_times[-1] - chunk_times[0]
                itl = span / (got_tokens - 1)
                itls.append(itl)
            if seg is not None:
                seg["ttfts"].append(ttft)
                if itl is not None:
                    seg["itls"].append(itl)
            if ten is not None:
                ten["ttfts"].append(ttft)
                if itl is not None:
                    ten["itls"].append(itl)
            if slo_class is not None:
                per_class[slo_class]["ttfts"].append(ttft)
                if itl is not None:
                    per_class[slo_class]["itls"].append(itl)

    async def one(session, i: int, seg: dict | None = None) -> None:
        if request_rate is not None or seg is not None:
            # Open loop: arrivals don't wait for departures — offered
            # load is what the operator configured, not what the
            # server can absorb.
            await drive_one(session, i, seg)
        else:
            async with sem:
                await drive_one(session, i)

    timeout = aiohttp.ClientTimeout(total=None, sock_read=600)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        before, slo_before = await scrape_metrics(session)
        t0 = time.perf_counter()
        if tenant_runs:
            # Multi-tenant: every profile is an independent concurrent
            # traffic source against the same deployment for a fixed
            # wall-clock window — the per-class contention workload the
            # QoS control plane is judged on.
            import itertools

            t_end = time.perf_counter() + tenant_seconds
            next_i = itertools.count()

            async def tenant_open_loop(ten: dict) -> None:
                p = ten["profile"]
                rng = ten["arr_rng"]
                n_burst = p["burst"] if p["arrival"] == "bursty" else 1
                # Burst epochs arrive Poisson at rate/burst so the
                # OFFERED rate equals the configured rate either way —
                # bursty just concentrates it into spikes.
                epoch_rate = p["rate"] / n_burst
                tasks = []
                while time.perf_counter() < t_end:
                    for _ in range(n_burst):
                        ten["offered"] += 1
                        tasks.append(
                            asyncio.create_task(
                                drive_one(session, next(next_i), ten=ten)
                            )
                        )
                    remaining = t_end - time.perf_counter()
                    if remaining <= 0:
                        break
                    await asyncio.sleep(
                        min(rng.expovariate(epoch_rate), remaining)
                    )
                await asyncio.gather(*tasks)

            async def tenant_closed_loop(ten: dict) -> None:
                async def worker() -> None:
                    while time.perf_counter() < t_end:
                        ten["offered"] += 1
                        await drive_one(session, next(next_i), ten=ten)

                await asyncio.gather(
                    *(
                        worker()
                        for _ in range(ten["profile"]["concurrency"])
                    )
                )

            await asyncio.gather(
                *(
                    tenant_closed_loop(t)
                    if t["profile"]["arrival"] == "closed"
                    else tenant_open_loop(t)
                    for t in tenant_runs
                )
            )
        elif ramp_segments is not None:
            rng = random.Random(seed)  # reproducible arrival process
            tasks = []
            i = 0
            for seg in seg_stats:
                seg_t0 = time.perf_counter()
                rate, dur = seg["rate_rps"], seg["seconds"]
                while True:
                    remaining = dur - (time.perf_counter() - seg_t0)
                    if remaining <= 0:
                        break
                    if rate <= 0:
                        # Idle dwell: no arrivals, just hold the clock
                        # (the settle tail of a scale-down assertion).
                        await asyncio.sleep(remaining)
                        break
                    seg["offered"] += 1
                    tasks.append(
                        asyncio.create_task(one(session, i, seg))
                    )
                    i += 1
                    await asyncio.sleep(
                        min(rng.expovariate(rate), remaining)
                    )
            await asyncio.gather(*tasks)
        elif request_rate is not None:
            rng = random.Random(seed)  # reproducible arrival process
            tasks = []
            for i in range(args.num_prompts):
                tasks.append(asyncio.create_task(one(session, i)))
                await asyncio.sleep(rng.expovariate(request_rate))
            await asyncio.gather(*tasks)
        else:
            await asyncio.gather(
                *(one(session, i) for i in range(args.num_prompts))
            )
        elapsed = time.perf_counter() - t0
        after, slo_after = await scrape_metrics(session)

    if tenant_runs:
        total_requests = sum(t["offered"] for t in tenant_runs)
    elif ramp_segments is not None:
        total_requests = sum(s["offered"] for s in seg_stats)
    else:
        total_requests = args.num_prompts
    result = {
        "mode": "serve",
        "url": url,
        "num_prompts": total_requests,
        "concurrency": (
            args.concurrency
            if request_rate is None
            and ramp_segments is None
            and not tenant_runs
            else None
        ),
        # Tenant runs carry per-profile length distributions instead of
        # the global fixed lengths.
        "input_len": None if tenant_runs else getattr(args, "input_len", None),
        "output_len": None if tenant_runs else getattr(args, "output_len", None),
        "elapsed_s": round(elapsed, 3),
        "output_tokens_per_s": round(out_tokens / elapsed, 1),
        "requests_per_s": round(total_requests / elapsed, 3),
        # Latency percentiles cover COMPLETED requests only; sheds are
        # reported in outcomes below.
        "ttft_s": _percentiles(ttfts) if ttfts else None,
        "itl_ms": (
            {k: round(v * 1e3, 3) for k, v in _percentiles(itls).items()}
            if itls
            else None
        ),
        "outcomes": dict(counts),
    }
    if request_rate is not None:
        result["offered_rps"] = request_rate
        result["arrival_process"] = "poisson"
    if request_rate is not None or ramp_segments is not None or tenant_runs:
        # The stochastic-load modes record their seed so a reported
        # result names the exact workload that produced it.
        result["seed"] = seed
    if tenant_runs:
        result["arrival_process"] = "multi_tenant"
        result["tenant_seconds"] = tenant_seconds
        result["tenants"] = {}
        for t in tenant_runs:
            p = t["profile"]
            entry: dict = {
                "class": p["slo_class"],
                "arrival": p["arrival"],
                "input": list(p["input"]),
                "output": list(p["output"]),
                "offered": t["offered"],
                "completed": t["completed"],
                "rejected": t["rejected"],
                "timed_out": t["timed_out"],
                "unavailable": t["unavailable"],
                "errors": t["errors"],
                "ttft_s": (
                    _percentiles(t["ttfts"]) if t["ttfts"] else None
                ),
                "itl_ms": (
                    {
                        k: round(v * 1e3, 3)
                        for k, v in _percentiles(t["itls"]).items()
                    }
                    if t["itls"]
                    else None
                ),
            }
            if p["arrival"] == "closed":
                entry["concurrency"] = p["concurrency"]
            else:
                entry["rate_rps"] = p["rate"]
                if p["arrival"] == "bursty":
                    entry["burst"] = p["burst"]
            result["tenants"][p["name"]] = entry
    if ramp_segments is not None:
        # Per-segment readout: the rate sweep with each segment's
        # client-side percentiles and shed accounting — what the
        # autoscaler acceptance run (and the chaos ramp harness) judge.
        result["arrival_process"] = "poisson_ramp"
        result["ramp"] = [
            {
                "rate_rps": s["rate_rps"],
                "seconds": s["seconds"],
                "offered": s["offered"],
                "completed": s["completed"],
                "rejected": s["rejected"],
                "timed_out": s["timed_out"],
                "unavailable": s["unavailable"],
                "errors": s["errors"],
                "ttft_s": _percentiles(s["ttfts"]) if s["ttfts"] else None,
                "itl_ms": (
                    {
                        k: round(v * 1e3, 3)
                        for k, v in _percentiles(s["itls"]).items()
                    }
                    if s["itls"]
                    else None
                ),
            }
            for s in seg_stats
        ]
    if per_class:
        # Per-class attainment readout (ISSUE 12): client percentiles
        # plus the server's own goodput judgment over the run window.
        def slo_delta(family: str, cls: str) -> float:
            return (slo_after.get(family) or {}).get(cls, 0.0) - (
                slo_before.get(family) or {}
            ).get(cls, 0.0)

        result["per_class"] = {}
        for cls, st in per_class.items():
            entry: dict = {
                "completed": st["completed"],
                "shed": st["shed"],
                "ttft_s": (
                    _percentiles(st["ttfts"]) if st["ttfts"] else None
                ),
                "itl_ms": (
                    {
                        k: round(v * 1e3, 3)
                        for k, v in _percentiles(st["itls"]).items()
                    }
                    if st["itls"]
                    else None
                ),
            }
            reqs = slo_delta("vllm:slo_requests_total", cls)
            if reqs > 0:
                entry["server_goodput"] = slo_delta(
                    "vllm:goodput_requests_total", cls
                )
                entry["server_goodput_ratio"] = round(
                    entry["server_goodput"] / reqs, 4
                )
                entry["server_ttft_attain_ratio"] = round(
                    slo_delta("vllm:slo_ttft_attained_total", cls) / reqs,
                    4,
                )
                entry["server_itl_attain_ratio"] = round(
                    slo_delta("vllm:slo_itl_attained_total", cls) / reqs,
                    4,
                )
            result["per_class"][cls] = entry
    if (
        itls
        and request_rate is None
        and ramp_segments is None
        and not tenant_runs
    ):
        # The dispatch tax as the CLIENT sees it (ISSUE 7): throughput
        # implied by the p50 inter-token pace at this concurrency minus
        # the wall-clock throughput.  ~0 when the driver holds the p50
        # pace for the whole run.  Closed-loop only (open-loop
        # concurrency is not a constant).
        itl_p50 = _percentiles(itls)["p50"]
        if itl_p50 > 0:
            result["wall_vs_p50_gap"] = round(
                args.concurrency / itl_p50 - result["output_tokens_per_s"],
                1,
            )
    if after:
        # Server-side cross-check: deltas of the Prometheus histograms
        # over the run window.
        def delta(key):
            return after.get(key, 0.0) - before.get(key, 0.0)

        ttft_n = delta("vllm:time_to_first_token_seconds_count")
        itl_n = delta("vllm:time_per_output_token_seconds_count")
        result["server_metrics"] = {
            "ttft_mean_s": round(
                delta("vllm:time_to_first_token_seconds_sum")
                / max(ttft_n, 1),
                4,
            ),
            "itl_mean_ms": round(
                delta("vllm:time_per_output_token_seconds_sum")
                / max(itl_n, 1)
                * 1e3,
                3,
            ),
            "generation_tokens": delta("vllm:generation_tokens_total"),
            # Cross-check: the server's own 429 count over the window
            # should match the client's rejected outcome.
            "requests_rejected": delta("vllm:requests_rejected_total"),
        }
        # Resilience columns (ISSUE 19): present whenever the scrape
        # target exposes the router families (i.e. --url points at a
        # router, not a bare replica).
        if any(k.startswith("vdt_router:") for k in after):
            result["server_metrics"]["router_resilience"] = {
                "retries_granted": int(
                    delta("vdt_router:retries_total|granted")
                ),
                "retries_denied": int(
                    delta("vdt_router:retries_total|denied")
                ),
                "hedges": int(
                    sum(
                        delta(k)
                        for k in set(after) | set(before)
                        if k.startswith("vdt_router:hedges_total|")
                        and not k.endswith("|denied")
                    )
                ),
                "hedges_denied": int(
                    delta("vdt_router:hedges_total|denied")
                ),
                "breaker_rejections": int(
                    delta("vdt_router:breaker_rejections_total")
                ),
            }
            # Sentinel columns (ISSUE 20): alerts fired over the run
            # window and the burn-rate high-water mark (a gauge — the
            # end-of-run value IS the peak, no delta).
            result["server_metrics"]["alerts_fired"] = int(
                delta("vdt_router:alerts_total")
            )
            result["server_metrics"]["peak_fleet_slo_burn_rate"] = round(
                after.get("vdt_router:fleet_slo_burn_rate_peak", 0.0), 3
            )
        queries = delta("vllm:prefix_cache_queries_total")
        hits = delta("vllm:prefix_cache_hits_total")
        if queries > 0:
            # The affinity A/B readout: run once with the router in
            # affinity mode and once in round_robin; the shared-prefix
            # workload should show a higher hit rate under affinity.
            result["server_metrics"]["prefix_cache_hit_rate"] = round(
                hits / queries, 4
            )
            result["server_metrics"]["prefix_cache_hits"] = hits
        if shared_prefix_len:
            result["shared_prefix_len"] = shared_prefix_len
        # Engine-side pipeline flushes over the run window (0 = the
        # async scheduler never had to drain and re-plan mid-run).
        result["stall_windows"] = int(
            delta("vllm:pipeline_breaks_total")
        )
    return result


def cmd_bench(args: argparse.Namespace) -> None:
    import time

    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    from vllm_distributed_tpu.sampling_params import SamplingParams

    if args.mode == "serve":
        print(json.dumps(asyncio.run(_bench_serve_async(args))))
        return

    engine_args = EngineArgs.from_cli_args(args)
    engine = LLMEngine.from_engine_args(engine_args)
    sp = SamplingParams(
        temperature=0.0, max_tokens=args.output_len, ignore_eos=True
    )
    vocab = engine.config.model_config.get_vocab_size()
    prompts = [
        [(13 * i + j) % (vocab - 10) + 1 for j in range(args.input_len)]
        for i in range(args.num_prompts)
    ]
    if args.mode == "latency":
        # One request at a time; report per-request latency.
        lat = []
        for i, p in enumerate(prompts[: min(8, len(prompts))]):
            t0 = time.perf_counter()
            engine.add_request(f"b{i}", prompt_token_ids=p, sampling_params=sp)
            while engine.has_unfinished_requests():
                engine.step()
            lat.append(time.perf_counter() - t0)
        lat.sort()
        print(
            json.dumps(
                {
                    "mode": "latency",
                    "p50_s": round(lat[len(lat) // 2], 4),
                    "mean_s": round(sum(lat) / len(lat), 4),
                    "output_len": args.output_len,
                }
            )
        )
    else:
        for i, p in enumerate(prompts):
            engine.add_request(f"b{i}", prompt_token_ids=p, sampling_params=sp)
        t0 = time.perf_counter()
        done = 0
        while engine.has_unfinished_requests():
            done += sum(1 for o in engine.step() if o.finished)
        elapsed = time.perf_counter() - t0
        total_tokens = args.num_prompts * (args.input_len + args.output_len)
        print(
            json.dumps(
                {
                    "mode": "throughput",
                    "requests_per_s": round(args.num_prompts / elapsed, 3),
                    "total_tokens_per_s": round(total_tokens / elapsed, 1),
                    "output_tokens_per_s": round(
                        args.num_prompts * args.output_len / elapsed, 1
                    ),
                    "elapsed_s": round(elapsed, 2),
                }
            )
        )


# ---- collect-env ----
def cmd_collect_env(args: argparse.Namespace) -> None:
    import platform

    info = {
        "vdt": __version__,
        "python": sys.version.split()[0],
        "platform": platform.platform(),
    }
    for mod in ("jax", "jaxlib", "flax", "numpy", "transformers", "aiohttp"):
        try:
            info[mod] = __import__(mod).__version__
        except Exception:  # noqa: BLE001
            info[mod] = "unavailable"
    try:
        import jax

        info["backend"] = jax.default_backend()
        info["devices"] = [str(d) for d in jax.devices()]
    except Exception as e:  # noqa: BLE001
        info["backend"] = f"error: {e}"
    from vllm_distributed_tpu import envs

    info["env"] = {
        k: str(v())
        for k, v in envs.environment_variables.items()
        if k in os.environ
    }
    print(json.dumps(info, indent=2))


# ---- run-batch ----
def cmd_run_batch(args: argparse.Namespace) -> None:
    """Each input line: {"custom_id": ..., "body": {"prompt" | "messages",
    sampling fields}} — the OpenAI batch-file shape (launch.py:25)."""
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    from vllm_distributed_tpu.entrypoints.openai.protocol import (
        CompletionRequest,
    )
    from vllm_distributed_tpu.sampling_params import SamplingParams

    engine_args = EngineArgs.from_cli_args(args)
    engine = LLMEngine.from_engine_args(engine_args)
    max_len = engine.config.model_config.max_model_len

    requests = []
    with open(args.input_file) as f:
        for line in f:
            if line.strip():
                requests.append(json.loads(line))
    for i, item in enumerate(requests):
        body = item.get("body", item)
        req = CompletionRequest(**{
            k: v for k, v in body.items()
            if k in CompletionRequest.model_fields
        })
        prompt = body.get("prompt", "")
        sp = req.to_sampling_params(max_len // 2, is_chat=False)
        if isinstance(prompt, list) and prompt and isinstance(prompt[0], int):
            engine.add_request(
                str(item.get("custom_id", i)),
                prompt_token_ids=prompt,
                sampling_params=sp,
            )
        else:
            engine.add_request(
                str(item.get("custom_id", i)),
                prompt=str(prompt),
                sampling_params=sp,
            )
    results = {}
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                results[out.request_id] = {
                    "custom_id": out.request_id,
                    "response": {
                        "text": out.outputs[0].text,
                        "token_ids": out.outputs[0].token_ids,
                        "finish_reason": out.outputs[0].finish_reason,
                    },
                }
    with open(args.output_file, "w") as f:
        for item in requests:
            rid = str(item.get("custom_id", requests.index(item)))
            f.write(json.dumps(results.get(rid, {"custom_id": rid})) + "\n")
    logger.info("wrote %d results to %s", len(results), args.output_file)


# ---- client commands ----
def cmd_client(args: argparse.Namespace, chat: bool) -> None:
    import urllib.request

    if chat:
        body = {
            "model": args.model or "",
            "messages": [{"role": "user", "content": args.prompt or "hi"}],
        }
        path = "/v1/chat/completions"
    else:
        body = {"model": args.model or "", "prompt": args.prompt or "hi"}
        path = "/v1/completions"
    req = urllib.request.Request(
        args.url + path,
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req) as resp:
        out = json.loads(resp.read())
    if chat:
        print(out["choices"][0]["message"]["content"])
    else:
        print(out["choices"][0]["text"])


def main(argv: list[str] | None = None) -> None:
    argv = _expand_env(argv if argv is not None else sys.argv[1:])
    args = make_parser().parse_args(argv)
    if args.command == "serve":
        cmd_serve(args)
    elif args.command == "remote":
        cmd_remote(args)
    elif args.command == "router":
        cmd_router(args)
    elif args.command == "bench":
        cmd_bench(args)
    elif args.command == "collect-env":
        cmd_collect_env(args)
    elif args.command == "run-batch":
        cmd_run_batch(args)
    elif args.command == "chat":
        cmd_client(args, chat=True)
    elif args.command == "complete":
        cmd_client(args, chat=False)


if __name__ == "__main__":
    main()
