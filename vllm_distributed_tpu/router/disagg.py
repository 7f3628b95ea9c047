"""Disaggregated prefill/decode hand-off orchestration (ISSUE 15).

DistServe/Splitwise role separation at the router: long prompts are
prefetched on a **prefill-pool** replica (``X-VDT-Disagg: prefill`` hop
→ the replica runs prefill plus the first sampled token and HOLDS its
KV pages for export), then the router streams the pages in per-layer
chunks from the prefill replica's ``/internal/kv/export`` to a
decode-pool replica's ``/internal/kv`` and resumes the request there
over the PR 8 ``/internal/resume`` path — the imported pages attach as
computed, so decode continues bit-identically while the decode pool's
ITL never shares a mesh with the compute-bound prefill.

Failure semantics (the chaos_soak ``--disagg`` contract): any failure
on the prefill side — replica SIGKILLed mid-export, checksum mismatch,
transfer aborted — falls back to the PR 8 recompute-resume on the
decode pool with whatever the journal already holds.  Planned hand-offs
AND their fallbacks are the happy path of role separation: they count
in ``vdt_router:handoffs``, never in ``vdt_router:migrations``, and
never burn ``VDT_ROUTER_MAX_MIGRATIONS`` budget.  Only a failure of the
decode-side continuation itself enters the normal migration loop.

Below the ``VDT_DISAGG_MIN_PROMPT_TOKENS`` crossover (not measured on
the chip; set from a CPU sweep before the round, ROADMAP.md D4) the
hand-off is not planned at all and the request serves on the decode/mixed pool exactly as today; a fleet
with no prefill-role replica never takes this path.
"""

from __future__ import annotations

import asyncio
import json
from dataclasses import dataclass

from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.router.resilience import ResilienceManager
from vllm_distributed_tpu.tracing import get_tracer

logger = init_logger(__name__)

# Test seams for the chaos harness (tools/chaos_soak.py --disagg):
# `_test_before_transfer` is awaited after the prefill stream yields
# its first token but before any export chunk moves;
# `_test_after_chunk` after each export→import chunk round trip (chunk
# index passed); `_test_after_chunk_failure` after a chunk round trip
# FAILS and is about to be resumed (failure count passed) — the
# partition soak heals the link exactly there, so "one lost chunk,
# then resume" is deterministic regardless of event-loop contention.
# Together they make "SIGKILL the prefill replica mid-hand-off /
# mid-export" deterministic scenarios instead of races.
_test_before_transfer = None
_test_after_chunk = None
_test_after_chunk_failure = None


def _emit_handoff_event(state, outcome: str, **attrs) -> None:
    """Mirror a hand-off outcome into the sentinel timeline (ISSUE 20);
    best-effort — the data path never blocks on observability."""
    sentinel = getattr(state, "sentinel", None)
    if sentinel is None:
        return
    try:
        sentinel.emit("router_handoff", outcome=outcome, **attrs)
    except Exception:  # noqa: BLE001 — observability must not break the stream
        logger.exception("sentinel router_handoff event failed")


@dataclass
class HandoffPlan:
    est_prompt_tokens: int


def estimate_prompt_tokens(journal) -> int:
    """Crossover estimate from what the router can see pre-placement:
    exact for token-id prompts, ~4 chars/token for text/chat."""
    text, ids = journal.affinity_source()
    if ids:
        return len(ids)
    return len(text or "") // 4


def plan_handoff(state, journal, keys) -> HandoffPlan | None:
    """Decide whether this stream takes the prefill→decode hand-off
    path: single-choice streaming request whose (estimated) prompt is
    at/above the crossover, with BOTH pools routable.  Everything else
    places exactly as before."""
    if not journal.stream or len(journal.choices) != 1:
        return None
    mt = journal.body.get("max_tokens")
    try:
        if mt is not None and int(mt) <= 1:
            return None  # finishes at the first token either way
    except (TypeError, ValueError):
        return None
    have_prefill = any(
        r.routable and r.role == "prefill" for r in state.pool.replicas
    )
    have_decode = any(
        r.routable and r.role != "prefill" for r in state.pool.replicas
    )
    if not (have_prefill and have_decode):
        return None
    est = estimate_prompt_tokens(journal)
    if est < state.disagg_min_prompt_tokens:
        return None
    return HandoffPlan(est_prompt_tokens=est)


async def _post_json(
    state,
    url: str,
    payload: dict,
    *,
    endpoint: str = "kv",
    replica_id: str | None = None,
    hedge: bool = False,
) -> tuple[int, dict]:
    """One bounded router→replica control POST; returns (status, body).
    Routed through the resilience manager (ISSUE 19): breaker-gated,
    adaptive-deadline'd, and — for idempotent export pulls — hedged.
    With no resilience envs set the manager is a pure passthrough."""
    import aiohttp

    rz = getattr(state, "resilience", None) or ResilienceManager.noop()
    timeout = aiohttp.ClientTimeout(
        total=state.read_timeout, connect=state.connect_timeout
    )

    async def fetch() -> tuple[int, dict]:
        async with await rz.request(
            state.session,
            "POST",
            url,
            endpoint=endpoint,
            replica_id=replica_id,
            json=payload,
            timeout=timeout,
        ) as resp:
            try:
                body = await resp.json()
            except Exception:  # noqa: BLE001 — a non-JSON error body still carries the status
                body = {}
            return resp.status, body or {}

    if hedge:
        return await rz.hedged(endpoint, replica_id, fetch)
    return await fetch()


async def _transfer_pages(
    state, prefill_url: str, decode_url: str, kv_handle: str,
    prompt_token_ids: list[int],
    *,
    prefill_id: str | None = None,
    decode_id: str | None = None,
) -> int:
    """Stream the held pages prefill→decode in per-layer chunks.
    Returns the adopted token count (0 = nothing transferred, e.g. the
    decode pool declined).  Raises on any wire/checksum/commit failure
    — the caller aborts and falls back to recompute.

    With ``VDT_ROUTER_KV_CHUNK_RETRIES > 0`` the transfer is resumable
    (ISSUE 19): a lost chunk round-trip re-begins with ``resume_from``,
    learns which checksummed layers actually landed decode-side, and
    re-pulls only the missing ones.  Each resume draws one token from
    the retry budget; only an exhausted budget or chunk-retry cap falls
    back to recompute."""
    rz = getattr(state, "resilience", None) or ResilienceManager.noop()
    kv_url = f"{decode_url}/internal/kv"
    status, begin = await _post_json(
        state,
        kv_url,
        {"op": "begin", "prompt_token_ids": prompt_token_ids},
        endpoint="kv_import",
        replica_id=decode_id,
    )
    if status != 200:
        raise RuntimeError(f"kv import begin failed: HTTP {status}")
    transfer_id = begin.get("transfer_id")
    if not transfer_id:
        return 0  # nothing importable decode-side; recompute is correct
    chunk_layers = max(int(state.disagg_chunk_layers), 1)
    chunk_retries = max(int(rz.cfg.kv_chunk_retries), 0)
    failures = 0
    need_sync = False
    try:
        layer = 0
        num_layers = None
        chunk_idx = 0
        while num_layers is None or layer < num_layers:
            try:
                if need_sync:
                    status, rebegin = await _post_json(
                        state,
                        kv_url,
                        {
                            "op": "begin",
                            "prompt_token_ids": prompt_token_ids,
                            "resume_from": transfer_id,
                        },
                        endpoint="kv_import",
                        replica_id=decode_id,
                    )
                    if (
                        status != 200
                        or rebegin.get("transfer_id") != transfer_id
                    ):
                        # Reservation gone (TTL, scatter-failure
                        # abort): nothing to resume onto — recompute.
                        raise RuntimeError(
                            "kv transfer resume rejected: "
                            f"HTTP {status}"
                        )
                    received = {
                        int(i) for i in rebegin.get("received") or ()
                    }
                    nl = rebegin.get("num_layers")
                    if nl:
                        num_layers = int(nl)
                    # Re-pull from the first missing layer.  Layers
                    # land in order, so the missing set is a suffix in
                    # practice; the import-side set-add is idempotent
                    # if it is not.
                    layer = 0
                    while layer in received:
                        layer += 1
                    need_sync = False
                    metrics = getattr(state, "metrics", None)
                    if metrics is not None:
                        metrics.record_kv_resume()
                    continue  # loop guard re-checks completion
                status, chunk = await _post_json(
                    state,
                    f"{prefill_url}/internal/kv/export",
                    {
                        "handle": kv_handle,
                        "layer_start": layer,
                        "layer_count": chunk_layers,
                    },
                    endpoint="kv_export",
                    replica_id=prefill_id,
                    hedge=True,  # pure read: chunks are idempotent pulls
                )
                if status != 200:
                    raise RuntimeError(
                        f"kv export chunk failed: HTTP {status}"
                    )
                num_layers = int(chunk.get("num_layers") or 0)
                layers = chunk.get("layers") or []
                if not layers:
                    raise RuntimeError(
                        f"kv export returned no layers at "
                        f"{layer}/{num_layers}"
                    )
                status, _ = await _post_json(
                    state,
                    kv_url,
                    {
                        "op": "chunk",
                        "transfer_id": transfer_id,
                        "layers": layers,
                    },
                    endpoint="kv_import",
                    replica_id=decode_id,
                )
                if status != 200:
                    raise RuntimeError(
                        f"kv import chunk failed: HTTP {status}"
                    )
            except asyncio.CancelledError:
                raise
            except Exception as e:  # noqa: BLE001 — any lost round-trip (even a breaker rejection: the cooldown is shorter than a recompute) is resumable
                resume_rejected = isinstance(
                    e, RuntimeError
                ) and "resume rejected" in str(e)
                failures += 1
                if (
                    resume_rejected
                    or failures > chunk_retries
                    or not rz.try_spend_retry(decode_id)
                ):
                    raise
                logger.warning(
                    "kv transfer chunk failed (%s); resuming transfer "
                    "%s (attempt %d/%d)",
                    e,
                    transfer_id,
                    failures,
                    chunk_retries,
                )
                if _test_after_chunk_failure is not None:
                    await _test_after_chunk_failure(failures)
                # Linear backoff: a partitioned link fails in
                # microseconds — give the heal (or the breaker
                # cooldown) a beat before re-syncing.
                await asyncio.sleep(min(0.25 * failures, 2.0))
                need_sync = True
                continue
            layer += len(layers)
            chunk_idx += 1
            if _test_after_chunk is not None:
                await _test_after_chunk(chunk_idx)
        status, commit = await _post_json(
            state,
            kv_url,
            {"op": "commit", "transfer_id": transfer_id},
            endpoint="kv_import",
            replica_id=decode_id,
        )
        if status != 200:
            raise RuntimeError(f"kv import commit failed: HTTP {status}")
        return int(commit.get("adopted_tokens") or 0)
    except BaseException:
        # Free the decode-side reservation; the TTL sweep is only the
        # backstop.  Best-effort: the abort itself may be unreachable.
        try:
            await _post_json(
                state,
                kv_url,
                {"op": "abort", "transfer_id": transfer_id},
                endpoint="kv_import",
                replica_id=decode_id,
            )
        except Exception:  # noqa: BLE001 — fallback proceeds regardless
            logger.debug("kv import abort failed", exc_info=True)
        raise


async def _release_hold(state, prefill_url: str, kv_handle: str) -> None:
    """Best-effort release of the prefill replica's export hold (the
    TTL sweep covers a replica we can no longer reach)."""
    try:
        await _post_json(
            state,
            f"{prefill_url}/internal/kv/release",
            {"handle": kv_handle},
        )
    except Exception:  # noqa: BLE001 — TTL backstop frees the hold
        logger.debug("kv hold release failed", exc_info=True)


async def forward_prefill_handoff(
    state, journal, keys, exclude, prefill, resp, fwd, write,
    client_debug, span,
) -> bool:
    """Pump the prefill-only stream to the client (journaling the first
    token), then hand the KV pages off and continue on a decode-pool
    replica.  Returns True when the client stream completed.  All
    prefill-side failures degrade to recompute-resume on the decode
    pool without touching the migration budget; only decode-side
    continuation failures enter the normal migration loop."""
    # Local import: app.py imports this module lazily per stream, so a
    # top-level back-import would be circular at module load.
    from vllm_distributed_tpu.router.app import (
        MigrationNeeded,
        _forward_resumed,
        _migrate_loop,
        _place_or_none,
        _sse_payloads,
    )

    tracer = get_tracer()
    kv_handle: str | None = None
    handoff_now = False
    prefill_ok = True
    try:
        async for payload in _sse_payloads(resp, state.read_timeout):
            if payload == "[DONE]":
                break
            try:
                obj = json.loads(payload)
            except ValueError:
                continue
            if "error" in obj and not obj.get("choices"):
                # Any typed error on the prefill hop — drain, shed,
                # death — is recoverable: fall back to recompute.
                prefill_ok = False
                break
            if journal.upstream_id is None and obj.get("id"):
                journal.upstream_id = obj["id"]
                journal.model = obj.get("model")
            genuine_finish = False
            for choice in obj.get("choices") or []:
                # Internal-only: the export handle must never reach
                # the client (even debug ones) — it names live pages.
                handle = choice.pop("vdt_kv_handle", None)
                if handle:
                    kv_handle = str(handle)
                finish = choice.get("finish_reason")
                if finish == "length":
                    # The synthetic prefill-only budget (max_tokens=1
                    # forced on the disagg hop): the request is NOT
                    # done — strip the finish and hand off.  A client
                    # asking for max_tokens<=1 is never planned here.
                    choice["finish_reason"] = None
                    handoff_now = True
                elif finish is not None:
                    genuine_finish = True  # EOS/stop at token one
                kept = dict(choice) if client_debug else None
                journal.observe_choice(choice)
                if kept is not None:
                    choice.update(
                        {
                            k: v
                            for k, v in kept.items()
                            if k.startswith("vdt_")
                        }
                    )
            await write(json.dumps(obj))
            if genuine_finish:
                await write("[DONE]")
                if kv_handle:
                    await _release_hold(state, prefill.url, kv_handle)
                state.metrics.record_handoff("finished_at_prefill")
                _emit_handoff_event(
                    state,
                    "finished_at_prefill",
                    from_replica=prefill.replica_id,
                )
                return True
            if handoff_now:
                break
        else:
            prefill_ok = False  # stream closed without a finish
    except (ConnectionResetError, asyncio.CancelledError):
        # CLIENT-side disconnect mid-forward: the prefill replica is
        # healthy — free its hold now instead of at the TTL, and never
        # misattribute the hangup to the replica (parity with
        # _forward_primary, which re-raises for the same reason).
        if kv_handle:
            await _release_hold(state, prefill.url, kv_handle)
        raise
    except Exception as e:  # noqa: BLE001 — prefill-side failure = recompute fallback
        prefill_ok = False
        state.pool.note_unreachable(prefill, f"{type(e).__name__}: {e}")
        state.index.forget(prefill.replica_id)
        exclude.add(prefill.url)

    # ---- pick the decode-side continuation target ----
    target = _place_or_none(
        state, keys, exclude, span, slo_class=journal.slo_class
    )
    if target is None:
        await write(
            json.dumps(
                {"error": "no decode replica for hand-off", "code": 503}
            )
        )
        state.metrics.record_handoff("fallback")
        _emit_handoff_event(
            state, "fallback", from_replica=prefill.replica_id
        )
        return False

    # ---- stream the KV pages across (best-effort) ----
    adopted = 0
    choice = journal.choices.get(0)
    prompt_ids = choice.prompt_token_ids if choice is not None else None
    if prefill_ok and handoff_now and kv_handle and prompt_ids:
        try:
            if _test_before_transfer is not None:
                await _test_before_transfer()
            adopted = await _transfer_pages(
                state,
                prefill.url,
                target.url,
                kv_handle,
                list(prompt_ids),
                prefill_id=prefill.replica_id,
                decode_id=target.replica_id,
            )
        except Exception as e:  # noqa: BLE001 — transfer failure = recompute fallback
            logger.warning(
                "kv hand-off transfer %s -> %s failed (%s); falling "
                "back to recompute-resume",
                prefill.replica_id,
                target.replica_id,
                e,
            )
            adopted = 0
    if kv_handle:
        # Release on EVERY fallback path too (gate failed, prefill
        # stream broke after the handle arrived): a reachable prefill
        # replica frees its pages now, not at the TTL; an unreachable
        # one fails the best-effort call and the TTL backstops.
        await _release_hold(state, prefill.url, kv_handle)
    outcome = "planned" if adopted > 0 else "fallback"
    state.metrics.record_handoff(outcome)
    _emit_handoff_event(
        state,
        outcome,
        from_replica=prefill.replica_id,
        to_replica=target.replica_id,
        adopted_tokens=adopted,
    )
    tracer.event(
        span.ctx,
        "router.handoff",
        outcome=outcome,
        from_replica=prefill.replica_id,
        to_replica=target.replica_id,
        adopted_tokens=adopted,
    )

    # ---- continue decoding on the target ----
    try:
        await _forward_resumed(
            state, journal, target, fwd, write, client_debug
        )
        journal.served_by = target.replica_id
        return True
    except MigrationNeeded as m:
        # The DECODE side failed: this is genuine failure recovery and
        # takes the normal migration loop (budget applies).
        return await _migrate_loop(
            state, journal, keys, exclude, target, m,
            fwd, write, client_debug, span,
        )
