"""Fused multi-step decode + engine pipelining (VERDICT r1 items #2/#3).

Proves (a) two dispatches are genuinely in flight at once, (b) the fused
decode scan and the pipelined engine produce bit-identical tokens to the
fully synchronous single-step engine, (c) mixed finish times, budget
ends, admissions and stop conditions are reconciled without draining
(PR 32: a dispatch of any kind is issued behind a dispatch of any kind,
its decode rows fed from a device carry looked up by row), and (d) what
does need settled host state still drains, and says why.
"""

from __future__ import annotations

import pytest

from tests.utils import hold_results
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.testing import write_llama_config


def _run(num_decode_steps: int, sampling_kwargs_per_req, track=None,
         engines=None):
    engine = LLMEngine.from_engine_args(
        EngineArgs(
            model=write_llama_config(),
            skip_tokenizer_init=True,
            load_format="dummy",
            num_kv_pages=128,
            max_model_len=256,
            num_decode_steps=num_decode_steps,
        )
    )
    if engines is not None:
        engines.append(engine)
    for i, kw in enumerate(sampling_kwargs_per_req):
        engine.add_request(
            f"r{i}",
            prompt_token_ids=[3 + i, 7, 11 + i],
            sampling_params=SamplingParams(**kw),
        )
    results: dict[str, list[int]] = {}
    steps = 0
    while engine.has_unfinished_requests():
        if track is not None:
            track.append(len(engine._pending))
        for out in engine.step():
            if out.finished:
                results[out.request_id] = out.outputs[0].token_ids
        steps += 1
        assert steps < 500
    return results


def test_pipelined_greedy_matches_sync():
    reqs = [dict(temperature=0.0, max_tokens=33, ignore_eos=True)] * 4
    sync = _run(1, reqs)
    pipelined = _run(8, reqs)
    assert sync == pipelined


def test_pipelined_seeded_sampling_matches_sync():
    reqs = [
        dict(temperature=0.9, seed=41 + i, max_tokens=19, ignore_eos=True)
        for i in range(3)
    ]
    sync = _run(1, reqs)
    pipelined = _run(4, reqs)
    assert sync == pipelined


def test_two_dispatches_in_flight():
    depths: list[int] = []
    reqs = [dict(temperature=0.0, max_tokens=49, ignore_eos=True)] * 2
    _run(8, reqs, track=depths)
    # At least one step() began with a dispatch still unresolved.
    assert max(depths) >= 1


def test_mixed_finish_times_drain():
    """Requests whose budgets end in different scans: the one whose
    whole budget is in flight is skipped by the schedule behind it (the
    batch shrinks, the carry is read by row), nothing drains."""
    reqs = [
        dict(temperature=0.0, max_tokens=9, ignore_eos=True),
        dict(temperature=0.0, max_tokens=30, ignore_eos=True),
        dict(temperature=0.0, max_tokens=17, ignore_eos=True),
    ]
    engines = []
    out = _run(8, reqs, engines=engines)
    assert sorted(len(v) for v in out.values()) == [9, 17, 30]
    assert out == _run(1, reqs)
    assert engines[0].pipeline_breaks == 0


def test_penalties_fall_back_to_sync_and_match():
    reqs = [
        dict(
            temperature=0.8,
            seed=7,
            repetition_penalty=1.3,
            max_tokens=12,
            ignore_eos=True,
        )
    ]
    assert _run(8, reqs) == _run(1, reqs)


def test_stop_token_mid_window():
    # Greedy on dummy weights is deterministic: find what it generates,
    # then use an early token as a stop token and check truncation.
    probe = _run(1, [dict(temperature=0.0, max_tokens=24, ignore_eos=True)])
    toks = probe["r0"]
    stop_tok = toks[5]
    reqs = [dict(temperature=0.0, max_tokens=24, stop_token_ids=[stop_tok])]
    out = _run(8, reqs)
    idx = toks.index(stop_tok)
    assert out["r0"] == toks[: idx + 1]


def test_heterogeneous_tails_masked_not_recompiled():
    """Uniform-K with per-sequence tail masking (round 5): requests
    with different max_tokens — none a multiple of K, several under
    one K — must produce exactly their budget, bit-identical to the
    sync engine, while the scheduler emits only K=num_decode_steps
    fused scans (no tail-K program proliferation)."""
    reqs = [
        dict(temperature=0.0, max_tokens=m, ignore_eos=True)
        for m in (3, 17, 40, 5, 29, 8)
    ]
    sync = _run(1, reqs)
    assert [len(sync[f"r{i}"]) for i in range(6)] == [3, 17, 40, 5, 29, 8]

    from vllm_distributed_tpu.engine.scheduler import Scheduler

    seen_k = set()
    orig = Scheduler.schedule

    def spy(self):
        out = orig(self)
        if out.decode_steps > 1:
            seen_k.add(out.decode_steps)
            # Under-K tails are per-request num_new, not a smaller K.
            for c in out.cached_requests:
                assert c.num_new_tokens <= out.decode_steps
        return out

    Scheduler.schedule = spy
    try:
        fused = _run(8, reqs)
    finally:
        Scheduler.schedule = orig
    assert fused == sync
    assert seen_k == {8}, seen_k


# ---- PR 32: a dispatch of any kind behind a dispatch of any kind ----


def _engine(num_decode_steps: int, held: bool = True, **kw):
    args = dict(
        model=write_llama_config(),
        skip_tokenizer_init=True,
        load_format="dummy",
        num_kv_pages=128,
        max_model_len=256,
        max_num_seqs=8,
        num_decode_steps=num_decode_steps,
    )
    args.update(kw)
    # Held: a dispatch lands when the engine blocks on it, never
    # sooner, so what overlaps does not depend on this machine's speed.
    engine = LLMEngine.from_engine_args(EngineArgs(**args))
    return hold_results(engine) if held else engine


def _greedy(max_tokens, **kw):
    return dict(temperature=0.0, max_tokens=max_tokens, ignore_eos=True, **kw)


def _prompt(i: int, n: int = 3) -> list[int]:
    return [(7 * i + 3 * j) % 90 + 3 for j in range(n)]


def _drive(engine, script, aborts=None, limit=600):
    """Play ``script`` (engine step number -> requests added before that
    step: (id, prompt, sampling kwargs)) and ``aborts`` (step -> ids) to
    the end.  Returns each request's final tokens and the largest number
    of dispatches seen in flight between steps."""
    results: dict[str, list[int]] = {}
    depth = 0
    step = 0
    while step <= max(script) or engine.has_unfinished_requests():
        for rid, prompt, kw in script.get(step, ()):
            engine.add_request(
                rid, prompt_token_ids=prompt,
                sampling_params=SamplingParams(**kw))
        for rid in (aborts or {}).get(step, ()):
            engine.abort_request(rid)
        depth = max(depth, len(engine._pending))
        if engine.has_unfinished_requests():
            for out in engine.step():
                if out.finished:
                    results[out.request_id] = out.outputs[0].token_ids
        step += 1
        assert step < limit
    assert not engine._pending
    return results, depth


def _assert_pool_whole(engine):
    """Every page came back exactly once: a page freed twice would be
    handed to two requests, one never freed would shrink the pool."""
    s = engine.scheduler
    assert not s._deferred_frees
    assert s.allocator.num_free_pages == s.allocator.num_pages - 1


def _overlapped_share(engine) -> float:
    total = sum(engine.dispatch_counts.values())
    over = sum(n for (_, o), n in engine.dispatch_counts.items() if o)
    return over / max(total, 1)


ARRIVALS = {
    "greedy": lambda i: _greedy(21 + 5 * i),
    "seeded": lambda i: dict(
        temperature=0.9, seed=100 + i, max_tokens=17 + 4 * i, ignore_eos=True),
}


@pytest.mark.parametrize("kind", sorted(ARRIVALS))
def test_arrivals_mid_decode_overlap_and_match_blocking(kind):
    """Requests arrive while a scan is in flight: the mixed step that
    admits them is issued behind the scan, the next scan behind the
    mixed step (two in flight), and every request's tokens are those
    of the blocking engine.  Nothing drains."""
    sp = ARRIVALS[kind]
    script = {
        0: [(f"a{i}", _prompt(i), sp(i)) for i in range(2)],
        3: [("b0", _prompt(5, 5), sp(2))],
        4: [("b1", _prompt(6, 9), sp(3)), ("b2", _prompt(7, 2), sp(1))],
        7: [("c0", _prompt(8, 4), sp(0))],
    }
    engine = _engine(4)
    got, depth = _drive(engine, script)
    want, _ = _drive(_engine(1), script)
    assert got == want
    assert depth >= 1
    assert engine.pipeline_breaks == 0, engine.pipeline_break_reasons
    kinds = {k for (k, over), n in engine.dispatch_counts.items() if over}
    assert kinds == {"decode", "step"}, engine.dispatch_counts
    _assert_pool_whole(engine)


def test_batch_that_shrinks_and_grows_reads_the_right_carry_rows():
    """Budgets end in flight (the batch shrinks, rows move up) while
    arrivals join (it grows): each decode row still reads its own
    request's last token from the carry, so the tokens are those of
    the blocking engine."""
    script = {
        0: [(f"s{i}", _prompt(i), _greedy(m))
            for i, m in enumerate((6, 30, 11, 23))],
        2: [("g0", _prompt(9, 6), _greedy(14))],
        4: [("g1", _prompt(10, 3), _greedy(9)),
            ("g2", _prompt(11, 7), _greedy(19))],
        6: [("g3", _prompt(12, 2), _greedy(5))],
    }
    engine = _engine(4)
    got, _ = _drive(engine, script)
    want, _ = _drive(_engine(1), script)
    assert got == want
    assert [len(got[f"s{i}"]) for i in range(4)] == [6, 30, 11, 23]
    assert engine.pipeline_breaks == 0, engine.pipeline_break_reasons
    _assert_pool_whole(engine)


def test_chunked_prompt_spans_two_in_flight_steps():
    """A prompt longer than the step's token budget is prefilled in
    chunks; the second chunk is scheduled while the first is still on
    the device (the scheduler counts the chunk in flight), and the
    request's first decode row reads the token its last chunk left in
    the carry."""
    from vllm_distributed_tpu.engine.scheduler import Scheduler

    script = {
        0: [("bg", _prompt(1), _greedy(40))],
        3: [("long", [(5 * j) % 90 + 3 for j in range(75)], _greedy(12))],
    }
    kw = dict(max_num_batched_tokens=32, max_num_seqs=4)
    engine = _engine(4, **kw)
    chunks_behind = []
    orig = Scheduler.schedule

    def spy(self):
        out = orig(self)
        if self is engine.scheduler and "long" in out.num_scheduled_tokens:
            chunks_behind.append(
                (out.num_scheduled_tokens["long"], len(engine._pending)))
        return out

    Scheduler.schedule = spy
    try:
        got, _ = _drive(engine, script)
    finally:
        Scheduler.schedule = orig
    want, _ = _drive(_engine(1, **kw), script)
    assert got == want and len(got["long"]) == 12
    prefill = [c for c in chunks_behind if c[0] > 4]
    assert len(prefill) >= 3, chunks_behind
    # a later chunk was scheduled with the one before it in flight
    assert any(pending for _, pending in prefill[1:]), chunks_behind
    assert engine.pipeline_breaks == 0, engine.pipeline_break_reasons
    _assert_pool_whole(engine)


def test_stop_token_ends_a_request_with_a_mixed_step_behind_it():
    """The host learns of a stop token only when the scan that sampled
    it is fetched; by then a mixed step holding the request is in
    flight.  Its tokens past the stop are dropped, its pages are freed
    once, when that step has landed, and the others' tokens are those
    of the blocking engine."""
    probe, _ = _drive(_engine(1), {0: [("p", _prompt(2), _greedy(24))]})
    toks = probe["p"]
    stop = toks[6]
    cut = toks[: toks.index(stop) + 1]
    script = {
        0: [("p", _prompt(2),
             dict(temperature=0.0, max_tokens=24, stop_token_ids=[stop])),
            ("q", _prompt(3), _greedy(30))],
        2: [("late", _prompt(4, 6), _greedy(10))],
        3: [("later", _prompt(5, 4), _greedy(10))],
    }
    engine = _engine(4)
    frees = []
    alloc = engine.scheduler.allocator
    orig_free = alloc.free

    def free(req):
        frees.append(req.request_id)
        return orig_free(req)

    alloc.free = free
    got, _ = _drive(engine, script)
    want, _ = _drive(_engine(1), script)
    assert got == want and got["p"] == cut
    assert sorted(frees) == ["late", "later", "p", "q"]
    assert engine.pipeline_breaks == 0, engine.pipeline_break_reasons
    _assert_pool_whole(engine)


def test_abort_with_a_mixed_step_in_flight():
    """An abort lands while a mixed step that holds the request (as a
    prompt chunk) is on the device: its pages wait for that step, the
    survivors' tokens are those of a run without the aborted one."""
    script = {
        0: [("a", _prompt(1), _greedy(28)), ("b", _prompt(2), _greedy(19))],
        3: [("victim", _prompt(3, 11), _greedy(16)),
            ("c", _prompt(4, 5), _greedy(13))],
    }
    engine = _engine(4)
    got, _ = _drive(engine, script, aborts={4: ["victim"]})
    assert "victim" not in got
    script[3] = script[3][1:]
    want, _ = _drive(_engine(1), script)
    assert got == want
    _assert_pool_whole(engine)


def _fallback_logprobs():
    script = {
        0: [("a", _prompt(1), _greedy(40))],
        3: [("lp", _prompt(2, 4), _greedy(6, logprobs=2))],
    }
    return dict(script=script)


def _fallback_penalties():
    script = {
        0: [("a", _prompt(1), _greedy(40))],
        3: [("pen", _prompt(2, 4), dict(
            temperature=0.8, seed=7, repetition_penalty=1.3, max_tokens=9,
            ignore_eos=True))],
    }
    return dict(script=script)


def _fallback_spec():
    # Nothing to draft in this text: after the dry limit the engine
    # pipelines again, and drains to probe for drafts every so often.
    script = {0: [("a", _prompt(1), _greedy(150))]}
    return dict(script=script, engine=dict(speculative_ngram_k=2))


def _fallback_pages():
    # 13 usable pages of 16: three requests growing to 5 pages each
    # cannot all stay, so a schedule behind a dispatch may have to
    # preempt, which it only does on settled state.
    script = {0: [(f"p{i}", _prompt(i, 8), _greedy(70)) for i in range(3)]}
    return dict(script=script, engine=dict(num_kv_pages=14, max_model_len=96))


FALLBACKS = {
    "logprobs": _fallback_logprobs,
    "penalties": _fallback_penalties,
    "spec": _fallback_spec,
    "pages": _fallback_pages,
}


@pytest.mark.parametrize("reason", sorted(FALLBACKS))
def test_what_needs_settled_host_state_still_drains(reason):
    case = FALLBACKS[reason]()
    kw = case.get("engine", {})
    engine = _engine(4, **kw)
    got, _ = _drive(engine, case["script"], limit=2000)
    want, _ = _drive(_engine(1, **kw), case["script"], limit=2000)
    assert got == want
    assert engine.pipeline_break_reasons.get(reason, 0) >= 1, (
        engine.pipeline_break_reasons)
    assert set(engine.pipeline_break_reasons) == {reason}
    assert engine.pipeline_breaks == sum(
        engine.pipeline_break_reasons.values())
    _assert_pool_whole(engine)


def test_closed_loop_of_budget_ended_requests_never_drains():
    """The benchmark's traffic in small: a fixed pool of clients, each
    sending its next request when the last one ended at its budget.
    Every boundary between a scan and the mixed step that admits the
    successors is an overlap, none is a drain."""
    engine = _engine(4, max_num_seqs=4)
    lengths = [9, 14, 6, 11, 17, 5, 12, 8]
    sent = 0
    done: dict[str, list[int]] = {}

    def send():
        nonlocal sent
        engine.add_request(
            f"r{sent}", prompt_token_ids=_prompt(sent, 3 + sent % 5),
            sampling_params=SamplingParams(
                **_greedy(lengths[sent % len(lengths)])))
        sent += 1

    for _ in range(4):
        send()
    steps = 0
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.outputs[0].token_ids
                if sent < 24:
                    send()
        steps += 1
        assert steps < 600
    assert len(done) == 24
    assert all(
        len(done[f"r{i}"]) == lengths[i % len(lengths)] for i in range(24))
    assert engine.pipeline_breaks == 0, engine.pipeline_break_reasons
    assert _overlapped_share(engine) >= 0.8, engine.dispatch_counts
    _assert_pool_whole(engine)


def test_schedule_slack_is_what_the_device_is_expected_to_need():
    """``schedule_slack()`` (PR 32): nothing to wait for while nothing
    is in flight or a dispatch's shape has not been timed yet; once it
    has, the wait is the dispatch's expected end less the host's lead;
    a dispatch that has landed means the time is up."""
    import time

    engine = _engine(4, held=False)  # this test reads the real future
    assert engine.schedule_slack() == 0.0
    engine.add_request(
        "a", prompt_token_ids=_prompt(1),
        sampling_params=SamplingParams(**_greedy(40)))
    while not engine._pending:
        engine.step()
    _, fut, key, t_issued = engine._pending[0]
    engine._device_estimate.pop(key, None)
    assert engine.schedule_slack() == 0.0  # shape never timed
    engine._device_estimate[key] = 5.0  # the shortest seen lately
    engine._last_done = 0.0
    lead = 2.0 * engine._issue_seconds
    assert 0.0 < lead < 0.2
    want = t_issued + 5.0 - lead - time.monotonic()
    assert abs(engine.schedule_slack() - want) < 0.05
    fut.result()
    assert engine.schedule_slack() == 0.0  # it has landed: time is up
    while engine.has_unfinished_requests():
        engine.step()
    assert engine.schedule_slack() == 0.0
    # every shape issued without blocking was timed by its done-callback
    assert engine._device_seconds and all(
        len(v) >= 1 and engine._device_estimate[k] == min(v) >= 0.0
        for k, v in engine._device_seconds.items())
