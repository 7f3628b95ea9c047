from vllm_distributed_tpu.config import CacheConfig, SchedulerConfig
from vllm_distributed_tpu.engine.request import Request, RequestStatus
from vllm_distributed_tpu.engine.scheduler import Scheduler
from vllm_distributed_tpu.sampling_params import SamplingParams


def make_scheduler(
    max_num_seqs=8,
    max_num_batched_tokens=64,
    num_pages=64,
    page_size=4,
    max_model_len=256,
    chunked=True,
):
    return Scheduler(
        SchedulerConfig(
            max_num_seqs=max_num_seqs,
            max_num_batched_tokens=max_num_batched_tokens,
            enable_chunked_prefill=chunked,
            max_model_len=max_model_len,
        ),
        CacheConfig(page_size=page_size),
        num_pages=num_pages,
    )


def make_req(rid, prompt_len=8, max_tokens=8):
    return Request(
        request_id=rid,
        prompt_token_ids=list(range(prompt_len)),
        sampling_params=SamplingParams(max_tokens=max_tokens),
        eos_token_id=None,
    )


def run_step(sched, sampled=None):
    out = sched.schedule()
    # Simulate workers: every running decode request samples token 7;
    # prefill-completing requests also sample.
    tokens = {}
    for req_id, n in out.num_scheduled_tokens.items():
        req = sched.requests[req_id]
        if req.num_computed_tokens + n >= req.num_prompt_tokens + req.num_output_tokens:
            tokens[req_id] = [7] if sampled is None else sampled.get(req_id, [7])
    finished = sched.update_from_output(out, tokens)
    return out, finished


def test_prefill_then_decode():
    sched = make_scheduler()
    req = make_req("a", prompt_len=8, max_tokens=2)
    sched.add_request(req)
    out, _ = run_step(sched)
    assert out.total_num_scheduled_tokens == 8
    assert len(out.new_requests) == 1
    assert req.num_computed_tokens == 8
    assert req.num_output_tokens == 1  # sampled when prefill completed
    # Decode step processes the sampled token and samples output #2 ->
    # max_tokens reached.
    out2, finished = run_step(sched)
    assert out2.num_scheduled_tokens["a"] == 1
    assert finished and finished[0].request_id == "a"
    assert req.status == RequestStatus.FINISHED_LENGTH
    assert not sched.has_unfinished_requests()


def test_chunked_prefill():
    sched = make_scheduler(max_num_batched_tokens=16)
    req = make_req("a", prompt_len=40, max_tokens=1)
    sched.add_request(req)
    out, _ = run_step(sched)
    assert out.num_scheduled_tokens["a"] == 16
    assert req.num_computed_tokens == 16
    out2, _ = run_step(sched)
    assert out2.num_scheduled_tokens["a"] == 16
    # Delta goes through cached_requests, not new_requests.
    assert len(out2.new_requests) == 0
    assert len(out2.cached_requests) == 1
    out3, _ = run_step(sched)
    assert out3.num_scheduled_tokens["a"] == 8
    assert req.num_output_tokens == 1


def test_batch_budget_shared():
    sched = make_scheduler(max_num_batched_tokens=16)
    for i in range(4):
        sched.add_request(make_req(f"r{i}", prompt_len=8, max_tokens=4))
    out, _ = run_step(sched)
    # Only two 8-token prefills fit.
    assert out.total_num_scheduled_tokens == 16
    assert set(out.num_scheduled_tokens) == {"r0", "r1"}
    out2, _ = run_step(sched)
    # r0/r1 decode (1 token each) + r2 prefill (8) + r3 partial (6).
    assert out2.num_scheduled_tokens["r0"] == 1
    assert out2.num_scheduled_tokens["r1"] == 1
    assert out2.num_scheduled_tokens["r2"] == 8
    assert out2.num_scheduled_tokens["r3"] == 6
    assert out2.total_num_scheduled_tokens == 16


def test_max_num_seqs_cap():
    sched = make_scheduler(max_num_seqs=2, max_num_batched_tokens=64)
    for i in range(4):
        sched.add_request(make_req(f"r{i}", prompt_len=4))
    out, _ = run_step(sched)
    assert len(out.new_requests) == 2


def test_preemption_and_resume():
    # 15 usable pages of 4 slots = 60 slots; each request peaks at
    # 12 + 20 = 32 tokens = 8 pages, so both together (16) exceed the pool.
    sched = make_scheduler(num_pages=16, page_size=4, max_num_batched_tokens=32)
    r1 = make_req("r1", prompt_len=12, max_tokens=20)
    r2 = make_req("r2", prompt_len=12, max_tokens=20)
    sched.add_request(r1)
    sched.add_request(r2)
    out, _ = run_step(sched)
    assert set(out.num_scheduled_tokens) == {"r1", "r2"}
    # Decode until pages run out: each req grows to 16 slots = 4 pages;
    # 4+4 > 7 so someone gets preempted eventually.
    preempted_seen = False
    for _ in range(40):
        out, _ = run_step(sched)
        if out.preempted_req_ids:
            preempted_seen = True
            break
    assert preempted_seen
    # The preempted request eventually resumes and finishes.
    for _ in range(80):
        out, finished = run_step(sched)
        if not sched.has_unfinished_requests():
            break
    assert not sched.has_unfinished_requests()
    assert r1.status.is_finished and r2.status.is_finished
    assert r1.num_output_tokens == 20
    assert r2.num_output_tokens == 20


def test_abort():
    sched = make_scheduler()
    req = make_req("a", prompt_len=8, max_tokens=100)
    sched.add_request(req)
    run_step(sched)
    sched.abort_request("a")
    assert not sched.has_unfinished_requests()
    out = sched.schedule()
    assert out.is_empty
    # Empty outputs are never dispatched, so the finish notice is HELD —
    # it must ride the next step that actually reaches the workers.
    assert out.finished_req_ids == []
    sched.add_request(make_req("b", prompt_len=4, max_tokens=1))
    out2 = sched.schedule()
    assert not out2.is_empty
    assert "a" in out2.finished_req_ids


def test_finished_ids_propagate_next_step():
    sched = make_scheduler()
    req = make_req("a", prompt_len=4, max_tokens=1)
    sched.add_request(req)
    run_step(sched)  # prefill + sample -> finished (max_tokens=1)
    assert req.status.is_finished
    # The next dispatched step carries the notice alongside its work.
    sched.add_request(make_req("b", prompt_len=4, max_tokens=2))
    out = sched.schedule()
    assert not out.is_empty
    assert "a" in out.finished_req_ids


def test_notices_held_across_empty_steps():
    """Finish notices survive any number of empty schedule() calls and
    arrive exactly once on the next dispatched (non-empty) step."""
    sched = make_scheduler()
    req = make_req("a", prompt_len=4, max_tokens=1)
    sched.add_request(req)
    run_step(sched)  # finishes (max_tokens=1)
    assert req.status.is_finished
    for _ in range(3):
        out = sched.schedule()
        assert out.is_empty
        assert out.finished_req_ids == []
    sched.add_request(make_req("b", prompt_len=4, max_tokens=2))
    out = sched.schedule()
    assert not out.is_empty
    assert out.finished_req_ids == ["a"]
    # Delivered once, not re-sent.
    sched.update_from_output(out, {"b": [7]})
    out2 = sched.schedule()
    assert "a" not in out2.finished_req_ids


def test_prefill_chunks_in_flight_are_counted():
    """PR 32: three schedules with no result applied in between.  The
    chunk in flight counts as computed (the next chunk starts behind
    it), the request turns into a decode row once its last chunk is in
    flight, and its budget counts the token that chunk will sample."""
    sched = make_scheduler(max_num_batched_tokens=16)
    req = make_req("a", prompt_len=24, max_tokens=3)
    sched.add_request(req)
    o1 = sched.schedule()
    assert o1.new_requests[0].num_new_tokens == 16
    assert (req.num_computed_tokens, req.num_inflight_tokens) == (0, 16)
    assert req.is_prefill and not req.prefill_dispatched
    o2 = sched.schedule()
    (c2,) = o2.cached_requests
    assert (c2.num_computed_tokens, c2.num_new_tokens) == (16, 8)
    assert o2.decode_steps == 1
    assert req.prefill_dispatched and req.num_tokens_with_inflight == 25
    o3 = sched.schedule()  # a decode row: 2 of its 3 tokens are left
    (c3,) = o3.cached_requests
    assert c3.num_computed_tokens == 24
    assert c3.num_new_tokens == min(o3.decode_steps, 2) == 2
    assert sched.decode_room(req) == 0
    assert sched.schedule().is_empty  # its whole budget is in flight
    # The results land in order: no chunk before the last samples.
    assert sched.update_from_output(o1, {}) == []
    assert (req.num_computed_tokens, req.num_inflight_tokens) == (16, 10)
    assert sched.update_from_output(o2, {"a": [7]}) == []
    assert not req.is_prefill and req.num_inflight_tokens == 2
    (done,) = sched.update_from_output(o3, {"a": [7, 7]})
    assert done is req and req.status == RequestStatus.FINISHED_LENGTH
    assert req.num_inflight_tokens == 0 and req.num_output_tokens == 3
    assert sched.allocator.num_free_pages == sched.allocator.num_pages - 1


def test_abort_with_a_prefill_chunk_in_flight_defers_the_free():
    """A request aborted while its prompt chunk is on the device keeps
    its pages until that step has landed (nothing may be handed the
    pages the device is still writing)."""
    sched = make_scheduler(max_num_batched_tokens=16)
    sched.add_request(make_req("a", prompt_len=24, max_tokens=3))
    o1 = sched.schedule()
    free = sched.allocator.num_free_pages
    sched.abort_request("a")
    assert sched.allocator.num_free_pages == free  # deferred
    assert sched.update_from_output(o1, {}) == []
    assert sched.allocator.num_free_pages == sched.allocator.num_pages - 1
    assert not sched._deferred_frees
