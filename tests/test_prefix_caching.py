"""Prefix caching: allocator unit tests + cached-vs-cold engine parity.

The adversarial bar (ISSUE 1): with --enable-prefix-caching the engine's
greedy outputs must be BIT-IDENTICAL to a cold engine for the same
prompts, including under eviction pressure on a tiny page pool.
"""

import pytest

from tests.utils import make_tiny_llama
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.block_manager import (
    NoFreePagesError,
    PageAllocator,
    PrefixCachingAllocator,
    hash_page_tokens,
)
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.engine.request import Request
from vllm_distributed_tpu.sampling_params import SamplingParams

PS = 4  # page size for the unit tests


def make_req(rid, tokens):
    return Request(
        request_id=rid,
        prompt_token_ids=list(tokens),
        sampling_params=SamplingParams(),
    )


def computed(alloc, rid, tokens):
    """Allocate + mark every token computed + register full pages."""
    req = make_req(rid, tokens)
    alloc.allocate(req, len(tokens))
    req.num_computed_tokens = len(tokens)
    alloc.register_computed(req)
    return req


_query_seq = iter(range(10**6))


def query(alloc, tokens):
    """Query the cache for a fresh request with this prompt."""
    return alloc.query_prefix(make_req(f"q{next(_query_seq)}", tokens))


# ---- allocator unit tests ----
def test_refcount_shared_pages_survive_one_free():
    alloc = PrefixCachingAllocator(num_pages=16, page_size=PS)
    prompt = list(range(1, 9))  # 2 full pages
    r1 = computed(alloc, "r1", prompt)
    shared = list(r1.page_ids)
    alloc.free(r1)

    hit, pages = query(alloc, prompt + [50])
    assert hit == 8 and pages == shared
    r2 = make_req("r2", prompt + [50])
    alloc.attach_prefix(r2, pages)
    r2.num_computed_tokens = hit
    r3 = make_req("r3", prompt + [60])
    alloc.attach_prefix(r3, pages)
    r3.num_computed_tokens = hit
    assert r2.page_ids == shared and r3.page_ids == shared

    # Free one sharer: the pages must survive for the other.
    alloc.free(r2)
    assert r3.page_ids == shared
    # They are NOT reusable garbage: exhaust the plain free list and the
    # shared pages must never be handed out.
    grabbed = []
    while True:
        r = make_req(f"g{len(grabbed)}", [1])
        try:
            grabbed.extend(alloc.allocate(r, 1))
        except NoFreePagesError:
            break
    assert not set(shared) & set(grabbed)
    # Free the last owner: now they become evictable (and allocatable).
    alloc.free(r3)
    r = make_req("last", list(range(8)))
    got = alloc.allocate(r, 8)
    assert set(got) == set(shared)


def test_lru_eviction_order():
    alloc = PrefixCachingAllocator(num_pages=9, page_size=PS)  # 8 usable
    a = computed(alloc, "a", [1, 2, 3, 4])
    b = computed(alloc, "b", [5, 6, 7, 8])
    page_a, page_b = a.page_ids[0], b.page_ids[0]
    alloc.free(a)  # freed first -> least recently used
    alloc.free(b)
    assert alloc.num_free_pages == 8
    # Drain the 6 plain-free pages; the next two allocations must evict
    # a's page before b's.
    filler = make_req("f", list(range(6 * PS)))
    alloc.allocate(filler, 6 * PS)
    first = alloc.allocate(make_req("x", [9]), 1)
    second = alloc.allocate(make_req("y", [9]), 1)
    assert first == [page_a]
    assert second == [page_b]
    # Both registrations are gone.
    assert query(alloc, [1, 2, 3, 4, 90]) == (0, [])
    assert query(alloc, [5, 6, 7, 8, 90]) == (0, [])


def test_lru_refreshes_on_reuse():
    alloc = PrefixCachingAllocator(num_pages=9, page_size=PS)
    a = computed(alloc, "a", [1, 2, 3, 4])
    b = computed(alloc, "b", [5, 6, 7, 8])
    page_a = a.page_ids[0]
    alloc.free(a)
    alloc.free(b)
    # Touch a's page: re-attach and free again -> now most recent.
    _, pages = query(alloc, [1, 2, 3, 4, 9])
    r = make_req("r", [1, 2, 3, 4, 9])
    alloc.attach_prefix(r, pages)
    r.num_computed_tokens = 4
    alloc.free(r)
    filler = make_req("f", list(range(6 * PS)))
    alloc.allocate(filler, 6 * PS)
    # b's page (now LRU) is evicted first.
    assert alloc.allocate(make_req("x", [9]), 1) != [page_a]
    assert alloc.allocate(make_req("y", [9]), 1) == [page_a]


def test_hash_chain_keying_no_cross_parent_collision():
    # Same page content under different parents must NOT collide.
    assert hash_page_tokens(b"", [7, 7, 7, 7]) != hash_page_tokens(
        hash_page_tokens(b"", [1, 2, 3, 4]), [7, 7, 7, 7]
    )
    alloc = PrefixCachingAllocator(num_pages=16, page_size=PS)
    r = computed(alloc, "r", [1, 2, 3, 4, 7, 7, 7, 7])
    alloc.free(r)
    # Identical second page under a different first page: no hit beyond
    # page granularity, and crucially no FALSE hit on page 2's content.
    assert query(alloc, [9, 9, 9, 9, 7, 7, 7, 7]) == (0, [])
    # The true chain hits both pages.
    hit, pages = query(alloc, [1, 2, 3, 4, 7, 7, 7, 7, 9])
    assert hit == 8 and len(pages) == 2


def test_partial_page_never_matches():
    alloc = PrefixCachingAllocator(num_pages=16, page_size=PS)
    r = computed(alloc, "r", [1, 2, 3, 4, 5, 6])  # page 2 only half full
    alloc.free(r)
    hit, pages = query(alloc, [1, 2, 3, 4, 5, 6, 8, 8])
    assert hit == 4 and len(pages) == 1  # full page only
    # A prompt shorter than one page can never hit.
    assert query(alloc, [1, 2, 3]) == (0, [])


def test_full_prompt_hit_drops_tail_page():
    """A fully cached prompt recomputes its whole last page into a fresh
    page: logits need at least one computed token, and a shared page must
    never be written (KV recompute is not bit-stable across shapes)."""
    alloc = PrefixCachingAllocator(num_pages=16, page_size=PS)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]
    r = computed(alloc, "r", prompt)
    alloc.free(r)
    hit, pages = query(alloc, prompt)
    assert hit == len(prompt) - PS and len(pages) == 1
    # Single fully-cached page: no usable hit at all.
    assert query(alloc, [1, 2, 3, 4]) == (0, [])


def test_register_computed_is_incremental_and_dedups():
    alloc = PrefixCachingAllocator(num_pages=16, page_size=PS)
    req = make_req("r", list(range(1, 9)))
    alloc.allocate(req, 8)
    req.num_computed_tokens = 4  # only page 0 computed so far
    alloc.register_computed(req)
    assert query(alloc, list(range(1, 6))) == (
        4,
        [req.page_ids[0]],
    )
    req.num_computed_tokens = 8
    alloc.register_computed(req)
    # A second request computing the same content does not re-register.
    dup = computed(alloc, "dup", list(range(1, 9)))
    hit, pages = query(alloc, list(range(1, 9)) + [9])
    assert pages == req.page_ids
    assert set(dup.page_ids).isdisjoint(pages)


def test_register_ignores_computed_overrun():
    """Early stop in a fused-decode dispatch advances num_computed_tokens
    past the surviving token list; pages past the real tokens must not be
    registered under truncated-slice hashes."""
    alloc = PrefixCachingAllocator(num_pages=16, page_size=PS)
    req = make_req("r", [1, 2, 3, 4, 5])  # 5 real tokens
    alloc.allocate(req, 5 + 7)  # room for the discarded tail
    req.num_computed_tokens = 12  # overran: tail tokens were discarded
    alloc.register_computed(req)
    assert query(alloc, [1, 2, 3, 4, 9]) == (4, [req.page_ids[0]])
    # Page 1 (tokens 4..7, only token 4 real) stayed unregistered: it
    # returns to the plain free list, not the LRU.
    alloc.free(req)
    assert len(alloc._lru) == 1


def test_allocate_rollback_under_true_exhaustion():
    alloc = PrefixCachingAllocator(num_pages=4, page_size=PS)  # 3 usable
    r1 = computed(alloc, "r1", list(range(2 * PS)))
    with pytest.raises(NoFreePagesError):
        alloc.allocate(make_req("r2", list(range(3 * PS))), 3 * PS)
    assert alloc.num_free_pages == 1
    # Cached pages count as free and get evicted when needed.
    alloc.free(r1)
    assert alloc.num_free_pages == 3
    r3 = make_req("r3", list(range(3 * PS)))
    assert len(alloc.allocate(r3, 3 * PS)) == 3


def test_flag_off_uses_seed_allocator():
    from vllm_distributed_tpu.config import CacheConfig, SchedulerConfig
    from vllm_distributed_tpu.engine.block_manager import (
        RadixPrefixCachingAllocator,
    )
    from vllm_distributed_tpu.engine.scheduler import Scheduler

    sched = Scheduler(SchedulerConfig(), CacheConfig(), num_pages=64)
    assert type(sched.allocator) is PageAllocator
    # The radix index (ISSUE 14) is the prefix-caching default; the
    # PR 1 hash-chain stays reachable as "flat", the tests' reference index.
    on = Scheduler(
        SchedulerConfig(),
        CacheConfig(enable_prefix_caching=True),
        num_pages=64,
    )
    assert type(on.allocator) is RadixPrefixCachingAllocator
    flat = Scheduler(
        SchedulerConfig(),
        CacheConfig(enable_prefix_caching=True, prefix_cache_index="flat"),
        num_pages=64,
    )
    assert type(flat.allocator) is PrefixCachingAllocator


# ---- engine-level parity (adversarial) ----
@pytest.fixture(scope="module")
def tiny_llama(tmp_path_factory):
    return make_tiny_llama(str(tmp_path_factory.mktemp("llama_pc")))


def _make_engine(model_dir, **kw):
    defaults = dict(
        model=model_dir,
        skip_tokenizer_init=True,
        num_kv_pages=128,
        page_size=16,
        max_num_seqs=8,
        max_model_len=256,
    )
    defaults.update(kw)
    return LLMEngine.from_engine_args(EngineArgs(**defaults))


def _run_greedy(engine, prompts, max_tokens=8, tag="r"):
    for i, p in enumerate(prompts):
        engine.add_request(
            f"{tag}{i}",
            prompt_token_ids=p,
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=max_tokens, ignore_eos=True
            ),
        )
    done = {}
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out
    return [
        done[f"{tag}{i}"].outputs[0].token_ids for i in range(len(prompts))
    ]


def test_cached_outputs_bit_identical_to_cold(tiny_llama):
    shared = list(range(1, 33))  # two full shared pages
    prompts = [
        shared + [40 + i, 41, 42 + i, 43, 44 + i] for i in range(4)
    ] + [shared[:20]]
    cold = _run_greedy(_make_engine(tiny_llama), prompts)
    cached_engine = _make_engine(tiny_llama, enable_prefix_caching=True)
    round1 = _run_greedy(cached_engine, prompts, tag="a")
    round2 = _run_greedy(cached_engine, prompts, tag="b")
    assert round1 == cold  # flag on, cache cold: seed behaviour
    assert round2 == cold  # cache warm: bit-identical reuse
    sched = cached_engine.scheduler
    assert sched.prefix_cache_hits > 0
    assert sched.prefix_cache_queries >= sched.prefix_cache_hits
    # Hit rate is visible through /metrics (acceptance criterion).
    rendered = cached_engine.metrics.render().decode()
    assert "vllm:prefix_cache_queries_total" in rendered
    hits = [
        float(ln.rsplit(" ", 1)[1])
        for ln in rendered.splitlines()
        if ln.startswith("vllm:prefix_cache_hits_total")
    ]
    # Per-tier series (ISSUE 14) sum to the scheduler's total.
    assert hits and sum(hits) == float(sched.prefix_cache_hits)


def test_cached_outputs_identical_under_eviction_pressure(tiny_llama):
    """Tiny page pool: eviction and preemption churn the cache while
    requests repeat; outputs must still match the unconstrained cold
    engine bit-for-bit."""
    prompts = [
        list(range(1, 20)),
        list(range(1, 17)) + [60, 61, 62],
        list(range(20, 40)),
        list(range(1, 20)),
    ]
    cold = _run_greedy(_make_engine(tiny_llama), prompts, max_tokens=6)
    poor = _make_engine(
        tiny_llama,
        enable_prefix_caching=True,
        num_kv_pages=8,
        page_size=16,
    )
    for rnd in range(3):
        got = _run_greedy(poor, prompts, max_tokens=6, tag=f"e{rnd}")
        assert got == cold, f"round {rnd} diverged under eviction"


def test_multi_turn_reuses_generated_tokens(tiny_llama):
    """Chat pattern: turn 2's prompt extends turn 1's prompt+completion,
    so pages containing GENERATED tokens are reused too."""
    engine = _make_engine(tiny_llama, enable_prefix_caching=True)
    turn1 = list(range(1, 30))
    out1 = _run_greedy(engine, [turn1], max_tokens=8, tag="t1")[0]
    turn2 = turn1 + list(out1) + [50, 51, 52]
    hits_before = engine.scheduler.prefix_cache_hits
    out2 = _run_greedy(engine, [turn2], max_tokens=8, tag="t2")[0]
    hit = engine.scheduler.prefix_cache_hits - hits_before
    assert hit >= 32  # beyond turn1's 29 prompt tokens -> generated KV
    cold = _run_greedy(_make_engine(tiny_llama), [turn2], max_tokens=8)[0]
    assert out2 == cold
