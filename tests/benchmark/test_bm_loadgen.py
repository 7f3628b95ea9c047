"""The load generator's schedule and the window's arithmetic."""

import json
import os

import pytest

from benchmark import loadgen, stats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _mix(name):
    return json.load(open(os.path.join(
        REPO, "benchmark", "traffic", name + ".json")))


@pytest.mark.parametrize("seeds", [(1, 3000000019), (7, 2147483999)])
def test_every_seed_gets_the_same_lengths_in_another_order(seeds):
    mix = _mix("chat-closed-32")
    n = mix["pool_size"]
    pools = []
    for seed in seeds:
        s = loadgen.Schedule(mix, 32000, seed)
        reqs = [s.request(i) for i in range(n)]
        pools.append(sorted((len(p), o) for p, o in reqs))
        assert all(3 <= t < 32000 for p, _ in reqs[:5] for t in p)
        assert all(len(p) + o <= mix["max_total_tokens"] for p, o in reqs)
    assert pools[0] == pools[1] == sorted(loadgen.length_pool(mix))
    a = loadgen.Schedule(mix, 32000, 5)
    b = loadgen.Schedule(mix, 32000, 5)
    assert [a.request(i) for i in range(8)] == [
        b.request(i) for i in range(8)]
    c = loadgen.Schedule(mix, 32000, 6)
    assert [len(a.request(i)[0]) for i in range(40)] != [
        len(c.request(i)[0]) for i in range(40)]


def test_length_pool_follows_the_stated_distributions():
    mix = _mix("chat-closed-32")
    pool = loadgen.length_pool(mix)
    prompts = sorted(p for p, _ in pool)
    outputs = sorted(o for _, o in pool)
    assert prompts[0] >= 32 and prompts[-1] <= 3072
    assert outputs[0] >= 16 and outputs[-1] <= 768
    assert abs(prompts[len(prompts) // 2] - 384) < 12
    assert abs(outputs[len(outputs) // 2] - 128) < 6


def test_an_arrival_process_the_generator_lacks_is_an_error():
    mix = dict(_mix("chat-closed-32"), arrival={"process": "poisson"})
    load = loadgen.Load(None, "", loadgen.Schedule(mix, 32000, 1))
    with pytest.raises(ValueError, match="unknown arrival"):
        load.start()


def _rec(due, sent, times_counts, want=None, status=200, error=""):
    n = sum(c for _, c in times_counts)
    return {"index": 0, "prompt_len": 10, "want": n if want is None else want,
            "due": due, "sent": sent, "status": status, "error": error,
            "chunks": [list(tc) for tc in times_counts], "done": 0.0}


def test_burst_stream_tpot_and_pooled_gaps():
    # first token alone, then four bursts of 8 tokens 80 ms apart:
    # seven gaps in eight are zero.
    chunks = [(1.0, 1)] + [(1.0 + 0.08 * k, 8) for k in range(1, 5)]
    rec = _rec(0.9, 0.905, chunks)
    assert stats.ttft_ms(rec) == pytest.approx(100.0)
    assert stats.tpot_ms(rec) == pytest.approx(320.0 / 32)
    gaps = stats.gaps_ms(rec)
    assert len(gaps) == 32 and sum(g == 0 for g in gaps) == 28
    assert max(gaps) == pytest.approx(80.0)
    assert stats.percentile(gaps, 50) == 0.0  # why a median gap says nothing
    assert stats.percentile(gaps, 95) == pytest.approx(80.0)


def test_window_counts_requests_by_due_time_and_failures_miss():
    good = _rec(10.2, 10.25, [(10.5, 1), (10.6, 8)])
    early = _rec(9.9, 9.9, [(10.1, 1), (10.3, 8)])  # due before the window
    short = _rec(10.4, 10.4, [(10.7, 1)], want=9)  # cut short
    shed = _rec(10.5, 10.5, [], want=9, status=429, error="queue_full")
    late = _rec(12.5, 12.5, [(12.6, 4)])  # due after it
    w = stats.window_metrics([good, early, short, shed, late], 10.0, 12.0)
    assert (w["attempted"], w["failed"]) == (3, 2)
    assert w["samples"]["ttft"] == 1
    assert w["ttft_p95_ms"] == pytest.approx(300.0)  # from due, not sent
    assert w["loadgen_late_p95_ms"] == pytest.approx(45.0, abs=0.01)
    # every token that arrived inside [10, 12), whichever request's
    assert w["out_tok_s"] == pytest.approx((9 + 9 + 1) / 2.0)


def test_percentile_interpolates():
    assert stats.percentile([], 95) is None
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([0, 10], 50) == 5.0
    assert stats.percentile(list(range(101)), 95) == 95.0
