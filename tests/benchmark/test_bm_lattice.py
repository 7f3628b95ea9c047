"""The warm-up's sweep: every single-step bucket a closed loop's
clients can make is enumerated, and each has a group of prompts that
makes one step of exactly that bucket beside the decoding batch."""

import json
import os

import pytest

from benchmark import bytes_model, lattice

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _mix(path):
    return json.load(open(os.path.join(REPO, path)))


MIXES = {
    "chat-closed-32": _mix("benchmark/traffic/chat-closed-32.json"),
    "tiny-closed": _mix("tests/benchmark/rehearsal/traffic/tiny-closed.json"),
}
CASES = [
    (name, t, q) for name, mix in MIXES.items()
    for t, q in lattice.buckets(
        mix["arrival"]["clients"], mix["warmup"]["sweep"])
]


@pytest.mark.parametrize("name,t,q", CASES)
def test_every_bucket_has_a_group_that_makes_one_step_of_it(name, t, q):
    mix = MIXES[name]
    sweep = mix["warmup"]["sweep"]
    group = lattice.group_for(t, q, sweep["background"], sweep["seats"], sweep)
    assert group is not None
    assert len(group) <= sweep["seats"]
    # the step's tokens: one of each decoding sequence, and the prompts
    tokens = sweep["background"] + sum(group)
    assert tokens <= sweep["token_budget"]
    assert max(lattice.next_power_of_2(tokens),
               sweep["min_token_bucket"]) == t
    assert lattice.next_power_of_2(max(group)) == q
    # a background sequence more or less leaves the step in its bucket
    for off in (-1, 1):
        assert max(lattice.next_power_of_2(tokens + off),
                   sweep["min_token_bucket"]) == t or t == q == 16
    # the sequence bucket is the traffic's own
    seqs = sweep["background"] + len(group)
    assert seqs <= mix["arrival"]["clients"] or name == "tiny-closed"
    assert lattice.next_power_of_2(seqs) <= max(
        lattice.next_power_of_2(mix["arrival"]["clients"]), 8)


def test_the_lattice_holds_what_the_cells_traffic_can_make():
    """Steps drawn the way the scheduler makes them — n clients' next
    prompts (chunked to the budget) beside the others' decodes — all
    fall into enumerated buckets."""
    import random

    from benchmark import loadgen

    mix = MIXES["chat-closed-32"]
    sweep = mix["warmup"]["sweep"]
    clients = mix["arrival"]["clients"]
    found = set(lattice.buckets(clients, sweep))
    lengths = [p for p, _ in loadgen.length_pool(mix)]
    rng = random.Random(5)
    for _ in range(20000):
        n = rng.choice([1, 1, 1, 2, 2, 3, 4, 6, 8])
        budget = sweep["token_budget"] - (clients - n)
        chunks = []
        for length in rng.choices(lengths, k=n):
            if rng.random() < 0.1:  # the rest of a prompt chunked before
                length = rng.randint(1, length)
            take = min(length, budget)
            if take > 0:
                chunks.append(take)
                budget -= take
        tokens = clients - n + sum(chunks)
        shape = (max(lattice.next_power_of_2(tokens), 16),
                 lattice.next_power_of_2(max(chunks)))
        assert shape in found, (shape, chunks)
    assert len(found) == 34  # what a cold first run compiles, and no more


def test_a_bucket_no_group_can_make_is_reported_not_guessed():
    sweep = dict(MIXES["chat-closed-32"]["warmup"]["sweep"])
    # 2,048 tokens of prompts no longer than 2 cannot sit in 12 seats
    assert lattice.group_for(2048, 2, 20, 12, sweep) is None


def test_experts_touched_runs_from_top_k_to_all_of_them():
    cfg = {"num_local_experts": 8, "num_experts_per_tok": 2}
    assert bytes_model.experts_touched(cfg, 1) == pytest.approx(2.0)
    assert bytes_model.experts_touched(cfg, 31) == pytest.approx(8.0, abs=2e-3)
    conf = _mix("benchmark/configs/mixtral-8x7b-cut-int8.json")
    one = bytes_model.decode_weight_bytes(conf, 1)
    full = bytes_model.decode_weight_bytes(conf, 31)
    assert one == pytest.approx(1.315e9, rel=1e-3)
    assert full == pytest.approx(4.488e9, rel=1e-3)
    # 16 tokens a page, 3 layers: 192 KiB a page, 47,700 pages 8.73 GiB
    assert bytes_model.kv_bytes_per_token(conf) == 12288
    assert 47700 * 16 * 12288 / 2**30 == pytest.approx(8.73, abs=5e-3)
