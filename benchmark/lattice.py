"""The single-step programs a closed-loop cell's traffic can meet, and
for each of them a group of prompts that makes one step of it.

The server compiles one single-step (prefill / mixed) program per
(token bucket, longest-chunk bucket): a step's tokens and its longest
new chunk are each padded to a power of two.  Which buckets a run meets
is chance — how many clients' requests ended in one step, and how long
the next prompts are — so the warm-up does not wait for chance: it
enumerates every bucket that ``clients`` sequences can make, and plays
one step of each beside a decoding batch (``run.shape_sweep``).  The
rule (powers of two, the floor, the step's token budget) is the
server's; its three numbers are data in the traffic file's ``sweep``.
"""

from __future__ import annotations


def next_power_of_2(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


def buckets(clients: int, sweep: dict) -> list[tuple[int, int]]:
    """Every (token bucket, longest-chunk bucket) of a step that holds
    the batch of a closed loop of ``clients``: ``n`` of them (1 to
    ``max_arrivals``) bring a prompt chunk — the longest in (q/2, q],
    the others 1 to q tokens — and the rest, less up to ``slack``
    whose successors have not arrived yet, decode one token each."""
    budget, floor = sweep["token_budget"], sweep["min_token_bucket"]
    found = set()
    q = 1
    while q <= budget:
        longest = q // 2 + 1
        for n in range(1, sweep["max_arrivals"] + 1):
            decodes = clients - n
            lo = max(decodes - sweep["slack"], 0) + longest + (n - 1)
            hi = min(decodes + n * q, budget)
            t = max(next_power_of_2(lo), floor)
            while lo <= hi and (t == floor or t // 2 < hi):
                found.add((t, q))
                t *= 2
        q *= 2
    return sorted(found)


def group_for(t: int, q: int, background: int, seats: int,
              sweep: dict) -> list[int] | None:
    """Prompt lengths which, arriving in one step beside ``background``
    decoding sequences, make a step of bucket (t, q): the longest in
    (q/2, q], none above q, at most ``seats`` of them, and the step's
    tokens in (t/2, t] — aimed at the bucket's middle, so that a
    background sequence more or less changes nothing.  ``None`` where
    no such group exists."""
    top = min(t, sweep["token_budget"])
    floor = sweep["min_token_bucket"]
    least = 0 if t <= floor else t // 2 + 1  # tokens the bucket needs
    longest = q // 2 + 1
    want = max((least + top) // 2 - background, longest)
    want = min(want, seats * q, top - background)
    if want < longest or background + want < least:
        return None
    first = min(q, want)
    rest = want - first
    group = [first]
    while rest > 0:
        group.append(min(q, rest))
        rest -= group[-1]
    return group if len(group) <= seats else None
