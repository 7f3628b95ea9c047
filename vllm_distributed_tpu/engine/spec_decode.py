"""Draftless speculative decoding: n-gram prompt-lookup proposer.

Decode is memory-bound (the weights and the KV stream from HBM once a
micro-step: decode_weight_bw_pct 78.8, ledger, PR 30), so verifying K drafted tokens in ONE
forward pass multiplies tokens-per-HBM-pass by the acceptance length —
the classic speculative-decoding win (Leviathan et al. 2023, PAPERS.md).
A separate draft model would need its own shards, compile cache, and
scheduler lane; the draftless *prompt-lookup* proposer (Saxena 2023; the
`[ngram]` speculative method in vLLM) drafts instead from the request's
OWN token history: if the tail n-gram of prompt+output has occurred
before, the tokens that followed that occurrence are proposed as drafts.
Free to produce, static-shape friendly, and precise exactly where the
memory-bound pain is worst — long repetitive stretches (code, JSON,
extraction, multi-turn chat with quoted context).

Verification is greedy-only and exact: the model runner runs the drafts
through one fused pass (`worker/model_runner._execute_spec_step`), the
accept kernel (`ops/sampling.spec_greedy_accept`) keeps the longest
prefix of drafts matching the argmax chain plus one bonus token, so
accepted tokens are precisely the tokens sequential greedy decode would
have produced — outputs are bit-identical to the non-speculative path
by construction, whatever the proposer guesses.

Scheduling contract (engine/scheduler.py): a spec step is an all-decode
step with ``SchedulerOutput.draft_token_ids`` carrying per-request
drafts and ``decode_steps == 1``; per-request ``num_scheduled_tokens``
is ``1 + len(drafts)`` and the ACTUAL advance (1 + accepted) is
reconciled in ``update_from_output`` from the emitted token count.
"""

from __future__ import annotations

from collections.abc import Sequence

from vllm_distributed_tpu.sampling_params import SamplingParams


def spec_eligible(sp: SamplingParams) -> bool:
    """True when a request can ride a speculative verify pass.

    Greedy-only by design: greedy accept/reject is exact (bit-identical
    outputs), while stochastic rejection sampling would need per-draft
    distribution bookkeeping.  Penalties are excluded because the
    penalized argmax depends on output history that changes *within*
    the pass; logprobs because the verify pass gathers [S, K+1] logits
    rows, not the per-step [S, V] fetches logprobs need.
    """
    return (
        sp.temperature == 0.0
        and sp.logprobs is None
        and sp.repetition_penalty == 1.0
        and sp.presence_penalty == 0.0
        and sp.frequency_penalty == 0.0
    )


class NgramProposer:
    """Per-request n-gram prompt-lookup draft proposer.

    ``propose`` matches the tail ``n``-gram of the token history
    (longest ``n`` first, ``max_n`` down to ``min_n``) against the
    EARLIEST prior occurrence in the history and returns up to
    ``max_draft`` tokens that followed it.  Earliest (not most recent)
    occurrence is deliberate: for periodic text the most recent match
    sits near the tail and truncates the continuation, while the
    earliest match has the whole cycle ahead of it — and in the
    chat/template workloads prompt-lookup targets, the earliest
    occurrence is the instruction/template copy being re-emitted.

    Pure host-side Python on the scheduler thread, anchored on the
    tail's FINAL token: candidate match positions come from C-speed
    ``list.index`` scans for that token, and only candidates are
    slice-compared against the pattern — so the common no-match case
    (non-repetitive text, large vocab) costs one C scan of the
    history, not a Python loop over it.  Wrong guesses cost only the
    wasted verify columns — never correctness.
    """

    # Candidate match positions examined per proposal: bounds the
    # pathological case (the tail's final token everywhere, the longer
    # pattern nowhere) to a constant amount of work per request per
    # step; past the cap the proposer just proposes nothing, which is
    # always safe.
    _MAX_CANDIDATES = 256

    def __init__(self, k: int, min_n: int = 1, max_n: int = 3) -> None:
        if k < 1:
            raise ValueError(f"spec ngram k must be >= 1, got {k}")
        if not 1 <= min_n <= max_n:
            raise ValueError(
                f"need 1 <= min_n <= max_n, got min_n={min_n} max_n={max_n}"
            )
        self.k = k
        self.min_n = min_n
        self.max_n = max_n

    def propose(
        self, tokens: Sequence[int], max_draft: int | None = None
    ) -> list[int]:
        """Draft up to ``min(self.k, max_draft)`` tokens continuing
        ``tokens`` (prompt + output history), or ``[]`` when no tail
        n-gram recurs."""
        budget = self.k if max_draft is None else min(self.k, max_draft)
        t = len(tokens)
        if budget <= 0 or t < self.min_n + 1:
            return []
        if not isinstance(tokens, list):
            tokens = list(tokens)
        last = tokens[-1]
        # Candidate match ends: every occurrence of the tail's final
        # token strictly before the final position, ascending (earliest
        # match wins), via C-speed index() scans.
        ends: list[int] = []
        j = 0
        while len(ends) < self._MAX_CANDIDATES:
            try:
                j = tokens.index(last, j, t - 1)
            except ValueError:
                break
            ends.append(j)
            j += 1
        if not ends:
            return []
        for n in range(min(self.max_n, t - 1), self.min_n - 1, -1):
            pattern = tokens[-n:]
            for end in ends:
                # A length-n match ends at `end` (may overlap the tail
                # itself — periodic text); `end` < t-1 guarantees at
                # least one draft token after it.
                i = end - n + 1
                if i >= 0 and tokens[i : end + 1] == pattern:
                    return tokens[end + 1 : end + 1 + budget]
        return []
