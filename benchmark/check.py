"""The comparison that decides ``correct``.

Two parts, both scored by the plain reference teacher-forced on the
tokens the system itself emitted (a near-tie cannot send the two down
different continuations):

``values``  requests served with ``logprobs`` on the idle server (the
            single-step path: prefill, then one decode step at a time
            through the paged cache).  For every logprob the API
            returned — the emitted token's and the top-k — the absolute
            difference from the reference's log-softmax at that token.
``tokens``  a sample of the window's own requests (no ``logprobs``, so
            they ran through the fused K-step scan and the mixed steps
            under real overlap).  At every emitted position the
            *regret*: how far the reference's logprob of the emitted
            token lies below the reference's maximum.  Greedy decoding
            on a sound path keeps it at zero but for near-ties.

The statistic of each part is a quantile over positions, and a mean
beside it — never a maximum, never an equality of token ids.  The limits
are data in the configuration's file, placed between the readings of
sound runs and of lower-precision runs on the chip (PERF.md has the
table).
"""

from __future__ import annotations

import numpy as np


def _stats(xs: np.ndarray) -> dict:
    if xs.size == 0:
        return {"n": 0}
    return {
        "n": int(xs.size),
        "mean": float(xs.mean()),
        "p25": float(np.quantile(xs, 0.25)),
        "p50": float(np.quantile(xs, 0.5)),
        "p75": float(np.quantile(xs, 0.75)),
        "p90": float(np.quantile(xs, 0.9)),
        "p99": float(np.quantile(xs, 0.99)),
        "max": float(xs.max()),
    }


def score_sequence(logprobs_fn, prompt: list, emitted: list) -> np.ndarray:
    """Reference log-softmax rows [len(emitted), vocab]: row i is the
    distribution of emitted token i given the prompt and the emitted
    tokens before it."""
    tokens = list(prompt) + list(emitted)
    first = len(prompt) - 1
    return logprobs_fn(tokens, list(range(first, first + len(emitted))))


def value_errors(rows: np.ndarray, run: dict) -> list[dict]:
    """Part 1 readings of one logprobs request."""
    out = []
    for i, (tok, top) in enumerate(zip(run["tokens"], run["top"])):
        got = dict(top)
        got[tok] = run["token_logprobs"][i]
        for tid, lp in got.items():
            out.append({"pos": i, "token": tid, "kind": run["kind"],
                        "system": lp, "reference": float(rows[i, tid]),
                        "err": abs(lp - float(rows[i, tid]))})
    return out


def regrets(rows: np.ndarray, emitted: list) -> np.ndarray:
    idx = np.arange(len(emitted))
    return rows.max(axis=-1) - rows[idx, np.asarray(emitted)]


def score_all(logprobs_fn, logprob_runs: list, window_sample: list):
    """The reference's rows for every sequence of both parts."""
    return (
        [score_sequence(logprobs_fn, r["prompt"], r["tokens"])
         for r in logprob_runs],
        [score_sequence(logprobs_fn, r["prompt"], r["tokens"])
         for r in window_sample],
    )


def evaluate(logprobs_fn, logprob_runs: list, window_sample: list,
             limits: dict) -> dict:
    """Score both parts and hold each stated statistic to its limit.
    ``limits`` is ``{"values": {"p50": x, ...}, "tokens": {...}}``:
    every statistic named there is compared, and printed beside its
    limit by the caller."""
    window_sample = [r for r in window_sample if r["tokens"]]
    lp_rows, win_rows = score_all(logprobs_fn, logprob_runs, window_sample)
    return evaluate_rows(lp_rows, win_rows, logprob_runs, window_sample,
                         limits)


def evaluate_rows(lp_rows: list, win_rows: list, logprob_runs: list,
                  window_sample: list, limits: dict) -> dict:
    value_rows: list[dict] = []
    for rows, run in zip(lp_rows, logprob_runs):
        value_rows += value_errors(rows, run)
    reg_rows: list[dict] = []
    for rows, rec in zip(win_rows, window_sample):
        for i, r in enumerate(regrets(rows, rec["tokens"])):
            reg_rows.append({"request": rec["index"], "pos": i,
                             "kind": "window", "regret": float(r)})
    readings = {
        "values": _stats(np.asarray([r["err"] for r in value_rows])),
        "tokens": _stats(np.asarray([r["regret"] for r in reg_rows])),
    }
    if reg_rows:
        readings["tokens"]["flipped_share"] = float(
            np.mean([r["regret"] > 0 for r in reg_rows]))
    compared = []
    for part, stats in limits.items():
        for stat, limit in stats.items():
            value = readings[part].get(stat)
            compared.append({
                "part": part, "stat": stat, "limit": limit,
                "value": float("nan") if value is None else value,
                "n": readings[part]["n"],
                "ok": value is not None and value <= limit,
            })
    correct = bool(compared) and all(c["ok"] for c in compared)
    result = {"correct": correct, "compared": compared, "readings": readings}
    if not correct:
        result["offending"] = {
            "values": sorted(value_rows, key=lambda r: -r["err"])[:50],
            "tokens": sorted(reg_rows, key=lambda r: -r["regret"])[:50],
        }
    return result


def control_in_place(control_fn, logprob_runs: list, window_sample: list,
                     top: int) -> tuple[list, list]:
    """The control put in the program's place: what a system computing
    ``control_fn`` (the reference in a lower precision) would have
    answered on the same contexts — its greedy token and its top-k
    logprobs at every position of the logprobs requests, its greedy
    token at every position of the window sample.  The result is scored
    by ``evaluate_rows`` against the sound reference's rows, exactly as
    the program's answers are."""
    window_sample = [r for r in window_sample if r["tokens"]]
    ctl_lp, ctl_win = score_all(control_fn, logprob_runs, window_sample)
    runs = []
    for rows, run in zip(ctl_lp, logprob_runs):
        best = rows.argmax(axis=-1)
        ids = np.argsort(-rows, axis=-1)[:, :top]
        runs.append({
            "kind": run["kind"], "prompt": run["prompt"],
            "tokens": [int(t) for t in best],
            "top": [{int(t): float(rows[i, t]) for t in ids[i]}
                    for i in range(len(best))],
            "token_logprobs": [float(rows[i, t]) for i, t in enumerate(best)],
        })
    sample = [dict(rec, tokens=[int(t) for t in rows.argmax(axis=-1)])
              for rows, rec in zip(ctl_win, window_sample)]
    return runs, sample
