"""The one general traffic generator: it reads a mix's parameters from
``benchmark/traffic/<mix>.json`` and offers that load to a server over
``/v1/completions`` (SSE), from one process and one thread.

Every seed gets the same work in another order.  A mix fixes a pool of
``pool_size`` (prompt length, output length) pairs — the quantiles of
its two distributions, paired by a permutation the file fixes.  The
seed shuffles the order in which the pool is played and draws the
prompts' token ids; nothing else.

A request is timed from when it was **due**: in a closed loop the
instant its client's previous request ended.  ``sent - due`` is how late
the generator ran.  The only arrival process so far is ``closed``; an
open loop (a schedule of due times, and a rate found on the chip) comes
with the cell that proves it (PERF.md section 7).
"""

from __future__ import annotations

import asyncio
import json
import math
import random
import statistics
import time
from dataclasses import dataclass, field

ROUTER_HEADER = {"X-VDT-Router": "1"}  # chunks then carry vdt_token_ids


# ---- the schedule: pure arithmetic, no I/O -----------------------------
def _quantiles(dist: dict, n: int) -> list[int]:
    """n lengths at the mid-quantiles (i + 0.5) / n of ``dist``."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist["dist"] == "lognormal":
            x = dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(u))
        elif dist["dist"] == "uniform":
            x = dist["min"] + (dist["max"] - dist["min"]) * u
        elif dist["dist"] == "fixed":
            x = dist["value"]
        else:
            raise ValueError(f"unknown distribution {dist['dist']!r}")
        out.append(int(min(max(round(x), dist["min"]), dist["max"])))
    return out


def length_pool(mix: dict) -> list[tuple[int, int]]:
    """The mix's fixed multiset of (prompt tokens, output tokens)."""
    n = mix["pool_size"]
    prompts = _quantiles(mix["prompt_len"], n)
    outputs = _quantiles(mix["output_len"], n)
    random.Random(mix["pairing_seed"]).shuffle(outputs)
    cap = mix["max_total_tokens"]
    return [(p, min(o, cap - p)) for p, o in zip(prompts, outputs)]


class Schedule:
    """Endless seeded stream of requests: each pass over the pool is a
    fresh shuffle drawn from the seed."""

    def __init__(self, mix: dict, vocab_size: int, seed: int) -> None:
        self.mix = mix
        self.vocab = vocab_size
        self.seed = seed
        self._lengths = length_pool(mix)
        self._orders: dict = {}

    def _order(self, what: str, cycle: int, size: int) -> list[int]:
        if (what, cycle) not in self._orders:
            idx = list(range(size))
            random.Random(f"{self.seed}/{what}/{cycle}").shuffle(idx)
            self._orders = {k: v for k, v in self._orders.items()
                            if k[0] != what}  # keep the current cycle only
            self._orders[what, cycle] = idx
        return self._orders[what, cycle]

    def request(self, i: int) -> tuple[list[int], int]:
        """The i-th request of this seed: (prompt token ids, max_tokens)."""
        size = len(self._lengths)
        order = self._order("len", i // size, size)
        p_len, o_len = self._lengths[order[i % size]]
        rng = random.Random(f"{self.seed}/tok/{i}")
        return rng.choices(range(3, self.vocab), k=p_len), o_len


# ---- the client -----------------------------------------------------------
@dataclass
class Record:
    index: int
    prompt: list[int]
    want: int
    due: float
    sent: float | None = None
    status: int | None = None
    error: str = ""
    chunks: list = field(default_factory=list)  # [time, n tokens]
    token_ids: list = field(default_factory=list)
    done: float | None = None

    def to_dict(self) -> dict:
        return {
            "index": self.index, "prompt_len": len(self.prompt),
            "want": self.want, "due": self.due, "sent": self.sent,
            "status": self.status, "error": self.error,
            "chunks": self.chunks, "done": self.done,
        }


def completion_body(prompt: list[int], max_tokens: int, **extra) -> dict:
    return {
        "prompt": prompt, "max_tokens": max_tokens, "temperature": 0,
        "ignore_eos": True, **extra,
    }


async def stream_one(http, url: str, rec: Record) -> None:
    """Send one streaming completion and record each chunk's arrival.
    Never raises for a failed request: the failure is the record."""
    import aiohttp

    body = completion_body(rec.prompt, rec.want, stream=True)
    rec.sent = time.monotonic()
    try:
        async with http.post(
            url + "/v1/completions", json=body, headers=ROUTER_HEADER
        ) as r:
            rec.status = r.status
            if r.status != 200:
                rec.error = (await r.text())[:300]
                return
            async for raw in r.content:
                if not raw.startswith(b"data: "):
                    continue
                now = time.monotonic()
                payload = raw[6:].strip()
                if payload == b"[DONE]":
                    break
                frame = json.loads(payload)
                if "error" in frame:
                    rec.error = json.dumps(frame["error"])[:300]
                    return
                ids = [
                    t for c in frame.get("choices", [])
                    for t in c.get("vdt_token_ids", [])
                ]
                if ids:
                    rec.chunks.append([now, len(ids)])
                    rec.token_ids.extend(ids)
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
            json.JSONDecodeError) as e:
        rec.error = f"{type(e).__name__}: {e}"[:300]
    finally:
        rec.done = time.monotonic()


class Load:
    """Offers a mix's load until ``stop()``; keeps every record."""

    def __init__(self, http, url: str, schedule: Schedule) -> None:
        self.http, self.url, self.schedule = http, url, schedule
        self.records: list[Record] = []
        self._next = 0
        self._stopping = False
        self._tasks: list[asyncio.Task] = []

    def start(self) -> None:
        arrival = self.schedule.mix["arrival"]
        if arrival["process"] == "closed":
            self._tasks = [
                asyncio.create_task(self._client())
                for _ in range(arrival["clients"])
            ]
        else:
            raise ValueError(f"unknown arrival {arrival['process']!r}")

    def _make(self, due: float) -> Record:
        i = self._next
        self._next += 1
        prompt, want = self.schedule.request(i)
        rec = Record(index=i, prompt=prompt, want=want, due=due)
        self.records.append(rec)
        return rec

    async def _client(self) -> None:
        while not self._stopping:
            rec = self._make(time.monotonic())
            await stream_one(self.http, self.url, rec)
            if rec.status != 200 or rec.error:
                await asyncio.sleep(0.05)  # a dead server must not spin us

    def unfinished(self, t0: float, t1: float) -> int:
        return sum(
            1 for r in self.records if t0 <= r.due < t1 and r.done is None
        )

    async def stop(self) -> None:
        """Stop offering load; requests still in flight are cancelled
        (the server aborts a request whose connection closes)."""
        self._stopping = True
        for t in self._tasks:
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)
