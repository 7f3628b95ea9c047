#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The parent (this process) writes the configuration's ``config.json``,
starts ``python -m vllm_distributed_tpu serve ... --load-format dummy``
as a child, warms it by traffic until no new step program has been met
for a stated stretch, measures for ``--seconds``, sends the check's
logprob requests to the then idle server, stops it, and only then
touches JAX itself: the plain reference scores what the server emitted.
The last line of stdout is the result; everything else is on earlier
lines and under ``chiprun_out/benchmark/<cell>/``.

No chip, a device kind that ``benchmark/peaks.json`` does not list, a
worker fallback or a kernel backend other than compiled Pallas is an
error and a non-zero exit, never a number.  ``--rehearsal`` drives the
same code on the CPU at toy widths from ``tests/benchmark/rehearsal``;
its last line carries counts only and no metric.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import lattice, loadgen, stats  # noqa: E402
from benchmark.server import Server, ServerError, parse_metrics  # noqa: E402

BENCH_DIR = os.path.join(REPO, "benchmark")
REHEARSAL_DIR = os.path.join(REPO, "tests", "benchmark", "rehearsal")
MAX_SERVER_SEED = 2147483629  # the server's --seed is kept inside int32


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:6.1f}s] {msg}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with the files it names resolved."""

    def __init__(self, name: str, rehearsal: bool) -> None:
        root = REHEARSAL_DIR if rehearsal else REPO
        data = REHEARSAL_DIR if rehearsal else BENCH_DIR
        self.manifest = load_json(os.path.join(
            root, "manifest.json" if rehearsal else "BENCHMARK.json"))
        # The metrics are always the real manifest's, so that a rehearsal
        # drives every reader.
        self.metric_lists = load_json(os.path.join(REPO, "BENCHMARK.json"))
        self.rehearsal = rehearsal
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has "
                f"{sorted(cells)}"
            )
        self.name = name
        self.entry = cells[name]
        conf = next(c for c in self.manifest["configs"]
                    if c["name"] == self.entry["config"])
        self.config_file = load_json(os.path.join(root, conf["file"]))
        self.bench = self.config_file["benchmark"]
        self.hf = {k: v for k, v in self.config_file.items()
                   if k != "benchmark"}
        self.mix = load_json(os.path.join(
            data, "traffic", self.entry["traffic"] + ".json"))
        self.peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))

    def model_cfg(self) -> dict:
        """The sizes the reference needs, from the configuration."""
        cfg = dict(self.hf)
        cfg.setdefault(
            "head_dim", cfg["hidden_size"] // cfg["num_attention_heads"])
        return cfg

    def metrics_for(self, kind: str) -> list[dict]:
        """The manifest's metrics of one kind that this cell reports."""
        return [m for m in self.metric_lists[kind]
                if self.rehearsal or "workloads" not in m
                or self.name in m["workloads"]]


# ---- the served session -------------------------------------------------
async def get_text(http, url: str) -> str:
    async with http.get(url) as r:
        if r.status != 200:
            raise ServerError(f"GET {url}: HTTP {r.status}")
        return await r.text()


async def scrape(http, server: Server) -> dict:
    return parse_metrics(await get_text(http, server.url + "/metrics"))


async def wait_health(http, server: Server, limit_s: float) -> None:
    import aiohttp

    while True:
        if not server.alive():
            raise ServerError(
                f"the server exited {server.proc.returncode} before "
                f"/health:\n{server.log_tail()}"
            )
        try:
            async with http.get(server.url + "/health") as r:
                if r.status == 200:
                    return
        except aiohttp.ClientError:
            pass
        if time.monotonic() - server.t_spawn > limit_s:
            raise ServerError(f"no /health within {limit_s:.0f}s")
        await asyncio.sleep(0.5)


async def warm_up(http, server, load, spec: dict) -> dict:
    """Offer the cell's own load until ``vllm:xla_compiles_total`` has
    not risen while ``flat_requests`` more requests completed (a compile
    stalls every request, so a stall cannot pass for flatness)."""
    t_begin = time.monotonic()
    compiles, done_at_rise, trail = None, 0, []
    while True:
        await asyncio.sleep(spec["poll_s"])
        now = time.monotonic()
        m = await scrape(http, server)
        if m.get("vllm:worker_fallbacks_total", 0) > 0:
            raise ServerError("vllm:worker_fallbacks_total > 0 in warm-up")
        c = int(m.get("vllm:xla_compiles_total", 0))
        done = sum(1 for r in load.records if r.done is not None)
        bad = [r for r in load.records if r.done is not None
               and (r.status != 200 or r.error)]
        if bad:
            raise ServerError(
                f"a warm-up request failed: HTTP {bad[0].status} "
                f"{bad[0].error}")
        if c != compiles:
            compiles, done_at_rise = c, done
            trail.append([round(now - t_begin, 1), done, c])
        if (done - done_at_rise >= spec["flat_requests"]
                and done >= spec["min_requests"]):
            flat = True
            break
        if now - t_begin > spec["cap_s"]:
            flat = False
            say(f"warm-up phase ended at its cap of {spec['cap_s']}s")
            break
    return {"seconds": time.monotonic() - t_begin, "requests": done,
            "compiles": compiles, "flat": flat, "trail": trail,
            "compile_s": m.get("vllm:xla_compile_seconds_sum", 0.0)}


async def shape_sweep(http, server, cell: Cell, sweep: dict, seed: int) -> dict:
    """The first part of the warm-up, for a cell whose step shapes are
    made by arrivals beside a full decode batch.  ``background`` long
    requests hold the batch in decode; then, for every (token bucket,
    longest-chunk bucket) the cell's clients can make
    (``lattice.buckets``), a group of prompts sent at one instant, one
    token asked of each, makes one mixed step of that bucket.  A
    group's requests were served by one step when their tokens came
    back together; a group whose tokens came ``same_step_ms`` apart
    fell into two steps and is sent again, up to ``retries`` times.
    (The compile counter cannot tell: the server refreshes it at most
    every two seconds.)"""
    t_begin = time.monotonic()
    rng = random.Random(f"{seed}/sweep")
    vocab = cell.hf["vocab_size"]

    def record(i: int, n_prompt: int, want: int) -> loadgen.Record:
        return loadgen.Record(
            index=i, prompt=rng.choices(range(3, vocab), k=n_prompt),
            want=want, due=time.monotonic())

    held: dict[int, loadgen.Record] = {}
    renewed = 0

    async def hold(i: int) -> None:
        """One seat of the decoding batch: a long request, and when it
        has run its length another (a renewal arriving beside a group
        spoils that group's step, which is then sent again)."""
        nonlocal renewed
        while True:
            held[i] = record(-1 - i, sweep["background_prompt"],
                             sweep["background_tokens"])
            await loadgen.stream_one(http, server.url, held[i])
            if held[i].status != 200 or held[i].error:
                raise ServerError(
                    f"a background request of the sweep failed: HTTP "
                    f"{held[i].status} {held[i].error}")
            renewed += 1

    def decoding() -> bool:
        for task in tasks:
            if task.done():
                task.result()  # raises what the seat raised
        return len(held) == len(tasks) and all(
            r.chunks for r in held.values())

    tasks = [asyncio.create_task(hold(i))
             for i in range(sweep["background"])]
    todo = lattice.buckets(cell.mix["arrival"]["clients"], sweep)
    trail, unmade, capped = [], [], False
    try:
        while not decoding():
            await asyncio.sleep(0.1)
        for t, q in todo:
            group = lattice.group_for(
                t, q, sweep["background"], sweep["seats"], sweep)
            if group is None:
                unmade.append([t, q])
                continue
            for attempt in range(1 + sweep["retries"]):
                if time.monotonic() - t_begin > sweep["cap_s"]:
                    capped = True
                    break
                recs = [record(0, n, sweep["probe_tokens"]) for n in group]
                await asyncio.gather(
                    *(loadgen.stream_one(http, server.url, r) for r in recs))
                decoding()
                bad = [r for r in recs
                       if r.status != 200 or r.error or not r.chunks]
                if bad:
                    raise ServerError(
                        f"a sweep request failed: HTTP {bad[0].status} "
                        f"{bad[0].error}")
                firsts = [r.chunks[0][0] for r in recs]
                apart_ms = (max(firsts) - min(firsts)) * 1e3
                if apart_ms <= sweep["same_step_ms"]:
                    break
            if capped:
                say(f"warm-up sweep ended at its cap of {sweep['cap_s']}s")
                break
            trail.append([t, q, attempt + 1, round(apart_ms, 2)])
    finally:
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    m = await scrape(http, server)
    return {"seconds": time.monotonic() - t_begin, "buckets": len(todo),
            "sends": sum(row[2] for row in trail), "unmade": unmade,
            "capped": capped, "renewed": renewed, "trail": trail,
            "compiles": int(m.get("vllm:xla_compiles_total", 0))}


async def logprob_requests(http, server, cell: Cell, seed: int) -> list:
    """Part 1 of the check: seeded prompts from the cell's own length
    pool, sent with ``logprobs`` to the idle server — the first
    ``alone`` one at a time, the next ``overlap`` together."""
    spec = cell.mix["check"]
    sched = loadgen.Schedule(cell.mix, cell.hf["vocab_size"], seed + 1)
    n = spec["alone"] + spec["overlap"]
    new = spec["new_tokens"]

    async def one(i: int, kind: str) -> dict:
        prompt, _ = sched.request(i)
        prompt = prompt[: cell.mix["max_total_tokens"] - new - 1]
        body = loadgen.completion_body(prompt, new, logprobs=spec["top"])
        async with http.post(server.url + "/v1/completions", json=body) as r:
            if r.status != 200:
                raise ServerError(
                    f"logprobs request: HTTP {r.status}: "
                    f"{(await r.text())[:300]}")
            lp = (await r.json())["choices"][0]["logprobs"]
        return {"kind": kind, "prompt": prompt,
                "tokens": [int(t) for t in lp["tokens"]],
                "top": [{int(k): v for k, v in d.items()}
                        for d in lp["top_logprobs"]],
                "token_logprobs": lp["token_logprobs"]}

    out = []
    for i in range(spec["alone"]):
        out.append(await one(i, "alone"))
    out += await asyncio.gather(
        *(one(i, "overlap") for i in range(spec["alone"], n)))
    return out


async def session(server: Server, cell: Cell, args) -> dict:
    import aiohttp

    mix = cell.mix
    timeout = aiohttp.ClientTimeout(total=None, sock_read=600)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as http:
        await wait_health(http, server, mix["warmup"]["health_limit_s"])
        t_health = time.monotonic()
        say(f"/health after {t_health - server.t_spawn:.1f}s")
        m_boot = await scrape(http, server)
        spec = mix["warmup"]
        sweep = spec.get("sweep")
        if args.check_only:
            sweep = None
            spec = dict(spec, flat_requests=0,
                        min_requests=mix["check"]["calibration_requests"])
        if sweep:
            swept = await shape_sweep(http, server, cell, sweep, args.seed)
            say(f"warm-up sweep: {swept['seconds']:.1f}s, "
                f"{swept['buckets']} step buckets in {swept['sends']} "
                f"arrival groups beside {sweep['background']} decoding "
                f"requests, {swept['compiles']} step programs met, "
                f"{len(swept['unmade'])} buckets no group could make")
        load = loadgen.Load(
            http, server.url,
            loadgen.Schedule(mix, cell.hf["vocab_size"], args.seed))
        load.start()
        try:
            warm = await warm_up(http, server, load, spec)
        except BaseException:
            await load.stop()  # no client outlives a failed warm-up
            raise
        say(f"warm-up: {warm['seconds']:.1f}s, {warm['requests']} requests, "
            f"{warm['compiles']} step programs met, flat={warm['flat']}")
        if sweep:
            warm["sweep"] = swept

        t0 = time.monotonic()
        t1 = t0 + args.seconds
        profile = None
        if args.trace:
            async def take_profile():
                p = mix["trace"]["profile_seconds"]
                await asyncio.sleep(max((args.seconds - p) / 2, 0))
                t_a = time.monotonic()
                async with http.post(
                    server.url + f"/debug/profile?seconds={p}") as r:
                    body = await r.json()
                    if r.status != 200:
                        raise ServerError(f"/debug/profile: {body}")
                return {"path": body["path"], "start": t_a,
                        "end": time.monotonic(), "seconds": p}
            profile = asyncio.create_task(take_profile())
        await asyncio.sleep(max(t1 - time.monotonic(), 0))
        m_end = await scrape(http, server)
        # Keep offering load until the window's own requests are done,
        # so that the last of them finish under the load they began in.
        t_cool = time.monotonic()
        while (load.unfinished(t0, t1)
               and time.monotonic() - t_cool < mix["cooldown_cap_s"]):
            await asyncio.sleep(0.1)
        await load.stop()
        prof = await profile if profile else None
        spans = None
        if args.trace:
            spans = json.loads(await get_text(
                http, server.url + "/debug/traces"))["traces"]
        await asyncio.sleep(0.5)  # cancelled requests leave the engine
        t_lp = time.monotonic()
        lp = await logprob_requests(http, server, cell, args.seed)
        say(f"check, part 1: {len(lp)} logprobs requests in "
            f"{time.monotonic() - t_lp:.1f}s")
        m_last = await scrape(http, server)
    return {"t_health": t_health, "t0": t0, "t1": t1, "warm": warm,
            "records": load.records, "m_boot": m_boot, "m_end": m_end,
            "m_last": m_last, "profile": prof, "spans": spans,
            "logprob_runs": lp}


# ---- after the server has gone: device, reference, traces --------------
def device_info(rehearsal: bool, peaks: dict, chips: int) -> dict:
    """Now that no child holds the chip the parent may look at it."""
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(REPO, ".jax_cache"))
    dev = jax.devices()
    info = {"platform": dev[0].platform, "kind": dev[0].device_kind,
            "count": len(dev)}
    if rehearsal:
        return info
    if info["platform"] != "tpu" or info["count"] < chips:
        raise ServerError(
            f"this cell needs {chips} TPU chip(s); JAX found {info}")
    if info["kind"] not in peaks:
        raise ServerError(
            f"device kind {info['kind']!r} is not in benchmark/peaks.json")
    return info


def run_check(cell: Cell, ses: dict, counted_ok: list, seed: int,
              server_seed: int, out_dir: str, control_bits: int = 0) -> dict:
    from benchmark import check

    ref = importlib.import_module(
        "benchmark.reference." + cell.bench["reference"])
    cfg = cell.model_cfg()
    t_a = time.monotonic()
    weights = ref.make_weights(cfg, server_seed)
    import jax

    jax.block_until_ready(weights)
    t_b = time.monotonic()
    n = min(cell.mix["check"]["window_sample"], len(counted_ok))
    sample = random.Random(f"{seed}/check").sample(counted_ok, n)
    window = [{"index": r.index, "prompt": r.prompt, "tokens": r.token_ids}
              for r in sample if r.token_ids]
    lp_rows, win_rows = check.score_all(
        lambda toks, pos: ref.logprobs(weights, cfg, toks, pos),
        ses["logprob_runs"], window)
    result = check.evaluate_rows(
        lp_rows, win_rows, ses["logprob_runs"], window, cell.bench["check"])
    say(f"check: reference weights {t_b - t_a:.1f}s, forward passes "
        f"{time.monotonic() - t_b:.1f}s")
    if control_bits:
        # Calibration only: the reference in the next precision down,
        # put in the program's place and scored like the program.
        del weights
        low = ref.make_weights(cfg, server_seed, bits=control_bits)
        runs, sample_c = check.control_in_place(
            lambda toks, pos: ref.logprobs(low, cfg, toks, pos),
            ses["logprob_runs"], window, cell.mix["check"]["top"])
        ctl = check.evaluate_rows(
            lp_rows, win_rows, runs, sample_c, cell.bench["check"])
        result["control"] = {"bits": control_bits, "correct": ctl["correct"],
                             "readings": ctl["readings"]}
        say(f"control (reference at int{control_bits} in the program's "
            f"place): correct={ctl['correct']} readings: "
            + json.dumps(ctl["readings"]))
    for row in result["compared"]:
        say(f"check: {row['part']}.{row['stat']} = {row['value']:.6g} "
            f"(limit {row['limit']:.6g}, n={row['n']}) "
            f"{'ok' if row['ok'] else 'OVER THE LIMIT'}")
    say("check readings: " + json.dumps(result["readings"]))
    if not result["correct"]:
        path = os.path.join(out_dir, "check.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        say(f"check FAILED: offending readings, positions and step kinds "
            f"written to {os.path.relpath(path, REPO)}")
    return result


def memory_in_use(cell: Cell, facts: dict, ses: dict, control: bool) -> int:
    """``memory_peak_bytes``: the most bytes the run's scrapes found in
    use on the chip — at boot, at the window's end with the load still
    on, after the check.  ``vllm:hbm_live_bytes`` counts the whole KV
    pool from the moment it is reserved; a reserved page that holds
    nothing is not memory in use, so the pool counts only by the share
    of its pages that requests held (``vllm:gpu_cache_usage_perc``).  A
    step's transient peak between scrapes is not seen: the server holds
    the chip, and only it can ask ``memory_stats``."""
    from benchmark import bytes_model

    if cell.rehearsal:
        return 0
    pool = (facts["kv_pool_pages"] * facts["kv_page_tokens"]
            * bytes_model.kv_bytes_per_token(cell.model_cfg()))
    logged = facts["kv_pool_gib"] * 2**30
    if not control and abs(pool - logged) > 0.02 * logged:
        raise ServerError(
            f"the KV pool is {pool} bytes by the configuration's sizes and "
            f"{logged:.0f} by the server's log")
    rows = []
    for when in ("m_boot", "m_end", "m_last"):
        live = ses[when].get("vllm:hbm_live_bytes", 0)
        held = ses[when].get("vllm:gpu_cache_usage_perc", 0.0)
        rows.append((int(live - pool * (1.0 - held)), when, live, held))
    say("memory: " + "; ".join(
        f"{when[2:]}: {live / 1e9:.2f} GB live, {100 * held:.1f}% of the "
        f"{pool / 1e9:.2f} GB pool held -> {used / 1e9:.2f} GB in use"
        for used, when, live, held in rows))
    return max(rows)[0]


def layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric is a reader of its own under
    ``benchmark/layer_metrics/``; one that finds nothing to read returns
    ``None`` and is left out."""
    out = {}
    for m in cell.metrics_for("per_layer"):
        reader = importlib.import_module(
            "benchmark.layer_metrics." + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, toy widths, counts only (tests)")
    ap.add_argument("--check-only", action="store_true",
                    help="calibrating the check: a short warm-up that does "
                    "not wait for the compile counter; no metric")
    ap.add_argument("--control-bits", type=int, choices=(0, 4), default=0,
                    help="calibrating the check: also score the reference "
                    "at this weight precision, put in the program's place")
    ap.add_argument("--server-arg", action="append", default=[],
                    help="extra server argument (calibrating the check's "
                    "lower-precision controls); the result line is then "
                    "marked as a control and carries no metric")
    ap.add_argument("--config-set", action="append", default=[],
                    metavar="KEY=JSON",
                    help="override one key of the configuration (the "
                    "controls' depth); marked like --server-arg")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(REPO, "vllm_distributed_tpu")):
        print("the program (vllm_distributed_tpu/) is not in this "
              "checkout; nothing to measure", file=sys.stderr)
        return 2
    cell = Cell(args.workload, args.rehearsal)
    for item in args.config_set:
        key, _, value = item.partition("=")
        cell.hf[key] = json.loads(value)
    control = bool(args.server_arg or args.config_set)
    out_dir = os.path.join(REPO, "chiprun_out", "benchmark",
                           cell.name + ("-control" if control else ""))
    os.makedirs(out_dir, exist_ok=True)
    server_seed = args.seed % MAX_SERVER_SEED
    env = dict(cell.bench.get("env", {}))
    server = Server(
        {"config": cell.hf, "server_args": cell.bench["server_args"],
         "trace_env": cell.bench.get("trace_env", {})},
        server_seed, out_dir, trace=bool(args.trace),
        extra_args=args.server_arg, env=env)
    say(f"cell {cell.name}, seed {args.seed}, {args.seconds:g}s window, "
        f"trace {args.trace}" + (", CPU REHEARSAL" if args.rehearsal else ""))
    def on_signal(signum, _frame):
        raise KeyboardInterrupt(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_signal)
    server.start()
    rc = None
    try:
        ses = asyncio.run(session(server, cell, args))
        rc = server.stop()
        say(f"server stopped (exit {rc})")
        return report(cell, args, server, ses, rc, out_dir, server_seed)
    except (ServerError, OSError, asyncio.TimeoutError, KeyError,
            ValueError) as e:
        print(f"benchmark failed: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    finally:
        server.stop()  # whatever happened, no child outlives the run


def report(cell, args, server, ses, rc, out_dir, server_seed) -> int:
    facts = server.log_facts()
    say(f"server log: {facts}")
    want = cell.bench["require_backends"]
    got = facts.get("backends", {})
    if any(got.get(k) != v for k, v in want.items()):
        raise ServerError(f"server chose backends {got}; the cell "
                          f"requires {want}")
    if rc != 0:
        raise ServerError(f"server exit code {rc} on SIGTERM")
    if ses["m_last"].get("vllm:worker_fallbacks_total", 0) > 0:
        raise ServerError("vllm:worker_fallbacks_total > 0")

    t0, t1 = ses["t0"], ses["t1"]
    records = [r.to_dict() for r in ses["records"]]
    win = stats.window_metrics(records, t0, t1)
    setup_s = t0 - T_START
    counted_ok = [rec for rec, d in zip(ses["records"], records)
                  if t0 <= d["due"] < t1 and stats.ok(d)]
    window_compiles = int(
        ses["m_end"].get("vllm:xla_compiles_total", 0)
        - ses["warm"]["compiles"])
    stall_s = (ses["m_end"].get("vllm:xla_compile_seconds_sum", 0.0)
               - ses["warm"]["compile_s"])
    say(f"window: {win['attempted']} requests due, {win['failed']} failed; "
        f"samples {win['samples']}; {window_compiles} step programs first "
        f"met inside the window ({stall_s:.2f}s of first calls)")
    bad = [r for r in records if t0 <= r["due"] < t1 and not stats.ok(r)]
    for r in bad[:5]:
        say(f"  failed request {r['index']}: HTTP {r['status']} "
            f"{r['error']!r}, {sum(n for _, n in r['chunks'])}/{r['want']} "
            "tokens")

    t_check = time.monotonic()
    dev = device_info(args.rehearsal, cell.peaks, cell.entry["chips"])
    result = run_check(cell, ses, counted_ok, args.seed, server_seed, out_dir,
                       args.control_bits)
    check_s = time.monotonic() - t_check
    say(f"check_s = {check_s:.1f} (no metric)")

    e2e_all = {"setup_s": setup_s, **{k: win[k] for k in (
        "ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms", "itl_p95_ms",
        "out_tok_s")}}
    say("end to end" + (" (traced run)" if args.trace else "") + ": "
        + json.dumps(e2e_all))
    device = dict(dev, memory_peak_bytes=memory_in_use(
        cell, facts, ses, control=bool(args.server_arg or args.config_set)))
    summary = {
        "cell": cell.name, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "server_args": server.argv, "facts": facts,
        "warmup": ses["warm"], "window": win, "end_to_end": e2e_all,
        "window_compiles": window_compiles, "window_first_call_s": stall_s,
        "check": result,
        "check_s": check_s, "device": device,
        "t0": t0, "t1": t1,
        # every request that touched the window, so that the window's
        # arithmetic can be redone by hand
        "records": [r for r in records if r["done"] is None
                    or (r["done"] >= t0 and r["due"] < t1)],
    }
    line: dict = {
        "correct": bool(result["correct"]),
        "attempted": win["attempted"], "failed": win["failed"],
    }
    if args.trace:
        from benchmark import trace_reduce

        try:
            trace = trace_reduce.reduce_profile(ses["profile"]["path"])
        except ValueError:
            if not args.rehearsal:
                raise
            trace = None  # a CPU trace has no device plane
        ctx = {"cell": cell, "window": win, "t0": t0, "t1": t1,
               "spans": ses["spans"], "trace": trace,
               "window_compiles": window_compiles,
               "programs_compiled": ses["warm"]["compiles"],
               "config": cell.model_cfg(), "peak": cell.peaks.get(
                   dev["kind"], {}), "records": records,
               "wall_offset": time.time() - time.monotonic()}
        metrics = layer_metrics(cell, ctx)
        sweep = cell.mix["warmup"].get("sweep")
        if sweep and ses["spans"]:
            from benchmark.layer_metrics import _spans

            shapes = _spans.step_shapes(ctx) or {(0, 0, 0)}
            swept = set(lattice.buckets(
                cell.mix["arrival"]["clients"], sweep))
            stray = sorted({(t, q) for t, q, _ in shapes} - swept)
            summary["step_shapes"] = {
                "in_window": sorted(shapes), "not_swept": stray}
            say(f"single-step shapes inside the window, by the schedule "
                f"spans: {len({(t, q) for t, q, _ in shapes})} (tokens, "
                f"longest chunk) buckets with "
                f"{min(n for *_, n in shapes)}-{max(n for *_, n in shapes)} "
                f"sequences; outside the sweep's {len(swept)}: {stray}")
        if trace is not None:
            with open(os.path.join(out_dir, "trace_events_sample.json"),
                      "w") as f:
                json.dump(trace.pop("sample"), f)
        shutil.rmtree(server.profile_dir, ignore_errors=True)
        if trace is not None:
            device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
            line["breakdown"] = trace["breakdown"]
            summary["trace_summary"] = {
                k: trace[k] for k in ("busy_s", "window_s", "modules")}
    else:
        metrics = {
            m["name"]: {"value": e2e_all[m["name"]], "unit": m["unit"]}
            for m in cell.metrics_for("end_to_end")
        }
    summary["metrics"] = metrics
    with open(os.path.join(
            out_dir, f"run_seed{args.seed}_trace{args.trace}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if (args.rehearsal or args.server_arg or args.config_set
            or args.check_only):
        # Not a measurement: a CPU rehearsal or a control with altered
        # server arguments reports counts and the check, never a metric.
        line["not_a_result"] = (
            "cpu rehearsal" if args.rehearsal else
            "control: " + " ".join(args.server_arg + args.config_set)
            if args.server_arg or args.config_set else "check only")
        line["readings"] = result["readings"]
        line["control"] = result.get("control")
        line["counts"] = {k: v["value"] for k, v in metrics.items()}
        print(json.dumps(line))
        return 0
    for m in metrics.values():
        if m["value"] is None:
            raise ServerError(f"a metric has no value: {metrics}")
    line["metrics"] = metrics
    line["device"] = device
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
