"""What PR 40 adds to the yardstick: nine readers of the host's time
line, each on a canned ``ctx`` whose numbers are known by hand (spans in
and out of the window, nothing to read, a program from before the
spans), the arithmetic that lays the other threads' annotations over the
device's gaps, the annotations as a real profile holds them, and the
manifest entries."""

import importlib
import json
import os
import threading
import time
import types

import pytest

from benchmark import run
from benchmark.layer_metrics import _host_timeline

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NEW = {
    "api_parse_p50_ms": ("ms", "program_span", "front end", "ttft_p50_ms"),
    "intake_wait_p50_ms": ("ms", "program_span", "front end", "ttft_p50_ms"),
    "prefill_behind_p50_ms": ("ms", "program_span", "engine", "ttft_p50_ms"),
    "first_chunk_emit_p50_ms": (
        "ms", "program_span", "front end", "ttft_p50_ms"),
    "flush_p95_ms": ("ms", "program_span", "front end", "itl_p95_ms"),
    "output_gap_max_ms": ("ms", "program_span", "engine", "out_tok_s"),
    "gc_pause_max_ms": ("ms", "program_span", "engine", "itl_p95_ms"),
    "window_backend_compiles": (
        "count", "program_span", "step programs", "out_tok_s"),
    "idle_with_loop_busy_pct": (
        "%", "device_trace", "front end", "tpot_p50_ms"),
}
T0, T1, OFFSET = 100.0, 110.0, 1000.0  # the window is wall 1100..1110


def span(name, start, ms, **attributes):
    return {"name": name, "start": start, "duration": ms / 1e3,
            "span_id": f"{name}@{start}", "parent_id": "x",
            "attributes": attributes}


def request(start, parse, intake, queue, prefill, emit, behind):
    """One request's trace: its stages tile from ``start``."""
    at, spans = start, []
    for name, ms, attrs in (
            ("api.parse", parse, {}), ("engine.intake", intake,
                                       {"phase": "slack"}),
            ("engine.queue", queue, {}),
            ("engine.prefill", prefill, {"behind_ms": behind, "steps": 1}),
            ("api.emit", emit, {"held_ms": 1.0, "hop_ms": emit - 1.5,
                                "write_ms": 0.5})):
        spans.append(span(name, at, ms, **attrs))
        at += ms / 1e3
    root = span("api.request", start, 500.0,
                first_chunk_ms=parse + intake + queue + prefill + emit)
    root["parent_id"] = None
    return {"trace_id": f"r{start}", "root_span_id": root["span_id"],
            "spans": spans + [root]}


def step(start, ms, step_id, outputs, **between):
    """One step's trace: the root and an ``engine.process`` that ends
    where the step does."""
    root = span("engine.step", start, ms, step_id=step_id, **between)
    root["parent_id"] = None
    spans = [root, span("engine.process", start + (ms - 1.0) / 1e3, 1.0,
                        step_id=step_id - 1, outputs=outputs)]
    if outputs:
        spans.append(span("frontend.flush", start + ms / 1e3,
                          2.0 * outputs, step_id=step_id, outputs=outputs))
    return {"trace_id": f"s{step_id}", "root_span_id": root["span_id"],
            "spans": spans}


def canned(instrumented=True) -> dict:
    between = dict(since_prev_ms=5.0, slack_ms=3.0, idle_ms=0.0,
                   intake_ms=1.0) if instrumented else {}
    traces = [
        # before the window: read by no reader
        request(1099.0, 50, 50, 50, 50, 50, 50),
        request(1101.0, 0.4, 8.0, 2.0, 60.0, 3.0, 20.0),
        request(1102.0, 0.6, 12.0, 4.0, 70.0, 5.0, 30.0),
        request(1103.0, 0.8, 10.0, 3.0, 80.0, 4.0, 40.0),
        step(1100.9, 40.0, 1, 0, **between),   # prefill chunk: no output
        step(1101.0, 50.0, 2, 3, **between),   # outputs end at 1101.050
        step(1101.1, 50.0, 3, 2, **between),   # ... 1101.150
        step(1101.5, 50.0, 4, 1, **between),   # ... 1101.550: 400 ms gap
        step(1101.6, 20.0, 5, 0, **between),   # no outputs: no end
        step(1101.7, 10.0, 6, 4, **between),   # ... 1101.710
        step(1111.0, 10.0, 7, 4, **between),   # after the window
    ]
    if instrumented:
        traces.insert(0, {"trace_id": "p", "root_span_id": None, "spans": [
            span("gc.collect", 1099.5, 900.0, generation=2),  # before
            span("gc.collect", 1101.2, 7.0, generation=1),
            span("gc.collect", 1101.3, 85.0, generation=2),
            span("xla.compile", 1101.25, 300.0, cache_hit=False,
                 seconds=0.3, module="jit_f"),
            span("xla.compile", 1104.0, 2.0, cache_hit=True,
                 seconds=0.002, module="jit_g"),
            span("xla.compile", 1120.0, 300.0, cache_hit=False,
                 seconds=0.3, module="jit_h"),  # after
        ]})
    else:
        for tr in traces:  # a program from before PR 40
            tr["spans"] = [s for s in tr["spans"] if s["name"] in (
                "api.request", "engine.queue", "engine.prefill",
                "engine.step", "engine.process")]
            for s in tr["spans"]:
                for key in ("behind_ms", "steps", "first_chunk_ms"):
                    s["attributes"].pop(key, None)
    cell = types.SimpleNamespace(name="no-such-cell", bench={}, mix={})
    return {"cell": cell, "t0": T0, "t1": T1, "wall_offset": OFFSET,
            "spans": traces, "window": {"ttft_p50_ms": 99.0}, "trace": None}


def read(metric: str, ctx: dict):
    return importlib.import_module(
        "benchmark.layer_metrics." + metric).read(ctx)


@pytest.mark.parametrize("metric, want", [
    ("api_parse_p50_ms", 0.6),
    ("intake_wait_p50_ms", 10.0),
    ("prefill_behind_p50_ms", 30.0),
    ("first_chunk_emit_p50_ms", 4.0),
    # flushes in the window last 6, 4, 2 and 8 ms
    ("flush_p95_ms", 7.7),
    ("output_gap_max_ms", 400.0),
    ("gc_pause_max_ms", 85.0),
    ("window_backend_compiles", 1),
    ("idle_with_loop_busy_pct", None),  # no profile beside this ctx
])
def test_reader_gives_the_known_number_on_the_canned_spans(metric, want):
    got = read(metric, canned())
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric, want", [
    ("api_parse_p50_ms", None), ("intake_wait_p50_ms", None),
    ("prefill_behind_p50_ms", None), ("first_chunk_emit_p50_ms", None),
    ("flush_p95_ms", None),
    # engine.process is older than PR 40: the parent reads this one too
    ("output_gap_max_ms", 400.0),
    ("gc_pause_max_ms", None), ("window_backend_compiles", None),
    ("idle_with_loop_busy_pct", None),
])
def test_a_program_from_before_the_spans_reads_none_and_does_not_raise(
        metric, want):
    got = read(metric, canned(instrumented=False))
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", sorted(NEW))
def test_nothing_in_the_window_reads_none_or_zero(metric):
    ctx = canned()
    ctx["t0"], ctx["t1"] = 300.0, 310.0  # a window no span started in
    want = {"gc_pause_max_ms": 0.0, "window_backend_compiles": 0}
    assert read(metric, ctx) == want.get(metric)
    ctx = dict(canned(), spans=None)  # an untraced run's ctx
    assert read(metric, ctx) is None


def test_the_longest_gap_comes_with_the_spans_over_it():
    gap = _host_timeline.output_gap(canned())
    assert gap["ms"] == pytest.approx(400.0)
    assert gap["start"] == pytest.approx(1101.150)
    assert gap["end"] == pytest.approx(1101.550)
    names = [(r["name"], r["step_id"]) for r in gap["spans"]]
    # the step that ended the gap, with its account of the time before
    # it, and the collections and the compile that lay inside it
    assert ("engine.step", 4) in names and ("engine.step", 5) not in names
    (four,) = [r for r in gap["spans"]
               if (r["name"], r["step_id"]) == ("engine.step", 4)]
    assert four["since_prev_ms"] == 5.0 and four["slack_ms"] == 3.0
    assert four["at_ms"] == pytest.approx(350.0)
    assert [r["ms"] for r in gap["spans"]
            if r["name"] == "gc.collect"] == pytest.approx([7.0, 85.0])
    assert [r["seconds"] for r in gap["spans"]
            if r["name"] == "xla.compile"] == [0.3]
    # requests' own stages overlap every gap and name no cause
    assert not any(r["name"].startswith("api.") for r in gap["spans"])


def test_report_is_the_split_perf_md_quotes(capsys):
    ctx = canned()
    out = _host_timeline.report(ctx)
    assert out["instrumented"] is True
    assert out["split_p50_ms"] == pytest.approx({
        "api.parse": 0.6, "engine.intake": 10.0, "engine.queue": 3.0,
        "engine.prefill": 70.0, "api.emit": 4.0})
    assert out["split_sum_ms"] == pytest.approx(87.6)
    assert out["first_chunk_p50_ms"] == pytest.approx(91.6)
    # medians need not add; means do, and each request's sum is exact
    assert sum(out["split_mean_ms"].values()) == pytest.approx(
        out["first_chunk_mean_ms"])
    assert out["closure"] == {
        "requests": 3, "sum_p50_ms": pytest.approx(91.6),
        "max_error_ms": pytest.approx(0.0, abs=1e-9)}
    assert out["client_ttft_p50_ms"] == 99.0
    assert out["intake_phase"] == {"slack": 3}
    assert out["between_steps_p50_ms"]["slack_ms"] == 3.0
    assert [g["generation"] for g in out["gc"]] == [1, 2]
    assert out["ring"]["traces"] == len(ctx["spans"])
    # the oldest root the ring still holds began a second before t0
    assert out["ring"]["oldest_root_after_t0_s"] == pytest.approx(-1.0)
    assert out["profile"] is None
    assert _host_timeline.report(ctx) is out  # computed once
    assert capsys.readouterr().out.count("[host timeline]") == 3
    bare = _host_timeline.report(canned(instrumented=False))
    assert bare["instrumented"] is False and bare["split_sum_ms"] is None


def test_gaps_under_another_threads_annotations():
    """Device busy 0-100, 200-300 and 1000-1100 us: gaps 100-200 and
    300-1000.  The loop wrote from 150 to 400 and a collection ran from
    900 to 1200 on a third thread: 50 + 100 + 100 of the 800 idle lay
    under them; the engine's own collection at 500 does not count."""
    us = 1000
    threads = {
        "ops": [(0, 100 * us), (200 * us, 300 * us), (1000 * us, 1100 * us),
                (1010 * us, 1050 * us)],
        "engine": [(0, 1100 * us)],
        "gc_engine": [("gc.collect", 500 * us, 600 * us)],
        "busy": [("api.write", 150 * us, 300 * us),
                 ("api.dispatch", 250 * us, 400 * us),
                 ("gc.collect", 900 * us, 1200 * us)],
    }
    got = _host_timeline.loop_busy(threads)
    assert got["window_s"] == pytest.approx(1100e-6)
    assert got["idle_s"] == pytest.approx(800e-6)
    assert got["idle_with_loop_busy_s"] == pytest.approx(250e-6)
    assert got["largest_gap"]["ms"] == pytest.approx(0.7)
    assert got["largest_gap"]["under_ms"] == pytest.approx({
        "api.dispatch": 0.1, "gc.collect": 0.1,
        "gc.collect (engine thread)": 0.1})
    ctx = canned()
    ctx["_loop_busy"] = got
    assert read("idle_with_loop_busy_pct", ctx) == pytest.approx(
        100 * 250 / 1100)
    assert _host_timeline.loop_busy(dict(threads, ops=[])) is None


def test_a_profile_holds_the_annotations_by_thread(tmp_path):
    """The annotations the program leaves while a capture runs, as
    ``load_threads`` finds them in a real trace: the engine's thread is
    the one that ran ``engine.step``; what the others were inside
    (``api.*``, ``gc.collect``) is busy time beside it."""
    import jax

    from benchmark import trace_reduce
    from vllm_distributed_tpu.tracing import Tracer

    tracer = Tracer()
    tracer.profiling = True

    def engine_thread():
        with tracer.annotation("engine.step"):
            with tracer.annotation("gc.collect"):
                time.sleep(0.01)

    jax.profiler.start_trace(str(tmp_path))
    try:
        worker = threading.Thread(target=engine_thread)
        worker.start()
        with tracer.annotation("api.write"):
            time.sleep(0.01)
        with tracer.annotation("api.dispatch"):
            with tracer.annotation("runner.fetch"):  # not loop-busy
                time.sleep(0.005)
        # a root span on the loop stays entered across awaits: not busy
        with tracer.annotation("api.request"):
            time.sleep(0.001)
        worker.join()
    finally:
        jax.profiler.stop_trace()
    found = _host_timeline.load_threads(
        trace_reduce.find_xplane(str(tmp_path)))
    assert found["ops"] == []  # a CPU trace: no device plane
    assert len(found["engine"]) == 1
    assert [n for n, *_ in found["gc_engine"]] == ["gc.collect"]
    assert sorted(n for n, *_ in found["busy"]) == [
        "api.dispatch", "api.write"]
    for _, start, end in found["busy"]:
        assert 4e6 < end - start < 1e9
    assert _host_timeline.loop_busy(found) is None


def test_manifest_entries_are_the_issues():
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, (unit, source, layer, moves) in NEW.items():
        assert entries[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": moves}, name
    # appended, in the issue's order, after everything the benchmark had
    assert [m["name"] for m in manifest["per_layer"][-9:]] == list(NEW)
    # no list of cells: every cell reports them
    for cell in manifest["workloads"]:
        reported = {m["name"] for m in run.Cell(
            cell["name"], rehearsal=False).metrics_for("per_layer")}
        assert set(NEW) <= reported
