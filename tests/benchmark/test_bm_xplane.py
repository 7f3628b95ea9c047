"""The named trace (``benchmark/layer_metrics/_xplane.py``) and the
readers over it: on hand-made events every number is known; on the
events recorded from PR 26's own chip trace
(``benchmark/testdata/v5e_named_trace_events.json``) the readers give
the numbers that run gave; the idle shares add up to
``device_idle_pct``; a roofline share over 100 fails the run; a trace
without a device plane gives ``None``; and the CPU rehearsal's traced
run leaves an annotation of every phase in the profile's host plane."""

import importlib
import json
import os
import re
import subprocess
import sys
import types

import pytest

from benchmark import kernel_model, trace_reduce
from benchmark.layer_metrics import _named, _xplane

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RECORDED = os.path.join(
    REPO, "benchmark", "testdata", "v5e_named_trace_events.json")
PEAK = json.load(open(os.path.join(REPO, "benchmark", "peaks.json")))[
    "TPU v5 lite"]
CONFIG = {k: v for k, v in json.load(open(os.path.join(
    REPO, "benchmark", "configs", "mixtral-8x7b-cut-int8.json"))).items()
    if k != "benchmark"}
IDLE = ["idle_in_%s_pct" % k for k in _xplane.IDLE_KINDS]
STEP = "jit(vdt_step_t16_q4_s8_p8)/call_exported/jit(vdt_step_t16_q4_s8_p8)/"
SCAN = ("jit(vdt_decode_k2_s8_p8)/call_exported/jit(vdt_decode_k2_s8_p8)/"
        "while/body/closed_call/")


def hand_made() -> dict:
    """One fused scan of two micro-steps, one single step, one eager
    helper; the engine's thread and the resolver's beside them."""
    return {
        "lines": {"/device:TPU:0": ["XLA Modules", "XLA Ops"],
                  "/host:CPU": ["python3", "python3"]},
        "devices": {"/device:TPU:0": {
            "modules": [["jit_vdt_decode_k2_s8_p8", 1000, 4000],
                        ["jit_vdt_step_t16_q4_s8_p8", 7000, 2000],
                        ["jit_broadcast_in_dim", 9500, 100]],
            "ops": [
                ["while.1", 1000, 3000, SCAN[:-24] + "while"],
                ["fusion.1", 1000, 1500,
                 SCAN + "mlp/moe_experts/th,ehi->tei/dot_general"],
                ["vdt_paged_attention.2", 2500, 1000,
                 SCAN + "attn/vdt_paged_attention/pallas_call"],
                ["copy.3", 3500, 500, ""],
                ["vdt_kv_flush", 4000, 1000,
                 STEP.replace("step_t16_q4", "decode_k2")
                 + "kv_write/vdt_kv_flush/pallas_call"],
                ["fusion.9", 7000, 2000, STEP + "attn_qkv/dot_general"],
                ["broadcast_in_dim.1", 9500, 100, ""],
            ]}},
        "host": [
            [["engine.step", 0, 10000, None],
             ["scheduler.schedule", 5000, 500, None],
             ["runner.prepare", 5500, 500, 7],
             ["runner.dispatch", 6000, 400, 7],
             ["engine.process", 9000, 300, 6]],
            [["runner.fetch", 4800, 400, 6],
             ["runner.fetch", 6400, 600, 7]],
        ],
    }


def ctx_for(events: dict, spans=None, batch=31.0) -> dict:
    named = _xplane.reduce(events)
    cell = types.SimpleNamespace(
        name="hand-made", bench={"num_decode_steps": 2}, mix={})
    schedule = {"name": "scheduler.schedule", "start": 10.0, "duration": 0.001,
                "attributes": {"num_cached": int(batch), "num_new": 0,
                               "decode_steps": 2}}
    return {
        "_named_trace": named, "cell": cell, "config": CONFIG, "peak": PEAK,
        "t0": 0.0, "t1": 100.0, "wall_offset": 0.0,
        "spans": [{"spans": [schedule] + (spans or [])}],
        "trace": named and {"busy_s": named["busy_s"],
                            "window_s": named["window_s"]},
    }


def read(metric: str, ctx: dict):
    return importlib.import_module(
        "benchmark.layer_metrics." + metric).read(ctx)


def test_interval_arithmetic():
    assert _xplane._merged([(0, 10), (10, 20), (5, 8), (30, 40)]) == [
        (0, 20), (30, 40)]
    assert _xplane._minus(
        [(0, 10), (20, 30)], [(2, 3), (8, 22), (29, 40)]) == [
        (0, 2), (3, 8), (22, 29)]
    assert _xplane._overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert _xplane._minus([(0, 10)], []) == [(0, 10)]


def test_op_names_are_read_from_the_hlo_protos_a_trace_carries(tmp_path):
    """The scope reaches a trace inside the programs' HloProtos
    (``/host:metadata``); a trace recorded here, of a function with two
    scopes behind jax.export as the AOT cache runs it, gives them back
    by program and instruction."""
    import jax
    import jax.numpy as jnp

    def vdt_step_t8(x, w):
        with jax.named_scope("attn_qkv"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("mlp"):
            with jax.named_scope("moe_experts"):
                return jnp.sin(y) @ w

    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
    exported = jax.export.deserialize(bytearray(
        jax.export.export(jax.jit(vdt_step_t8))(x, w).serialize()))

    def program(*a):
        return exported.call(*a)

    program.__name__ = "vdt_step_t8"
    run = jax.jit(program)
    run(x, w).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    run(x, w).block_until_ready()
    jax.profiler.stop_trace()
    path = _xplane.find_xplane(str(tmp_path))
    (table,) = [t for name, t in _xplane.hlo_op_names(path).items()
                if name.startswith("jit_vdt_step_t8(")]
    scopes = {_xplane.scope_of(op) for op in table.values()}
    assert {"attn_qkv", "moe_experts"} <= scopes, table
    assert any(op.startswith("jit(vdt_step_t8)/call_exported/")
               for op in table.values()), table
    events = _xplane.load_events(path)
    assert events["devices"] == {} and _xplane.reduce(events) is None


def test_names_are_taken_apart():
    assert _xplane.scope_of(SCAN + "mlp/moe_experts/dot_general") == (
        "moe_experts")
    assert _xplane.scope_of(STEP + "mlp/reduce_sum") == "mlp"
    assert _xplane.scope_of("jit(f)/while/body") is None
    assert _xplane.kernel_of("vdt_paged_attention.12") == (
        "vdt_paged_attention")
    assert _xplane.kernel_of("vdt_int8_matmul") == "vdt_int8_matmul"
    assert _xplane.kernel_of("fusion.12") is None
    assert _xplane.program_kind("jit_vdt_decode_k8_s32_p256") == "decode"
    assert _xplane.program_kind("jit_vdt_step_t16_q4_s8_p8_lp") == "step"
    assert _xplane.program_kind("jit_call") == "other"


def test_hand_made_trace_reduces_to_known_numbers():
    named = _xplane.reduce(hand_made())
    assert named["busy_s"] == pytest.approx(6100e-9)
    assert named["window_s"] == pytest.approx(8600e-9)
    decode = named["programs"]["decode"]
    assert decode["executions"] == 1
    assert decode["scopes"] == pytest.approx(
        {"moe_experts": 1500e-9, "attn": 1000e-9, "kv_write": 1000e-9})
    assert decode["kernels"] == pytest.approx(
        {"vdt_paged_attention": 1000e-9, "vdt_kv_flush": 1000e-9})
    assert named["programs"]["step"]["scopes"] == pytest.approx(
        {"attn_qkv": 2000e-9})
    # the while's own time is nothing; the copy and the helper are
    assert named["unattributed"] == pytest.approx(
        {"copy": 500e-9, "broadcast_in_dim": 100e-9, "while": 0.0})
    assert {k: round(v * 1e9) for k, v in named["idle_s"].items()} == {
        "schedule": 500, "runner": 900, "fetch": 600, "process": 300,
        "other": 200}
    gap = named["gaps"][0]
    assert gap["ms"] == pytest.approx(2000e-6)
    assert gap["after"] == "jit_vdt_decode_k2_s8_p8"
    assert gap["before"] == "jit_vdt_step_t16_q4_s8_p8"
    assert list(gap["host"])[0] == "runner.fetch"
    assert named["host"]["runner.fetch"] == 2


@pytest.mark.parametrize("metric,want", [
    ("single_step_busy_pct", 100 * 2000 / 6100),
    ("decode_experts_ms", 1500e-6 / 2),
    ("decode_attention_ms", 1000e-6 / 2),
    ("device_unattributed_pct", 100 * 600 / 6100),
    ("idle_in_schedule_pct", 100 * 500 / 8600),
    ("idle_in_runner_pct", 100 * 900 / 8600),
    ("idle_in_fetch_pct", 100 * 600 / 8600),
    ("idle_in_process_pct", 100 * 300 / 8600),
    ("idle_in_other_pct", 100 * 200 / 8600),
])
def test_reader_gives_the_known_number_on_the_hand_made_trace(metric, want):
    assert read(metric, ctx_for(hand_made())) == pytest.approx(want)


def test_idle_shares_add_up_to_device_idle_pct():
    ctx = ctx_for(hand_made())
    assert sum(read(m, ctx) for m in IDLE) == pytest.approx(
        read("device_idle_pct", ctx))


def test_dispatch_p50_leaves_first_calls_out():
    def span(ms, first):
        return {"name": "runner.dispatch", "start": 5.0,
                "duration": ms / 1e3,
                "attributes": {"first_call": first, "program": "vdt_x"}}

    ctx = ctx_for(hand_made(), spans=[
        span(900.0, True), span(1.0, False), span(2.0, False),
        span(4.0, False)])
    assert read("dispatch_p50_ms", ctx) == pytest.approx(2.0)
    assert read("dispatch_p50_ms", ctx_for(hand_made())) is None


def test_a_roofline_share_over_100_fails_the_run():
    # two micro-steps' experts in 1.5 us: no chip reads 4 GB that fast
    with pytest.raises(ValueError, match="decode_experts_roofline_pct"):
        read("decode_experts_roofline_pct", ctx_for(hand_made()))
    model = kernel_model.experts(CONFIG, 31.0, PEAK)
    assert _named.roofline_pct("x", model, model["seconds"] * 2e3) == (
        pytest.approx(50.0))
    with pytest.raises(ValueError, match="hbm-bound"):
        _named.roofline_pct("x", model, model["seconds"] * 0.9e3)


def test_kernel_model_counts_what_the_issue_counted():
    experts = kernel_model.experts(CONFIG, 31.0, PEAK)
    # 3 layers x 8 experts x 3 matrices of 4096 x 14336 int8: 4.23 GB
    assert experts["bytes"] == pytest.approx(4.23e9, rel=0.01)
    assert experts["binds"] == "hbm"
    assert experts["seconds"] == pytest.approx(5.16e-3, rel=0.01)
    one = kernel_model.experts(CONFIG, 1.0, PEAK)
    assert one["bytes"] == pytest.approx(experts["bytes"] / 4, rel=0.01)
    attn = kernel_model.paged_attention(CONFIG, 23000, 31, PEAK)
    # 12 KiB a cached token over 3 layers
    assert attn["bytes"] == pytest.approx(23000 * 12288, rel=0.01)
    assert attn["binds"] == "hbm"


def test_attention_roofline_reads_the_dispatch_spans_inside_the_capture():
    def dispatch(start, kv_tokens, program="vdt_decode_k2_s8_p8"):
        return {"name": "runner.dispatch", "start": start, "duration": 0.001,
                "attributes": {"program": program, "kv_tokens": kv_tokens,
                               "seqs": 8, "first_call": False}}

    capture = {"name": "profile.capture", "start": 19.0, "duration": 12.0,
               "attributes": {"begin_wall": 20.0, "end_wall": 30.0,
                              "begin_mono": 0.0, "end_mono": 10.0}}
    events = hand_made()
    # make the kernel slow enough for the share to be a share: 1 ms
    events["devices"]["/device:TPU:0"]["ops"][2][2] = 1_000_000
    events["devices"]["/device:TPU:0"]["ops"][0][2] = 1_002_000
    events["devices"]["/device:TPU:0"]["modules"][0][2] = 1_003_000
    for op in events["devices"]["/device:TPU:0"]["ops"][3:]:
        op[1] += 1_000_000
    for mod in events["devices"]["/device:TPU:0"]["modules"][1:]:
        mod[1] += 1_000_000
    ctx = ctx_for(events, spans=[
        capture, dispatch(10.0, 900_000), dispatch(21.0, 1000),
        dispatch(29.0, 3000), dispatch(25.0, 10**9, "vdt_step_t16")])
    want = kernel_model.paged_attention(CONFIG, 2000, 8, PEAK)
    got = read("decode_attention_roofline_pct", ctx)
    assert got == pytest.approx(100 * want["seconds"] / 0.5e-3)
    # without the capture's span the whole window's spans are read
    ctx = ctx_for(events, spans=[dispatch(21.0, 1000), dispatch(50.0, 2000)])
    want = kernel_model.paged_attention(CONFIG, 1500, 8, PEAK)
    assert read("decode_attention_roofline_pct", ctx) == pytest.approx(
        100 * want["seconds"] / 0.5e-3)


NEW_METRICS = [
    "single_step_busy_pct", "decode_experts_ms",
    "decode_experts_roofline_pct", "decode_attention_ms",
    "decode_attention_roofline_pct", "device_unattributed_pct", *IDLE]


def test_every_new_metric_is_in_the_manifest_with_its_reader():
    manifest = {m["name"]: m for m in json.load(open(os.path.join(
        REPO, "BENCHMARK.json")))["per_layer"]}
    for name in NEW_METRICS + ["dispatch_p50_ms"]:
        assert name in manifest, name
        assert manifest[name]["source"] == (
            "program_span" if name == "dispatch_p50_ms" else "device_trace")
        assert manifest[name]["better"] == (
            "higher" if "roofline" in name else "lower")


def recorded() -> dict:
    with open(RECORDED) as f:
        return _xplane.expand(json.load(f))


def test_recorded_v5e_trace_reads_as_the_run_that_made_it_did():
    """One single step, three fused scans and the step after them, from
    PR 26's traced run of ``mixtral8x7b-chat-closed`` on a v5e."""
    named = _xplane.reduce(recorded())
    assert named["busy_s"] == pytest.approx(0.359341423)
    assert named["window_s"] == pytest.approx(0.389496299)
    decode, step = named["programs"]["decode"], named["programs"]["step"]
    assert decode["executions"] == 3 and step["executions"] == 2
    assert decode["names"] == {"jit_vdt_decode_k8_s64_p256": 3}
    assert set(step["names"]) == {"jit_vdt_step_t2048_q2048_s32_p256",
                                  "jit_vdt_step_t256_q256_s32_p256"}
    # every scope of the program's list reached the trace, in both kinds
    assert set(decode["scopes"]) == set(step["scopes"]) == set(
        _xplane.SCOPES)
    assert set(decode["kernels"]) == {
        "vdt_paged_attention", "vdt_int8_matmul", "vdt_kv_flush"}
    assert not any(k in named["unattributed"]
                   for k in ("flat_fn", "closed_call"))
    assert named["host"] == {
        "runner.dispatch": 5, "runner.fetch": 5, "engine.process": 4,
        "engine.step": 4, "scheduler.schedule": 4, "runner.prepare": 4}
    gap = named["gaps"][0]
    assert gap["after"].startswith("jit_vdt_decode_")
    assert gap["before"].startswith("jit_vdt_step_")
    assert gap["ms"] == pytest.approx(10.453355)


@pytest.mark.parametrize("metric,want", [
    ("single_step_busy_pct", 53.5297),
    ("decode_experts_ms", 5.62312),
    ("decode_experts_roofline_pct", 91.8596),
    ("decode_attention_ms", 0.703938),
    ("decode_attention_roofline_pct", 49.2862),
    ("device_unattributed_pct", 3.15269),
    ("idle_in_schedule_pct", 0.191963),
    ("idle_in_runner_pct", 1.89857),
    ("idle_in_fetch_pct", 2.35180),
    ("idle_in_process_pct", 1.92795),
    ("idle_in_other_pct", 1.37173),
])
def test_reader_gives_the_known_number_on_the_recorded_trace(metric, want):
    dispatch = {"name": "runner.dispatch", "start": 5.0, "duration": 0.001,
                "attributes": {"program": "vdt_decode_k8_s64_p256",
                               "kv_tokens": 23000, "seqs": 31,
                               "first_call": False}}
    ctx = ctx_for(recorded(), spans=[dispatch])
    ctx["cell"].bench["num_decode_steps"] = 8
    assert read(metric, ctx) == pytest.approx(want, rel=1e-4)


def test_idle_shares_of_the_recorded_trace_add_up_to_device_idle_pct():
    ctx = ctx_for(recorded())
    total = sum(read(m, ctx) for m in IDLE)
    assert total == pytest.approx(read("device_idle_pct", ctx), abs=1e-9)
    assert total == pytest.approx(100 * (1 - 0.359341423 / 0.389496299))


def test_a_sample_of_events_expands_to_what_it_was_taken_from():
    events = hand_made()
    again = _xplane.expand(_xplane.sample_events(events, executions=5))
    # it ends with the last step program: the helper after it is cut
    plane = events["devices"]["/device:TPU:0"]
    assert again["devices"]["/device:TPU:0"] == {
        "modules": plane["modules"][:2], "ops": plane["ops"][:6]}
    assert again["host"][1] == events["host"][1]
    cut = _xplane.expand(_xplane.sample_events(events, executions=1))
    assert [m[0] for m in cut["devices"]["/device:TPU:0"]["modules"]] == [
        "jit_vdt_decode_k2_s8_p8", "jit_vdt_step_t16_q4_s8_p8"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_trace_without_a_device_plane_gives_none(metric):
    events = hand_made()
    events["devices"] = {}
    assert _xplane.reduce(events) is None
    assert read(metric, ctx_for(events)) is None


@pytest.mark.parametrize("metric", NEW_METRICS + ["dispatch_p50_ms"])
def test_a_program_without_names_gives_none_and_does_not_raise(metric):
    """The parent of PR 26 in the same harness: modules ``jit_call``,
    kernels ``flat_fn``, no op_name paths, no annotations, no
    ``runner.dispatch`` span."""
    events = hand_made()
    plane = events["devices"]["/device:TPU:0"]
    plane["modules"] = [["jit_call", s, d] for _, s, d in plane["modules"]]
    plane["ops"] = [[re.sub(r"vdt_\w+", "flat_fn", n), s, d, ""]
                    for n, s, d, _ in plane["ops"]]
    events["host"] = []
    assert read(metric, ctx_for(events)) is None


def test_cpu_rehearsal_leaves_an_annotation_of_every_phase():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("VDT_USE_PALLAS", None)
    p = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"), "--rehearsal",
         "--workload", "tiny.closed", "--seed", "3000000023",
         "--seconds", "3", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = next(ln for ln in p.stdout.splitlines()
                if ln.startswith("[named trace] no device plane"))
    found = json.loads(line.split("host annotations ", 1)[1].replace("'", '"'))
    for phase in ("engine.step", "scheduler.schedule", "runner.prepare",
                  "runner.dispatch", "runner.fetch", "engine.process"):
        assert found.get(phase, 0) >= 1, (phase, found)
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["counts"]["dispatch_p50_ms"] > 0
    assert not any(m in last["counts"] for m in NEW_METRICS)
