"""The reduction from a profiler trace to the benchmark's numbers: on a
hand-made set of device events, on the events recorded from a v5e run
kept beside the code, and on a trace this process records itself."""

import json
import os

import pytest

from benchmark import trace_reduce

HERE = os.path.dirname(os.path.abspath(trace_reduce.__file__))
RECORDED = os.path.join(HERE, "testdata", "v5e_trace_events.json")


def _events(ops, mods):
    return {"devices": {"/device:TPU:0": {
        trace_reduce.OPS_LINE: ops, trace_reduce.MODULES_LINE: mods}},
        "lines": {"/device:TPU:0": ["XLA Modules", "XLA Ops"]}}


def test_busy_is_the_union_of_op_intervals_and_gaps_are_named():
    ops = [("fusion.1", 0, 400), ("fusion.2", 400, 200),  # touching: 0..600
           ("custom-call.3", 1000, 500),                  # gap 600..1000
           ("fusion.1", 2000, 1000)]                      # gap 1500..2000
    mods = [("jit_step(11)", 0, 600), ("jit_decode(12)", 1000, 500),
            ("jit_decode(12)", 2000, 1000)]
    r = trace_reduce.reduce(_events(ops, mods))
    assert r["busy_s"] == pytest.approx(2100e-9)
    assert r["window_s"] == pytest.approx(3000e-9)
    assert r["modules"]["jit_decode"]["n"] == 2
    assert r["breakdown"]["device_ops"][0] == [
        "fusion", pytest.approx(1600e-9)]
    assert r["breakdown"]["idle_gaps"][0] == [
        "before jit_decode", pytest.approx(500e-9)]
    assert trace_reduce.module_median_ms(
        r, {"name": "decode"}) == pytest.approx(750e-9 * 1e3)
    assert trace_reduce.module_median_ms(
        r, {"name": "jit_", "contains_op": "custom-call"}
    ) == pytest.approx(500e-9 * 1e3)
    assert trace_reduce.module_median_ms(
        r, {"name": "jit_", "lacks_op": "fusion"}) == pytest.approx(500e-6)
    assert trace_reduce.module_median_ms(r, {"name": "absent"}) is None
    assert trace_reduce.module_median_ms(
        r, {"name": "jit_", "min_ms": 1.0}) is None


def test_nested_operations_count_each_nanosecond_once():
    ops = [("while.1", 0, 1000), ("fusion.2", 100, 300),
           ("fusion.3", 500, 400), ("copy.4", 1200, 100)]
    rows = {n: (depth, self_ns)
            for n, _, _, depth, self_ns in trace_reduce.nest(ops)}
    assert rows == {"while.1": (0, 300), "fusion.2": (1, 300),
                    "fusion.3": (1, 400), "copy.4": (0, 100)}
    r = trace_reduce.reduce(_events(ops, [("jit_call(7)", 0, 1300)]))
    assert r["busy_s"] == pytest.approx(1100e-9)
    assert dict(map(tuple, r["breakdown"]["device_ops"])) == {
        "fusion": pytest.approx(700e-9), "while": pytest.approx(300e-9),
        "copy": pytest.approx(100e-9)}
    assert trace_reduce.op_name(
        "%fusion.12 = bf16[8]{0} fusion(bf16[8] %p), kind=kLoop"
    ) == "fusion.12"


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce({"devices": {}, "lines": {"/host:CPU": []}})


def test_union_merges_touching_and_nested_intervals():
    total, gaps = trace_reduce.union_ns(
        [(0, 10), (10, 20), (5, 8), (30, 40)])
    assert total == 30 and gaps == [(20, 30)]


def test_recorded_v5e_events_reduce_to_sane_numbers():
    events = json.load(open(RECORDED))
    events["devices"] = {
        plane: {line: [tuple(e) for e in evs] for line, evs in lines.items()}
        for plane, lines in events["devices"].items()}
    r = trace_reduce.reduce(events)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["breakdown"]["device_ops"] and r["modules"]
    decode = trace_reduce.module_median_ms(
        r, {"name": "jit_", "contains_op": "while", "min_ms": 1.0})
    step = trace_reduce.module_median_ms(
        r, {"name": "jit_", "lacks_op": "while", "min_ms": 1.0})
    assert decode is not None and step is not None and decode != step


def test_load_events_reads_a_trace_recorded_here(tmp_path):
    """A CPU trace has no TPU plane: the reader must list what it found
    and the reduction must refuse it, not report zero."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.dot(jnp.ones((64, 64)), jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    events = trace_reduce.load_events(trace_reduce.find_xplane(str(tmp_path)))
    assert any(p.startswith("/host:") for p in events["lines"])
    assert events["devices"] == {}
