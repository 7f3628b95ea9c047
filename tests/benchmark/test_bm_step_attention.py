"""``step_attention_ms`` (PR 30): attention's device time in one single
step, read from the named trace as ``step_experts_ms`` reads the
experts'.  On hand-made events the number is known, and the kernel's
two calls of a layer (chunk tiles, single tokens) and the staging
around them are all filed under the scope; on the events recorded from
PR 26's chip trace it gives what that run's scopes gave; a trace
without the names, or without a single step, gives ``None``."""

import json
import os

import pytest

from benchmark.layer_metrics import _xplane, step_attention_ms
from tests.benchmark import test_bm_xplane as base

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def with_attention() -> dict:
    """``test_bm_xplane.hand_made`` with one more single step, whose
    layer ran the kernel twice beside its gathers."""
    events = base.hand_made()
    plane = events["devices"]["/device:TPU:0"]
    plane["modules"] += [["jit_vdt_step_t512_q512_s8_p8", 20000, 3000]]
    step = base.STEP.replace("t16_q4", "t512_q512")
    plane["ops"] += [
        ["fusion.7", 20000, 500, step + "attn_qkv/dot_general"],
        ["gather.1", 20500, 100, step + "attn/gather"],
        ["vdt_paged_attention.1", 20600, 700,
         step + "attn/vdt_paged_attention/pallas_call"],
        ["vdt_paged_attention.2", 21300, 300,
         step + "attn/vdt_paged_attention/pallas_call"],
        ["fusion.8", 21600, 100, step + "attn/select_n"],
        ["fusion.9", 21700, 1300, step + "attn_out/dot_general"],
    ]
    return events


def test_hand_made_trace_gives_the_known_number():
    ctx = base.ctx_for(with_attention())
    # 100 + 700 + 300 + 100 ns under attn over two single steps
    assert step_attention_ms.read(ctx) == pytest.approx(1200e-6 / 2)
    step = ctx["_named_trace"]["programs"]["step"]
    assert step["kernels"]["vdt_paged_attention"] == pytest.approx(1000e-9)
    # the scan's attention is not in it, and reads as before
    assert base.read("decode_attention_ms", ctx) == pytest.approx(
        1000e-6 / 2)
    # attn_qkv and attn_out are scopes of their own
    assert step["scopes"]["attn_qkv"] == pytest.approx(2500e-9)


def test_recorded_v5e_trace_reads_as_the_run_that_made_it_did():
    ctx = base.ctx_for(base.recorded())
    step = ctx["_named_trace"]["programs"]["step"]
    want = step["scopes"]["attn"] * 1e3 / step["executions"]
    assert step_attention_ms.read(ctx) == pytest.approx(want)
    # PR 26's run: the dense grid, a step of 2,048 rows and one of 256
    assert 20 < want < 60


def test_nothing_to_read_gives_none_and_does_not_raise():
    events = base.hand_made()  # a single step whose attention never ran
    assert step_attention_ms.read(base.ctx_for(events)) is None
    plane = events["devices"]["/device:TPU:0"]
    plane["modules"] = [m for m in plane["modules"]
                        if not m[0].startswith("jit_vdt_step_")]
    assert step_attention_ms.read(base.ctx_for(events)) is None
    # the parent of PR 26: no names anywhere
    plane["modules"] = [["jit_call", s, d] for _, s, d in plane["modules"]]
    plane["ops"] = [[n, s, d, ""] for n, s, d, _ in plane["ops"]]
    assert step_attention_ms.read(base.ctx_for(events)) is None
    events["devices"] = {}
    assert _xplane.reduce(events) is None
    assert step_attention_ms.read(base.ctx_for(events)) is None


def test_manifest_entry_is_the_one_the_issue_names():
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "step_attention_ms"]
    # no ``workloads`` list: every model has the scope
    assert entry == {
        "name": "step_attention_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels", "moves": "out_tok_s"}
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index("step_attention_ms") > names.index("step_experts_ms")
