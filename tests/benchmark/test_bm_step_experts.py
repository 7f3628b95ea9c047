"""``step_experts_ms`` (PR 27): the expert layers' device time in one
single step, read from the named trace like ``decode_experts_ms`` reads
the scans'.  On hand-made events the number is known; on the events
recorded from PR 26's chip trace (dense experts: one step of 2,048 rows
and one of 256) it gives what that run's scopes gave; a trace without
the names, or without a single step, gives ``None``; the grouped kernel
is filed under the same scope, so one reader compares the two."""

import json
import os

import pytest

from benchmark.layer_metrics import _xplane, step_experts_ms
from tests.benchmark import test_bm_xplane as base

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def with_experts() -> dict:
    """``test_bm_xplane.hand_made`` with two more single steps whose
    experts ran: one the dense fusion, one the grouped kernel twice (up
    and down projection) beside the router's sort."""
    events = base.hand_made()
    plane = events["devices"]["/device:TPU:0"]
    plane["modules"] += [["jit_vdt_step_t512_q512_s8_p8", 20000, 3000],
                         ["jit_vdt_step_t16_q4_s8_p8", 30000, 1000]]
    step = base.STEP.replace("t16_q4", "t512_q512")
    plane["ops"] += [
        ["sort.3", 20000, 200, step + "mlp/moe_router/sort"],
        ["vdt_grouped_int8_matmul.1", 20200, 1500,
         step + "mlp/moe_experts/vdt_grouped_int8_matmul/pallas_call"],
        ["vdt_grouped_int8_matmul.2", 21700, 900,
         step + "mlp/moe_experts/vdt_grouped_int8_matmul/pallas_call"],
        ["fusion.4", 22600, 400, step + "mlp/reduce_sum"],
        ["fusion.5", 30000, 600,
         base.STEP + "mlp/moe_experts/th,ehi->tei/dot_general"],
    ]
    return events


def test_hand_made_trace_gives_the_known_number():
    ctx = base.ctx_for(with_experts())
    # 1500 + 900 + 600 ns under moe_experts over three single steps
    assert step_experts_ms.read(ctx) == pytest.approx(3000e-6 / 3)
    step = ctx["_named_trace"]["programs"]["step"]
    assert step["kernels"]["vdt_grouped_int8_matmul"] == pytest.approx(
        2400e-9)
    # the scan's experts are not in it
    assert base.read("decode_experts_ms", ctx) == pytest.approx(1500e-6 / 2)
    # the new operations all sit under a scope
    assert base.read("device_unattributed_pct", ctx) == pytest.approx(
        100 * 600 / (6100 + 3000 + 600))


def test_recorded_v5e_trace_reads_as_the_run_that_made_it_did():
    ctx = base.ctx_for(base.recorded())
    assert step_experts_ms.read(ctx) == pytest.approx(54.5500395)


def test_nothing_to_read_gives_none_and_does_not_raise():
    events = base.hand_made()  # a single step whose experts never ran
    assert step_experts_ms.read(base.ctx_for(events)) is None
    plane = events["devices"]["/device:TPU:0"]
    plane["modules"] = [m for m in plane["modules"]
                        if not m[0].startswith("jit_vdt_step_")]
    assert step_experts_ms.read(base.ctx_for(events)) is None
    # the parent of PR 26: no names anywhere
    plane["modules"] = [["jit_call", s, d] for _, s, d in plane["modules"]]
    plane["ops"] = [[n, s, d, ""] for n, s, d, _ in plane["ops"]]
    assert step_experts_ms.read(base.ctx_for(events)) is None
    events["devices"] = {}
    assert _xplane.reduce(events) is None
    assert step_experts_ms.read(base.ctx_for(events)) is None


def test_manifest_entry_is_the_one_the_issue_names():
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "step_experts_ms"]
    assert entry == {
        "name": "step_experts_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels", "moves": "out_tok_s",
        "workloads": ["mixtral8x7b-chat-closed"]}
    assert manifest["per_layer"][-1] == entry  # appended, nothing moved
