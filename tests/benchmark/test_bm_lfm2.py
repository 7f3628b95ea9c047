"""What ISSUE 38 adds to the yardstick: the LFM2-MoE configuration's
sizes module against hand counts, its manifest entries and files, the
six new readers on a hand-made trace (and ``None`` on the other
models'), the kernel model, and the reference's control."""

import json
import os

import pytest

from benchmark import kernel_model_lfm2, run
from benchmark.layer_metrics import (
    _xplane,
    decode_conv_ms,
    decode_moe32_ms,
    decode_moe32_roofline_pct,
    moe_busiest_expert_x,
    step_conv_ms,
    step_moe32_ms,
    step_moe32_roofline_pct,
)
from tests.benchmark import test_bm_xplane as base

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm2-8b-a1b-chat-closed-64"
CONFIG = "lfm2-8b-a1b-cut-bf16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("decode_conv_ms", "step_conv_ms", "decode_moe32_ms",
               "decode_moe32_roofline_pct", "step_moe32_ms",
               "step_moe32_roofline_pct", "moe_busiest_expert_x")


@pytest.fixture(scope="module")
def cell():
    return run.Cell(CELL, rehearsal=False)


# ---- sizes, by hand ---------------------------------------------------------
def test_a_token_holds_2048_bytes_and_a_sequence_40960(cell):
    cfg = cell.model_cfg()
    assert "head_dim" not in cfg and cell.sizes.head_dim(cfg) == 64
    # 1 attention layer x K and V x 8 heads x 64 x 2 B
    assert cell.sizes.kv_bytes_per_token(cfg) == 1 * 2 * 8 * 64 * 2 == 2048
    # 5 conv layers x 2 rows x 2048 x 2 B, whatever the length
    assert cell.sizes.state_bytes_per_seq(cfg) == 5 * 2 * 2048 * 2 == 40960
    # ten layers (two periods): 4,096 B and 65,536 B; the published
    # depth's six attention layers: 12,288 B a token
    period = ["full_attention", "conv", "conv", "conv"]
    ten = dict(cfg, layer_types=["conv", "conv"] + 2 * period)
    assert cell.sizes.kv_bytes_per_token(ten) == 4096
    assert cell.sizes.state_bytes_per_seq(ten) == 65536
    whole = dict(cfg, layer_types=["conv", "conv"] + 4 * period
                 + ["full_attention", "conv", "conv",
                    "full_attention", "conv", "conv"])
    assert len(whole["layer_types"]) == 24
    assert cell.sizes.kv_bytes_per_token(whole) == 12288


def test_parameter_counts_are_the_issues_arithmetic(cell):
    cfg, sz = cell.model_cfg(), cell.sizes
    assert sz.conv_params(cfg) == 2048 * 6144 + 2048 * 2048 + 3 * 2048
    assert round(sz.conv_params(cfg) / 1e6, 2) == 16.78
    assert sz.attention_params(cfg) == 2 * 2048 * 2048 + 2 * 2048 * 512
    assert round(sz.attention_params(cfg) / 1e6, 2) == 10.49
    assert sz.expert_params(cfg) == 3 * 2048 * 1792 == 11_010_048
    routed = 32 * sz.expert_params(cfg) + 2048 * 32
    assert round(routed / 1e6, 1) == 352.4
    dense, embed = 3 * 2048 * 7168, 65536 * 2048
    assert round(dense / 1e6, 2) == 44.04 and round(embed / 1e6, 1) == 134.2
    # ten layers, the issue's first choice: 3.20 B, 6.39 GB
    ten = 8 * routed + 8 * sz.conv_params(cfg) + 2 * sz.attention_params(
        cfg) + 2 * dense + embed
    assert round(ten / 1e9, 2) == 3.20 and round(2 * ten / 1e9, 2) == 6.39
    # six, the cut: 4 routed layers, 5 convolutions, 1 attention layer
    held = 4 * routed + 5 * sz.conv_params(cfg) + sz.attention_params(
        cfg) + 2 * dense + embed
    assert round(held / 1e9, 2) == 1.73 and round(2 * held / 1e9, 2) == 3.45
    # a micro-step reads all of it once the batch reaches every expert
    assert sz.experts_touched(cfg, 1) == pytest.approx(4.0)
    assert sz.experts_touched(cfg, 64) == pytest.approx(32.0, abs=0.01)
    assert sz.decode_weight_bytes(cfg, 64) == pytest.approx(2 * held, rel=1e-3)
    # one sequence reaches 4 experts a layer
    one = 2.0 * (held - 4 * 28 * sz.expert_params(cfg))
    assert sz.decode_weight_bytes(cfg, 1) == pytest.approx(one)
    assert sz.routed_layers(cfg) == 4


def test_the_kernel_model_reads_the_reached_experts_once(cell):
    cfg = cell.model_cfg()
    got = kernel_model_lfm2.experts(cfg, 64.0, base.PEAK)
    touched = 32 * (1 - (1 - 4 / 32) ** 64)
    assert got["bytes"] == pytest.approx(
        2 * 4 * (touched * 11_010_048 + 2048 * 32))
    assert got["bytes"] == pytest.approx(2.82e9, rel=0.002)
    assert got["ops"] == 4 * 64 * 4 * 2 * 11_010_048
    assert got["binds"] == "hbm"  # 8 operations a byte
    assert got["seconds"] == got["bytes"] / base.PEAK["hbm_bytes_per_s"]


# ---- manifest and files -----------------------------------------------------
def test_manifest_entries_are_the_issues(cell):
    m = cell.manifest
    (conf,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert conf == m["configs"][-1]
    assert conf["reduced"] == [
        "num_hidden_layers", "layer_types", "max_model_len"]
    assert cell.entry == m["workloads"][-1] and cell.entry["chips"] == 1
    assert cell.entry["traffic"] == "chat-closed-64"
    assert len(cell.entry["why"]) <= 200 and len(conf["why"]) <= 200
    new = m["per_layer"][-7:]
    assert tuple(x["name"] for x in new) == NEW_METRICS
    assert all(x["workloads"] == [CELL] for x in new)
    reported = {x["name"] for x in cell.metrics_for("per_layer")}
    assert set(NEW_METRICS) <= reported
    assert {"decode_weight_bw_pct", "step_attention_ms"} <= reported
    assert not {"decode_experts_ms", "step_experts_ms", "decode_mla_ms",
                "moe_zero_pick_pct"} & reported
    for other in ("mixtral8x7b-chat-closed", "longcat-flash-chat-closed-64"):
        theirs = {x["name"] for x in run.Cell(
            other, rehearsal=False).metrics_for("per_layer")}
        assert not set(NEW_METRICS) & theirs
    # the traffic is the file the benchmark had
    assert cell.mix["arrival"] == {"process": "closed", "clients": 64}
    assert cell.bench["scopes"] == ["short_conv"]
    assert cell.bench["sizes"] == cell.bench["reference"] == "lfm2_moe"


def test_every_published_width_stands_and_the_cuts_are_the_three_named(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    (row,) = [r for r in map(json.loads, open(CATALOG))
              if r["name"] == "LFM2-8B-A1B"]
    assert cell.bench["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cell.hf.get(k) != v}
    assert differs == {"num_hidden_layers", "layer_types"}
    assert set(cell.bench["reduced"]) == differs | {"max_model_len"}
    assert cell.hf["layer_types"] == row["config"]["layer_types"][:6]
    assert cell.hf["num_hidden_layers"] == len(cell.hf["layer_types"]) == 6
    assert cell.bench["published"]["num_hidden_layers"] == 24
    assert "--max-model-len" in cell.bench["server_args"]
    assert "head_dim" not in cell.hf


# ---- the readers, on a hand-made trace --------------------------------------
SCAN, STEP = base.SCAN, base.STEP


def conv_events() -> dict:
    """``hand_made`` with the programs of a model of convolutions and
    many small experts: in the fused scan the operator's projections
    and its state's scatter, the router and the experts; the same in
    the single step."""
    events = base.hand_made()
    plane = events["devices"]["/device:TPU:0"]
    plane["ops"] = [
        ["while.1", 1000, 3000, SCAN[:-24] + "while"],
        ["fusion.1", 1000, 600, SCAN + "short_conv/dot_general"],
        ["scatter.2", 1600, 200, SCAN + "short_conv/scatter"],
        ["fusion.3", 1800, 300, SCAN + "moe_router/top_k"],
        ["fusion.4", 2100, 1500, SCAN + "moe_experts/th,ehi->tei/dot_general"],
        ["fusion.5", 3600, 400, SCAN + "mlp/add"],
        ["fusion.6", 7000, 500, STEP + "short_conv/dot_general"],
        ["fusion.7", 7500, 100, STEP + "moe_router/sort"],
        ["vdt_grouped_matmul.8", 7600, 1200,
         STEP + "moe_experts/vdt_grouped_matmul/pallas_call"],
        ["fusion.9", 8800, 200, STEP + "attn_qkv/dot_general"],
    ]
    return events


def conv_ctx(cell=None, spans=None, batch=8.0) -> dict:
    ctx = base.ctx_for(base.hand_made(), spans=spans, batch=batch)
    ctx["_named_trace"] = _xplane.reduce(conv_events(), scopes=["short_conv"])
    if cell is not None:
        ctx["config"] = cell.model_cfg()
    return ctx


def test_readers_divide_by_executions_and_by_k():
    ctx = conv_ctx()
    # one scan of two micro-steps, one single step; ns -> ms
    assert decode_conv_ms.read(ctx) == pytest.approx(800 / 1e6 / 2)
    assert decode_moe32_ms.read(ctx) == pytest.approx(1800 / 1e6 / 2)
    assert step_conv_ms.read(ctx) == pytest.approx(500 / 1e6)
    assert step_moe32_ms.read(ctx) == pytest.approx(1200 / 1e6)


def test_without_the_configurations_scope_the_time_is_unattributed():
    """The parent's side of a traced run: a cell whose configuration
    lists no ``short_conv`` files those operations nowhere, and the
    reader finds nothing and does not raise."""
    ctx = conv_ctx()
    ctx["_named_trace"] = _xplane.reduce(conv_events())
    assert decode_conv_ms.read(ctx) is None
    assert step_conv_ms.read(ctx) is None


def test_new_readers_find_nothing_in_the_other_models_traces():
    ctx = base.ctx_for(base.hand_made())  # the Mixtral-shaped trace
    for reader in (decode_conv_ms, step_conv_ms, moe_busiest_expert_x,
                   decode_moe32_roofline_pct):
        assert reader.read(ctx) is None
    no_trace = dict(conv_ctx(), _named_trace=None, spans=None)
    for name in NEW_METRICS:
        assert base.read(name, no_trace) is None


def test_roofline_share_is_the_models_seconds_over_the_scopes(cell):
    ctx = conv_ctx(cell)
    decode = ctx["_named_trace"]["programs"]["decode"]["scopes"]
    decode["moe_router"], decode["moe_experts"] = 1e-3, 19e-3  # two steps
    model = kernel_model_lfm2.experts(ctx["config"], 8.0, base.PEAK)
    assert decode_moe32_roofline_pct.read(ctx) == pytest.approx(
        100 * model["seconds"] / 10e-3)
    decode["moe_experts"] = 1e-3  # 1 ms a step: no chip reads that fast
    with pytest.raises(ValueError, match="decode_moe32_roofline_pct reads"):
        decode_moe32_roofline_pct.read(ctx)


CAPTURE = {"name": "profile.capture", "start": 10.5, "duration": 2.0,
           "attributes": {"begin_wall": 10.5, "end_wall": 12.5}}


def _step_dispatch(tokens: int, moe: str = "grouped", start=11.0) -> dict:
    return {"name": "runner.dispatch", "start": start, "duration": 0.001,
            "attributes": {"program": "vdt_step_t2048_q2048_s64_p256",
                           "tokens": tokens, "moe": moe}}


def test_grouped_kernels_share_is_the_steps_work_over_its_time(cell):
    cfg = cell.model_cfg()
    one = kernel_model_lfm2.grouped_experts(cfg, 2000.0, base.PEAK)
    # 8,000 rows x 3 products x 2 x 2048 x 1792 in each of 4 layers
    assert one["ops"] == 4 * 8000 * 2 * 11_010_048
    assert one["bytes"] == pytest.approx(2.0 * 4 * (
        32 * 11_010_048 + 8000 * (2048 + 1792 + 1792 + 2048)), rel=1e-6)
    # 704 MB of experts a layer: their read is the floor up to ~2,100
    # tokens, the products past it
    assert one["binds"] == "hbm"
    assert kernel_model_lfm2.grouped_experts(
        cfg, 4000.0, base.PEAK)["binds"] == "flops"
    spans = [CAPTURE, _step_dispatch(2000), _step_dispatch(1000),
             _step_dispatch(64, moe="dense"),
             _step_dispatch(2048, start=20.0)]  # after the capture
    ctx = conv_ctx(cell, spans)
    step = ctx["_named_trace"]["programs"]["step"]
    step["kernels"]["vdt_grouped_matmul"] = 20e-3  # the capture's steps
    two = kernel_model_lfm2.grouped_experts(cfg, 1000.0, base.PEAK)
    assert step_moe32_roofline_pct.read(ctx) == pytest.approx(
        100 * (one["seconds"] + two["seconds"]) / 20e-3)
    step["kernels"]["vdt_grouped_matmul"] = 1e-3
    with pytest.raises(ValueError, match="step_moe32_roofline_pct reads"):
        step_moe32_roofline_pct.read(ctx)
    # no step traced with the kernel, no capture span, or another
    # model's trace: nothing
    assert step_moe32_roofline_pct.read(
        conv_ctx(cell, [CAPTURE, _step_dispatch(64, moe="dense")])) is None
    assert step_moe32_roofline_pct.read(
        conv_ctx(cell, [_step_dispatch(2000)])) is None
    assert step_moe32_roofline_pct.read(base.ctx_for(base.hand_made())) is None


def _fetch(start: float, **picks) -> dict:
    return {"name": "runner.fetch", "start": start, "duration": 0.001,
            "attributes": {"step_id": 1, **{
                f"moe_picks_{k}": v for k, v in picks.items()}}}


def test_busiest_expert_sums_the_windows_fetch_spans(cell):
    ctx = conv_ctx(cell, [
        _fetch(12.0, all=2048, busiest=96),
        _fetch(13.0, all=1024, busiest=64),
        _fetch(500.0, all=4096, busiest=4096),  # after the window
        _fetch(14.0, zero=5, held=1, absent=2),  # another model's kinds
        {"name": "runner.fetch", "start": 15.0, "duration": 0.001,
         "attributes": {"step_id": 2}},
    ])
    assert moe_busiest_expert_x.read(ctx) == pytest.approx(32 * 160 / 3072)
    assert base.read("moe_zero_pick_pct", conv_ctx(cell, [
        _fetch(12.0, all=2048, busiest=96)])) is None


# ---- the reference ----------------------------------------------------------
def test_the_int4_control_moves_the_references_logprobs(cell):
    import numpy as np

    from benchmark.reference import lfm2_moe as ref
    from vllm_distributed_tpu import testing

    cfg = testing.lfm2_config()
    toks, pos = list(range(3, 23)), [18, 19]
    sound = ref.logprobs(ref.make_weights(cfg, 1), cfg, toks, pos)
    low = ref.logprobs(ref.make_weights(cfg, 1, bits=4), cfg, toks, pos)
    assert sound.shape == low.shape == (2, cfg["vocab_size"])
    assert np.isfinite(low).all() and np.abs(sound - low).max() > 1e-4
    assert np.allclose(np.exp(sound).sum(-1), 1.0, atol=1e-5)
    assert ref.ARCHITECTURE == cell.hf["architectures"][0]
