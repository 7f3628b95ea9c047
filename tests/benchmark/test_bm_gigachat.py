"""What ISSUE 43 adds to the yardstick: the GigaChat3.1 configuration's
sizes module against hand counts, its manifest entries **selected by
name**, its file's keys against the catalog row, the kernel model, the
nine new readers on a hand-made trace (and ``None`` on the other
cells'), and the reference's control."""

import json
import os

import pytest

from benchmark import kernel_model_dsv3, run
from benchmark.layer_metrics import (
    _xplane,
    decode_mla1_ms,
    decode_mla1_roofline_pct,
    decode_moe_held16_ms,
    decode_moe_held16_roofline_pct,
    decode_moe_shared_ms,
    moe_held_busiest_x,
    moe_held_pick_pct,
    step_moe_held16_ms,
    step_moe_held16_roofline_pct,
)
from tests.benchmark import test_bm_xplane as base

CELL = "gigachat3.1-702b-chat-closed-64"
CONFIG = "gigachat3.1-702b-a36b-ep16-bf16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OTHER_CELLS = ("mixtral8x7b-chat-closed", "longcat-flash-chat-closed-64",
               "lfm2-8b-a1b-chat-closed-64")
NEW_METRICS = (
    "decode_mla1_ms", "decode_mla1_roofline_pct", "decode_moe_shared_ms",
    "decode_moe_held16_ms", "decode_moe_held16_roofline_pct",
    "step_moe_held16_ms", "step_moe_held16_roofline_pct",
    "moe_held_pick_pct", "moe_held_busiest_x")
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers", "max_model_len"]


@pytest.fixture(scope="module")
def cell():
    return run.Cell(CELL, rehearsal=False)


# ---- sizes, by hand ---------------------------------------------------------
def test_a_token_holds_7680_bytes(cell):
    cfg = cell.model_cfg()
    assert "head_dim" not in cfg  # a sizes module of its own: nothing added
    # 6 layers x one row of 512 + 64 values in 640 lanes x 2 B; the
    # 192-wide values are no part of it
    assert cell.sizes.kv_bytes_per_token(cfg) == 6 * 640 * 2 == 7680
    assert cell.sizes.kv_bytes_per_token(dict(cfg, v_head_dim=128)) == 7680
    # the issue's fallback depth, and the published one
    assert cell.sizes.kv_bytes_per_token(dict(cfg, num_hidden_layers=5)) == 6400
    assert cell.sizes.kv_bytes_per_token(
        dict(cfg, num_hidden_layers=64)) == 81920


def test_parameter_counts_are_the_issues_arithmetic(cell):
    cfg, sz = cell.model_cfg(), cell.sizes
    mla = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
           + 512 * 64 * (128 + 192) + 64 * 192 * 7168)
    assert sz.mla_params(cfg) == mla and round(mla / 1e6, 1) == 132.6
    assert sz.expert_params(cfg) == 3 * 7168 * 2048
    assert round(sz.expert_params(cfg) / 1e6, 2) == 44.04
    assert sz.shared_params(cfg) == sz.expert_params(cfg)
    assert sz.routed_width(cfg) == 256 and sz.routed_layers(cfg) == 5
    outside = mla + 3 * 7168 * 2048 + 7168 * 256  # a routed layer
    assert round(outside / 1e6, 1) == 178.5
    routed = outside + 16 * sz.expert_params(cfg)
    assert round(routed / 1e6, 1) == 883.1 and round(2 * routed / 1e9, 3) == 1.766
    dense = mla + 3 * 7168 * 18432
    # 528.9 M (the issue adds the rounded 132.6 and 396.4: 529.0)
    assert round(dense / 1e6) == 529 and round(2 * dense / 1e9, 3) == 1.058
    vocab = 2 * 16032 * 7168
    assert round(vocab / 1e6, 1) == 229.8 and round(2 * vocab / 1e9, 2) == 0.46
    held = dense + 5 * routed + vocab
    assert sz.weight_params(cfg) == held
    assert round(2 * held / 1e9, 2) == 10.35
    # whole, one routed layer is 11.45 B parameters: no chip holds one
    whole = outside + 256 * sz.expert_params(cfg)
    assert round(whole / 1e9, 2) == 11.45 and round(2 * whole / 1e9, 1) == 22.9
    # the fallback depth: one dense and four routed layers
    four = dict(cfg, num_hidden_layers=5)
    assert round(2 * sz.weight_params(four) / 1e9, 2) == 8.58


def test_a_micro_step_reads_the_experts_its_batch_reaches(cell):
    cfg, sz = cell.model_cfg(), cell.sizes
    assert sz.experts_touched(cfg, 1) == pytest.approx(16 * 8 / 256)
    reached = 16 * (1 - (1 - 8 / 256) ** 64)
    assert sz.experts_touched(cfg, 64) == pytest.approx(reached)
    assert round(reached, 1) == 13.9
    outside = (6 * sz.mla_params(cfg) + 3 * 7168 * 18432
               + 5 * (3 * 7168 * 2048 + 7168 * 256) + 7168 * 16032)
    assert sz.outside_experts_params(cfg) == outside
    want = 2.0 * (outside + 5 * reached * 3 * 7168 * 2048)
    assert sz.decode_weight_bytes(cfg, 64) == pytest.approx(want)
    assert round(want / 1e9, 1) == 9.2  # the issue's ~9.2 GB
    # never more than what the chip holds less the gathered embedding:
    # a form that reads all 16 reads more than this count, so
    # decode_weight_bw_pct cannot pass 100 on its account
    ceiling = 2.0 * (sz.weight_params(cfg) - 7168 * 16032)
    assert sz.decode_weight_bytes(cfg, 4096) == pytest.approx(ceiling)
    assert sz.decode_weight_bytes(cfg, 64) < ceiling


def test_the_kernel_model_counts_one_sublayer_a_layer_and_the_rows_here(cell):
    cfg = cell.model_cfg()
    got = kernel_model_dsv3.mla_attention(cfg, 40000.0, 64.0, base.PEAK)
    assert got["bytes"] == 6 * 2 * (40000 * 576 + 64 * 64 * (576 + 512))
    assert got["ops"] == 6 * 40000 * 64 * (576 + 512) * 2
    wider = kernel_model_dsv3.mla_attention(
        dict(cfg, v_head_dim=128), 40000.0, 64.0, base.PEAK)
    assert wider == got  # the latent-space count does not depend on dv
    held = kernel_model_dsv3.held_experts(cfg, 64.0, base.PEAK)
    reached = 16 * (1 - (1 - 8 / 256) ** 64)
    assert held["bytes"] == pytest.approx(
        2 * 5 * (reached * 44_040_192 + 7168 * 256))
    # 64 rows x 8 picks x 1/16 land here: 32 rows an expert layer
    assert held["ops"] == pytest.approx(5 * 32 * 2 * 44_040_192)
    assert held["binds"] == "hbm"
    assert held["seconds"] == held["bytes"] / base.PEAK["hbm_bytes_per_s"]
    # a t2048 step: 16,384 picks a layer of which ~1,024 land here
    step = kernel_model_dsv3.grouped_held_experts(cfg, 5 * 1024.0, base.PEAK)
    assert step["ops"] == 5 * 1024 * 2 * 44_040_192
    all16 = 16 * (1 - (15 / 16) ** 1024)
    assert step["bytes"] == pytest.approx(2.0 * (
        5 * all16 * 44_040_192 + 5120 * (7168 + 2048 + 2048 + 7168)))
    assert step["binds"] == "hbm"  # 1.4 GB of experts a layer bind
    # few rows reach few experts: 5 picks over 5 layers reach one each
    few = kernel_model_dsv3.grouped_held_experts(cfg, 5.0, base.PEAK)
    assert few["bytes"] == pytest.approx(2.0 * (
        5 * 44_040_192 + 5 * (7168 + 2048 + 2048 + 7168)))


# ---- manifest and files -----------------------------------------------------
def test_manifest_entries_are_the_issues_selected_by_name(cell):
    m = cell.manifest
    (conf,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert conf["reduced"] == REDUCED
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    (entry,) = [w for w in m["workloads"] if w["name"] == CELL]
    assert entry == cell.entry
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "chat-closed-64", 1)
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    # no other cell runs this configuration; one pair, once
    assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] == [CELL]
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert len(by_name) == len(m["per_layer"])
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["layer"] in ("kernels", "engine")
    assert {by_name[n]["moves"] for n in NEW_METRICS} == {
        "tpot_p50_ms", "out_tok_s"}
    for name in NEW_METRICS:
        if name.endswith("_roofline_pct"):
            assert by_name[name]["unit"] == "%"
    reported = {x["name"] for x in cell.metrics_for("per_layer")}
    assert set(NEW_METRICS) <= reported
    assert {"decode_weight_bw_pct", "step_attention_ms", "decode_step_ms",
            "device_idle_pct"} <= reported
    assert not {"decode_experts_ms", "decode_mla_ms", "moe_zero_pick_pct",
                "decode_moe32_ms", "moe_busiest_expert_x"} & reported
    for other in OTHER_CELLS:
        theirs = {x["name"] for x in run.Cell(
            other, rehearsal=False).metrics_for("per_layer")}
        assert not set(NEW_METRICS) & theirs
    # the traffic is the file the benchmark had
    assert cell.mix["arrival"] == {"process": "closed", "clients": 64}
    assert cell.bench["scopes"] == ["mla_absorb", "moe_shared"]
    assert cell.bench["sizes"] == cell.bench["reference"] == "deepseek_v3"
    assert cell.bench["num_decode_steps"] == 8
    assert cell.bench["server_args"] == [
        "--quantization", "none", "--max-model-len", "4096"]
    longcat = run.Cell("longcat-flash-chat-closed-64", rehearsal=False)
    assert cell.bench["require_backends"] == longcat.bench["require_backends"]


def test_every_published_key_stands_and_the_cuts_are_the_six_named(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    (row,) = [r for r in map(json.loads, open(CATALOG))
              if r["name"] == "GigaChat3.1-702B-A36B"]
    assert cell.bench["source"] == row["source_url"]
    (conf,) = [c for c in cell.manifest["configs"] if c["name"] == CONFIG]
    assert conf["source"] == row["source_url"]
    missing = set(row["config"]) - set(cell.hf)
    assert not missing, missing  # every catalog key is in the file
    differs = {k for k, v in row["config"].items() if cell.hf.get(k) != v}
    assert differs == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers"}
    assert set(cell.bench["reduced"]) == differs | {"max_model_len"}
    assert list(cell.bench["reduced"]) == REDUCED
    assert cell.hf["rope_scaling"] == row["config"]["rope_scaling"]
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
                "qk_nope_head_dim", "v_head_dim", "num_attention_heads",
                "num_experts_per_tok", "n_group", "topk_group",
                "n_shared_experts"):
        assert cell.hf[key] == row["config"][key], key
    # the floors: one leading dense layer and >= 4 after it, >= 8
    # experts held, >= 1/8 of the vocabulary
    assert cell.hf["first_k_dense_replace"] == 1
    assert cell.hf["num_hidden_layers"] - 1 >= 4
    assert cell.hf["n_routed_experts"] >= 8
    assert cell.hf["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert cell.bench["published"] == {
        "num_hidden_layers": 64, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 128256,
        "num_nextn_predict_layers": 1, "max_model_len": 262144}
    # the share: 16 chips x 16 held = the published 256
    assert set(cell.bench["not_published"]) == {
        "expert_parallel_size", "expert_parallel_rank"}
    assert (cell.hf["expert_parallel_size"] * cell.hf["n_routed_experts"]
            == row["config"]["n_routed_experts"])
    assert cell.hf["expert_parallel_rank"] == 0
    assert "--max-model-len" in cell.bench["server_args"]


def test_the_check_has_limits_and_says_how_they_were_placed(cell):
    check, cal = cell.bench["check"], cell.bench["check_calibration"]
    assert set(check) == {"values", "tokens"}
    assert set(check["values"]) == {"p50", "p75"}
    assert set(check["tokens"]) == {"flipped_share", "mean"}
    for key in ("where", "rule", "sound_runs",
                "control_int4_reference_in_place", "factors"):
        assert cal.get(key), key


# ---- the readers, on a hand-made trace --------------------------------------
SCAN, STEP = base.SCAN, base.STEP


def dsv3_events() -> dict:
    """``hand_made`` with the programs of a model of one latent
    sublayer a layer, a shared expert and held experts: in the fused
    scan the latent kernel, the absorb products, the router, the held
    experts' einsum and the shared expert; in the single step the
    router's sort, the grouped kernel and the shared expert."""
    events = base.hand_made()
    plane = events["devices"]["/device:TPU:0"]
    plane["ops"] = [
        ["while.1", 1000, 3000, SCAN[:-24] + "while"],
        ["vdt_mla_attention.1", 1000, 400,
         SCAN + "attn/vdt_mla_attention/pallas_call"],
        ["fusion.2", 1400, 200, SCAN + "mla_absorb/dot_general"],
        ["fusion.3", 1600, 300, SCAN + "moe_router/top_k"],
        ["fusion.4", 1900, 1500, SCAN + "moe_experts/th,ehi->tei/dot_general"],
        ["fusion.5", 3400, 500, SCAN + "moe_shared/dot_general"],
        ["fusion.6", 3900, 100, SCAN + "mlp/add"],
        ["fusion.7", 7000, 100, STEP + "moe_router/sort"],
        ["vdt_grouped_matmul.8", 7100, 1200,
         STEP + "moe_experts/vdt_grouped_matmul/pallas_call"],
        ["fusion.9", 8300, 300, STEP + "moe_shared/dot_general"],
        ["fusion.10", 8600, 200, STEP + "attn_qkv/dot_general"],
    ]
    return events


def dsv3_ctx(cell=None, spans=None, batch=8.0, scopes=None) -> dict:
    ctx = base.ctx_for(base.hand_made(), spans=spans, batch=batch)
    ctx["_named_trace"] = _xplane.reduce(
        dsv3_events(),
        scopes=["mla_absorb", "moe_shared"] if scopes is None else scopes)
    if cell is not None:
        ctx["config"] = cell.model_cfg()
    return ctx


def test_readers_divide_by_executions_and_by_k(cell):
    ctx = dsv3_ctx(cell)
    # one scan of two micro-steps, one single step; ns -> ms
    assert decode_mla1_ms.read(ctx) == pytest.approx(400 / 1e6 / 2)
    assert decode_moe_shared_ms.read(ctx) == pytest.approx(500 / 1e6 / 2)
    assert decode_moe_held16_ms.read(ctx) == pytest.approx(1800 / 1e6 / 2)
    assert step_moe_held16_ms.read(ctx) == pytest.approx(1200 / 1e6)


def test_without_the_configurations_scope_the_shared_expert_is_unattributed(
        cell):
    """The parent's side of a traced run, or a cell whose configuration
    lists no ``moe_shared``: those operations are filed nowhere, the
    reader finds nothing and does not raise."""
    ctx = dsv3_ctx(cell, scopes=[])
    assert decode_moe_shared_ms.read(ctx) is None
    assert decode_mla1_ms.read(ctx) == pytest.approx(400 / 1e6 / 2)


@pytest.mark.parametrize("other", OTHER_CELLS)
def test_new_readers_find_nothing_in_the_other_cells(other):
    """Each new reader on the new model's own trace and spans, under
    another cell's configuration: ``None``, whatever the trace holds
    (the LongCat cell has the latent kernel and ``held`` picks too)."""
    theirs = run.Cell(other, rehearsal=False).model_cfg()
    spans = [CAPTURE, _step_dispatch(7), _fetch(11.0, 7, held=600, absent=9000,
                                                busiest=70)]
    ctx = dict(dsv3_ctx(None, spans), config=theirs)
    for name in NEW_METRICS:
        if name == "decode_moe_shared_ms":
            continue  # a scope's reader: silent where no scope is listed
        assert base.read(name, ctx) is None, name
    plain = base.ctx_for(base.hand_made())  # the Mixtral-shaped trace
    plain["config"] = theirs
    for name in NEW_METRICS:
        assert base.read(name, plain) is None, name


def test_no_trace_and_no_spans_give_none(cell):
    no_trace = dict(dsv3_ctx(cell), _named_trace=None, spans=None)
    for name in NEW_METRICS:
        assert base.read(name, no_trace) is None, name


def test_roofline_shares_are_the_models_seconds_over_the_measured(cell):
    ctx = dsv3_ctx(cell)
    decode = ctx["_named_trace"]["programs"]["decode"]
    decode["scopes"]["moe_router"] = 1e-3
    decode["scopes"]["moe_experts"] = 19e-3  # two micro-steps: 10 ms each
    model = kernel_model_dsv3.held_experts(ctx["config"], 8.0, base.PEAK)
    assert decode_moe_held16_roofline_pct.read(ctx) == pytest.approx(
        100 * model["seconds"] / 10e-3)
    decode["scopes"]["moe_experts"] = 1e-3  # 1 ms a step: no chip reads so
    with pytest.raises(ValueError,
                       match="decode_moe_held16_roofline_pct reads"):
        decode_moe_held16_roofline_pct.read(ctx)


def _decode_dispatch(kv_tokens: int, seqs: int, start=11.0) -> dict:
    return {"name": "runner.dispatch", "start": start, "duration": 0.001,
            "attributes": {"program": "vdt_decode_k8_s64_p256",
                           "kv_tokens": kv_tokens, "seqs": seqs}}


def test_latent_attentions_share_reads_the_scans_inside_the_capture(cell):
    spans = [CAPTURE, _decode_dispatch(30000, 60), _decode_dispatch(50000, 64),
             _decode_dispatch(900000, 64, start=20.0)]  # after the capture
    ctx = dsv3_ctx(cell, spans)
    ctx["_named_trace"]["programs"]["decode"]["kernels"][
        "vdt_mla_attention"] = 2e-3  # two micro-steps: 1 ms each
    model = kernel_model_dsv3.mla_attention(
        ctx["config"], 40000.0, 62.0, base.PEAK)
    assert decode_mla1_roofline_pct.read(ctx) == pytest.approx(
        100 * model["seconds"] / 1e-3)
    ctx["_named_trace"]["programs"]["decode"]["kernels"][
        "vdt_mla_attention"] = 2e-5
    with pytest.raises(ValueError, match="decode_mla1_roofline_pct reads"):
        decode_mla1_roofline_pct.read(ctx)


CAPTURE = {"name": "profile.capture", "start": 10.5, "duration": 2.0,
           "attributes": {"begin_wall": 10.5, "end_wall": 12.5}}


def _step_dispatch(step_id: int, moe: str = "grouped", start=11.0) -> dict:
    return {"name": "runner.dispatch", "start": start, "duration": 0.001,
            "attributes": {"program": "vdt_step_t2048_q2048_s64_p256",
                           "tokens": 2000, "moe": moe, "step_id": step_id}}


def _fetch(start: float, step_id: int, **picks) -> dict:
    return {"name": "runner.fetch", "start": start, "duration": 0.001,
            "attributes": {"step_id": step_id, **{
                f"moe_picks_{k}": v for k, v in picks.items()}}}


def test_grouped_kernels_share_counts_the_rows_routed_here(cell):
    """Two grouped steps inside the capture whose fetches say 5,000 and
    2,500 picks of held experts; a dense step, a grouped step after the
    capture and a fetch of a step that was not grouped do not count;
    neither do the steps' 16,000 ``tokens x k`` picks."""
    cfg = cell.model_cfg()
    spans = [CAPTURE,
             _step_dispatch(1), _fetch(11.1, 1, held=5000, absent=75000,
                                       busiest=600),
             _step_dispatch(2), _fetch(11.2, 2, held=2500, absent=37500,
                                       busiest=300),
             _step_dispatch(3, moe="dense"), _fetch(11.3, 3, held=99,
                                                    absent=1, busiest=9),
             _step_dispatch(4, start=20.0), _fetch(20.1, 4, held=4000,
                                                   absent=1, busiest=9)]
    ctx = dsv3_ctx(cell, spans)
    assert step_moe_held16_roofline_pct.rows_here(ctx) == [5000, 2500]
    step = ctx["_named_trace"]["programs"]["step"]
    step["kernels"]["vdt_grouped_matmul"] = 30e-3
    one = kernel_model_dsv3.grouped_held_experts(cfg, 5000.0, base.PEAK)
    two = kernel_model_dsv3.grouped_held_experts(cfg, 2500.0, base.PEAK)
    assert step_moe_held16_roofline_pct.read(ctx) == pytest.approx(
        100 * (one["seconds"] + two["seconds"]) / 30e-3)
    step["kernels"]["vdt_grouped_matmul"] = 1e-3
    with pytest.raises(ValueError,
                       match="step_moe_held16_roofline_pct reads"):
        step_moe_held16_roofline_pct.read(ctx)
    # no grouped step, no capture, or no fetch with the count: nothing
    assert step_moe_held16_roofline_pct.read(dsv3_ctx(cell, [
        CAPTURE, _step_dispatch(3, moe="dense"),
        _fetch(11.3, 3, held=9, absent=1, busiest=1)])) is None
    assert step_moe_held16_roofline_pct.read(dsv3_ctx(cell, [
        _step_dispatch(1), _fetch(11.1, 1, held=9, absent=1,
                                  busiest=1)])) is None
    assert step_moe_held16_roofline_pct.read(dsv3_ctx(cell, [
        CAPTURE, _step_dispatch(1)])) is None


def test_pick_readers_sum_the_windows_fetch_spans(cell):
    ctx = dsv3_ctx(cell, [
        _fetch(12.0, 1, held=1000, absent=15384, busiest=100),
        _fetch(13.0, 2, held=600, absent=7592, busiest=60),
        _fetch(500.0, 3, held=9999, absent=1, busiest=9999),  # after it
        _fetch(14.0, 4, zero=5, held=1, absent=2),  # another model's kinds
        _fetch(15.0, 5, all=2048, busiest=96),  # and another's
        {"name": "runner.fetch", "start": 16.0, "duration": 0.001,
         "attributes": {"step_id": 6}},
    ])
    assert moe_held_pick_pct.read(ctx) == pytest.approx(
        100 * 1600 / (1600 + 22976))
    assert moe_held_busiest_x.read(ctx) == pytest.approx(16 * 160 / 1600)
    # the other models' readers find nothing of theirs in this cell's
    # kinds, and this cell's nothing in theirs
    mine = dsv3_ctx(cell, [_fetch(12.0, 1, held=10, absent=150, busiest=3)])
    assert base.read("moe_zero_pick_pct", mine) is None
    assert base.read("moe_busiest_expert_x", mine) is None
    theirs = dsv3_ctx(cell, [_fetch(14.0, 4, zero=5, held=1, absent=2)])
    assert moe_held_pick_pct.read(theirs) is None
    assert moe_held_busiest_x.read(theirs) is None
    # an even router over 256 of which 16 are held reads 6.25
    even = dsv3_ctx(cell, [_fetch(12.0, 1, held=16, absent=240, busiest=1)])
    assert moe_held_pick_pct.read(even) == pytest.approx(6.25)
    assert moe_held_busiest_x.read(even) == pytest.approx(1.0)


# ---- the reference ----------------------------------------------------------
def test_the_int4_control_moves_the_references_logprobs(cell):
    import numpy as np

    from benchmark.reference import deepseek_v3 as ref
    from vllm_distributed_tpu import testing

    cfg = testing.deepseek_v3_config()
    toks, pos = list(range(3, 23)), [18, 19]
    sound = ref.logprobs(ref.make_weights(cfg, 1), cfg, toks, pos)
    low = ref.logprobs(ref.make_weights(cfg, 1, bits=4), cfg, toks, pos)
    assert sound.shape == low.shape == (2, cfg["vocab_size"])
    assert np.isfinite(low).all() and np.abs(sound - low).max() > 1e-4
    assert np.allclose(np.exp(sound).sum(-1), 1.0, atol=1e-5)
    assert ref.ARCHITECTURE == cell.hf["architectures"][0]
    # the cell's own file goes through ``flat``: the nested rope_scaling
    # reaches the jitted layer as numbers
    flat = ref.flat(cell.model_cfg())
    assert flat["yarn.factor"] == 64.0
    assert flat["yarn.original_max_position_embeddings"] == 4096.0
    assert ref.softmax_scale(flat) == pytest.approx(0.1446796)
