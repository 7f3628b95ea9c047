"""What ISSUE 48 (ISSUE 47's change, asked for again) adds to the
yardstick: the Falcon-H1 configuration's file against the catalog row,
its manifest entries **selected by name**, its sizes module and kernel model against the issue's hand
counts, the six new readers on a hand-made trace (and ``None`` on the
other four cells'), the reference's int4 control, and that the fused
scan at the cell's widths is a case of the compile rehearsal for a
described v5e."""

import json
import os

import pytest

from benchmark import kernel_model_falcon_h1 as km
from benchmark import run
from benchmark.layer_metrics import (
    _xplane,
    decode_ssm_ms,
    decode_ssm_update_ms,
    decode_ssm_update_roofline_pct,
    ssm_chunk_fill_pct,
    step_ssd_roofline_pct,
    step_ssm_ms,
)
from tests.benchmark import test_bm_xplane as base

CELL = "falcon-h1-34b-chat-closed-64"
CONFIG = "falcon-h1-34b-cut-bf16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OTHER_CELLS = ("mixtral8x7b-chat-closed", "longcat-flash-chat-closed-64",
               "lfm2-8b-a1b-chat-closed-64",
               "gigachat3.1-702b-chat-closed-64")
NEW_METRICS = (
    "decode_ssm_ms", "decode_ssm_update_ms", "decode_ssm_update_roofline_pct",
    "step_ssm_ms", "step_ssd_roofline_pct", "ssm_chunk_fill_pct")
REDUCED = ["num_hidden_layers", "max_model_len"]
STATE_VALUES = 32 * 128 * 256  # heads x head size x state size, a layer
DEPTH = 4  # the cut's layers: ISSUE 48's floor (run first at six)


@pytest.fixture(scope="module")
def cell():
    return run.Cell(CELL, rehearsal=False)


# ---- sizes, by hand ---------------------------------------------------------
def test_a_token_holds_2048_bytes_and_a_sequence_two_megabytes_a_layer(cell):
    cfg, sz = cell.model_cfg(), cell.sizes
    assert "benchmark" not in cfg and cfg["head_dim"] == 128
    # a layer: K and V x 4 KV heads x 128 x 2 B; the cut holds four
    # (8,192 B a token; 12,288 at the six layers ISSUE 48 counted)
    assert cfg["num_hidden_layers"] == DEPTH == 4
    assert sz.kv_bytes_per_token(cfg) == DEPTH * 2 * 4 * 128 * 2 == 8192
    # the convolution's last 3 rows of 4096 + 2 x 2 x 256 lanes
    assert sz.conv_dim(cfg) == 5120
    assert sz.conv_state_bytes_per_seq(cfg) == DEPTH * 30720
    assert STATE_VALUES == 1048576
    assert sz.ssm_state_bytes_per_seq(cfg) == DEPTH * 2 * STATE_VALUES
    assert sz.ssm_state_bytes_per_seq(cfg, "float32") == DEPTH * 4 * STATE_VALUES
    assert sz.state_bytes_per_seq(cfg) == DEPTH * 2127872 == 8511488
    # 64 sequences: 0.54 GB at a bfloat16 state, 1.08 GB at float32
    assert round(64 * sz.state_bytes_per_seq(cfg) / 1e9, 2) == 0.54
    assert round(64 * sz.state_bytes_per_seq(cfg, "float32") / 1e9, 2) == 1.08
    assert sz.state_bytes_per_seq(dict(cfg, ssm_state_dtype="float32")) == (
        sz.state_bytes_per_seq(cfg, "float32"))
    for depth in (4, 5, 6, 72):
        at = dict(cfg, num_hidden_layers=depth)
        assert sz.kv_bytes_per_token(at) == depth * 2048
        assert sz.state_bytes_per_seq(at) == depth * 2127872
    six = dict(cfg, num_hidden_layers=6)  # the issue's hand counts
    assert sz.kv_bytes_per_token(six) == 12288
    assert sz.state_bytes_per_seq(six) == 12767232
    assert round(64 * sz.state_bytes_per_seq(six) / 1e9, 2) == 0.82


def test_parameter_counts_are_the_issues_arithmetic(cell):
    cfg, sz = cell.model_cfg(), cell.sizes
    six = dict(cfg, num_hidden_layers=6)
    attention = 5120 * 2560 + 2 * 5120 * 512 + 2560 * 5120
    assert sz.attention_params(cfg) == attention
    assert round(attention / 1e6, 2) == 31.46
    in_proj, out_proj = 5120 * 9248, 4096 * 5120
    assert round(in_proj / 1e6, 2) == 47.35 and round(out_proj / 1e6, 2) == 20.97
    small = 4 * 5120 + 5120 + 4096 + 3 * 32  # taps, bias, norm, A, D, dt_bias
    assert sz.mixer_params(cfg) == in_proj + out_proj + small
    assert round(sz.mixer_params(cfg) / 1e6, 2) == 68.35
    assert sz.mlp_params(cfg) == 3 * 5120 * 21504
    assert round(sz.mlp_params(cfg) / 1e6, 1) == 330.3
    assert round(sz.layer_params(cfg) / 1e6, 1) == 430.1
    assert sz.vocab_params(cfg) == 261120 * 5120
    assert round(sz.vocab_params(cfg) / 1e6, 1) == 1336.9
    # whole layers and the untied embedding and head (the cut: four)
    for depth, gb in ((4, 8.79), (5, 9.65), (6, 10.51), (72, 67.28)):
        at = dict(cfg, num_hidden_layers=depth)
        assert round(2 * sz.total_params(at) / 1e9, 2) == gb, depth
    # a micro-step reads the layers and the head, whatever the batch
    assert sz.decode_weight_bytes(cfg, 1) == sz.decode_weight_bytes(cfg, 64)
    assert round(2 * sz.total_params(cfg) / 1e9, 2) == 8.79
    assert round(sz.decode_weight_bytes(cfg, 64) / 1e9, 2) == 6.11
    assert round(sz.decode_weight_bytes(six, 64) / 1e9, 2) == 7.84
    for at, pct in ((cfg, 37), (six, 28),
                    (dict(cfg, num_hidden_layers=72), 3)):  # 4% of weights
        head_share = 2 * sz.vocab_params(at) / (
            sz.decode_weight_bytes(at)
            + 2 * 64 * sz.ssm_state_bytes_per_seq(at))
        assert round(100 * head_share) == pct, pct


def test_the_kernel_model_counts_states_once_in_and_once_out(cell):
    cfg = cell.model_cfg()
    got = km.state_update(cfg, 64.0, base.PEAK)
    per_token = DEPTH * (2 * (4096 + 1024 + 4096) + 4 * 32)
    assert got["bytes"] == 64 * (2 * DEPTH * 2 * STATE_VALUES + per_token)
    assert round(got["bytes"] / 1e9, 2) == 1.08
    assert got["ops"] == 64 * DEPTH * STATE_VALUES * 5
    assert got["binds"] == "hbm"
    assert got["seconds"] == got["bytes"] / base.PEAK["hbm_bytes_per_s"]
    wide = km.state_update(
        dict(cfg, ssm_state_dtype="float32"), 64.0, base.PEAK)
    assert wide["bytes"] - got["bytes"] == 64 * 2 * DEPTH * 2 * STATE_VALUES
    # a t2048 step of 64 sequences: the products at chunk 128 (causal
    # half of a tile) and the states once in, once out
    step = km.chunk_scan(cfg, 2048.0, 64.0, base.PEAK)
    per = 2 * (64 * 512 + 64 * 4096 + 2 * STATE_VALUES)
    assert step["ops"] == DEPTH * 2048 * per
    assert step["bytes"] == (
        DEPTH * 2048 * (2 * (2 * 4096 + 1024) + 128)
        + 64 * 2 * DEPTH * 2 * STATE_VALUES)
    assert step["binds"] == "hbm"
    # the states bind: without them the products and the activations'
    # bytes are level (0.30 ms each at the chip's peaks)
    alone = km.chunk_scan(cfg, 2048.0, 0.0, base.PEAK)
    assert 0.9 < (alone["ops"] / base.PEAK["bf16_flops"]) / (
        alone["bytes"] / base.PEAK["hbm_bytes_per_s"]) < 1.1


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
    for said in ("four layers", "head", "16k+"):
        assert said in entry["why"], said
    assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] == [CELL]
    by_name = {x["name"]: x for x in m["per_layer"]}
    assert len(by_name) == len(m["per_layer"])
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["layer"] in ("kernels", "step programs")
        if name.endswith("_roofline_pct"):
            assert by_name[name]["unit"] == "%"
    assert {by_name[n]["moves"] for n in NEW_METRICS} == {
        "tpot_p50_ms", "out_tok_s"}
    # (by name, never by place: the next PR appends behind these)
    reported = {x["name"] for x in cell.metrics_for("per_layer")}
    assert set(NEW_METRICS) <= reported
    assert {"decode_weight_bw_pct", "step_attention_ms", "decode_step_ms",
            "device_idle_pct"} <= reported
    assert not {"decode_experts_ms", "decode_mla_ms", "decode_conv_ms",
                "decode_moe32_ms", "decode_mla1_ms"} & reported
    for other in OTHER_CELLS:
        theirs = {x["name"] for x in run.Cell(
            other, rehearsal=False).metrics_for("per_layer")}
        assert not set(NEW_METRICS) & theirs
    assert cell.mix["arrival"] == {"process": "closed", "clients": 64}
    assert cell.bench["scopes"] == ["ssm_mixer", "ssm_scan"]
    assert cell.bench["sizes"] == cell.bench["reference"] == "falcon_h1"
    assert cell.bench["num_decode_steps"] == 8
    assert cell.bench["server_args"] == [
        "--quantization", "none", "--max-model-len", "4096"]
    lfm2 = run.Cell("lfm2-8b-a1b-chat-closed-64", rehearsal=False)
    assert cell.bench["require_backends"] == lfm2.bench["require_backends"]
    # the step programs are told apart by name here: a single step's
    # scan may hold an XLA loop one day, a fused scan always does
    assert cell.bench["trace"]["decode_module"]["name"] == "jit_vdt_decode"
    assert cell.bench["trace"]["prefill_module"]["name"] == "jit_vdt_step"


def test_every_published_key_stands_and_the_cuts_are_the_two_named(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    (row,) = [r for r in map(json.loads, open(CATALOG))
              if r["name"] == "Falcon-H1-34B-Instruct"]
    assert cell.bench["source"] == row["source_url"]
    (conf,) = [c for c in cell.manifest["configs"] if c["name"] == CONFIG]
    assert conf["source"] == row["source_url"]
    missing = set(row["config"]) - set(cell.hf)
    assert not missing, missing  # every catalog key is in the file
    differs = {k for k, v in row["config"].items() if cell.hf.get(k) != v}
    assert differs == {"num_hidden_layers"}
    assert set(cell.bench["reduced"]) == differs | {"max_model_len"}
    assert list(cell.bench["reduced"]) == REDUCED
    assert cell.hf["num_hidden_layers"] in (4, 5, 6)  # the issue's rule
    assert cell.bench["published"] == {
        "num_hidden_layers": 72, "max_model_len": 262144}
    # what the file adds to the catalog's keys is said under ``assumed``
    added = set(cell.hf) - set(row["config"])
    assert added == {"architectures", "torch_dtype", "ssm_state_dtype",
                     "bos_token_id", "eos_token_id"}
    assert {"architectures", "torch_dtype", "ssm_state_dtype",
            "weights"} <= set(cell.bench["assumed"])
    assert cell.hf["ssm_state_dtype"] in ("bfloat16", "float32")
    for said in ("state_dtype", "bytes", "why"):
        assert cell.bench["precision"].get(said), said
    assert "--max-model-len" in cell.bench["server_args"]


def test_the_check_has_limits_and_says_how_they_were_placed(cell):
    check, cal = cell.bench["check"], cell.bench["check_calibration"]
    assert set(check) == {"values", "tokens"}
    assert set(check["values"]) == {"p50", "p75"}
    assert set(check["tokens"]) == {"flipped_share", "mean"}
    for key in ("where", "rule", "sound_runs",
                "control_int4_reference_in_place", "control_of_the_state",
                "blind_to", "factors"):
        assert cal.get(key), key


# ---- the readers, on a hand-made trace --------------------------------------
SCAN, STEP = base.SCAN, base.STEP
SCOPES = ["ssm_mixer", "ssm_scan"]
CAPTURE = {"name": "profile.capture", "start": 10.5, "duration": 2.0,
           "attributes": {"begin_wall": 10.5, "end_wall": 12.5}}


def falcon_events() -> dict:
    """``hand_made`` with the programs of a model with a mixer beside
    its attention: in the fused scan the mixer's projections, the
    one-token update nested in it, attention, the MLP; in the single
    step the same with the chunked scan."""
    events = base.hand_made()
    plane = events["devices"]["/device:TPU:0"]
    plane["ops"] = [
        ["while.1", 1000, 3000, SCAN[:-24] + "while"],
        ["fusion.1", 1000, 600, SCAN + "ssm_mixer/dot_general"],
        ["fusion.2", 1600, 1000, SCAN + "ssm_mixer/ssm_scan/mul"],
        ["fusion.3", 2600, 200, SCAN + "ssm_mixer/reduce"],
        ["fusion.4", 2800, 400, SCAN + "attn/dot_general"],
        ["fusion.5", 3200, 800, SCAN + "mlp/dot_general"],
        ["fusion.6", 7000, 500, STEP + "ssm_mixer/dot_general"],
        ["fusion.7", 7500, 900, STEP + "ssm_mixer/ssm_scan/dot_general"],
        ["fusion.8", 8400, 300, STEP + "attn_qkv/dot_general"],
    ]
    return events


def falcon_ctx(cell=None, spans=None, batch=8.0, scopes=None) -> dict:
    ctx = base.ctx_for(base.hand_made(), spans=spans, batch=batch)
    ctx["_named_trace"] = _xplane.reduce(
        falcon_events(), scopes=SCOPES if scopes is None else scopes)
    if cell is not None:
        ctx["config"] = cell.model_cfg()
    return ctx


def _step_dispatch(start=11.0, **attrs) -> dict:
    return {"name": "runner.dispatch", "start": start, "duration": 0.001,
            "attributes": {"program": "vdt_step_t2048_q2048_s64_p256",
                           **attrs}}


def test_readers_divide_by_executions_and_by_k(cell):
    ctx = falcon_ctx(cell)
    # one scan of two micro-steps, one single step; ns -> ms; the
    # mixer's time is its own and the nested recurrence's
    assert decode_ssm_ms.read(ctx) == pytest.approx(1800 / 1e6 / 2)
    assert decode_ssm_update_ms.read(ctx) == pytest.approx(1000 / 1e6 / 2)
    assert step_ssm_ms.read(ctx) == pytest.approx(1400 / 1e6)
    # a kernel of the expected name takes the scope's place
    ctx["_named_trace"]["programs"]["decode"]["kernels"][
        "vdt_ssm_update"] = 3e-6
    assert decode_ssm_update_ms.read(ctx) == pytest.approx(4000 / 1e6 / 2)


def test_without_the_configurations_scopes_nothing_is_read(cell):
    """The parent's side of a traced run lists the scopes (the
    benchmark's files are laid over it) but its programs have none; a
    trace attributed without them files the mixer nowhere: the readers
    find nothing and do not raise."""
    ctx = falcon_ctx(cell, scopes=[])
    for name in NEW_METRICS:
        assert base.read(name, ctx) is None, name


@pytest.mark.parametrize("other", OTHER_CELLS)
def test_new_readers_find_nothing_in_the_other_cells(other):
    theirs = run.Cell(other, rehearsal=False).model_cfg()
    spans = [CAPTURE, _step_dispatch(tokens=2000, state_rows=64,
                                     state_fresh=3)]
    ctx = dict(falcon_ctx(None, spans), config=theirs)
    for name in NEW_METRICS:
        assert base.read(name, ctx) is None, name
    plain = base.ctx_for(base.hand_made())
    plain["config"] = theirs
    for name in NEW_METRICS:
        assert base.read(name, plain) is None, name


def test_no_trace_and_no_spans_give_none(cell):
    no_trace = dict(falcon_ctx(cell), _named_trace=None, spans=None)
    for name in NEW_METRICS:
        assert base.read(name, no_trace) is None, name


def test_the_updates_share_is_the_models_seconds_over_the_measured(cell):
    ctx = falcon_ctx(cell)
    decode = ctx["_named_trace"]["programs"]["decode"]
    decode["scopes"]["ssm_scan"] = 10e-3  # two micro-steps: 5 ms each
    model = km.state_update(ctx["config"], 8.0, base.PEAK)
    assert decode_ssm_update_roofline_pct.read(ctx) == pytest.approx(
        100 * model["seconds"] / 5e-3)
    decode["scopes"]["ssm_scan"] = 1e-4  # no chip moves 0.2 GB so fast
    with pytest.raises(ValueError,
                       match="decode_ssm_update_roofline_pct reads"):
        decode_ssm_update_roofline_pct.read(ctx)


def test_the_scans_share_reads_the_single_steps_inside_the_capture(cell):
    """A chunked step and a one-token step inside the capture count,
    each by its own model; a step after the capture and a fused scan do
    not."""
    cfg = cell.model_cfg()
    spans = [
        CAPTURE,
        _step_dispatch(tokens=1900, state_rows=60, state_fresh=2,
                       ssm_tokens_live=1900, ssm_tokens_tiled=2048),
        _step_dispatch(tokens=40, state_rows=40, state_fresh=0),
        _step_dispatch(start=20.0, tokens=900, state_rows=9,
                       ssm_tokens_live=900, ssm_tokens_tiled=1024),
        {"name": "runner.dispatch", "start": 11.2, "duration": 0.001,
         "attributes": {"program": "vdt_decode_k8_s64_p256", "seqs": 64,
                        "state_rows": 64}},
    ]
    ctx = falcon_ctx(cell, spans)
    step = ctx["_named_trace"]["programs"]["step"]
    step["scopes"]["ssm_scan"] = 20e-3
    one = km.chunk_scan(cfg, 1900.0, 60.0, base.PEAK)
    two = km.state_update(cfg, 40.0, base.PEAK)
    assert step_ssd_roofline_pct.read(ctx) == pytest.approx(
        100 * (one["seconds"] + two["seconds"]) / 20e-3)
    step["scopes"]["ssm_scan"] = 1e-4
    with pytest.raises(ValueError, match="step_ssd_roofline_pct reads"):
        step_ssd_roofline_pct.read(ctx)
    # no capture span, or no step that says its state rows: nothing
    assert step_ssd_roofline_pct.read(falcon_ctx(cell, spans[1:])) is None
    assert step_ssd_roofline_pct.read(falcon_ctx(cell, [
        CAPTURE, _step_dispatch(tokens=40)])) is None


def test_the_fill_sums_the_windows_dispatch_spans(cell):
    ctx = falcon_ctx(cell, [
        _step_dispatch(ssm_tokens_live=1500, ssm_tokens_tiled=2048),
        _step_dispatch(start=13.0, ssm_tokens_live=100, ssm_tokens_tiled=128),
        _step_dispatch(start=14.0, tokens=40, state_rows=40),  # one-token
        _step_dispatch(start=500.0, ssm_tokens_live=1, ssm_tokens_tiled=16),
    ])
    assert ssm_chunk_fill_pct.read(ctx) == pytest.approx(
        100 * 1600 / (2048 + 128))
    assert ssm_chunk_fill_pct.read(falcon_ctx(cell, [
        _step_dispatch(tokens=40, state_rows=40)])) is None


# ---- the reference ----------------------------------------------------------
def test_the_int4_control_moves_the_references_logprobs(cell):
    import numpy as np

    from benchmark.reference import falcon_h1 as ref
    from vllm_distributed_tpu import testing

    cfg = testing.falcon_h1_config()
    toks, pos = list(range(3, 23)), [18, 19]
    sound = ref.logprobs(ref.make_weights(cfg, 1), cfg, toks, pos)
    low = ref.logprobs(ref.make_weights(cfg, 1, bits=4), cfg, toks, pos)
    assert sound.shape == low.shape == (2, cfg["vocab_size"])
    assert np.isfinite(low).all() and np.abs(sound - low).max() > 1e-3
    assert np.allclose(np.exp(sound).sum(-1), 1.0, atol=1e-5)
    assert ref.ARCHITECTURE == cell.hf["architectures"][0]
    # the head in blocks of vocabulary columns is the head
    assert ref.HEAD_BLOCKS > 1 and cfg["vocab_size"] % ref.HEAD_BLOCKS == 0
    # the cell's own widths are what the reference's split reads
    assert ref.segment_widths(cell.model_cfg()) == (4096, 4096, 512, 512, 32)
    assert sum(ref.segment_widths(cell.model_cfg())) == 9248


# ---- the fused scan, compiled for a described v5e ---------------------------
def test_the_compile_rehearsal_has_this_configuration_as_a_case():
    """``test_bm_compile_rehearsal.py`` compiles the fused scan of every
    file under ``benchmark/configs/`` for a described v5e, at its real
    widths and depth, in the one file that may describe the topology:
    this configuration's file being there makes it a case, and no file
    the benchmark has is edited for it."""
    from tests.benchmark import test_bm_compile_rehearsal as rehearsal

    assert CONFIG in rehearsal.CONFIGS
