"""What ISSUE 34 adds to the yardstick: the LongCat-Flash
configuration's sizes module against hand counts, its manifest entries
and files, the five new readers on a hand-made trace (and ``None`` on
the Mixtral one), the kernel model, and the reference's control."""

import json
import os

import pytest

from benchmark import kernel_model_mla, run
from benchmark.layer_metrics import (
    _xplane,
    decode_mla_absorb_ms,
    decode_mla_ms,
    decode_mla_roofline_pct,
    decode_moe_held_ms,
    moe_zero_pick_pct,
)
from tests.benchmark import test_bm_xplane as base

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "longcat-flash-chat-closed-64"
CONFIG = "longcat-flash-chat-ep32-bf16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("decode_mla_ms", "decode_mla_roofline_pct",
               "decode_mla_absorb_ms", "decode_moe_held_ms",
               "moe_zero_pick_pct")


@pytest.fixture(scope="module")
def cell():
    return run.Cell(CELL, rehearsal=False)


# ---- sizes, by hand ---------------------------------------------------------
def test_a_token_holds_eight_padded_latent_rows(cell):
    # 4 layers x 2 sublayers x (512 + 64 values in 5 lane tiles) x bf16
    assert cell.sizes.kv_bytes_per_token(cell.model_cfg()) == (
        4 * 2 * 640 * 2) == 10240


def test_decode_weight_bytes_are_the_issues_arithmetic(cell):
    cfg = cell.model_cfg()
    mla = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576
           + 512 * 64 * 256 + 64 * 128 * 6144)
    assert cell.sizes.mla_params(cfg) == mla == 90_570_752
    dense, expert, router = 3 * 6144 * 12288, 3 * 6144 * 2048, 6144 * 768
    outside = 2 * mla + 2 * dense + router
    assert round(outside / 1e6) == 639  # the issue's "639 M outside"
    assert cell.sizes.router_width(cfg) == 768
    assert cell.sizes.experts_touched(cfg, 1) == pytest.approx(16 * 12 / 768)
    touched = 16 * (1 - (1 - 12 / 768) ** 64)
    assert touched == pytest.approx(10.16, abs=0.01)
    want = 2.0 * (4 * (outside + touched * expert) + 6144 * 16384)
    assert cell.sizes.decode_weight_bytes(cfg, 64) == pytest.approx(want)
    assert want == pytest.approx(8.38e9, rel=0.01)  # 10.2 ms at 819 GB/s
    # everything held, read once: the 10.35 GB less the gathered embedding
    whole = 2.0 * (4 * (outside + 16 * expert) + 6144 * 16384)
    assert whole + 2 * 6144 * 16384 == pytest.approx(10.35e9, rel=0.005)


def test_the_kernel_model_counts_a_row_once_for_all_heads(cell):
    got = kernel_model_mla.mla_attention(
        cell.model_cfg(), kv_tokens=64_000, seqs=64, peak=base.PEAK)
    assert got["bytes"] == 8 * 2 * (64_000 * 576 + 64 * 64 * (576 + 512))
    assert got["ops"] == 8 * 64_000 * 64 * (576 + 512) * 2
    assert got["binds"] == "hbm"  # 139 ops a byte, under the chip's ridge
    assert got["seconds"] == got["bytes"] / base.PEAK["hbm_bytes_per_s"]


# ---- manifest and files -----------------------------------------------------
def test_manifest_entries_are_the_issues(cell):
    m = cell.manifest
    (conf,) = [c for c in m["configs"] if c["name"] == CONFIG]
    assert conf["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size", "max_model_len"]
    assert cell.entry == m["workloads"][-1] and cell.entry["chips"] == 1
    assert cell.mix["arrival"] == {"process": "closed", "clients": 64}
    sweep = cell.mix["warmup"]["sweep"]
    assert sweep["background"] + sweep["seats"] == 64
    new = m["per_layer"][-5:]
    assert tuple(x["name"] for x in new) == NEW_METRICS
    assert all(x["workloads"] == [CELL] for x in new)
    reported = {x["name"] for x in cell.metrics_for("per_layer")}
    assert set(NEW_METRICS) <= reported
    assert not {"decode_experts_ms", "step_experts_ms"} & reported
    mixtral = run.Cell("mixtral8x7b-chat-closed", rehearsal=False)
    assert len(mixtral.metrics_for("per_layer")) == 23
    assert not set(NEW_METRICS) & {
        x["name"] for x in mixtral.metrics_for("per_layer")}


def test_traffic_is_the_mixtral_cells_lengths_at_twice_the_clients(cell):
    old = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "chat-closed-32.json")))
    for key in ("prompt_len", "output_len", "pool_size", "pairing_seed",
                "max_total_tokens", "check", "trace", "cooldown_cap_s"):
        assert cell.mix[key] == old[key], key


def test_every_published_width_stands_and_the_cuts_are_the_four_named(cell):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    (row,) = [r for r in map(json.loads, open(CATALOG))
              if r["name"] == "LongCat-Flash-Chat"]
    assert cell.bench["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cell.hf.get(k) != v}
    assert differs == {"num_layers", "n_routed_experts", "vocab_size"}
    assert set(cell.bench["reduced"]) == differs | {"max_model_len"}
    assert cell.bench["published"] == {
        "num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072,
        "max_model_len": 131072}
    assert (cell.hf["expert_parallel_size"], cell.hf[
        "expert_parallel_rank"]) == (32, 0)
    assert set(cell.bench["not_published"]) == {
        "expert_parallel_size", "expert_parallel_rank"}
    assert "--max-model-len" in cell.bench["server_args"]


# ---- the readers, on a hand-made trace --------------------------------------
SCAN = base.SCAN


def latent_events() -> dict:
    """``hand_made`` with the fused scan of a latent-attention model:
    its kernel, the absorb products around it, the routed layer."""
    events = base.hand_made()
    plane = events["devices"]["/device:TPU:0"]
    plane["ops"] = [
        ["while.1", 1000, 3000, SCAN[:-24] + "while"],
        ["fusion.1", 1000, 400, SCAN + "mla_absorb/thd,hdc->thc/dot_general"],
        ["vdt_mla_attention.2", 1400, 1000,
         SCAN + "attn/vdt_mla_attention/pallas_call"],
        ["fusion.3", 2400, 200, SCAN + "mla_absorb/thc,hcv->thv/dot_general"],
        ["fusion.4", 2600, 300, SCAN + "moe_router/top_k"],
        ["conditional.5", 2900, 500, SCAN + "moe_experts/cond"],
    ] + plane["ops"][4:]
    return events


def latent_ctx(spans=None) -> dict:
    ctx = base.ctx_for(base.hand_made(), spans=spans, batch=8.0)
    ctx["_named_trace"] = _xplane.reduce(latent_events(), scopes=["mla_absorb"])
    return ctx


def test_decode_readers_divide_by_executions_times_k():
    ctx = latent_ctx()
    # one scan of two micro-steps; nanoseconds -> milliseconds a step
    assert decode_mla_ms.read(ctx) == pytest.approx(1000 / 1e6 / 2)
    assert decode_mla_absorb_ms.read(ctx) == pytest.approx(600 / 1e6 / 2)
    assert decode_moe_held_ms.read(ctx) == pytest.approx(800 / 1e6 / 2)


def test_new_readers_find_nothing_in_a_trace_without_their_names():
    ctx = base.ctx_for(base.hand_made())  # the Mixtral-shaped trace
    for reader in (decode_mla_ms, decode_mla_absorb_ms,
                   decode_mla_roofline_pct, moe_zero_pick_pct):
        assert reader.read(ctx) is None
    # ... and the K/V kernel's readers nothing in a latent one
    ctx = latent_ctx()
    assert base.read("decode_attention_ms", ctx) is None
    assert base.read("decode_attention_roofline_pct", ctx) is None
    no_trace = dict(ctx, _named_trace=None, spans=None)
    for name in NEW_METRICS:
        assert base.read(name, no_trace) is None


def _dispatch(kv_tokens: int, seqs: int) -> dict:
    return {"name": "runner.dispatch", "start": 11.0, "duration": 0.001,
            "attributes": {"program": "vdt_decode_k2_s8_p8",
                           "kv_tokens": kv_tokens, "seqs": seqs}}


def test_roofline_share_is_the_models_seconds_over_the_kernels(cell):
    def ctx_with(kernel_s, spans):
        ctx = latent_ctx(spans)
        ctx["config"] = cell.model_cfg()
        ctx["_named_trace"]["programs"]["decode"]["kernels"][
            "vdt_mla_attention"] = kernel_s  # over 2 micro-steps
        return ctx

    ctx = ctx_with(4e-3, [_dispatch(40_000, 8), _dispatch(56_000, 8)])
    model = kernel_model_mla.mla_attention(
        ctx["config"], 48_000, 8, base.PEAK)
    assert decode_mla_roofline_pct.read(ctx) == pytest.approx(
        100 * model["seconds"] / 2e-3)
    # more work than the measured time allows fails the run
    with pytest.raises(ValueError, match="decode_mla_roofline_pct reads"):
        decode_mla_roofline_pct.read(
            ctx_with(1e-5, [_dispatch(48_000, 8)]))


def _fetch(start: float, **picks) -> dict:
    return {"name": "runner.fetch", "start": start, "duration": 0.001,
            "attributes": {"step_id": 1, **{
                f"moe_picks_{k}": v for k, v in picks.items()}}}


def test_zero_pick_share_sums_the_windows_fetch_spans():
    ctx = latent_ctx([
        _fetch(12.0, zero=100, held=6, absent=194),
        _fetch(13.0, zero=156, held=4, absent=140),
        _fetch(500.0, zero=1000, held=0, absent=0),  # after the window
        {"name": "runner.fetch", "start": 14.0, "duration": 0.001,
         "attributes": {"step_id": 2}},  # a step that reported none
    ])
    assert moe_zero_pick_pct.picks(ctx) == {
        "zero": 256, "held": 10, "absent": 334}
    assert moe_zero_pick_pct.read(ctx) == pytest.approx(100 * 256 / 600)


# ---- the reference ----------------------------------------------------------
def test_reference_weights_have_the_share_and_the_control_is_int4(cell):
    import jax

    from benchmark.reference import longcat_flash as ref
    from vllm_distributed_tpu import testing

    cfg = testing.longcat_config()
    w = jax.eval_shape(lambda: ref.make_weights(cfg, 1))
    layer = w["layers"][0]
    assert layer["router"].shape == (64, 4 * 2 + 4)
    assert layer["w1"].shape == (4, 64, 32)  # the held experts alone
    assert layer["router_bias"].dtype == "float32"
    low = ref.make_weights(cfg, 1, bits=4)["layers"][0]
    q, scale = low["ffn"][0]["gate"]
    assert q.dtype == "int8" and int(abs(q).max()) <= 8
    assert not isinstance(low["router"], tuple)  # never quantized
    assert not isinstance(low["attn"][0]["wkv_b"], tuple)
    assert ref.ARCHITECTURE == cell.hf["architectures"][0]


def test_the_int4_control_runs_through_the_references_scan():
    """The held experts are a scan over their stack: stored pairs
    (int4 values, group scales) are its operands too, and the control
    moves the logprobs."""
    import numpy as np

    from benchmark.reference import longcat_flash as ref
    from vllm_distributed_tpu import testing

    cfg = testing.longcat_config()
    toks, pos = list(range(3, 23)), [18, 19]
    sound = ref.logprobs(ref.make_weights(cfg, 1), cfg, toks, pos)
    low = ref.logprobs(ref.make_weights(cfg, 1, bits=4), cfg, toks, pos)
    assert sound.shape == low.shape == (2, cfg["vocab_size"])
    assert np.isfinite(low).all() and np.abs(sound - low).max() > 1e-4

