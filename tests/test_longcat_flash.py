"""LongCat-Flash on the served path (ISSUE 34): the engine against the
plain reference through chunked prefill and the fused decode scan over
the latent cache, the chip's share of the experts adding up, the
router's rules, the models' cache statements, what a latent pool
refuses at load, and no XLA loop in a single step."""

import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as ref_common
from benchmark.reference import longcat_flash as ref
from vllm_distributed_tpu import testing
from vllm_distributed_tpu.config import EngineArgs, ModelConfig
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.models import longcat_flash as lc
from vllm_distributed_tpu.models.registry import get_model_class
from vllm_distributed_tpu.sampling_params import SamplingParams

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 20250929
# float32 on the CPU: the program (absorbed form, paged latent cache,
# chunked) and the reference (materialised, one pass) differ by
# rounding alone (5e-7 measured, on a logprob of order 5); an int8
# weight moves a logprob by 1e-3 and more, a bfloat16 router by 2e-5.
TOL = 5e-6


def _engine(model_dir, **kw):
    args = dict(model=model_dir, skip_tokenizer_init=True, num_kv_pages=64,
                max_model_len=256, max_num_seqs=8, num_decode_steps=4,
                max_num_batched_tokens=32, load_format="dummy", seed=SEED)
    args.update(kw)
    return LLMEngine.from_engine_args(EngineArgs(**args))


def _generate(engine, prompts, logprobs=None, max_tokens=9):
    for i, p in enumerate(prompts):
        engine.add_request(f"r{i}", prompt_token_ids=p,
                           sampling_params=SamplingParams(
                               temperature=0.0, max_tokens=max_tokens,
                               ignore_eos=True, logprobs=logprobs))
    done = {}
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.outputs[0]
    return [done[f"r{i}"] for i in range(len(prompts))]


def _prompts():
    rng = np.random.default_rng(0)
    # 70 tokens: three chunks of the 32-token budget; 5: one beside them
    return [list(map(int, rng.integers(3, 128, n))) for n in (70, 5, 37)]


def _worst(cfg, prompts, outs):
    """(largest |system logprob - reference logprob| over the returned
    tokens, or None without logprobs; largest regret of an emitted
    token by the reference, teacher-forced)."""
    weights = ref.make_weights(cfg, SEED)
    diff, regret = None, 0.0
    for p, o in zip(prompts, outs):
        toks = list(o.token_ids)
        full = p + toks
        lp = ref.logprobs(weights, cfg, full,
                          list(range(len(p) - 1, len(full) - 1)))
        for j, t in enumerate(toks):
            regret = max(regret, float(lp[j].max() - lp[j, t]))
            if o.logprobs:
                for tok, value in o.logprobs[j].items():
                    diff = max(diff or 0.0, abs(value - float(lp[j, tok])))
    return diff, regret


@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_engine_agrees_with_the_reference_through_the_latent_cache(
        backend, tmp_path):
    """Chunked prefill (a 70-token prompt in 32-token steps, mixed with
    the others), then decode: with ``logprobs`` through single steps,
    whose values are compared; without, through the fused scan and its
    staged side buffer, whose tokens the reference must rank first."""
    # Interpreted kernels compile slowly (six step programs, ~15 s
    # each cold at two layers): one layer of them, which is both of a
    # layer's caches; the XLA backend runs two layers.
    sizes = {"layers": 1} if backend == "pallas_interpret" else {}
    d = testing.write_longcat_config(str(tmp_path), **sizes)
    cfg = testing.longcat_config(**sizes)
    prompts = _prompts()
    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": backend}):
        engine = _engine(d)
        try:
            with_lp = _generate(engine, prompts, logprobs=3)
            fused = _generate(engine, prompts)
            picks = dict(engine.executor.worker.runner.telemetry.counters[
                "moe_picks"])
        finally:
            engine.shutdown()
    diff, regret = _worst(cfg, prompts, with_lp)
    assert diff is not None and diff < TOL, diff
    assert regret < TOL
    _, regret = _worst(cfg, prompts, fused)
    assert regret < TOL
    assert [o.token_ids for o in fused] == [o.token_ids for o in with_lp]
    # every live row made top_k picks in each of the layers
    assert set(picks) == set(lc.PICK_KINDS) and min(picks.values()) > 0
    assert sum(picks.values()) % (cfg["moe_topk"] * cfg["num_layers"]) == 0


def test_one_request_goes_materialised_then_absorbed_then_the_scan(tmp_path):
    """A 75-token prompt against a 32-token budget, interpreted
    kernels: its first chunk is fresh (the flat per-head kernel), the
    next two have a cached prefix (the absorbed chunk tiles behind the
    conditional), then the fused scan decodes.  The returned logprobs
    (single steps) and the scan's tokens are the plain reference's, the
    steps' ``runner.dispatch`` spans say which form each chunk took,
    and ``vllm:mla_chunk_tokens_total`` adds up to them."""
    from vllm_distributed_tpu.tracing import get_tracer

    d = testing.write_longcat_config(str(tmp_path), layers=1)
    cfg = testing.longcat_config(layers=1)
    rng = np.random.default_rng(5)
    prompts = [list(map(int, rng.integers(3, 128, 75)))]
    tracer = get_tracer()
    tracer.reset()
    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": "pallas_interpret"}):
        engine = _engine(d, enable_tracing=True)
        try:
            with_lp = _generate(engine, prompts, logprobs=3, max_tokens=5)
            spans = [s["attributes"] for tr in tracer.snapshot()
                     for s in tr["spans"] if s["name"] == "runner.dispatch"]
            fused = _generate(engine, prompts, max_tokens=5)
            engine.refresh_device_telemetry()
            text = engine.metrics.render().decode()
        finally:
            engine.shutdown()
            tracer.configure(False)
            tracer.reset()
    diff, regret = _worst(cfg, prompts, with_lp)
    assert diff is not None and diff < TOL, diff
    assert regret < TOL
    assert _worst(cfg, prompts, fused)[1] < TOL
    assert fused[0].token_ids == with_lp[0].token_ids
    chunks = [(a["chunk_tokens_fresh"], a["chunk_tokens_prefixed"])
              for a in spans if "chunk_tokens_fresh" in a]
    assert chunks == [(32, 0), (0, 32), (0, 11)]
    # decode-only single steps (max_q 1) keep the absorbed form and
    # carry neither attribute
    assert sum("chunk_tokens_fresh" not in a for a in spans) == 4
    counter = {}
    for line in text.splitlines():
        if line.startswith("vllm:mla_chunk_tokens_total{"):
            form = line.split('form="')[1].split('"')[0]
            counter[form] = float(line.rsplit(" ", 1)[1])
    assert counter == {"materialised": 64.0, "absorbed": 86.0}  # two runs


def test_a_scans_span_and_counter_say_what_the_kernel_fetches(tmp_path):
    """Interpreted kernels: each fused scan's ``runner.dispatch`` span
    carries ``kv_rows_fetched`` beside ``kv_tokens`` (whole sub-blocks
    of each sequence's pool-resident rows: the host-side twin of the
    kernel's list), single steps carry neither, and
    ``vllm:mla_kv_rows_total`` adds up to the spans."""
    from vllm_distributed_tpu.ops.pallas import mla_attention as mla
    from vllm_distributed_tpu.tracing import get_tracer

    d = testing.write_longcat_config(str(tmp_path), layers=1)
    cfg = testing.longcat_config(layers=1)
    rng = np.random.default_rng(6)
    prompts = [list(map(int, rng.integers(3, 128, n))) for n in (20, 7)]
    tracer = get_tracer()
    tracer.reset()
    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": "pallas_interpret"}):
        engine = _engine(d, enable_tracing=True)
        try:
            _generate(engine, prompts, max_tokens=9)
            spans = [s["attributes"] for tr in tracer.snapshot()
                     for s in tr["spans"] if s["name"] == "runner.dispatch"]
            engine.refresh_device_telemetry()
            text = engine.metrics.render().decode()
        finally:
            engine.shutdown()
            tracer.configure(False)
            tracer.reset()
    scans = [a for a in spans if "kv_rows_fetched" in a]
    assert scans and all("_decode_" in a["program"] for a in scans)
    assert all("kv_rows_fetched" in a for a in spans
               if "_decode_" in a["program"])
    for a in scans:
        pages = int(a["program"].rsplit("_p", 1)[1])
        # the two sequences decode side by side: the scan's kv_tokens
        # split as their prompts differ, 13 tokens
        lens = [(a["kv_tokens"] + 13) // 2, (a["kv_tokens"] - 13) // 2]
        assert a["kv_rows_fetched"] == mla.mla_kv_rows_fetched(
            lens, num_heads=cfg["num_attention_heads"],
            value_width=cfg["kv_lora_rank"], lanes=128, itemsize=4,
            page_size=16, max_pages=pages)
        assert a["kv_rows_fetched"] >= a["kv_tokens"] > 0
    counter = {}
    for line in text.splitlines():
        if line.startswith("vllm:mla_kv_rows_total{"):
            kind = line.split('kind="')[1].split('"')[0]
            counter[kind] = float(line.rsplit(" ", 1)[1])
    assert counter == {
        "live": float(sum(a["kv_tokens"] for a in scans)),
        "fetched": float(sum(a["kv_rows_fetched"] for a in scans))}


def test_picks_reach_the_metrics_and_the_fetch_spans(tmp_path):
    """``vllm:moe_picks_total`` by kind on ``/metrics``' text and the
    same counts as attributes of the steps' ``runner.fetch`` spans: the
    two add up to each other, and to top_k x layers x live rows."""
    from vllm_distributed_tpu.tracing import get_tracer

    d = testing.write_longcat_config(str(tmp_path))
    cfg = testing.longcat_config()
    tracer = get_tracer()
    tracer.reset()
    engine = _engine(d, enable_tracing=True)
    try:
        outs = _generate(engine, _prompts()[:2], max_tokens=6)
        engine.refresh_device_telemetry()
        text = engine.metrics.render().decode()
        spans = [s["attributes"] for tr in tracer.snapshot()
                 for s in tr["spans"] if s["name"] == "runner.fetch"]
    finally:
        engine.shutdown()
        tracer.configure(False)
        tracer.reset()
    counter = {}
    for line in text.splitlines():
        if line.startswith("vllm:moe_picks_total{"):
            kind = line.split('kind="')[1].split('"')[0]
            counter[kind] = float(line.rsplit(" ", 1)[1])
    assert set(counter) == set(lc.PICK_KINDS)
    by_span = {k: sum(a.get(f"moe_picks_{k}", 0) for a in spans)
               for k in lc.PICK_KINDS}
    assert by_span == {k: int(v) for k, v in counter.items()}
    assert all("moe_picks_zero" in a for a in spans)
    # rows: every prompt token once, and every emitted token but the last
    rows = sum(len(p) for p in _prompts()[:2]) + sum(
        len(o.token_ids) - 1 for o in outs)
    assert sum(counter.values()) == rows * cfg["moe_topk"] * cfg["num_layers"]


def test_an_int8_weight_fails_the_tolerance(tmp_path):
    d = testing.write_longcat_config(str(tmp_path))
    prompts = _prompts()
    engine = _engine(d, quantization="int8")
    try:
        outs = _generate(engine, prompts, logprobs=3)
    finally:
        engine.shutdown()
    diff, _ = _worst(testing.longcat_config(), prompts, outs)
    assert diff > 10 * TOL, diff


def test_a_bfloat16_router_fails_the_tolerance(tmp_path):
    d = testing.write_longcat_config(str(tmp_path))
    prompts = _prompts()

    def bf16_route(self, u, layer):
        logits = (u.astype(jnp.bfloat16)
                  @ layer["router"].astype(jnp.bfloat16))
        scores = jax.nn.softmax(logits, axis=-1).astype(jnp.float32)
        _, idx = jax.lax.top_k(scores + layer["router_bias"], self.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1) * self.routed_scale
        return idx.astype(jnp.int32), w

    with mock.patch.object(lc.LongcatFlashForCausalLM, "_route", bf16_route):
        engine = _engine(d)
        try:
            outs = _generate(engine, prompts, logprobs=3)
        finally:
            engine.shutdown()
    diff, _ = _worst(testing.longcat_config(), prompts, outs)
    assert diff > 3 * TOL, diff


# ---- the share of the experts ---------------------------------------------
def _model(tmp_path, **sizes):
    d = testing.write_longcat_config(str(tmp_path), **sizes)
    mc = EngineArgs(model=d, skip_tokenizer_init=True, load_format="dummy",
                    num_kv_pages=16).create_engine_config().model_config
    return get_model_class(mc.architecture)(mc)


def test_the_shares_add_up_to_the_uncut_layer(tmp_path):
    """8 routed + 4 identity experts over 4 chips of 2: the shares'
    routed parts, the identity part counted once, equal the uncut
    reference's routed layer — by the program's ``_moe`` and by the
    reference's share alike."""
    whole = testing.longcat_config(held=8, ep_size=1, zero=4, top_k=5)
    w = ref.make_weights(whole, SEED)["layers"][0]
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.standard_normal((40, whole["hidden_size"])),
                    jnp.float32)
    live = jnp.ones(40, bool)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.moe(u, w, whole))
        idx, weights = ref.route(u, w, whole)
        z = np.asarray(jnp.where(idx >= 8, weights, 0.0).sum(
            -1, keepdims=True) * u)
        total_prog, total_ref = z.copy(), z.copy()
        kinds = np.zeros(3, np.int64)
        for rank in range(4):
            sizes = dict(held=2, ep_size=4, ep_rank=rank, zero=4, top_k=5)
            share = testing.longcat_config(**sizes)
            model = _model(tmp_path / str(rank), **sizes)
            ws = dict(w, **{n: w[n][2 * rank: 2 * rank + 2]
                            for n in ("w1", "w2", "w3")})
            m, picks = jax.jit(lambda x: model._moe(x, ws, live))(u)
            total_prog += np.asarray(m) - z
            total_ref += np.asarray(ref.moe(u, ws, share)) - z
            kinds += np.asarray(picks)
    assert np.abs(total_prog - want).max() < 1e-5
    assert np.abs(total_ref - want).max() < 1e-5
    # zero picks are every chip's alike; each routed pick is held once
    assert kinds[0] == 4 * int((np.asarray(idx) >= 8).sum())
    assert kinds[1] == int((np.asarray(idx) < 8).sum())
    assert kinds.sum() == 4 * 40 * 5


@pytest.mark.parametrize("tokens,over", [(8, False), (64, True)])
def test_an_expert_over_its_buffer_still_counts_every_pick(
        tokens, over, tmp_path):
    """A router that sends every row to expert 0: at 64 rows that is
    four times its buffer (16 rows: 4 x the even mean), and the third
    branch computes every row, exactly; at 8 rows the buffer is the
    whole step."""
    sizes = dict(held=4, ep_size=1, zero=12, top_k=1)
    cfg = testing.longcat_config(**sizes)
    model = _model(tmp_path, **sizes)
    w = dict(ref.make_weights(cfg, SEED)["layers"][0])
    w["router_bias"] = jnp.zeros(16, jnp.float32).at[0].set(9.0)
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.standard_normal((tokens, cfg["hidden_size"])),
                    jnp.float32)
    assert (lc._capacity(tokens, 1, 16) < tokens) == over
    with jax.default_matmul_precision("highest"):
        got, picks = jax.jit(
            lambda x: model._moe(x, w, jnp.ones(tokens, bool)))(u)
        want = ref.moe(u, w, cfg)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert np.asarray(picks).tolist() == [0, tokens, 0]


def test_padding_rows_are_not_counted_and_add_nothing(tmp_path):
    model = _model(tmp_path)
    cfg = testing.longcat_config()
    w = ref.make_weights(cfg, SEED)["layers"][0]
    u = jnp.asarray(np.random.default_rng(3).standard_normal(
        (16, cfg["hidden_size"])), jnp.float32)
    live = jnp.arange(16) < 10
    m, picks = jax.jit(lambda x: model._moe(x, w, live))(u)
    assert int(np.asarray(picks).sum()) == 10 * cfg["moe_topk"]
    assert not np.asarray(m)[10:].any()


# ---- the router ------------------------------------------------------------
def test_the_bias_moves_the_choice_and_never_a_weight(tmp_path):
    cfg = testing.longcat_config()
    model = _model(tmp_path)
    w = dict(ref.make_weights(cfg, SEED)["layers"][0])
    u = jnp.asarray(np.random.default_rng(4).standard_normal(
        (32, cfg["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jax.nn.softmax(
            u @ ref_common.deq(w["router"]), axis=-1))
        for route in (lambda: model._route(u, w),
                      lambda: ref.route(u, w, cfg)):
            idx, weights = map(np.asarray, route())
            no_bias = dict(w, router_bias=jnp.zeros_like(w["router_bias"]))
            w, w_was = no_bias, w
            idx0, weights0 = map(np.asarray, route())
            w = w_was
            assert weights.dtype == np.float32
            # the weight is the score at the index, times the factor:
            # no bias in it, not renormalised
            want = np.take_along_axis(scores, idx, -1) * 6.0
            assert np.abs(weights - want).max() < 1e-6
            assert np.abs(weights.sum(-1) - 6.0).min() > 0.1
            # the bias alone moved the choice (at this toy width the
            # seeded bias, std 1 / width, is larger than the scores'
            # spread; a tenth of it moves some rows' picks, not all)
            assert any(set(a) != set(b) for a, b in zip(idx, idx0))
            w = dict(w_was, router_bias=w_was["router_bias"] * 0.1)
            idx1, _ = map(np.asarray, route())
            w = w_was
            moved = [set(a) != set(b) for a, b in zip(idx1, idx0)]
            assert 0 < sum(moved) < len(moved)


def test_seeded_weights_are_the_references(tmp_path):
    """The program's init_params and the reference's make_weights draw
    the same values (w_uk / w_uv are W_kvb's per-head halves)."""
    cfg = testing.longcat_config()
    model = _model(tmp_path)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(SEED))  # the loader jits
    w = ref.make_weights(cfg, SEED)
    nh, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    for name in ("embed", "lm_head"):
        assert np.array_equal(np.asarray(params[name]), np.asarray(w[name]))
    for got, want in zip(params["layers"], w["layers"]):
        for name in ("router", "router_bias", "w1", "w2", "w3"):
            assert np.array_equal(np.asarray(got[name]), np.asarray(want[name]))
        for j in range(2):
            for name in ("wq_a", "wq_b", "wkv_a", "wo"):
                assert np.array_equal(np.asarray(got["attn"][j][name]),
                                      np.asarray(want["attn"][j][name]))
            kvb = np.asarray(want["attn"][j]["wkv_b"]).reshape(
                cfg["kv_lora_rank"], nh, -1)
            assert np.array_equal(np.asarray(got["attn"][j]["w_uk"]),
                                  kvb[:, :, :nope].transpose(1, 2, 0))
            assert np.array_equal(np.asarray(got["attn"][j]["w_uv"]),
                                  kvb[:, :, nope:].transpose(1, 0, 2))
            for name in ("gate", "up", "down"):
                assert np.array_equal(np.asarray(got["ffn"][j][name]),
                                      np.asarray(want["ffn"][j][name]))


# ---- the cache statement ---------------------------------------------------
def _runner_for(model_dir, **kw):
    from vllm_distributed_tpu.worker.model_runner import ModelRunner

    cfg = EngineArgs(model=model_dir, skip_tokenizer_init=True,
                     load_format="dummy", num_kv_pages=32,
                     **kw).create_engine_config()
    runner = ModelRunner(cfg, attn_backend="reference")
    runner.model = get_model_class(cfg.model_config.architecture)(
        cfg.model_config)
    return runner


@pytest.mark.parametrize("arch", ["llama", "mixtral"])
@pytest.mark.parametrize("cache_dtype", ["auto", "int8"])
def test_kv_heads_models_state_the_pool_they_always_had(
        arch, cache_dtype, tmp_path):
    write = (testing.write_llama_config if arch == "llama"
             else testing.write_mixtral_config)
    d = write(str(tmp_path), layers=3, heads=8, kv_heads=4, hidden=64,
              dtype="bfloat16")
    runner = _runner_for(d, kv_cache_dtype=cache_dtype)
    per_token = 4 * 8 * (1 if cache_dtype == "int8" else 2)
    if cache_dtype == "int8":
        per_token += 4 * 4  # a float32 scale a KV head
    assert runner.kv_cache_bytes_per_page() == 3 * 2 * 16 * per_token
    pool = jax.eval_shape(lambda: runner.alloc_kv_pool(32))
    assert len(pool) == 3
    data = pool[0][0] if cache_dtype == "int8" else pool[0]
    assert data.shape == (2, 32, 16, 32)
    if cache_dtype == "int8":
        assert pool[0][1].shape == (2, 32, 16, 4)


def test_the_latent_pool_is_one_plane_of_10240_bytes_a_token_at_the_cut(
        tmp_path):
    conf = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "longcat-flash-chat-ep32-bf16.json")))
    with open(tmp_path / "config.json", "w") as f:
        json.dump({k: v for k, v in conf.items() if k != "benchmark"}, f)
    runner = _runner_for(str(tmp_path))
    # 8 sublayers x 640 lanes (576 values in whole lane tiles) x 2 B
    assert runner.kv_cache_bytes_per_page() == 16 * 10240
    pool = jax.eval_shape(lambda: runner.alloc_kv_pool(32))
    assert len(pool) == 8 and pool[0].shape == (1, 32, 16, 640)
    assert pool[0].dtype == jnp.bfloat16
    m = runner.model
    assert (m.num_layers, m.num_held, m.router_width) == (4, 16, 768)
    # side buffers of 64 sequences, 8 micro-steps, two dispatches in flight
    sc = runner.config.scheduler_config
    runner._staged_decode = True
    assert runner._pipeline_reserve_bytes() == (
        sc.max_num_seqs * sc.num_decode_steps * 10240
        * max(sc.max_concurrent_dispatches, 1))


@pytest.mark.parametrize("kw,names", [
    (dict(kv_cache_dtype="int8"), "--kv-cache-dtype int8"),
    (dict(kv_spill_host_pages=8, enable_prefix_caching=True),
     "--kv-spill-host-pages"),
    (dict(kv_transfer_config={"kv_connector": "x"}), "--kv-transfer-config"),
    (dict(tensor_parallel_size=2), "tp=2"),
])
def test_what_a_latent_pool_cannot_do_fails_at_load_and_says_so(
        kw, names, tmp_path):
    d = testing.write_longcat_config(str(tmp_path))
    with pytest.raises(ValueError, match="latent|longcat_flash") as e:
        _engine(d, **kw)
    assert names in str(e.value)


def test_getters_name_the_keys_a_config_lacks():
    class Bare:
        model_type = "mystery"

    mc = ModelConfig(model="m", hf_config=Bare(), dtype="float32",
                     skip_tokenizer_init=True)
    for getter in (mc.get_num_layers, mc.get_hidden_size,
                   mc.get_num_attention_heads, mc.get_num_kv_heads):
        with pytest.raises(ValueError, match="none of"):
            getter()

    class Latent(Bare):
        kv_lora_rank, num_layers, num_hidden_layers = 16, 3, 6
        hidden_size, num_attention_heads = 64, 4

    mc = ModelConfig(model="m", hf_config=Latent(), dtype="float32",
                     skip_tokenizer_init=True)
    assert mc.get_num_layers() == 3  # not the count of sublayers
    for getter in (mc.get_num_kv_heads, mc.get_head_dim):
        with pytest.raises(ValueError, match="kv_cache_layout"):
            getter()


# ---- no XLA loop in a single step -----------------------------------------
@pytest.mark.parametrize("zero,conds", [(60, 2), (124, 2 * (1 + 4))])
def test_a_single_step_holds_no_xla_while(zero, conds, tmp_path):
    """The benchmark tells a fused scan from a single step by the
    ``while`` of the scan: the forward pass of a step (its routed layer
    in both forms — one batched product at buffers of 16 rows, a branch
    an expert at buffers of 8 — behind the branch for an expert over its
    buffer; the tile lists of the latent kernel) lowers to none.
    Branches are HLO conditionals, which are allowed."""
    from vllm_distributed_tpu.ops.attention import AttentionMetadata
    from vllm_distributed_tpu.ops.pallas.mla_attention import (
        mla_attention_cpu,
    )

    model = _model(tmp_path, zero=zero)
    assert lc._capacity(64, 3, 8 + zero) == (16 if zero == 60 else 8)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    t, s = 64, 8
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    meta = AttentionMetadata(
        q_seq_ids=i32(t), q_positions=i32(t), slot_mapping=i32(t),
        block_tables=i32(s, 16), seq_lens=i32(s), logits_indices=i32(s),
        chunk_starts=i32(s))
    kv = [jax.ShapeDtypeStruct((1, 32, 16, 128), jnp.float32)] * 4
    from functools import partial

    jaxpr = jax.make_jaxpr(lambda p, tok, kv, meta: model.forward(
        p, tok, kv, meta, attn_fn=partial(mla_attention_cpu, max_q=32)))(
            params, i32(t), kv, meta)

    def primitives(jxp):
        for eqn in jxp.eqns:
            yield eqn.primitive.name
            if eqn.primitive.name == "pallas_call":
                continue  # the kernel's own loop never reaches XLA
            for v in eqn.params.values():
                for inner in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        yield from primitives(inner)

    names = list(primitives(jaxpr.jaxpr))
    assert "pallas_call" in names
    assert names.count("cond") == conds  # two layers
    assert not set(names) & {"while", "scan"}, set(names)


def test_a_single_step_as_the_runner_binds_it_holds_no_xla_while(tmp_path):
    """With the head-space entry bound (``bind_max_q``: fresh chunks in
    per-head form) a step has one more conditional and two more kernel
    calls a sublayer than the absorbed form alone, and still no loop."""
    from functools import partial

    from vllm_distributed_tpu.ops.attention import (
        AttentionMetadata,
        bind_max_q,
    )
    from vllm_distributed_tpu.ops.pallas.mla_attention import (
        mla_attention_cpu,
    )

    model = _model(tmp_path, zero=60)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    t, s = 64, 8
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    meta = AttentionMetadata(
        q_seq_ids=i32(t), q_positions=i32(t), slot_mapping=i32(t),
        block_tables=i32(s, 16), seq_lens=i32(s), logits_indices=i32(s),
        chunk_starts=i32(s))
    kv = [jax.ShapeDtypeStruct((1, 32, 16, 128), jnp.float32)] * 4

    def primitives(jxp):
        for eqn in jxp.eqns:
            yield eqn.primitive.name
            if eqn.primitive.name == "pallas_call":
                continue
            for v in eqn.params.values():
                for inner in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        yield from primitives(inner)

    def names(attn_fn):
        return list(primitives(jax.make_jaxpr(
            lambda p, tok, kv, meta: model.forward(
                p, tok, kv, meta, attn_fn=attn_fn))(
                    params, i32(t), kv, meta).jaxpr))

    absorbed = names(partial(mla_attention_cpu, max_q=32))
    bound = names(bind_max_q(mla_attention_cpu, 32))
    assert bound.count("cond") == absorbed.count("cond") + 4  # 2 layers
    # absorbed: chunk tiles + one-token rows; bound: the flat kernel,
    # one-token rows, and the chunk tiles inside the conditional
    assert absorbed.count("pallas_call") == 2 * 4
    assert bound.count("pallas_call") == 3 * 4
    assert not set(bound) & {"while", "scan"}, set(bound)
