"""DeepSeek-V3-architecture models on the served path (ISSUE 43): the
engine against the plain reference through chunked prefill, the latent
cache and the fused decode scan at a preset whose values are wider
than its keys, the group-limited router against a hand-written loop,
YaRN at the published settings against hand numbers, the chip's share
of the experts adding up, preemption, a prefix-cache hit, what is
refused at load, and no XLA loop in a single step.  ISSUE 45: the
``branch`` form (an expert behind a conditional) against the same
reference, which steps of the cell's shape take it, and the held
experts it skipped as the fourth pick kind."""

import contextlib
import math
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as ref_common
from benchmark.reference import deepseek_v3 as ref
from vllm_distributed_tpu import testing
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.models import common
from vllm_distributed_tpu.models import deepseek_v3 as ds
from vllm_distributed_tpu.models.registry import get_model_class
from vllm_distributed_tpu.sampling_params import SamplingParams

SEED = 20251001
# float32 on the CPU: the program (absorbed form, paged latent cache,
# chunked) and the reference (materialised, one pass) differ by
# rounding alone (5e-7 measured, on a logprob of order 5); a bfloat16
# router moves a logprob by 1e-4 and more, an int8 weight by 1e-3.
TOL = 5e-6
# GigaChat3.1-702B-A36B's published rope_scaling and rotary sizes
PUBLISHED_YARN = {
    "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
    "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
    "rope_type": "yarn",
}


def _engine(model_dir, **kw):
    args = dict(model=model_dir, skip_tokenizer_init=True, num_kv_pages=64,
                max_model_len=256, max_num_seqs=8, num_decode_steps=4,
                max_num_batched_tokens=32, load_format="dummy", seed=SEED)
    args.update(kw)
    return LLMEngine.from_engine_args(EngineArgs(**args))


def _generate(engine, prompts, logprobs=None, max_tokens=9, prefix="r"):
    for i, p in enumerate(prompts):
        engine.add_request(f"{prefix}{i}", prompt_token_ids=p,
                           sampling_params=SamplingParams(
                               temperature=0.0, max_tokens=max_tokens,
                               ignore_eos=True, logprobs=logprobs))
    done = {}
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.outputs[0]
    return [done[f"{prefix}{i}"] for i in range(len(prompts))]


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


def test_the_preset_keeps_what_the_architecture_is_about():
    cfg = testing.deepseek_v3_config()
    assert cfg["v_head_dim"] != cfg["qk_nope_head_dim"]
    assert cfg["rope_scaling"]["factor"] > 1
    assert 2 <= cfg["topk_group"] < cfg["n_group"] and cfg["n_group"] >= 4
    assert 1 <= cfg["first_k_dense_replace"] < cfg["num_hidden_layers"]
    assert cfg["vocab_size"] % 128
    assert cfg["n_shared_experts"] == 1 and cfg["expert_parallel_size"] > 1


@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_engine_agrees_with_the_reference_through_the_latent_cache(
        backend, tmp_path):
    """Chunked prefill (a 70-token prompt in 32-token steps, mixed with
    the others), then decode: with ``logprobs`` through single steps,
    whose values are compared; without, through the fused scan and its
    staged side buffer, whose tokens the reference must rank first."""
    d = testing.write_deepseek_v3_config(str(tmp_path))
    cfg = testing.deepseek_v3_config()
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
    # every live row made top_k picks in each routed layer: held and
    # absent add up to them, the busiest expert's are some of the held;
    # ``skipped`` is no pick (experts whose conditional did not run)
    assert set(picks) == set(ds.PICK_KINDS)
    assert min(picks[k] for k in ("held", "absent", "busiest")) > 0
    assert picks["skipped"] >= 0
    routed = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert (picks["held"] + picks["absent"]) % (
        cfg["num_experts_per_tok"] * routed) == 0
    assert picks["busiest"] <= picks["held"]


def test_the_many_row_form_serves_the_same_logits(tmp_path):
    """``VDT_MOE_IMPL=ragged`` under the interpreted kernels: every
    step's routed layers go through the sorted dispatch and
    ``vdt_grouped_matmul``, picks of absent experts and all."""
    d = testing.write_deepseek_v3_config(str(tmp_path), layers=2)
    cfg = testing.deepseek_v3_config(layers=2)
    prompts = _prompts()[:2]
    env = {"VDT_USE_PALLAS": "pallas_interpret", "VDT_MOE_IMPL": "ragged"}
    with mock.patch.dict(os.environ, env):
        engine = _engine(d)
        try:
            model = engine.executor.worker.runner.model
            layer = engine.executor.worker.runner.params["layers"][1]
            assert model.moe_impl(layer, 32) == "grouped"
            outs = _generate(engine, prompts, logprobs=3, max_tokens=4)
        finally:
            engine.shutdown()
    diff, regret = _worst(cfg, prompts, outs)
    assert diff < TOL and regret < TOL, (diff, regret)


def test_the_branch_form_serves_the_same_logits(tmp_path):
    """``VDT_MOE_IMPL=branch``: every step's routed layers run an
    expert behind a conditional, in single steps (whose logprobs are
    compared) and in the fused scan (whose tokens the reference must
    rank first), at the tolerance the dense form is held to."""
    d = testing.write_deepseek_v3_config(str(tmp_path))
    cfg = testing.deepseek_v3_config()
    prompts = _prompts()
    with mock.patch.dict(os.environ, {"VDT_MOE_IMPL": "branch"}):
        engine = _engine(d)
        try:
            runner = engine.executor.worker.runner
            layer = runner.params["layers"][runner.model.first_routed_layer]
            assert runner.model.moe_impl(layer, 32) == "branch"
            with_lp = _generate(engine, prompts, logprobs=3)
            fused = _generate(engine, prompts)
            picks = dict(runner.telemetry.counters["moe_picks"])
        finally:
            engine.shutdown()
    diff, regret = _worst(cfg, prompts, with_lp)
    assert diff is not None and diff < TOL, diff
    assert regret < TOL
    _, regret = _worst(cfg, prompts, fused)
    assert regret < TOL
    assert [o.token_ids for o in fused] == [o.token_ids for o in with_lp]
    # 8 held experts a layer, two routed layers: fewer skipped than
    # there are, and some (a decode step of three rows makes 9 picks
    # of 16 experts, half of them held here)
    assert 0 < picks["skipped"] < picks["held"] + picks["absent"]


def test_picks_reach_the_metrics_and_the_fetch_spans(tmp_path):
    from vllm_distributed_tpu.tracing import get_tracer

    # 8 held of 32 routed: the scan's 8 rows are expected to leave
    # 8 x (29/32)^8 = 3.6 of them unreached, so it takes the branch
    # form and the fourth kind counts
    d = testing.write_deepseek_v3_config(str(tmp_path), ep_size=4)
    tracer = get_tracer()
    tracer.reset()
    engine = _engine(d, enable_tracing=True)
    try:
        _generate(engine, _prompts()[:2], max_tokens=6)
        engine.refresh_device_telemetry()
        text = engine.metrics.render().decode()
        picks = dict(engine.executor.worker.runner.telemetry.counters[
            "moe_picks"])
        fetches = [s["attributes"] for tr in tracer.snapshot()
                   for s in tr["spans"] if s["name"] == "runner.fetch"]
    finally:
        engine.shutdown()
        tracer.reset()
    for kind in ds.PICK_KINDS:
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("vllm:moe_picks_total")
                    and f'kind="{kind}"' in ln)
        assert float(line.rsplit(" ", 1)[1]) == picks[kind]
        assert sum(a.get(f"moe_picks_{kind}", 0) for a in fetches) == (
            picks[kind])
    assert not any("moe_picks_zero" in a for a in fetches)
    assert picks["skipped"] > 0


@pytest.mark.parametrize("what,least", [("router", 3), ("weights", 10)])
def test_a_lower_precision_fails_the_tolerance(what, least, tmp_path):
    """The controls: the router in bfloat16 (matmul and sigmoid; groups,
    picks and weights from those scores) and int8 weights both move the
    returned logprobs past the tolerance the sound program keeps."""
    d = testing.write_deepseek_v3_config(str(tmp_path))
    prompts = _prompts()
    def bf16_scores(self, u, layer):
        logits = (u.astype(jnp.bfloat16)
                  @ layer["router"].astype(jnp.bfloat16))
        return jax.nn.sigmoid(logits).astype(jnp.float32)

    patch = (
        mock.patch.object(ds.DeepseekV3ForCausalLM, "_scores", bf16_scores)
        if what == "router" else contextlib.nullcontext())
    kw = {"quantization": "int8"} if what == "weights" else {}
    with patch:
        engine = _engine(d, **kw)
        try:
            outs = _generate(engine, prompts, logprobs=3)
        finally:
            engine.shutdown()
    diff, _ = _worst(testing.deepseek_v3_config(), prompts, outs)
    assert diff > least * TOL, diff


# ---- preemption and the prefix cache ---------------------------------------
def test_preemption_and_resume_give_the_same_logits(tmp_path):
    """A pool too small for three sequences at once: some are preempted
    and recomputed; the logprobs are the roomy run's."""
    d = testing.write_deepseek_v3_config(str(tmp_path))
    prompts = _prompts()
    roomy = _engine(d)
    try:
        want = _generate(roomy, prompts, logprobs=2, max_tokens=12)
    finally:
        roomy.shutdown()
    tight = _engine(d, num_kv_pages=9)
    try:
        got = _generate(tight, prompts, logprobs=2, max_tokens=12)
        preempted = tight.scheduler.num_preemptions
    finally:
        tight.shutdown()
    assert preempted > 0
    for w, g in zip(want, got):
        assert list(w.token_ids) == list(g.token_ids)
        for a, b in zip(w.logprobs, g.logprobs):
            for tok, value in a.items():
                assert abs(value - b[tok]) < TOL


def test_a_prefix_cache_hit_gives_the_same_logits(tmp_path):
    """The second request shares 64 tokens (four pages) with the first:
    its chunk starts behind a cached prefix (the absorbed form over the
    cached rows) and its logprobs are the plain reference's."""
    d = testing.write_deepseek_v3_config(str(tmp_path))
    cfg = testing.deepseek_v3_config()
    rng = np.random.default_rng(7)
    shared = list(map(int, rng.integers(3, 128, 64)))
    prompts = [shared + list(map(int, rng.integers(3, 128, n)))
               for n in (9, 13)]
    engine = _engine(d, enable_prefix_caching=True)
    try:
        first = _generate(engine, prompts[:1], logprobs=3, prefix="a")
        second = _generate(engine, prompts[1:], logprobs=3, prefix="b")
        hit_tokens = engine.scheduler.prefix_cache_hits
    finally:
        engine.shutdown()
    assert hit_tokens >= 64, hit_tokens
    diff, regret = _worst(cfg, prompts, first + second)
    assert diff < TOL and regret < TOL, (diff, regret)


# ---- the share of the experts ---------------------------------------------
def _model(tmp_path, **sizes):
    d = testing.write_deepseek_v3_config(str(tmp_path), **sizes)
    mc = EngineArgs(model=d, skip_tokenizer_init=True, load_format="dummy",
                    num_kv_pages=16).create_engine_config().model_config
    return get_model_class(mc.architecture)(mc)


def test_the_shares_add_up_to_the_uncut_layer(tmp_path):
    """16 routed experts in 4 groups over 4 chips of 4: the routed
    parts of ranks 0-3 plus the shared expert once equal the uncut
    reference's ``F(u)``, by the program's ``_moe`` and by the
    reference's share alike; every pick is held by exactly one rank."""
    whole = testing.deepseek_v3_config(held=16, ep_size=1)
    w = ref.make_weights(whole, SEED)["layers"][1]
    flat = ref.flat(whole)
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.standard_normal((40, whole["hidden_size"])),
                    jnp.float32)
    live = jnp.ones(40, bool)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.feed_forward(u, w, flat))
        shared = np.asarray(ref.ffn(u, w["shared"]))
        total_prog, total_ref = shared.copy(), shared.copy()
        kinds = np.zeros(len(ds.PICK_KINDS), np.int64)
        for rank in range(4):
            sizes = dict(held=4, ep_size=4, ep_rank=rank)
            share = ref.flat(testing.deepseek_v3_config(**sizes))
            model = _model(tmp_path / str(rank), **sizes)
            ws = dict(w, **{n: w[n][4 * rank: 4 * rank + 4]
                            for n in ("w1", "w2", "w3")})
            m, picks = jax.jit(lambda x: model._moe(x, ws, live))(u)
            total_prog += np.asarray(m)
            total_ref += np.asarray(ref.moe(u, ws, share))
            kinds += np.asarray(picks)
    assert np.abs(total_prog - want).max() < 1e-5
    assert np.abs(total_ref - want).max() < 1e-5
    assert kinds[0] == 40 * whole["num_experts_per_tok"]
    assert kinds[1] == 3 * kinds[0]


def test_padding_rows_are_not_counted_and_add_nothing(tmp_path):
    model = _model(tmp_path)
    cfg = testing.deepseek_v3_config()
    w = ref.make_weights(cfg, SEED)["layers"][1]
    u = jnp.asarray(np.random.default_rng(3).standard_normal(
        (16, cfg["hidden_size"])), jnp.float32)
    live = jnp.arange(16) < 10
    for impl in ("dense", "ragged", "branch"):
        with mock.patch.dict(os.environ, {"VDT_MOE_IMPL": impl}):
            m, picks = jax.jit(lambda x: model._moe(x, w, live))(u)
        held, absent, busiest, skipped = map(int, np.asarray(picks))
        assert held + absent == 10 * cfg["num_experts_per_tok"]
        assert 0 < busiest <= held
        if impl != "dense":
            assert not np.asarray(m)[10:].any()
        if impl != "branch":
            assert skipped == 0


def test_skipped_counts_the_held_experts_no_live_row_picked(tmp_path):
    """The fourth kind against the router's own picks: under ``branch``
    the held experts (8 of 32 routed) that no live row's picks name,
    counted by hand; the padding rows' picks reach nothing; and the
    count reaches ``stats["moe_picks"]`` summed over the routed
    layers."""
    from vllm_distributed_tpu.ops.attention import AttentionMetadata

    sizes = dict(ep_size=4, ep_rank=1)
    model = _model(tmp_path, **sizes)
    cfg = testing.deepseek_v3_config(**sizes)
    held = cfg["n_routed_experts"]
    w = ref.make_weights(cfg, SEED)["layers"][1]
    u = jnp.asarray(np.random.default_rng(5).standard_normal(
        (8, cfg["hidden_size"])), jnp.float32)
    live = jnp.arange(8) < 6
    idx = np.asarray(model._route(u, w)[0])[:6] - held  # rank 1's ids
    by_hand = held - len({int(e) for e in idx.ravel() if 0 <= e < held})
    assert 0 < by_hand < held
    with mock.patch.dict(os.environ, {"VDT_MOE_IMPL": "branch"}):
        m, picks = jax.jit(lambda x: model._moe(x, w, live))(u)
    with mock.patch.dict(os.environ, {"VDT_MOE_IMPL": "dense"}):
        want, _ = jax.jit(lambda x: model._moe(x, w, live))(u)
    assert int(picks[ds.PICK_KINDS.index("skipped")]) == by_hand
    assert np.abs(np.asarray(m)[:6] - np.asarray(want)[:6]).max() < 1e-5
    # through forward: both routed layers' counts in stats["moe_picks"]
    params = model.init_params(jax.random.PRNGKey(0))
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    meta = AttentionMetadata(
        q_seq_ids=jnp.arange(8, dtype=jnp.int32), q_positions=i32(8),
        slot_mapping=jnp.arange(8, dtype=jnp.int32),
        block_tables=jnp.arange(8, dtype=jnp.int32)[:, None],
        seq_lens=jnp.ones(8, jnp.int32),
        logits_indices=jnp.arange(8, dtype=jnp.int32), chunk_starts=i32(8))
    kv = [jnp.zeros((1, 8, 16, 128), jnp.float32)
          for _ in range(model.num_layers)]
    tokens = jnp.arange(3, 11, dtype=jnp.int32)
    got = {}
    for impl in ("branch", "dense"):
        stats = {}
        with mock.patch.dict(os.environ, {"VDT_MOE_IMPL": impl}):
            model.forward(params, tokens, kv, meta, stats=stats)
        got[impl] = dict(zip(ds.PICK_KINDS, map(int, stats["moe_picks"])))
    routed = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert 0 < got["branch"]["skipped"] < routed * held
    assert got["dense"]["skipped"] == 0
    assert {k: v for k, v in got["branch"].items() if k != "skipped"} == {
        k: v for k, v in got["dense"].items() if k != "skipped"}


def test_the_cells_steps_branch_at_64_rows_and_no_other(tmp_path):
    """The GigaChat cell's ratios at toy widths (16 held of a 256-wide
    router in 8 groups, 8 picks a row): the scan's 64 rows and the
    ``t64`` single step take ``branch`` (2.1 experts expected
    unreached), 128 to 512 tokens the dense einsum as before (0.27 at
    128), 1,024 and up the grouped kernel where Pallas runs and
    ``ragged_dot`` elsewhere, as before."""
    sizes = dict(held=16, ep_size=16, top_k=8, n_group=8, topk_group=4)
    layer = {"w1": jax.ShapeDtypeStruct((16, 64, 32), jnp.float32)}
    want = {64: "branch", 128: "dense", 256: "dense", 512: "dense"}
    for backend, many in (("pallas_interpret", "grouped"),
                          ("reference", "ragged")):
        with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": backend}):
            model = _model(tmp_path / backend, **sizes)
        got = {t: model.moe_impl(layer, t)
               for t in (64, 128, 256, 512, 1024, 2048)}
        assert got == {**want, 1024: many, 2048: many}, backend


# ---- the router -----------------------------------------------------------
def _router_by_hand(u, router, bias, *, n_group, topk_group, top_k, scale):
    """The published rule as a loop over tokens, groups and picks."""
    u, router, bias = (np.asarray(a, np.float64) for a in (u, router, bias))
    idx_out, w_out = [], []
    for x in u:
        s = 1.0 / (1.0 + np.exp(-(x @ router)))
        sp = s + bias
        per = len(s) // n_group
        scores = []
        for g in range(n_group):
            members = sorted(sp[g * per:(g + 1) * per])
            scores.append(members[-1] + members[-2])
        kept = sorted(range(n_group), key=lambda g: -scores[g])[:topk_group]
        masked = [sp[e] if e // per in kept else 0.0 for e in range(len(s))]
        idx = sorted(range(len(s)), key=lambda e: -masked[e])[:top_k]
        picked = np.array([s[e] for e in idx])
        idx_out.append(idx)
        w_out.append(picked / (picked.sum() + 1e-20) * scale)
    return np.array(idx_out), np.array(w_out)


def test_the_router_is_the_hand_written_loop(tmp_path):
    """Groups before experts, a choice-only bias, weights normalised
    over the picks times 2.5: the program's ``_route`` and the
    reference's ``route`` against a loop in float64."""
    cfg = testing.deepseek_v3_config()
    model = _model(tmp_path)
    w = dict(ref.make_weights(cfg, SEED)["layers"][1])
    # a bias of the scores' own order, so that it moves picks here
    w["router_bias"] = w["router_bias"] * 10.0
    u = jnp.asarray(np.random.default_rng(4).standard_normal(
        (48, cfg["hidden_size"])) * 4.0, jnp.float32)
    want_idx, want_w = _router_by_hand(
        u, ref_common.deq(w["router"]), w["router_bias"], n_group=4,
        topk_group=2, top_k=3, scale=2.5)
    no_bias = dict(w, router_bias=jnp.zeros_like(w["router_bias"]))
    with jax.default_matmul_precision("highest"):
        for route in (lambda lw: model._route(u, lw),
                      lambda lw: ref.route(u, lw, cfg)):
            idx, weights = map(np.asarray, route(w))
            assert weights.dtype == np.float32
            assert [set(a) for a in idx] == [set(a) for a in want_idx]
            order = np.argsort(idx, -1)
            want_order = np.argsort(want_idx, -1)
            assert np.abs(
                np.take_along_axis(weights, order, -1)
                - np.take_along_axis(want_w, want_order, -1)).max() < 1e-6
            assert np.abs(weights.sum(-1) - 2.5).max() < 1e-5
            # at most topk_group groups are picked from
            assert max(len({e // 4 for e in row}) for row in idx) <= 2
            # the bias alone moved some rows' choice, and not all
            idx0, _ = map(np.asarray, route(no_bias))
            moved = [set(a) != set(b) for a, b in zip(idx, idx0)]
            assert 0 < sum(moved) < len(moved)


# ---- YaRN -------------------------------------------------------------------
def test_yarn_at_the_published_settings_is_the_hand_numbers():
    """theta 100000, d 64, factor 64, L0 4096: pairs 0-8 keep their
    frequency, pairs 19-31 take 1/64 of it, a linear blend between;
    cos and sin unscaled; the softmax scale 192^-1/2 x (0.1 ln 64 + 1)^2."""
    lo, hi = common.yarn_correction_range(64, 100000.0, PUBLISHED_YARN)
    assert (lo, hi) == (8, 19)
    inv = np.asarray(common.yarn_inv_freq(64, 100000.0, PUBLISHED_YARN))
    f = 100000.0 ** (-2.0 * np.arange(32) / 64)
    assert np.allclose(inv[:9], f[:9], rtol=1e-6)
    assert np.allclose(inv[19:], f[19:] / 64, rtol=1e-6)
    i = 12  # inside the blend: m = 1 - 4/11
    m = 1 - (i - 8) / 11
    assert inv[i] == pytest.approx(f[i] / 64 * (1 - m) + f[i] * m, rel=1e-6)
    assert inv[i] == pytest.approx(0.0085618, rel=1e-4)
    assert common.yarn_mscale(64, 1) == pytest.approx(1.4158883)
    assert common.yarn_mscale(1, 1) == 1.0
    # the reference's own arithmetic, from the same published numbers
    cfg = ref.flat({"qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
                    "rope_theta": 100000, "rope_scaling": PUBLISHED_YARN})
    assert np.allclose(np.asarray(ref.inv_freq(cfg)), inv, rtol=1e-6)
    scale = 192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2
    assert ref.softmax_scale(cfg) == pytest.approx(scale)
    assert scale == pytest.approx(0.1446796)


def test_the_model_takes_yarns_scale_and_frequencies(tmp_path):
    model = _model(tmp_path)
    cfg = testing.deepseek_v3_config()
    m = 0.1 * math.log(4.0) + 1
    assert model.scale == pytest.approx(16 ** -0.5 * m * m)
    assert model.rope_factor == pytest.approx(1.0)
    plain = np.asarray(common.rope_frequencies(8, 10000.0))
    got = np.asarray(model._inv_freq())
    assert got[0] == pytest.approx(plain[0])
    assert got[-1] == pytest.approx(plain[-1] / 4.0)
    assert np.allclose(got, np.asarray(ref.inv_freq(ref.flat(cfg))))
    # without rope_scaling: the plain frequencies and scale
    bare = _model(tmp_path / "bare", yarn_factor=1.0)
    assert np.asarray(bare._inv_freq())[-1] == pytest.approx(plain[-1])


def test_seeded_weights_are_the_references(tmp_path):
    """The program's init_params and the reference's make_weights draw
    the same values (w_uk / w_uv are W_kvb's per-head halves)."""
    cfg = testing.deepseek_v3_config()
    model = _model(tmp_path)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(SEED))
    w = ref.make_weights(cfg, SEED)
    nh, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]

    def same(a, b):
        return np.array_equal(np.asarray(a), np.asarray(b))

    assert same(params["embed"], w["embed"])
    assert same(params["lm_head"], w["lm_head"])
    for i, (got, want) in enumerate(zip(params["layers"], w["layers"])):
        for name in ("wq_a", "wq_b", "wkv_a", "wo"):
            assert same(got["attn"][name], want["attn"][name])
        kvb = np.asarray(want["attn"]["wkv_b"]).reshape(
            cfg["kv_lora_rank"], nh, -1)
        assert same(got["attn"]["w_uk"], kvb[:, :, :nope].transpose(1, 2, 0))
        assert same(got["attn"]["w_uv"], kvb[:, :, nope:].transpose(1, 0, 2))
        assert got["attn"]["w_uv"].shape[-1] == cfg["v_head_dim"]
        group = "ffn" if i < cfg["first_k_dense_replace"] else "shared"
        assert (group in got) and (group in want)
        for name in ("gate", "up", "down"):
            assert same(got[group][name], want[group][name])
        if group == "shared":
            for name in ("router", "router_bias", "w1", "w2", "w3"):
                assert same(got[name], want[name])
            assert got["router_bias"].dtype == jnp.float32


# ---- what is refused at load, and the cache statement -----------------------
def test_speculative_decoding_and_tp_are_refused_at_load(tmp_path):
    from jax.sharding import Mesh

    d = testing.write_deepseek_v3_config(str(tmp_path))
    with pytest.raises(ValueError, match="multi-token-prediction"):
        _engine(d, speculative_ngram_k=2)
    model = _model(tmp_path / "m")
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    with pytest.raises(ValueError, match="tp=2"):
        model.validate_mesh(mesh)
    with pytest.raises(ValueError, match="expert_parallel_rank"):
        _model(tmp_path / "r", ep_rank=5)
    with pytest.raises(ValueError, match="n_group"):
        _model(tmp_path / "g", n_group=3)


def test_the_pool_is_one_plane_a_layer_of_7680_bytes_a_token_at_the_cut(
        tmp_path):
    """At the benchmark's sizes (kv_lora_rank 512 + rope 64 in 640
    lanes, six layers, bfloat16) a token holds 7,680 bytes."""
    model = _model(tmp_path, kv_lora=512, rope=64, layers=6,
                   dtype="bfloat16")
    layout = model.kv_cache_layout()
    assert (layout.num_caches, layout.planes, layout.width) == (6, 1, 640)
    assert layout.token_bytes(2) == 7680
    assert model.first_routed_layer == 1


def test_a_single_step_holds_no_xla_while(tmp_path):
    """The benchmark tells a fused scan from a single step by an XLA
    ``while``: the router's two top-k's, the group mask, the pick
    counts and the sorted dispatch are comparisons, sorts and sums."""
    from vllm_distributed_tpu.ops.attention import AttentionMetadata

    model = _model(tmp_path)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    t, s, pages = 32, 8, 4
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    meta = AttentionMetadata(
        q_seq_ids=i32(t), q_positions=i32(t), slot_mapping=i32(t),
        block_tables=i32(s, pages), seq_lens=i32(s), logits_indices=i32(s),
        chunk_starts=i32(s))
    kv = [jax.ShapeDtypeStruct((1, 16, 16, 128), jnp.float32)
          for _ in range(model.num_layers)]
    for impl in ("dense", "ragged", "branch"):
        with mock.patch.dict(os.environ, {"VDT_MOE_IMPL": impl}):
            jaxpr = jax.make_jaxpr(
                lambda p, tok, kv, m: model.forward(p, tok, kv, m, stats={})
            )(params, i32(t), kv, meta)
        assert "while" not in str(jaxpr), impl
