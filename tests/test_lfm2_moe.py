"""LFM2-MoE on the served path (ISSUE 38): the engine against the plain
reference through chunked prefill that splits sequences at every offset,
mixed steps and the fused decode scan; state rows that change hands;
the sigmoid router's rules; the statement of caches and of sequence
state; what per-sequence state refuses at load; no XLA loop in a single
step; the kernels at heads of 64 compiled for a described v5e."""

import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import common as ref_common
from benchmark.reference import lfm2_moe as ref
from vllm_distributed_tpu import testing
from vllm_distributed_tpu.config import EngineArgs, ModelConfig
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.models import lfm2_moe as lfm
from vllm_distributed_tpu.models.registry import get_model_class
from vllm_distributed_tpu.sampling_params import SamplingParams

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 20250930
# float32 on the CPU: the program (ragged chunks, state rows, the paged
# cache) and the reference (whole sequences, no state) differ by
# rounding alone (4e-7 measured on a logprob of order 5); an int8
# weight moves a logprob by 1e-3 and more, a bfloat16 router by 1e-4, a
# state row one token off by 1e-4 and more.
TOL = 5e-6


def _engine(model_dir, **kw):
    args = dict(model=model_dir, skip_tokenizer_init=True, num_kv_pages=64,
                max_model_len=256, max_num_seqs=8, num_decode_steps=4,
                max_num_batched_tokens=32, load_format="dummy", seed=SEED)
    args.update(kw)
    return LLMEngine.from_engine_args(EngineArgs(**args))


def _generate(engine, prompts, logprobs=None, max_tokens=9, tag="r"):
    for i, p in enumerate(prompts):
        engine.add_request(f"{tag}{i}", prompt_token_ids=p,
                           sampling_params=SamplingParams(
                               temperature=0.0, max_tokens=max_tokens,
                               ignore_eos=True, logprobs=logprobs))
    done = {}
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.outputs[0]
    return [done[f"{tag}{i}"] for i in range(len(prompts))]


def _prompts(lengths=(66, 65, 67, 5)):
    """66, 65 and 67 tokens against a 32-token budget: served together
    their chunks start at 0, 32, 64 (two tokens), then where the budget
    left off, so that chunks of one, two and more tokens and chunk
    starts at every offset mod 3 all occur."""
    rng = np.random.default_rng(0)
    return [list(map(int, rng.integers(3, 128, n))) for n in lengths]


def _worst(cfg, prompts, outs):
    """(largest |system logprob - reference logprob| over the returned
    tokens, or None without logprobs; largest regret of an emitted
    token by the reference's logits, teacher-forced)."""
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


def _chunks(tracer):
    """Every (chunk start, chunk length) the scheduler made, from the
    ``scheduler.schedule`` spans' ``batch`` attribute."""
    seen, done = [], {}
    for tr in tracer.snapshot():
        for s in tr["spans"]:
            batch = s["attributes"].get("batch")
            if s["name"] != "scheduler.schedule" or not batch:
                continue
            steps = s["attributes"].get("decode_steps", 1)
            for item in batch.split(","):
                req, n = item.rsplit(":", 1)
                seen.append((done.get(req, 0), int(n), steps))
                done[req] = done.get(req, 0) + int(n) * steps
    return seen


@pytest.mark.parametrize("backend", ["reference", "pallas_interpret"])
def test_engine_agrees_with_the_reference_at_every_split(backend, tmp_path):
    """Chunked prefill whose chunks split a sequence at every offset mod
    3 (and chunks of one and two tokens, which read one or both state
    rows), mixed steps, then decode: with ``logprobs`` through single
    steps, whose values are the reference's logits under a log-softmax;
    without, through the fused scan, whose state rides the loop."""
    from vllm_distributed_tpu.tracing import get_tracer

    d = testing.write_lfm2_config(str(tmp_path))
    cfg = testing.lfm2_config()
    prompts = _prompts()
    tracer = get_tracer()
    tracer.reset()
    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": backend}):
        engine = _engine(d, enable_tracing=True)
        try:
            with_lp = _generate(engine, prompts, logprobs=3)
            chunks = _chunks(tracer)
            fused = _generate(engine, prompts, tag="f")
            scans = [c for c in _chunks(tracer) if c[2] > 1]
            tel = engine.executor.worker.runner.telemetry
            picks = dict(tel.counters["moe_expert_picks"])
            rows = dict(tel.counters["seq_state_rows"])
        finally:
            engine.shutdown()
            tracer.configure(False)
            tracer.reset()
    prefill = [(lo, n) for lo, n, _ in chunks if n > 1 or lo < 5]
    assert {lo % 3 for lo, n in prefill if lo > 0} == {0, 1, 2}, prefill
    assert {1, 2} <= {n for lo, n, _ in chunks if lo > 0}, chunks
    assert scans, "the second pass decoded through the fused scan"
    diff, regret = _worst(cfg, prompts, with_lp)
    assert diff is not None and diff < TOL, diff
    assert regret < TOL
    _, regret = _worst(cfg, prompts, fused)
    assert regret < TOL
    assert [o.token_ids for o in fused] == [o.token_ids for o in with_lp]
    # every live row made top_k picks in each of the routed layers; the
    # busiest expert of a layer has at least its even share of them
    routed = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    assert set(picks) == set(lfm.PICK_KINDS)
    assert picks["all"] % (cfg["num_experts_per_tok"] * routed) == 0
    assert picks["all"] / cfg["num_experts"] <= picks["busiest"] < picks["all"]
    # each request was fresh once a pass, and carried in every other step
    assert rows["fresh"] == 2 * len(prompts) and rows["carried"] > rows["fresh"]


def _patched_run(tmp_path, patch, **engine_kw):
    d = testing.write_lfm2_config(str(tmp_path))
    prompts = _prompts()
    with patch:
        engine = _engine(d, **engine_kw)
        try:
            outs = _generate(engine, prompts, logprobs=3)
        finally:
            engine.shutdown()
    return _worst(testing.lfm2_config(), prompts, outs)[0]


def test_an_int8_weight_fails_the_tolerance(tmp_path):
    diff = _patched_run(tmp_path, mock.patch.dict(os.environ, {}),
                        quantization="int8")
    assert diff > 10 * TOL, diff


def test_a_bfloat16_router_fails_the_tolerance(tmp_path):
    def bf16_route(self, u, layer):
        logits = (u.astype(jnp.bfloat16)
                  @ layer["router"].astype(jnp.bfloat16))
        scores = jax.nn.sigmoid(logits).astype(jnp.float32)
        _, idx = jax.lax.top_k(scores + layer["expert_bias"], self.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        return idx.astype(jnp.int32), w / (w.sum(-1, keepdims=True) + 1e-6)

    diff = _patched_run(tmp_path, mock.patch.object(
        lfm.Lfm2MoeForCausalLM, "_route", bf16_route))
    assert diff > 3 * TOL, diff


def test_a_state_row_one_token_off_fails_the_tolerance(tmp_path):
    """The rows left behind in the wrong order ((z_t, z_{t-1}) for
    (z_{t-1}, z_t)): every chunk that starts mid-sequence reads its two
    predecessors swapped."""
    sound = lfm.Lfm2MoeForCausalLM._short_conv

    def swapped(self, x, layer, state, seq, meta):
        x, new = sound(self, x, layer, state, seq, meta)
        rows = new[jnp.minimum(seq.slots, new.shape[0] - 1)]
        return x, new.at[seq.slots].set(rows[:, ::-1], mode="drop")

    diff = _patched_run(tmp_path, mock.patch.object(
        lfm.Lfm2MoeForCausalLM, "_short_conv", swapped))
    assert diff > 10 * TOL, diff


# ---- slots change hands ---------------------------------------------------
def test_state_rows_change_hands_and_survive_a_recompute(tmp_path):
    """Ten requests over four rows and a pool too small for four long
    ones: rows pass from finished requests to waiting ones with nothing
    cleared between, and a preempted request starts again at position
    0.  Every request's logprobs are those of the same request served
    alone, and the scan's tokens likewise."""
    d = testing.write_lfm2_config(str(tmp_path))
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(3, 128, n)))
               for n in (40, 9, 33, 50, 3, 47, 20, 41, 8, 38)]

    def run(batch, logprobs, **kw):
        engine = _engine(d, max_num_seqs=4, **kw)
        try:
            outs = _generate(engine, batch, logprobs=logprobs, max_tokens=24)
            return outs, engine.scheduler.num_preemptions
        finally:
            engine.shutdown()

    # 12 pages of 16 tokens: three of the longer requests fill them
    crowded, preempted = run(prompts, 2, num_kv_pages=12)
    assert preempted > 0
    fused, preempted_f = run(prompts, None, num_kv_pages=12)
    assert preempted_f > 0
    for i, p in enumerate(prompts):
        (alone,), _ = run([p], 2)
        assert crowded[i].token_ids == alone.token_ids == fused[i].token_ids
        for got, want in zip(crowded[i].logprobs, alone.logprobs):
            for tok, value in want.items():
                assert abs(got[tok] - value) < TOL, (i, tok)


def test_single_steps_are_compiled_at_the_sequence_bucket(tmp_path):
    """One request served alone runs the programs a full batch runs: a
    single step's sequence bucket is the runner's (32 here), not the
    step's own count rounded up (8), so no (tokens, longest chunk) pair
    is compiled once a count of sequences."""
    from vllm_distributed_tpu.tracing import get_tracer

    d = testing.write_lfm2_config(str(tmp_path))
    tracer = get_tracer()
    tracer.reset()
    engine = _engine(d, max_num_seqs=32, enable_tracing=True)
    try:
        _generate(engine, _prompts((40,)), logprobs=1, max_tokens=3)
        programs = {s["attributes"]["program"] for tr in tracer.snapshot()
                    for s in tr["spans"] if s["name"] == "runner.dispatch"}
    finally:
        engine.shutdown()
        tracer.configure(False)
        tracer.reset()
    assert programs and all("_s32_" in p for p in programs), programs


# ---- the router ------------------------------------------------------------
def _model(tmp_path, **sizes):
    d = testing.write_lfm2_config(str(tmp_path), **sizes)
    mc = EngineArgs(model=d, skip_tokenizer_init=True, load_format="dummy",
                    num_kv_pages=16).create_engine_config().model_config
    return get_model_class(mc.architecture)(mc)


def test_the_bias_moves_the_choice_and_never_a_weight(tmp_path):
    cfg = testing.lfm2_config()
    model = _model(tmp_path)
    w = ref.make_weights(cfg, SEED)["layers"][cfg["num_dense_layers"]]
    u = jnp.asarray(np.random.default_rng(4).standard_normal(
        (64, cfg["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        scores = np.asarray(jax.nn.sigmoid(u @ ref_common.deq(w["router"])))
        for route in (lambda lw: model._route(u, lw),
                      lambda lw: ref.route(u, lw, cfg)):
            idx, weights = map(np.asarray, route(w))
            idx0, weights0 = map(np.asarray, route(dict(
                w, expert_bias=jnp.zeros_like(w["expert_bias"]))))
            assert weights.dtype == np.float32
            # the weight is the unbiased score at the index over the
            # chosen scores' sum: no bias in it
            chosen = np.take_along_axis(scores, idx, -1)
            want = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
            assert np.abs(weights - want).max() < 1e-6
            assert np.abs(weights.sum(-1) - 1.0).max() < 1e-6
            # the seeded bias (of the order of the scores' spread)
            # moved some rows' picks and not all
            moved = [set(a) != set(b) for a, b in zip(idx, idx0)]
            assert 0 < sum(moved) < len(moved)
            # without the bias the picks are the top scores
            top = np.sort(np.argsort(-scores, -1)[:, :idx0.shape[1]], -1)
            assert np.array_equal(np.sort(idx0, -1), top)


def test_seeded_weights_are_the_references(tmp_path):
    cfg = testing.lfm2_config()
    model = _model(tmp_path)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(SEED))
    w = ref.make_weights(cfg, SEED)
    assert np.array_equal(np.asarray(params["embed"]), np.asarray(w["embed"]))
    assert np.array_equal(np.asarray(w["lm_head"]),
                          np.asarray(params["embed"]).T)
    for got, want in zip(params["layers"], w["layers"]):
        assert want, "a layer holds tensors"
        for name, value in want.items():
            assert np.array_equal(np.asarray(got[name]), np.asarray(value)), (
                name)
    bias = np.asarray(w["layers"][-1]["expert_bias"])
    assert bias.dtype == np.float32 and 0.02 < bias.std() < 0.3
    low = ref.make_weights(cfg, SEED, bits=4)["layers"]
    q, _ = low[0]["in_proj"]
    assert q.dtype == "int8" and int(abs(q).max()) <= 8
    for name in ("router", "expert_bias"):
        assert not isinstance(low[-1][name], tuple)  # never quantized
    assert not isinstance(low[0]["conv_w"], tuple)
    assert ref.ARCHITECTURE == lfm.Lfm2MoeForCausalLM.architectures[0]


# ---- the statement of caches and of state --------------------------------
def _runner_for(model_dir, **kw):
    from vllm_distributed_tpu.worker.model_runner import ModelRunner

    cfg = EngineArgs(model=model_dir, skip_tokenizer_init=True,
                     load_format="dummy", num_kv_pages=32,
                     **kw).create_engine_config()
    runner = ModelRunner(cfg, attn_backend="reference")
    runner.model = get_model_class(cfg.model_config.architecture)(
        cfg.model_config)
    return runner


def test_one_paged_cache_and_40960_bytes_of_state_a_row_at_the_cut(tmp_path):
    conf = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "lfm2-8b-a1b-cut-bf16.json")))
    with open(tmp_path / "config.json", "w") as f:
        json.dump({k: v for k, v in conf.items() if k != "benchmark"}, f)
    runner = _runner_for(str(tmp_path), max_num_seqs=64)
    m = runner.model
    assert (len(m.attn_layers), len(m.conv_layers)) == (1, 5)
    assert m.attn_layers == (2,) and m.head_dim == 64
    assert (m.num_dense, m.first_routed_layer) == (2, 2)
    # 1 attention layer (of 6 layers) x K and V x 8 heads x 64 x 2 B
    assert runner.kv_cache_bytes_per_page() == 16 * 2048
    # what every step program takes donated: the pools, then the states
    pool, states = jax.eval_shape(lambda: runner.alloc_kv_pool(32))
    assert len(pool) == 1 and pool[0].shape == (2, 32, 16, 512)
    layout = m.seq_state_layout()
    (kind,) = layout.kinds
    assert (kind.name, kind.num_layers, kind.shape) == ("conv", 5, (2, 2048))
    assert layout.num_states == 5
    assert layout.seq_bytes(m.dtype) == 40960  # 65,536 at ten layers' 8
    assert len(states) == 5 and states[0].shape == (64, 2, 2048)
    assert states[0].dtype == jnp.bfloat16
    assert runner._seq_state_bytes() == 64 * 40960


@pytest.mark.parametrize("write", [testing.write_llama_config,
                                   testing.write_mixtral_config,
                                   testing.write_longcat_config])
def test_the_other_models_keep_no_state_and_their_pool(write, tmp_path):
    runner = _runner_for(write(str(tmp_path)))
    assert runner._state_layout is None and runner._seq_state_bytes() == 0
    assert runner.alloc_seq_states(8) is None
    assert isinstance(jax.eval_shape(lambda: runner.alloc_kv_pool(8)), list)
    assert runner._state_slots([], 8) == []


@pytest.mark.parametrize("kw,names", [
    (dict(enable_prefix_caching=True), "--enable-prefix-caching"),
    (dict(speculative_ngram_k=2), "--speculative-ngram-k"),
    (dict(kv_spill_host_pages=8), "--kv-spill-host-pages"),
    (dict(kv_transfer_config={"kv_connector": "x"}), "--kv-transfer-config"),
    (dict(tensor_parallel_size=2), "mesh of 2"),
])
def test_what_sequence_state_cannot_do_fails_at_load_and_says_so(
        kw, names, tmp_path):
    d = testing.write_lfm2_config(str(tmp_path))
    with pytest.raises(ValueError, match="per-sequence|lfm2_moe") as e:
        _engine(d, **kw)
    assert names in str(e.value)


def test_page_export_is_refused_by_name(tmp_path):
    runner = _runner_for(testing.write_lfm2_config(str(tmp_path)))
    with pytest.raises(ValueError, match="per-sequence state"):
        runner._refuse_page_transfer()


@pytest.mark.parametrize("change,says", [
    (dict(layer_types=["conv", "sliding_attention", "conv", "conv"]),
     "layer_types entries"),
    (dict(layer_types=["conv", "conv"]), "2 layer_types"),
    (dict(num_dense_layers=5), "num_dense_layers 5"),
])
def test_a_config_the_engine_cannot_read_is_an_error_at_load(
        change, says, tmp_path):
    cfg = dict(testing.lfm2_config(), **change)
    with open(tmp_path / "config.json", "w") as f:
        json.dump(cfg, f)
    with pytest.raises(ValueError, match=says):
        ModelConfig(model=str(tmp_path), dtype="float32",
                    skip_tokenizer_init=True)


# ---- no XLA loop in a single step -----------------------------------------
def _primitives(jxp):
    for eqn in jxp.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name == "pallas_call":
            continue  # a kernel's own loop never reaches XLA
        for v in eqn.params.values():
            for inner in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _primitives(inner)


@pytest.mark.parametrize("tokens,backend,impl", [
    (64, "reference", "dense"), (512, "reference", "ragged"),
    (64, "pallas_interpret", "dense"), (512, "pallas_interpret", "grouped")])
def test_a_single_step_holds_no_xla_while(tokens, backend, impl, tmp_path):
    """The benchmark tells a fused scan from a single step by the
    ``while`` of the scan: a step's forward pass (the convolution's
    shifted reads and its state's scatter, the router, the experts in
    the dense form, through ``ragged_dot`` and through the grouped
    kernel with its sort and its tile layout) lowers to none."""
    from vllm_distributed_tpu.ops.attention import (
        AttentionMetadata,
        SeqState,
    )

    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": backend}):
        model = _model(tmp_path)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert model.moe_impl(params["layers"][1], tokens) == impl
    s = 8
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    meta = AttentionMetadata(
        q_seq_ids=i32(tokens), q_positions=i32(tokens),
        slot_mapping=i32(tokens), block_tables=i32(s, 16), seq_lens=i32(s),
        logits_indices=i32(s), chunk_starts=i32(s))
    kv = [jax.ShapeDtypeStruct((2, 32, 16, 32), jnp.float32)]
    state = SeqState(
        states=[jax.ShapeDtypeStruct((s, 2, 64), jnp.float32)] * 3,
        slots=i32(s), last_rows=i32(s))
    jaxpr = jax.make_jaxpr(lambda p, tok, kv, meta, st: model.forward(
        p, tok, kv, meta, seq_state=st))(params, i32(tokens), kv, meta, state)
    names = list(_primitives(jaxpr.jaxpr))
    if impl == "ragged":
        ragged = [n for n in names if n.startswith("ragged_dot")]
        assert len(ragged) == 3 * 3  # three routed layers
    if impl == "grouped":
        assert names.count("pallas_call") == 2 * 3  # up (fused), down
    assert not set(names) & {"while", "scan", "cond"}, set(names)


@pytest.mark.parametrize("tokens,live_rows", [(96, 96), (200, 170)])
def test_the_grouped_kernel_gives_the_references_routed_layer(
        tokens, live_rows, tmp_path):
    """Above 256 routed rows the unquantized experts take the grouped
    kernel where Pallas runs (interpreted here): the reference's routed
    layer on the live rows, zeros on a step's padding, and the picks
    counted over the live rows alone."""
    cfg = testing.lfm2_config()
    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": "pallas_interpret"}):
        model = _model(tmp_path)
    w = ref.make_weights(cfg, SEED)["layers"][-1]
    assert model.moe_impl(w, tokens) == "grouped"
    assert model.moe_impl(w, 64) == "dense"  # the fused scan's rows
    u = jnp.asarray(np.random.default_rng(9).standard_normal(
        (tokens, cfg["hidden_size"])), jnp.float32)
    live = jnp.arange(tokens) < live_rows
    with jax.default_matmul_precision("highest"):
        got, picks = jax.jit(lambda x: model._moe(x, w, live))(u)
        want = np.asarray(ref.moe(u, w, cfg))
    got = np.asarray(got)
    assert np.abs(got[:live_rows] - want[:live_rows]).max() < 1e-5
    assert not got[live_rows:].any()
    assert int(picks[0]) == live_rows * cfg["num_experts_per_tok"]
    assert live_rows * 3 / 8 <= int(picks[1]) <= live_rows


# ---- the lifted routed layer: Mixtral's outputs bit-equal -----------------
def _mixtral_layer_as_it_was(model, h, layer, impl):
    """Mixtral's dense and sorted-ragged forms as ``models/mixtral.py``
    computed them before the routed layer was lifted out of the class
    (PR 37's tree, the single-device branches)."""
    t, e, k = h.shape[0], model.num_experts, model.top_k
    topw, topi = model._route(h, layer)
    w1, w3, w2 = layer["w1"], layer["w3"], layer["w2"]
    if impl == "dense":
        combine = (jnp.zeros((t, e), jnp.float32)
                   .at[jnp.arange(t)[:, None], topi].set(topw)
                   .astype(h.dtype))
        inner = jax.nn.silu(jnp.einsum("th,ehi->tei", h, w1)) * jnp.einsum(
            "th,ehi->tei", h, w3)
        return jnp.einsum("tei,eih,te->th", inner, w2, combine)
    flat_e = topi.reshape(-1).astype(jnp.int32)
    order = jnp.argsort(flat_e)
    gs = (flat_e[:, None] == jnp.arange(e, dtype=jnp.int32)).sum(
        axis=0, dtype=jnp.int32)
    tok = order // k
    xs = h[tok]
    row_w = topw.reshape(-1)[order].astype(h.dtype)
    inner = jax.nn.silu(jax.lax.ragged_dot(xs, w1, gs)) * jax.lax.ragged_dot(
        xs, w3, gs)
    orows = jax.lax.ragged_dot(inner, w2, gs)
    y = jnp.zeros((t, h.shape[1]), jnp.float32)
    contrib = orows.astype(jnp.float32) * row_w.astype(jnp.float32)[:, None]
    return y.at[tok].add(contrib).astype(h.dtype)


@pytest.mark.parametrize("impl", ["dense", "ragged"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mixtrals_routed_layer_is_bit_equal_to_what_it_was(
        impl, dtype, tmp_path):
    """tests/test_moe.py's model (16 experts, top-2) on its prompts'
    widths: the lifted layer gives Mixtral the bits it gave before, in
    both unquantized forms, jitted as a step program jits them."""
    d = testing.write_mixtral_config(
        str(tmp_path), num_experts=16, top_k=2, dtype=dtype)
    mc = EngineArgs(model=d, skip_tokenizer_init=True, load_format="dummy",
                    num_kv_pages=16).create_engine_config().model_config
    model = get_model_class(mc.architecture)(mc)
    layer = model.init_params(jax.random.PRNGKey(3))["layers"][0]
    h = jnp.asarray(np.random.default_rng(5).standard_normal((40, 64)),
                    model.dtype)
    now = jax.jit(lambda x: model._experts(impl, x, layer))(h)
    was = jax.jit(lambda x: _mixtral_layer_as_it_was(model, x, layer, impl))(h)
    assert now.dtype == was.dtype
    assert np.array_equal(np.asarray(now, np.float32),
                          np.asarray(was, np.float32))
    # and with a live mask the sorted form drops the padding rows alone
    if impl == "ragged":
        live = jnp.arange(40) < 33
        masked = jax.jit(lambda x: model._experts(impl, x, layer, live))(h)
        assert np.array_equal(np.asarray(masked, np.float32)[:33],
                              np.asarray(now, np.float32)[:33])
        assert not np.asarray(masked, np.float32)[33:].any()


# ---- compiled for a described v5e chip, no chip attached -----------------
@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("t,max_q,s,side", [
    (2048, 2048, 64, False),  # the cell's largest mixed step
    (256, 128, 64, False),
    (64, 1, 64, True),  # the fused scan's call
    (64, 1, 64, False),  # a decode-only single step
])
def test_attention_compiles_for_the_v5e_at_heads_of_64(
        t, max_q, s, side, one_chip, no_compile_cache):
    """32 query heads over 8 KV heads of 64 lanes (a pool row of 512
    lanes, two heads to a 128-lane group), a 256-page table: Mosaic
    takes it, and no XLA loop comes with the tile lists."""
    from vllm_distributed_tpu.ops.attention import AttentionMetadata
    from vllm_distributed_tpu.ops.pallas.paged_attention import (
        paged_attention,
    )

    hq, hkv, d, page, pages, pool = 32, 8, 64, 16, 256, 8192

    def shaped(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = AttentionMetadata(
        q_seq_ids=shaped((t,)), q_positions=shaped((t,)),
        slot_mapping=shaped((t,)), block_tables=shaped((s, pages)),
        seq_lens=shaped((s,)), logits_indices=shaped((s,)),
        chunk_starts=shaped((s,)))
    args = [shaped((t, hq, d), jnp.bfloat16),
            shaped((2, pool, page, hkv * d), jnp.bfloat16), meta]
    if side:
        args += [shaped((s, 2, 8, hkv * d), jnp.bfloat16), shaped((1,))]

    def call(q, kv, meta, *staged):
        kw = dict(side_kv=staged[0], side_len=staged[1]) if staged else {}
        return paged_attention(
            q, kv, meta, scale=0.125, num_kv_heads=hkv, max_q=max_q, **kw)

    compiled = jax.jit(call).lower(*args).compile()
    text = compiled.as_text()
    assert "vdt_paged_attention" in text
    assert text.count("tpu_custom_call") == (2 if max_q >= 16 else 1)
    assert " while(" not in text and " conditional(" not in text


def test_kv_flush_compiles_for_the_v5e_at_heads_of_64(
        one_chip, no_compile_cache):
    from vllm_distributed_tpu.ops.pallas.kv_flush import kv_flush

    s, k, hd, pages, pool = 64, 8, 8 * 64, 256, 8192

    def shaped(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(kv_flush, donate_argnums=(0,)).lower(
        shaped((2, pool, 16, hd), jnp.bfloat16),
        shaped((s, 2, k, hd), jnp.bfloat16), shaped((s, pages)),
        shaped((s,)), shaped((s,))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vdt_kv_flush" in text
