"""Falcon-H1 on the served path (ISSUE 48): the engine against the plain
reference through chunked prefill that cuts sequences at offsets that
are no multiple of the scan's chunk, mixed steps and the fused decode
scan; the chunked scan against the recurrence alone; controls that must
fail; state rows that change hands; what per-sequence state refuses at
load; the seeded recipe's spreads."""

import functools
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import falcon_h1 as ref
from vllm_distributed_tpu import testing
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.models import common
from vllm_distributed_tpu.models import falcon_h1 as fh
from vllm_distributed_tpu.models.registry import get_model_class
from vllm_distributed_tpu.ops import ssm
from vllm_distributed_tpu.sampling_params import SamplingParams

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 20251002
CHUNK = 8  # the toy config's mamba_chunk_size
# float32 on the CPU: the program (ragged chunks in tiles of 8, state
# rows, the paged cache, one-token updates) and the reference (whole
# sequences, the recurrence token by token) differ by rounding alone
# (2e-6 measured on a logprob of order 5).  Every control below moves a
# logprob by 1e-3 and more but the bfloat16 cumulative sum (2e-4).
TOL = 1e-5


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
    """66, 65 and 67 tokens against a 32-token budget: the first is
    prefilled in three chunks (32, 32, 2), and the budget's rest goes to
    the next, whose chunks then start at 30, 62, ... — no multiple of
    the scan's chunk of 8 — beside the one-token rows of the sequences
    that decode already."""
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


def _steps(tracer):
    """Every step the scheduler made: [(chunk start, chunk length), ...]
    and the fused steps' K, from the ``scheduler.schedule`` spans."""
    steps, done = [], {}
    for tr in tracer.snapshot():
        for s in tr["spans"]:
            batch = s["attributes"].get("batch")
            if s["name"] != "scheduler.schedule" or not batch:
                continue
            k = s["attributes"].get("decode_steps", 1)
            step = []
            for item in batch.split(","):
                req, n = item.rsplit(":", 1)
                step.append((done.get(req, 0), int(n)))
                done[req] = done.get(req, 0) + int(n) * k
            steps.append((step, k))
    return steps


# ---- (i) the engine against the reference ---------------------------------
@pytest.mark.parametrize("backend", [
    "reference",
    # the production attention and flush kernels interpreted, at a query
    # group of 5: ~50 s, so outside the tier-1 run
    pytest.param("pallas_interpret", marks=pytest.mark.slow),
])
def test_engine_agrees_with_the_reference_at_every_split(backend, tmp_path):
    from vllm_distributed_tpu.tracing import get_tracer

    d = testing.write_falcon_h1_config(str(tmp_path))
    cfg = testing.falcon_h1_config()
    prompts = _prompts()
    tracer = get_tracer()
    tracer.reset()
    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": backend}):
        engine = _engine(d, enable_tracing=True)
        try:
            with_lp = _generate(engine, prompts, logprobs=3)
            single = _steps(tracer)
            fused = _generate(engine, prompts, tag="f")
            scans = [s for s in _steps(tracer) if s[1] > 1]
            tel = engine.executor.worker.runner.telemetry
            rows = dict(tel.counters["seq_state_rows"])
            tiles = dict(tel.counters["ssm_chunk_tokens"])
            state_bytes = dict(tel.counters["seq_state_bytes"])
        finally:
            engine.shutdown()
            tracer.configure(False)
            tracer.reset()
    # the first prompt took three chunks; chunks start where no tile of
    # the scan does; one-token rows rode beside multi-token chunks
    first = [c for step, _ in single for c in step if c[0] < 66][:3]
    assert [n for _, n in first] == [32, 32, 2], first
    starts = {lo for step, _ in single for lo, n in step if n > 1 and lo}
    assert any(lo % CHUNK for lo in starts), starts
    assert any(
        {n for _, n in step} >= {1} and max(n for _, n in step) > 1
        for step, _ in single), single
    assert scans, "the second pass decoded through the fused scan"
    diff, regret = _worst(cfg, prompts, with_lp)
    assert diff is not None and diff < TOL, diff
    assert regret < TOL
    _, regret = _worst(cfg, prompts, fused)
    assert regret < TOL
    assert [o.token_ids for o in fused] == [o.token_ids for o in with_lp]
    assert rows["fresh"] == 2 * len(prompts) and rows["carried"] > rows["fresh"]
    # the scans walked whole tiles of the token bucket, live or not
    assert 0 < tiles["live"] < tiles["tiled"] and tiles["tiled"] % CHUNK == 0
    # 8 rows x 2 layers x (3 x 96 conv | 4 x 8 x 16 ssm) float32
    assert state_bytes == {"conv": 8 * 2 * 3 * 96 * 4,
                           "ssm": 8 * 2 * 4 * 8 * 16 * 4}


def test_single_steps_are_one_program_a_token_bucket(tmp_path):
    """A step's longest-chunk bucket is 1 (every sequence has one
    token: the one-token update) or the token bucket itself, and its
    sequence bucket the runner's: one program a token bucket."""
    from vllm_distributed_tpu.tracing import get_tracer

    d = testing.write_falcon_h1_config(str(tmp_path))
    tracer = get_tracer()
    tracer.reset()
    engine = _engine(d, max_num_seqs=16, enable_tracing=True)
    try:
        _generate(engine, _prompts((40, 7, 21)), logprobs=1, max_tokens=3)
        # ... and one whether or not a request reads logprobs: the same
        # prompts without them meet no program of their own
        plain = _generate(engine, _prompts((40, 7, 21)), max_tokens=1,
                          tag="p")
        assert all(not o.logprobs for o in plain)
        programs = {s["attributes"]["program"] for tr in tracer.snapshot()
                    for s in tr["spans"] if s["name"] == "runner.dispatch"}
    finally:
        engine.shutdown()
        tracer.configure(False)
        tracer.reset()
    assert programs == {"vdt_step_t32_q32_s16_p16_lp",
                        "vdt_step_t16_q16_s16_p16_lp",
                        "vdt_step_t16_q1_s16_p16_lp"}, programs
    model = get_model_class("FalconH1ForCausalLM")
    assert model.step_max_q_class(None, 1, 64) == 1
    assert model.step_max_q_class(None, 2, 64) == 64
    assert model.step_max_q_class(None, 2048, 2048) == 2048


def test_the_cells_sweep_is_eight_programs_and_other_models_keep_their_keys():
    """The benchmark cell's sweep plays 30 (tokens, longest chunk)
    buckets (``benchmark/lattice.py`` over ``chat-closed-64``'s 64
    clients); under this model's rule they are seven single-step
    programs, eight with the fused scan.  The rule is the model's own
    attribute: the four other benchmarked models have neither it nor
    ``step_logprobs_always``, so the runner keys their programs as it
    did (``tests/test_seq_state_layout.py`` holds LFM2's to the text)."""
    from benchmark import lattice

    with open(os.path.join(
            REPO, "benchmark", "traffic", "chat-closed-64.json")) as f:
        sweep = json.load(f)["warmup"]["sweep"]
    buckets = list(lattice.buckets(64, sweep))
    assert len(buckets) == 30
    model = get_model_class("FalconH1ForCausalLM")
    steps = {(t, model.step_max_q_class(None, q, t)) for t, q in buckets}
    assert steps == {(64, 1), (64, 64), (128, 128), (256, 256), (512, 512),
                     (1024, 1024), (2048, 2048)}
    assert len(steps) + 1 == 8  # ... and jit_vdt_decode_k8_s64_p256
    for arch in ("MixtralForCausalLM", "LongcatFlashForCausalLM",
                 "Lfm2MoeForCausalLM", "DeepseekV3ForCausalLM"):
        other = get_model_class(arch)
        assert not hasattr(other, "step_max_q_class"), arch
        assert not hasattr(other, "step_logprobs_always"), arch


# ---- (ii) the chunked scan against the recurrence alone -------------------
def _scan_case(lengths, starts, rows=None, chunk=CHUNK, seed=3):
    """A flat batch of ``lengths`` tokens a sequence (chunk ``i`` starts
    at position ``starts[i]`` of its sequence), padded to a power of
    two, float32: the inputs of ``ssd_chunk_scan`` and, sequence by
    sequence, what the recurrence gives from the stored state."""
    h, p, g, n = 4, 8, 2, 16
    rng = np.random.default_rng(seed)
    s_real, s_pad = len(lengths), 8
    t_real = sum(lengths)
    t_pad = max(1 << (t_real - 1).bit_length(), 16)
    r = s_pad + 2
    rows = rows or list(rng.permutation(r)[:s_real])
    f32 = np.float32
    x = rng.standard_normal((t_pad, h, p)).astype(f32)
    dt = np.exp(rng.uniform(np.log(0.01), np.log(0.5), (t_pad, h))).astype(f32)
    a = -rng.uniform(1, 8, h).astype(f32)
    b = rng.standard_normal((t_pad, g, n)).astype(f32)
    c = rng.standard_normal((t_pad, g, n)).astype(f32)
    d_skip = rng.standard_normal(h).astype(f32)
    state = rng.standard_normal((r, h, p, n)).astype(f32)
    sid = np.full(t_pad, s_pad, np.int32)
    pos = np.zeros(t_pad, np.int32)
    slots = np.full(s_pad, r, np.int32)
    last = np.zeros(s_pad, np.int32)
    chunk_starts = np.zeros(s_pad, np.int32)
    want_y = np.zeros((t_pad, h, p), f32)
    want_state = state.copy()
    lo = 0
    for i, (ln, start) in enumerate(zip(lengths, starts)):
        sl = slice(lo, lo + ln)
        sid[sl], pos[sl] = i, np.arange(start, start + ln)
        slots[i], last[i], chunk_starts[i] = rows[i], lo + ln - 1, start
        init = state[rows[i]] if start else np.zeros((h, p, n), f32)
        y, st = ssm.ssm_recurrence(
            *map(jnp.asarray, (x[sl], dt[sl], a, b[sl], c[sl], d_skip, init)))
        want_y[sl], want_state[rows[i]] = np.asarray(y), np.asarray(st)
        lo += ln
    args = tuple(map(jnp.asarray, (x, dt, a, b, c, d_skip, state)))
    kw = dict(seq_ids=jnp.asarray(sid), positions=jnp.asarray(pos),
              chunk_starts=jnp.asarray(chunk_starts),
              slots=jnp.asarray(slots), last_rows=jnp.asarray(last),
              chunk=chunk)
    return args, kw, t_real, want_y, want_state


@pytest.mark.parametrize("lengths,starts", [
    # the first tile of 8 holds three sequences and the start of a
    # fourth, which crosses two tile edges; a one-token row rides last
    ((2, 3, 2, 19, 1), (5, 0, 11, 3, 40)),
    # a sequence that ends exactly at a tile's edge, the next starting
    # at the edge; a fresh one crossing; whole tiles of one sequence
    ((8, 11, 30), (7, 0, 9)),
    # one-token rows only, through the chunked form
    ((1, 1, 1, 1, 1), (4, 0, 9, 1, 2)),
    # a chunk past the tile, fresh, alone
    ((37,), (0,)),
])
def test_the_chunked_scan_is_the_recurrence(lengths, starts):
    args, kw, t_real, want_y, want_state = _scan_case(lengths, starts)
    with jax.default_matmul_precision("highest"):
        y, state = jax.jit(functools.partial(ssm.ssd_chunk_scan, **kw))(*args)
    # float32 sums in another order: 1e-5 of values of order 10
    assert np.abs(np.asarray(y)[:t_real] - want_y[:t_real]).max() < 2e-4
    assert np.abs(np.asarray(state) - want_state).max() < 2e-4
    untouched = sorted(set(range(10)) - set(np.asarray(kw["slots"]).tolist()))
    assert np.array_equal(np.asarray(state)[untouched],
                          np.asarray(args[-1])[untouched])


def test_the_one_token_update_is_the_recurrence():
    lengths, starts = (1, 1, 1, 1, 1, 1), (4, 0, 9, 1, 2, 77)
    args, kw, t_real, want_y, want_state = _scan_case(lengths, starts)
    x, dt, a, b, c, d_skip, state = args
    r = state.shape[0]
    tok_rows = jnp.where(
        kw["seq_ids"] < 8, kw["slots"][jnp.minimum(kw["seq_ids"], 7)], r)
    # a masked row between live ones, as the fused scan has them
    tok_rows = tok_rows.at[2].set(r)
    want_state[np.asarray(kw["slots"])[2]] = np.asarray(state)[
        np.asarray(kw["slots"])[2]]
    y, new = jax.jit(ssm.ssm_update)(
        x, dt, a, b, c, d_skip, state, tok_rows, kw["positions"] == 0)
    live = [i for i in range(t_real) if i != 2]
    assert np.abs(np.asarray(y)[live] - want_y[live]).max() < 2e-5
    assert np.abs(np.asarray(new) - want_state).max() < 2e-5


# ---- (iii) controls that must fail -----------------------------------------
def _patched_run(tmp_path, patch, **engine_kw):
    d = testing.write_falcon_h1_config(str(tmp_path))
    prompts = _prompts()
    with patch:
        engine = _engine(d, **engine_kw)
        try:
            outs = _generate(engine, prompts, logprobs=3)
        finally:
            engine.shutdown()
    return _worst(testing.falcon_h1_config(), prompts, outs)[0]


def _stale_state(real):
    """The state written is the one before each sequence's last token
    (its Δ set to zero for the state alone: no decay, no input); the
    outputs are the sound ones."""
    def chunked(x, dt, *rest, last_rows, **kw):
        y, _ = real(x, dt, *rest, last_rows=last_rows, **kw)
        _, state = real(x, dt.at[last_rows].set(0.0), *rest,
                        last_rows=last_rows, **kw)
        return y, state
    return chunked


def _stale_update(x, dt, *rest):
    y, _ = ssm.ssm_update(x, dt, *rest)
    return y, ssm.ssm_update(x, jnp.zeros_like(dt), *rest)[1]


def _conv_rows_swapped(*args):
    out, prevs = ssm.ragged_causal_conv(*args)
    return out, [prevs[1], prevs[0], prevs[2]]  # (z_{t-1}, z_{t-2}, z_t) kept


def _no_key_multiplier(real):
    """The forward pass without it; the seeded weights as they are
    (drawn for a key that is multiplied)."""
    def attention(self, *args, **kw):
        with mock.patch.object(self, "key_multiplier", 1.0):
            return real(self, *args, **kw)
    return attention


def _keys_one_position_off(x, positions, inv_freq):
    # the toy config's one KV head: a key, not a query
    return common.apply_rope(x, positions + (x.shape[1] == 1), inv_freq)


def _norm_then_gate(self, y, z, layer):
    t, g = y.shape[0], self.ssm_groups
    y = y.reshape(t, g, self.d_ssm // g)
    y = y * jax.lax.rsqrt(
        jnp.mean(y * y, axis=-1, keepdims=True) + self.rms_eps)
    return (y.reshape(t, self.d_ssm) * layer["ssm_norm"].astype(jnp.float32)
            * jax.nn.silu(z))


CONTROLS = {
    "a state row one token stale": lambda: mock.patch.multiple(
        fh, ssd_chunk_scan=_stale_state(ssm.ssd_chunk_scan),
        ssm_update=_stale_update),
    "the convolution state one row off": lambda: mock.patch.object(
        fh, "ragged_causal_conv", _conv_rows_swapped),
    "key_multiplier left out": lambda: mock.patch.object(
        fh.FalconH1ForCausalLM, "_attention",
        _no_key_multiplier(fh.FalconH1ForCausalLM._attention)),
    "a cached key one position off": lambda: mock.patch.object(
        fh, "apply_rope", _keys_one_position_off),
    "the gate applied after the norm": lambda: mock.patch.object(
        fh.FalconH1ForCausalLM, "_gated_norm", _norm_then_gate),
    "a bfloat16 cumulative sum of Δ·A": lambda: mock.patch.object(
        fh, "ssd_chunk_scan", functools.partial(
            ssm.ssd_chunk_scan, cumsum_dtype=jnp.bfloat16)),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_fails_the_tolerance(control, tmp_path):
    diff = _patched_run(tmp_path, CONTROLS[control]())
    assert diff > 10 * TOL, (control, diff)


# ---- (iv) slots change hands ----------------------------------------------
def test_state_rows_change_hands_and_survive_a_recompute(tmp_path):
    """Six requests over three rows and a pool too small for three
    long ones: rows pass from finished requests to waiting ones with
    nothing cleared between (a sequence at position 0 reads nothing of
    the row it is given), and a preempted request starts again at
    position 0.  Every request's logprobs are those of the same request
    served alone (one after another on one engine, so each is given a
    row another has just left), and the scan's tokens likewise."""
    d = testing.write_falcon_h1_config(str(tmp_path))
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(3, 128, n)))
               for n in (40, 9, 50, 3, 47, 41)]

    def run(batches, logprobs, **kw):
        engine = _engine(d, max_num_seqs=3, **kw)
        try:
            outs = [_generate(engine, batch, logprobs=logprobs,
                              max_tokens=16, tag=f"b{i}-")
                    for i, batch in enumerate(batches)]
            return outs, engine.scheduler.num_preemptions
        finally:
            engine.shutdown()

    # 9 pages of 16 tokens: two of the longer requests fill them
    (crowded,), preempted = run([prompts], 2, num_kv_pages=9)
    assert preempted > 0
    (fused,), preempted_f = run([prompts], None, num_kv_pages=9)
    assert preempted_f > 0
    alone, _ = run([[p] for p in prompts], 2)
    for i, ((want,), got, scan) in enumerate(zip(alone, crowded, fused)):
        assert got.token_ids == want.token_ids == scan.token_ids
        for a, b in zip(got.logprobs, want.logprobs):
            for tok, value in b.items():
                assert abs(a[tok] - value) < TOL, (i, tok)


# ---- (v) what is refused at load ------------------------------------------
@pytest.mark.parametrize("kw,names", [
    (dict(enable_prefix_caching=True), "--enable-prefix-caching"),
    (dict(speculative_ngram_k=2), "--speculative-ngram-k"),
    (dict(kv_spill_host_pages=8), "--kv-spill-host-pages"),
    (dict(kv_transfer_config={"kv_connector": "x"}), "--kv-transfer-config"),
    (dict(tensor_parallel_size=2), "mesh of 2"),
])
def test_what_sequence_state_cannot_do_fails_at_load_and_says_so(
        kw, names, tmp_path):
    d = testing.write_falcon_h1_config(str(tmp_path))
    with pytest.raises(ValueError, match="per-sequence|falcon_h1") as e:
        _engine(d, **kw)
    assert names in str(e.value)


@pytest.mark.parametrize("change,says", [
    (dict(rope_scaling={"type": "linear", "factor": 2.0}), "rope_scaling"),
    (dict(mamba_norm_before_gate=True), "mamba_norm_before_gate"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(attention_bias=True), "attention_bias"),
])
def test_a_config_the_block_is_not_written_for_is_refused(
        change, says, tmp_path):
    d = testing.write_falcon_h1_config(str(tmp_path), **change)
    mc = EngineArgs(model=d, skip_tokenizer_init=True, load_format="dummy",
                    num_kv_pages=16).create_engine_config().model_config
    with pytest.raises(NotImplementedError, match=says):
        get_model_class(mc.architecture)(mc)


def test_a_checkpoint_is_refused_by_name(tmp_path):
    d = testing.write_falcon_h1_config(str(tmp_path))
    mc = EngineArgs(model=d, skip_tokenizer_init=True, load_format="dummy",
                    num_kv_pages=16).create_engine_config().model_config
    with pytest.raises(NotImplementedError, match="seeded weights only"):
        get_model_class(mc.architecture)(mc).map_hf_name("model.norm.weight")


# ---- (vi) the seeded recipe ------------------------------------------------
def test_seeded_weights_are_the_references(tmp_path):
    cfg = testing.falcon_h1_config()
    d = testing.write_falcon_h1_config(str(tmp_path))
    mc = EngineArgs(model=d, skip_tokenizer_init=True, load_format="dummy",
                    num_kv_pages=16).create_engine_config().model_config
    model = get_model_class(mc.architecture)(mc)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(SEED))
    w = ref.make_weights(cfg, SEED)
    for name in ("embed", "lm_head"):
        assert np.array_equal(np.asarray(params[name]), np.asarray(w[name]))
    for got, want in zip(params["layers"], w["layers"]):
        in_proj = np.concatenate(
            [np.asarray(want[seg]) for seg in ref.SEGMENTS], axis=1)
        assert np.array_equal(np.asarray(got["in_proj"]), in_proj)
        for name, value in want.items():
            if name not in ref.SEGMENTS:
                assert np.allclose(np.asarray(got[name]), np.asarray(value),
                                   rtol=1e-6, atol=0), name
    low = ref.make_weights(cfg, SEED, bits=4)
    q, s = low["layers"][0]["in_x"]
    assert q.dtype == "int8" and int(abs(q).max()) <= 8 and s.shape == (1, 32)
    q, s = low["lm_head"]
    assert q.shape == (64, 128) and s.shape == (1, 128)
    for name in ("conv_w", "conv_b", "dt_bias", "a_log", "d_skip"):
        assert not isinstance(low["layers"][0][name], tuple)
    assert not isinstance(low["embed"], tuple)
    assert ref.ARCHITECTURE == fh.FalconH1ForCausalLM.architectures[0]
    # the int4 control moves a logprob far past the tolerance
    toks = _prompts((40,))[0]
    sound = ref.logprobs(w, cfg, toks, [38, 39])
    rough = ref.logprobs(low, cfg, toks, [38, 39])
    assert np.abs(sound - rough).max() > 100 * TOL


def test_the_recipe_gives_every_branch_weight_at_toy_widths():
    """With the published multipliers and each matrix drawn at a spread
    that undoes them: the attention's scores before the softmax spread
    by 0.3 to 3, the three branches enter the residual within a factor
    of four of each other, the logits spread by 0.5 to 4, and the
    per-token decay spans [0.5, 0.99] over the heads.  (``normal x
    0.02`` under these multipliers gives scores of spread ~1e-4 and
    logits of ~1e-3: a check blind to most of the block.)"""
    cfg = testing.falcon_h1_config(
        hidden=128, intermediate=256, layers=3, ssm_heads=32, ssm_head_dim=4,
        ssm_state=16, vocab_size=512)
    toks = list(map(int, np.random.default_rng(1).integers(3, 512, 96)))
    got = ref.spreads(ref.make_weights(cfg, SEED), cfg, toks)
    assert 0.5 < got["logits_std"] < 4.0, got
    for row in got["layers"]:
        assert 0.3 < row["score_std"] < 3.0, row
        branches = [row["mixer_rms"], row["attn_rms"], row["mlp_rms"]]
        assert max(branches) < 4.0 * min(branches), row
        assert row["decay_lo"] < 0.5 and row["decay_hi"] > 0.99, row


def test_the_cut_states_four_caches_and_eight_states(tmp_path):
    from vllm_distributed_tpu.worker.model_runner import ModelRunner

    conf = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "falcon-h1-34b-cut-bf16.json")))
    with open(tmp_path / "config.json", "w") as f:
        json.dump({k: v for k, v in conf.items() if k != "benchmark"}, f)
    cfg = EngineArgs(model=str(tmp_path), skip_tokenizer_init=True,
                     load_format="dummy", num_kv_pages=32,
                     max_num_seqs=64).create_engine_config()
    runner = ModelRunner(cfg, attn_backend="reference")
    runner.model = m = get_model_class(cfg.model_config.architecture)(
        cfg.model_config)
    # 4 layers x K and V x 4 heads x 128 x 2 B = 8,192 B a token
    # (12,288 at the six layers the cell was run at first)
    assert runner.kv_cache_bytes_per_page() == 16 * 8192
    pool, states = jax.eval_shape(lambda: runner.alloc_kv_pool(32))
    assert len(pool) == 4 and pool[0].shape == (2, 32, 16, 512)
    assert [s.shape for s in states] == [(64, 3, 5120)] * 4 + [
        (64, 32, 128, 256)] * 4
    assert {s.dtype for s in states} == {jnp.dtype("bfloat16")}
    layout = m.seq_state_layout()
    assert layout.bytes_by_kind(m.dtype) == {
        "conv": 4 * 30720, "ssm": 4 * 2097152}
    assert runner._seq_state_bytes() == 64 * 4 * 2127872
    shapes = jax.eval_shape(m.init_params, jax.random.PRNGKey(0))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    assert round(total / 1e6, 1) == 4394.4  # 8.79 GB in bfloat16 (norms in)
