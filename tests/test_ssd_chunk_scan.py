"""The chunked state-space scan as a kernel (``vdt_ssd_chunk_scan``,
ISSUE 51), interpreted: against the recurrence token by token at widths
the kernel accepts; one-token rows riding with prompts; one sequence
over every tile of the largest bucket; a fresh chunk on a row that holds
no number; rows the step does not hold; bfloat16 against the plain form.
Then where the call sits in a lowered step (under neither of the
mixer's scopes), which shapes and backends take it, what the runner
counts as walked, and the kernel compiled for a described v5e at the
benchmark cell's widths."""

import functools
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_distributed_tpu import testing
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.models.common import SCOPE_SSM_MIXER, SCOPE_SSM_SCAN
from vllm_distributed_tpu.models.registry import get_model_class
from vllm_distributed_tpu.ops import ssm
from vllm_distributed_tpu.ops.attention import AttentionMetadata, SeqState
from vllm_distributed_tpu.ops.pallas import ssd_chunk_scan as kernel

CHUNK = 16
H, P, G, N = 4, 128, 2, 128  # the least the kernel accepts: whole lane tiles


def _case(lengths, starts, *, chunk=CHUNK, heads=H, groups=G, rows=None,
          s_pad=8, t_pad=None, seed=3):
    """tests/test_falcon_h1.py's ``_scan_case`` at the kernel's widths:
    a flat batch of ``lengths`` tokens a sequence (chunk ``i`` starts at
    position ``starts[i]``), float32, and what the recurrence gives
    sequence by sequence from the stored state.  ``rows[i]`` at or past
    the state's rows is a sequence without a row."""
    rng = np.random.default_rng(seed)
    s_real = len(lengths)
    s_pad = max(s_pad, s_real)
    t_real = sum(lengths)
    t_pad = t_pad or max(-(-t_real // chunk) * chunk, 2 * chunk)
    r = s_pad + 2
    rows = list(rng.permutation(r)[:s_real]) if rows is None else rows
    f32 = np.float32
    x = rng.standard_normal((t_pad, heads, P)).astype(f32)
    dt = np.exp(
        rng.uniform(np.log(0.01), np.log(0.5), (t_pad, heads))).astype(f32)
    a = -rng.uniform(1, 8, heads).astype(f32)
    b = rng.standard_normal((t_pad, groups, N)).astype(f32) * 0.3
    c = rng.standard_normal((t_pad, groups, N)).astype(f32) * 0.3
    d_skip = rng.standard_normal(heads).astype(f32)
    state = rng.standard_normal((r, heads, P, N)).astype(f32)
    sid = np.full(t_pad, s_pad, np.int32)
    pos = np.zeros(t_pad, np.int32)
    slots = np.full(s_pad, r, np.int32)
    last = np.zeros(s_pad, np.int32)
    chunk_starts = np.zeros(s_pad, np.int32)
    want_y = np.zeros((t_pad, heads, P), f32)
    want_state = state.copy()
    lo = 0
    for i, (ln, start) in enumerate(zip(lengths, starts)):
        sl = slice(lo, lo + ln)
        sid[sl], pos[sl] = i, np.arange(start, start + ln)
        slots[i], last[i], chunk_starts[i] = rows[i], lo + ln - 1, start
        if rows[i] < r:
            init = state[rows[i]] if start else np.zeros((heads, P, N), f32)
            y, st = _recurrence(x[sl], dt[sl], a, b[sl], c[sl], d_skip, init)
            want_y[sl], want_state[rows[i]] = np.asarray(y), np.asarray(st)
        lo += ln
    args = tuple(map(jnp.asarray, (x, dt, a, b, c, d_skip, state)))
    kw = dict(seq_ids=jnp.asarray(sid), positions=jnp.asarray(pos),
              chunk_starts=jnp.asarray(chunk_starts),
              slots=jnp.asarray(slots), last_rows=jnp.asarray(last),
              chunk=chunk)
    return args, kw, want_y, want_state


_recurrence = jax.jit(ssm.ssm_recurrence)


def _run(args, kw, **more):
    with jax.default_matmul_precision("highest"):
        y, state = jax.jit(functools.partial(
            kernel.ssd_chunk_scan, interpret=True, **kw, **more))(*args)
    return np.asarray(y), np.asarray(state)


def _held(kw, r):
    """Flat tokens of the sequences that hold a row, as a mask."""
    sid, slots = np.asarray(kw["seq_ids"]), np.asarray(kw["slots"])
    s = len(slots)
    return (sid < s) & (slots[np.minimum(sid, s - 1)] < r)


CASES = {
    # tests/test_falcon_h1.py's four, at a tile of 16: three sequences
    # and the start of a fourth in the first tile, which crosses a tile
    # edge; a one-token row rides last
    "ragged": ((2, 3, 2, 19, 1), (5, 0, 11, 3, 40), {}),
    # a sequence that ends exactly at a tile's edge, the next starting
    # at the edge; a fresh one crossing; whole tiles of one sequence
    "edges": ((16, 11, 53), (7, 0, 9), {}),
    # one-token rows only, through the chunked form
    "one-token rows": ((1, 1, 1, 1, 1), (4, 0, 9, 1, 2), {}),
    # a chunk past the tile, fresh, alone
    "fresh alone": ((37,), (0,), {}),
    # 60 running sequences' rows beside three prompts' chunks, as the
    # benchmark cell's mixed steps have them (the rows first)
    "60 rows and 3 prompts": (
        (1,) * 60 + (70, 33, 9),
        tuple(range(5, 65)) + (48, 0, 0),
        dict(s_pad=64, heads=2, groups=1)),
    # one sequence over all 16 tiles of a t2048 bucket, at the cell's
    # tile of 128, from a stored state
    "16 tiles": ((2048,), (77,), dict(chunk=128, heads=2, groups=1)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_the_recurrence(name):
    lengths, starts, more = CASES[name]
    args, kw, want_y, want_state = _case(lengths, starts, **more)
    y, state = _run(args, kw)
    live = _held(kw, args[-1].shape[0])
    # float32 sums in another order: 1e-5 of values of order 10
    assert np.abs(y[live] - want_y[live]).max() < 2e-4
    assert np.abs(state - want_state).max() < 2e-4
    slots = set(np.asarray(kw["slots"]).tolist())
    untouched = sorted(set(range(args[-1].shape[0])) - slots)
    assert untouched
    assert np.array_equal(state[untouched], np.asarray(args[-1])[untouched])


@pytest.mark.parametrize("filler", [np.inf, np.nan])
def test_a_fresh_chunk_starts_from_zero_whatever_the_row_holds(filler):
    lengths, starts = (1, 21, 5), (0, 0, 3)
    args, kw, want_y, want_state = _case(lengths, starts)
    slots = np.asarray(kw["slots"])
    state = np.asarray(args[-1]).copy()
    state[slots[0]] = filler  # a one-token row at position 0
    state[slots[1], :, ::2] = filler  # a fresh chunk of several tokens
    y, new = _run(args[:-1] + (jnp.asarray(state),), kw)
    assert np.isfinite(y).all() and np.isfinite(new[slots[:3]]).all()
    assert np.abs(y[:27] - want_y[:27]).max() < 2e-4
    assert np.abs(new[slots[:3]] - want_state[slots[:3]]).max() < 2e-4


@pytest.mark.parametrize("rows", [
    # a sequence without a row between two that hold one
    (3, 99, 5),
    # ... before the first that holds one (its grid steps copy through)
    (99, 2, 7),
    # ... after the last
    (4, 1, 99),
    # no sequence of the step holds a row
    (99, 99, 99),
])
def test_rows_the_step_does_not_hold_are_bit_identical(rows):
    lengths, starts = (9, 1, 20), (3, 8, 0)
    args, kw, want_y, want_state = _case(lengths, starts, rows=list(rows))
    before = np.asarray(args[-1])
    y, state = _run(args, kw)
    r = before.shape[0]
    held = [row for row in rows if row < r]
    others = sorted(set(range(r)) - set(held))
    assert np.array_equal(state[others], before[others])
    assert np.abs(state[held] - want_state[held]).max() < 2e-4 if held else True
    live = _held(kw, r)
    assert np.abs(y[live] - want_y[live]).max() < 2e-4 if held else True
    assert np.isfinite(y).all()


def test_bfloat16_agrees_with_the_plain_form():
    """The cell's dtypes: activations and state bfloat16, against the
    plain form over the same (bfloat16-rounded) numbers in float32: XLA's
    CPU backend has no bfloat16 product that accumulates in float32 for
    the plain form's einsums.  The kernel rounds Δ·x, the masked
    weights and the carried state to bfloat16 where the products take
    them, and the state once at the write: 2^-8 of what is summed."""
    lengths, starts = (1, 1, 45, 1, 30, 3), (9, 0, 16, 300, 0, 5)
    args, kw, _, _ = _case(lengths, starts, t_pad=96)
    bf, f32 = jnp.bfloat16, jnp.float32
    x, dt, a, b, c, d_skip, state = args
    low = (x.astype(bf), dt, a, b.astype(bf), c.astype(bf), d_skip,
           state.astype(bf))
    exact = tuple(v.astype(f32) for v in low)
    with jax.default_matmul_precision("highest"):
        y0, s0 = jax.jit(functools.partial(ssm.ssd_chunk_scan, **kw))(*exact)
    y1, s1 = jax.jit(functools.partial(
        kernel.ssd_chunk_scan, interpret=True, **kw))(*low)
    assert s1.dtype == bf and y1.dtype == f32
    live = sum(lengths)
    y0, y1 = np.asarray(y0)[:live], np.asarray(y1)[:live]
    s0, s1 = np.asarray(s0), np.asarray(s1.astype(f32))
    assert np.abs(y0 - y1).max() < 2.0 ** -7 * np.abs(y0).max()
    assert np.abs(s0 - s1).max() < 2.0 ** -7 * np.abs(s0).max()
    untouched = sorted(
        set(range(s0.shape[0])) - set(np.asarray(kw["slots"]).tolist()))
    assert np.array_equal(s1[untouched], s0[untouched])


def test_heads_a_grid_step_divide_a_groups_heads():
    assert kernel.heads_per_step(16) == 8  # the cell: 32 heads, 2 groups
    assert kernel.heads_per_step(2) == 2
    assert kernel.heads_per_step(6) == 6
    assert kernel.heads_per_step(12) == 6
    lengths, starts = (20, 1), (3, 2)
    args, kw, want_y, want_state = _case(lengths, starts)
    for heads in (1, 2):
        y, state = _run(args, kw, heads=heads)
        assert np.abs(y[:21] - want_y[:21]).max() < 2e-4
        assert np.abs(state - want_state).max() < 2e-4


# ---- which steps take the kernel, and what the runner counts -------------
def _model(tmp_path, backend, **sizes):
    d = testing.write_falcon_h1_config(str(tmp_path), **sizes)
    mc = EngineArgs(model=d, skip_tokenizer_init=True, load_format="dummy",
                    num_kv_pages=16).create_engine_config().model_config
    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": backend}):
        return get_model_class(mc.architecture)(mc)


KERNEL_WIDTHS = dict(ssm_heads=2, ssm_head_dim=128, ssm_groups=1,
                     ssm_state=128, chunk=128)


@pytest.mark.parametrize("backend,sizes,tokens,taken", [
    ("pallas", KERNEL_WIDTHS, 256, True),
    ("pallas_interpret", KERNEL_WIDTHS, 128, True),
    ("reference", KERNEL_WIDTHS, 256, False),
    # a bucket under the tile; a head, a state or a tile that is no
    # whole lane tile
    ("pallas", KERNEL_WIDTHS, 64, False),
    ("pallas", dict(KERNEL_WIDTHS, ssm_head_dim=64), 256, False),
    ("pallas", dict(KERNEL_WIDTHS, ssm_state=16), 256, False),
    ("pallas", dict(KERNEL_WIDTHS, chunk=64), 256, False),
    ("pallas", {}, 64, False),  # the toy widths of tests/test_falcon_h1.py
])
def test_backend_and_shape_choose_the_path(
        backend, sizes, tokens, taken, tmp_path):
    model = _model(tmp_path, backend, **sizes)
    assert model._scan_in_kernel(tokens) is taken
    # what the runner counts: every tile of the bucket in the plain
    # form; a 16-token window a one-token row and a sequence's own
    # windows in the kernel
    lens = [1, 1, 20, 5]
    chunk = model.ssm_chunk_size
    walked = model.ssm_tokens_walked(lens, tokens)
    if taken:
        assert walked == 16 + 16 + chunk + chunk
    else:
        tile = min(chunk, tokens)
        assert walked == tile * -(-tokens // tile)


def test_walked_tokens_are_the_kernels_windows():
    # 60 one-token rows, then 1,000 tokens from flat index 60 (tiles
    # 0..8 of 128), 300 from 1,060 (tiles 8..10) and 2 more in tile 10
    assert kernel.walked_tokens([1] * 60 + [1000, 300, 2], 128) == (
        60 * 16 + 9 * 128 + 3 * 128 + 128)
    assert kernel.walked_tokens([128, 128], 128) == 256
    assert kernel.walked_tokens([0, 1], 128) == 16


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


def _step_text(model, tokens, one_chip) -> str:
    """The compiled text of ``model.forward`` over a chunked single
    step of ``tokens`` rows, for the described chip."""
    s, pages = 4, 4
    i32 = jnp.int32

    def shaped(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda v: shaped(v.shape, v.dtype),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    kv = [shaped((2, 8, 16, model.num_kv_heads * model.head_dim), model.dtype)
          for _ in range(model.num_layers)]
    states = [shaped(shape, dtype) for shape, dtype in
              model.seq_state_layout().arrays(s + 1, model.dtype)]
    meta = AttentionMetadata(
        q_seq_ids=shaped((tokens,)), q_positions=shaped((tokens,)),
        slot_mapping=shaped((tokens,)), block_tables=shaped((s, pages)),
        seq_lens=shaped((s,)), logits_indices=shaped((s,)),
        chunk_starts=shaped((s,)))

    def step(params, token_ids, kv, meta, states, slots, last_rows):
        seq = SeqState(states, slots, last_rows, max_q=tokens)
        return model.forward(params, token_ids, kv, meta, seq_state=seq)

    return jax.jit(step).lower(
        params, shaped((tokens,)), kv, meta, states, shaped((s,)),
        shaped((s,))).compile().as_text()


def _kernel_calls(text: str) -> list[str]:
    """The ``op_name`` of every Pallas call of the compiled text."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in text.splitlines() if "tpu_custom_call" in line]


def test_the_call_sits_under_neither_scope_of_the_mixer(
        tmp_path, one_chip, no_compile_cache):
    """``benchmark/layer_metrics/_xplane.reduce`` files an operation
    under its kernel's name and under the innermost known scope of its
    ``op_name``; ``_falcon_h1.scan_seconds`` adds the two.  So the call
    carries neither scope, and what is made in front of it and behind
    it carries both."""
    model = _model(tmp_path, "pallas", layers=1, **KERNEL_WIDTHS)
    text = _step_text(model, 256, one_chip)
    calls = _kernel_calls(text)
    assert len(calls) == 1 and "vdt_ssd_chunk_scan" in calls[0], calls
    assert SCOPE_SSM_SCAN not in calls[0] and SCOPE_SSM_MIXER not in calls[0]
    assert f"{SCOPE_SSM_MIXER}/{SCOPE_SSM_SCAN}/" in text
    assert " while(" not in text


@pytest.mark.parametrize("backend,sizes,tokens", [
    ("reference", KERNEL_WIDTHS, 256),
    ("pallas", dict(KERNEL_WIDTHS, ssm_state=64), 256),
    ("pallas", KERNEL_WIDTHS, 64),
])
def test_another_backend_or_shape_lowers_to_no_kernel(
        backend, sizes, tokens, tmp_path, one_chip, no_compile_cache):
    model = _model(tmp_path, backend, layers=1, **sizes)
    text = _step_text(model, tokens, one_chip)
    assert "vdt_ssd_chunk_scan" not in text and not _kernel_calls(text)
    assert f"{SCOPE_SSM_MIXER}/{SCOPE_SSM_SCAN}/" in text


@pytest.mark.parametrize("tokens", [2048, 256])
def test_the_kernel_compiles_for_the_v5e_at_the_cells_widths(
        tokens, one_chip, no_compile_cache):
    h, p, g, n, r, s = 32, 128, 2, 256, 65, 64
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32

    def shaped(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(x, dt, a, b, c, d, state, sid, pos, starts, slots, last):
        return kernel.ssd_chunk_scan(
            x, dt, a, b, c, d, state, seq_ids=sid, positions=pos,
            chunk_starts=starts, slots=slots, last_rows=last, chunk=128)

    compiled = jax.jit(call, donate_argnums=(6,)).lower(
        shaped((tokens, h, p), bf), shaped((tokens, h), f32),
        shaped((h,), f32), shaped((tokens, g, n), bf),
        shaped((tokens, g, n), bf), shaped((h,), f32),
        shaped((r, h, p, n), bf), shaped((tokens,)), shaped((tokens,)),
        shaped((s,)), shaped((s,)), shaped((s,))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vdt_ssd_chunk_scan" in text
    assert " while(" not in text
    # the state goes out in the buffer it came in: no copy of the rows
    # the step does not hold
    assert compiled.memory_analysis().alias_size_in_bytes >= r * h * p * n * 2


# ---- the engine through the kernel ---------------------------------------
@pytest.mark.slow  # the production kernels interpreted: ~1-2 min
def test_engine_through_the_kernel_agrees_with_the_reference(tmp_path):
    from benchmark.reference import falcon_h1 as ref
    from tests.test_falcon_h1 import (
        SEED, TOL, _engine, _generate, _prompts, _worst)

    sizes = dict(KERNEL_WIDTHS, layers=1)
    d = testing.write_falcon_h1_config(str(tmp_path), **sizes)
    cfg = testing.falcon_h1_config(**sizes)
    assert ref.ARCHITECTURE == cfg["architectures"][0] and SEED
    # a budget of one tile of 128: the first prompt goes in two chunks
    # (128, 22), the budget's rest to the next, whose chunks start at
    # 106 and 234; the running sequences' rows ride beside them
    prompts = _prompts((150, 140, 130, 5))
    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": "pallas_interpret"}):
        engine = _engine(d, max_num_batched_tokens=128, max_model_len=512)
        try:
            runner = engine.executor.worker.runner
            assert runner.model._scan_in_kernel(128)
            outs = _generate(engine, prompts, logprobs=3)
            tiles = dict(runner.telemetry.counters["ssm_chunk_tokens"])
        finally:
            engine.shutdown()
    diff, regret = _worst(cfg, prompts, outs)
    assert diff is not None and diff < TOL and regret < TOL, (diff, regret)
    # one-token rows rode in 16-token windows: what was walked is no
    # longer whole tiles of the bucket only
    assert 0 < tiles["live"] < tiles["tiled"] and tiles["tiled"] % 128
