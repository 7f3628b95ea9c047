"""The grouped int8 expert matmul (ops/pallas/grouped_matmul.py) and
its place in the MoE layer (PR 27): the kernel against dequantize + a
dot per group (interpreted on the CPU), the rule that picks dense,
ragged or grouped from the step's rows and the tensor, the grouped MLP
against the dense oracle on a tiny int8 Mixtral, and the kernel
compiled at Mixtral's widths for a described v5e chip."""

import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.utils import make_tiny_mixtral
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.models.mixtral import (
    GROUPED_MIN_ROWS,
    MixtralForCausalLM,
    select_moe_impl,
)
from vllm_distributed_tpu.ops.pallas import grouped_matmul as gm
from vllm_distributed_tpu.ops.quant import QuantizedTensor, dequantize, quantize
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.tracing import get_tracer


def _padded(x, group_sizes, block_rows, fill=3.0):
    """Rows sorted by group -> the kernel's tile-padded layout (rows in
    no group hold ``fill``), and each group's first padded row."""
    gs = np.asarray(group_sizes)
    mp = gm.padded_rows(x.shape[0], len(gs), block_rows)
    starts = np.asarray(
        gm.group_layout(jnp.asarray(gs, jnp.int32), mp, block_rows)[0])
    xs = np.full((mp, x.shape[1]), fill, np.float32)
    off = np.concatenate([[0], np.cumsum(gs)])
    for e, n in enumerate(gs):
        xs[starts[e]:starts[e] + n] = x[off[e]:off[e + 1]]
    return xs, starts, off


@pytest.mark.parametrize(
    "group_sizes,extra,in_dim,out_dim,block_rows,fused",
    [
        ([5, 0, 17, 3], 0, 64, 96, 8, False),  # an empty expert
        ([0, 0, 40, 0], 0, 64, 96, 8, True),  # one expert holds every row
        ([7, 9, 1, 30], 0, 128, 256, 16, True),  # no size a tile multiple
        ([7, 9, 1, 30], 11, 128, 256, 16, False),  # 11 rows in no group
        ([0, 0, 0, 0], 5, 64, 96, 8, False),  # nothing but padding
        ([3, 6], 0, 4096, 128, 8, True),  # K in one cast chunk's multiple
        ([3, 6], 0, 14336, 128, 8, False),  # K walked in 28 chunks
        ([130, 2, 0, 60], 0, 128, 1024, 128, True),  # the real row tile
    ],
)
def test_grouped_matmul_matches_dequantize_and_a_dot_per_group(
        group_sizes, extra, in_dim, out_dim, block_rows, fused):
    rng = np.random.default_rng(0)
    e, m = len(group_sizes), sum(group_sizes) + extra
    w = [quantize((rng.standard_normal((e, in_dim, out_dim)) * 0.1).astype(
        np.float32), 8) for _ in range(2)]
    x = (rng.standard_normal((m, in_dim)) * 0.5).astype(np.float32)
    xs, starts, off = _padded(x, group_sizes, block_rows)
    up = dict(q_up=jnp.asarray(w[1].q), scale_up=jnp.asarray(w[1].scale))
    out = np.asarray(gm.grouped_int8_matmul(
        jnp.asarray(xs), jnp.asarray(w[0].q), jnp.asarray(w[0].scale),
        jnp.asarray(group_sizes, jnp.int32), block_rows=block_rows,
        interpret=True, **(up if fused else {})))
    assert out.shape == (xs.shape[0], out_dim)
    dense = [np.asarray(dequantize(t, jnp.float32)) for t in w]
    for g, n in enumerate(group_sizes):
        rows = x[off[g]:off[g + 1]]
        want = rows @ dense[0][g]
        if fused:
            want = np.asarray(jax.nn.silu(want)) * (rows @ dense[1][g])
        got = out[starts[g]:starts[g] + n]
        if n:
            assert np.abs(got - want).max() <= 2e-5 * max(
                np.abs(want).max(), 1.0), (g, n)


def test_layout_pads_each_group_to_whole_tiles_and_parks_spare_tiles():
    gs = jnp.asarray([5, 0, 17, 3], jnp.int32)
    mp = gm.padded_rows(25, 4, 8)
    assert mp == 6 * 8  # (25 + 4 * 7) // 8 tiles: enough for any split
    starts, tile_expert, used = gm.group_layout(gs, mp, 8)
    assert starts.tolist() == [0, 8, 8, 32]
    assert used.tolist() == [5]
    # the spare tile keeps the last used tile's expert: no new block
    assert tile_expert.tolist() == [0, 2, 2, 2, 3, 3]
    assert gm.padded_rows(0, 4, 1) == 1  # never an empty grid
    # worst case: every group one row over a tile multiple
    assert gm.padded_rows(4 * 9, 4, 8) >= 4 * 16


def test_vmem_model_admits_mixtral_and_states_no_more_than_the_core_has():
    up = gm.pick_block_out(4096, 14336, 2, 2)
    down = gm.pick_block_out(14336, 4096, 2, 1)
    assert (up, down) == (1024, 512)
    for blk, k, n_w in ((up, 4096, 2), (down, 14336, 1)):
        stated = gm.vmem_bytes(
            gm.BLOCK_ROWS, k, blk, 2, n_w) + gm._VMEM_HEADROOM
        assert stated <= 64 * 2**20  # half of a v5e core's 128 MiB
    assert gm.has_tiles(4096, 14336)
    assert not gm.has_tiles(64, 96)  # toy widths: interpreted only
    with pytest.raises(ValueError, match="no tile"):
        gm.grouped_int8_matmul(
            jnp.zeros((128, 64)), jnp.zeros((2, 64, 96), jnp.int8),
            jnp.ones((2, 96)), jnp.asarray([1, 1], jnp.int32))


def _qt(bits=8, stamp="pallas", mesh=None, shape=(8, 4096, 14336)):
    return QuantizedTensor(
        q=None, scale=None, bits=bits, group=0 if bits == 8 else 128,
        shape=shape, dtype=jnp.bfloat16, matmul=stamp, mesh=mesh)


SCAN_ROWS = 64 * 2  # the cell's fused scan: s_pad 64, top-2
BF16 = object()  # an unquantized expert stack


@pytest.mark.parametrize(
    "requested,rows,w1,on_mesh,want",
    [
        # auto: int8 + a Pallas stamp + one chip + many rows -> grouped
        ("auto", 2048 * 2, _qt(), False, "grouped"),
        ("auto", 256 * 2, _qt(), False, "grouped"),
        ("auto", GROUPED_MIN_ROWS + 1, _qt(), False, "grouped"),
        ("auto", 512, _qt(stamp="pallas_interpret", shape=(4, 64, 96)),
         False, "grouped"),
        # decode scan rows stay dense, as every step at the threshold
        ("auto", SCAN_ROWS, _qt(), False, "dense"),
        ("auto", GROUPED_MIN_ROWS, _qt(), False, "dense"),
        # int4, a dequant stamp, a mesh, widths without a tile: as before
        ("auto", 4096, _qt(bits=4), False, "dense"),
        ("auto", 4096, _qt(stamp="dequant"), False, "dense"),
        ("auto", 4096, _qt(), True, "dense"),
        ("auto", 4096, _qt(mesh=object()), False, "dense"),
        ("auto", 4096, _qt(shape=(4, 64, 96)), False, "dense"),
        # unquantized experts: ragged_dot on many rows, dense on few
        ("auto", 4096, BF16, False, "ragged"),
        ("auto", 4096, BF16, True, "ragged"),
        ("auto", 256, BF16, False, "dense"),
        # the variable keeps its three values and their meaning
        ("dense", 4096, _qt(), False, "dense"),
        ("ragged", 4096, BF16, False, "ragged"),
        ("ragged", 4096, _qt(stamp="dequant"), False, "ragged"),
        ("ragged", 4096, _qt(), True, "ragged"),
        ("ragged", SCAN_ROWS, _qt(), False, "grouped"),
    ],
)
def test_selection_rule(requested, rows, w1, on_mesh, want):
    assert select_moe_impl(requested, rows, w1, on_mesh) == want


def _tiny_model(t_dtype=jnp.float32):
    model = MixtralForCausalLM.__new__(MixtralForCausalLM)
    model.num_experts, model.top_k, model.norm_topk = 4, 2, True
    rng = np.random.default_rng(1)
    layer = {"router": jnp.asarray(rng.standard_normal((64, 4)) * 0.5,
                                   t_dtype)}
    for name, shape in (("w1", (4, 64, 96)), ("w3", (4, 64, 96)),
                        ("w2", (4, 96, 64))):
        layer[name] = quantize(
            jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32), 8,
            dtype=t_dtype, matmul="pallas_interpret")
    return model, layer, rng


def test_grouped_mlp_matches_the_dense_oracle_and_skips_padding_rows():
    model, layer, rng = _tiny_model()
    t, t_real = 300, 250
    h = jnp.asarray(rng.standard_normal((t, 64)), jnp.float32)
    live = jnp.arange(t) < t_real
    with mock.patch.dict(os.environ, {"VDT_MOE_IMPL": "auto"}):
        assert model.moe_impl(layer, t) == "grouped"
        assert model.moe_impl(layer, 64) == "dense"
        got = np.asarray(jax.jit(
            lambda x: model._mlp(x, layer, live))(h))
        whole = np.asarray(model._mlp(h, layer))
    want = np.asarray(model._mlp_dense(h, layer))
    assert np.abs(got[:t_real] - want[:t_real]).max() < 1e-4
    assert not got[t_real:].any()  # padding rows join no group
    assert np.abs(whole - want).max() < 1e-4
    # the padding rows cost nothing: they are in no expert's group
    with mock.patch.dict(os.environ, {"VDT_MOE_IMPL": "auto"}):
        gs = model._sorted_dispatch(h, layer, live)[3]
    assert int(gs.sum()) == t_real * 2


def test_grouped_mlp_puts_no_xla_loop_into_a_single_step():
    """The benchmark tells a fused scan from a single step by the
    ``while`` the scan's program holds (``decode_step_ms``,
    ``prefill_step_ms``): nothing of the grouped path outside the
    kernel itself may lower to a loop."""
    model, layer, rng = _tiny_model()
    h = jnp.asarray(rng.standard_normal((300, 64)), jnp.float32)
    live = jnp.arange(300) < 250
    jaxpr = jax.make_jaxpr(
        lambda x: model._mlp_grouped(x, layer, live))(h)

    def primitives(jxp):
        for eqn in jxp.eqns:
            yield eqn.primitive.name
            if eqn.primitive.name == "pallas_call":
                continue  # the kernel's own loop never reaches XLA
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    yield from primitives(inner)

    names = set(primitives(jaxpr.jaxpr))
    assert "pallas_call" in names
    assert not names & {"while", "scan", "cond"}, names


def _serve(model_dir, env, prompts, max_tokens=9):
    """Greedy tokens, the ``runner.dispatch`` spans' attributes in
    order, the worker's count of grouped steps and the rendered
    counter, with a fused scan of 4 after the prefill."""
    tracer = get_tracer()
    tracer.reset()
    with mock.patch.dict(os.environ, env):
        engine = LLMEngine.from_engine_args(EngineArgs(
            model=model_dir, skip_tokenizer_init=True, num_kv_pages=64,
            max_model_len=512, quantization="int8", num_decode_steps=4,
            enable_tracing=True))
        try:
            for i, p in enumerate(prompts):
                engine.add_request(
                    f"r{i}", prompt_token_ids=p,
                    sampling_params=SamplingParams(
                        temperature=0.0, max_tokens=max_tokens,
                        ignore_eos=True))
            done = {}
            while engine.has_unfinished_requests():
                for out in engine.step():
                    if out.finished:
                        done[out.request_id] = out.outputs[0].token_ids
            engine.refresh_device_telemetry()
            text = engine.metrics.render().decode()
            (line,) = [ln for ln in text.splitlines()
                       if ln.startswith("vllm:moe_grouped_steps_total")]
            dispatches = [
                s["attributes"] for trace in tracer.snapshot()
                for s in trace["spans"] if s["name"] == "runner.dispatch"]
            dispatches.sort(key=lambda a: a["step_id"])
            return {
                "tokens": [done[f"r{i}"] for i in range(len(prompts))],
                "dispatches": dispatches,
                "grouped_steps": sum(
                    engine.executor.worker.runner.telemetry
                    .counters["moe_grouped_steps"].values()),
                "counter": float(line.rsplit(" ", 1)[1]),
            }
        finally:
            engine.shutdown()
            tracer.configure(False)
            tracer.reset()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A 200-token prompt makes one step of 256 padded rows (52 of them
    padding): 512 routed rows, over the threshold; the fused scans and
    the last single steps after it hold 8 rows."""
    model_dir = make_tiny_mixtral(str(tmp_path_factory.mktemp("grouped")))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(3, 120, 200).tolist(), [7, 2, 88, 14]]
    env = {"VDT_USE_PALLAS": "pallas_interpret"}
    return {impl: _serve(model_dir, {**env, "VDT_MOE_IMPL": impl}, prompts)
            for impl in ("dense", "auto")}


def test_int8_mixtral_prefill_runs_grouped_and_emits_the_dense_tokens(
        served):
    assert served["auto"]["tokens"] == served["dense"]["tokens"]
    assert all(len(t) == 9 for t in served["auto"]["tokens"])


def test_dispatch_span_says_what_the_programs_experts_were_traced_with(
        served):
    auto = served["auto"]["dispatches"]
    assert all("moe" in a for a in auto)
    prefill = auto[0]
    assert prefill["program"].startswith("vdt_step_t256_")
    assert prefill["moe"] == "grouped"
    scans = [a for a in auto if a["program"].startswith("vdt_decode_")]
    assert scans and all(a["moe"] == "dense" for a in scans)
    # every other single step is a few decode rows: dense
    assert [a["moe"] for a in auto[1:]] == ["dense"] * (len(auto) - 1)
    assert {a["moe"] for a in served["dense"]["dispatches"]} == {"dense"}


def test_grouped_steps_counter_rises_once_a_grouped_step_and_never_in_a_scan(
        served):
    auto, dense = served["auto"], served["dense"]
    grouped = [a for a in auto["dispatches"] if a["moe"] == "grouped"]
    assert len(grouped) == 1
    assert auto["grouped_steps"] == auto["counter"] == 1
    assert dense["grouped_steps"] == dense["counter"] == 0


def test_single_step_dispatch_span_counts_the_query_tiles(served):
    """PR 30: a single step's span says how many query tiles the
    attention kernel walked and how many rows they compute; a fused
    scan's span does not; no program gained a key."""
    import re

    auto = served["auto"]["dispatches"]
    singles = [a for a in auto if a["program"].startswith("vdt_step_")]
    scans = [a for a in auto if a["program"].startswith("vdt_decode_")]
    assert singles and scans
    assert all("q_tiles" not in a and "q_rows" not in a for a in scans)
    for a in singles:
        assert re.fullmatch(r"vdt_step_t\d+_q\d+_s\d+_p\d+", a["program"])
        assert 1 <= a["q_tiles"] and a["tokens"] <= a["q_rows"]
    prefill = singles[0]  # prompts of 200 and 4 tokens, q bucket 256
    assert prefill["tokens"] == 204 and prefill["seqs"] == 2
    # at these toy widths a tile holds the whole 256-token bucket, so
    # two chunks fill the dense grid's rows and no more
    assert prefill["q_tiles"] == 2 and prefill["q_rows"] == 2 * 256
    # the decode-only steps after the scans: one tile a sequence
    assert all(a["q_tiles"] == a["seqs"] for a in singles
               if a["tokens"] == a["seqs"])


def test_a_dense_model_has_no_moe_on_its_dispatch_span(tmp_path):
    from tests.utils import make_tiny_llama

    tracer = get_tracer()
    tracer.reset()
    engine = LLMEngine.from_engine_args(EngineArgs(
        model=make_tiny_llama(str(tmp_path / "m")),
        skip_tokenizer_init=True, num_kv_pages=64, max_model_len=128,
        enable_tracing=True))
    try:
        engine.add_request(
            "r0", prompt_token_ids=[1, 5, 9],
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=3, ignore_eos=True))
        while engine.has_unfinished_requests():
            engine.step()
        spans = [s for trace in tracer.snapshot() for s in trace["spans"]
                 if s["name"] == "runner.dispatch"]
        assert spans and all("moe" not in s["attributes"] for s in spans)
        # nor query tiles: the reference backend has no kernel
        assert all("q_tiles" not in s["attributes"] for s in spans)
        assert not engine.executor.worker.runner.telemetry.counters[
            "moe_grouped_steps"]
    finally:
        engine.shutdown()
        tracer.configure(False)
        tracer.reset()


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


@pytest.mark.parametrize("rows,in_dim,out_dim,fused", [
    (4096, 4096, 14336, True),  # t 2048: the fused up projection
    (4096, 14336, 4096, False),  # and the down projection
    (512, 4096, 14336, True),  # t 256: the smallest grouped step
])
def test_kernel_compiles_for_the_v5e_at_mixtral_widths(
        rows, in_dim, out_dim, fused, one_chip, no_compile_cache):
    e = 8
    mp = gm.padded_rows(rows, e, gm.BLOCK_ROWS)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(xs, q, s, q_up, s_up, gs):
        up = dict(q_up=q_up, scale_up=s_up) if fused else {}
        return gm.grouped_int8_matmul(xs, q, s, gs, **up)

    weight = shaped((e, in_dim, out_dim), jnp.int8)
    scale = shaped((e, out_dim), jnp.float32)
    compiled = jax.jit(call).lower(
        shaped((mp, in_dim), jnp.bfloat16), weight, scale, weight, scale,
        shaped((e,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vdt_grouped_int8_matmul" in text


@pytest.mark.parametrize("t,max_q,s,side,quant", [
    (2048, 2048, 32, False, False),  # the cell's largest mixed step
    (256, 128, 32, False, False),
    (64, 1, 64, True, False),  # the fused scan's call
    (512, 4, 64, False, False),  # a verify step: one call, dense list
    (2048, 1024, 32, False, True),  # the int8 pool's scale blocks
])
def test_attention_compiles_for_the_v5e_at_mixtral_widths(
        t, max_q, s, side, quant, one_chip, no_compile_cache):
    """PR 30's tile-list kernel, both geometries, at Hq 32 / Hkv 8 /
    D 128 over a 256-page table: Mosaic takes it, a chunk step is two
    kernel calls (chunk tiles, single tokens), everything else one, and
    no XLA loop comes with the lists."""
    from vllm_distributed_tpu.ops.attention import AttentionMetadata
    from vllm_distributed_tpu.ops.pallas.paged_attention import (
        paged_attention,
    )

    hq, hkv, d, page, pages, pool = 32, 8, 128, 16, 256, 8192

    def shaped(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = AttentionMetadata(
        q_seq_ids=shaped((t,)), q_positions=shaped((t,)),
        slot_mapping=shaped((t,)), block_tables=shaped((s, pages)),
        seq_lens=shaped((s,)), logits_indices=shaped((s,)),
        chunk_starts=shaped((s,)))
    kv = shaped((2, pool, page, hkv * d), jnp.int8 if quant else jnp.bfloat16)
    if quant:
        kv = (kv, shaped((2, pool, page, hkv), jnp.float32))
    args = [shaped((t, hq, d), jnp.bfloat16), kv, meta]
    if side:
        args += [shaped((s, 2, 8, hkv * d), jnp.bfloat16), shaped((1,))]

    def call(q, kv, meta, *staged):
        kw = dict(side_kv=staged[0], side_len=staged[1]) if staged else {}
        return paged_attention(
            q, kv, meta, scale=0.088, num_kv_heads=hkv, max_q=max_q, **kw)

    compiled = jax.jit(call).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (2 if max_q >= 16 else 1)
    assert " while(" not in text and " conditional(" not in text
    # the staging is packed: at t 2048 the dense grid alone was 537 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 160e6


# ---- the unquantized kernel (PR 38) ----------------------------------------
@pytest.mark.parametrize("sizes,fused", [
    ([5, 0, 300, 1], True), ([5, 0, 300, 1], False),
    ([0, 0, 0, 7], True), ([128, 128, 128, 128], True)])
def test_unquantized_kernel_matches_a_loop_over_the_groups(sizes, fused):
    """``grouped_matmul`` (interpreted): every group's rows times its
    own expert's weights, the gated epilogue with a second weight, in
    ``grouped_int8_matmul``'s layout; empty groups and the skipped tail
    change nothing."""
    e, k, n = len(sizes), 128, 256
    rng = np.random.default_rng(0)
    gs = jnp.asarray(sizes, jnp.int32)
    mp = gm.padded_rows(sum(sizes), e, gm.BLOCK_ROWS)
    starts, _, _ = gm.group_layout(gs, mp, gm.BLOCK_ROWS)
    xs = jnp.asarray(rng.standard_normal((mp, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, k, n)) * 0.1, jnp.float32)
    w_up = jnp.asarray(rng.standard_normal((e, k, n)) * 0.1, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(gm.grouped_matmul(
            xs, w, gs, w_up=w_up if fused else None, interpret=True))
        for g, (lo, size) in enumerate(zip(np.asarray(starts), sizes)):
            if not size:
                continue
            rows = xs[lo: lo + size]
            want = rows @ w[g]
            if fused:
                want = jax.nn.silu(want) * (rows @ w_up[g])
            assert np.abs(got[lo: lo + size] - np.asarray(want)).max() < 1e-4


@pytest.mark.parametrize("rows,in_dim,out_dim,fused", [
    (8192, 2048, 1792, True),  # t 2048, top-4: the fused up projection
    (8192, 1792, 2048, False),  # and the down projection
    (512, 2048, 1792, True),  # t 128: the smallest grouped step
])
def test_unquantized_kernel_compiles_for_the_v5e_at_lfm2_widths(
        rows, in_dim, out_dim, fused, one_chip, no_compile_cache):
    """32 bfloat16 experts of 1,792: the whole output width is one
    tile, so ``xs`` and an expert's weights are read once."""
    e = 32
    mp = gm.padded_rows(rows, e, gm.BLOCK_ROWS)
    assert gm.pick_unquantized_block_out(
        in_dim, out_dim, 2, 2 if fused else 1) == out_dim
    assert gm.has_unquantized_tiles(2048, 1792)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(xs, w, w_up, gs):
        return gm.grouped_matmul(xs, w, gs, w_up=w_up if fused else None)

    weight = shaped((e, in_dim, out_dim), jnp.bfloat16)
    compiled = jax.jit(call).lower(
        shaped((mp, in_dim), jnp.bfloat16), weight, weight,
        shaped((e,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "vdt_grouped_matmul" in text
    assert "vdt_grouped_int8_matmul" not in text
