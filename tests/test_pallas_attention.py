"""Pallas paged-attention kernel vs the pure-JAX reference oracle, in
interpreter mode on CPU (SURVEY.md §4 item 2: kernel tests over head
dims, page sizes, GQA ratios, masks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    merge_kv_pages,
    paged_attention_reference,
)
from vllm_distributed_tpu.ops.pallas.paged_attention import paged_attention


def build_case(
    rng,
    *,
    seq_specs,  # list of (ctx_len, chunk_len): context incl. chunk
    s_pad=8,
    t_pad=None,
    hq=4,
    hkv=2,
    d=64,
    page_size=16,
    num_pages=64,
    dtype=jnp.float32,
):
    """Random paged KV state + flat query batch covering mixed
    prefill/decode."""
    t_real = sum(c for _, c in seq_specs)
    t_pad = t_pad or max(16, 1 << (t_real - 1).bit_length())
    max_pages_needed = max(
        -(-ctx // page_size) for ctx, _ in seq_specs
    )
    pages_pad = max(8, 1 << (max_pages_needed - 1).bit_length())

    k_pages = jnp.asarray(
        rng.standard_normal((num_pages, page_size, hkv, d)), dtype
    )
    v_pages = jnp.asarray(
        rng.standard_normal((num_pages, page_size, hkv, d)), dtype
    )
    kv_pages = merge_kv_pages(k_pages, v_pages)
    q = jnp.asarray(rng.standard_normal((t_pad, hq, d)), dtype)

    seq_ids = np.full(t_pad, s_pad, np.int32)
    positions = np.zeros(t_pad, np.int32)
    block_tables = np.zeros((s_pad, pages_pad), np.int32)
    seq_lens = np.zeros(s_pad, np.int32)
    chunk_starts = np.zeros(s_pad, np.int32)
    logits_idx = np.zeros(s_pad, np.int32)

    next_page = 1  # page 0 reserved
    cursor = 0
    for s, (ctx, chunk) in enumerate(seq_specs):
        n_pages = -(-ctx // page_size)
        pages = list(range(next_page, next_page + n_pages))
        next_page += n_pages
        block_tables[s, :n_pages] = pages
        seq_lens[s] = ctx
        chunk_starts[s] = ctx - chunk
        positions[cursor : cursor + chunk] = np.arange(ctx - chunk, ctx)
        seq_ids[cursor : cursor + chunk] = s
        logits_idx[s] = cursor + chunk - 1
        cursor += chunk

    meta = AttentionMetadata(
        q_seq_ids=jnp.asarray(seq_ids),
        q_positions=jnp.asarray(positions),
        slot_mapping=jnp.zeros(t_pad, jnp.int32),
        block_tables=jnp.asarray(block_tables),
        seq_lens=jnp.asarray(seq_lens),
        logits_indices=jnp.asarray(logits_idx),
        chunk_starts=jnp.asarray(chunk_starts),
    )
    max_q = max(c for _, c in seq_specs)
    max_q = 1 << (max_q - 1).bit_length() if max_q > 1 else 1
    return q, kv_pages, meta, max_q, cursor, hkv


def _compare(case, scale=0.125, atol=2e-5):
    q, kv_pages, meta, max_q, t_real, hkv = case
    ref = paged_attention_reference(
        q, kv_pages, meta, scale=scale, num_kv_heads=hkv
    )
    got = paged_attention(
        q, kv_pages, meta, scale=scale, num_kv_heads=hkv,
        max_q=max_q, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got[:t_real]),
        np.asarray(ref[:t_real]),
        rtol=1e-4,
        atol=atol,
    )
    # padding tokens read no tile (one past the live count is never
    # written): their rows are zeros, whatever that memory holds
    assert not np.asarray(got[t_real:]).any()


def test_pure_decode_batch():
    rng = np.random.default_rng(0)
    _compare(build_case(rng, seq_specs=[(17, 1), (33, 1), (160, 1)]))


def test_pure_prefill():
    rng = np.random.default_rng(1)
    _compare(build_case(rng, seq_specs=[(24, 24), (7, 7)]))


def test_chunked_prefill_continuation():
    # Context 40 of which the last 8 are this step's chunk.
    rng = np.random.default_rng(2)
    _compare(build_case(rng, seq_specs=[(40, 8), (64, 16)]))


def test_mixed_prefill_and_decode():
    rng = np.random.default_rng(3)
    _compare(
        build_case(rng, seq_specs=[(50, 1), (20, 20), (33, 1), (48, 12)])
    )


def test_gqa_ratios():
    rng = np.random.default_rng(4)
    _compare(
        build_case(rng, seq_specs=[(30, 1), (12, 12)], hq=8, hkv=2)
    )


def test_mha_group_1():
    rng = np.random.default_rng(5)
    _compare(build_case(rng, seq_specs=[(21, 1), (9, 9)], hq=4, hkv=4))


def test_page_size_32_head_dim_128():
    rng = np.random.default_rng(6)
    _compare(
        build_case(
            rng,
            seq_specs=[(70, 6), (100, 1)],
            page_size=32,
            d=128,
            num_pages=32,
        )
    )


def test_single_token_context():
    rng = np.random.default_rng(7)
    _compare(build_case(rng, seq_specs=[(1, 1)]))


def test_long_context_multiblock():
    # Forces multiple kv blocks (ctx 600 > 256-token block).
    rng = np.random.default_rng(8)
    _compare(
        build_case(
            rng, seq_specs=[(600, 1), (300, 4)], num_pages=80
        )
    )


def test_bfloat16_cache():
    rng = np.random.default_rng(9)
    q, kv, meta, max_q, t_real, hkv = build_case(
        rng, seq_specs=[(40, 4), (21, 1)], dtype=jnp.bfloat16
    )
    ref = paged_attention_reference(
        q, kv, meta, scale=0.125, num_kv_heads=hkv
    )
    got = paged_attention(
        q, kv, meta, scale=0.125, num_kv_heads=hkv,
        max_q=max_q, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got[:t_real], np.float32),
        np.asarray(ref[:t_real], np.float32),
        rtol=5e-2,
        atol=5e-2,
    )


def test_engine_e2e_with_pallas_backend(tmp_path):
    """Whole engine on the interpret-mode kernel must equal the
    reference backend token-for-token."""
    from tests.utils import make_tiny_llama
    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    from vllm_distributed_tpu.sampling_params import SamplingParams

    model_dir = make_tiny_llama(str(tmp_path / "m"))

    def run(backend):
        config = EngineArgs(
            model=model_dir,
            skip_tokenizer_init=True,
            num_kv_pages=64,
            max_model_len=128,
            max_num_seqs=8,
            max_num_batched_tokens=32,  # force chunking
        ).create_engine_config()
        engine = LLMEngine(config)
        engine.executor.worker.runner._attn_fn = _backend(backend)
        prompts = [list(range(1, 40)), [5, 6, 7], list(range(50, 70))]
        for i, p in enumerate(prompts):
            engine.add_request(
                f"r{i}",
                prompt_token_ids=p,
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=5, ignore_eos=True
                ),
            )
        done = {}
        while engine.has_unfinished_requests():
            for out in engine.step():
                if out.finished:
                    done[out.request_id] = out.outputs[0].token_ids
        return [done[f"r{i}"] for i in range(len(prompts))]

    def _backend(name):
        if name == "pallas":
            from vllm_distributed_tpu.ops.pallas.paged_attention import (
                paged_attention_cpu,
            )

            return paged_attention_cpu
        return paged_attention_reference

    assert run("pallas") == run("reference")


def test_cross_seq_prefetch_multiblock_decode():
    """Decode with contexts spanning MULTIPLE kv blocks (ctx > 1024
    tokens at the default 512 KiB KV buffer with hkv=2/d=64/f32), which
    flips on the cross-sequence block-0 prefetch path — including an
    empty sequence between live ones and uneven final blocks."""
    rng = np.random.default_rng(9)
    _compare(
        build_case(
            rng,
            seq_specs=[(1100, 1), (2047, 1), (1025, 1)],
            num_pages=300,
        )
    )


def test_cross_seq_prefetch_with_empty_seq():
    rng = np.random.default_rng(10)
    # A zero-length sequence in the middle: the prefetch chain must skip
    # it without unbalancing DMA starts/waits.
    _compare(
        build_case(
            rng,
            seq_specs=[(1500, 1), (0, 0), (1100, 1)],
            num_pages=300,
        )
    )


def test_staged_side_buffer_decode():
    """Kernel + reference with side_kv/side_len must equal the reference
    over a pool where the staged rows were already flushed."""
    rng = np.random.default_rng(11)
    hq, hkv, d, page_size = 8, 2, 64, 16
    k_steps, step_i = 16, 9  # micro-step 9 of a 16-step dispatch
    bases = [37, 160, 0, 5]  # pool-resident lengths; row 2 = padding
    s_pad = len(bases)
    num_pages = 64

    from vllm_distributed_tpu.ops.attention import (
        kv_pool_shape,
        write_kv_pages,
    )

    kv = jnp.asarray(
        rng.standard_normal(kv_pool_shape(num_pages, page_size, hkv, d)),
        jnp.float32,
    )
    side = jnp.asarray(
        rng.standard_normal((s_pad, 2, k_steps, hkv * d)), jnp.float32
    )
    max_pages = 16
    bt = np.zeros((s_pad, max_pages), np.int32)
    nxt = 1
    for i, b in enumerate(bases):
        if b <= 0:
            continue
        need = -(-(b + k_steps) // page_size)
        bt[i, :need] = np.arange(nxt, nxt + need)
        nxt += need

    # Queries: one decode token per live row at position base + step_i.
    pos = np.asarray(
        [b + step_i if b > 0 else 0 for b in bases], np.int32
    )
    sid = np.asarray(
        [i if b > 0 else s_pad for i, b in enumerate(bases)], np.int32
    )
    q = jnp.asarray(rng.standard_normal((s_pad, hq, d)), jnp.float32)
    meta_staged = AttentionMetadata(
        q_seq_ids=jnp.asarray(sid),
        q_positions=jnp.asarray(pos),
        slot_mapping=jnp.zeros(s_pad, jnp.int32),
        block_tables=jnp.asarray(bt),
        seq_lens=jnp.asarray(np.asarray(bases, np.int32)),  # POOL lens
        logits_indices=jnp.arange(s_pad, dtype=jnp.int32),
        chunk_starts=jnp.asarray(pos),
    )
    side_len = jnp.asarray([step_i + 1], jnp.int32)

    # Oracle: flush side rows 0..step_i into a copy of the pool and run
    # the plain reference with full sequence lengths.
    flushed = kv
    for i, b in enumerate(bases):
        if b <= 0:
            continue
        for j in range(step_i + 1):
            p = b + j
            slot = bt[i, p // page_size] * page_size + p % page_size
            flushed = write_kv_pages(
                flushed,
                side[i, 0, j].reshape(1, hkv, d),
                side[i, 1, j].reshape(1, hkv, d),
                jnp.asarray([slot], jnp.int32),
            )
    meta_full = AttentionMetadata(
        q_seq_ids=meta_staged.q_seq_ids,
        q_positions=meta_staged.q_positions,
        slot_mapping=meta_staged.slot_mapping,
        block_tables=meta_staged.block_tables,
        seq_lens=jnp.asarray(
            np.asarray(
                [b + step_i + 1 if b > 0 else 0 for b in bases], np.int32
            )
        ),
        logits_indices=meta_staged.logits_indices,
        chunk_starts=meta_staged.chunk_starts,
    )
    want = paged_attention_reference(
        q, flushed, meta_full, scale=0.125, num_kv_heads=hkv
    )

    got_ref = paged_attention_reference(
        q, kv, meta_staged, scale=0.125, num_kv_heads=hkv,
        side_kv=side, side_len=side_len,
    )
    got_pl = paged_attention(
        q, kv, meta_staged, scale=0.125, num_kv_heads=hkv,
        max_q=1, side_kv=side, side_len=side_len, interpret=True,
    )
    live = np.asarray([i for i, b in enumerate(bases) if b > 0])
    np.testing.assert_allclose(
        np.asarray(got_ref)[live], np.asarray(want)[live],
        rtol=1e-4, atol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(got_pl)[live], np.asarray(want)[live],
        rtol=1e-4, atol=2e-5,
    )


# ---- the ragged list of query tiles (PR 30) ------------------------------
from vllm_distributed_tpu.ops.pallas import paged_attention as pa  # noqa: E402

# At the test widths (hq 4, hkv 2, d 64, f32) the budgets below give
# query tiles of 32 tokens and kv blocks of 64, so a chunk spans several
# tiles, a context several blocks, and single tokens leave the chunk
# tiles for the decode geometry (32 >= _SPLIT_SINGLES_MIN_MQ).
_SMALL_TILES = dict(_STATE_BYTES=6144 * 32, _KV_BUF_BYTES=64 * 1024)


def _decodes(n, rng):
    return [(int(c), 1) for c in rng.integers(3, 300, size=n)]


_RAGGED_CASES = {
    # 31 decode rows + one chunk at s 32
    "decodes_and_chunk_q128": dict(
        specs=lambda rng: _decodes(31, rng) + [(128, 128)], s_pad=32),
    "decodes_and_chunk_q1024": dict(
        specs=lambda rng: _decodes(31, rng) + [(1024, 1024)], s_pad=32,
        tiles=dict(_STATE_BYTES=6144 * 128, _KV_BUF_BYTES=256 * 1024)),
    # unequal chunks; one continues a prompt and ends mid-tile
    "unequal_chunks_one_continuation": dict(
        specs=lambda rng: [(200, 70), (45, 45), (33, 1), (90, 90)]),
    "empty_sequences_between_live_ones": dict(
        specs=lambda rng: [(50, 1), (0, 0), (40, 40), (0, 0), (33, 1),
                           (70, 3)]),
    "token_padding_rows": dict(
        specs=lambda rng: [(37, 5), (60, 1), (35, 35)], t_pad=128),
    "int8_pool_in_a_mixed_step": dict(
        specs=lambda rng: [(50, 1), (100, 40), (33, 1), (64, 64)],
        quant=True),
    # equal q lengths: the list is the dense grid
    "verify_shape_equal_windows": dict(
        specs=lambda rng: [(c, 4) for c in (9, 40, 77, 130, 4, 64)]),
    "equal_long_chunks": dict(
        specs=lambda rng: [(64, 64), (164, 64), (70, 64)]),
    "prefill_only_no_singles": dict(
        specs=lambda rng: [(100, 100), (31, 31)]),
    "gqa_4_mixed": dict(
        specs=lambda rng: [(50, 1), (75, 75), (20, 1)], hq=8, hkv=2),
}


@pytest.mark.parametrize("name", sorted(_RAGGED_CASES))
def test_ragged_query_tiles_match_reference(name, monkeypatch):
    case = _RAGGED_CASES[name]
    for key, value in case.get("tiles", _SMALL_TILES).items():
        monkeypatch.setattr(pa, key, value)
    rng = np.random.default_rng(sorted(_RAGGED_CASES).index(name))
    specs = case["specs"](rng)
    kw = {k: case[k] for k in ("s_pad", "t_pad", "hq", "hkv") if k in case}
    ctx = sum(-(-c // 16) for c, _ in specs)
    q, kv, meta, max_q, t_real, hkv = build_case(
        rng, seq_specs=specs, num_pages=ctx + 2, **kw
    )
    if case.get("quant"):
        from tests.test_kv_int8 import _quantize_pool

        kv = _quantize_pool(kv, hkv)
    _compare((q, kv, meta, max_q, t_real, hkv))


def _walked_blocks(q_lens, ctx_lens, *, s_pad, t_pad, max_q, hq, hkv, d,
                   blk):
    """(live (tile, kv block) pairs the lists make the kernel walk,
    tiles in use, static tiles) through the wrapper's own functions."""
    hd = hkv * d
    n = len(q_lens)
    q_len = np.zeros(s_pad, np.int32)
    q_len[:n] = q_lens
    seq_lens = np.zeros(s_pad, np.int32)
    seq_lens[:n] = ctx_lens
    chunk_starts = seq_lens - q_len
    pairs = tiles = static = 0
    geo_1 = pa._tile_geometry(1, hq, hkv, d, hd)
    geo_n = pa._tile_geometry(max_q, hq, hkv, d, hd)
    split = max_q > 1 and pa._splits_singles(geo_n.mq_blk)
    lists = []
    if max_q == 1 or split:
        per = (q_len == 1) if max_q > 1 else (q_len > 0)
        lists.append((geo_1.mq_blk, per.astype(np.int32), s_pad))
    if max_q > 1:
        mq = geo_n.mq_blk
        per = np.where(q_len > (1 if split else 0), -(-q_len // mq), 0)
        lists.append(
            (mq, per, pa.num_query_tiles(t_pad, s_pad, mq, geo_n.maxq)))
    for mq, per, num_tiles in lists:
        assert per.sum() <= num_tiles, (per.sum(), num_tiles)
        (seq, q0, n_live), first = pa.tile_list(
            jnp.asarray(per, jnp.int32), num_tiles, mq)
        assert int(n_live[0]) == per.sum()
        np.testing.assert_array_equal(
            np.asarray(first), np.cumsum(per) - per)
        nb = pa.tile_kv_blocks(
            seq, q0, n_live, jnp.asarray(seq_lens),
            jnp.asarray(chunk_starts), mq, blk)
        used = int(n_live[0])
        assert not np.asarray(nb)[used:].any()  # skipped past the count
        # and they repeat the last live tile: no block index changes
        assert used == 0 or (np.asarray(seq)[used:] == int(seq[used - 1])
                             ).all()
        pairs += int(nb.sum())
        tiles += int(per.sum())
        static += num_tiles
    return pairs, tiles, static


def test_tile_list_walks_the_analytic_number_of_blocks():
    """Live (tile, kv block) pairs = per sequence, per tile of its
    chunk, the kv blocks up to the tile's last row (causal), cut at the
    sequence's length; a single token walks its whole context once."""
    hq, hkv, d, blk = 32, 8, 128, 256
    mq = pa._tile_geometry(2048, hq, hkv, d, hkv * d).mq_blk
    assert mq == 128
    q_lens = [1] * 31 + [700]
    ctx_lens = [660] * 31 + [1724]  # the chunk continues a prompt
    pairs, tiles, static = _walked_blocks(
        q_lens, ctx_lens, s_pad=32, t_pad=1024, max_q=1024, hq=hq, hkv=hkv,
        d=d, blk=blk)
    want = 31 * -(-660 // blk)
    for j in range(-(-700 // mq)):
        want += -(-min(1724, 1024 + (j + 1) * mq) // blk)
    assert pairs == want
    assert tiles == 31 + 6 and static == 32 + (1024 // mq + 32)
    assert pa.query_tile_counts(
        q_lens, max_q=1024, num_q_heads=hq, num_kv_heads=hkv, head_dim=d
    ) == (37, 31 * 1 + 6 * 128)
    # the dense [sequences x longest chunk] grid walked every q block of
    # every sequence: 8 q blocks each
    dense = 31 * 8 * -(-660 // blk) + sum(
        -(-min(1724, 1024 + (j + 1) * mq) // blk) for j in range(8))
    assert dense > 5 * pairs
    # the scan's call: one tile a sequence, the grid it walked before
    pairs, tiles, static = _walked_blocks(
        [1] * 31, [660] * 31, s_pad=64, t_pad=64, max_q=1, hq=hq, hkv=hkv,
        d=d, blk=blk)
    assert (pairs, tiles, static) == (31 * 3, 31, 64)


def test_tile_list_never_overflows_at_the_lattice_corners():
    """The list's static length holds the most tiles any step of a
    bucket can make, at Mixtral's widths, for every bucket the cell's 32
    clients reach (``benchmark/lattice.py``)."""
    import json
    import os

    from benchmark import lattice

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    traffic = json.load(open(os.path.join(
        here, "benchmark", "traffic", "chat-closed-32.json")))
    sweep = traffic["warmup"]["sweep"]
    hq, hkv, d = 32, 8, 128
    buckets = lattice.buckets(32, sweep)
    assert len(buckets) == 34
    for t, q in buckets:
        geo = pa._tile_geometry(q, hq, hkv, d, hkv * d)
        mq, maxq = geo.mq_blk, geo.maxq
        static = pa.num_query_tiles(t, 32, mq, maxq)
        assert static <= 32 * (maxq // mq)  # never more than dense
        # the most tiles: as many chunks as fit, each one token past a
        # tile's edge (or two tokens, the shortest chunk there is)
        for chunk in {2, mq + 1, q // 2 + 1, q}:
            chunk = min(max(chunk, 2), q)
            n = min(32, t // chunk)
            if n == 0:
                continue
            q_lens = [chunk] * n
            rest = t - chunk * n
            if n < 32 and rest >= 2:
                q_lens.append(min(rest, q))
            tiles, _ = pa.query_tile_counts(
                q_lens, max_q=q, num_q_heads=hq, num_kv_heads=hkv,
                head_dim=d)
            assert tiles <= static, (t, q, chunk, tiles, static)


def _primitives(jxp):
    for eqn in jxp.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue  # the kernel's own loops never reach XLA
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield from _primitives(inner)


def test_single_step_glue_has_no_xla_loop_and_no_dense_query_grid(
        monkeypatch):
    """Twin of test_grouped_matmul's loop-free test: the benchmark tells
    a fused scan from a single step by the ``while`` the scan's program
    holds, so the tile lists are counted by comparison and a cumulative
    sum.  And nothing of a mixed step's staging has the dense grid's
    s_pad x max_q_pad query rows."""
    for key, value in _SMALL_TILES.items():
        monkeypatch.setattr(pa, key, value)
    rng = np.random.default_rng(0)
    specs = _decodes(31, rng) + [(128, 128)]
    q, kv, meta, max_q, _, hkv = build_case(
        rng, seq_specs=specs, s_pad=32, num_pages=420)
    jaxpr = jax.make_jaxpr(
        lambda q_, kv_, m_: paged_attention(
            q_, kv_, m_, scale=0.125, num_kv_heads=hkv, max_q=max_q,
            interpret=True))(q, kv, meta)
    eqns = list(_primitives(jaxpr.jaxpr))
    names = {e.primitive.name for e in eqns}
    assert sum(e.primitive.name == "pallas_call" for e in eqns) == 2
    assert not names & {"while", "scan", "cond"}, names
    t, hq, d = q.shape
    dense_rows = 32 * max_q
    for e in eqns:
        for v in e.outvars:
            shape = getattr(v.aval, "shape", ())
            if len(shape) >= 2 and shape[-1] % d == 0:
                rows = int(np.prod(shape)) // (hq * d)
                assert rows < dense_rows, (e.primitive.name, shape)


def test_attention_entry_has_no_new_key():
    """What makes a program: ``max_q`` is still the one static the
    runner passes, and the tile lists come from the step's metadata."""
    import inspect

    params = inspect.signature(paged_attention).parameters
    assert list(params) == [
        "q", "kv_pages", "metadata", "scale", "soft_cap", "num_kv_heads",
        "max_q", "side_kv", "side_len", "interpret"]
