"""``vdt_mla_attention`` (ops/pallas/mla_attention.py): the interpreted
kernel against plain ``jnp`` over ragged chunks, cached prefixes and
decode rows with the staged side buffer; the one-plane flush; the
interleaved RoPE against its pairwise definition; the materialised
form's flat kernel (``vdt_mla_chunk_attention``) over ragged fresh
chunks, and a step of all three kinds of row against the reference;
and the kernels compiled at the LongCat widths for a described v5e
chip."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vllm_distributed_tpu.models.common import (
    apply_rope_interleaved,
    rope_frequencies,
)
from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    bind_max_q,
    mla_attention_reference,
    mla_sublayer_attention,
    write_kv_pages,
)
from vllm_distributed_tpu.ops.pallas import mla_attention as mla
from vllm_distributed_tpu.ops.pallas import mla_chunk_attention as chunk
from vllm_distributed_tpu.ops.pallas.kv_flush import kv_flush, kv_flush_cpu

PAGE = 16


def _plain(q, rows, q_pos, scale, vw):
    """One sequence, no pages: q [n, H, W] at positions q_pos over rows
    [ctx, W] (row j at position j)."""
    s = jnp.einsum("nhw,cw->nhc", q, rows) * scale
    mask = jnp.arange(rows.shape[0])[None, :] <= q_pos[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, -jnp.inf), axis=-1)
    return jnp.einsum("nhc,cv->nhv", p, rows[:, :vw])


def _batch(rng, seqs, h, w, wp, t_pad, s_pad, pages_pad, num_pages=64):
    """seqs: [(cached tokens, new tokens)].  Returns q, pool, metadata
    and the dense rows of every sequence."""
    pool = jnp.zeros((1, num_pages, PAGE, wp), jnp.float32)
    q = np.zeros((t_pad, h, w), np.float32)
    ids = np.full(t_pad, s_pad, np.int32)
    pos = np.zeros(t_pad, np.int32)
    bt = np.zeros((s_pad, pages_pad), np.int32)
    lens = np.zeros(s_pad, np.int32)
    starts = np.zeros(s_pad, np.int32)
    dense, cursor, page = [], 0, 1
    for s, (cached, new) in enumerate(seqs):
        total = cached + new
        if not total:  # an empty slot between live sequences
            dense.append((np.zeros((0, w), np.float32), slice(cursor, cursor)))
            continue
        n_pages = -(-total // PAGE)
        bt[s, :n_pages] = np.arange(page, page + n_pages)
        rows = rng.standard_normal((total, w)).astype(np.float32)
        full = np.zeros((total, 1, wp), np.float32)
        full[:, 0, :w] = rows
        slots = bt[s, np.arange(total) // PAGE] * PAGE + np.arange(total) % PAGE
        pool = write_kv_pages(pool, jnp.asarray(full), None, jnp.asarray(slots))
        page += n_pages
        q[cursor:cursor + new] = rng.standard_normal((new, h, w))
        ids[cursor:cursor + new] = s
        pos[cursor:cursor + new] = np.arange(cached, total)
        lens[s], starts[s] = total, cached
        dense.append((rows, slice(cursor, cursor + new)))
        cursor += new
    meta = AttentionMetadata(
        q_seq_ids=jnp.asarray(ids), q_positions=jnp.asarray(pos),
        slot_mapping=jnp.zeros(t_pad, jnp.int32),
        block_tables=jnp.asarray(bt), seq_lens=jnp.asarray(lens),
        logits_indices=jnp.zeros(s_pad, jnp.int32),
        chunk_starts=jnp.asarray(starts))
    return jnp.asarray(q), pool, meta, dense


@pytest.mark.parametrize("seqs,max_q", [
    # ragged chunks, two with a cached prefix, single tokens between
    ([(0, 40), (50, 1), (33, 17), (0, 1), (90, 1), (16, 3)], 64),
    ([(0, 5), (7, 2)], 8),  # tiles too narrow to split the singles off
    ([(35, 1), (0, 1), (64, 1), (17, 1)], 1),  # a decode-only step
])
def test_kernel_matches_plain_attention(seqs, max_q):
    rng = np.random.default_rng(0)
    h, w, wp, vw = 4, 24, 128, 16
    q, pool, meta, dense = _batch(rng, seqs, h, w, wp, 64, 8, 8)
    scale = 0.3
    got = np.asarray(mla.mla_attention_cpu(
        q, pool, meta, scale=scale, value_width=vw, max_q=max_q))
    ref = np.asarray(mla_attention_reference(
        q, pool, meta, scale=scale, value_width=vw))
    for rows, sl in dense:
        n = sl.stop - sl.start
        pos = jnp.arange(rows.shape[0] - n, rows.shape[0])
        want = np.asarray(_plain(q[sl], jnp.asarray(rows), pos, scale, vw))
        assert np.abs(got[sl] - want).max() < 2e-5
        assert np.abs(ref[sl] - want).max() < 2e-5
    assert not got[sum(n for _, n in seqs):].any()  # padding rows: zeros


def test_a_step_compiled_for_its_token_bucket_serves_every_longer_chunk(
        monkeypatch):
    """``mla_max_q_class``: where the chunk tile is at its most, the
    token bucket stands for every longest-chunk bucket, and the program
    made for it gives what the tight one gives."""
    # a state budget of four query tokens a tile at these widths
    h, w, wp, vw = 4, 24, 128, 16
    monkeypatch.setattr(
        mla, "_STATE_BYTES", 4 * h * (vw + 2 * mla._LANES) * 4)
    kw = dict(num_heads=h, value_width=vw)
    assert [mla.mla_max_q_class(m, 64, **kw) for m in (1, 2, 4, 8, 64)] == [
        1, 2, 64, 64, 64]
    rng = np.random.default_rng(3)
    seqs = [(0, 9), (50, 1), (33, 6), (0, 1), (16, 3)]
    q, pool, meta, dense = _batch(rng, seqs, h, w, wp, 64, 8, 8)
    tight, wide = (
        np.asarray(mla.mla_attention_cpu(
            q, pool, meta, scale=0.3, value_width=vw, max_q=m))
        for m in (16, mla.mla_max_q_class(16, 64, **kw)))
    assert np.array_equal(tight, wide)
    for rows, sl in dense:
        n = sl.stop - sl.start
        pos = jnp.arange(rows.shape[0] - n, rows.shape[0])
        want = np.asarray(_plain(q[sl], jnp.asarray(rows), pos, 0.3, vw))
        assert np.abs(wide[sl] - want).max() < 2e-5


def test_the_cells_lattice_is_one_program_a_token_bucket_past_the_tile():
    """At the LongCat widths a chunk tile holds 16 tokens: of the 30
    (token bucket, longest chunk) pairs that 64 clients make, 11 step
    programs are left to compile."""
    import json

    from benchmark import lattice

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    mix = json.load(open(os.path.join(
        repo, "benchmark", "traffic", "chat-closed-64.json")))
    pairs = lattice.buckets(
        mix["arrival"]["clients"], mix["warmup"]["sweep"])
    kw = dict(num_heads=64, value_width=512)
    assert mla._tile_tokens(2048, **kw) == 16
    classes = {(t, mla.mla_max_q_class(q, t, **kw)) for t, q in pairs}
    assert len(pairs) == 30 and len(classes) == 11
    # a class's tile list is never shorter than its members need
    for t, q in pairs:
        c = mla.mla_max_q_class(q, t, **kw)
        mq = mla._tile_tokens(c, **kw)
        assert mq == mla._tile_tokens(q, **kw)
        assert mla.num_query_tiles(t, 64, mq, c) >= mla.num_query_tiles(
            t, 64, mq, max(q, mq))


def test_decode_rows_read_the_pool_and_the_staged_side_buffer():
    """The fused scan's call: ``seq_lens`` is the pool-resident length,
    this dispatch's rows are in the side buffer; then the one-plane
    flush puts them where a single step's scatter would."""
    rng = np.random.default_rng(1)
    h, w, wp, vw, k_blk = 4, 24, 128, 16, 4
    base = [(35, 0), (16, 0), (0, 0), (50, 0)]  # (pool tokens, -)
    _, pool, meta, dense = _batch(
        rng, [(c, 1) for c, _ in base], h, w, wp, 8, 8, 8)
    # pool holds base+1 rows a sequence; the scan sees ``base`` of them
    lens = np.array([c for c, _ in base] + [0] * 4, np.int32)
    n_side = 3  # micro-step 2: three staged rows a sequence
    side = np.zeros((8, 1, k_blk, wp), np.float32)
    side[:4, 0, :n_side, :w] = rng.standard_normal((4, n_side, w))
    q = rng.standard_normal((8, h, w)).astype(np.float32)
    ids = np.array([0, 1, 8, 3, 8, 8, 8, 8], np.int32)  # row 2 masked
    meta = AttentionMetadata(
        q_seq_ids=jnp.asarray(ids),
        q_positions=jnp.asarray(lens + n_side - 1),
        slot_mapping=meta.slot_mapping, block_tables=meta.block_tables,
        seq_lens=jnp.asarray(lens), logits_indices=meta.logits_indices,
        chunk_starts=jnp.asarray(lens + n_side - 1))
    kw = dict(scale=0.3, value_width=vw, side_kv=jnp.asarray(side),
              side_len=jnp.asarray([n_side], jnp.int32))
    got = np.asarray(mla.mla_attention_cpu(
        jnp.asarray(q), pool, meta, max_q=1, **kw))
    ref = np.asarray(mla_attention_reference(jnp.asarray(q), pool, meta, **kw))
    for s in (0, 1, 3):
        rows = np.concatenate([dense[s][0][:lens[s]], side[s, 0, :n_side, :w]])
        want = np.asarray(_plain(
            jnp.asarray(q[s:s + 1]), jnp.asarray(rows),
            jnp.asarray([rows.shape[0] - 1]), 0.3, vw))
        assert np.abs(got[s] - want[0]).max() < 2e-5
        assert np.abs(ref[s] - want[0]).max() < 2e-5
    assert not got[2].any()
    # the flush: rows [base, base + n) of each live sequence, in place
    n_active = jnp.asarray([3, 2, 0, 3, 0, 0, 0, 0], jnp.int32)
    flushed = kv_flush_cpu(
        pool, jnp.asarray(side), meta.block_tables, jnp.asarray(lens),
        n_active)
    want_pool = pool
    for s, n in ((0, 3), (1, 2), (3, 3)):
        p = lens[s] + np.arange(n)
        slots = np.asarray(meta.block_tables)[s, p // PAGE] * PAGE + p % PAGE
        want_pool = write_kv_pages(
            want_pool, jnp.asarray(side[s, 0, :n])[:, None, :], None,
            jnp.asarray(slots))
    assert np.array_equal(np.asarray(flushed)[0, 1:], np.asarray(want_pool)[0, 1:])


def test_interleaved_rope_is_the_pairwise_rotation():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((5, 3, 8)).astype(np.float32)
    pos = np.array([0, 1, 7, 100, 4095], np.int32)
    inv = np.asarray(rope_frequencies(8, 1e7))
    got = np.asarray(apply_rope_interleaved(
        jnp.asarray(x), jnp.asarray(pos), jnp.asarray(inv)))
    assert inv.shape == (4,) and np.allclose(inv[1], 1e7 ** (-2 / 8))
    for t in range(5):
        for i in range(4):
            a = pos[t] * inv[i]
            rot = np.array([[np.cos(a), -np.sin(a)], [np.sin(a), np.cos(a)]])
            want = x[t, :, 2 * i:2 * i + 2] @ rot.T
            assert np.abs(got[t, :, 2 * i:2 * i + 2] - want).max() < 1e-5
    # a rotation: norms kept, position 0 untouched, and the score of a
    # query and a key depends on their distance alone
    assert np.allclose(np.linalg.norm(got, axis=-1),
                       np.linalg.norm(x, axis=-1), atol=1e-5)
    assert np.array_equal(got[0], x[0])

    def score(pq, pk):
        r = apply_rope_interleaved(
            jnp.asarray(x[:2]), jnp.asarray([pq, pk]), jnp.asarray(inv))
        return float((r[0, 0] * r[1, 0]).sum())

    assert abs(score(10, 3) - score(1007, 1000)) < 1e-4


def test_tile_counts_are_the_wrappers_lists():
    # 64 heads: a chunk tile holds 16 tokens (1,024 softmax rows)
    assert mla._tile_tokens(1, 64, 512) == 1
    assert mla._tile_tokens(2048, 64, 512) == 16
    assert mla._tile_tokens(4, 64, 512) == 4
    tiles, rows = mla.mla_query_tile_counts(
        [1] * 40 + [100, 17], max_q=128, num_heads=64, value_width=512)
    assert (tiles, rows) == (40 + 7 + 2, 40 + 9 * 16)
    assert mla.mla_query_tile_counts(
        [1] * 64, max_q=1, num_heads=64, value_width=512) == (64, 64)


# ---- the in-kernel walk over a tile's live sub-blocks ---------------------
# 24 live lanes in a 256-lane float32 row: 1 KiB a token, so the buffer
# slot's budget gives the 512-token sub-block of the LongCat widths
_WALK = dict(h=4, w=24, wp=256, vw=16)
_WALK_PAGES = 128  # the pages bucket: 2,048 tokens
_EDGES = [0, 1, 15, 16, 17, 255, 256, 257, 511, 512, 513, 767, 768, 769,
          1023, 1024, 1025,
          _WALK_PAGES * PAGE - 1]


def _walk_batch(rng, seqs, t_pad):
    return _batch(
        rng, seqs, _WALK["h"], _WALK["w"], _WALK["wp"], t_pad, 8,
        _WALK_PAGES, num_pages=2 + sum(-(-(c + n) // PAGE) for c, n in seqs))


def _assert_walk_matches(got, ref, live):
    assert np.isfinite(got).all()
    assert np.abs(got[live] - ref[live]).max() < 3e-5


def test_the_walk_tests_sub_block_is_the_cells():
    """The edges below are edges of the loop only if a sub-block is 512
    tokens here as at the LongCat widths (bf16 rows of 640 lanes)."""
    assert mla._kv_blocking(
        _WALK["wp"], 4, _WALK["h"] * 2, PAGE, _WALK_PAGES) == (32, 512)
    assert mla._kv_blocking(640, 2, 64, PAGE, 256) == (32, 512)


@pytest.mark.parametrize("side", [False, True], ids=["step", "scan"])
@pytest.mark.parametrize("cached", _EDGES)
def test_decode_walk_matches_the_reference_at_every_edge(cached, side):
    """The decode geometry over sequences whose cached lengths sit on
    the edges of the in-kernel loop (no sub-block, one row, a page more
    or less, half a sub-block, a whole one, one and a half and two of
    them more or less, the longest the pages bucket holds), with empty slots between the live ones: behind
    an empty slot the lookahead misses and the tile fetches its own
    first sub-block; between live neighbours it hits.  ``scan``: the
    pool holds ``cached`` rows and the staged side buffer (K = 8) five
    more; ``step``: a decode-only single step, the row's own token in
    the pool behind them."""
    rng = np.random.default_rng(cached)
    cfg = _WALK
    # slots: the edge, empty, short, empty, empty, the edge, two
    # neighbours that prefetch each other
    lens = [cached, 0, 33, 0, 0, cached, 300, 17]
    if not side:
        seqs = [(n, 1) if (n or i in (0, 5)) else (0, 0)
                for i, n in enumerate(lens)]
        q, pool, meta, _ = _walk_batch(rng, seqs, 8)
        live = np.asarray(meta.q_seq_ids) < 8
        kw = dict(scale=0.3, value_width=cfg["vw"])
        got = mla.mla_attention_cpu(q, pool, meta, max_q=1, **kw)
        ref = mla_attention_reference(q, pool, meta, **kw)
        _assert_walk_matches(np.asarray(got), np.asarray(ref), live)
        assert live.sum() == 5 and not np.asarray(got)[~live].any()
        return
    _, pool, meta, _ = _walk_batch(rng, [(n, 0) for n in lens], 8)
    n_side = 5
    side_kv = np.zeros((8, 1, 8, cfg["wp"]), np.float32)
    side_kv[:, 0, :n_side, :cfg["w"]] = rng.standard_normal(
        (8, n_side, cfg["w"]))
    q = rng.standard_normal((8, cfg["h"], cfg["w"])).astype(np.float32)
    pool_lens = np.asarray(lens, np.int32)
    # a sequence with nothing in the pool is no row of a scan: masked
    ids = np.where(pool_lens > 0, np.arange(8), 8).astype(np.int32)
    meta = AttentionMetadata(
        q_seq_ids=jnp.asarray(ids),
        q_positions=jnp.asarray(pool_lens + n_side - 1),
        slot_mapping=meta.slot_mapping, block_tables=meta.block_tables,
        seq_lens=jnp.asarray(pool_lens), logits_indices=meta.logits_indices,
        chunk_starts=jnp.asarray(pool_lens + n_side - 1))
    kw = dict(scale=0.3, value_width=cfg["vw"], side_kv=jnp.asarray(side_kv),
              side_len=jnp.asarray([n_side], jnp.int32))
    got = np.asarray(mla.mla_attention_cpu(
        jnp.asarray(q), pool, meta, max_q=1, **kw))
    ref = np.asarray(mla_attention_reference(jnp.asarray(q), pool, meta, **kw))
    _assert_walk_matches(got, ref, ids < 8)
    assert not got[ids == 8].any()


@pytest.mark.parametrize(
    "prefix", [16, 255, 256, 257, 511, 513, 767, 769, 1023, 1025])
def test_chunk_tiles_walk_a_cached_prefix_to_their_causal_bound(
        prefix, monkeypatch):
    """The chunk-tile geometry (32 query tokens a tile here): a chunk
    behind a cached prefix on an edge of the loop walks the prefix and
    its own rows up to each tile's last token, beside a fresh chunk, a
    second prefixed chunk and one-token rows (the decode geometry's own
    call), an empty slot between them."""
    rng = np.random.default_rng(prefix)
    seqs = [(prefix, 70), (0, 0), (0, 30), (300, 1), (511, 9), (0, 1)]
    q, pool, meta, _ = _walk_batch(rng, seqs, 128)
    # a state budget of 32 query tokens a tile: the 70-token chunk is
    # three tiles, each with a causal bound of its own
    monkeypatch.setattr(mla, "_STATE_BYTES", 32 * _WALK["h"] * (
        _WALK["vw"] + 2 * mla._LANES) * 4)
    assert mla._tile_tokens(128, _WALK["h"], _WALK["vw"]) == 32
    kw = dict(scale=0.3, value_width=_WALK["vw"])
    got = np.asarray(mla.mla_attention_cpu(q, pool, meta, max_q=128, **kw))
    ref = np.asarray(mla_attention_reference(q, pool, meta, **kw))
    n = sum(new for _, new in seqs)
    _assert_walk_matches(got, ref, np.arange(128) < n)
    assert not got[n:].any()


def test_rows_fetched_are_the_wrappers_list():
    """``mla_kv_rows_fetched`` (the decode ``runner.dispatch`` span's
    ``kv_rows_fetched``) against the lists the wrapper hands the kernel
    and the kernel's own rule over them: whole 512-token sub-blocks of
    each sequence's live rows at the LongCat widths, the last one half
    where the rows end in its first half, nothing for an empty slot."""
    kw = dict(num_heads=64, value_width=512, lanes=640, itemsize=2,
              page_size=PAGE, max_pages=256)
    lens = [0, 1, 256, 257, 511, 512, 513, 680, 768, 769, 1024, 1025, 3840,
            4096]
    rows = [0, 256, 256, 512, 512, 512, 768, 768, 768, 1024, 1024, 1280, 3840,
            4096]
    assert [mla.mla_kv_rows_fetched([n], **kw) for n in lens] == rows
    assert mla.mla_kv_rows_fetched(lens, **kw) == sum(rows)
    s = len(lens)
    nb = np.asarray(mla.tile_kv_blocks(
        jnp.arange(s, dtype=jnp.int32), jnp.zeros(s, jnp.int32),
        jnp.full((1,), s, jnp.int32), jnp.asarray(lens, jnp.int32),
        jnp.asarray(lens, jnp.int32), 1, 512))
    # the kernel's ``is_short`` over (tile_nb, tile_rows): the last
    # sub-block stops at half its pages
    short = (nb > 0) & (np.asarray(lens) - (nb - 1) * 512 <= 256)
    assert int((nb * 512 - short * 256).sum()) == sum(rows)
    # a short pages bucket caps the sub-block at what it holds
    assert mla.mla_kv_rows_fetched([1, 100], **{**kw, "max_pages": 8}) == (
        64 + 128)
    assert mla.mla_kv_rows_fetched([1, 600], **{**kw, "max_pages": 64}) == (
        256 + 768)


# ---- the materialised form: fresh chunks over the flat batch ---------------
def _plain_heads(q, k, v, scale):
    """One sequence, per head, causal: q, k [n, H, D], v [n, H, dv]."""
    n = q.shape[0]
    s = np.einsum("qhd,khd->hqk", q, k) * scale
    s = np.where(np.tril(np.ones((n, n), bool))[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqk,khv->qhv", p / p.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("tq,tk", [(16, 16), (32, 16), (64, 64), (16, 8)])
def test_chunk_kernel_matches_plain_attention_over_ragged_fresh_chunks(tq, tk):
    """Fresh chunks of unequal lengths between rows of other kinds (one
    decode row, three more), padding tokens at the end of the flat
    batch: each fresh row sees its own sequence's rows up to itself and
    nothing else; every other row comes out finite."""
    _check_chunk_kernel(tq, tk, dv=16)


@pytest.mark.parametrize("tq,tk,dv", [(16, 16, 24), (32, 16, 24), (64, 64, 24),
                                      (16, 8, 8)])
def test_chunk_kernel_takes_values_of_another_width_than_the_keys(tq, tk, dv):
    """``v_head_dim`` 1.5 x ``qk_nope_head_dim`` (192 / 128 in the
    DeepSeek-V3-architecture cell) and half of it: the value blocks,
    the accumulator and the output are ``dv`` wide, the scores are
    not."""
    _check_chunk_kernel(tq, tk, dv=dv)


def _check_chunk_kernel(tq, tk, dv):
    rng = np.random.default_rng(3)
    h, dn, dr, t = 4, 16, 8, 64
    kinds = [(3, False), (20, True), (1, False), (15, True), (9, True)]
    start, fresh = np.arange(t, dtype=np.int32), np.zeros(t, bool)
    cursor, chunks = 0, []
    for n, is_fresh in kinds:
        if is_fresh:
            start[cursor:cursor + n], fresh[cursor:cursor + n] = cursor, True
            chunks.append(slice(cursor, cursor + n))
        cursor += n
    assert cursor == 48  # rows 48..63 are padding
    qn, kn = rng.standard_normal((2, t, h, dn)).astype(np.float32)
    qr = rng.standard_normal((t, h, dr)).astype(np.float32)
    kr = rng.standard_normal((t, dr)).astype(np.float32)
    v = rng.standard_normal((t, h, dv)).astype(np.float32)
    got = np.asarray(chunk.mla_chunk_attention(
        *(jnp.asarray(x.reshape(t, -1)) for x in (qn, qr, kn)),
        jnp.asarray(kr), jnp.asarray(v.reshape(t, -1)), jnp.asarray(start),
        jnp.asarray(fresh), num_heads=h, scale=0.3, tile_q=tq, tile_k=tk,
        interpret=True)).reshape(t, h, dv)
    assert np.isfinite(got).all()
    for sl in chunks:
        n = sl.stop - sl.start
        k = np.concatenate(
            [kn[sl], np.broadcast_to(kr[sl, None], (n, h, dr))], -1)
        want = _plain_heads(
            np.concatenate([qn[sl], qr[sl]], -1), k, v[sl], 0.3)
        assert np.abs(got[sl] - want).max() < 2e-5
    if tq == 16:  # the last tile holds no fresh row: zeros, not garbage
        assert not got[48:].any()


def test_the_pair_list_skips_other_sequences_and_is_bounded_by_the_rows():
    """Three 680-token prompts in a 2,048-row batch walk 20 (tile,
    block) pairs of 256 rows, not the flat triangle's 36; one sequence
    filling the batch walks all 36, which is the list's static length
    whatever the chunks are."""
    t = 2048
    assert chunk.num_pair_entries(t, 256, 256) == 36
    assert chunk.num_pair_entries(64, 32, 16) == 2 + 4

    def pairs(lens):
        start, fresh = np.arange(t, dtype=np.int32), np.zeros(t, bool)
        cursor = 0
        for n in lens:
            start[cursor:cursor + n], fresh[cursor:cursor + n] = cursor, True
            cursor += n
        pq, pk, nb, n = chunk.pair_list(
            jnp.asarray(start), jnp.asarray(fresh), 256, 256)
        assert pq.shape == pk.shape == (36,)
        return (np.asarray(pq), np.asarray(pk), np.asarray(nb), int(n[0]))

    pq, pk, nb, n = pairs([680, 680, 680])
    assert nb.tolist() == [1, 2, 3, 2, 3, 4, 2, 3] and n == 20
    # the tile of rows 768..1023 is all the second prompt (from row 680)
    assert pk[pq == 3].tolist()[:2] == [2, 3]
    # entries past the live count repeat the last live pair
    assert set(pq[n:]) == {pq[n - 1]} and set(pk[n:]) == {pk[n - 1]}
    _, _, nb, n = pairs([2048])
    assert nb.tolist() == list(range(1, 9)) and n == 36
    # half the batch is padding: four tiles are one zero-writing entry
    _, _, nb, n = pairs([1000])
    assert nb.tolist() == [1, 2, 3, 4, 0, 0, 0, 0] and n == 10 + 4


def _latent_step(rng, seqs, t_pad, s_pad, pages_pad, h=4, dn=16, dr=8,
                 dv=16, c_w=24, wp=128):
    """A step in head space: seqs [(cached tokens, new tokens)]; the
    pool holds the cached rows and this step's own ``[c, k_rope]``."""
    w = c_w + dr
    pool = jnp.zeros((1, 64, PAGE, wp), jnp.float32)
    qn = rng.standard_normal((t_pad, h, dn)).astype(np.float32)
    qr = rng.standard_normal((t_pad, h, dr)).astype(np.float32)
    c = rng.standard_normal((t_pad, c_w)).astype(np.float32)
    kr = rng.standard_normal((t_pad, dr)).astype(np.float32)
    ids = np.full(t_pad, s_pad, np.int32)
    pos = np.zeros(t_pad, np.int32)
    bt = np.zeros((s_pad, pages_pad), np.int32)
    lens, starts = np.zeros((2, s_pad), np.int32)
    cursor, page = 0, 1
    for s, (cached, new) in enumerate(seqs):
        total = cached + new
        n_pages = -(-total // PAGE)
        bt[s, :n_pages] = np.arange(page, page + n_pages)
        page += n_pages
        slots = bt[s, np.arange(total) // PAGE] * PAGE + np.arange(total) % PAGE
        rows = np.zeros((total, 1, wp), np.float32)
        rows[:cached, 0, :w] = rng.standard_normal((cached, w))
        rows[cached:, 0, :c_w] = c[cursor:cursor + new]
        rows[cached:, 0, c_w:w] = kr[cursor:cursor + new]
        pool = write_kv_pages(pool, jnp.asarray(rows), None, jnp.asarray(slots))
        ids[cursor:cursor + new] = s
        pos[cursor:cursor + new] = np.arange(cached, total)
        lens[s], starts[s] = total, cached
        cursor += new
    meta = AttentionMetadata(
        q_seq_ids=jnp.asarray(ids), q_positions=jnp.asarray(pos),
        slot_mapping=jnp.zeros(t_pad, jnp.int32),
        block_tables=jnp.asarray(bt), seq_lens=jnp.asarray(lens),
        logits_indices=jnp.zeros(s_pad, jnp.int32),
        chunk_starts=jnp.asarray(starts))
    w_uk = rng.standard_normal((h, dn, c_w)).astype(np.float32) * 0.3
    w_uv = rng.standard_normal((h, c_w, dv)).astype(np.float32) * 0.3
    ops = [jnp.asarray(x) for x in (qn, qr, c, kr, w_uk, w_uv)]
    return ops + [pool, meta], cursor


@pytest.mark.parametrize("seqs,taken", [
    # a fresh chunk, chunks with a cached prefix, one-token rows
    ([(0, 25), (50, 1), (33, 17), (0, 1), (90, 1), (0, 3), (16, 3)], True),
    # no chunk with a cached prefix: the conditional is not taken
    ([(0, 30), (12, 1), (0, 20), (3, 1)], False),
    ([(40, 9), (7, 12)], True),  # nothing fresh
])
def test_a_step_of_all_three_kinds_of_row_matches_the_reference(seqs, taken):
    """``mla_attention_from_heads`` (what a chunked step's model call
    reaches through ``bind_max_q``) against the absorbed form around
    ``mla_attention_reference``: fresh chunks through the flat kernel,
    one-token rows through the decode geometry, chunks with a cached
    prefix behind the conditional, whether or not it is taken."""
    rng = np.random.default_rng(4)
    ops, n = _latent_step(rng, seqs, 64, 8, 8)
    want = np.asarray(mla_sublayer_attention(
        mla_attention_reference, *ops, scale=0.3))
    fn = bind_max_q(mla.mla_attention_cpu, 64)
    got = np.asarray(mla_sublayer_attention(fn, *ops, scale=0.3))
    assert got.shape == (64, 4 * 16)  # flat, as W_o takes it
    got = got.reshape(want.shape)
    assert np.isfinite(got).all()  # padding rows too: nothing poisons
    assert np.abs(got[:n] - want[:n]).max() < 2e-5
    meta = ops[-1]
    q_len = np.bincount(np.asarray(meta.q_seq_ids), minlength=9)[:8]
    assert bool(((q_len > 1) & (np.asarray(meta.chunk_starts) > 0)).any()
                ) is taken
    fresh, prefixed = mla.mla_chunk_token_counts(
        [new for _, new in seqs], [cached for cached, _ in seqs])
    assert fresh == sum(new for c, new in seqs if new > 1 and c == 0)
    assert prefixed == sum(new for c, new in seqs if new > 1 and c > 0)
    # the fused scan's and a decode-only step's calls keep the absorbed
    # form: no head-space entry is bound at max_q = 1, nor on a closure
    assert not hasattr(bind_max_q(mla.mla_attention_cpu, 1), "from_heads")
    assert bind_max_q(mla_attention_reference, 64) is mla_attention_reference


@pytest.mark.parametrize("dv", [24, 8])
@pytest.mark.parametrize("seqs", [
    [(0, 25), (50, 1), (33, 17), (0, 1), (90, 1), (0, 3), (16, 3)],
    [(0, 30), (12, 1), (0, 20), (3, 1)],
], ids=["all-three-kinds", "no-cached-prefix"])
def test_a_step_whose_values_are_not_as_wide_as_its_keys_matches_the_reference(
        seqs, dv):
    """The absorbed path and the flat kernel at ``v_head_dim`` 1.5 x
    (192 / 128 at the DeepSeek-V3-architecture cell's widths) and half
    ``qk_nope_head_dim``: ``W_uv`` alone says how wide a head comes
    out; the cached row, the scores and the latent kernel do not."""
    rng = np.random.default_rng(4)
    ops, n = _latent_step(rng, seqs, 64, 8, 8, dv=dv)
    want = np.asarray(mla_sublayer_attention(
        mla_attention_reference, *ops, scale=0.3))
    assert want.shape == (64, 4, dv)
    fn = bind_max_q(mla.mla_attention_cpu, 64)
    got = np.asarray(mla_sublayer_attention(fn, *ops, scale=0.3))
    assert got.shape == (64, 4 * dv)
    assert np.isfinite(got).all()
    assert np.abs(got.reshape(want.shape)[:n] - want[:n]).max() < 2e-5
    # a decode-only step (max_q 1) keeps the absorbed form round the
    # kernel: latent in, latent out, W_uv after
    one = [(40, 1), (7, 1), (19, 1)]
    ops, n = _latent_step(np.random.default_rng(5), one, 16, 8, 8, dv=dv)
    want = np.asarray(mla_sublayer_attention(
        mla_attention_reference, *ops, scale=0.3))
    got = np.asarray(mla_sublayer_attention(
        bind_max_q(mla.mla_attention_cpu, 1), *ops, scale=0.3))
    assert got.shape == want.shape == (16, 4, dv)
    assert np.abs(got[:n] - want[:n]).max() < 2e-5


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
def test_attention_compiles_for_the_v5e_at_longcat_widths(
        t, max_q, s, side, one_chip, no_compile_cache):
    """64 heads over one 576-value row in 640 lanes, a 256-page table:
    Mosaic takes it, a chunk step is two kernel calls (chunk tiles,
    single tokens), a decode step one, and no XLA loop comes with the
    lists."""
    h, w, wp, vw, pages, pool = 64, 576, 640, 512, 256, 8192

    def shaped(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    meta = AttentionMetadata(
        q_seq_ids=shaped((t,)), q_positions=shaped((t,)),
        slot_mapping=shaped((t,)), block_tables=shaped((s, pages)),
        seq_lens=shaped((s,)), logits_indices=shaped((s,)),
        chunk_starts=shaped((s,)))
    args = [shaped((t, h, w), jnp.bfloat16),
            shaped((1, pool, PAGE, wp), jnp.bfloat16), meta]
    if side:
        args += [shaped((s, 1, 8, wp), jnp.bfloat16), shaped((1,))]

    def call(q, kv, meta, *staged):
        kw = dict(side_kv=staged[0], side_len=staged[1]) if staged else {}
        return mla.mla_attention(
            q, kv, meta, scale=192 ** -0.5, value_width=vw, max_q=max_q, **kw)

    compiled = jax.jit(call).lower(*args).compile()
    text = compiled.as_text()
    assert "vdt_mla_attention" in text
    assert text.count("tpu_custom_call") == (2 if max_q >= 16 else 1)
    assert " while(" not in text and " conditional(" not in text
    # the absorbed queries are 576 values a head: 151 MB for 2,048
    # tokens; their tiles and the latent outputs are the rest
    assert compiled.memory_analysis().temp_size_in_bytes < 500e6


@pytest.mark.parametrize("dv", [128, 192], ids=["dv128", "dv192"])
@pytest.mark.parametrize("t", [2048, 256])
def test_chunk_attention_compiles_for_the_v5e_at_longcat_widths(
        t, dv, one_chip, no_compile_cache):
    """The materialised form at the cell's widths (64 heads of 128 + 64
    / 128 over 512-wide latents): Mosaic takes the flat kernel's column
    blocks of eight heads, a chunked step's sublayer is three kernel
    calls outside the conditional (the flat kernel, the one-token rows)
    and in it (the absorbed chunk tiles), and no XLA loop comes with
    the lists.  At the DeepSeek-V3-architecture cell's **192**-wide
    values the same: eight heads are 1,536 value lanes, whole tiles,
    though one head's 192 are not, and Mosaic takes the head slices at
    192-lane offsets."""
    h, dn, dr, c_w, wp, s, pages, pool = 64, 128, 64, 512, 640, 64, 256, 8192
    bf = jnp.bfloat16

    def shaped(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert chunk.heads_per_step(h, dn, dr, dv) == 8
    meta = AttentionMetadata(
        q_seq_ids=shaped((t,)), q_positions=shaped((t,)),
        slot_mapping=shaped((t,)), block_tables=shaped((s, pages)),
        seq_lens=shaped((s,)), logits_indices=shaped((s,)),
        chunk_starts=shaped((s,)))
    args = [shaped((t, h, dn), bf), shaped((t, h, dr), bf),
            shaped((t, c_w), bf), shaped((t, dr), bf),
            shaped((h, dn, c_w), bf), shaped((h, c_w, dv), bf),
            shaped((1, pool, PAGE, wp), bf), meta]
    fn = bind_max_q(mla.mla_attention, t)
    compiled = jax.jit(lambda *a: mla_sublayer_attention(
        fn, *a, scale=192 ** -0.5)).lower(*args).compile()
    text = compiled.as_text()
    assert "vdt_mla_chunk_attention" in text and "vdt_mla_attention" in text
    assert text.count("tpu_custom_call") == 3
    assert " while(" not in text and text.count(" conditional(") == 1
    # the per-head keys and values come out row-major: no transpose of
    # a [T, H, 128] product is left in front of the kernel
    assert "{0,2,1" not in "".join(
        line for line in text.splitlines()
        if "mla_absorb/dot_general" in line and "cond" not in line)


def test_one_plane_flush_compiles_for_the_v5e(one_chip, no_compile_cache):
    def shaped(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(kv_flush).lower(
        shaped((1, 8192, PAGE, 640), jnp.bfloat16),
        shaped((64, 1, 8, 640), jnp.bfloat16), shaped((64, 256)),
        shaped((64,)), shaped((64,))).compile()
    assert "vdt_kv_flush" in compiled.as_text()
