"""Staged-decode flush kernel vs the functional scatter oracle, in
interpret mode on CPU (chip_smoke.py phase A compiles and checks the
production TPU path on the chip)."""

import jax.numpy as jnp
import numpy as np

from vllm_distributed_tpu.ops.attention import kv_pool_shape, write_kv_pages
from vllm_distributed_tpu.ops.pallas.kv_flush import kv_flush


def _run_case(
    *,
    base_lens,  # python list; 0 = padding row
    n_side,
    k_blk=16,
    page_size=16,
    hkv=2,
    d=64,
    num_pages=32,
    seed=0,
    table_slack=1,  # 0 = exact-fit table (the slab slack column steps
    #                 past the table and must hit the dump page)
):
    rng = np.random.default_rng(seed)
    s = len(base_lens)
    kv = jnp.asarray(
        rng.standard_normal(kv_pool_shape(num_pages, page_size, hkv, d)),
        jnp.float32,
    )
    side = jnp.asarray(
        rng.standard_normal((s, 2, k_blk, hkv * d)), jnp.float32
    )
    # Per-seq block tables: enough pages for base + k rows, disjoint.
    max_pages = max(
        -(-(b + k_blk) // page_size) for b in base_lens
    ) + table_slack
    bt = np.zeros((s, max_pages), np.int32)
    nxt = 1
    for i, b in enumerate(base_lens):
        if b <= 0:
            continue
        need = -(-(b + k_blk) // page_size)
        bt[i, :need] = np.arange(nxt, nxt + need)
        nxt += need
    assert nxt <= num_pages

    got = kv_flush(
        kv,
        side,
        jnp.asarray(bt),
        jnp.asarray(np.asarray(base_lens, np.int32)),
        jnp.asarray([n_side], jnp.int32),
        interpret=True,
    )

    # Oracle: scatter each live sequence's first n_side side rows at
    # slots base..base+n_side-1.
    want = kv
    for i, b in enumerate(base_lens):
        if b <= 0:
            continue
        for j in range(n_side):
            pos = b + j
            slot = bt[i, pos // page_size] * page_size + pos % page_size
            want = write_kv_pages(
                want,
                side[i, 0, j].reshape(1, hkv, d),
                side[i, 1, j].reshape(1, hkv, d),
                jnp.asarray([slot], jnp.int32),
            )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_page_aligned_base():
    _run_case(base_lens=[16, 48], n_side=16)


def test_unaligned_bases():
    _run_case(base_lens=[3, 21, 70], n_side=16)


def test_partial_flush():
    _run_case(base_lens=[5, 33], n_side=7)


def test_padding_rows_skipped():
    _run_case(base_lens=[9, 0, 25, 0], n_side=16)


def test_small_page_spans_three():
    # k=16 rows over page_size 8 spans up to 3 pages.
    _run_case(base_lens=[5, 19], n_side=16, page_size=8)


def test_single_row_flush():
    _run_case(base_lens=[31], n_side=1)


def test_exact_fit_table():
    # base + k exactly fills the table's last page and the table has NO
    # slack column: the slab's extra page must fall through to the dump
    # page instead of duplicating (and clobbering) the last real page.
    _run_case(base_lens=[48], n_side=16, table_slack=0)


def test_exact_fit_mixed_lengths():
    # Row 0's slack page is an in-table zero entry (dump page); row 1's
    # steps past the table width entirely.
    _run_case(base_lens=[32, 64], n_side=16, table_slack=0)
