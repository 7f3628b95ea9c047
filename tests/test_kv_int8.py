"""Quantized (int8) KV cache: per-head quantization, kernel dequant,
flush-path quantization, and engine e2e under --kv-cache-dtype int8.

Parity: the reference drives vLLM's --kv-cache-dtype engine-arg surface
(/root/reference/src/launch.py:29 via AsyncEngineArgs.from_cli_args);
the TPU pool stores int8 rows + per-(token, kv-head) f32 scales so the
scale plane TP-shards over the same lane axis as the data plane
(ops/attention.py kv_scales_shape).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_pallas_attention import build_case
from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    kv_pool_shape,
    kv_scales_shape,
    paged_attention_reference,
    quantize_kv_heads,
    split_kv_pages,
    write_kv_pages,
)
from vllm_distributed_tpu.ops.pallas.kv_flush import kv_flush_cpu
from vllm_distributed_tpu.ops.pallas.paged_attention import paged_attention


def _quantize_pool(kv_pages, hkv):
    """Quantize a dense pool into the (int8 data, per-head scales) form
    via the production write path (token-row granularity)."""
    _, p, page, hd = kv_pages.shape
    data = jnp.zeros((2, p, page, hd), jnp.int8)
    scales = jnp.zeros(kv_scales_shape(p, page, hkv), jnp.float32)
    d = hd // hkv
    slots = jnp.arange(p * page, dtype=jnp.int32)
    k = kv_pages[0].reshape(p * page, hkv, d)
    v = kv_pages[1].reshape(p * page, hkv, d)
    return write_kv_pages((data, scales), k, v, slots)


# write_kv_pages on an unquantized pool against a NumPy row loop.  It is
# every single step's KV writer and the oracle of the staged flush
# (tests/test_kv_flush.py) and of the int8 write path below.
# (t, hkv, d, pool lane width or None for hkv*d, slots or None for
# distinct random ones, pool dtype)
_WRITE_CASES = {
    "basic_scatter": (16, 2, 64, None, None, "float32"),
    # hkv*d = 64 < the 128-lane tile, pool left unpadded (tiny-model and
    # per-shard shapes).
    "sub_tile_width_pool": (8, 1, 64, None, None, "float32"),
    # The same rows into a pool padded to the lane tile: the writer pads
    # each row with zeros up to the pool's width.
    "sub_tile_rows_into_padded_pool": (8, 1, 64, 128, None, "float32"),
    # Padding tokens all target reserved page 0 (never read).
    "duplicate_slots_onto_page_0": (
        8, 2, 64, None, [5, 5, 5, 17, 17, 3, 0, 0], "float32",
    ),
    "single_token_decode_shape": (1, 8, 128, None, None, "float32"),
    "bfloat16_pool_casts_inputs": (4, 2, 64, None, None, "bfloat16"),
}


@pytest.mark.parametrize(
    "t,hkv,d,width,slots,dtype",
    list(_WRITE_CASES.values()),
    ids=list(_WRITE_CASES),
)
def test_write_kv_pages_matches_row_loop(t, hkv, d, width, slots, dtype):
    rng = np.random.default_rng(t * 131 + hkv)
    num_pages, page_size = 8, 16
    shape = list(kv_pool_shape(num_pages, page_size, hkv, d))
    if width is not None:
        shape[-1] = width
    pool = jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)
    k = jnp.asarray(rng.standard_normal((t, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((t, hkv, d)), jnp.float32)
    if slots is None:
        slots = rng.choice(num_pages * page_size, size=t, replace=False)
    slots = [int(s) for s in slots]

    got = write_kv_pages(pool, k, v, jnp.asarray(slots, jnp.int32))
    assert got.dtype == pool.dtype and got.shape == pool.shape
    got = np.asarray(got.astype(jnp.float32))

    def rows(x):  # what lands in the pool: cast, flattened, zero-padded
        x = np.asarray(x.astype(dtype).astype(jnp.float32)).reshape(t, -1)
        return np.pad(x, [(0, 0), (0, shape[-1] - hkv * d)])

    k_rows, v_rows = rows(k), rows(v)
    want = np.asarray(pool.astype(jnp.float32)).copy()
    for slot in set(slots):
        writers = [i for i, s in enumerate(slots) if s == slot]
        page, row = divmod(slot, page_size)
        # XLA leaves the order of duplicate scatter indices unspecified:
        # a slot written more than once holds one of its writers' rows.
        for plane, cand in ((0, k_rows), (1, v_rows)):
            cell = got[plane, page, row]
            assert any(np.array_equal(cell, cand[i]) for i in writers), (
                f"slot {slot} plane {plane} holds a row no writer produced"
            )
            want[plane, page, row] = cell
    # Every row no slot named is untouched.
    np.testing.assert_array_equal(got, want)


def test_quantize_roundtrip_error():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 4 * 32)) * 3, jnp.float32)
    q, s = quantize_kv_heads(x, 4)
    deq = np.asarray(q, np.float32).reshape(64, 4, 32) * np.asarray(s)[
        ..., None
    ]
    err = np.abs(deq.reshape(64, -1) - np.asarray(x))
    # Symmetric int8: error bounded by scale/2 = absmax/254 per head.
    bound = np.asarray(s).max() * 0.51
    assert err.max() <= bound


def test_split_kv_pages_dequantizes():
    rng = np.random.default_rng(1)
    hkv, d, p, page = 2, 32, 4, 8
    kv = jnp.asarray(
        rng.standard_normal(kv_pool_shape(p, page, hkv, d)), jnp.float32
    )
    qpool = _quantize_pool(kv, hkv)
    k_deq, v_deq = split_kv_pages(qpool, hkv, d)
    k_ref, v_ref = split_kv_pages(kv, hkv, d)
    np.testing.assert_allclose(
        np.asarray(k_deq), np.asarray(k_ref), atol=0.05
    )
    np.testing.assert_allclose(
        np.asarray(v_deq), np.asarray(v_ref), atol=0.05
    )


@pytest.mark.parametrize(
    "specs,hq,hkv",
    [
        ([(17, 1), (33, 1), (160, 1)], 4, 2),  # pure decode, GQA
        ([(24, 24), (7, 7)], 4, 2),  # prefill
        ([(50, 1), (20, 20), (33, 1)], 8, 2),  # mixed
        ([(21, 1), (9, 9)], 4, 4),  # MHA
    ],
)
def test_pallas_matches_reference_on_quantized_pool(specs, hq, hkv):
    """Kernel and reference read the SAME int8 pool, so they dequantize
    identical values — agreement is float-rounding tight, proving the
    in-kernel scale application (scores/probs side) is exact."""
    rng = np.random.default_rng(2)
    q, kv, meta, max_q, t_real, hkv = build_case(
        rng, seq_specs=specs, hq=hq, hkv=hkv
    )
    qpool = _quantize_pool(kv, hkv)
    ref = paged_attention_reference(
        q, qpool, meta, scale=0.125, num_kv_heads=hkv
    )
    got = paged_attention(
        q, qpool, meta, scale=0.125, num_kv_heads=hkv,
        max_q=max_q, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got[:t_real]), np.asarray(ref[:t_real]),
        rtol=1e-4, atol=2e-5,
    )


def test_quantized_vs_f32_tolerance():
    """End-to-end numerics: attention over the quantized pool stays
    close to attention over the original f32 pool."""
    rng = np.random.default_rng(3)
    q, kv, meta, max_q, t_real, hkv = build_case(
        rng, seq_specs=[(40, 8), (64, 16), (100, 1)]
    )
    want = paged_attention_reference(
        q, kv, meta, scale=0.125, num_kv_heads=hkv
    )
    got = paged_attention(
        q, _quantize_pool(kv, hkv), meta, scale=0.125, num_kv_heads=hkv,
        max_q=max_q, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got[:t_real]), np.asarray(want[:t_real]), atol=0.05
    )


def test_staged_side_buffer_on_quantized_pool():
    """Decode scan shape: int8 pool history + unquantized (model-dtype)
    side rows must match the reference with the same operands."""
    rng = np.random.default_rng(4)
    hq, hkv, d, page_size = 4, 2, 64, 16
    s_pad, k_steps, step_i = 4, 8, 5
    bases = [37, 21, 0, 5]
    num_pages = 32
    kv = jnp.asarray(
        rng.standard_normal(kv_pool_shape(num_pages, page_size, hkv, d)),
        jnp.float32,
    )
    qpool = _quantize_pool(kv, hkv)
    side = jnp.asarray(
        rng.standard_normal((s_pad, 2, k_steps, hkv * d)), jnp.float32
    )
    bt = np.zeros((s_pad, 8), np.int32)
    nxt = 1
    for i, b in enumerate(bases):
        if b <= 0:
            continue
        need = -(-(b + k_steps) // page_size)
        bt[i, :need] = np.arange(nxt, nxt + need)
        nxt += need
    pos = np.asarray([b + step_i if b > 0 else 0 for b in bases], np.int32)
    sid = np.asarray(
        [i if b > 0 else s_pad for i, b in enumerate(bases)], np.int32
    )
    q = jnp.asarray(rng.standard_normal((s_pad, hq, d)), jnp.float32)
    meta = AttentionMetadata(
        q_seq_ids=jnp.asarray(sid),
        q_positions=jnp.asarray(pos),
        slot_mapping=jnp.zeros(s_pad, jnp.int32),
        block_tables=jnp.asarray(bt),
        seq_lens=jnp.asarray(np.asarray(bases, np.int32)),
        logits_indices=jnp.arange(s_pad, dtype=jnp.int32),
        chunk_starts=jnp.asarray(pos),
    )
    side_len = jnp.asarray([step_i + 1], jnp.int32)
    want = paged_attention_reference(
        q, qpool, meta, scale=0.125, num_kv_heads=hkv,
        side_kv=side, side_len=side_len,
    )
    got = paged_attention(
        q, qpool, meta, scale=0.125, num_kv_heads=hkv,
        max_q=1, side_kv=side, side_len=side_len, interpret=True,
    )
    live = np.asarray([i for i, b in enumerate(bases) if b > 0])
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live],
        rtol=1e-4, atol=2e-5,
    )


def test_kv_flush_quantized_matches_functional_write():
    """The double-kernel flush (data planes + scale planes) must equal
    the functional quantized scatter over the same rows — EXACTLY,
    since both quantize per head with the same reduction."""
    rng = np.random.default_rng(5)
    hkv, d, page_size, num_pages = 2, 32, 16, 32
    s_pad, k_steps = 4, 8
    hd = hkv * d
    kv = jnp.asarray(
        rng.standard_normal(kv_pool_shape(num_pages, page_size, hkv, d)),
        jnp.float32,
    )
    qpool = _quantize_pool(kv, hkv)
    side = jnp.asarray(
        rng.standard_normal((s_pad, 2, k_steps, hd)), jnp.float32
    )
    bases = np.asarray([17, 40, 0, 3], np.int32)
    n_side = np.asarray([k_steps, 5, 0, k_steps], np.int32)
    bt = np.zeros((s_pad, 8), np.int32)
    nxt = 1
    for i, b in enumerate(bases):
        if b <= 0:
            continue
        need = -(-(int(b) + k_steps) // page_size)
        bt[i, :need] = np.arange(nxt, nxt + need)
        nxt += need

    got_data, got_scales = kv_flush_cpu(
        qpool,
        side,
        jnp.asarray(bt),
        jnp.asarray(bases),
        jnp.asarray(n_side),
    )

    want_data, want_scales = qpool
    for i, b in enumerate(bases):
        if b <= 0 or n_side[i] <= 0:
            continue
        for j in range(int(n_side[i])):
            p = int(b) + j
            slot = bt[i, p // page_size] * page_size + p % page_size
            want_data, want_scales = write_kv_pages(
                (want_data, want_scales),
                side[i, 0, j].reshape(1, hkv, d),
                side[i, 1, j].reshape(1, hkv, d),
                jnp.asarray([slot], jnp.int32),
            )
    # Page 0 is the dump page (dead rows scatter garbage there by
    # contract) — exclude it from the comparison.
    np.testing.assert_array_equal(
        np.asarray(got_data)[:, 1:], np.asarray(want_data)[:, 1:]
    )
    np.testing.assert_allclose(
        np.asarray(got_scales)[:, 1:],
        np.asarray(want_scales)[:, 1:],
        rtol=1e-6,
    )


def test_engine_e2e_int8_kv(tmp_path):
    """Whole engine with --kv-cache-dtype int8: the interpret-mode
    Pallas path and the XLA reference path must agree token-for-token
    (same quantized pool contents), and the run must complete."""
    from tests.utils import make_tiny_llama
    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.engine.llm_engine import LLMEngine
    from vllm_distributed_tpu.ops.attention import (
        paged_attention_reference as ref_fn,
    )
    from vllm_distributed_tpu.sampling_params import SamplingParams

    model_dir = make_tiny_llama(str(tmp_path / "m"))

    def run(backend):
        config = EngineArgs(
            model=model_dir,
            skip_tokenizer_init=True,
            num_kv_pages=64,
            max_model_len=128,
            max_num_seqs=8,
            max_num_batched_tokens=64,
            kv_cache_dtype="int8",
            num_decode_steps=4,
        ).create_engine_config()
        engine = LLMEngine(config)
        runner = engine.executor.worker.runner
        if backend == "pallas":
            from vllm_distributed_tpu.ops.pallas.kv_flush import (
                kv_flush_cpu,
            )
            from vllm_distributed_tpu.ops.pallas.paged_attention import (
                paged_attention_cpu,
            )

            runner._attn_fn = paged_attention_cpu
            runner._kv_flush_fn = kv_flush_cpu
            runner._staged_decode = True
        else:
            runner._attn_fn = ref_fn
            runner._kv_flush_fn = None
            runner._staged_decode = False
        prompts = [list(range(1, 30)), [5, 6, 7], list(range(40, 60))]
        for i, p in enumerate(prompts):
            engine.add_request(
                f"r{i}",
                prompt_token_ids=p,
                sampling_params=SamplingParams(
                    temperature=0.0, max_tokens=6, ignore_eos=True
                ),
            )
        done = {}
        while engine.has_unfinished_requests():
            for out in engine.step():
                if out.finished:
                    done[out.request_id] = out.outputs[0].token_ids
        return [done[f"r{i}"] for i in range(len(prompts))]

    ref_tokens = run("reference")
    assert all(len(t) == 6 for t in ref_tokens)
    # Pallas staged path quantizes at flush; reference quantizes in-step.
    # Both write identical per-head-quantized rows, so greedy tokens on
    # a tiny model should agree.
    assert run("pallas") == ref_tokens
