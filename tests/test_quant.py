"""Weight-only quantization (--quantization int8/int4): roundtrip
accuracy, engine integration, memory halving, and sharded bit-equality.

The reference serves AWQ 4-bit checkpoints via vLLM's CUDA kernels
(/root/reference/.env.server:11); the TPU-native design quantizes on
load and dequantizes in-graph (ops/quant.py).
"""

import numpy as np
import pytest

from tests.utils import make_tiny_llama, make_tiny_mixtral
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.ops.quant import (
    QuantizedTensor,
    dequantize,
    pick_group_size,
    quantize,
)
from vllm_distributed_tpu.sampling_params import SamplingParams

PROMPTS = [[1, 5, 9, 23, 77, 41, 3], [7, 2, 88, 14]]


@pytest.fixture(scope="module")
def tiny_llama(tmp_path_factory):
    return make_tiny_llama(
        str(tmp_path_factory.mktemp("llama_q")), heads=8, kv_heads=4
    )


@pytest.fixture(scope="module")
def tiny_mixtral(tmp_path_factory):
    return make_tiny_mixtral(
        str(tmp_path_factory.mktemp("mixtral_q")), heads=8, kv_heads=4
    )


# ---- kernel-level roundtrips ----
def test_int8_roundtrip():
    rng = np.random.default_rng(0)
    w = (rng.standard_normal((64, 48)) * 0.1).astype(np.float32)
    qt = quantize(w, 8)
    got = np.asarray(dequantize(qt, np.float32))
    assert np.abs(got - w).max() / np.abs(w).max() < 0.01
    assert qt.nbytes < 0.3 * w.nbytes


def test_int4_roundtrip_grouped():
    rng = np.random.default_rng(1)
    w = (rng.standard_normal((128, 32)) * 0.1).astype(np.float32)
    qt = quantize(w, 4, group=32)
    got = np.asarray(dequantize(qt, np.float32))
    assert np.abs(got - w).max() / np.abs(w).max() < 0.12
    assert qt.q.shape == (64, 32)  # two nibbles per byte
    assert qt.scale.shape == (4, 32)
    assert qt.nbytes < 0.2 * w.nbytes


def test_int4_stacked_experts_roundtrip():
    rng = np.random.default_rng(2)
    w = (rng.standard_normal((4, 64, 32)) * 0.1).astype(np.float32)
    qt = quantize(w, 4)
    got = np.asarray(dequantize(qt, np.float32))
    assert np.abs(got - w).max() / np.abs(w).max() < 0.12


def test_group_size_respects_shards():
    assert pick_group_size(11008, 8) <= 11008 // 8
    assert (11008 // 8) % pick_group_size(11008, 8) == 0
    assert pick_group_size(4096, 1) == 128


def test_rejects_unknown_method(tiny_llama):
    with pytest.raises(ValueError, match="unsupported quantization"):
        EngineArgs(model=tiny_llama, quantization="fp8").create_engine_config()


# ---- engine integration ----
def _greedy(model_dir, quantization=None, tp=1, ep=False, max_tokens=6):
    engine = LLMEngine.from_engine_args(
        EngineArgs(
            model=model_dir,
            skip_tokenizer_init=True,
            num_kv_pages=64,
            max_model_len=256,
            quantization=quantization,
            tensor_parallel_size=tp,
            enable_expert_parallel=ep,
        )
    )
    done = {}
    for i, p in enumerate(PROMPTS):
        engine.add_request(
            f"r{i}",
            prompt_token_ids=p,
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=max_tokens, ignore_eos=True
            ),
        )
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.outputs[0].token_ids
    return engine, [done[f"r{i}"] for i in range(len(PROMPTS))]


def _param_bytes(engine):
    import jax

    return sum(
        x.nbytes
        for x in jax.tree.leaves(engine.executor.worker.runner.params)
    )


def test_int8_engine_memory_and_logits(tiny_llama):
    eng_fp, _ = _greedy(tiny_llama)
    eng_q, _ = _greedy(tiny_llama, quantization="int8")
    # Attn+MLP weights dominate this tiny model less than a real one
    # (embed/lm_head stay fp32), so just require a real reduction.
    assert _param_bytes(eng_q) < 0.75 * _param_bytes(eng_fp)
    # Logit agreement on a prefill: loose tolerance, quantization noise.
    import jax.numpy as jnp

    from vllm_distributed_tpu.ops.attention import AttentionMetadata

    def prefill_logits(eng):
        runner = eng.executor.worker.runner
        prompt = PROMPTS[0]
        t = len(prompt)
        meta = AttentionMetadata(
            q_seq_ids=jnp.zeros(t, jnp.int32),
            q_positions=jnp.arange(t, dtype=jnp.int32),
            slot_mapping=16 + jnp.arange(t, dtype=jnp.int32),
            block_tables=jnp.ones((1, 4), jnp.int32),
            seq_lens=jnp.full(1, t, jnp.int32),
            logits_indices=jnp.full(1, t - 1, jnp.int32),
            chunk_starts=jnp.zeros(1, jnp.int32),
        )
        logits, _ = runner.model.forward(
            runner.params,
            jnp.asarray(prompt, jnp.int32),
            runner.kv_caches,
            meta,
        )
        return np.asarray(logits)[0]

    lf, lq = prefill_logits(eng_fp), prefill_logits(eng_q)
    scale = np.abs(lf).max()
    assert np.abs(lf - lq).max() / scale < 0.05


def test_int8_tp4_matches_tp1(tiny_llama):
    _, base = _greedy(tiny_llama, quantization="int8")
    _, tp4 = _greedy(tiny_llama, quantization="int8", tp=4)
    assert tp4 == base


def test_int4_engine_runs(tiny_llama):
    eng_q, toks = _greedy(tiny_llama, quantization="int4")
    assert all(len(t) == 6 for t in toks)
    eng_fp, _ = _greedy(tiny_llama)
    assert _param_bytes(eng_q) < 0.7 * _param_bytes(eng_fp)


def test_int4_tp4_deterministic(tiny_llama):
    """int4 grouping follows the tp layout (shard-aligned groups), so
    tp=4 is compared against itself (determinism), not bit-against tp=1
    — int8 is the layout-independent scheme (see test above)."""
    _, a = _greedy(tiny_llama, quantization="int4", tp=4)
    _, b = _greedy(tiny_llama, quantization="int4", tp=4)
    assert a == b


def test_int8_mixtral_ep(tiny_mixtral):
    """Quantized experts through the HF load path (per-expert tensors
    quantized in-stream, stacked by finalize_params) under EP."""
    _, base = _greedy(tiny_mixtral, quantization="int8")
    _, ep4 = _greedy(tiny_mixtral, quantization="int8", tp=4, ep=True)
    assert ep4 == base
    # Quantized params flow as pytrees with int8 leaves.
    eng, _ = _greedy(tiny_mixtral, quantization="int8")
    layer = eng.executor.worker.runner.params["layers"][0]
    assert isinstance(layer["w1"], QuantizedTensor)
    assert layer["w1"].q.dtype == np.int8


def test_int8_matmul_kernel_interpret():
    """Pallas weight-streaming matmul vs dequant-in-graph (interpret
    mode; chip_smoke.py phase A re-checks on the live chip)."""
    import jax.numpy as jnp

    from vllm_distributed_tpu.ops.pallas.quant_matmul import int8_matmul

    rng = np.random.default_rng(3)
    for (t, i, o, blk) in [(32, 2048, 512, 512), (16, 256, 1024, 512),
                           (8, 128, 640, 128)]:
        x = jnp.asarray(rng.standard_normal((t, i)) * 0.5, jnp.float32)
        qt = quantize((rng.standard_normal((i, o)) * 0.1).astype(np.float32), 8)
        want = np.asarray(x @ dequantize(qt, jnp.float32))
        got = np.asarray(int8_matmul(
            x, jnp.asarray(qt.q), jnp.asarray(qt.scale),
            block_out=min(blk, o), interpret=True))
        assert np.abs(got - want).max() / np.abs(want).max() < 1e-5


def test_int8_engine_pallas_interpret_path(tiny_llama):
    """The quant-matmul 'pallas' mode end to end via VDT_USE_PALLAS
    (interpret kernels on CPU), vs the dequant path: same tokens."""
    import os
    from unittest import mock

    _, base = _greedy(tiny_llama, quantization="int8")
    with mock.patch.dict(os.environ, {"VDT_USE_PALLAS": "pallas_interpret"}):
        eng, via_kernel = _greedy(tiny_llama, quantization="int8")
    # The loader stamps the backend on each tensor at load time; on the
    # single-chip kernel path Q|K|V and gate|up fuse into one streaming
    # call each (bit-identical: per-out-block computation independent).
    layer = eng.executor.worker.runner.params["layers"][0]
    assert layer["wqkv"].matmul == "pallas_interpret"
    assert "wgu" in layer and "wq" not in layer and "gate" not in layer
    assert via_kernel == base


def test_int4_matmul_kernel_interpret():
    """Weight-streaming int4 kernel (permuted-contraction nibble
    unpack) vs dequantize-in-graph, multiple group sizes."""
    import jax.numpy as jnp
    import numpy as np

    from vllm_distributed_tpu.ops.pallas.quant_matmul import int4_matmul
    from vllm_distributed_tpu.ops.quant import dequantize, quantize

    rng = np.random.default_rng(0)
    for in_dim, out_dim, group, blk in (
        (256, 512, 128, 256),
        (256, 256, 64, 128),
        (128, 128, 2, 128),
    ):
        x = jnp.asarray(
            rng.standard_normal((8, in_dim)) * 0.3, jnp.float32
        )
        w = rng.standard_normal((in_dim, out_dim)).astype(np.float32) * 0.1
        qt = quantize(w, 4, group=group)
        want = np.asarray(x @ dequantize(qt, jnp.float32))
        got = np.asarray(
            int4_matmul(
                x, jnp.asarray(qt.q), jnp.asarray(qt.scale),
                group=group, block_out=blk, interpret=True,
            )
        )
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_int4_engine_pallas_interpret_path(tiny_llama):
    """Engine e2e on the int4 streaming path must match the int4
    dequant-in-graph path token-for-token (identical quantized values,
    different execution backend)."""
    import os
    from unittest import mock

    _, base = _greedy(tiny_llama, quantization="int4")
    with mock.patch.dict(
        os.environ, {"VDT_USE_PALLAS": "pallas_interpret"}
    ):
        eng, via_kernel = _greedy(tiny_llama, quantization="int4")
    layer = eng.executor.worker.runner.params["layers"][0]
    # int4 projections fuse like int8 on the kernel path (same concat
    # along the out dim preserves packing and group layout).
    assert layer["wqkv"].matmul == "pallas_interpret"
    assert layer["wqkv"].bits == 4
    assert via_kernel == base
