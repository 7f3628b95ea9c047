"""TP/DP sharding on the 8-device virtual CPU mesh (SURVEY.md §4 item 4):
sharded execution must be bit-compatible with single-device greedy."""

import pytest

from tests.utils import make_tiny_llama
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams

PROMPTS = [[1, 5, 9, 23, 77, 41, 3], [7, 2, 88, 14], [100, 3, 9]]


@pytest.fixture(scope="module")
def tiny_llama(tmp_path_factory):
    # heads=8 / kv_heads=4 so tp up to 4 divides both.
    return make_tiny_llama(
        str(tmp_path_factory.mktemp("llama_shard")), heads=8, kv_heads=4
    )


def _greedy(
    model_dir, tp=1, dp=1, env=None, quantization=None, kv_cache_dtype="auto"
):
    import os
    from unittest import mock

    with mock.patch.dict(os.environ, env or {}):
        return _greedy_inner(model_dir, tp, dp, quantization, kv_cache_dtype)


def _greedy_inner(
    model_dir, tp=1, dp=1, quantization=None, kv_cache_dtype="auto"
):
    engine = LLMEngine.from_engine_args(
        EngineArgs(
            model=model_dir,
            skip_tokenizer_init=True,
            num_kv_pages=64,
            max_model_len=256,
            tensor_parallel_size=tp,
            data_parallel_size=dp,
            quantization=quantization,
            kv_cache_dtype=kv_cache_dtype,
        )
    )
    for i, p in enumerate(PROMPTS):
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
    return [done[f"r{i}"] for i in range(len(PROMPTS))]


@pytest.fixture(scope="module")
def baseline(tiny_llama):
    return _greedy(tiny_llama, tp=1)


def test_tp4_matches_single_device(tiny_llama, baseline):
    assert _greedy(tiny_llama, tp=4) == baseline


def test_tp2_dp2_matches_single_device(tiny_llama, baseline):
    assert _greedy(tiny_llama, tp=2, dp=2) == baseline


def _late_arrival_run(model_dir, **kw):
    """Two requests decode; a third arrives once a scan is in flight
    (or after the first step of a blocking engine).  Returns (tokens,
    engine)."""
    from tests.utils import hold_results

    engine = hold_results(
        LLMEngine.from_engine_args(
            EngineArgs(
                model=model_dir,
                skip_tokenizer_init=True,
                num_kv_pages=64,
                max_model_len=256,
                **kw,
            )
        )
    )
    sp = SamplingParams(temperature=0.0, max_tokens=21, ignore_eos=True)
    for i, p in enumerate(PROMPTS[:2]):
        engine.add_request(f"r{i}", prompt_token_ids=p, sampling_params=sp)
    for _ in range(20):
        engine.step()
        if engine._pending or kw.get("num_decode_steps") == 1:
            break
    engine.add_request(
        "late", prompt_token_ids=PROMPTS[-1] + [9, 4], sampling_params=sp
    )
    done = {}
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.outputs[0].token_ids
    return done, engine


@pytest.mark.parametrize("tp,dp", [(2, 2), (4, 1)])
def test_single_step_behind_a_scan_on_a_mesh(tiny_llama, tp, dp):
    """PR 32 on a mesh: the unpacked dp-sharded ``_jit_step`` (dp > 1)
    and the packed one under tp take the same path as one chip — the
    single step that admits an arrival is issued behind the scan in
    flight, its decode rows read the replicated carry by row — and the
    tokens are those of the blocking single-device engine."""
    want, _ = _late_arrival_run(tiny_llama, num_decode_steps=1)
    got, engine = _late_arrival_run(
        tiny_llama,
        num_decode_steps=4,
        tensor_parallel_size=tp,
        data_parallel_size=dp,
    )
    assert got == want
    assert engine.pipeline_breaks == 0, engine.pipeline_break_reasons
    assert engine.dispatch_counts.get(("step", True), 0) >= 1
    assert engine.dispatch_counts.get(("decode", True), 0) >= 1


def test_tp4_pallas_matches_single_device(tiny_llama, baseline):
    """The PRODUCTION kernel path (interpret-mode Pallas attention +
    in-place KV writer) under shard_map on a real tp=4 mesh must be
    bit-identical to single-device greedy — the partitioning the real
    chip mesh relies on (GSPMD cannot partition the custom calls)."""
    assert (
        _greedy(
            tiny_llama, tp=4, env={"VDT_USE_PALLAS": "pallas_interpret"}
        )
        == baseline
    )


def test_pallas_dp_rejected(tiny_llama):
    """dp>1 would diverge the replicated KV pool under per-shard in-place
    writes; the runner must refuse loudly."""
    with pytest.raises(Exception, match="dp>1"):
        _greedy(
            tiny_llama,
            tp=2,
            dp=2,
            env={"VDT_USE_PALLAS": "pallas_interpret"},
        )


def test_pallas_dp_rejected_at_tp1(tiny_llama):
    """tp=1 must not bypass the dp rejection (the kernels would run
    unwrapped under a dp-sharded GSPMD mesh)."""
    with pytest.raises(Exception, match="dp>1"):
        _greedy(
            tiny_llama,
            tp=1,
            dp=2,
            env={"VDT_USE_PALLAS": "pallas_interpret"},
        )


def test_tp8_rejected_when_kv_heads_insufficient(tiny_llama):
    # kv_heads=4 cannot shard 8 ways; the mesh builds but XLA sharding of
    # the KV cache must fail loudly, not silently misshard.  (tp=8 also
    # equals the device count, so this documents the boundary.)
    with pytest.raises(Exception):
        _greedy(tiny_llama, tp=8)


def test_tp4_int8_kv_cache_matches_single_device(tiny_llama):
    """Quantized KV pool under tp=4 shard_map: per-shard per-head
    quantization at flush is the SAME reduction as single-device
    (scales are per kv head and heads shard whole), so greedy tokens
    must be bit-identical to the single-device int8-KV run."""
    env = {"VDT_USE_PALLAS": "pallas_interpret"}
    single = _greedy(tiny_llama, tp=1, env=env, kv_cache_dtype="int8")
    assert (
        _greedy(tiny_llama, tp=4, env=env, kv_cache_dtype="int8")
        == single
    )


def test_tp4_int8_pallas_matches_single_device(tiny_llama):
    """Sharded int8 weight streaming (VERDICT r3 #5): the Pallas int8
    matmul under shard_map at tp=4 must be bit-identical to the
    single-device int8 Pallas path (per-shard streaming changes neither
    quantization grouping nor accumulation order per output column)."""
    env = {"VDT_USE_PALLAS": "pallas_interpret"}
    single = _greedy(tiny_llama, tp=1, env=env, quantization="int8")
    assert (
        _greedy(tiny_llama, tp=4, env=env, quantization="int8") == single
    )
