"""Persistent AOT program cache (worker/aot_cache.py): warm-restart
parity — a fresh engine process-equivalent (new runner, same cache dir)
must load serialized jax.export artifacts instead of retracing, and
produce bit-identical greedy tokens.  SURVEY.md §5.4 (compile cache /
warm restarts)."""

import os
from unittest import mock

from tests.utils import make_tiny_llama
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.worker.aot_cache import package_source_digest

PROMPTS = [[1, 5, 9, 23, 77, 41, 3], [7, 2, 88, 14]]


def _greedy(model_dir, cache_dir):
    env = {"VDT_AOT_CACHE": "1", "JAX_COMPILATION_CACHE_DIR": cache_dir}
    with mock.patch.dict(os.environ, env):
        engine = LLMEngine.from_engine_args(
            EngineArgs(
                model=model_dir,
                skip_tokenizer_init=True,
                num_kv_pages=64,
                max_model_len=128,
                max_num_seqs=8,
                num_decode_steps=4,
                warmup_decode=True,
            )
        )
        runner = engine.executor.worker.runner
        assert runner._aot.enabled
        # The artifact key carries the package's source digest.
        assert runner._aot.context.split("|")[0] == package_source_digest()
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


def test_aot_artifacts_roundtrip(tmp_path):
    model_dir = make_tiny_llama(str(tmp_path / "m"))
    cache = str(tmp_path / "cache")
    first = _greedy(model_dir, cache)
    aot_dir = os.path.join(cache, "aot")
    arts = [f for f in os.listdir(aot_dir) if f.endswith(".bin")]
    assert arts, "no AOT artifacts were exported"
    mtimes = {
        f: os.path.getmtime(os.path.join(aot_dir, f)) for f in arts
    }
    # Second engine: same cache dir, fresh runner — must LOAD, not
    # re-export (artifact mtimes unchanged), and match token-for-token.
    second = _greedy(model_dir, cache)
    assert second == first
    for f, t in mtimes.items():
        assert os.path.getmtime(os.path.join(aot_dir, f)) == t


def test_aot_corrupt_artifact_falls_back(tmp_path):
    model_dir = make_tiny_llama(str(tmp_path / "m"))
    cache = str(tmp_path / "cache")
    first = _greedy(model_dir, cache)
    aot_dir = os.path.join(cache, "aot")
    for f in os.listdir(aot_dir):
        if f.endswith(".bin"):
            with open(os.path.join(aot_dir, f), "wb") as fh:
                fh.write(b"garbage")
    assert _greedy(model_dir, cache) == first
