"""MoE (Mixtral / Qwen3-MoE) correctness + expert parallelism
(milestone config 5: Mixtral tp + EP; BASELINE.md).

Oracles: transformers on torch CPU for model math; single-device greedy
for sharding bit-compatibility on the 8-device virtual CPU mesh.
"""

import pytest

from tests.utils import (
    hf_greedy_generate,
    make_tiny_mixtral,
    make_tiny_qwen3_moe,
)
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams

PROMPTS = [[1, 5, 9, 23, 77, 41, 3], [7, 2, 88, 14], [100, 3, 9]]


@pytest.fixture(scope="module")
def tiny_mixtral(tmp_path_factory):
    # heads=8/kv=4 so tp up to 4 divides; 4 experts so ep 2/4 divide.
    return make_tiny_mixtral(
        str(tmp_path_factory.mktemp("mixtral")), heads=8, kv_heads=4
    )


@pytest.fixture(scope="module")
def tiny_qwen3_moe(tmp_path_factory):
    return make_tiny_qwen3_moe(str(tmp_path_factory.mktemp("qwen3moe")))


def _greedy(model_dir, tp=1, dp=1, ep=False, max_tokens=6):
    engine = LLMEngine.from_engine_args(
        EngineArgs(
            model=model_dir,
            skip_tokenizer_init=True,
            num_kv_pages=64,
            max_model_len=256,
            tensor_parallel_size=tp,
            data_parallel_size=dp,
            enable_expert_parallel=ep,
        )
    )
    for i, p in enumerate(PROMPTS):
        engine.add_request(
            f"r{i}",
            prompt_token_ids=p,
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=max_tokens, ignore_eos=True
            ),
        )
    done = {}
    while engine.has_unfinished_requests():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.outputs[0].token_ids
    return [done[f"r{i}"] for i in range(len(PROMPTS))]


def test_mixtral_greedy_matches_hf(tiny_mixtral):
    """Model math vs the transformers Mixtral implementation."""
    expected = [hf_greedy_generate(tiny_mixtral, p, 6) for p in PROMPTS]
    assert _greedy(tiny_mixtral) == expected


def test_qwen3_moe_greedy_matches_hf(tiny_qwen3_moe):
    """Qwen3-MoE (the reference's flagship family: Qwen3-Coder MoE,
    /root/reference/.env.server:11) vs transformers."""
    expected = [hf_greedy_generate(tiny_qwen3_moe, p, 6) for p in PROMPTS]
    assert _greedy(tiny_qwen3_moe) == expected


@pytest.fixture(scope="module")
def mixtral_baseline(tiny_mixtral):
    return _greedy(tiny_mixtral)


def test_mixtral_tp4_matches_single_device(tiny_mixtral, mixtral_baseline):
    """Non-EP: every expert split over tp like a dense MLP."""
    assert _greedy(tiny_mixtral, tp=4) == mixtral_baseline


def test_mixtral_ep4_matches_single_device(tiny_mixtral, mixtral_baseline):
    """EP: whole experts sharded over the tp axis (1 expert/device);
    GSPMD inserts the combine psum."""
    assert _greedy(tiny_mixtral, tp=4, ep=True) == mixtral_baseline


def test_mixtral_ep2_dp2_matches_single_device(tiny_mixtral, mixtral_baseline):
    """EP composed with data parallelism on the same mesh."""
    assert _greedy(tiny_mixtral, tp=2, dp=2, ep=True) == mixtral_baseline


def test_ep_requires_divisible_experts(tiny_mixtral):
    # 4 experts cannot shard 8 ways.
    with pytest.raises(Exception, match="divisible"):
        _greedy(tiny_mixtral, tp=8, ep=True)


def _greedy_env(model_dir, env, **kw):
    import os
    from unittest import mock

    with mock.patch.dict(os.environ, env):
        return _greedy(model_dir, **kw)


@pytest.fixture(scope="module")
def tiny_mixtral_16e(tmp_path_factory):
    # The VERDICT r3 #4 shape: 16 experts, top-2 — sparse dispatch must
    # do ~k/E of the dense expert FLOPs.
    return make_tiny_mixtral(
        str(tmp_path_factory.mktemp("mixtral16")),
        num_experts=16,
        top_k=2,
        heads=8,
        kv_heads=4,
    )


def test_ragged_matches_dense_16_experts(tiny_mixtral_16e):
    dense = _greedy_env(tiny_mixtral_16e, {"VDT_MOE_IMPL": "dense"})
    ragged = _greedy_env(tiny_mixtral_16e, {"VDT_MOE_IMPL": "ragged"})
    assert ragged == dense


def test_ragged_matches_dense_under_ep(tiny_mixtral_16e):
    dense = _greedy_env(tiny_mixtral_16e, {"VDT_MOE_IMPL": "dense"})
    ragged_ep = _greedy_env(
        tiny_mixtral_16e, {"VDT_MOE_IMPL": "ragged"}, tp=4, ep=True
    )
    assert ragged_ep == dense


def test_ragged_dispatch_is_sparse(tiny_mixtral_16e):
    """The ragged MLP must dispatch T*k rows through grouped matmuls —
    not T*E token-expert pairs like the dense path.  Asserted on the
    jaxpr (op shapes): CPU's ragged_dot lowering is masked-dense, so
    FLOP counts would not show sparsity here."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.models.registry import get_model_class

    config = EngineArgs(
        model=tiny_mixtral_16e, skip_tokenizer_init=True
    ).create_engine_config()
    model = get_model_class(config.model_config.architecture)(
        config.model_config
    )
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    layer = params["layers"][0]
    t = 64
    h = jnp.asarray(np.random.default_rng(0).standard_normal((t, 64)),
                    jnp.float32)

    jaxpr = jax.make_jaxpr(lambda x: model._mlp_ragged(x, layer))(h)

    def all_eqns(jxp):
        for eqn in jxp.eqns:
            yield eqn
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    yield from all_eqns(inner)

    ragged_eqns = [
        e for e in all_eqns(jaxpr.jaxpr)
        if e.primitive.name.startswith("ragged_dot")
    ]
    assert len(ragged_eqns) == 3, jaxpr  # w1, w3, w2
    for eqn in ragged_eqns:
        m = eqn.invars[0].aval.shape[0]
        assert m == t * model.top_k, (m, t, model.top_k)
