"""The generalised statement of sequence state (ISSUE 48, first asked
for as ISSUE 47): a layout is a list of state kinds, each with a shape,
a dtype and a count of layers of its own.  ``Lfm2MoeForCausalLM`` states its one kind through it and
its step programs are, to the byte of their lowered text, the programs
of the tree before the generalisation, for every bucket of the LFM2
cell's lattice and the fused scan; ``FalconH1ForCausalLM`` states two.

``python tests/test_seq_state_layout.py`` prints the digests of the
tree it is run in (how ``PARENT_DIGESTS`` was made, in a checkout of
commit 765e7dd)."""

import hashlib
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
SEQS = 64  # the cell's clients, the server's max_num_seqs


class _Captured(Exception):
    pass


def _lfm2_runner(tmp):
    import jax

    from vllm_distributed_tpu import testing
    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.models.registry import get_model_class
    from vllm_distributed_tpu.ops.attention import write_kv_pages
    from vllm_distributed_tpu.worker.model_runner import ModelRunner

    cfg = EngineArgs(
        model=testing.write_lfm2_config(tmp), skip_tokenizer_init=True,
        load_format="dummy", num_kv_pages=64, max_model_len=4096,
        max_num_seqs=SEQS, num_decode_steps=8,
    ).create_engine_config()
    runner = ModelRunner(cfg, attn_backend="reference")
    model = get_model_class(cfg.model_config.architecture)(cfg.model_config)
    runner.model = model
    runner.params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    runner._kv_write_fn = write_kv_pages
    runner._attn_fn = runner._pick_attn_fn()
    runner._kv_flush_fn = runner._pick_kv_flush_fn()
    runner._staged_decode = runner._kv_flush_fn is not None
    runner.num_pages = 64
    runner.kv_caches = jax.eval_shape(lambda: runner.alloc_kv_pool(64))
    return runner


def _lowered_text(runner, attr: str, trigger) -> str:
    """The lowered text of the step program ``trigger`` would run: the
    runner builds the packed inputs and the statics, a recorder takes
    them in the program's place."""
    import jax

    from vllm_distributed_tpu.worker.model_runner import ModelRunner

    real, seen = getattr(ModelRunner, attr), {}

    def record(self, params, kv, packed, carry, **statics):
        seen.update(packed=packed, carry=carry, statics=statics)
        raise _Captured

    setattr(ModelRunner, attr, record)
    try:
        with pytest.raises(_Captured):
            trigger()
    finally:
        setattr(ModelRunner, attr, real)
    for rid in list(runner.requests):
        runner._forget(rid)
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (runner.params, runner.kv_caches, seen["packed"], seen["carry"]))
    return real.lower(runner, *args, **seen["statics"]).as_text()


def _one_step(runner, lengths):
    from vllm_distributed_tpu.engine.scheduler import (
        NewRequestData,
        SchedulerOutput,
    )
    from vllm_distributed_tpu.sampling_params import SamplingParams
    from vllm_distributed_tpu.utils import cdiv

    news = [
        NewRequestData(
            req_id=f"__l{i}", prompt_token_ids=[1] * n, num_prompt_tokens=n,
            page_ids=[0] * runner._pages_bucket(cdiv(n + 1, runner.page_size)),
            num_computed_tokens=0, num_new_tokens=n,
            sampling_params=SamplingParams(temperature=0.0, max_tokens=2))
        for i, n in enumerate(lengths)]
    runner.execute_model(SchedulerOutput(
        step_id=0, new_requests=news,
        num_scheduled_tokens={r.req_id: r.num_new_tokens for r in news},
        total_num_scheduled_tokens=sum(lengths), decode_steps=1))


def program_digests(tmp: str) -> dict:
    """sha256 (16 hex digits) of the lowered text of every step program
    the LFM2 cell's lattice holds, at toy widths and the cell's real
    bucket sizes, and of the fused scan."""
    from benchmark import lattice

    with open(os.path.join(
            REPO, "benchmark", "traffic", "chat-closed-64.json")) as f:
        sweep = json.load(f)["warmup"]["sweep"]
    runner = _lfm2_runner(tmp)
    out = {}
    for t, q in lattice.buckets(SEQS, sweep):
        group = lattice.group_for(
            t, q, sweep["background"], sweep["seats"], sweep)
        if group is None:
            continue
        lengths = group + [1] * sweep["background"]
        text = _lowered_text(
            runner, "_jit_step_packed", lambda: _one_step(runner, lengths))
        assert f"_t{t}_" not in text  # the name is XLA's, not the text's
        out[f"step_t{t}_q{q}"] = hashlib.sha256(
            text.encode()).hexdigest()[:16]
    text = _lowered_text(runner, "_jit_decode_steps", runner.warmup_decode)
    out["decode_k8"] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return out


PARENT_DIGESTS = {
    "step_t64_q1": "aa6fd441ae6212fb",
    "step_t64_q2": "aa6fd441ae6212fb",
    "step_t64_q4": "aa6fd441ae6212fb",
    "step_t128_q2": "09859db7c7752f07",
    "step_t128_q4": "09859db7c7752f07",
    "step_t128_q8": "09859db7c7752f07",
    "step_t128_q16": "09859db7c7752f07",
    "step_t128_q32": "09859db7c7752f07",
    "step_t128_q64": "09859db7c7752f07",
    "step_t128_q128": "09859db7c7752f07",
    "step_t256_q16": "867c695344d92c7a",
    "step_t256_q32": "867c695344d92c7a",
    "step_t256_q64": "867c695344d92c7a",
    "step_t256_q128": "867c695344d92c7a",
    "step_t256_q256": "867c695344d92c7a",
    "step_t512_q32": "db5f4dafa103d92b",
    "step_t512_q64": "db5f4dafa103d92b",
    "step_t512_q128": "db5f4dafa103d92b",
    "step_t512_q256": "db5f4dafa103d92b",
    "step_t512_q512": "db5f4dafa103d92b",
    "step_t1024_q64": "bc5afe531b178e69",
    "step_t1024_q128": "bc5afe531b178e69",
    "step_t1024_q256": "bc5afe531b178e69",
    "step_t1024_q512": "bc5afe531b178e69",
    "step_t1024_q1024": "bc5afe531b178e69",
    "step_t2048_q128": "fdf886f6da08f41d",
    "step_t2048_q256": "fdf886f6da08f41d",
    "step_t2048_q512": "fdf886f6da08f41d",
    "step_t2048_q1024": "fdf886f6da08f41d",
    "step_t2048_q2048": "fdf886f6da08f41d",
    "decode_k8": "db89b283d881a882",
}


def test_lfm2_step_programs_are_the_parents(tmp_path):
    """With the XLA attention backend the text does not depend on the
    longest-chunk bucket, so buckets of one token count share a digest;
    every one of the lattice's is lowered all the same."""
    got = program_digests(str(tmp_path))
    assert len(got) >= 30, sorted(got)
    assert got == PARENT_DIGESTS


def test_lfm2_states_one_kind_of_two_rows(tmp_path):
    import jax
    import jax.numpy as jnp

    runner = _lfm2_runner(str(tmp_path))
    layout = runner.model.seq_state_layout()
    (kind,) = layout.kinds
    assert (kind.name, kind.shape, kind.num_layers, kind.dtype) == (
        "conv", (2, 64), 3, None)
    assert layout.num_states == 3
    assert layout.arrays(SEQS, jnp.float32) == [
        ((SEQS, 2, 64), jnp.dtype("float32"))] * 3
    # 3 layers x 2 rows x 64 lanes, at the model's dtype
    assert layout.bytes_by_kind(jnp.float32) == {"conv": 3 * 2 * 64 * 4}
    assert layout.seq_bytes(jnp.bfloat16) == 3 * 2 * 64 * 2
    assert runner._seq_state_bytes() == SEQS * 3 * 2 * 64 * 4
    states = runner.alloc_seq_states(SEQS)
    assert [s.shape for s in states] == [(SEQS, 2, 64)] * 3
    assert layout.by_kind(states) == {"conv": states}
    pool, behind = jax.eval_shape(lambda: runner.alloc_kv_pool(8))
    assert len(pool) == 1 and [s.shape for s in behind] == [
        (SEQS, 2, 64)] * 3


def test_falcon_h1_states_two_kinds(tmp_path):
    import jax.numpy as jnp

    from vllm_distributed_tpu import testing
    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.models.registry import get_model_class

    for stated, want in ((None, "bfloat16"), ("float32", "float32")):
        d = testing.write_falcon_h1_config(
            str(tmp_path), dtype="bfloat16", layers=3,
            **({"ssm_state_dtype": stated} if stated else {}))
        mc = EngineArgs(
            model=d, skip_tokenizer_init=True, load_format="dummy",
            num_kv_pages=16).create_engine_config().model_config
        layout = get_model_class(mc.architecture)(mc).seq_state_layout()
        conv, ssm = layout.kinds
        # x, B and C enter the convolution: 32 + 2 x 2 x 16 lanes
        assert (conv.name, conv.shape, conv.num_layers, conv.dtype) == (
            "conv", (3, 96), 3, None)
        assert (ssm.name, ssm.shape, ssm.num_layers, ssm.dtype) == (
            "ssm", (4, 8, 16), 3, want)
        item = jnp.dtype(want).itemsize
        assert layout.bytes_by_kind(jnp.bfloat16) == {
            "conv": 3 * 3 * 96 * 2, "ssm": 3 * 4 * 8 * 16 * item}
        shapes = layout.arrays(8, jnp.bfloat16)
        assert shapes == [((8, 3, 96), jnp.dtype("bfloat16"))] * 3 + [
            ((8, 4, 8, 16), jnp.dtype(want))] * 3
        kinds = layout.by_kind(list(range(6)))
        assert kinds == {"conv": [0, 1, 2], "ssm": [3, 4, 5]}


if __name__ == "__main__":
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("VDT_USE_PALLAS", "reference")
    print(json.dumps(program_digests(tempfile.mkdtemp()), indent=1))
