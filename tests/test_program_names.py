"""Stable names for what runs on the chip (PR 26, part A): every step
program carries a name built from its kind and padded sizes through
both forms the AOT cache can run; the exported programs of a tiny Llama,
a tiny Mixtral and a tiny LongCat-Flash hold every ``jax.named_scope`` of
``models/common.SCOPES`` that their blocks have (that every Pallas kernel states a unique
literal name is held by ``tests/test_code_hygiene.py``).  The benchmark's readers (``benchmark/layer_metrics/_xplane.py``)
find device time by these names, so a rename here is a rename of a
metric's source."""

import glob
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest

from tests.utils import make_tiny_llama, make_tiny_mixtral
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.models import common
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.worker.aot_cache import AotCache
from vllm_distributed_tpu.worker.model_runner import program_name

MOE_SCOPES = {common.SCOPE_MOE_ROUTER, common.SCOPE_MOE_EXPERTS}
MLA_SCOPES = {common.SCOPE_MLA_ABSORB}
CONV_SCOPES = {common.SCOPE_SHORT_CONV}
SHARED_SCOPES = {common.SCOPE_MOE_SHARED}
SSM_SCOPES = {common.SCOPE_SSM_MIXER, common.SCOPE_SSM_SCAN}


def make_tiny_longcat(path: str) -> str:
    from vllm_distributed_tpu import testing

    return testing.write_longcat_config(path)


def make_tiny_lfm2(path: str) -> str:
    from vllm_distributed_tpu import testing

    return testing.write_lfm2_config(path)


def make_tiny_falcon_h1(path: str) -> str:
    from vllm_distributed_tpu import testing

    return testing.write_falcon_h1_config(path)


def make_tiny_deepseek_v3(path: str) -> str:
    from vllm_distributed_tpu import testing

    return testing.write_deepseek_v3_config(path)


@pytest.mark.parametrize(
    "kind,statics,dims,want",
    [
        ("decode", {"do_penalties": False, "do_top_k_p": False},
         dict(k=8, s=32, p=256), "vdt_decode_k8_s32_p256"),
        ("step", {"return_logprobs": True, "do_top_k_p": True},
         dict(t=512, q=256, s=32, p=256),
         "vdt_step_t512_q256_s32_p256_topkp_lp"),
        ("spec", {"spec": ()}, dict(t=64, q=4, s=8, p=8),
         "vdt_spec_t64_q4_s8_p8"),
    ],
)
def test_program_name_is_kind_sizes_and_the_flags_that_are_on(
        kind, statics, dims, want):
    assert program_name(kind, statics, **dims) == want
    assert re.match(r"^[A-Za-z0-9_]+$", want)  # a legal module name


def _module_name(runner, x) -> str:
    text = runner.program.lower(x).as_text()
    return re.search(r"module @(\S+)", text).group(1)


def test_program_name_survives_the_built_and_the_loaded_runner(tmp_path):
    x = jnp.arange(8.0)

    def double(v):
        return v * 2.0

    built = AotCache(str(tmp_path))
    assert built.call("d", double, (x,), name="vdt_test_k1").tolist() == (
        x * 2).tolist()
    (runner,) = built._mem.values()
    assert _module_name(runner, x) == "jit_vdt_test_k1"
    # A fresh cache over the same directory loads the artifact: the
    # wrapper around exp.call carries the name too, and the exported
    # module inside it kept the one it was built under.
    loaded = AotCache(str(tmp_path))
    with mock.patch.object(
            AotCache, "_build_and_save",
            side_effect=AssertionError("must load, not rebuild")):
        loaded.call("d", double, (x,), name="vdt_test_k1")
    (runner,) = loaded._mem.values()
    assert _module_name(runner, x) == "jit_vdt_test_k1"
    (artifact,) = glob.glob(str(tmp_path / "aot" / "*.bin"))
    with open(artifact, "rb") as f:
        exported = jax.export.deserialize(bytearray(f.read()))
    assert "module @jit_vdt_test_k1" in exported.mlir_module()


def _exported_programs(model_dir: str, cache_dir: str) -> dict[str, set]:
    """Serve one request through a tiny engine with the AOT cache on and
    return {module name: the scope parts on its operations' locations}
    of every program it exported."""
    env = {"VDT_AOT_CACHE": "1", "JAX_COMPILATION_CACHE_DIR": cache_dir}
    with mock.patch.dict(os.environ, env):
        seeded = not glob.glob(os.path.join(model_dir, "*.safetensors"))
        engine = LLMEngine.from_engine_args(EngineArgs(
            model=model_dir, skip_tokenizer_init=True, num_kv_pages=64,
            max_model_len=128, max_num_seqs=8, num_decode_steps=4,
            load_format="dummy" if seeded else "auto"))
        engine.add_request(
            "r", prompt_token_ids=[1, 5, 9, 23],
            sampling_params=SamplingParams(
                temperature=0.0, max_tokens=10, ignore_eos=True))
        while engine.has_unfinished_requests():
            engine.step()
    out = {}
    for path in glob.glob(os.path.join(cache_dir, "aot", "*.bin")):
        with open(path, "rb") as f:
            text = jax.export.deserialize(bytearray(f.read())).mlir_module()
        name = re.search(r"module @(\S+)", text).group(1)
        out[name] = {part for loc in re.findall(r'loc\("([^"]*)"', text)
                     for part in loc.split("/")}
    return out


@pytest.mark.parametrize("make,absent", [
    (make_tiny_llama,
     MOE_SCOPES | MLA_SCOPES | CONV_SCOPES | SHARED_SCOPES | SSM_SCOPES),
    (make_tiny_mixtral,
     MLA_SCOPES | CONV_SCOPES | SHARED_SCOPES | SSM_SCOPES),
    (make_tiny_longcat, CONV_SCOPES | SHARED_SCOPES | SSM_SCOPES),
    (make_tiny_lfm2, MLA_SCOPES | SHARED_SCOPES | SSM_SCOPES),
    (make_tiny_deepseek_v3, CONV_SCOPES | SSM_SCOPES),
    (make_tiny_falcon_h1,
     MOE_SCOPES | MLA_SCOPES | CONV_SCOPES | SHARED_SCOPES)],
    ids=["llama", "mixtral", "longcat", "lfm2", "deepseek_v3",
         "falcon_h1"])
def test_exported_step_programs_hold_every_scope(make, absent, tmp_path):
    programs = _exported_programs(
        make(str(tmp_path / "m")), str(tmp_path / "cache"))
    kinds = {re.match(r"jit_vdt_([a-z]+)_", n).group(1) for n in programs}
    assert kinds == {"step", "decode"}, sorted(programs)
    for name, parts in programs.items():
        missing = set(common.SCOPES) - absent - parts
        assert not missing, (name, sorted(missing))
        assert not (absent & parts), (name, sorted(absent & parts))
