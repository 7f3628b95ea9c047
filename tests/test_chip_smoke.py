"""chip_smoke.py and the rules it stands on: with no chip it refuses to
run, the CPU rehearsal drives phases A-C labelled ``cpu``, the compile
cache can be placed from outside, an edit to the package's source
changes the AOT artifact key, and ``auto`` never quietly means the XLA
reference."""

import json
import os
import subprocess
import sys

import jax
import pytest

from vllm_distributed_tpu import compile_cache
from vllm_distributed_tpu.ops import resolve_kernel_backend
from vllm_distributed_tpu.worker.aot_cache import (
    AotCache,
    package_source_digest,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(tmp_path, *args, timeout):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, SMOKE, "--out-dir", str(tmp_path / "out"), *args],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_default_path_refuses_to_run_without_a_chip(tmp_path):
    proc = _smoke(tmp_path, timeout=60)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr and "nothing was run" in proc.stderr
    # No phase ran and no result was printed.
    assert "phase" not in proc.stdout
    assert '"ok"' not in proc.stdout


@pytest.mark.slow  # ~95 s: two server boots with interpreted kernels
def test_cpu_rehearsal_runs_all_phases_labelled_cpu(tmp_path):
    proc = _smoke(tmp_path, "--cpu-rehearsal", timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert all(line.startswith("[cpu ") for line in lines[:-1])
    last = json.loads(lines[-1])
    assert last["ok"] is True and last["device"]["platform"] == "cpu"
    with open(tmp_path / "out" / "result.json") as f:
        report = json.load(f)
    assert report["label"] == "cpu"
    assert [p["phase"] for p in report["phases"]] == ["A", "B", "C"]
    assert all(p["ok"] for p in report["phases"])
    warm = report["phases"][2]
    assert warm["persistent_cache"]["writes"] == 0
    assert warm["persistent_cache"]["hits"] > 0
    assert warm["fallbacks"] == 0 and warm["aot_artifacts_saved"] == 0


# ---- a compile cache that can be placed from outside ----
@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


def test_cache_dir_from_env_is_left_to_jax(monkeypatch, tmp_path, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert compile_cache.compile_cache_dir() == str(tmp_path)  # no sub-dir
    assert config_updates == []


def test_cache_dir_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]


# ---- generated programs come from committed files only ----
def test_source_edit_changes_the_aot_key(tmp_path):
    pkg = tmp_path / "pkg"
    (pkg / "ops").mkdir(parents=True)
    (pkg / "ops" / "kernel.py").write_text("TILE = 512\n")
    (pkg / "model.py").write_text("x = 1\n")
    before = package_source_digest(str(pkg))
    (pkg / "ops" / "kernel.py").write_text("TILE = 256\n")
    package_source_digest.cache_clear()
    after = package_source_digest(str(pkg))
    assert before != after
    leaves = [jax.numpy.zeros((4, 4))]
    keys = {
        AotCache(str(tmp_path), context=f"{digest}|cfg")._key("step", leaves)
        for digest in (before, after)
    }
    assert len(keys) == 2


# ---- no fallback that hides the device ----
def test_auto_backend_is_an_error_off_tpu():
    with pytest.raises(RuntimeError, match="need a TPU.*'cpu'"):
        resolve_kernel_backend("auto")
    assert resolve_kernel_backend("reference") == "reference"
    with pytest.raises(ValueError, match="unknown VDT_USE_PALLAS"):
        resolve_kernel_backend("1")


# ---- one process for each chip ----
def test_serve_reaches_the_worker_without_initialising_a_backend(tmp_path):
    """Everything `serve` does before the worker's init_device — parse,
    build the engine config, import the engine, server and executor —
    must leave JAX's backends untouched: under --num-hosts > 1 the
    worker's jax.distributed.initialize has to come first, and a process
    that touched the chip early would hold it."""
    code = f"""
import sys
sys.path.insert(0, {REPO!r})
from vllm_distributed_tpu.config import EngineArgs
from vllm_distributed_tpu.entrypoints.cli import make_parser
from vllm_distributed_tpu.testing import write_llama_config

args = make_parser().parse_args(
    ["serve", write_llama_config({str(tmp_path / "m")!r}),
     "--load-format", "dummy", "--skip-tokenizer-init"])
args.model = args.model_tag
EngineArgs.from_cli_args(args).create_engine_config()
import vllm_distributed_tpu.engine.async_llm
import vllm_distributed_tpu.entrypoints.openai.api_server
import vllm_distributed_tpu.executor.multihost
import vllm_distributed_tpu.executor.uniproc
import jax._src.xla_bridge as xb
assert not xb._backends, list(xb._backends)
"""
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
