"""Compile-only rehearsal, without the chip: each configuration's
fused-decode step program, at its real widths and its chosen depth, is
compiled for a described v5e chip (``topologies.get_topology_desc``) and
``memory_analysis()`` is held to the chip's 16 GB with the weights and
the KV pool resident.  A depth that does not fit shows here, at no chip
time.  (What set the Mixtral depth in the end is not this program but the
loader's eager int8 quantization, whose float32 temporaries sit beside
the whole bf16 tree: PERF.md section 4.)

One file, the topology described inside a fixture: only one process may
load the TPU's library, and every xdist worker imports every test file.
"""

import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# every configuration file, in the manifest or waiting to be proved
CONFIGS = sorted(
    f[:-5] for f in os.listdir(os.path.join(REPO, "benchmark", "configs"))
    if f.endswith(".json"))
HBM_BYTES = 16e9  # benchmark/peaks.json, "TPU v5 lite"
POOL_PAGES = 4096  # 64 seqs x 1,024 tokens: enough to shape the program


class _Captured(Exception):
    pass


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
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip; keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _abstract_runner(conf_file: dict, tmp_path, monkeypatch):
    """The program's own ModelRunner with shapes in place of arrays:
    abstract weights (dummy init, int8 placement and projection fusion
    under ``eval_shape``) and an abstract KV pool."""
    import jax

    from vllm_distributed_tpu.config import EngineArgs
    from vllm_distributed_tpu.model_loader import loader
    from vllm_distributed_tpu.models.registry import get_model_class
    from vllm_distributed_tpu.worker.model_runner import ModelRunner

    monkeypatch.setenv("VDT_USE_PALLAS", "pallas")
    hf = {k: v for k, v in conf_file.items() if k != "benchmark"}
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    args = conf_file["benchmark"]["server_args"]
    opts = dict(zip(args[::2], args[1::2]))
    cfg = EngineArgs(
        model=str(tmp_path), skip_tokenizer_init=True, load_format="dummy",
        quantization=opts["--quantization"],
        max_model_len=int(opts["--max-model-len"]),
        num_kv_pages=POOL_PAGES,
    ).create_engine_config()
    runner = ModelRunner(cfg, attn_backend="pallas")
    model = get_model_class(cfg.model_config.architecture)(cfg.model_config)

    def build(rng):
        params = loader._place_tree(
            model, model.init_params(rng), None, None)
        return model.fuse_quantized_projections(params)

    runner.model = model
    runner.params = jax.eval_shape(build, jax.random.PRNGKey(0))
    runner._attn_fn = runner._pick_attn_fn()
    runner._kv_flush_fn = runner._pick_kv_flush_fn()
    runner._staged_decode = True
    runner.num_pages = POOL_PAGES
    runner.kv_caches = jax.eval_shape(
        lambda: runner.alloc_kv_pool(POOL_PAGES))
    return runner


@pytest.mark.parametrize("conf", CONFIGS)
def test_fused_decode_program_fits_the_chip(
        conf, one_chip, no_compile_cache, tmp_path, monkeypatch):
    import jax

    from vllm_distributed_tpu.worker.model_runner import ModelRunner

    conf_file = json.load(open(os.path.join(
        REPO, "benchmark", "configs", conf + ".json")))
    runner = _abstract_runner(conf_file, tmp_path, monkeypatch)
    real = ModelRunner._jit_decode_steps
    seen = {}

    def record(self, params, kv, packed, carry, **statics):
        seen.update(packed=packed, carry=carry, statics=statics)
        raise _Captured

    # The runner's own warm-up builds the packed step inputs and the
    # static arguments; the recorder takes them instead of running.
    monkeypatch.setattr(ModelRunner, "_jit_decode_steps", record)
    with pytest.raises(_Captured):
        runner.warmup_decode()
    assert seen["statics"]["k_steps"] == conf_file["benchmark"][
        "num_decode_steps"]

    def shaped(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    args = jax.tree.map(
        shaped, (runner.params, runner.kv_caches, seen["packed"],
                 seen["carry"]))
    compiled = real.lower(runner, *args, **seen["statics"]).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernels
    mem = compiled.memory_analysis()
    weights = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(runner.params))
    # arguments hold the weights and this test's small pool; on the chip
    # the pool takes what is left, so what must fit beside the weights is
    # the program's own temporaries and outputs.
    assert mem.argument_size_in_bytes >= weights
    beside = mem.temp_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes
    assert weights + beside < HBM_BYTES, (weights, beside)
    print(f"{conf}: weights {weights / 1e9:.2f} GB, program "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB")
