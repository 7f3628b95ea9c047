"""Model construction + HF safetensors → sharded jax arrays.

The TPU analog of the reference's `collective_rpc("load_model")` step
(launch.py:292, SURVEY.md §5.4): weights come from a local HF snapshot
(safetensors shards), are read tensor-by-tensor on host, mapped through
the model's ``map_hf_name`` table, and placed onto the device mesh with
the model's ``partition_specs`` — each host materializes only its own
shard bytes when a mesh is given (host-parallel load).
"""

from __future__ import annotations

import glob
import os
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.models import get_model_class

logger = init_logger(__name__)


def resolve_model_dir(model: str) -> str:
    """Local dir, or an HF-hub snapshot already present in the cache."""
    if os.path.isdir(model):
        return model
    cache = os.environ.get(
        "HF_HUB_CACHE",
        os.path.join(
            os.environ.get(
                "HF_HOME", os.path.expanduser("~/.cache/huggingface")
            ),
            "hub",
        ),
    )
    repo_dir = os.path.join(cache, "models--" + model.replace("/", "--"))
    snapshots = sorted(glob.glob(os.path.join(repo_dir, "snapshots", "*")))
    if snapshots:
        return snapshots[-1]
    raise FileNotFoundError(
        f"model {model!r} is neither a local directory nor a cached HF "
        f"snapshot (no network egress; pre-download weights)"
    )


def _set_path(tree: dict, path: tuple, value) -> None:
    node = tree
    for key in path[:-1]:
        if isinstance(key, int):
            node = node[key]
        else:
            node = node.setdefault(key, {})
    node[path[-1]] = value


def _spec_for(path: tuple, specs: dict | None) -> P:
    if specs is None:
        return P()
    node: Any = specs
    try:
        for key in path:
            node = node[key]
        return node
    except (KeyError, IndexError, TypeError):
        return P()


def _in_dim_shards(spec: P, mesh: Mesh | None, ndim: int) -> int:
    """Mesh shards along the weight's input (second-to-last) dim."""
    from vllm_distributed_tpu.ops.quant import axis_shards

    if mesh is None:
        return 1
    t = tuple(spec)
    pos = ndim - 2
    if pos < 0 or pos >= len(t) or t[pos] is None:
        return 1
    return axis_shards(t[pos], mesh)


def _quantize_and_place(model, tensor, spec: P, mesh: Mesh | None, dtype):
    """Weight-only quantize one tensor and shard its q/scale parts.

    int4 group boundaries align with the deployment's tp shards (so the
    grouped dequant reshape never crosses devices in the decode hot
    path).  This makes int4 grouping a function of the tp layout — like
    an AWQ checkpoint generated for a target config, int4 outputs agree
    across tp sizes within quantization tolerance, not bit-for-bit
    (int8 is layout-independent and bit-identical across tp)."""
    from vllm_distributed_tpu.ops.quant import (
        pick_group_size,
        pick_matmul_mode,
        place_quantized,
        quantize,
    )

    bits = 8 if model.quant_method == "int8" else 4
    group = 0
    if bits == 4:
        group = pick_group_size(
            tensor.shape[-2], _in_dim_shards(spec, mesh, tensor.ndim)
        )
    qt = quantize(
        tensor,
        bits,
        group,
        dtype=dtype,
        matmul=pick_matmul_mode(model.quant_method),
    )
    if mesh is not None:
        qt = place_quantized(qt, spec, mesh)
    return qt


def _place_tree(model, params, specs, mesh: Mesh | None):
    """Recursive device placement for an in-memory param tree (dummy
    init), quantizing the model's QUANT_PARAMS leaves when configured."""
    quant = getattr(model, "quant_method", None)

    def rec(p, s, path):
        # Containers are drained as they are processed (entries nulled
        # right after use) so original full-precision device arrays free
        # eagerly — otherwise quantizing a model that nearly fills HBM
        # peaks at original + quantized and OOMs (e.g. 7B bf16 on v5e).
        if isinstance(p, dict):
            out = {}
            for k in list(p):
                out[k] = rec(
                    p[k],
                    s.get(k) if isinstance(s, dict) else None,
                    path + (k,),
                )
                p[k] = None
            return out
        if isinstance(p, list):
            out_list = []
            for i in range(len(p)):
                out_list.append(
                    rec(
                        p[i],
                        s[i] if isinstance(s, (list, tuple)) else None,
                        path + (i,),
                    )
                )
                p[i] = None
            return out_list
        if s is None and specs is not None:
            # partition_specs() drifted from init_params(): loading a
            # weight fully replicated at scale is a silent perf/memory
            # bug, so make the drift visible.
            logger.warning(
                "no partition spec for param %r; replicating", path
            )
        spec = s if s is not None else P()
        if quant and model.should_quantize(path):
            return _quantize_and_place(model, p, spec, mesh, model.dtype)
        if mesh is not None:
            return jax.device_put(p, NamedSharding(mesh, spec))
        return p

    return rec(params, specs, ())


def load_hf_weights(
    model,
    model_dir: str,
    *,
    mesh: Mesh | None = None,
    dtype: Any = None,
) -> dict:
    """Stream every tensor of every *.safetensors shard into the param
    tree.  Layer-norm/bias params keep float32 precision headroom is not
    needed — everything is cast to the model dtype."""
    from safetensors import safe_open

    dtype = jnp.dtype(dtype or model.dtype)
    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no *.safetensors under {model_dir}")
    # load_specs (when present) describes per-tensor placement DURING the
    # load, which can differ from the final partition_specs — e.g. MoE
    # expert tensors arrive unstacked and are stacked by finalize_params.
    if hasattr(model, "load_specs"):
        specs = model.load_specs()
    elif hasattr(model, "partition_specs"):
        specs = model.partition_specs()
    else:
        specs = None

    params: dict = {"layers": [{} for _ in range(model.num_layers)]}
    start = time.monotonic()
    n = 0
    cpu = jax.devices("cpu")[0]
    quant = getattr(model, "quant_method", None)
    for file in files:
        with safe_open(file, framework="flax") as f:
            for name in f.keys():
                mapped = model.map_hf_name(name)
                if mapped is None:
                    continue
                path, transform = mapped
                with jax.default_device(cpu):
                    tensor = f.get_tensor(name)
                    if transform == "T":
                        tensor = tensor.T
                    tensor = tensor.astype(dtype)
                spec = _spec_for(path, specs) if mesh is not None else P()
                if quant and model.should_quantize(path):
                    # Quantize per tensor DURING the stream so the full-
                    # precision model never materializes (the point of
                    # weight-only quant: 70B-class fits v5e HBM).
                    tensor = _quantize_and_place(
                        model, tensor, spec, mesh, dtype
                    )
                elif mesh is not None:
                    tensor = jax.device_put(
                        tensor, NamedSharding(mesh, spec)
                    )
                _set_path(params, path, tensor)
                n += 1
    if hasattr(model, "finalize_params"):
        params = model.finalize_params(params, mesh)
    logger.info(
        "loaded %d tensors from %d shard(s) in %.1fs",
        n,
        len(files),
        time.monotonic() - start,
    )
    return params


def get_model(
    model_config,
    *,
    load_format: str = "auto",
    mesh: Mesh | None = None,
    rng: jax.Array | None = None,
) -> tuple[Any, dict]:
    """Build (model, params).  load_format: "auto" reads safetensors,
    "dummy" random-initializes (tests, perf smoke)."""
    cls = get_model_class(model_config.architecture)
    model = cls(model_config)
    # Model-specific mesh preconditions (e.g. EP expert divisibility),
    # checked before any device placement so failures are clear errors
    # rather than GSPMD sharding failures mid-load.
    if mesh is not None and hasattr(model, "validate_mesh"):
        model.validate_mesh(mesh)
    if load_format == "dummy":
        rng = rng if rng is not None else jax.random.PRNGKey(model_config.seed)
        specs = (
            model.partition_specs()
            if hasattr(model, "partition_specs")
            else None
        )
        # One jitted program for the whole tree (init_params issues ~1
        # tiny RNG/cast op per tensor), with every tensor born on its
        # final sharding: under a mesh no device ever holds more than
        # its own shard, so a model larger than one chip can be built.
        out_shardings = None
        if mesh is not None:
            out_shardings = jax.tree_util.tree_map_with_path(
                lambda kp, _: NamedSharding(
                    mesh,
                    _spec_for(
                        tuple(getattr(k, "key", getattr(k, "idx", None))
                              for k in kp),
                        specs,
                    ),
                ),
                jax.eval_shape(model.init_params, rng),
            )
        params = jax.jit(model.init_params, out_shardings=out_shardings)(rng)
        if mesh is not None or getattr(model, "quant_method", None):
            params = _place_tree(model, params, specs, mesh)
        return model, params
    model_dir = resolve_model_dir(model_config.model)
    params = load_hf_weights(model, model_dir, mesh=mesh)
    return model, params
