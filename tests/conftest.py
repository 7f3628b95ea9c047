"""Test bootstrap: force JAX onto CPU with 8 virtual devices so sharding
tests exercise real multi-device meshes without TPU hardware (SURVEY.md §4
item 4), and name the kernel backend CPU runs use — ``auto`` means the
compiled Pallas kernels and is an error off-TPU (tests that want the
interpreted kernels set ``pallas_interpret`` themselves).

The persistent compile cache is on for every test process, at the
resolver's path and with no size or compile-time floor: the suite builds
the same tiny-model programs hundreds of times, and a cache hit replaces
each repeat's XLA compile (a third of the engine-heavy files' wall time
on a cold cache)."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("VDT_USE_PALLAS", "reference")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

from vllm_distributed_tpu.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
