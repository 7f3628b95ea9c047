"""Persistent AOT program cache: serialized jax.export artifacts.

XLA's persistent compilation cache only removes the backend-compile
phase of a warm restart; re-tracing and lowering the 1B-class step
programs still costs ~6 s + ~3 s per program (measured on v5e, PERF.md),
which is the entire warm-TTFT story of SURVEY.md §5.4.  This cache
removes those phases too: at first compile the program is exported
(jax.export) over its FLAT argument leaves and serialized next to the
XLA cache; a later process deserializes (~0 s) and compiles the embedded
StableHLO (persistent-cache hit, sub-second) without ever tracing
Python.

Flat leaves are the boundary on purpose: jax.export's pytree
serialization needs per-type registration, and our QuantizedTensor
carries a Mesh in its auxdata, which does not serialize.  Flattening
at the call site sidesteps both (the artifact sees only arrays); the
output treedef is pickled alongside the artifact.

Scope: single-device programs (the runner gates on mesh is None).
Artifacts are keyed by a hash of the program description, every leaf's
shape/dtype, the jax/jaxlib/device identity, and the caller's context
(which carries a digest of this package's source) — any mismatch is a
clean miss, never a wrong program.  Every failure path falls back to
the normal jit call and reports itself through ``on_fallback``.
"""

from __future__ import annotations

import functools
import hashlib
import os
import pickle
from typing import Any, Callable

import jax

from vllm_distributed_tpu.logger import init_logger

logger = init_logger(__name__)


_PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.cache
def package_source_digest(root: str = _PACKAGE_ROOT) -> str:
    """sha256 over every ``*.py`` under ``root`` (this package) as it is
    on disk: relative path + bytes, in sorted order.  The step programs
    are traced from these files, so the digest stands in the artifact
    key where a version string cannot: it changes with any edit."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for name in sorted(filenames):
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()[:16]


class AotCache:
    def __init__(
        self,
        cache_dir: str | None,
        context: str = "",
        on_fallback: Callable[[str], None] = lambda kind: None,
    ) -> None:
        self.dir = os.path.join(cache_dir, "aot") if cache_dir else None
        # Told the kind ("aot_load" | "aot_export") each time the cache
        # carries on without an artifact it should have had.
        self._on_fallback = on_fallback
        # Caller-supplied identity of everything traced into the
        # programs BEYOND leaf shapes/dtypes: model hyperparameters
        # (two checkpoints can share every tensor shape but differ in
        # rope_theta etc.), kernel-backend selection, package version.
        # Without it a warm restart could silently replay a stale or
        # wrong program.
        self.context = context
        # key -> ready-to-call compiled flat function.
        self._mem: dict[str, Callable] = {}
        self._env = None

    @property
    def enabled(self) -> bool:
        return self.dir is not None

    def _env_key(self) -> str:
        if self._env is None:
            dev = jax.devices()[0]
            self._env = (
                f"{jax.__version__}:{jax.lib.__version__}:"
                f"{dev.platform}:{getattr(dev, 'device_kind', '')}"
            )
        return self._env

    def _key(self, desc: str, leaves: list) -> str:
        shapes = ";".join(
            f"{x.shape}:{x.dtype}" for x in leaves
        )
        return hashlib.sha256(
            f"{self._env_key()}|{self.context}|{desc}|{shapes}".encode()
        ).hexdigest()[:32]

    @staticmethod
    def _donated_leaf_indices(args: tuple, donate_args: tuple) -> tuple:
        out, off = [], 0
        for i, a in enumerate(args):
            n = len(jax.tree.leaves(a))
            if i in donate_args:
                out.extend(range(off, off + n))
            off += n
        return tuple(out)

    def call(
        self,
        desc: str,
        fn: Callable,
        args: tuple,
        donate_args: tuple = (),
        name: str = "call",
    ) -> Any:
        """Run ``fn(*args)`` through the artifact cache.

        ``fn`` must be a pure function of its positional pytree args
        (static configuration baked in via partial — and spelled into
        ``desc``, which keys the artifact together with all leaf
        shapes/dtypes).  ``donate_args`` are positional indices of args
        whose buffers are donated.  ``name`` is what the program is
        called wherever XLA names it (module ``jit_<name>`` in a
        profile, in HLO dumps): both the function that is exported and
        the one that wraps a loaded artifact carry it."""
        leaves, in_tree = jax.tree.flatten(args)
        key = self._key(desc, leaves)
        cached = self._mem.get(key)
        if cached is not None:
            return cached(leaves)
        dleaves = self._donated_leaf_indices(args, donate_args)
        path = os.path.join(self.dir, key)
        try:
            runner = self._load(path, dleaves, name)
        except FileNotFoundError:
            runner = None
        except Exception as e:  # noqa: BLE001 — stale/corrupt artifact
            logger.warning("AOT artifact %s unusable (%s); recompiling",
                           key, e)
            self._on_fallback("aot_load")
            runner = None
        if runner is None:
            runner = self._build_and_save(
                desc, fn, in_tree, leaves, dleaves, path, key, name
            )
        self._mem[key] = runner
        return runner(leaves)

    @staticmethod
    def _runner_from_exported(exp, out_tree, dleaves, name) -> Callable:
        def program(*leaves):
            return exp.call(*leaves)

        # jit names the XLA module after the function it wraps.
        program.__name__ = program.__qualname__ = name
        call = jax.jit(program, donate_argnums=dleaves)

        def run(leaves):
            return jax.tree.unflatten(out_tree, call(*leaves))

        run.program = call  # the jitted form, for tests to lower
        return run

    def _load(self, path: str, dleaves: tuple, name: str) -> Callable:
        with open(path + ".bin", "rb") as f:
            exp = jax.export.deserialize(bytearray(f.read()))
        with open(path + ".tree", "rb") as f:
            out_tree = pickle.load(f)
        return self._runner_from_exported(exp, out_tree, dleaves, name)

    def _build_and_save(
        self, desc, fn, in_tree, leaves, dleaves, path, key, name
    ) -> Callable:
        out_box = {}

        def flat_fn(*lv):
            out = fn(*jax.tree.unflatten(in_tree, list(lv)))
            out_leaves, out_box["tree"] = jax.tree.flatten(out)
            return out_leaves

        flat_fn.__name__ = flat_fn.__qualname__ = name
        jitted = jax.jit(flat_fn, donate_argnums=dleaves)
        try:
            exp = jax.export.export(jitted)(*leaves)
            os.makedirs(self.dir, exist_ok=True)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "wb") as f:
                f.write(exp.serialize())
            os.replace(tmp, path + ".bin")
            with open(tmp, "wb") as f:
                pickle.dump(out_box["tree"], f)
            os.replace(tmp, path + ".tree")
            logger.info("AOT artifact saved: %s (%s)", key, desc)
        except Exception as e:  # noqa: BLE001 — export is best-effort
            logger.warning("AOT export failed for %s (%s)", desc, e)
            self._on_fallback("aot_export")

            def run(leaves):
                out_leaves = jitted(*leaves)
                return jax.tree.unflatten(out_box["tree"], out_leaves)

            return run
        # Execute through exp.call ON THE FIRST RUN TOO: the exported
        # wrapper is a different XLA module than the plain jitted one,
        # and whichever form runs first is what lands in the persistent
        # XLA cache — compiling the jitted form here would leave a warm
        # RESTART paying a full backend compile for the exp.call form
        # (pre-round observation; not measured on this tree).
        return self._runner_from_exported(
            exp, out_box["tree"], dleaves, name
        )
