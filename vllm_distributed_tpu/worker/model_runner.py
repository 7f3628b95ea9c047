"""Model runner: SchedulerOutput → one fused, jitted device step.

Replaces the reference's GPU model runner (driven via
`collective_rpc("execute_model")`, launch.py:322-343) with a TPU-first
design (SURVEY.md §7): the whole step — embedding, every layer, paged KV
scatter, attention, and sampling — is ONE compiled XLA program with the KV
cache donated, so steady state is a single dispatch per scheduler step and
no per-layer host round trips.

Static-shape discipline (XLA compiles per shape): token count, sequence
count, pages-per-seq, and penalty-history lengths are padded to
power-of-two buckets, so the number of distinct compiled programs stays
logarithmic in batch size (SURVEY.md §7 hard part #2).

Workers mirror request state (token ids, page tables, cursors) so each
step's input is only the scheduler's delta — the control-plane economy the
reference gets by shipping SchedulerOutput, not tensors (SURVEY.md §2.5).
"""

from __future__ import annotations

import hashlib
import time
import zlib
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.engine.scheduler import SchedulerOutput
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.model_loader import get_model
from vllm_distributed_tpu.models.common import SCOPE_KV_WRITE, SCOPE_SAMPLER
from vllm_distributed_tpu.ops import resolve_kernel_backend
from vllm_distributed_tpu.ops.attention import (
    AttentionMetadata,
    SeqState,
    StepShape,
    bind_max_q,
    bind_step_counts,
    paged_attention_reference,
)
from vllm_distributed_tpu.ops.sampling import (
    SamplingMetadata,
    sample,
    spec_greedy_accept,
)
from vllm_distributed_tpu.outputs import ModelRunnerOutput
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.tracing import NOOP_SPAN, current_ctx, get_tracer
from vllm_distributed_tpu.utils import cdiv, next_power_of_2
from vllm_distributed_tpu.worker.telemetry import (
    DeviceTelemetry,
    local_memory_stats,
    peak_hbm_bandwidth,
)

logger = init_logger(__name__)

_MIN_TOKEN_BUCKET = 16
_MIN_SEQ_BUCKET = 8
_MIN_PAGES_BUCKET = 8
# HBM left free per device, beside weights and pool, for a step's
# activations and XLA temporaries (a 2048-token chunk at 7B width holds
# a few hundred MiB of grouped queries and dequantized weight tiles).
_STEP_WORKSPACE_BYTES = 1 << 30


def _log_device_memory(when: str) -> None:
    """One line with every local device's bytes_in_use — what a sharded
    load that quietly landed on device 0 looks like is in this line."""
    stats = local_memory_stats()
    if stats:
        logger.info(
            "HBM %s: bytes_in_use per device %s",
            when,
            [int(s.get("bytes_in_use", 0)) for s in stats],
        )


def pack_host_arrays(arrays: list[np.ndarray]) -> tuple[np.ndarray, tuple]:
    """Concatenate 4-byte-dtype host arrays into ONE int32 buffer.

    Every host→device transfer is a dispatch of its own, so every step
    ships exactly one buffer; `unpack_device_arrays` rebuilds
    the typed views inside the jitted program via static slicing +
    bitcasts.  Returns (buffer, spec) where spec is hashable (a static jit
    argument).
    """
    views: list[np.ndarray] = []
    spec: list[tuple] = []
    for a in arrays:
        a = np.ascontiguousarray(a)
        assert a.dtype.itemsize == 4, f"pack needs 4-byte dtypes, got {a.dtype}"
        v = a.view(np.int32).ravel()
        spec.append((a.shape, a.dtype.str, v.size))
        views.append(v)
    return np.concatenate(views), tuple(spec)


def unpack_device_arrays(packed: jax.Array, spec: tuple) -> list[jax.Array]:
    """Inverse of pack_host_arrays, inside jit (static offsets/shapes)."""
    out = []
    off = 0
    for shape, dtype_str, size in spec:
        seg = jax.lax.slice(packed, (off,), (off + size,))
        dt = np.dtype(dtype_str)
        if dt != np.int32:
            seg = jax.lax.bitcast_convert_type(seg, dt)
        out.append(seg.reshape(shape))
        off += size
    return out


@dataclass
class CachedReqState:
    req_id: str
    token_ids: list[int]  # prompt + everything sampled so far
    sampling_params: SamplingParams
    page_ids: list[int]
    num_computed: int
    prefill_target: int  # sample only once computed tokens reach this
    num_prompt: int  # true prompt/output boundary (stable across preemption)
    # Row of the device carry that holds this request's last sampled
    # token; -1 until a dispatch first includes the request.
    slot: int = -1


def _needs_top_k_p(sp: SamplingParams) -> bool:
    return sp.top_k > 0 or sp.top_p < 1.0 or sp.min_p > 0.0


def _needs_penalties(sp: SamplingParams) -> bool:
    return (
        sp.repetition_penalty != 1.0
        or sp.presence_penalty != 0.0
        or sp.frequency_penalty != 0.0
    )


def program_name(kind: str, statics: dict, **dims: int) -> str:
    """The stable name of one step program: its kind and the padded
    sizes that pick it, ``vdt_decode_k8_s32_p256``.  XLA calls the
    module ``jit_<name>``, which is how a profile, the benchmark's
    readers and the ``runner.dispatch`` spans tell programs apart.  The
    sampling flags that are on make a program of their own and a
    suffix."""
    name = f"vdt_{kind}_" + "_".join(f"{k}{v}" for k, v in dims.items())
    for flag, suffix in (
        ("do_penalties", "_pen"),
        ("do_top_k_p", "_topkp"),
        ("return_logprobs", "_lp"),
    ):
        if statics.get(flag):
            name += suffix
    return name


class ModelRunner:
    def __init__(
        self,
        config: EngineConfig,
        mesh: Any = None,
        attn_backend: str = "auto",
        telemetry: DeviceTelemetry | None = None,
    ) -> None:
        self.config = config
        self.mesh = mesh
        self.page_size = config.cache_config.page_size
        self.global_seed = config.model_config.seed
        self.model = None
        self.params = None
        # What every step program takes donated and returns rewritten:
        # the paged pools (a list, one a cache), and for a model that
        # keeps state a sequence beside its pages (a short convolution's
        # last rows) the pair (pools, states): one array a layer that
        # has such state, [seq bucket, rows, width], a request's row its
        # carry row (``CachedReqState.slot``).
        self.kv_caches: list | tuple | None = None
        self.requests: dict[str, CachedReqState] = {}
        self.attn_backend = resolve_kernel_backend(attn_backend)
        self._attn_fn = None
        # Device carry: every request's last sampled token, looked up by
        # row.  Each step program (fused scan or single step) takes the
        # array, reads the rows of the requests whose token the host has
        # not fetched yet (``CachedReqState.slot``), and returns it with
        # the rows of the requests it sampled for rewritten.  So a
        # dispatch of any kind can be built while another is in flight,
        # whatever joined or left the batch in between (SURVEY.md §3.3,
        # launch.py:298-302's max_concurrent_batches analog).
        self._carry: jax.Array | None = None
        self._free_slots: list[int] = []
        # Dispatches whose results have not been fetched: a dispatch
        # issued while this is non-zero overlaps another (the
        # ``runner.dispatch`` span's ``overlapped``).
        self._num_dispatched = 0
        self._num_fetched = 0
        # Input sharding (set at load): step inputs shard their leading
        # dim over the mesh's "dp" axis; with dp=1 they are replicated.
        self._input_spec = None
        self._dp = 1
        from vllm_distributed_tpu.worker.aot_cache import AotCache

        self._aot = AotCache(None)  # armed in load_model (single-chip)
        # XLA/device telemetry (ISSUE 12): every first execution of a
        # distinct (kind, statics) shape key is counted and timed as a
        # compile; per-step achieved-vs-roofline bandwidth rides along.
        self.telemetry = telemetry or DeviceTelemetry()
        self._compiled_keys: set[str] = set()
        self._param_bytes = 0
        self._kv_token_bytes = 0
        self._peak_bw = 0.0
        # Tiered KV cache (ISSUE 14): host-DRAM copies of spilled pages,
        # slot id -> per-layer pytree of [2, page, width] host arrays.
        # Bounded by the driver-side allocator's host pool — slots are
        # only reused after their restore shipped, so the dict can never
        # exceed the configured host page count (plus entries whose
        # slot the driver freed without a restore, until that slot's
        # next spill overwrites them).
        self._host_kv: dict[int, Any] = {}

    # ---- lifecycle (the collective_rpc verbs, launch.py:290-292) ----
    def load_model(self, load_format: str = "auto") -> None:
        self.model, self.params = get_model(
            self.config.model_config, load_format=load_format, mesh=self.mesh
        )
        # Single-chip fast path: fuse quantized Q|K|V and gate|up so
        # each layer issues one weight-streaming kernel call instead of
        # three/two (bit-identical results).  Self-gating: fusable()
        # requires w.mesh is None — a tp-sharded out-dim concat would
        # interleave shards of q|k|v instead of sharding the fused
        # tensor (llama.py fuse_quantized_projections).
        if hasattr(self.model, "fuse_quantized_projections"):
            self.params = self.model.fuse_quantized_projections(self.params)
        self._refuse_unsupported_latent()
        self._refuse_unsupported_seq_state()
        self._refuse_unsupported_spec_decode()
        self._attn_fn = self._pick_attn_fn()
        # Two writers: prefill/mixed steps keep the functional XLA
        # scatter (batched, GSPMD-partitionable — the aliased Pallas
        # writer's grid=(T,) would issue T serialized per-token DMAs per
        # layer on a 2048-token chunk); the fused decode scan uses the
        # in-place Pallas writer, where XLA's non-aliased scatter copies
        # the whole pool per layer per micro-step.
        from vllm_distributed_tpu.ops.attention import write_kv_pages

        self._kv_write_fn = write_kv_pages
        # Staged decode writes (side buffer + per-dispatch flush) ride
        # the Pallas attention path (the flush kernel is its writer);
        # the XLA reference path keeps the in-loop functional scatter.
        self._kv_flush_fn = self._pick_kv_flush_fn()
        self._staged_decode = self._kv_flush_fn is not None
        if self.mesh is not None:
            self._dp = self.mesh.shape.get("dp", 1)
            if self._dp & (self._dp - 1):
                raise ValueError(
                    f"dp axis size must be a power of 2, got {self._dp} "
                    "(power-of-two shape buckets must stay divisible)"
                )
            if (
                self.config.scheduler_config.spec_ngram_k > 0
                and self._dp > 1
            ):
                raise ValueError(
                    "speculative decoding does not support dp>1 (the "
                    "verify pass ships one packed replicated buffer; a "
                    "dp-sharded variant would need per-shard verify "
                    "windows) — use dp=1 or --speculative-ngram-k 0"
                )
            tp = self.mesh.shape.get("tp", 1)
            if tp > 1 and not self._latent and self.model.num_kv_heads % tp:
                # The combined pool shards its flat head×dim lanes; a tp
                # that does not divide the head count would silently
                # split heads mid-lane instead of failing.
                raise ValueError(
                    f"tp={tp} must divide num_kv_heads="
                    f"{self.model.num_kv_heads} to shard the KV cache"
                )
            axis = "dp" if self._dp > 1 else None
            self._input_spec = NamedSharding(self.mesh, P(axis))
        self._shard_kernels()
        # Persistent AOT program cache (§5.4 warm restarts): skips
        # trace+lower on reboot, not just XLA compile.  Single-device
        # only — a meshed program's shardings don't round-trip through
        # the flat-leaf export boundary.  "auto" = TPU only (CPU test
        # runs would litter the cache with host-specific artifacts).
        from vllm_distributed_tpu import envs
        from vllm_distributed_tpu.compile_cache import compile_cache_dir
        from vllm_distributed_tpu.ops.quant import pick_matmul_mode
        from vllm_distributed_tpu.worker.aot_cache import (
            AotCache,
            package_source_digest,
        )

        mode = envs.VDT_AOT_CACHE
        use_aot = self.mesh is None and (
            mode == "1" or (mode == "auto" and jax.default_backend() == "tpu")
        )
        # Everything traced into the programs that leaf shapes/dtypes
        # do NOT capture: model hyperparameters (rope/eps/soft-cap
        # constants can differ between same-shaped checkpoints), the
        # kernel backend, quantization scheme, cache dtype, and the
        # source the programs are traced from (so an edit to any model,
        # kernel or runner file is a clean miss — a parent commit's
        # artifact is never replayed for its child).
        mc = self.config.model_config
        try:
            hf_id = mc.hf_config.to_json_string(use_diff=False)
        except Exception:  # noqa: BLE001 — exotic config objects
            hf_id = repr(mc.hf_config.__dict__)
        context = "|".join(
            (
                package_source_digest(),
                hashlib.sha256(hf_id.encode()).hexdigest()[:16],
                str(mc.quantization),
                str(mc.dtype),
                self.config.cache_config.cache_dtype,
                self.attn_backend,
                str(self.page_size),
            )
        )
        self._aot = AotCache(
            compile_cache_dir() if use_aot else None,
            context=context,
            on_fallback=self.telemetry.record_fallback,
        )
        logger.info(
            "kernel backends: attention=%s kv_write=%s quant_matmul=%s "
            "aot_cache=%s",
            self.attn_backend,
            "kv_flush:" + self.attn_backend
            if self._staged_decode
            else "xla_scatter",
            pick_matmul_mode(mc.quantization) if mc.quantization else None,
            "on" if use_aot else "off",
        )
        _log_device_memory("after load")
        # Device-telemetry constants (ISSUE 12): resident param bytes
        # and per-KV-token bytes for the step roofline estimate, peak
        # HBM bandwidth from the device kind.  All best-effort — the
        # gauges degrade to 0, never fail the load.
        try:
            self._param_bytes = sum(
                int(x.size) * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(self.params)
                if hasattr(x, "size") and hasattr(x, "dtype")
            )
        except Exception:  # noqa: BLE001 — telemetry only
            self._param_bytes = 0
        # (an int8 pool's scale planes are left out, as they always were)
        self._kv_token_bytes = self._kv_layout.token_bytes(
            1 if self.kv_cache_quantized
            else jnp.dtype(self.kv_cache_dtype()).itemsize
        )
        try:
            self._peak_bw = peak_hbm_bandwidth(
                getattr(jax.local_devices()[0], "device_kind", "")
            )
        except Exception:  # noqa: BLE001 — telemetry only
            self._peak_bw = 0.0

    # ---- device telemetry helpers (ISSUE 12) ----
    def _run_program(
        self,
        kind: str,
        method: str,
        name: str,
        statics: dict,
        args: tuple,
        so: SchedulerOutput,
        **counts: int,
    ):
        """Run one step program, ``method`` of this class with
        ``statics`` baked in, under its stable ``name``: through the AOT
        cache where that is armed, else through the method's own jit
        (which XLA then names after the method: the name stays the
        span's).  The call is a ``runner.dispatch`` span, which carries
        ``counts``: the step's sizes and what its attention function and
        model say of it (``_step_counts``).

        The FIRST execution of each distinct (kind, statics) key is
        timed and recorded as an XLA compile (trace+lower+compile
        dominate that call); later calls are passthrough.  AOT-cache
        hits still count: a warm artifact load is exactly the stall
        class the counter tracks, just cheaper — the histogram shows
        the difference."""
        shape_key = f"{sorted(statics.items())}"
        key = f"{kind}:{name}:{shape_key}"
        first_call = key not in self._compiled_keys

        def run():
            if self._aot.enabled:
                return self._aot.call(
                    f"{method}:{shape_key}",
                    partial(
                        getattr(type(self), method).__wrapped__,
                        self,
                        **statics,
                    ),
                    args,
                    donate_args=(1,),
                    name=name,
                )
            return getattr(self, method)(*args, **statics)

        tracer = get_tracer()
        # Each counter has one writer at a time (this thread; the thread
        # that fetches), so their difference needs no lock.
        overlapped = self._num_dispatched > self._num_fetched
        self._num_dispatched += 1
        attrs = (
            dict(
                step_id=so.step_id,
                program=name,
                first_call=first_call,
                overlapped=overlapped,
                **counts,
            )
            if tracer.enabled
            else {}
        )
        with tracer.span("runner.dispatch", **attrs):
            if not first_call:
                return run()
            t0 = time.perf_counter()
            out = run()
            self._compiled_keys.add(key)
            seconds = time.perf_counter() - t0
            self.telemetry.record_compile(kind, seconds, key)
            logger.info("first call of %s: %.2f s", name, seconds)
            return out

    def _record_step_bw(
        self, seconds: float, kv_tokens: int, passes: int = 1
    ) -> None:
        """Achieved-vs-roofline gauge: weights + live-KV bytes per HBM
        pass over the measured step wall time (an estimate — exact DMA
        accounting would need a profiler, which /debug/profile is for)."""
        est = passes * (
            self._param_bytes + self._kv_token_bytes * max(kv_tokens, 0)
        )
        self.telemetry.record_step(seconds, int(est), self._peak_bw)

    def _shard_kernels(self) -> None:
        """Partition the Pallas kernels over the mesh "tp" axis.

        GSPMD cannot partition a Pallas custom call, so at tp>1 the
        kernels are wrapped in jax.shard_map to run on local head shards
        (ops/sharded.py).  The XLA reference path needs no wrapping —
        GSPMD partitions gather/scatter/einsum natively.
        """
        if self.mesh is None:
            return
        from vllm_distributed_tpu.ops import sharded
        from vllm_distributed_tpu.ops.attention import (
            paged_attention_reference,
            write_kv_pages,
        )

        uses_pallas = (
            self._attn_fn is not paged_attention_reference
            or self._staged_decode
        )
        if not uses_pallas:
            return
        # dp must be rejected regardless of tp (at tp==1 the kernels
        # would otherwise run unwrapped under a dp-sharded GSPMD mesh).
        if self._dp > 1:
            raise ValueError(
                "the Pallas backend does not support dp>1 (the KV pool is "
                "replicated over dp; per-shard in-place writes would "
                "diverge the replicas) — use dp=1 or attn_backend="
                "'reference'"
            )
        if self.mesh.shape.get("tp", 1) <= 1:
            return
        sharded._check_divisible(
            self.mesh, self.model.num_heads, self.model.num_kv_heads
        )
        if self._attn_fn is not paged_attention_reference:
            self._attn_fn = sharded.shard_attention(self._attn_fn, self.mesh)
        if self._kv_flush_fn is not None:
            self._kv_flush_fn = sharded.shard_kv_flush(
                self._kv_flush_fn, self.mesh
            )

    @property
    def _kv_layout(self):
        """What the model states of its caches (``KVCacheLayout``)."""
        return self.model.kv_cache_layout()

    @property
    def _latent(self) -> bool:
        """A one-plane layout is a latent (MLA) cache, with kernels and
        limits of its own."""
        return self._kv_layout.planes == 1

    @property
    def _state_layout(self):
        """What the model states of per-sequence state
        (``SeqStateLayout``), None for a model that keeps none."""
        layout = getattr(self.model, "seq_state_layout", None)
        return layout() if layout is not None else None

    def _seq_state_bytes(self) -> int:
        layout = self._state_layout
        if layout is None:
            return 0
        return self._seq_bucket() * layout.seq_bytes(self.model.dtype)

    def _refuse_unsupported_seq_state(self) -> None:
        """What state that is not pages of tokens cannot do yet fails
        here, at load, with a message that names it."""
        if self._state_layout is None:
            return
        cc = self.config.cache_config
        why = (
            "the model keeps per-sequence state beside its paged cache "
            "(worker/model_runner.py)"
        )
        if cc.enable_prefix_caching:
            raise ValueError(
                "--enable-prefix-caching is not supported: a prefix hit "
                "would resume a sequence whose state at that position "
                f"was never kept; {why}"
            )
        if self.config.scheduler_config.spec_ngram_k > 0:
            raise ValueError(
                "speculative decoding (--speculative-ngram-k) is not "
                "supported: a rejected draft would have to roll the "
                f"state back; {why}"
            )
        if getattr(cc, "kv_spill_host_pages", 0):
            raise ValueError(
                "--kv-spill-host-pages is not supported: the spill tier "
                f"moves pages, not sequence state; {why}"
            )
        if self.config.kv_transfer_config is not None:
            raise ValueError(
                "--kv-transfer-config is not supported: KV page transfer "
                "(engine/kv_transfer.py) moves pages, not sequence "
                f"state; {why}"
            )

    def _refuse_unsupported_spec_decode(self) -> None:
        """A model that says why it has no speculative path
        (``refuses_spec_decode``) fails here, at load, with its reason."""
        why = getattr(self.model, "refuses_spec_decode", None)
        if why and self.config.scheduler_config.spec_ngram_k > 0:
            raise ValueError(
                "speculative decoding (--speculative-ngram-k) is not "
                f"supported for {type(self.model).__name__}: {why}"
            )

    def _refuse_unsupported_latent(self) -> None:
        """What a latent (one-plane) pool cannot do fails here, at load,
        with a message that names it."""
        if not self._latent:
            return
        cc = self.config.cache_config
        if self.kv_cache_quantized:
            raise ValueError(
                "--kv-cache-dtype int8 is not supported for a latent "
                "(MLA) cache: its rows have no heads to scale by, and "
                "no int8 form of them is written (worker/model_runner.py)"
            )
        if getattr(cc, "kv_spill_host_pages", 0):
            raise ValueError(
                "--kv-spill-host-pages is not supported for a latent "
                "(MLA) cache: the spill tier has not been carried over "
                "to one-plane pools (worker/model_runner.py)"
            )
        if self.config.kv_transfer_config is not None:
            raise ValueError(
                "--kv-transfer-config is not supported for a latent "
                "(MLA) cache: KV page transfer (engine/kv_transfer.py) "
                "has not been carried over to one-plane pools"
            )

    def _pick_attn_fn(self):
        """The backend's attention function for the model's cache, with
        what a step of it counts bound to the model's widths
        (``step_counts``) where its module says that."""
        if self._latent:
            from vllm_distributed_tpu.ops.attention import (
                mla_attention_reference,
            )

            if self.attn_backend == "reference":
                return mla_attention_reference
            from vllm_distributed_tpu.ops.pallas.mla_attention import (
                mla_attention as compiled,
                mla_attention_cpu as interpreted,
            )
        else:
            if self.attn_backend == "reference":
                return paged_attention_reference
            from vllm_distributed_tpu.ops.pallas.paged_attention import (
                paged_attention as compiled,
                paged_attention_cpu as interpreted,
            )
        return bind_step_counts(
            interpreted if self.attn_backend == "pallas_interpret"
            else compiled,
            self.model,
        )

    def _step_counts(
        self, kind: str, num_new, starts, *, rows_pad: int, seqs_pad: int,
        max_q_pad: int, pages_pad: int,
    ) -> dict:
        """What a step about to be dispatched counts: its
        ``runner.dispatch`` attributes beside its sizes.  The runner
        says what it knows of the step (``StepShape``); the attention
        function it picked and the model answer with attributes and
        with increments of the worker's counters, which are passed on
        unread.  The runner's own part is the sequence state it lays
        out: ``state_rows`` (every row's is read and rewritten) and
        ``state_fresh`` (those that start at position 0 and read
        nothing of theirs), the counter's fresh | carried."""
        shape = StepShape(
            kind=kind, num_new=num_new, starts=starts, rows_pad=rows_pad,
            seqs_pad=seqs_pad, max_q_pad=max_q_pad, pages_pad=pages_pad,
            page_size=self.page_size,
            cache_itemsize=jnp.dtype(self.kv_cache_dtype()).itemsize,
            head_shards=(
                self.mesh.shape.get("tp", 1) if self.mesh is not None else 1
            ),
        )
        attrs, increments = {}, {}
        attn_counts = getattr(self._attn_fn, "step_counts", None)
        if attn_counts is not None:
            attrs, increments = attn_counts(shape)
        model_counts = getattr(self.model, "step_counts", None)
        if model_counts is not None:
            more, added = model_counts(shape, self.params)
            attrs, increments = {**attrs, **more}, {**increments, **added}
        if self._state_layout is not None:
            fresh = sum(1 for lo in starts if lo == 0)
            attrs.update(state_rows=len(starts), state_fresh=fresh)
            increments["seq_state_rows"] = {
                "fresh": fresh, "carried": len(starts) - fresh
            }
        for counter, by_label in increments.items():
            self.telemetry.record(counter, by_label)
        return attrs

    def _pick_kv_flush_fn(self):
        """Per-dispatch flush of the staged decode side buffers.  None
        under the XLA reference backend, whose decode scan scatters each
        micro-step's rows into the pool itself."""
        if self.attn_backend == "reference":
            return None
        from vllm_distributed_tpu.ops.pallas.kv_flush import (
            kv_flush,
            kv_flush_cpu,
        )

        if self.attn_backend == "pallas_interpret":
            return kv_flush_cpu
        return kv_flush

    @property
    def kv_cache_quantized(self) -> bool:
        """--kv-cache-dtype int8: pool stores int8 rows + per-(token,
        kv-head) f32 scales; staged side buffers stay in model dtype
        (quantized once per dispatch at flush, not per micro-step)."""
        return self.config.cache_config.cache_dtype == "int8"

    def kv_cache_dtype(self):
        """Pool dtype: cache_config.cache_dtype, "auto" = model dtype.
        A narrower cache (e.g. bfloat16 under a float32 model) doubles
        the KV capacity; kernels read/write the pool dtype directly."""
        name = self.config.cache_config.cache_dtype
        if name in (None, "auto"):
            return self.model.dtype
        return jnp.dtype(name)

    def kv_cache_bytes_per_page(self) -> int:
        return self.page_size * self._kv_layout.token_bytes(
            jnp.dtype(self.kv_cache_dtype()).itemsize,
            self.kv_cache_quantized,
        )

    def _pipeline_reserve_bytes(self) -> int:
        """HBM held by in-flight fused-decode dispatches beyond the pool:
        each concurrent dispatch's program keeps its staged side buffers
        ([S, planes, K, width] per cache) live for the program's duration.  At
        7B/K=32/depth-6 this is ~3 GiB — unreserved, the allocator
        thrashes mid-serve (measured: multi-second stalls)."""
        if not getattr(self, "_staged_decode", False):
            return 0
        sc = self.config.scheduler_config
        # Side buffers stay in MODEL dtype even for an int8 pool.
        side_dtype = (
            self.model.dtype
            if self.kv_cache_quantized
            else self.kv_cache_dtype()
        )
        side = (
            sc.max_num_seqs
            * sc.num_decode_steps
            * self._kv_layout.token_bytes(jnp.dtype(side_dtype).itemsize)
        )
        return side * max(sc.max_concurrent_dispatches, 1)

    def profile_num_pages(self) -> int:
        """Derive the KV pool size from free HBM (the analog of
        gpu_memory_utilization profiling in the inherited engine).

        Every local device reports ``memory_stats()``; the pool is sized
        for the fullest one.  From its utilization-scaled limit come off:
        what is resident (the weights), the pipelined dispatches' side
        buffers, and _STEP_WORKSPACE_BYTES for the activations and XLA
        temporaries of the largest step.  A backend without
        ``memory_stats`` (the CPU backend) cannot be profiled: the caller
        passes --num-kv-pages."""
        cc = self.config.cache_config
        if cc.num_pages is not None:
            return cc.num_pages
        stats = local_memory_stats()
        if len(stats) != jax.local_device_count() or not all(
            "bytes_limit" in s for s in stats
        ):
            raise RuntimeError(
                f"the {jax.default_backend()} backend reports no "
                "memory_stats() to size the KV pool from; pass "
                "--num-kv-pages"
            )
        shards = 1
        if self.mesh is not None and "tp" in self.mesh.shape:
            shards = self.mesh.shape["tp"]
        # Stats are per device: the side-buffer reserve shards with tp.
        reserve = (
            self._pipeline_reserve_bytes() // shards
            + _STEP_WORKSPACE_BYTES
            + self._seq_state_bytes()
        )
        free = min(
            int(s["bytes_limit"] * cc.hbm_utilization)
            - int(s.get("bytes_in_use", 0))
            for s in stats
        ) - reserve
        per_device_page = self.kv_cache_bytes_per_page() // shards
        num_pages = free // per_device_page
        if num_pages < 16:
            raise RuntimeError(
                f"{free / 2**30:.2f} GiB of HBM is left for the KV pool "
                f"after weights and a {reserve / 2**30:.2f} GiB reserve: "
                f"room for {max(num_pages, 0)} pages, need at least 16"
            )
        logger.info(
            "KV pool: %d pages × %d tokens (%.2f GiB per device, from "
            "memory_stats of %d device(s); %.2f GiB reserved for side "
            "buffers and step workspace)",
            num_pages,
            self.page_size,
            num_pages * per_device_page / 2**30,
            len(stats),
            reserve / 2**30,
        )
        return int(num_pages)

    def alloc_kv_pool(self, num_pages: int) -> list | tuple:
        """What ``kv_caches`` holds at ``num_pages`` pages: the paged
        pools, and behind them the sequence states of a model that has
        them, at the sequence bucket."""
        pools = self._alloc_pages(num_pages)
        if self._state_layout is None:
            return pools
        return (pools, self.alloc_seq_states(self._seq_bucket()))

    def _alloc_pages(self, num_pages: int) -> list:
        """Allocate a paged KV pool: as many [planes, P, page, width]
        arrays as the model states caches (``kv_cache_layout``; for K
        and V of every head one a layer, see ops/attention.py — K/V
        fused so a page is ONE DMA, flat head lanes unpadded), sharded
        per the model's kv_cache_spec.  Used for the serving cache and for aux-forward
        scratch pools — one definition of the layout."""
        m = self.model
        layout = self._kv_layout
        shape = layout.pool_shape(num_pages, self.page_size)
        # Zeros are created on their final sharding: a full per-layer
        # pool staged on device 0 first would be a transient of tp× one
        # shard on a device the weights already fill.
        sharding = None
        if self.mesh is not None:
            sharding = NamedSharding(self.mesh, m.kv_cache_spec())
        dtype = self.kv_cache_dtype()

        if self.kv_cache_quantized:
            # (int8 data, per-head f32 scales) — the scale plane's lane
            # axis is kv heads, sharding like the data plane's HD lanes.
            s_shape = layout.scales_shape(num_pages, self.page_size)

            def alloc():
                return (
                    jnp.zeros(shape, jnp.int8, device=sharding),
                    jnp.zeros(s_shape, jnp.float32, device=sharding),
                )

        else:

            def alloc():
                return jnp.zeros(shape, dtype, device=sharding)

        return [alloc() for _ in range(layout.num_caches)]

    def alloc_seq_states(self, num_seqs: int) -> list | None:
        """The model's per-sequence state at ``num_seqs`` rows (zeros;
        nothing ever has to clear a row again), None for a model that
        keeps none."""
        layout = self._state_layout
        if layout is None:
            return None
        return [
            jnp.zeros(shape, dtype)
            for shape, dtype in layout.arrays(num_seqs, self.model.dtype)
        ]

    def init_kv_cache(self, num_pages: int) -> None:
        self.num_pages = num_pages
        self.kv_caches = self.alloc_kv_pool(num_pages)
        layout = self._state_layout
        if layout is not None:
            rows = self._seq_bucket()
            by_kind = {
                name: rows * n
                for name, n in layout.bytes_by_kind(self.model.dtype).items()
            }
            self.telemetry.record("seq_state_bytes", by_kind)
            logger.info(
                "sequence state: %s at %d sequences (%.2f MiB, off the "
                "KV pool)",
                "; ".join(
                    f"{k.num_layers} × {k.name} "
                    f"{'×'.join(map(str, k.shape))} "
                    f"{jnp.dtype(k.dtype or self.model.dtype).name} "
                    f"({by_kind[k.name] / 2**20:.2f} MiB)"
                    for k in layout.kinds
                ),
                rows,
                self._seq_state_bytes() / 2**20,
            )
        _log_device_memory("after KV pool")

    def _state_slots(self, states: list, s_pad: int) -> list[np.ndarray]:
        """For a model with sequence state, one more packed array: the
        state row of each of the step's sequences (its carry row), the
        row count for padding (past the end: a write there is dropped).
        Nothing for any other model, whose programs stay as they were."""
        if self._state_layout is None:
            return []
        slots = np.full(s_pad, self._seq_bucket(), np.int32)
        slots[: len(states)] = [st.slot for st in states]
        return [slots]

    def _forward(
        self, params, tokens, caches, meta, state_slots, max_q=0, **kw
    ):
        """``model.forward`` for both kinds of model: (logits, caches as
        ``kv_caches`` holds them).  ``state_slots`` [S]: each
        sequence's state row; a sequence's last token of the step is
        the one its logits are read at; ``max_q`` the step's
        longest-chunk bucket."""
        if self._state_layout is None:
            return self.model.forward(params, tokens, caches, meta, **kw)
        kv, states = caches
        logits, kv, states = self.model.forward(
            params, tokens, kv, meta,
            seq_state=SeqState(
                states=states, slots=state_slots,
                last_rows=meta.logits_indices, max_q=max_q,
            ),
            **kw,
        )
        return logits, (kv, states)

    def warmup_decode(self) -> int:
        """Pre-compile the fused-decode program so serving never
        recompiles mid-stream — with the pinned sequence bucket
        (_seq_bucket), uniform K (scheduler), and the traced carry rows
        there is exactly ONE decode program per config.  Two
        back-to-back dispatches exercise both the host-token and
        device-carry paths through it.  Returns the number of dispatches
        issued.  Synthetic requests write into reserved page 0 (garbage
        by contract) and are removed after."""
        import time as _time

        from vllm_distributed_tpu.engine.scheduler import (
            CachedRequestData,
            SchedulerOutput,
        )

        sc = self.config.scheduler_config
        # Speculative verify programs are part of decode warmup too —
        # the first mid-serve verify compile is exactly the stall class
        # this warmup exists to remove.
        n_spec = self._warmup_spec() if sc.spec_ngram_k > 0 else 0
        # The exact K the scheduler will emit — warming any other scan
        # length is wasted.
        k = sc.fused_decode_steps()
        if k <= 1 or self.kv_caches is None:
            return n_spec
        t0 = _time.monotonic()
        buckets = [self._seq_bucket()]
        pages_pad = self._pages_bucket(cdiv(2 + 2 * k, self.page_size))
        n = 0
        for s_pad in buckets:
            ids = [f"__warm-{i}" for i in range(s_pad)]
            for i, rid in enumerate(ids):
                self.requests[rid] = CachedReqState(
                    req_id=rid,
                    token_ids=[1, 1],
                    sampling_params=SamplingParams(
                        temperature=0.0, max_tokens=2 * k + 2
                    ),
                    page_ids=[0] * pages_pad,
                    num_computed=1,
                    prefill_target=1,
                    num_prompt=1,
                )

            def so(step):
                return SchedulerOutput(
                    step_id=step,
                    cached_requests=[
                        CachedRequestData(
                            req_id=rid,
                            new_page_ids=[],
                            num_computed_tokens=1 + step * k,
                            num_new_tokens=k,
                        )
                        for rid in ids
                    ],
                    num_scheduled_tokens={rid: k for rid in ids},
                    total_num_scheduled_tokens=s_pad * k,
                    decode_steps=k,
                )

            # Two back-to-back dispatches without resolving: which rows
            # read the carry is traced (one program), but the second
            # dispatch still validates the device-carry handoff end to
            # end: it starts past the host token list, so every row
            # reads its carry row.
            r1 = self._execute_decode_steps(so(0))
            self._apply_scheduler_deltas(so(1))
            r2 = self._execute_decode_steps(so(1))
            r1()
            r2()
            n += 2
            for rid in ids:
                self._forget(rid)
        logger.info(
            "decode warmup: %d dispatches over %s seq buckets in %.1fs",
            n,
            buckets,
            _time.monotonic() - t0,
        )
        return n + n_spec

    def _warmup_spec(self) -> int:
        """Pre-compile the speculative verify program for every token
        bucket a spec step can produce (ISSUE 11).  With the pinned
        sequence bucket and verify-window width the only dynamic shape
        is the power-of-2 token bucket, capped at s_bucket * (K+1) —
        log-many programs, each warmed by one synthetic dispatch whose
        KV writes land in reserved page 0 (garbage by contract)."""
        if self.kv_caches is None:
            return 0
        import time as _time

        from vllm_distributed_tpu.engine.scheduler import (
            CachedRequestData,
            SchedulerOutput,
        )

        t0 = _time.monotonic()
        kp1 = self._spec_kp1()
        s_pad = self._seq_bucket()
        pages_pad = self._pages_bucket(cdiv(2 + kp1, self.page_size))
        buckets = []
        b = _MIN_TOKEN_BUCKET
        cap = next_power_of_2(s_pad * kp1)
        while b <= cap:
            buckets.append(b)
            b *= 2
        n = 0
        for t_bucket in buckets:
            # Window sizes summing exactly to the bucket: full K+1
            # windows first, the remainder spread so every row keeps
            # at least its input token.
            n_live = min(max(cdiv(t_bucket, kp1), 1), s_pad)
            sizes = []
            remaining = t_bucket
            for i in range(n_live):
                take = min(kp1, remaining - (n_live - i - 1))
                sizes.append(take)
                remaining -= take
            ids = [f"__warms-{i}" for i in range(n_live)]
            for rid in ids:
                self.requests[rid] = CachedReqState(
                    req_id=rid,
                    token_ids=[1, 1],
                    sampling_params=SamplingParams(
                        temperature=0.0, max_tokens=kp1 + 2
                    ),
                    page_ids=[0] * pages_pad,
                    num_computed=1,
                    prefill_target=1,
                    num_prompt=1,
                )
            so = SchedulerOutput(
                step_id=0,
                cached_requests=[
                    CachedRequestData(
                        req_id=rid,
                        new_page_ids=[],
                        num_computed_tokens=1,
                        num_new_tokens=sizes[i],
                    )
                    for i, rid in enumerate(ids)
                ],
                num_scheduled_tokens={
                    rid: sizes[i] for i, rid in enumerate(ids)
                },
                total_num_scheduled_tokens=t_bucket,
                decode_steps=1,
                draft_token_ids={
                    rid: [1] * (sizes[i] - 1)
                    for i, rid in enumerate(ids)
                    if sizes[i] > 1
                },
            )
            self._execute_spec_step(so)
            for rid in ids:
                self._forget(rid)
            n += 1
        logger.info(
            "spec-decode warmup: %d token buckets %s in %.1fs",
            n,
            buckets,
            _time.monotonic() - t0,
        )
        return n

    def warmup_prefill(self) -> int:
        """Pre-compile the single-step (prefill/mixed) program for each
        power-of-2 token bucket up to the step budget, so the FIRST
        request after boot pays execution time, not a trace+compile
        (r4's 21 s cold TTFT at 1B was exactly this compile).  One
        synthetic single-request prefill per bucket, written into
        reserved page 0.  Returns the number of buckets compiled."""
        import time as _time

        from vllm_distributed_tpu.engine.scheduler import (
            NewRequestData,
            SchedulerOutput,
        )

        if self.kv_caches is None:
            return 0
        t0 = _time.monotonic()
        sc = self.config.scheduler_config
        cap = min(
            next_power_of_2(sc.max_num_batched_tokens),
            next_power_of_2(max(sc.max_model_len - 1, 1)),
        )
        t = _MIN_TOKEN_BUCKET  # shortest prompts land in bucket 16
        buckets = []
        while t <= cap:
            buckets.append(t)
            t *= 2
        if not buckets:
            buckets = [cap]
        n = 0
        for t_pad in buckets:
            prompt_len = min(t_pad, sc.max_model_len - 1)
            pages_pad = self._pages_bucket(
                cdiv(prompt_len + 1, self.page_size)
            )
            so = SchedulerOutput(
                step_id=0,
                new_requests=[
                    NewRequestData(
                        req_id="__warmp",
                        prompt_token_ids=[1] * prompt_len,
                        num_prompt_tokens=prompt_len,
                        page_ids=[0] * pages_pad,
                        num_computed_tokens=0,
                        num_new_tokens=prompt_len,
                        sampling_params=SamplingParams(
                            temperature=0.0, max_tokens=2
                        ),
                    )
                ],
                num_scheduled_tokens={"__warmp": prompt_len},
                total_num_scheduled_tokens=prompt_len,
                decode_steps=1,
            )
            out = self.execute_model(so)
            if callable(out):
                out()
            self._forget("__warmp")
            n += 1
        logger.info(
            "prefill warmup: %d token buckets %s in %.1fs",
            n,
            buckets,
            _time.monotonic() - t0,
        )
        return n

    # ---- auxiliary (non-scheduled) forwards: embeddings & scoring ----
    @partial(jax.jit, static_argnames=("self",))
    def _jit_aux_forward(self, params, kv, tokens, meta):
        from vllm_distributed_tpu.ops.attention import (
            mla_attention_reference,
            paged_attention_reference,
            write_kv_pages,
        )

        extra = {}
        if self._state_layout is not None:
            # One sequence from position 0: it reads no state, and what
            # it leaves in this scratch row is thrown away.
            zero = jnp.zeros(1, jnp.int32)
            extra["seq_state"] = SeqState(
                states=self.alloc_seq_states(1), slots=zero, last_rows=zero
            )
        logits, kv, *_, hidden = self.model.forward(
            params,
            tokens,
            kv,
            meta,
            attn_fn=(
                mla_attention_reference
                if self._latent
                else paged_attention_reference
            ),
            kv_write_fn=write_kv_pages,
            return_hidden=True,
            **extra,
        )
        return logits, kv, hidden

    def _aux_forward(self, token_ids: list[int]):
        """One-off teacher-forced forward over a scratch KV pool with
        logits/hidden at EVERY position (the scheduled path only emits
        last-position logits).  Off the hot path by design: serves
        /v1/embeddings and prompt-logprobs scoring."""
        from vllm_distributed_tpu.ops.attention import AttentionMetadata

        t = len(token_ids)
        t_pad = max(next_power_of_2(t), _MIN_TOKEN_BUCKET)
        pages = cdiv(t_pad, self.page_size) + 1  # +1: reserved dump page
        # Cached scratch pool, grown to the largest request seen: aux
        # calls must not allocate a fresh pool each time on a device
        # whose HBM the serving pool was sized to fill.
        cached = getattr(self, "_aux_pool", None)
        if cached is None or cached[0] < pages:
            self._aux_pool = (pages, self._alloc_pages(pages))
        kv = self._aux_pool[1]
        # Previous contents are dead history for this single-sequence
        # teacher-forced pass (slots are overwritten; reads are bounded
        # by seq_lens=t), so reuse without zeroing.
        tokens = np.zeros(t_pad, np.int32)
        tokens[:t] = token_ids
        positions = np.zeros(t_pad, np.int32)
        positions[:t] = np.arange(t)
        seq_ids = np.full(t_pad, 1, np.int32)  # padding -> dropped row
        seq_ids[:t] = 0
        slots = np.full(t_pad, 0, np.int32)  # padding -> dump page 0
        slots[:t] = self.page_size + np.arange(t)  # data pages from 1
        meta = AttentionMetadata(
            q_seq_ids=jnp.asarray(seq_ids),
            q_positions=jnp.asarray(positions),
            slot_mapping=jnp.asarray(slots),
            block_tables=jnp.asarray(
                np.arange(1, pages + 1, dtype=np.int32)[None, :] % pages
            ),
            seq_lens=jnp.asarray([t], jnp.int32),
            logits_indices=jnp.arange(t_pad, dtype=jnp.int32),
            chunk_starts=jnp.zeros(1, jnp.int32),
        )
        args = (jnp.asarray(tokens), meta)
        if self.mesh is not None:
            args = jax.device_put(args, NamedSharding(self.mesh, P()))
        logits, kv_out, hidden = self._jit_aux_forward(
            self.params, kv, args[0], args[1]
        )
        self._aux_pool = (pages, kv_out)  # keep the written pool warm
        return np.asarray(logits)[:t], np.asarray(hidden)[:t]

    def embed(self, token_ids: list[int]) -> list[float]:
        """Mean-pooled, L2-normalized final hidden states (the pooling
        vLLM's embedding path applies to causal LMs)."""
        _, hidden = self._aux_forward(token_ids)
        vec = hidden.mean(axis=0)
        norm = float(np.linalg.norm(vec))
        return (vec / norm if norm > 0 else vec).astype(float).tolist()

    def score(self, token_ids: list[int]) -> list[float | None]:
        """Prompt logprobs: log p(token_i | tokens_<i); index 0 is None
        (no context).  Serves completions echo+logprobs."""
        logits, _ = self._aux_forward(token_ids)
        # Stable log_softmax: shift by max.
        shifted = logits - logits.max(-1, keepdims=True)
        logps = shifted - np.log(np.exp(shifted).sum(-1, keepdims=True))
        out: list[float | None] = [None]
        for i in range(1, len(token_ids)):
            out.append(float(logps[i - 1, token_ids[i]]))
        return out

    def _seq_bucket(self) -> int:
        """Fused-decode sequence bucket: PINNED to the max_num_seqs
        power-of-2 so batch growth/shrink never changes the compiled
        decode program — with uniform K (scheduler) and the traced carry
        rows, steady-state decode is ONE program per config.  Padded
        rows cost ~nothing in decode (seq_len 0 ⇒ the kernel skips them;
        the matmuls are weight-bandwidth-bound, not row-bound).  The
        single-step path keeps growth bucketing: its q-grouping scratch
        scales with s_pad × max_q, which decode's max_q=1 avoids."""
        sc = self.config.scheduler_config
        return max(
            next_power_of_2(sc.max_num_seqs), _MIN_SEQ_BUCKET, self._dp
        )

    def _pages_bucket(self, need: int) -> int:
        """Static pages-per-seq bucket.  For small max_model_len the bucket
        is floored at the model-length maximum so growing contexts never
        trigger a mid-serve recompile; for long-context configs (> 4096
        tokens of pages) it falls back to power-of-2 growth (log-many
        compiles, served from the compilation cache)."""
        floor = _MIN_PAGES_BUCKET
        ml_pages = next_power_of_2(
            cdiv(self.config.scheduler_config.max_model_len, self.page_size)
        )
        if ml_pages <= 256:
            floor = max(floor, ml_pages)
        return max(next_power_of_2(need), floor)

    # ---- tiered KV cache (ISSUE 14) ----
    @partial(jax.jit, static_argnames=("self",), donate_argnums=(1,))
    def _jit_write_kv_pages(self, leaf, idx, data):
        """Scatter restored page data back into the (donated) pool leaf
        in place — without donation XLA would copy the whole pool per
        layer.  ``idx`` is padded with the reserved page 0 (garbage by
        contract), so every restore batch of a size bucket shares one
        compiled program."""
        return leaf.at[:, idx].set(data)

    def _apply_kv_tier_ops(self, so: SchedulerOutput) -> float:
        """Apply a step's KV-tier spans BEFORE executing it: spills
        first (``jax.device_get`` the evicted pages to host DRAM before
        any step may overwrite them), then restores (``device_put`` the
        streamed-back pages into their freshly allocated homes before
        the step reads them).  Batched: one gather / one scatter per
        layer leaf per batch, off the jitted step itself.  Returns the
        wall seconds spent (the restore-stall observable)."""
        spills = getattr(so, "kv_spill_ops", None) or []
        restores = getattr(so, "kv_restore_ops", None) or []
        if (not spills and not restores) or self.kv_caches is None:
            return 0.0
        t0 = time.perf_counter()
        tree = jax.tree_util
        if spills:
            idx = jnp.asarray([p for p, _ in spills], jnp.int32)
            # device_get blocks until any in-flight step producing the
            # current pool has resolved — the page content captured is
            # exactly what the allocator registered.
            gathered = tree.tree_map(
                lambda leaf: np.asarray(jax.device_get(leaf[:, idx])),
                self.kv_caches,
            )
            for i, (_, slot) in enumerate(spills):
                self._host_kv[slot] = tree.tree_map(
                    lambda a: np.ascontiguousarray(a[:, i]), gathered
                )
        if restores:
            n = len(restores)
            npad = max(next_power_of_2(n), 1)
            pages = np.zeros(npad, np.int32)  # pad -> reserved page 0
            pages[:n] = [p for _, p in restores]
            idx = jnp.asarray(pages)
            # A missing slot is a protocol violation (the driver only
            # restores slots it spilled and never reuses one before its
            # restore shipped) — fail loudly, never serve garbage KV.
            datas = [self._host_kv.pop(s) for s, _ in restores]
            stacked = tree.tree_map(
                lambda *xs: np.stack(xs, axis=1), datas[0], *datas[1:]
            )
            kv_leaves, treedef = tree.tree_flatten(self.kv_caches)
            data_leaves, _ = tree.tree_flatten(stacked)
            new_leaves = []
            for leaf, dat in zip(kv_leaves, data_leaves):
                if npad > n:
                    pad = np.zeros(
                        (dat.shape[0], npad - n) + dat.shape[2:], dat.dtype
                    )
                    dat = np.concatenate([dat, pad], axis=1)
                new_leaves.append(
                    self._jit_write_kv_pages(leaf, idx, jnp.asarray(dat))
                )
            self.kv_caches = tree.tree_unflatten(treedef, new_leaves)
        return time.perf_counter() - t0

    # ---- KV-page export/import (disaggregated prefill, ISSUE 15) ----
    def export_kv_pages(
        self, page_ids: list[int], layer_start: int, layer_count: int
    ) -> dict:
        """Gather the KV content of ``page_ids`` for one per-layer chunk
        of the prefill→decode hand-off: the same batched
        ``jax.device_get`` the spill path uses (one gather per layer
        leaf, blocking until in-flight writes resolve, so content is
        exact), serialized with a per-layer sha256 so the receiving
        replica can verify every chunk before scattering it.

        Layer indexing is the flattened ``kv_caches`` leaf order — the
        exact inverse of ``import_kv_pages``.  Validated on single-host
        replicas (the standard disagg topology: one replica per
        host/slice, where ``device_get`` materializes the full logical
        array across local devices); multi-process meshes would need
        shard-aware reassembly.
        """
        import hashlib

        self._refuse_page_transfer()
        tree = jax.tree_util
        leaves, _ = tree.tree_flatten(self.kv_caches)
        num_layers = len(leaves)
        start = max(int(layer_start), 0)
        end = min(start + max(int(layer_count), 0), num_layers)
        idx = jnp.asarray(page_ids, jnp.int32)
        layers: list[dict] = []
        for i in range(start, end):
            arr = np.ascontiguousarray(
                np.asarray(jax.device_get(leaves[i][:, idx]))
            )
            data = arr.tobytes()
            layers.append(
                {
                    "index": i,
                    "num_layers": num_layers,
                    "shape": list(arr.shape),
                    "data": data,
                    "checksum": hashlib.sha256(data).hexdigest(),
                }
            )
        return {"num_layers": num_layers, "layers": layers}

    def import_kv_pages(self, page_ids: list[int], layers: list[dict]) -> dict:
        """Scatter received layer chunks into freshly reserved pages —
        the donated in-place write the restore path uses, with the
        page-content checksum verified BEFORE any byte lands.  The
        target pages are outside every index until the driver commits
        the transfer, so no step can be reading (or writing) them."""
        import hashlib

        self._refuse_page_transfer()
        tree = jax.tree_util
        leaves, treedef = tree.tree_flatten(self.kv_caches)
        n = len(page_ids)
        npad = max(next_power_of_2(n), 1)
        pages = np.zeros(npad, np.int32)  # pad -> reserved page 0
        pages[:n] = page_ids
        idx = jnp.asarray(pages)
        for layer in layers:
            data = layer["data"]
            if hashlib.sha256(data).hexdigest() != layer["checksum"]:
                return {
                    "ok": False,
                    "error": (
                        f"kv transfer checksum mismatch on layer "
                        f"{layer.get('index')}"
                    ),
                }
            i = int(layer["index"])
            leaf = leaves[i]
            arr = np.frombuffer(data, dtype=np.dtype(leaf.dtype)).reshape(
                tuple(layer["shape"])
            )
            if npad > n:
                pad = np.zeros(
                    (arr.shape[0], npad - n) + arr.shape[2:], arr.dtype
                )
                arr = np.concatenate([arr, pad], axis=1)
            leaves[i] = self._jit_write_kv_pages(
                leaf, idx, jnp.asarray(arr)
            )
        self.kv_caches = tree.tree_unflatten(treedef, leaves)
        return {"ok": True}

    def _refuse_page_transfer(self) -> None:
        if self._latent:
            raise ValueError(
                "KV page export/import is not supported for a latent "
                "(MLA) cache (worker/model_runner.py)"
            )
        if self._state_layout is not None:
            raise ValueError(
                "KV page export/import is not supported for a model "
                "that keeps per-sequence state beside its pages: the "
                "pages alone do not resume a sequence "
                "(worker/model_runner.py)"
            )

    def host_kv_stats(self) -> dict:
        """Host-tier occupancy (driver telemetry + leak assertions)."""
        total = 0
        for entry in self._host_kv.values():
            for leaf in jax.tree_util.tree_leaves(entry):
                total += leaf.nbytes
        return {"host_slots": len(self._host_kv), "host_bytes": total}

    # ---- per-step state mirroring ----
    def _forget(self, req_id: str) -> None:
        """Drop a request's mirrored state; its carry row is free for
        the next request (programs run in dispatch order, so one still
        in flight writes the row before its next owner's does)."""
        st = self.requests.pop(req_id, None)
        if st is not None and st.slot >= 0:
            self._free_slots.append(st.slot)

    def _carry_inputs(
        self, states: list[CachedReqState], sampling: list[bool]
    ) -> tuple[jax.Array, np.ndarray]:
        """(the device carry a step program takes, ``slot_rows``: for
        each carry row the batch row whose sampled token the program
        writes there, -1 where it leaves the row as it is).  Gives each
        of ``states`` a row on its first dispatch."""
        size = self._seq_bucket()
        if self._carry is None:
            self._carry = jnp.zeros(
                size,
                jnp.int32,
                device=(
                    NamedSharding(self.mesh, P())
                    if self.mesh is not None
                    else None
                ),
            )
            self._free_slots = list(range(size - 1, -1, -1))
        slot_rows = np.full(size, -1, np.int32)
        for row, (st, samples) in enumerate(zip(states, sampling)):
            if st.slot < 0:
                if not self._free_slots:
                    raise RuntimeError(
                        f"no free carry row for {st.req_id}: "
                        f"{len(self.requests)} requests mirrored, "
                        f"{size} rows"
                    )
                st.slot = self._free_slots.pop()
            if samples:
                slot_rows[st.slot] = row
        return self._carry, slot_rows

    @staticmethod
    def _merge_carry(carry, slot_rows, sampled):
        """Inside a step program: the carry with the rows this step
        sampled for rewritten (one gather, no loop)."""
        return jnp.where(
            slot_rows >= 0, sampled[jnp.maximum(slot_rows, 0)], carry
        )

    def _apply_scheduler_deltas(self, so: SchedulerOutput) -> None:
        for req_id in so.finished_req_ids:
            self._forget(req_id)
        for req_id in so.preempted_req_ids:
            self._forget(req_id)
        for new in so.new_requests:
            self._forget(new.req_id)
            self.requests[new.req_id] = CachedReqState(
                req_id=new.req_id,
                token_ids=list(new.prompt_token_ids),
                sampling_params=new.sampling_params,
                page_ids=list(new.page_ids),
                num_computed=new.num_computed_tokens,
                prefill_target=len(new.prompt_token_ids),
                num_prompt=new.num_prompt_tokens,
            )
        for cached in so.cached_requests:
            state = self.requests[cached.req_id]
            state.page_ids.extend(cached.new_page_ids)
            state.num_computed = cached.num_computed_tokens

    # ---- the step ----
    def execute_model(self, so: SchedulerOutput):
        """Issue one step.  The fused scan and the single step return a
        ``resolve()`` closure (the program is in flight; the closure
        fetches its tokens and brings the mirrored state up to date);
        the spec-verify step and an empty one return their
        ``ModelRunnerOutput``."""
        # ``runner.prepare`` runs from here to the step program's
        # dispatch: each path ends it (``prep.end()``) where its inputs
        # are on their way to the device; the ``with`` closes it on the
        # paths that dispatch nothing.
        with get_tracer().span("runner.prepare", step_id=so.step_id) as prep:
            return self._execute(so, prep)

    def _execute(self, so: SchedulerOutput, prep):
        self._apply_scheduler_deltas(so)
        # KV-tier spans land before ANY path may touch their pages
        # (spills before the evicted page is rewritten, restores before
        # the attached chain is read).
        tier_s = self._apply_kv_tier_ops(so)
        if so.is_empty:
            return ModelRunnerOutput()
        if so.draft_token_ids:
            return self._execute_spec_step(so, prep)
        if so.decode_steps > 1:
            return self._execute_decode_steps(so, prep)

        # A row starts where the scheduler says (what is in flight
        # counted as computed), not at the mirrored cursor, which a
        # resolve() still to come will move.
        rows = [(c.req_id, c.num_computed_tokens) for c in so.cached_requests]
        rows += [(n.req_id, n.num_computed_tokens) for n in so.new_requests]
        order = [r for r, _ in rows]
        starts = [lo for _, lo in rows]
        states = [self.requests[r] for r in order]
        num_new = [so.num_scheduled_tokens[r] for r in order]

        t_real = sum(num_new)
        s_real = len(order)
        # dp is a power of two (validated at load), so power-of-two buckets
        # at least dp wide stay divisible for the dp input sharding.
        t_pad = max(next_power_of_2(t_real), _MIN_TOKEN_BUCKET, self._dp)
        s_pad = max(next_power_of_2(s_real), _MIN_SEQ_BUCKET, self._dp)
        if getattr(self.model, "pin_step_seq_bucket", False):
            # One single-step program a (token, longest-chunk) bucket,
            # whatever the step's sequences: the model asks for it.
            s_pad = self._seq_bucket()
        max_pages = max(
            max((len(st.page_ids) for st in states), default=1), 1
        )
        pages_pad = self._pages_bucket(max_pages)

        tokens = np.zeros(t_pad, np.int32)
        positions = np.zeros(t_pad, np.int32)
        # Padding tokens point one past the last seq row: identifiable as
        # padding (kernels drop them); OOB gathers clip under jit.
        seq_ids = np.full(t_pad, s_pad, np.int32)
        slots = np.zeros(t_pad, np.int32)
        block_tables = np.zeros((s_pad, pages_pad), np.int32)
        seq_lens = np.zeros(s_pad, np.int32)
        logits_idx = np.zeros(s_pad, np.int32)
        chunk_starts = np.zeros(s_pad, np.int32)
        # Per token: the carry row its id is read from on the device,
        # -1 where the host gives it (every prompt token; a decode
        # row's once the host has fetched it).
        carry_idx = np.full(t_pad, -1, np.int32)
        needs_sample = [
            lo + n >= st.prefill_target
            for st, lo, n in zip(states, starts, num_new)
        ]
        carry, slot_rows = self._carry_inputs(states, needs_sample)

        cursor = 0
        for s, (state, lo, n) in enumerate(zip(states, starts, num_new)):
            hi = lo + n
            # Thread invariant (see _execute_decode_steps): resolve() of
            # the dispatch before this one may extend token_ids now.  A
            # prefill row reads prompt tokens only, which never move; a
            # decode row reads ONE list element or none, and either
            # way gets the same token: from the list once it is there,
            # from the carry row until then.
            if lo >= state.prefill_target and lo >= len(state.token_ids):
                assert n == 1 and state.slot >= 0, (state.req_id, lo, n)
                carry_idx[cursor] = state.slot
            else:
                tokens[cursor : cursor + n] = state.token_ids[lo:hi]
            pos = np.arange(lo, hi, dtype=np.int32)
            positions[cursor : cursor + n] = pos
            seq_ids[cursor : cursor + n] = s
            page_arr = np.asarray(state.page_ids, np.int32)
            slots[cursor : cursor + n] = (
                page_arr[pos // self.page_size] * self.page_size
                + pos % self.page_size
            )
            block_tables[s, : len(state.page_ids)] = page_arr
            seq_lens[s] = hi
            logits_idx[s] = cursor + n - 1
            chunk_starts[s] = lo
            cursor += n

        max_q_pad = max(next_power_of_2(max(num_new)), 1)
        max_q_class = getattr(self._attn_fn, "max_q_class", None)
        if max_q_class is not None:
            # longest-chunk buckets the kernel compiles alike are one
            max_q_pad = max_q_class(
                max_q_pad, t_pad, num_heads=self.model.num_heads,
                value_width=self.model.kv_lora_rank,
            )
        step_max_q_class = getattr(self.model, "step_max_q_class", None)
        if step_max_q_class is not None:
            # ... and so are those the model says it compiles alike
            max_q_pad = step_max_q_class(max_q_pad, t_pad)
        smeta_np, flags = self._build_sampling_metadata(states, s_pad)
        # Whether a request of the step reads logprobs; a model may ask
        # for one single-step program either way (the program computes
        # them, the host fetches them only when wanted).
        want_lp = flags["return_logprobs"]
        if getattr(self.model, "step_logprobs_always", False):
            flags["return_logprobs"] = True
        # The PRNG stream position is the index of the token a row
        # samples, by the dispatched count: the mirrored token list may
        # lag behind it.
        smeta_np.keys[:s_real, 1] = seq_lens[:s_real].astype(np.uint32)
        assert not (flags["do_penalties"] and (carry_idx >= 0).any()), (
            "penalties need the host's token history: the engine drains "
            "before such a step"
        )

        if self._dp == 1:
            # One packed host→device transfer per step (see
            # pack_host_arrays).  Replicated across the mesh under tp.
            packed, pack_spec = pack_host_arrays(
                [
                    tokens, seq_ids, positions, slots, block_tables,
                    seq_lens, logits_idx, chunk_starts,
                    smeta_np.temperature, smeta_np.top_k, smeta_np.top_p,
                    smeta_np.min_p, smeta_np.repetition_penalty,
                    smeta_np.presence_penalty, smeta_np.frequency_penalty,
                    smeta_np.keys, smeta_np.prompt_tokens,
                    smeta_np.output_tokens, carry_idx, slot_rows,
                    *self._state_slots(states, s_pad),
                ]
            )
            if self.mesh is not None:
                packed = jax.device_put(
                    packed, NamedSharding(self.mesh, P())
                )
            statics = dict(spec=pack_spec, max_q_pad=max_q_pad, **flags)
            method = "_jit_step_packed"
            args = (self.params, self.kv_caches, packed, carry)
        else:
            assert self._state_layout is None, "sequence state under dp>1"
            meta = AttentionMetadata(
                q_seq_ids=jnp.asarray(seq_ids),
                q_positions=jnp.asarray(positions),
                slot_mapping=jnp.asarray(slots),
                block_tables=jnp.asarray(block_tables),
                seq_lens=jnp.asarray(seq_lens),
                logits_indices=jnp.asarray(logits_idx),
                chunk_starts=jnp.asarray(chunk_starts),
            )
            token_ids = jnp.asarray(tokens)
            smeta = smeta_np
            spec = self._input_spec
            token_ids = jax.device_put(token_ids, spec)
            meta = jax.tree.map(lambda x: jax.device_put(x, spec), meta)
            smeta = jax.tree.map(lambda x: jax.device_put(x, spec), smeta)
            whole = NamedSharding(self.mesh, P())
            statics = dict(max_q_pad=max_q_pad, **flags)
            method = "_jit_step"
            args = (
                self.params, self.kv_caches, token_ids, meta, smeta,
                carry,
                jax.device_put(carry_idx, spec),
                jax.device_put(slot_rows, whole),
            )

        kv_tokens = int(seq_lens.sum())
        counts = dict(seqs=s_real, tokens=t_real, kv_tokens=kv_tokens)
        counts.update(self._step_counts(
            "prefill", num_new, starts, rows_pad=t_pad, seqs_pad=s_pad,
            max_q_pad=max_q_pad, pages_pad=pages_pad,
        ))
        prep.end()
        t_step0 = time.perf_counter()
        sampled, logprobs, self.kv_caches, self._carry = self._run_program(
            "prefill",
            method,
            program_name(
                "step", flags, t=t_pad, q=max_q_pad, s=s_pad, p=pages_pad
            ),
            statics,
            args,
            so,
            **counts,
        )
        # As in the fused scan: resolve() may run on the executor's
        # resolver thread, and the fetch stays a child of this step.
        step_ctx = current_ctx()

        def resolve() -> ModelRunnerOutput:
            with get_tracer().span(
                "runner.fetch", parent=step_ctx, step_id=so.step_id
            ) as fetch:
                if logprobs is not None and want_lp:
                    host_toks, host_lps = jax.device_get((sampled, logprobs))
                    host_lps = np.asarray(host_lps)
                else:
                    host_toks, host_lps = jax.device_get(sampled), None
                host_toks = np.asarray(host_toks)
                if host_toks.shape[0] > s_pad:
                    self._record_fetched(fetch, host_toks[s_pad:])
            self._num_fetched += 1
            self._record_step_bw(time.perf_counter() - t_step0, kv_tokens)

            out = ModelRunnerOutput()
            # Restore-bearing steps are always admission (blocking)
            # steps, so the stall lands on the output the engine reads.
            out.kv_tier_seconds = tier_s
            for s, (state, lo, n) in enumerate(zip(states, starts, num_new)):
                # Never backwards: the deltas of a dispatch built since
                # may have moved the cursor on.
                state.num_computed = max(state.num_computed, lo + n)
                if not needs_sample[s]:
                    out.num_prompt_tokens_processed[state.req_id] = n
                    continue
                tok = int(host_toks[s])
                state.token_ids.append(tok)
                out.sampled_token_ids[state.req_id] = [tok]
                nlp = state.sampling_params.logprobs
                if nlp is not None and host_lps is not None:
                    row = host_lps[s]
                    nlp = min(nlp, row.shape[-1] - 1)
                    top = np.argpartition(row, -max(nlp, 1))[-max(nlp, 1) :]
                    d = {int(i): float(row[i]) for i in top}
                    d[tok] = float(row[tok])
                    out.logprobs[state.req_id] = [d]
            return out

        return resolve

    def _build_sampling_metadata(
        self,
        states: list[CachedReqState],
        s_pad: int,
        extra_output_len: int = 1,
    ) -> tuple[SamplingMetadata, dict]:
        vocab = self.model.vocab_size
        temp = np.zeros(s_pad, np.float32)
        top_k = np.full(s_pad, vocab, np.int32)
        top_p = np.ones(s_pad, np.float32)
        min_p = np.zeros(s_pad, np.float32)
        rep = np.ones(s_pad, np.float32)
        pres = np.zeros(s_pad, np.float32)
        freq = np.zeros(s_pad, np.float32)
        keys = np.zeros((s_pad, 2), np.uint32)
        do_pen = False
        do_tkp = False
        want_lp = False
        for s, st in enumerate(states):
            sp = st.sampling_params
            temp[s] = sp.temperature
            if sp.top_k > 0:
                top_k[s] = sp.top_k
            top_p[s] = sp.top_p
            min_p[s] = sp.min_p
            rep[s] = sp.repetition_penalty
            pres[s] = sp.presence_penalty
            freq[s] = sp.frequency_penalty
            seed = sp.seed if sp.seed is not None else self.global_seed
            keys[s, 0] = np.uint32(
                (seed ^ zlib.crc32(st.req_id.encode())) & 0xFFFFFFFF
            )
            keys[s, 1] = np.uint32(len(st.token_ids))
            do_pen |= _needs_penalties(sp)
            do_tkp |= _needs_top_k_p(sp)
            want_lp |= sp.logprobs is not None

        if do_pen:
            lp = max(
                next_power_of_2(max(st.num_prompt for st in states)),
                _MIN_TOKEN_BUCKET,
            )
            lo = max(
                next_power_of_2(
                    max(len(st.token_ids) - st.num_prompt for st in states)
                    + extra_output_len
                ),
                _MIN_TOKEN_BUCKET,
            )
            prompt_toks = np.full((s_pad, lp), -1, np.int32)
            output_toks = np.full((s_pad, lo), -1, np.int32)
            for s, st in enumerate(states):
                p = st.token_ids[: st.num_prompt][:lp]
                o = st.token_ids[st.num_prompt :][:lo]
                prompt_toks[s, : len(p)] = p
                output_toks[s, : len(o)] = o
        else:
            prompt_toks = np.full((s_pad, 1), -1, np.int32)
            output_toks = np.full((s_pad, 1), -1, np.int32)

        # Numpy leaves: the packed path ships them in one fused buffer;
        # the unpacked path converts at the jit boundary.
        smeta = SamplingMetadata(
            temperature=temp,
            top_k=top_k,
            top_p=top_p,
            min_p=min_p,
            repetition_penalty=rep,
            presence_penalty=pres,
            frequency_penalty=freq,
            keys=keys,
            prompt_tokens=prompt_toks,
            output_tokens=output_toks,
        )
        flags = dict(
            do_penalties=do_pen,
            do_top_k_p=do_tkp,
            return_logprobs=want_lp,
        )
        return smeta, flags

    def _step_core(
        self,
        params,
        kv_caches,
        token_ids,
        meta: AttentionMetadata,
        smeta: SamplingMetadata,
        carry,
        carry_idx,
        slot_rows,
        max_q_pad: int,
        do_penalties: bool,
        do_top_k_p: bool,
        return_logprobs: bool,
        state_slots=None,
    ):
        # A decode row whose last token the host has not fetched reads
        # it from the carry, by row.
        token_ids = jnp.where(
            carry_idx >= 0, carry[jnp.maximum(carry_idx, 0)], token_ids
        )
        attn_fn = bind_max_q(self._attn_fn, max_q_pad)
        stats = self._model_stats()
        logits, kv_caches = self._forward(
            params,
            token_ids,
            kv_caches,
            meta,
            state_slots,
            max_q=max_q_pad,
            attn_fn=attn_fn,
            kv_write_fn=self._kv_write_fn,
            **stats,
        )
        with jax.named_scope(SCOPE_SAMPLER):
            tokens, logprobs = sample(
                logits,
                smeta,
                do_penalties=do_penalties,
                do_top_k_p=do_top_k_p,
                return_logprobs=return_logprobs,
            )
        carry = self._merge_carry(carry, slot_rows, tokens)
        if stats:
            # The router's pick counts ride behind the sampled tokens:
            # one array, one transfer (resolve() reads the tail).
            tokens = jnp.concatenate(
                [tokens, stats["stats"]["moe_picks"].astype(tokens.dtype)]
            )
        return tokens, logprobs, kv_caches, carry

    def _model_stats(self) -> dict:
        """``forward``'s ``stats`` argument for a model that counts its
        router's picks on the device, nothing for any other."""
        if getattr(self.model, "moe_pick_kinds", None):
            return {"stats": {}}
        return {}

    def _record_fetched(self, span, host_tail) -> None:
        """The counts a step program appended to its tokens, as the
        model that counted them reads them: onto the step's fetch span
        and, unread, onto the worker's counters."""
        attrs, increments = self.model.fetched_counts(host_tail)
        for counter, by_label in increments.items():
            self.telemetry.record(counter, by_label)
        for name, n in attrs.items():
            span.set_attribute(name, n)

    @partial(
        jax.jit,
        static_argnames=(
            "self",
            "max_q_pad",
            "do_penalties",
            "do_top_k_p",
            "return_logprobs",
        ),
        donate_argnums=(2,),
    )
    def _jit_step(
        self,
        params,
        kv_caches,
        token_ids,
        meta: AttentionMetadata,
        smeta: SamplingMetadata,
        carry,
        carry_idx,
        slot_rows,
        *,
        max_q_pad: int,
        do_penalties: bool,
        do_top_k_p: bool,
        return_logprobs: bool,
    ):
        return self._step_core(
            params, kv_caches, token_ids, meta, smeta,
            carry, carry_idx, slot_rows,
            max_q_pad, do_penalties, do_top_k_p, return_logprobs,
        )

    @partial(
        jax.jit,
        static_argnames=(
            "self",
            "spec",
            "max_q_pad",
            "do_penalties",
            "do_top_k_p",
            "return_logprobs",
        ),
        donate_argnums=(2,),
    )
    def _jit_step_packed(
        self,
        params,
        kv_caches,
        packed,
        carry,
        *,
        spec: tuple,
        max_q_pad: int,
        do_penalties: bool,
        do_top_k_p: bool,
        return_logprobs: bool,
    ):
        (
            tokens, seq_ids, positions, slots, block_tables, seq_lens,
            logits_idx, chunk_starts, temp, top_k, top_p, min_p, rep,
            pres, freq, keys, prompt_toks, out_toks, carry_idx, slot_rows,
            *state_slots,
        ) = unpack_device_arrays(packed, spec)
        meta = AttentionMetadata(
            q_seq_ids=seq_ids,
            q_positions=positions,
            slot_mapping=slots,
            block_tables=block_tables,
            seq_lens=seq_lens,
            logits_indices=logits_idx,
            chunk_starts=chunk_starts,
        )
        smeta = SamplingMetadata(
            temperature=temp,
            top_k=top_k,
            top_p=top_p,
            min_p=min_p,
            repetition_penalty=rep,
            presence_penalty=pres,
            frequency_penalty=freq,
            keys=keys,
            prompt_tokens=prompt_toks,
            output_tokens=out_toks,
        )
        return self._step_core(
            params, kv_caches, tokens, meta, smeta,
            carry, carry_idx, slot_rows,
            max_q_pad, do_penalties, do_top_k_p, return_logprobs,
            *state_slots,
        )

    # ---- speculative verify pass (SchedulerOutput.draft_token_ids) ----
    def _spec_kp1(self) -> int:
        """Static verify-window width: the configured max drafts + 1
        bonus column, padded to a power of two, so every spec step of a
        config shares ONE compiled gather/accept shape regardless of
        how many drafts each request actually found."""
        return max(
            next_power_of_2(
                self.config.scheduler_config.spec_ngram_k + 1
            ),
            2,
        )

    def _execute_spec_step(
        self, so: SchedulerOutput, prep=NOOP_SPAN
    ) -> ModelRunnerOutput:
        """Verify every request's drafted tokens in ONE fused dispatch
        (ISSUE 11): feed ``[input token, d_1..d_d]`` per sequence
        through the single-pass forward (teacher-forced; causal within
        the window exactly like a prefill chunk), gather logits at
        EVERY window position, and let the greedy accept kernel keep
        the longest draft prefix matching the argmax chain plus one
        bonus token.  One weight+KV HBM pass buys up to K+1 tokens
        instead of one.  KV rows written for rejected drafts sit past
        the reconciled cursor and are overwritten in place by the next
        window — never registered by the prefix cache, never read by
        later attention (seq_lens follows the accepted cursor)."""
        order = [c.req_id for c in so.cached_requests]
        states = [self.requests[r] for r in order]
        num_new = [so.num_scheduled_tokens[r] for r in order]
        drafts = so.draft_token_ids

        t_real = sum(num_new)
        s_real = len(order)
        # Sequence bucket PINNED like the fused-decode path (batch
        # growth/shrink never recompiles) and max_q pinned to the
        # verify-window width (per-request draft counts never
        # recompile): the only dynamic shape left is the power-of-2
        # token bucket — log-many programs, all pre-compiled by
        # warmup_decode when spec is on.
        t_pad = max(next_power_of_2(t_real), _MIN_TOKEN_BUCKET)
        s_pad = self._seq_bucket()
        kp1 = self._spec_kp1()
        max_pages = max(max(len(st.page_ids) for st in states), 1)
        pages_pad = self._pages_bucket(max_pages)

        tokens = np.zeros(t_pad, np.int32)
        positions = np.zeros(t_pad, np.int32)
        seq_ids = np.full(t_pad, s_pad, np.int32)
        slots = np.zeros(t_pad, np.int32)
        block_tables = np.zeros((s_pad, pages_pad), np.int32)
        seq_lens = np.zeros(s_pad, np.int32)
        chunk_starts = np.zeros(s_pad, np.int32)
        # Logits rows gathered per (sequence, window column); columns
        # past a short window re-gather its last row — the accept
        # kernel masks them via n_drafts.
        verify_idx = np.zeros((s_pad, kp1), np.int32)
        draft_mat = np.full((s_pad, kp1 - 1), -1, np.int32)
        n_drafts = np.zeros(s_pad, np.int32)

        cursor = 0
        for s, (state, n) in enumerate(zip(states, num_new)):
            lo = state.num_computed
            assert lo == len(state.token_ids) - 1, (
                "spec verify dispatched without the host-current last "
                "token (pipeline must be drained)"
            )
            d = drafts.get(state.req_id, [])
            window = [state.token_ids[lo], *d]
            assert len(window) == n, (state.req_id, len(window), n)
            tokens[cursor : cursor + n] = window
            pos = np.arange(lo, lo + n, dtype=np.int32)
            positions[cursor : cursor + n] = pos
            seq_ids[cursor : cursor + n] = s
            page_arr = np.asarray(state.page_ids, np.int32)
            slots[cursor : cursor + n] = (
                page_arr[pos // self.page_size] * self.page_size
                + pos % self.page_size
            )
            block_tables[s, : len(state.page_ids)] = page_arr
            seq_lens[s] = lo + n
            chunk_starts[s] = lo
            verify_idx[s, :] = cursor + np.minimum(np.arange(kp1), n - 1)
            draft_mat[s, : len(d)] = d
            n_drafts[s] = len(d)
            cursor += n

        max_q_pad = kp1
        packed, pack_spec = pack_host_arrays(
            [
                tokens, seq_ids, positions, slots, block_tables,
                seq_lens, chunk_starts, verify_idx, draft_mat, n_drafts,
            ]
        )
        if self.mesh is not None:
            packed = jax.device_put(packed, NamedSharding(self.mesh, P()))
        statics = dict(spec=pack_spec, max_q_pad=max_q_pad)
        kv_tokens = int(seq_lens.sum())
        prep.end()
        t_step0 = time.perf_counter()
        toks, n_emit, self.kv_caches = self._run_program(
            "spec",
            "_jit_spec_step",
            program_name(
                "spec", statics, t=t_pad, q=kp1, s=s_pad, p=pages_pad
            ),
            statics,
            (self.params, self.kv_caches, packed),
            so,
            **self._step_counts(
                "spec", num_new, chunk_starts[:s_real].tolist(),
                rows_pad=t_pad, seqs_pad=s_pad, max_q_pad=kp1,
                pages_pad=pages_pad,
            ),
            seqs=s_real,
            tokens=t_real,
            kv_tokens=kv_tokens,
        )
        with get_tracer().span("runner.fetch", step_id=so.step_id):
            toks, n_emit = jax.device_get((toks, n_emit))
        self._num_fetched += 1
        # A verify window streams weights+KV ONCE for up to K+1 tokens —
        # the roofline asymmetry spec decode exists to exploit.
        self._record_step_bw(time.perf_counter() - t_step0, kv_tokens)
        toks = np.asarray(toks)
        n_emit = np.asarray(n_emit)

        out = ModelRunnerOutput()
        for s, (state, n) in enumerate(zip(states, num_new)):
            m = min(int(n_emit[s]), n)
            seq_toks = [int(t) for t in toks[s, :m]]
            # The deltas set num_computed to the window base; advance by
            # the EMITTED count (input + accepted drafts), mirroring the
            # scheduler's update_from_output reconciliation.
            state.num_computed += m
            state.token_ids.extend(seq_toks)
            out.sampled_token_ids[state.req_id] = seq_toks
        return out

    @partial(
        jax.jit,
        static_argnames=("self", "spec", "max_q_pad"),
        donate_argnums=(2,),
    )
    def _jit_spec_step(
        self,
        params,
        kv_caches,
        packed,
        *,
        spec: tuple,
        max_q_pad: int,
    ):
        (
            tokens, seq_ids, positions, slots, block_tables, seq_lens,
            chunk_starts, verify_idx, draft_mat, n_drafts,
        ) = unpack_device_arrays(packed, spec)
        s_pad, kp1 = verify_idx.shape
        meta = AttentionMetadata(
            q_seq_ids=seq_ids,
            q_positions=positions,
            slot_mapping=slots,
            block_tables=block_tables,
            seq_lens=seq_lens,
            logits_indices=verify_idx.reshape(-1),
            chunk_starts=chunk_starts,
        )
        attn_fn = bind_max_q(self._attn_fn, max_q_pad)
        logits, kv_caches = self.model.forward(
            params,
            tokens,
            kv_caches,
            meta,
            attn_fn=attn_fn,
            kv_write_fn=self._kv_write_fn,
        )
        with jax.named_scope(SCOPE_SAMPLER):
            toks, n_emit = spec_greedy_accept(
                logits.reshape(s_pad, kp1, -1), draft_mat, n_drafts
            )
        return toks, n_emit, kv_caches

    # ---- fused multi-step decode (SchedulerOutput.decode_steps > 1) ----
    def _execute_decode_steps(
        self, so: SchedulerOutput, prep=NOOP_SPAN
    ) -> ModelRunnerOutput:
        """Run `so.decode_steps` decode micro-steps in ONE device dispatch
        (a lax.scan feeding each sampled token back in).  Amortizes the
        host round trip the reference pays per scheduler step
        (launch.py:322-343) by K — the TPU-first redesign SURVEY.md §3.3
        calls for."""
        k_steps = so.decode_steps
        order = tuple(c.req_id for c in so.cached_requests)
        states = [self.requests[r] for r in order]
        # Where each row starts is the scheduler's word (what is in
        # flight counted as computed), not the mirrored cursor.
        starts = [c.num_computed_tokens for c in so.cached_requests]
        # Per-sequence scheduled token counts: a request whose remaining
        # budget is under k_steps runs its first n micro-steps and is
        # MASKED for the rest (queries dropped, KV writes routed to the
        # dump page, sampled tokens discarded) — the scan length stays
        # the single compiled k_steps program (see Scheduler.schedule).
        num_new = {c.req_id: c.num_new_tokens for c in so.cached_requests}
        # Thread-interleaving invariant (engine thread here vs a prior
        # dispatch's resolve() on the executor's resolver thread): both
        # may touch CachedReqState concurrently, which is safe because
        # (a) a row's start comes from the SchedulerOutput, never from
        # the cursor resolve() moves, (b) the one read of token_ids
        # below gives the same dispatched token whether it lands before
        # or after resolve() appends (either the list already holds the
        # token at index ``start`` or the request's carry row does), and
        # (c) penalties/logprobs — the only consumers of token_ids
        # contents — make the engine drain before the step.  CPython's
        # GIL makes each individual list/int access atomic.  Do not add
        # reads of st.token_ids beyond the patterns below without
        # revisiting this.
        s_real = len(order)
        s_pad = self._seq_bucket()
        max_pages = max(max(len(st.page_ids) for st in states), 1)
        pages_pad = self._pages_bucket(max_pages)

        tokens = np.zeros(s_pad, np.int32)
        base_lens = np.zeros(s_pad, np.int32)
        valid = np.zeros(s_pad, np.int32)
        n_active = np.zeros(s_pad, np.int32)
        block_tables = np.zeros((s_pad, pages_pad), np.int32)
        out_lens = np.zeros(s_pad, np.int32)
        # Per row: the carry row its first token is read from on the
        # device, -1 where the host gives it.  Traced (not static), so
        # every mix of the two shares ONE compiled program.
        carry_idx = np.full(s_pad, -1, np.int32)
        carry, slot_rows = self._carry_inputs(states, [True] * s_real)
        for s, (st, lo) in enumerate(zip(states, starts)):
            base_lens[s] = lo
            valid[s] = 1
            n_active[s] = num_new[st.req_id]
            block_tables[s, : len(st.page_ids)] = st.page_ids
            out_lens[s] = len(st.token_ids) - st.num_prompt
            if lo < len(st.token_ids):
                tokens[s] = st.token_ids[lo]
            else:
                # Results of a previous dispatch are still in flight;
                # the token lives in the request's carry row.
                carry_idx[s] = st.slot

        smeta_np, flags = self._build_sampling_metadata(
            states, s_pad, extra_output_len=k_steps + 1
        )
        assert not flags["return_logprobs"], (
            "scheduler must not fuse decode steps when logprobs are on"
        )
        assert not (flags["do_penalties"] and (carry_idx >= 0).any()), (
            "pipelined decode cannot run with penalties (stale host state)"
        )
        # PRNG stream position must follow the device-side token count,
        # which host token_ids may lag behind under pipelining.
        smeta_np.keys[:s_real, 1] = (base_lens[:s_real] + 1).astype(np.uint32)
        packed, pack_spec = pack_host_arrays(
            [
                tokens, base_lens, valid, n_active, carry_idx, slot_rows,
                block_tables, out_lens,
                smeta_np.temperature, smeta_np.top_k, smeta_np.top_p,
                smeta_np.min_p, smeta_np.repetition_penalty,
                smeta_np.presence_penalty, smeta_np.frequency_penalty,
                smeta_np.keys, smeta_np.prompt_tokens,
                smeta_np.output_tokens,
                *self._state_slots(states, s_pad),
            ]
        )
        if self.mesh is not None:
            packed = jax.device_put(packed, NamedSharding(self.mesh, P()))
        statics = dict(
            spec=pack_spec,
            k_steps=k_steps,
            do_penalties=flags["do_penalties"],
            do_top_k_p=flags["do_top_k_p"],
        )
        kv_tokens_scanned = int(base_lens.sum())
        counts = dict(
            seqs=s_real, tokens=int(n_active.sum()),
            kv_tokens=kv_tokens_scanned,
        )
        counts.update(self._step_counts(
            "decode", [num_new[r] for r in order], starts, rows_pad=s_pad,
            seqs_pad=s_pad, max_q_pad=1, pages_pad=pages_pad,
        ))
        prep.end()
        t_step0 = time.perf_counter()
        toks, self._carry, self.kv_caches = self._run_program(
            "decode",
            "_jit_decode_steps",
            program_name(
                "decode", statics, k=k_steps, s=s_pad, p=pages_pad
            ),
            statics,
            (self.params, self.kv_caches, packed, carry),
            so,
            **counts,
        )
        # Each sequence's LAST VALID token stays on device, in its carry
        # row, as the input of the next dispatch that holds the request
        # (under-K tails: token n_active-1, not K-1).
        # resolve() may run on the executor's resolver thread, which
        # has no span context of its own: the fetch stays a child of
        # the step that dispatched it.
        step_ctx = current_ctx()

        def resolve() -> ModelRunnerOutput:
            with get_tracer().span(
                "runner.fetch", parent=step_ctx, step_id=so.step_id
            ) as fetch:
                host_toks = np.asarray(jax.device_get(toks))  # [K, s_pad]
                if host_toks.shape[0] > k_steps:
                    self._record_fetched(fetch, host_toks[k_steps])
            self._num_fetched += 1
            # K micro-steps = K weights+KV HBM passes.  Wall time spans
            # dispatch→resolve (includes any pipeline overlap — an
            # estimate, like the byte count).
            self._record_step_bw(
                time.perf_counter() - t_step0, kv_tokens_scanned, k_steps
            )
            out = ModelRunnerOutput()
            for s, st in enumerate(states):
                n = int(n_active[s])
                seq_toks = [int(t) for t in host_toks[:n, s]]
                # Never backwards: the deltas of a dispatch built since
                # may have moved the cursor on.
                st.num_computed = max(st.num_computed, int(base_lens[s]) + n)
                st.token_ids.extend(seq_toks)
                out.sampled_token_ids[st.req_id] = seq_toks
            return out

        return resolve

    @partial(
        jax.jit,
        static_argnames=(
            "self",
            "spec",
            "k_steps",
            "do_penalties",
            "do_top_k_p",
        ),
        donate_argnums=(2,),
    )
    def _jit_decode_steps(
        self,
        params,
        kv_caches,
        packed,
        carry,
        *,
        spec: tuple,
        k_steps: int,
        do_penalties: bool,
        do_top_k_p: bool,
    ):
        (
            tokens, base_lens, valid, n_active, carry_idx, slot_rows,
            block_tables, out_lens, temp, top_k,
            top_p, min_p, rep, pres, freq, keys, prompt_toks, out_toks,
            *state_slots,
        ) = unpack_device_arrays(packed, spec)
        # A model with sequence state: its arrays ride behind the pools
        # (``kv_caches``) and through the loop's carry beside them.
        seq_states = None
        if state_slots:
            kv_caches, seq_states = kv_caches
            (state_slots,) = state_slots
        tokens = jnp.where(
            carry_idx >= 0, carry[jnp.maximum(carry_idx, 0)], tokens
        )
        s_pad = tokens.shape[0]
        rows = jnp.arange(s_pad, dtype=jnp.int32)
        page_size = self.page_size
        attn_fn = bind_max_q(self._attn_fn, 1)
        staged = self._staged_decode
        if staged:
            # Staged decode writes: micro-step K/V rows go to a dense
            # per-layer side buffer (one in-place DUS per layer per
            # step); attention reads pool (positions < base) + side
            # (positions base..base+i); the pool is flushed once after
            # the scan.  Removes the per-row pool writes (~1.8 µs each)
            # from the micro-step path.
            base_valid = jnp.where(valid > 0, base_lens, 0)

            def make_entry(kv, side, i):
                return (kv, side, i)

            def staged_write(entry, k, v, slot_mapping):
                kv, side, i = entry
                t = k.shape[0]
                hd = side.shape[-1]
                planes = [k] if v is None else [k, v]
                rows_kv = jnp.stack(
                    [p.reshape(t, -1) for p in planes], axis=1
                ).astype(side.dtype)
                if rows_kv.shape[-1] < hd:
                    rows_kv = jnp.pad(
                        rows_kv,
                        [(0, 0), (0, 0), (0, hd - rows_kv.shape[-1])],
                    )
                side = jax.lax.dynamic_update_slice(
                    side, rows_kv[:, :, None, :], (0, 0, i, 0)
                )
                return (kv, side, i)

            def staged_attn(q, entry, meta, **kw):
                kv, side, i = entry
                return attn_fn(
                    q, kv, meta,
                    side_kv=side,
                    side_len=jnp.reshape(i + 1, (1,)),
                    **kw,
                )

        def body(carry, i):
            kv, sides, states, tok, out_buf = carry
            extra = self._model_stats()
            pos = base_lens + i
            # Micro-step i runs only sequences with i < n_active: under-K
            # tails drop their queries (id == s_pad, the kernels' drop
            # convention, like padding rows) and route their KV writes to
            # the reserved dump page 0.  pos for a masked row may step
            # past the sequence's page allocation, so the page index is
            # masked BEFORE the table gather (jit clips OOB gathers to
            # the last column — a real page).
            live = (valid > 0) & (i < n_active)
            page_idx = jnp.where(live, pos // page_size, 0)
            meta = AttentionMetadata(
                q_seq_ids=jnp.where(live, rows, s_pad),
                q_positions=pos,
                slot_mapping=jnp.where(
                    live,
                    block_tables[rows, page_idx] * page_size
                    + pos % page_size,
                    0,
                ),
                block_tables=block_tables,
                # Staged: seq_lens is the POOL-resident length (base);
                # this dispatch's rows are covered by the side buffer.
                seq_lens=(
                    base_valid
                    if staged
                    else jnp.where(live, pos + 1, 0)
                ),
                logits_indices=rows,
                chunk_starts=pos,
            )
            smeta = SamplingMetadata(
                temperature=temp,
                top_k=top_k,
                top_p=top_p,
                min_p=min_p,
                repetition_penalty=rep,
                presence_penalty=pres,
                frequency_penalty=freq,
                # Per-token PRNG stream: low word advances with position,
                # matching the single-step path's keys[s,1]=len(tokens).
                keys=jnp.stack(
                    [keys[:, 0], keys[:, 1] + i.astype(jnp.uint32)], axis=1
                ),
                prompt_tokens=prompt_toks,
                output_tokens=out_buf,
            )
            # Barrier blocks XLA's loop-invariant code motion on the
            # params: without it, quantized weights get dequantized ONCE
            # outside the scan — materializing the full bf16 model in
            # HBM (OOM at serving pool sizes) and erasing the int8
            # bandwidth win.  With it, the int8 bytes stream per
            # micro-step and the dequant fuses into the matmuls.
            params_i = jax.lax.optimization_barrier(params)
            if states is not None:
                # A row this micro-step masks keeps its state: its slot
                # points past the end, where a write is dropped.
                extra["seq_state"] = SeqState(
                    states=states,
                    slots=jnp.where(live, state_slots, states[0].shape[0]),
                    last_rows=rows,
                    max_q=1,
                )
            if staged:
                entries = [
                    make_entry(kv_l, side_l, i)
                    for kv_l, side_l in zip(kv, sides)
                ]
                logits, new_entries, *new_states = self.model.forward(
                    params_i,
                    tok,
                    entries,
                    meta,
                    attn_fn=staged_attn,
                    kv_write_fn=staged_write,
                    **extra,
                )
                kv = [e[0] for e in new_entries]
                sides = [e[1] for e in new_entries]
            else:
                logits, kv, *new_states = self.model.forward(
                    params_i,
                    tok,
                    kv,
                    meta,
                    attn_fn=attn_fn,
                    kv_write_fn=self._kv_write_fn,
                    **extra,
                )
            if new_states:
                (states,) = new_states
            with jax.named_scope(SCOPE_SAMPLER):
                new_tok, _ = sample(
                    logits,
                    smeta,
                    do_penalties=do_penalties,
                    do_top_k_p=do_top_k_p,
                    return_logprobs=False,
                )
            if do_penalties:
                # Masked rows scatter out of bounds (dropped).
                out_buf = out_buf.at[
                    rows,
                    jnp.where(live, out_lens + i, out_buf.shape[1]),
                ].set(new_tok, mode="drop")
            if "stats" in extra:
                return (kv, sides, states, new_tok, out_buf), (
                    new_tok, extra["stats"]["moe_picks"])
            return (kv, sides, states, new_tok, out_buf), new_tok

        if staged:
            # Model dtype even for int8 pools: rows quantize ONCE per
            # dispatch at flush, not per micro-step.
            def side0(kv_l):
                data = kv_l[0] if isinstance(kv_l, tuple) else kv_l
                dt = (
                    self.model.dtype
                    if isinstance(kv_l, tuple)
                    else data.dtype
                )
                return jnp.zeros(
                    (s_pad, data.shape[0], k_steps, data.shape[-1]), dt
                )

            sides0 = [side0(kv_l) for kv_l in kv_caches]
        else:
            sides0 = [jnp.zeros((), jnp.int32) for _ in kv_caches]
        (kv_caches, sides_out, seq_states, _, _), toks = jax.lax.scan(
            body,
            (kv_caches, sides0, seq_states, tokens, out_toks),
            jnp.arange(k_steps, dtype=jnp.int32),
        )
        if staged:
            # Per-sequence flush lengths: under-K tails staged only
            # n_active rows; columns past that are garbage.
            with jax.named_scope(SCOPE_KV_WRITE):
                kv_caches = [
                    self._kv_flush_fn(
                        kv_l, side_l, block_tables, base_valid, n_active
                    )
                    for kv_l, side_l in zip(kv_caches, sides_out)
                ]
        picks = None
        if isinstance(toks, tuple):
            toks, picks = toks
        # Each sequence's last VALID token, into its carry row.
        last = toks[jnp.clip(n_active - 1, 0, k_steps - 1), rows]
        carry = self._merge_carry(carry, slot_rows, last)
        if picks is not None:
            # The router's pick counts over the K micro-steps ride as
            # one more row behind the tokens: one array, one transfer.
            picks = picks.sum(axis=0).astype(toks.dtype)
            row = jnp.zeros((1, s_pad), toks.dtype).at[
                0, : picks.shape[0]
            ].set(picks)
            toks = jnp.concatenate([toks, row], axis=0)
        if seq_states is not None:
            kv_caches = (kv_caches, seq_states)
        return toks, carry, kv_caches
