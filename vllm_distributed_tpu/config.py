"""Engine configuration.

Capability parity with the config surface the reference drives
(SURVEY.md §2.3): ``AsyncEngineArgs.from_cli_args`` (launch.py:29,399) →
``EngineArgs.from_cli_args`` here; vLLM's VllmConfig with model / cache /
parallel / scheduler sub-configs → ``EngineConfig``.  The
``distributed_executor_backend`` field is pluggable with an executor class,
which is exactly how the reference injects its CustomExecutor
(launch.py:400-405).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any

from vllm_distributed_tpu import envs
from vllm_distributed_tpu.logger import init_logger

logger = init_logger(__name__)

_STR_DTYPE_TO_JAX = {
    "float32": "float32",
    "fp32": "float32",
    "bfloat16": "bfloat16",
    "bf16": "bfloat16",
    "float16": "bfloat16",  # TPUs have no fp16 MXU path; promote to bf16.
    "half": "bfloat16",
}


# Model types this engine has a block for and the installed
# ``transformers`` may not know: their config.json is read as a plain
# ``PretrainedConfig`` (every key an attribute, nothing defaulted).
_OWN_MODEL_TYPES = ("lfm2_moe",)
LAYER_TYPES = ("conv", "full_attention")


def _load_hf_config(model: str, trust_remote_code: bool = False):
    """Load a HuggingFace config.json for `model` (local dir or hub id)."""
    from transformers import AutoConfig, PretrainedConfig
    from transformers.models.auto.configuration_auto import (
        CONFIG_MAPPING_NAMES,
    )

    path = os.path.join(model, "config.json")
    if os.path.isfile(path):
        with open(path) as f:
            raw = json.load(f)
        model_type = raw.get("model_type")
        if (
            model_type in _OWN_MODEL_TYPES
            and model_type not in CONFIG_MAPPING_NAMES
        ):
            config = PretrainedConfig.from_dict(raw)
            config.model_type = model_type  # an instance attribute
            return config
    return AutoConfig.from_pretrained(model, trust_remote_code=trust_remote_code)


def check_layer_types(hf_config: Any, model: str = "") -> None:
    """A config that mixes kinds of layer (``layer_types``) or leads
    with dense layers (``num_dense_layers``) says so in keys the engine
    reads; an entry it does not know, a list of another length than the
    depth or more dense layers than layers is an error here, at load,
    not a silent default."""
    depth = getattr(hf_config, "num_hidden_layers", None)
    kinds = getattr(hf_config, "layer_types", None)
    if kinds is not None and getattr(hf_config, "model_type", "") in (
        _OWN_MODEL_TYPES
    ):
        unknown = sorted({str(k) for k in kinds} - set(LAYER_TYPES))
        if unknown:
            raise ValueError(
                f"the config of {model!r} has layer_types entries "
                f"{unknown}; this engine knows {list(LAYER_TYPES)}"
            )
        if depth is not None and len(kinds) != int(depth):
            raise ValueError(
                f"the config of {model!r} states {len(kinds)} layer_types "
                f"for num_hidden_layers {depth}"
            )
    dense = getattr(hf_config, "num_dense_layers", None)
    if dense is not None and depth is not None and not (
        0 <= int(dense) <= int(depth)
    ):
        raise ValueError(
            f"the config of {model!r} states num_dense_layers {dense} "
            f"for num_hidden_layers {depth}"
        )


@dataclass
class ModelConfig:
    model: str
    tokenizer: str | None = None
    dtype: str = "auto"
    seed: int = 0
    max_model_len: int | None = None
    trust_remote_code: bool = False
    hf_config: Any = None  # transformers PretrainedConfig, loaded lazily
    quantization: str | None = None
    skip_tokenizer_init: bool = False
    load_format: str = "auto"  # "auto" (safetensors) | "dummy"
    # Mirrored from ParallelConfig so MoE models can pick the expert
    # sharding layout (experts whole over "tp" vs split like dense MLPs).
    enable_expert_parallel: bool = False

    def __post_init__(self) -> None:
        if self.quantization == "none":  # spelt out: no quantization
            self.quantization = None
        if self.quantization is not None:
            from vllm_distributed_tpu.ops.quant import METHODS

            if self.quantization not in METHODS:
                raise ValueError(
                    f"unsupported quantization {self.quantization!r}; "
                    f"supported: {METHODS} (weight-only, quantized on load)"
                )
        if self.tokenizer is None:
            self.tokenizer = self.model
        if self.hf_config is None:
            self.hf_config = _load_hf_config(self.model, self.trust_remote_code)
        check_layer_types(self.hf_config, self.model)
        if self.dtype == "auto":
            torch_dtype = getattr(self.hf_config, "torch_dtype", None)
            name = str(torch_dtype).replace("torch.", "") if torch_dtype else "bfloat16"
            self.dtype = _STR_DTYPE_TO_JAX.get(name, "bfloat16")
        else:
            self.dtype = _STR_DTYPE_TO_JAX[self.dtype]
        derived_max = getattr(self.hf_config, "max_position_embeddings", 2048)
        if self.max_model_len is None:
            self.max_model_len = derived_max
        elif self.max_model_len > derived_max and not _supports_rope_scaling(
            self.hf_config
        ):
            logger.warning(
                "max_model_len %d exceeds the model's max_position_embeddings %d",
                self.max_model_len,
                derived_max,
            )

    # --- architecture helpers used by the engine/runner ---
    @property
    def architecture(self) -> str:
        archs = getattr(self.hf_config, "architectures", None) or []
        return archs[0] if archs else self.hf_config.model_type

    def _first_key(self, what: str, keys: tuple) -> int:
        """The first of ``keys`` the config has, or an error that names
        them: a config that spells a size some other way fails here,
        not on a bare AttributeError deep in the runner."""
        for key in keys:
            value = getattr(self.hf_config, key, None)
            if value is not None:
                return int(value)
        raise ValueError(
            f"the config of {self.model!r} ("
            f"{getattr(self.hf_config, 'model_type', '?')}) states no "
            f"{what}: none of {list(keys)} is set"
        )

    @property
    def latent_attention(self) -> bool:
        """A config with ``kv_lora_rank`` caches one latent row a token
        (MLA): it has no KV heads and no single head size."""
        return getattr(self.hf_config, "kv_lora_rank", None) is not None

    def get_num_layers(self) -> int:
        # A latent-attention config of the double-layer family counts
        # its layers in ``num_layers``; the HF class of that family also
        # carries ``num_hidden_layers``, its count of attention
        # sublayers, which is not the number of layers.
        if self.latent_attention and hasattr(self.hf_config, "num_layers"):
            return self._first_key("number of layers", ("num_layers",))
        return self._first_key(
            "number of layers", ("num_hidden_layers", "n_layer", "num_layers")
        )

    def get_hidden_size(self) -> int:
        return self._first_key("hidden size", ("hidden_size", "n_embd"))

    def get_num_attention_heads(self) -> int:
        return self._first_key(
            "number of attention heads", ("num_attention_heads", "n_head")
        )

    def _refuse_latent(self, what: str) -> None:
        if self.latent_attention:
            raise ValueError(
                f"the config of {self.model!r} has latent attention "
                f"(kv_lora_rank): it states no {what}; what its cache "
                "holds is the model's kv_cache_layout()"
            )

    def get_num_kv_heads(self) -> int:
        self._refuse_latent("number of KV heads")
        return self._first_key(
            "number of KV heads",
            ("num_key_value_heads", "num_attention_heads", "n_head"),
        )

    def get_head_dim(self) -> int:
        self._refuse_latent("single head size")
        head_dim = getattr(self.hf_config, "head_dim", None)
        if head_dim is not None:
            return head_dim
        return self.get_hidden_size() // self.get_num_attention_heads()

    def get_vocab_size(self) -> int:
        return self.hf_config.vocab_size


def _supports_rope_scaling(hf_config: Any) -> bool:
    return getattr(hf_config, "rope_scaling", None) is not None


@dataclass
class CacheConfig:
    """Paged KV cache configuration.

    `page_size` is tokens per page.  `num_pages` may be given explicitly
    (tests, CPU) or derived from free HBM at engine init
    (hbm_utilization, the analog of gpu_memory_utilization).
    """

    page_size: int = 16
    num_pages: int | None = None
    hbm_utilization: float = 0.9
    # Automatic prefix caching: content-addressed full pages with
    # ref-counted LRU reuse (block_manager.PrefixCachingAllocator).
    # Default off — the seed allocator path is byte-for-byte unchanged.
    enable_prefix_caching: bool = False
    # Prefix index structure (ISSUE 14): "radix" = radix tree over
    # token sequences with leaf-first cache-aware LRU eviction and the
    # optional host-DRAM spill tier; "flat" = the PR 1 hash-chain map
    # (kept as the tests' reference index).  Ignored unless
    # enable_prefix_caching.
    prefix_cache_index: str = "radix"
    # Host-DRAM spill tier (ISSUE 14): pages evicted from HBM spill to
    # a bounded host pool of this many pages and stream back ahead of a
    # prefill resume.  0 = off; radix index only.
    kv_spill_host_pages: int = 0
    # Restore-vs-recompute crossover in tokens: shorter host runs are
    # recomputed rather than restored.
    kv_spill_restore_min_tokens: int = 32
    # "auto" follows model dtype; "int8" quantizes the pool per (token,
    # kv head) — ~2x capacity, ~2x less attention HBM traffic; staged
    # decode rows quantize at flush, numerics run f32 in-kernel.
    cache_dtype: str = "auto"

    _CACHE_DTYPES = ("auto", "bfloat16", "float16", "float32", "int8")

    def __post_init__(self) -> None:
        if self.page_size & (self.page_size - 1):
            raise ValueError(f"page_size must be a power of 2, got {self.page_size}")
        if self.prefix_cache_index not in ("radix", "flat"):
            raise ValueError(
                f"unsupported prefix_cache_index "
                f"{self.prefix_cache_index!r}; supported: radix | flat"
            )
        if self.kv_spill_host_pages < 0:
            raise ValueError("kv_spill_host_pages must be >= 0")
        if self.kv_spill_host_pages > 0 and self.prefix_cache_index != "radix":
            raise ValueError(
                "the host-DRAM spill tier needs the radix prefix index "
                "(--prefix-cache-index radix)"
            )
        if self.cache_dtype == "fp8":
            raise ValueError(
                "fp8 KV cache is not supported on TPU (no fp8 VPU "
                "path on v5e) — use --kv-cache-dtype int8"
            )
        if self.cache_dtype not in self._CACHE_DTYPES:
            raise ValueError(
                f"unsupported kv-cache dtype {self.cache_dtype!r}; "
                f"supported: {self._CACHE_DTYPES}"
            )


@dataclass
class ParallelConfig:
    """Parallelism layout.

    The reference asserts world == tp × pp (launch.py:85-92).  Here the
    world is a JAX mesh with named axes; TP/EP/DP are sharding annotations
    over it (SURVEY.md §7 design stance), and world_size counts chips.
    """

    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    data_parallel_size: int = 1
    enable_expert_parallel: bool = False
    # Pluggable executor class or name — the injection point the reference
    # uses for CustomExecutor (launch.py:400-405).
    distributed_executor_backend: Any = None
    # Multi-host topology
    num_hosts: int = 1
    host_id: int = 0
    coordinator_address: str | None = None

    @property
    def world_size(self) -> int:
        return (
            self.tensor_parallel_size
            * self.pipeline_parallel_size
            * self.data_parallel_size
        )

    def __post_init__(self) -> None:
        if self.pipeline_parallel_size != 1:
            raise ValueError(
                "pipeline parallelism is deliberately not supported on "
                "TPU: one jitted SPMD program spans every mesh device, so "
                "stage-level overlap between in-flight batches cannot "
                "happen inside a single program, and ICI bandwidth makes "
                "pure tensor parallelism scale to pod slices without PP's "
                "pipeline bubbles (the reference needed PP because its "
                "data plane was NCCL over a LAN, launch.py:211-314).  Use "
                "-tp across chips/hosts instead; see README.md."
            )


@dataclass
class SchedulerConfig:
    max_num_seqs: int = 64
    max_num_batched_tokens: int = 2048
    enable_chunked_prefill: bool = True
    max_model_len: int = 2048
    # Decode steps fused into one device dispatch when every running
    # request is decoding (a lax.scan on device).  Amortizes the per-step
    # host round trip — the TPU answer to SURVEY.md §3.3's "push the
    # steady-state loop into a compiled while-loop".  1 disables.
    num_decode_steps: int = 8
    # Fused-decode dispatches kept in flight before the engine blocks on
    # results (the reference's max_concurrent_batches, launch.py:298-302,
    # generalized).  The device carry makes dispatch N+1 independent of
    # N's results, so depth only trades token-delivery latency for
    # host/transport-latency hiding; raise it when the chip is reached
    # over a high-RTT link.
    max_concurrent_dispatches: int = 2
    # Pre-compile the fused-decode programs for every batch bucket at
    # boot (adds startup time; removes mid-serve recompile stalls).
    warmup_decode: bool = False
    # Pre-compile the prefill/mixed single-step program per token
    # bucket at boot (first-request TTFT becomes execution time).
    warmup_prefill: bool = False
    # ---- overload resilience (ISSUE 8; every knob defaults OFF so the
    # seed behavior is unchanged until an operator opts in) ----
    # Caps on the admission queue: waiting requests / queued prompt
    # tokens.  0 = unbounded.  Enforced at the AsyncLLM surface (typed
    # EngineOverloadedError -> HTTP 429 + Retry-After).
    max_waiting_requests: int = 0
    max_queued_tokens: int = 0
    # Reject admission when the prompt's estimated page demand would
    # leave less than this fraction of usable KV pages free.  0 = off.
    kv_admission_watermark: float = 0.0
    # Server-default per-request deadline (ms); 0 = none.
    default_deadline_ms: int = 0
    # Preemptions per request (while others wait) before the scheduler
    # sheds it with finish_reason="overloaded" instead of recompute
    # thrash.  0 = off.
    preempt_shed_threshold: int = 0
    # ---- QoS control plane (ISSUE 16; default OFF = seed behavior) ----
    # SLO class registry spec, "name:priority[:share[:weight]]" comma
    # list (engine/qos.py).  Empty disables class-aware admission,
    # priority admission ordering, and class-weighted preemption.
    qos_classes: str = ""
    # Chunked-prefill fairness budget: max fraction of the per-step
    # token budget prefill chunks may take while a decode-bound request
    # of higher-or-equal class is running.  0 = off.
    qos_prefill_share: float = 0.0
    # ---- speculative decoding (ISSUE 11; default OFF) ----
    # Max tokens the n-gram prompt-lookup proposer drafts per request
    # per step (engine/spec_decode.py); the model runner verifies all
    # drafts in one fused pass and greedy accept/reject keeps the
    # matching prefix + one bonus token.  Greedy outputs stay
    # bit-identical to the non-speculative path.  0 = off.
    spec_ngram_k: int = 0
    # Tail n-gram match lengths the proposer tries (longest first).
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1

    def fused_decode_steps(self) -> int:
        """The uniform fused-scan length K the scheduler emits: the
        configured num_decode_steps clamped by the token budget at the
        FULL batch size (so K never varies with batch growth — every
        distinct K compiles its own scan) and floored to a power of 2.
        The single source of truth for schedule() and warmup_decode."""
        k = min(
            self.num_decode_steps,
            max(self.max_num_batched_tokens // self.max_num_seqs, 1),
        )
        return 1 << (k.bit_length() - 1)

    def __post_init__(self) -> None:
        if self.max_num_batched_tokens < self.max_num_seqs:
            raise ValueError(
                "max_num_batched_tokens must be >= max_num_seqs "
                f"({self.max_num_batched_tokens} < {self.max_num_seqs})"
            )
        if self.num_decode_steps < 1:
            raise ValueError("num_decode_steps must be >= 1")
        if self.max_concurrent_dispatches < 1:
            raise ValueError("max_concurrent_dispatches must be >= 1")
        if not 0.0 <= self.kv_admission_watermark < 1.0:
            raise ValueError(
                "kv_admission_watermark must be in [0, 1), got "
                f"{self.kv_admission_watermark}"
            )
        if self.spec_ngram_k < 0:
            raise ValueError(
                f"spec_ngram_k must be >= 0 (0 disables), got "
                f"{self.spec_ngram_k}"
            )
        if self.spec_ngram_k and not (
            1 <= self.spec_ngram_min <= self.spec_ngram_max
        ):
            raise ValueError(
                "need 1 <= spec_ngram_min <= spec_ngram_max, got "
                f"min={self.spec_ngram_min} max={self.spec_ngram_max}"
            )
        if self.spec_ngram_k and (
            self.spec_ngram_k + 1 > self.max_num_batched_tokens
        ):
            raise ValueError(
                f"spec_ngram_k={self.spec_ngram_k} needs a verify window "
                f"of {self.spec_ngram_k + 1} tokens but the step budget "
                f"is {self.max_num_batched_tokens}"
            )
        for name in (
            "max_waiting_requests",
            "max_queued_tokens",
            "default_deadline_ms",
            "preempt_shed_threshold",
        ):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"{name} must be >= 0 (0 disables), got "
                    f"{getattr(self, name)}"
                )
        if not 0.0 <= self.qos_prefill_share <= 1.0:
            raise ValueError(
                "qos_prefill_share must be in [0, 1] (0 disables), got "
                f"{self.qos_prefill_share}"
            )
        # Malformed class specs fail at config time, not mid-overload.
        from vllm_distributed_tpu.engine.qos import parse_qos_classes

        parse_qos_classes(self.qos_classes)
        if 1 < self.num_decode_steps and (
            self.fused_decode_steps() < self.num_decode_steps
        ):
            budget_k = max(
                self.max_num_batched_tokens // self.max_num_seqs, 1
            )
            if budget_k < self.num_decode_steps:
                hint = (
                    "raise max_num_batched_tokens "
                    f"(budget allows only {budget_k} steps at full "
                    f"batch max_num_seqs={self.max_num_seqs})"
                )
            else:
                hint = "use a power-of-2 num_decode_steps"
            logger.warning(
                "num_decode_steps=%d runs as %d (uniform fused scan "
                "length); %s to keep the configured depth",
                self.num_decode_steps,
                self.fused_decode_steps(),
                hint,
            )


@dataclass
class DeviceConfig:
    # Recorded, not acted on: the platform is whatever JAX finds
    # (JAX_PLATFORMS), and the kernel backend is VDT_USE_PALLAS.
    device: str = "auto"


@dataclass
class ObservabilityConfig:
    collect_metrics: bool = True
    # Server-side jax.profiler captures: artifact directory for the
    # worker `profile` verb AND the gated POST /debug/profile endpoint
    # (None/empty = endpoint answers 404).  --profile-dir wins over
    # VDT_PROFILE_DIR.
    profile_dir: str | None = None
    # Per-request tracing (tracing.py): root span per API request,
    # queue/prefill/decode spans, per-step schedule/dispatch/gather
    # spans, and worker-side RPC spans merged across hosts.  Default
    # off: the engine loop runs the no-op tracer path.
    enable_tracing: bool = False
    # Completed traces kept in the in-memory ring (/debug/traces).
    trace_ring_size: int = 256
    # Flight recorder (engine/flight_recorder.py): per-step records
    # kept in the always-on bounded ring (0 disables recording and the
    # automatic failure/drain dumps).
    flight_recorder_size: int = 512


@dataclass
class EngineConfig:
    """Bundle of all sub-configs (the analog of vllm_config, which the
    reference passes whole to workers at launch.py:162, 238, 284)."""

    model_config: ModelConfig
    cache_config: CacheConfig
    parallel_config: ParallelConfig
    scheduler_config: SchedulerConfig
    device_config: DeviceConfig
    observability_config: ObservabilityConfig = field(
        default_factory=ObservabilityConfig
    )
    # KV transfer / disaggregated-prefill hook (SURVEY.md §3.4); None = off.
    kv_transfer_config: Any = None

    def to_json(self) -> str:
        def _default(o):
            if dataclasses.is_dataclass(o):
                return dataclasses.asdict(o)
            return str(o)

        d = {
            k: v
            for k, v in dataclasses.asdict(self).items()
            if k not in ("model_config",)
        }
        d["model"] = self.model_config.model
        return json.dumps(d, default=_default)


@dataclass
class RouterArgs:
    """CLI-buildable config for the multi-replica router front-end
    (ISSUE 10, router/app.py) — `vdt router`.  None fields resolve late
    from the VDT_ROUTER_* env registry so every knob works on both the
    CLI and the programmatic path."""

    replicas: list[str] = field(default_factory=list)
    policy: str | None = None  # affinity | least_loaded | round_robin
    max_migrations: int | None = None
    affinity_block_tokens: int | None = None
    affinity_capacity: int | None = None
    affinity_min_tokens: int | None = None
    health_interval: float | None = None
    connect_timeout: float | None = None
    read_timeout: float | None = None
    api_key: str | None = None
    # Elastic fleet (ISSUE 13; default off — static --replica URLs
    # behave exactly as before): spawn and supervise this many managed
    # `vdt serve` replicas from the --fleet-cmd template.
    fleet_size: int = 0
    fleet_cmd: str | None = None  # None -> $VDT_FLEET_CMD
    # Disaggregated pools (ISSUE 15): fixed per-role replica counts
    # spawned alongside the mixed fleet from the same --fleet-cmd
    # template (the launcher sets VDT_ROUTER_ROLE and substitutes a
    # {role} placeholder when present).  0 = no role-separated pools.
    fleet_prefill: int = 0
    fleet_decode: int = 0
    # Arm the autoscaler control loop over the managed fleet
    # (min/max None -> $VDT_AUTOSCALE_MIN/MAX_REPLICAS).
    autoscale: bool = False
    autoscale_min: int | None = None
    autoscale_max: int | None = None
    # Crash-safe router (ISSUE 17; default off): directory for the
    # durable control-plane WAL.  A router restarted against the same
    # dir re-adopts its still-running managed replicas and replays
    # journaled in-flight requests when their clients reconnect.
    state_dir: str | None = None  # None -> $VDT_ROUTER_STATE_DIR

    @staticmethod
    def add_cli_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
        parser.add_argument(
            "--replica",
            dest="replicas",
            action="append",
            default=None,
            metavar="URL",
            help="replica base URL (repeatable); defaults to "
            "$VDT_ROUTER_REPLICAS (comma-separated)",
        )
        parser.add_argument(
            "--policy",
            type=str,
            default=None,
            choices=["affinity", "least_loaded", "round_robin"],
            help="placement policy (default: $VDT_ROUTER_POLICY or "
            "affinity)",
        )
        parser.add_argument(
            "--max-migrations",
            type=int,
            default=None,
            help="live migrations allowed per request (default: "
            "$VDT_ROUTER_MAX_MIGRATIONS or 3)",
        )
        parser.add_argument(
            "--affinity-block-tokens", type=int, default=None,
            help="prefix-chain block size in tokens (default: "
            "$VDT_ROUTER_AFFINITY_BLOCK_TOKENS or 16; match the engine "
            "page size)",
        )
        parser.add_argument(
            "--affinity-capacity", type=int, default=None,
            help="blocks remembered per replica, LRU beyond (default: "
            "$VDT_ROUTER_AFFINITY_CAPACITY or 8192)",
        )
        parser.add_argument(
            "--affinity-min-tokens", type=int, default=None,
            help="matched tokens before affinity outranks least-loaded "
            "(default: $VDT_ROUTER_AFFINITY_MIN_TOKENS or 16)",
        )
        parser.add_argument(
            "--health-interval", type=float, default=None,
            help="replica health-poll interval in seconds (default: "
            "$VDT_ROUTER_HEALTH_INTERVAL_SECONDS or 2)",
        )
        parser.add_argument(
            "--connect-timeout", type=float, default=None,
            help="router→replica TCP connect deadline in seconds "
            "(default: $VDT_ROUTER_CONNECT_TIMEOUT_SECONDS or 5)",
        )
        parser.add_argument(
            "--read-timeout", type=float, default=None,
            help="router→replica per-read (SSE) deadline in seconds — "
            "bounds how long a silent replica stalls a stream before "
            "migration (default: $VDT_ROUTER_READ_TIMEOUT_SECONDS or "
            "600)",
        )
        parser.add_argument(
            "--fleet-size", type=int, default=0,
            help="spawn and supervise this many managed `vdt serve` "
            "replicas as child processes (health-gated warmup, "
            "drain-before-terminate scale-down, crash-loop restarts); "
            "0 = static --replica URLs only",
        )
        parser.add_argument(
            "--fleet-cmd", type=str, default=None,
            help="command template for managed replicas with {port} "
            "(and optional {replica_id}) placeholders, e.g. "
            "'vdt serve MODEL --host 127.0.0.1 --port {port}' "
            "(default: $VDT_FLEET_CMD)",
        )
        parser.add_argument(
            "--fleet-prefill", type=int, default=0,
            help="spawn this many PREFILL-role managed replicas "
            "(disaggregated prefill/decode, ISSUE 15): long prompts "
            "prefill here and hand their KV pages off to the "
            "decode/mixed pool at first token; 0 = no prefill pool",
        )
        parser.add_argument(
            "--fleet-decode", type=int, default=0,
            help="spawn this many DECODE-role managed replicas "
            "alongside the mixed fleet; 0 = none (mixed replicas "
            "already decode)",
        )
        parser.add_argument(
            "--autoscale", action="store_true", default=False,
            help="arm the autoscaler control loop: hold the managed "
            "replica count to the traffic (queue-depth watermarks "
            "with hysteresis, optional 429-rate and fleet-ITL-p99 "
            "triggers, per-direction cooldowns) within "
            "[--autoscale-min, --autoscale-max]",
        )
        parser.add_argument(
            "--autoscale-min", type=int, default=None,
            help="autoscaler floor (default: "
            "$VDT_AUTOSCALE_MIN_REPLICAS or 1)",
        )
        parser.add_argument(
            "--autoscale-max", type=int, default=None,
            help="autoscaler ceiling (default: "
            "$VDT_AUTOSCALE_MAX_REPLICAS or 4)",
        )
        parser.add_argument(
            "--state-dir", type=str, default=None,
            help="durable control-plane state directory: a bounded "
            "write-ahead log of fleet membership, in-flight request "
            "journals, and QoS config; a router restarted against it "
            "re-adopts still-running managed replicas and finishes "
            "interrupted streams bit-identically when clients "
            "reconnect (default: $VDT_ROUTER_STATE_DIR; empty = off)",
        )
        return parser

    @classmethod
    def from_cli_args(cls, args: argparse.Namespace) -> "RouterArgs":
        attrs = [f.name for f in dataclasses.fields(cls)]
        kwargs = {a: getattr(args, a) for a in attrs if hasattr(args, a)}
        if kwargs.get("replicas") is None:
            kwargs["replicas"] = []
        return cls(**kwargs)

    def resolved_replicas(self) -> list[str]:
        urls = [u.rstrip("/") for u in self.replicas if u]
        if not urls:
            urls = list(envs.VDT_ROUTER_REPLICAS)
        return urls

    def resolved_state_dir(self) -> str:
        """--state-dir over $VDT_ROUTER_STATE_DIR; "" = durable state
        off (the seed behavior)."""
        if self.state_dir is not None:
            return self.state_dir
        return envs.VDT_ROUTER_STATE_DIR


@dataclass
class EngineArgs:
    """CLI-buildable engine args (parity: AsyncEngineArgs.from_cli_args,
    launch.py:29, 399)."""

    model: str = "facebook/opt-125m"
    tokenizer: str | None = None
    dtype: str = "auto"
    seed: int = 0
    max_model_len: int | None = None
    trust_remote_code: bool = False
    quantization: str | None = None
    skip_tokenizer_init: bool = False
    load_format: str = "auto"

    page_size: int = 16
    num_kv_pages: int | None = None
    # None -> resolved late from VDT_HBM_UTILIZATION (default 0.9), so the
    # env var works on both the CLI and the programmatic path.
    hbm_utilization: float | None = None
    kv_cache_dtype: str = "auto"
    enable_prefix_caching: bool = False
    prefix_cache_index: str = "radix"
    # Tiered KV spill knobs (None -> resolved late from VDT_KV_SPILL_*
    # so the env vars work on both the CLI and programmatic paths).
    kv_spill_host_pages: int | None = None
    kv_spill_restore_min_tokens: int | None = None

    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    data_parallel_size: int = 1
    enable_expert_parallel: bool = False
    distributed_executor_backend: Any = None
    num_hosts: int = 1
    host_id: int = 0
    coordinator_address: str | None = None

    max_num_seqs: int = 64
    max_num_batched_tokens: int | None = None
    enable_chunked_prefill: bool = True
    num_decode_steps: int = 8
    max_concurrent_dispatches: int = 2
    warmup_decode: bool = False
    warmup_prefill: bool = False

    # Overload resilience (None -> resolved late from the VDT_* env
    # vars, so the knobs work on both the CLI and programmatic paths).
    max_waiting_requests: int | None = None
    max_queued_tokens: int | None = None
    kv_admission_watermark: float | None = None
    default_deadline_ms: int | None = None
    preempt_shed_threshold: int | None = None

    # Speculative decoding (None -> resolved late from VDT_SPEC_NGRAM_*).
    speculative_ngram_k: int | None = None
    speculative_ngram_max: int | None = None
    speculative_ngram_min: int | None = None

    # QoS control plane (None -> resolved late from VDT_QOS_*).
    qos_classes: str | None = None
    qos_prefill_share: float | None = None

    # JSON dict (or dict) configuring a KV connector (disaggregated
    # prefill hook, SURVEY.md §3.4); None = off.
    kv_transfer_config: Any = None

    device: str = "auto"
    profile_dir: str | None = None
    disable_log_stats: bool = False
    # None -> resolved late from VDT_TRACING so the env var works on
    # both the CLI and the programmatic path.
    enable_tracing: bool | None = None

    @staticmethod
    def add_cli_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
        parser.add_argument("--model", type=str, default=EngineArgs.model)
        parser.add_argument("--tokenizer", type=str, default=None)
        parser.add_argument(
            "--dtype",
            type=str,
            default="auto",
            choices=["auto", *sorted(_STR_DTYPE_TO_JAX)],
        )
        parser.add_argument("--seed", type=int, default=0)
        parser.add_argument("--max-model-len", type=int, default=None)
        parser.add_argument("--trust-remote-code", action="store_true")
        parser.add_argument("--quantization", "-q", type=str, default=None)
        parser.add_argument("--skip-tokenizer-init", action="store_true")
        parser.add_argument(
            "--load-format",
            type=str,
            default="auto",
            choices=["auto", "dummy"],
        )
        parser.add_argument("--page-size", "--block-size", type=int, default=16)
        parser.add_argument("--num-kv-pages", type=int, default=None)
        parser.add_argument(
            "--hbm-utilization",
            "--gpu-memory-utilization",
            type=float,
            default=None,
            help="fraction of free HBM given to the KV cache "
            "(default: $VDT_HBM_UTILIZATION or 0.9)",
        )
        parser.add_argument("--kv-cache-dtype", type=str, default="auto")
        parser.add_argument(
            "--enable-prefix-caching",
            action="store_true",
            help="reuse KV pages across requests sharing a prompt "
            "prefix (content-addressed pages, ref-counted LRU "
            "eviction)",
        )
        parser.add_argument(
            "--prefix-cache-index",
            type=str,
            default="radix",
            choices=["radix", "flat"],
            help="prefix index structure: radix tree with leaf-first "
            "cache-aware eviction + optional host-DRAM spill tier, or "
            "the flat hash-chain map (kept as the tests' reference index)",
        )
        parser.add_argument(
            "--kv-spill-host-pages",
            type=int,
            default=None,
            help="host-DRAM spill tier size in KV pages: evicted pages "
            "spill to host memory and stream back ahead of prefill "
            "resume (default: $VDT_KV_SPILL_HOST_PAGES or 0 = off)",
        )
        parser.add_argument(
            "--kv-spill-restore-min-tokens",
            type=int,
            default=None,
            help="restore-vs-recompute crossover: host runs shorter "
            "than this many tokens are recomputed instead of restored "
            "(default: $VDT_KV_SPILL_RESTORE_MIN_TOKENS or 32)",
        )
        parser.add_argument(
            "--tensor-parallel-size", "-tp", type=int, default=1
        )
        parser.add_argument(
            "--pipeline-parallel-size", "-pp", type=int, default=1
        )
        parser.add_argument("--data-parallel-size", "-dp", type=int, default=1)
        parser.add_argument("--enable-expert-parallel", action="store_true")
        parser.add_argument(
            "--distributed-executor-backend", type=str, default=None
        )
        parser.add_argument("--num-hosts", type=int, default=1)
        parser.add_argument("--host-id", type=int, default=0)
        parser.add_argument("--coordinator-address", type=str, default=None)
        parser.add_argument("--max-num-seqs", type=int, default=64)
        parser.add_argument("--max-num-batched-tokens", type=int, default=None)
        parser.add_argument(
            "--num-decode-steps",
            type=int,
            default=8,
            help="decode steps fused into one device dispatch (1 disables)",
        )
        parser.add_argument(
            "--max-concurrent-dispatches",
            type=int,
            default=2,
            help="fused-decode dispatches kept in flight before the "
            "engine blocks on results (raise over high-RTT links)",
        )
        parser.add_argument(
            "--warmup-decode",
            action="store_true",
            help="pre-compile fused-decode programs for every batch "
            "bucket at boot (no mid-serve recompile stalls)",
        )
        parser.add_argument(
            "--warmup-prefill",
            action="store_true",
            help="pre-compile the prefill program per token bucket at "
            "boot (first-request TTFT becomes execution time)",
        )
        parser.add_argument(
            "--no-enable-chunked-prefill",
            dest="enable_chunked_prefill",
            action="store_false",
        )
        parser.add_argument(
            "--max-waiting-requests",
            type=int,
            default=None,
            help="admission cap on waiting requests; excess rejected "
            "with HTTP 429 (default: $VDT_MAX_WAITING_REQUESTS or "
            "0 = unbounded)",
        )
        parser.add_argument(
            "--max-queued-tokens",
            type=int,
            default=None,
            help="admission cap on queued prompt tokens (default: "
            "$VDT_MAX_QUEUED_TOKENS or 0 = unbounded)",
        )
        parser.add_argument(
            "--kv-admission-watermark",
            type=float,
            default=None,
            help="reject admission when the prompt's estimated KV page "
            "demand would leave less than this fraction of pages free "
            "(default: $VDT_KV_ADMISSION_WATERMARK or 0 = off)",
        )
        parser.add_argument(
            "--default-deadline-ms",
            type=int,
            default=None,
            help="server-default per-request deadline in ms; expired "
            "requests are shed (waiting) or finish with "
            'finish_reason="timeout" (running) (default: '
            "$VDT_DEFAULT_DEADLINE_MS or 0 = none)",
        )
        parser.add_argument(
            "--preempt-shed-threshold",
            type=int,
            default=None,
            help="preemptions per request before it is shed with "
            'finish_reason="overloaded" instead of recompute thrash '
            "(default: $VDT_PREEMPT_SHED_THRESHOLD or 0 = off)",
        )
        parser.add_argument(
            "--qos-classes",
            type=str,
            default=None,
            help="SLO class registry, one entry per class "
            '"name:priority[:share[:weight]]" comma-separated: priority '
            "orders admission/preemption, share is the class's "
            "guaranteed-minimum fraction of the admission caps, weight "
            "scales the preempt-to-shed budget (default: "
            "$VDT_QOS_CLASSES or empty = QoS off, seed scheduling)",
        )
        parser.add_argument(
            "--qos-prefill-share",
            type=float,
            default=None,
            help="chunked-prefill fairness budget: max fraction of the "
            "per-step token budget prefill may take while a "
            "decode-bound request of higher-or-equal class runs "
            "(default: $VDT_QOS_PREFILL_SHARE or 0 = off)",
        )
        parser.add_argument(
            "--speculative-ngram-k",
            type=int,
            default=None,
            help="speculative decoding: max tokens the n-gram "
            "prompt-lookup proposer drafts per request per step, "
            "verified in one fused pass; greedy outputs stay "
            "bit-identical (default: $VDT_SPEC_NGRAM_K or 0 = off)",
        )
        parser.add_argument(
            "--speculative-ngram-max",
            type=int,
            default=None,
            help="longest tail n-gram the proposer matches (default: "
            "$VDT_SPEC_NGRAM_MAX or 3)",
        )
        parser.add_argument(
            "--speculative-ngram-min",
            type=int,
            default=None,
            help="shortest tail n-gram the proposer matches (default: "
            "$VDT_SPEC_NGRAM_MIN or 1)",
        )
        parser.add_argument("--device", type=str, default="auto")
        parser.add_argument("--profile-dir", type=str, default=None)
        parser.add_argument("--disable-log-stats", action="store_true")
        parser.add_argument(
            "--enable-tracing",
            action="store_true",
            default=None,
            help="per-request tracing: /debug/traces (JSON + Perfetto), "
            "per-stage latency histograms, cross-host RPC spans "
            "(default: $VDT_TRACING or off)",
        )
        parser.add_argument(
            "--kv-transfer-config",
            type=str,
            default=None,
            help="JSON KV-connector config (disaggregated prefill hook): "
            "all workers reply per step and KV-transfer progress is "
            "merged by KVOutputAggregator",
        )
        return parser

    @classmethod
    def from_cli_args(cls, args: argparse.Namespace) -> "EngineArgs":
        attrs = [f.name for f in dataclasses.fields(cls)]
        return cls(
            **{a: getattr(args, a) for a in attrs if hasattr(args, a)}
        )

    def create_engine_config(self) -> EngineConfig:
        model_config = ModelConfig(
            model=self.model,
            tokenizer=self.tokenizer,
            dtype=self.dtype,
            seed=self.seed,
            max_model_len=self.max_model_len,
            trust_remote_code=self.trust_remote_code,
            quantization=self.quantization,
            skip_tokenizer_init=self.skip_tokenizer_init,
            load_format=self.load_format,
            enable_expert_parallel=self.enable_expert_parallel,
        )
        max_batched = self.max_num_batched_tokens
        if max_batched is None:
            max_batched = max(2048, self.max_num_seqs)
        hbm_utilization = self.hbm_utilization
        if hbm_utilization is None:
            hbm_utilization = envs.VDT_HBM_UTILIZATION
        kv_spill_host_pages = self.kv_spill_host_pages
        if kv_spill_host_pages is None:
            kv_spill_host_pages = envs.VDT_KV_SPILL_HOST_PAGES
        kv_spill_restore_min = self.kv_spill_restore_min_tokens
        if kv_spill_restore_min is None:
            kv_spill_restore_min = envs.VDT_KV_SPILL_RESTORE_MIN_TOKENS
        cache_config = CacheConfig(
            page_size=self.page_size,
            num_pages=self.num_kv_pages,
            hbm_utilization=hbm_utilization,
            cache_dtype=self.kv_cache_dtype,
            enable_prefix_caching=self.enable_prefix_caching,
            prefix_cache_index=self.prefix_cache_index,
            kv_spill_host_pages=kv_spill_host_pages,
            kv_spill_restore_min_tokens=kv_spill_restore_min,
        )
        parallel_config = ParallelConfig(
            tensor_parallel_size=self.tensor_parallel_size,
            pipeline_parallel_size=self.pipeline_parallel_size,
            data_parallel_size=self.data_parallel_size,
            enable_expert_parallel=self.enable_expert_parallel,
            distributed_executor_backend=self.distributed_executor_backend,
            num_hosts=self.num_hosts,
            host_id=self.host_id,
            coordinator_address=self.coordinator_address,
        )
        def _env_default(value, env_name):
            return getattr(envs, env_name) if value is None else value

        scheduler_config = SchedulerConfig(
            max_num_seqs=self.max_num_seqs,
            max_num_batched_tokens=max_batched,
            enable_chunked_prefill=self.enable_chunked_prefill,
            max_model_len=model_config.max_model_len,
            num_decode_steps=self.num_decode_steps,
            max_concurrent_dispatches=self.max_concurrent_dispatches,
            warmup_decode=self.warmup_decode,
            warmup_prefill=self.warmup_prefill,
            max_waiting_requests=_env_default(
                self.max_waiting_requests, "VDT_MAX_WAITING_REQUESTS"
            ),
            max_queued_tokens=_env_default(
                self.max_queued_tokens, "VDT_MAX_QUEUED_TOKENS"
            ),
            kv_admission_watermark=_env_default(
                self.kv_admission_watermark, "VDT_KV_ADMISSION_WATERMARK"
            ),
            default_deadline_ms=_env_default(
                self.default_deadline_ms, "VDT_DEFAULT_DEADLINE_MS"
            ),
            preempt_shed_threshold=_env_default(
                self.preempt_shed_threshold, "VDT_PREEMPT_SHED_THRESHOLD"
            ),
            spec_ngram_k=_env_default(
                self.speculative_ngram_k, "VDT_SPEC_NGRAM_K"
            ),
            spec_ngram_max=_env_default(
                self.speculative_ngram_max, "VDT_SPEC_NGRAM_MAX"
            ),
            spec_ngram_min=_env_default(
                self.speculative_ngram_min, "VDT_SPEC_NGRAM_MIN"
            ),
            qos_classes=_env_default(
                self.qos_classes, "VDT_QOS_CLASSES"
            ),
            qos_prefill_share=_env_default(
                self.qos_prefill_share, "VDT_QOS_PREFILL_SHARE"
            ),
        )
        kv_transfer = self.kv_transfer_config
        if isinstance(kv_transfer, str):
            kv_transfer = json.loads(kv_transfer)
        return EngineConfig(
            model_config=model_config,
            cache_config=cache_config,
            parallel_config=parallel_config,
            scheduler_config=scheduler_config,
            device_config=DeviceConfig(device=self.device),
            observability_config=ObservabilityConfig(
                collect_metrics=not self.disable_log_stats,
                profile_dir=self.profile_dir or envs.VDT_PROFILE_DIR or None,
                enable_tracing=(
                    envs.VDT_TRACING
                    if self.enable_tracing is None
                    else self.enable_tracing
                ),
                trace_ring_size=envs.VDT_TRACE_RING_SIZE,
                flight_recorder_size=envs.VDT_FLIGHT_RECORDER_SIZE,
            ),
            kv_transfer_config=kv_transfer,
        )
