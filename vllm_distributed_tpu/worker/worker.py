"""Worker: owns the chips of one host and runs the model step.

The TPU analog of the vLLM worker the reference drives through
WorkerWrapperBase with string-dispatched lifecycle methods —
init_worker/init_device/load_model/execute_model/check_health
(launch.py:290-292, 329-343, 387; SURVEY.md §2.3).  One process per TPU
host owning all local chips (SURVEY.md §7 design stance), vs. the
reference's process-per-GPU.

All methods here are reachable by string name via ``run_method`` — that
is the executor's collective_rpc contract.
"""

from __future__ import annotations

import os
import queue
from typing import Any

import jax

from vllm_distributed_tpu.config import EngineConfig
from vllm_distributed_tpu.engine.scheduler import SchedulerOutput
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.outputs import ModelRunnerOutput
from vllm_distributed_tpu.worker.model_runner import ModelRunner
from vllm_distributed_tpu.worker.telemetry import DeviceTelemetry

logger = init_logger(__name__)


class Worker:
    def __init__(
        self,
        config: EngineConfig,
        rank: int = 0,
        local_rank: int = 0,
        distributed_init_method: str | None = None,
        is_driver_worker: bool = True,
    ) -> None:
        self.config = config
        self.rank = rank
        self.local_rank = local_rank
        self.distributed_init_method = distributed_init_method
        self.is_driver_worker = is_driver_worker
        self.mesh = None
        self.runner: ModelRunner | None = None
        # Created here, not by the runner: the compile-cache set-up in
        # init_device reports into it before a runner exists.
        self.telemetry = DeviceTelemetry()
        # Dispatched-but-unresolved steps (cross-RPC pipelining): filled
        # by dispatch_model on the dispatch thread, drained FIFO by
        # fetch_results on the fetch thread.
        self._deferred: queue.Queue[tuple[int, Any]] = queue.Queue()

    # ---- lifecycle RPCs ----
    def init_device(self) -> None:
        """Join the distributed world (multi-host: jax.distributed over DCN,
        the analog of the torch/NCCL rendezvous at launch.py:94) and build
        the device mesh."""
        pc = self.config.parallel_config
        if pc.num_hosts > 1 and self.distributed_init_method:
            jax.distributed.initialize(
                coordinator_address=self.distributed_init_method,
                num_processes=pc.num_hosts,
                process_id=self.rank,
            )
        self._enable_compilation_cache()
        if pc.world_size > 1:
            from vllm_distributed_tpu.distributed.mesh import build_mesh

            self.mesh = build_mesh(pc)
        logger.info(
            "worker rank=%d devices=%d backend=%s",
            self.rank,
            jax.local_device_count(),
            jax.default_backend(),
        )

    def _enable_compilation_cache(self) -> None:
        """Persistent XLA compilation cache (the analog of the reference's
        per-container /root/.cache compiled-model volume,
        docker-compose.yml:24-25).  Makes restart-to-first-token fast —
        SURVEY.md §5.4 / hard part #4."""
        from vllm_distributed_tpu.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        try:
            os.makedirs(cache_dir, exist_ok=True)
        except OSError as e:  # read-only fs: serve, but every start is cold
            self.telemetry.record_fallback("compile_cache_setup")
            logger.warning("compilation cache at %s unusable: %s", cache_dir, e)
        else:
            logger.info("compilation cache: %s", cache_dir)

    def load_model(self, load_format: str | None = None) -> None:
        from vllm_distributed_tpu import envs

        self.runner = ModelRunner(
            self.config,
            mesh=self.mesh,
            attn_backend=envs.VDT_USE_PALLAS,
            telemetry=self.telemetry,
        )
        self.runner.load_model(
            load_format=load_format or self.config.model_config.load_format
        )

    def determine_num_pages(self) -> int:
        return self.runner.profile_num_pages()

    def initialize_cache(self, num_pages: int) -> None:
        self.runner.init_kv_cache(num_pages)

    def warmup_decode(self) -> int:
        return self.runner.warmup_decode()

    def warmup_prefill(self) -> int:
        return self.runner.warmup_prefill()

    def execute_model(
        self, scheduler_output: SchedulerOutput, defer: bool = False
    ) -> ModelRunnerOutput | None:
        """Run one step.  The runner may return a deferred resolver (fused
        decode: the dispatch is in flight, results fetched on resolve);
        over RPC the resolver cannot cross the wire, so it is resolved
        here unless the in-process caller asks to defer."""
        out = self.runner.execute_model(scheduler_output)
        if callable(out) and not defer:
            out = out()
        return out if self._replies() else None

    def _replies(self) -> bool:
        """Non-driver ranks reply too when a KV connector is configured
        (the aggregator needs every worker's KV-transfer progress;
        reference launch.py:338-349)."""
        return (
            self.is_driver_worker
            or self.config.kv_transfer_config is not None
        )

    # ---- two-phase step (cross-RPC pipelining, VERDICT r2 weak #4) ----
    def dispatch_model(self, scheduler_output: SchedulerOutput) -> int:
        """Issue the step to the device and return immediately; results
        come from a later fetch_results.  Lets the driver put dispatch
        N+1 on the wire while N is still computing — the remote analog
        of the engine's in-flight pipelining (launch.py:298-302)."""
        out = self.runner.execute_model(scheduler_output)
        self._deferred.put((scheduler_output.step_id, out))
        return scheduler_output.step_id

    def fetch_results(
        self, step_id: int, timeout: float = 300.0
    ) -> ModelRunnerOutput | None:
        """Resolve the oldest dispatched step (FIFO).  Blocks until its
        dispatch has been issued and the device results are ready; must
        run on a different thread than dispatch_model (the agent and the
        executor route the two verbs to separate ordered pools)."""
        sid, out = self._deferred.get(timeout=timeout)
        if sid != step_id:
            raise RuntimeError(
                f"fetch_results out of order: expected step {sid}, "
                f"got {step_id}"
            )
        if callable(out):
            out = out()
        return out if self._replies() else None

    def embed(self, token_ids: list[int]) -> list[float] | None:
        out = self.runner.embed(token_ids)
        return out if self.is_driver_worker else None

    def score(self, token_ids: list[int]) -> list[float | None] | None:
        out = self.runner.score(token_ids)
        return out if self.is_driver_worker else None

    def check_health(self) -> bool:
        return True

    def get_kv_tier_info(self) -> dict | None:
        """Tiered-KV telemetry RPC (ISSUE 14): per-page pool bytes (the
        driver's host_kv_bytes gauge scale) and this worker's live
        host-tier occupancy (leak assertions in the chaos harness)."""
        if self.runner is None or not self.is_driver_worker:
            return None
        info = {"page_bytes": self.runner.kv_cache_bytes_per_page()}
        info.update(self.runner.host_kv_stats())
        return info

    def export_kv_pages(
        self, page_ids: list[int], layer_start: int, layer_count: int
    ) -> dict | None:
        """Disaggregated-prefill hand-off (ISSUE 15): gather one
        per-layer chunk of held pages' KV with content checksums.  Only
        the reply rank answers (single-host replica topology)."""
        if self.runner is None or not self.is_driver_worker:
            return None
        return self.runner.export_kv_pages(
            page_ids, layer_start, layer_count
        )

    def import_kv_pages(
        self, page_ids: list[int], layers: list[dict]
    ) -> dict | None:
        """Hand-off import: checksum-verify and scatter received layer
        chunks into reserved pages (ISSUE 15)."""
        if self.runner is None or not self.is_driver_worker:
            return None
        return self.runner.import_kv_pages(page_ids, layers)

    def get_device_telemetry(self) -> dict | None:
        """XLA compile / HBM / roofline snapshot (ISSUE 12): the driver
        pulls this on /metrics scrapes and folds it into the engine's
        Prometheus instruments.  Non-reply ranks skip the snapshot (and
        its device memory probe) entirely — their reply is discarded."""
        if self.runner is None or not self.is_driver_worker:
            return None
        return self.runner.telemetry.snapshot()

    def shutdown(self) -> None:
        """Leave the jax.distributed world cleanly (both sides must reach
        the coordination-service shutdown barrier, or the survivor is
        killed by a barrier timeout)."""
        if self.config.parallel_config.num_hosts > 1:
            try:
                jax.distributed.shutdown()
            except Exception as e:  # noqa: BLE001 — already torn down
                logger.debug("jax.distributed.shutdown: %s", e)

    def profile(self, action: str, profile_dir: str | None = None) -> None:
        if action == "start":
            jax.profiler.start_trace(
                profile_dir
                or self.config.observability_config.profile_dir
                or "/tmp/vdt_profile"
            )
        else:
            jax.profiler.stop_trace()
