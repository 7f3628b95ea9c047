"""Accelerator-free mock worker for control-plane tests (SURVEY.md §4
item 4: exercise executor topology, lifecycle ordering, reply-rank
selection, env replication, and failure propagation without chips).

Fault hooks: ``inject_fault`` arms deterministic failures on NON-driver
workers only (the worker lives in the agent process, so transport-level
modes reach the agent's process-global FaultInjector installed when the
agent runs with VDT_FAULT_INJECTION=1):

- worker faults:    ``hang_execute``, ``die_in_execute`` — fire on the
                    next execute_model/dispatch_model;

Token modes: by default every step samples the constant 42 (topology
tests only care that A token arrived).  With ``VDT_MOCK_TOKEN_SEQ=1``
the worker instead samples a deterministic function of the request's
absolute position — token i equals the total token count before it — so
recovery/replay tests can assert bit-identical continuations: a request
replayed as prompt+emitted-prefix continues with exactly the tokens an
uninterrupted run would have produced, and any replay bug (dropped,
duplicated, or restarted-from-scratch tokens) changes the sequence.
``VDT_MOCK_TOKEN_SEQ=seq:t0,t1,...`` generalizes this to token i =
L[i mod len(L)] so speculative-decoding tests (ISSUE 11) can force
full-accept (a periodic history the n-gram proposer predicts exactly),
full-reject (a prompt whose recurring n-gram continues differently
than the emitted stream), and mixed-acceptance batches — the mock
verifies drafts against the same function, accepting the longest
matching prefix plus one bonus token, exactly like the real greedy
accept kernel.

``VDT_MOCK_HBM_PASS_SECONDS`` (default 0) simulates memory-bound
device time per step as cost × HBM passes: a fused decode window of K
micro-steps streams weights+KV K times, a speculative verify window
streams them ONCE — the pass-count asymmetry that
tests/test_spec_decode.py counts deterministically without chips.
- transport faults: ``drop_writes`` / ``blackhole_writes`` /
                    ``corrupt_writes`` / ``delay_writes`` / ``hang_writes``
                    — armed with a small ``after_writes`` budget so the
                    arming RPC's own reply frame (and at most one
                    concurrent pong) escapes before the fault engages.
"""

from __future__ import annotations

import concurrent.futures
import os
import queue
import time

from vllm_distributed_tpu.executor.abstract import Executor
from vllm_distributed_tpu.outputs import ModelRunnerOutput
from vllm_distributed_tpu.utils import next_power_of_2, run_method
from vllm_distributed_tpu.worker.telemetry import DeviceTelemetry

# Simulated device time per fused dispatch in the two-phase protocol
# (per-process override: VDT_MOCK_STEP_SECONDS).
MOCK_STEP_SECONDS = 0.3

_TRANSPORT_FAULTS = {
    "drop_writes": "drop",
    "blackhole_writes": "blackhole",
    "corrupt_writes": "corrupt",
    "delay_writes": "delay",
    "hang_writes": "hang",
}


class MockWorker:
    def __init__(
        self,
        config,
        rank: int = 0,
        local_rank: int = 0,
        distributed_init_method: str | None = None,
        is_driver_worker: bool = True,
    ) -> None:
        self.config = config
        self.rank = rank
        self.distributed_init_method = distributed_init_method
        self.is_driver_worker = is_driver_worker
        self.calls: list[str] = []
        self._deferred: queue.Queue = queue.Queue()
        self._fault: str | None = None
        # (event, step_id, monotonic time) — lets tests assert that
        # dispatch N+1 reached this worker before fetch N completed.
        self.timeline: list[tuple[str, int, float]] = []
        # Deterministic position-based sampling (see module docstring):
        # "1" -> token i = i; "seq:a,b,c" -> token i = L[i % len(L)].
        mode = os.environ.get("VDT_MOCK_TOKEN_SEQ", "")
        self._seq_mode = mode == "1" or mode.startswith("seq:")
        self._seq_list: list[int] | None = (
            [int(x) for x in mode[4:].split(",")]
            if mode.startswith("seq:")
            else None
        )
        # req_id -> {"total": tokens known, "computed": KV computed}.
        self._seq_state: dict[str, dict[str, int]] = {}
        # Simulated memory-bound device time: cost per weights+KV HBM
        # pass (fused decode pays one per micro-step, a spec verify
        # window pays ONE for the whole window).
        self._hbm_pass_seconds = float(
            os.environ.get("VDT_MOCK_HBM_PASS_SECONDS", "0")
        )
        # Simulated device time per blocking execute_model (recovery
        # tests need a stream slow enough to kill mid-generation).
        self._execute_sleep = float(
            os.environ.get("VDT_MOCK_EXECUTE_SLEEP_SECONDS", "0")
        )
        self._step_seconds = float(
            os.environ.get(
                "VDT_MOCK_STEP_SECONDS", str(MOCK_STEP_SECONDS)
            )
        )
        # Simulated XLA-compile accounting (ISSUE 12): the real runner
        # compiles one program per (kind, shape-bucket) key; the mock
        # mirrors that with the scheduler-visible power-of-2 token
        # bucket so tests can induce and observe a "recompile" without
        # chips.  Same DeviceTelemetry ledger + snapshot wire format.
        self.telemetry = DeviceTelemetry()
        self._compiled_buckets: set[str] = set()
        # Simulated per-scheduled-token device time (seconds): makes
        # prefill cost proportional to chunk length, so prefix-cache /
        # restore tests can order a warm request before a cold one
        # without chips.
        self._token_seconds = float(
            os.environ.get("VDT_MOCK_TOKEN_SECONDS", "0")
        )
        # Tiered-KV simulation (ISSUE 14): the mock "writes" actual
        # token ids into a per-page store as steps advance, mirrors
        # spill/restore spans between the page store and a host dict,
        # and VERIFIES on every prefix-cache admission (new request
        # with num_computed_tokens > 0) that the attached pages hold
        # exactly the prompt's tokens — so any protocol bug (stale page
        # handed out as a hit, restore landing after use, spill
        # capturing after overwrite, slot reuse races) fails loudly
        # instead of silently passing the trivially-deterministic
        # output checks.
        self._kv_page_size = config.cache_config.page_size
        self._kv_pages: dict[int, list] = {}
        self._kv_host: dict[int, list] = {}
        self._kv_req: dict[str, dict] = {}

    # ---- fault injection ----
    def inject_fault(
        self, name: str, value: float = 1.0, after_writes: int = 1
    ) -> str:
        """Arm one fault on the remote worker; no-op on the driver (the
        fault under test is always a REMOTE host misbehaving)."""
        if self.is_driver_worker:
            return "driver-noop"
        if name in _TRANSPORT_FAULTS:
            from vllm_distributed_tpu.distributed.rpc_transport import (
                get_global_injector,
            )

            injector = get_global_injector()
            assert injector is not None, (
                "transport faults need the agent started with "
                "VDT_FAULT_INJECTION=1"
            )
            injector.arm(
                _TRANSPORT_FAULTS[name], value, after_writes=after_writes
            )
            return "armed"
        assert name in ("hang_execute", "die_in_execute"), name
        self._fault = name
        return "armed"

    def _maybe_fault(self) -> None:
        fault, self._fault = self._fault, None
        if fault == "hang_execute":
            time.sleep(3600)  # wedged device program; agent proc is
            # terminated by the test, the thread never outlives it
        elif fault == "die_in_execute":
            os._exit(17)  # crash mid-RPC: no goodbye, just EOF

    def init_device(self) -> None:
        self.calls.append("init_device")

    def load_model(self, load_format=None) -> None:
        self.calls.append("load_model")

    def determine_num_pages(self) -> int:
        # An explicit pool size wins (tiering/ablation tests constrain
        # it); otherwise different per rank so min() aggregation is
        # observable.
        if self.config.cache_config.num_pages is not None:
            return self.config.cache_config.num_pages
        return 100 + self.rank

    def initialize_cache(self, num_pages: int) -> None:
        self.num_pages = num_pages

    def _tok(self, pos: int) -> int:
        """Deterministic token at absolute position ``pos``."""
        if self._seq_list is not None:
            return self._seq_list[pos % len(self._seq_list)]
        return pos

    def _hbm_passes(self, scheduler_output) -> int:
        """Weights+KV HBM passes one dispatch costs: a fused decode
        window pays one per micro-step, a spec verify window ONE for
        the whole window (the memory-bound asymmetry spec decode
        exploits)."""
        if getattr(scheduler_output, "draft_token_ids", None):
            return 1
        return max(getattr(scheduler_output, "decode_steps", 1) or 1, 1)

    def _simulate_device(self, scheduler_output) -> None:
        self._simulate_compile(scheduler_output)
        if self._hbm_pass_seconds:
            time.sleep(
                self._hbm_pass_seconds * self._hbm_passes(scheduler_output)
            )
        if self._token_seconds:
            time.sleep(
                self._token_seconds
                * scheduler_output.total_num_scheduled_tokens
            )

    # ---- tiered-KV simulation (ISSUE 14) ----
    def _apply_kv_ops(self, so) -> None:
        """Mirror the real runner's span application order: all spills
        (page store -> host dict), then all restores (host dict -> page
        store, slot consumed).  A restore from a missing slot is a
        protocol violation and raises."""
        ps = self._kv_page_size
        for page, slot in getattr(so, "kv_spill_ops", None) or []:
            self._kv_host[slot] = list(
                self._kv_pages.get(page, [None] * ps)
            )
        for slot, page in getattr(so, "kv_restore_ops", None) or []:
            self._kv_pages[page] = self._kv_host.pop(slot)

    def _kv_track(self, so, sampled: dict[str, list[int]]) -> None:
        """Write this step's token ids into the simulated page store
        and VERIFY prefix-cache admissions against it (see __init__).
        getattr-defensive: topology tests drive the mock with minimal
        hand-built payloads that may omit scheduler-only fields."""
        ps = self._kv_page_size
        finished = getattr(so, "finished_req_ids", None) or []
        preempted = getattr(so, "preempted_req_ids", None) or []
        for rid in finished + preempted:
            self._kv_req.pop(rid, None)
        for nr in getattr(so, "new_requests", None) or []:
            st = {
                "tokens": list(nr.prompt_token_ids),
                "pages": list(nr.page_ids),
                "computed": nr.num_computed_tokens,
            }
            self._kv_req[nr.req_id] = st
            for pos in range(nr.num_computed_tokens):
                page = st["pages"][pos // ps]
                row = self._kv_pages.get(page)
                got = row[pos % ps] if row is not None else None
                want = st["tokens"][pos]
                if got != want:
                    raise RuntimeError(
                        f"prefix-cache KV mismatch for {nr.req_id}: "
                        f"pos {pos} (page {page}) holds {got!r}, "
                        f"prompt says {want!r} — the allocator served "
                        "a stale or mis-restored page as a hit"
                    )
        for c in getattr(so, "cached_requests", None) or []:
            st = self._kv_req.get(c.req_id)
            if st is not None:
                st["pages"].extend(c.new_page_ids)
        drafts = getattr(so, "draft_token_ids", None) or {}
        for rid, n in (
            getattr(so, "num_scheduled_tokens", None) or {}
        ).items():
            st = self._kv_req.get(rid)
            if st is None:
                continue  # hand-built test payloads / unknown requests
            emitted = sampled.get(rid, [])
            st["tokens"].extend(emitted)
            # Spec verify windows advance by the EMITTED count (the
            # rejected-draft rows are overwritten in place and never
            # reach the prefix index); everything else by the scheduled
            # width, clamped to known tokens like registrable_tokens.
            adv = len(emitted) if rid in drafts else n
            end = min(st["computed"] + adv, len(st["tokens"]))
            for pos in range(st["computed"], end):
                page_i = pos // ps
                if page_i >= len(st["pages"]):
                    break
                page = st["pages"][page_i]
                row = self._kv_pages.get(page)
                if row is None or len(row) != ps:
                    row = [None] * ps
                    self._kv_pages[page] = row
                row[pos % ps] = st["tokens"][pos]
            st["computed"] += adv

    # ---- KV-page export/import (disaggregated prefill, ISSUE 15) ----
    # The mock's "KV" is the simulated page-content store (token ids per
    # page row), so a handed-off page carries exactly the content the
    # decode-side admission verification checks against the prompt — a
    # transfer that reorders, corrupts, or half-applies pages fails
    # loudly.  Two synthetic "layers" (identical rows) exercise the
    # per-layer chunking + completeness contract without chips.
    MOCK_KV_LAYERS = 2

    def export_kv_pages(
        self, page_ids: list[int], layer_start: int, layer_count: int
    ) -> dict | None:
        if not self.is_driver_worker:
            return None
        import hashlib
        import json

        ps = self._kv_page_size
        rows = [
            list(self._kv_pages.get(p, [None] * ps)) for p in page_ids
        ]
        data = json.dumps(rows).encode()
        checksum = hashlib.sha256(data).hexdigest()
        start = max(int(layer_start), 0)
        end = min(start + max(int(layer_count), 0), self.MOCK_KV_LAYERS)
        return {
            "num_layers": self.MOCK_KV_LAYERS,
            "layers": [
                {
                    "index": i,
                    "num_layers": self.MOCK_KV_LAYERS,
                    "data": data,
                    "checksum": checksum,
                }
                for i in range(start, end)
            ],
        }

    def import_kv_pages(
        self, page_ids: list[int], layers: list[dict]
    ) -> dict | None:
        if not self.is_driver_worker:
            return None
        import hashlib
        import json

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
            rows = json.loads(data)
            for page, row in zip(page_ids, rows):
                self._kv_pages[page] = list(row)
        return {"ok": True}

    def get_kv_tier_info(self) -> dict | None:
        if not self.is_driver_worker:
            return None
        page_bytes = 4096  # deterministic stand-in for the gauge scale
        return {
            "page_bytes": page_bytes,
            "host_slots": len(self._kv_host),
            "host_bytes": len(self._kv_host) * page_bytes,
        }

    def _simulate_compile(self, scheduler_output) -> None:
        """Record one simulated XLA compile per new (kind, token-bucket)
        shape key — the mock analog of ModelRunner._observed_call."""
        if getattr(scheduler_output, "draft_token_ids", None):
            kind = "spec"
        elif (getattr(scheduler_output, "decode_steps", 1) or 1) > 1:
            kind = "decode"
        else:
            kind = "prefill"
        bucket = next_power_of_2(
            max(scheduler_output.total_num_scheduled_tokens, 16)
        )
        key = f"{kind}:t={bucket}"
        if key not in self._compiled_buckets:
            self._compiled_buckets.add(key)
            self.telemetry.record_compile(kind, 0.001, key)
        self.telemetry.record_step(
            max(self._step_seconds, 1e-6),
            scheduler_output.total_num_scheduled_tokens * 1024,
            819e9,
        )

    def get_device_telemetry(self) -> dict | None:
        if not self.is_driver_worker:
            return None
        snap = self.telemetry.snapshot(probe_memory=False)
        # Deterministic stand-in HBM numbers so the gauges move in tests.
        snap["hbm_live_bytes"] = 1 << 30
        snap["hbm_limit_bytes"] = 16 << 30
        return snap

    def _sample(self, scheduler_output) -> dict[str, list[int]]:
        """One sampled token per scheduled request: constant 42, or the
        deterministic position stream under VDT_MOCK_TOKEN_SEQ.  Spec
        verify windows (draft_token_ids) emit the longest draft prefix
        matching the stream plus one bonus token — the mock analog of
        ops/sampling.spec_greedy_accept, so greedy bit-identity holds
        by the same argument as on the real runner."""
        drafts = getattr(scheduler_output, "draft_token_ids", None) or {}
        if not self._seq_mode:
            out: dict[str, list[int]] = {}
            for req_id in scheduler_output.num_scheduled_tokens:
                d = drafts.get(req_id)
                if d:
                    a = 0
                    while a < len(d) and d[a] == 42:
                        a += 1
                    out[req_id] = [42] * (a + 1)
                else:
                    out[req_id] = [42]
            return out
        # Drop finished/preempted state BEFORE seeding new requests —
        # the real worker's _apply_scheduler_deltas order — so a step
        # that both finishes request id X and re-admits a new X keeps
        # the new state.
        for req_id in (
            scheduler_output.finished_req_ids
            + scheduler_output.preempted_req_ids
        ):
            self._seq_state.pop(req_id, None)
        for nr in scheduler_output.new_requests:
            self._seq_state[nr.req_id] = {
                "total": len(nr.prompt_token_ids),
                "computed": nr.num_computed_tokens,
            }
        sampled: dict[str, list[int]] = {}
        for req_id, n in scheduler_output.num_scheduled_tokens.items():
            st = self._seq_state.get(req_id)
            if st is None:
                continue
            d = drafts.get(req_id)
            if d is not None:
                # Spec verify window: accept the longest draft prefix
                # matching the deterministic stream, emit it plus one
                # bonus token, and advance by the EMITTED count (the
                # scheduler reconciles the same way).
                pos0 = st["total"]
                a = 0
                while a < len(d) and d[a] == self._tok(pos0 + a):
                    a += 1
                emitted = [self._tok(pos0 + j) for j in range(a + 1)]
                st["total"] += len(emitted)
                st["computed"] = st["total"] - 1
                sampled[req_id] = emitted
                continue
            st["computed"] += n
            if st["computed"] >= st["total"]:
                # Prompt fully prefetched: sample.  The token IS a
                # function of the absolute position, so a replayed
                # request (longer prompt, same total) continues the
                # identical sequence.  A fused decode window (num_new
                # > 1, engine num_decode_steps > 1) emits one position
                # token per micro-step, exactly like the real worker's
                # scan.
                k = st["computed"] - st["total"] + 1
                sampled[req_id] = [
                    self._tok(p)
                    for p in range(st["total"], st["total"] + k)
                ]
                st["total"] += k
        return sampled

    def execute_model(self, scheduler_output) -> ModelRunnerOutput | None:
        self._maybe_fault()
        if self._execute_sleep:
            time.sleep(self._execute_sleep)
        t0 = time.perf_counter()
        self._apply_kv_ops(scheduler_output)
        tier_s = time.perf_counter() - t0
        self._simulate_device(scheduler_output)
        sampled = self._sample(scheduler_output)
        self._kv_track(scheduler_output, sampled)
        if not self.is_driver_worker:
            return None
        out = ModelRunnerOutput()
        out.sampled_token_ids = sampled
        out.kv_tier_seconds = tier_s
        return out

    # ---- two-phase step (cross-RPC pipelining) ----
    def dispatch_model(self, scheduler_output) -> int:
        self._maybe_fault()
        self.timeline.append(
            ("dispatch", scheduler_output.step_id, time.monotonic())
        )
        self._deferred.put(scheduler_output)
        return scheduler_output.step_id

    def fetch_results(self, step_id: int) -> ModelRunnerOutput | None:
        so = self._deferred.get(timeout=10)
        assert so.step_id == step_id, (so.step_id, step_id)
        time.sleep(self._step_seconds)  # pretend the device is busy
        self._apply_kv_ops(so)  # FIFO order == frame order
        self._simulate_device(so)
        self.timeline.append(("fetch_done", step_id, time.monotonic()))
        sampled = self._sample(so)
        self._kv_track(so, sampled)
        if not self.is_driver_worker:
            return None
        out = ModelRunnerOutput()
        out.sampled_token_ids = sampled
        return out

    def get_timeline(self) -> list[tuple[str, int, float]]:
        return list(self.timeline)

    def check_health(self) -> bool:
        return True

    def shutdown(self) -> None:
        pass

    def get_rank_and_env(self, var: str) -> tuple[int, str | None]:
        return self.rank, os.environ.get(var)

    def get_lifecycle(self) -> list[str]:
        return list(self.calls)


class MockUniProcExecutor(Executor):
    """In-process single-worker executor over MockWorker: the lightest
    way to boot a whole AsyncLLM + api_server 'replica' without chips or
    agent processes (router tests and chaos_soak --replicas spin up N
    of these behind the router).  Honors VDT_MOCK_TOKEN_SEQ /
    VDT_MOCK_EXECUTE_SLEEP_SECONDS like the multihost mock deployments.
    """

    def _init_executor(self) -> None:
        self.worker = MockWorker(
            self.config, rank=0, is_driver_worker=True
        )
        self.collective_rpc("init_device")
        self.collective_rpc("load_model")

    def collective_rpc(
        self,
        method: str,
        args: tuple = (),
        kwargs: dict | None = None,
        *,
        unique_reply_rank: int | None = None,
        non_block: bool = False,
        timeout: float | None = None,
    ):
        result = run_method(self.worker, method, args, kwargs or {})
        if non_block:
            fut: concurrent.futures.Future = concurrent.futures.Future()
            fut.set_result(result)
            return fut
        return result if unique_reply_rank is not None else [result]
