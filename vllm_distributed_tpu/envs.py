"""Environment-variable registry.

The reference treats vLLM's ``envs.environment_variables`` as the single
registry of recognized env vars and uses it as the replication allowlist
when forwarding driver env vars to remote workers (launch.py:26, 62-72,
198-208).  We keep that design: every env var the framework understands is
declared here, and the control plane replicates everything in the registry
*except* per-host variables to remote hosts.
"""

from __future__ import annotations

import os
from collections.abc import Callable
from typing import Any

# name -> lambda returning the parsed value (lazy so tests can monkeypatch).
environment_variables: dict[str, Callable[[], Any]] = {
    # --- control plane ---
    "VDT_SERVER_PORT": lambda: int(os.environ.get("VDT_SERVER_PORT", "30044")),
    "VDT_HOST_IP": lambda: os.environ.get("VDT_HOST_IP", ""),
    # per-step execute timeout, like VLLM_EXECUTE_MODEL_TIMEOUT_SECONDS
    # (launch.py:334, 343)
    "VDT_EXECUTE_MODEL_TIMEOUT_SECONDS": lambda: int(
        os.environ.get("VDT_EXECUTE_MODEL_TIMEOUT_SECONDS", "300")
    ),
    # Heartbeat liveness: the driver pings every remote agent on this
    # interval (seconds, float; 0 disables); VDT_HEARTBEAT_MISS_THRESHOLD
    # consecutive misses mark the host dead without waiting for a request
    # to hit the execute timeout.  Replicated to agents, which run the
    # symmetric watchdog and fail-fast when the server goes silent.
    "VDT_HEARTBEAT_INTERVAL_SECONDS": lambda: float(
        os.environ.get("VDT_HEARTBEAT_INTERVAL_SECONDS", "10")
    ),
    "VDT_HEARTBEAT_MISS_THRESHOLD": lambda: int(
        os.environ.get("VDT_HEARTBEAT_MISS_THRESHOLD", "3")
    ),
    # Boot deadlines: how long the driver waits for all agents to dial in
    # (0 = forever), and for remote worker creation.
    "VDT_CONNECT_TIMEOUT_SECONDS": lambda: float(
        os.environ.get("VDT_CONNECT_TIMEOUT_SECONDS", "600")
    ),
    "VDT_INIT_TIMEOUT_SECONDS": lambda: float(
        os.environ.get("VDT_INIT_TIMEOUT_SECONDS", "120")
    ),
    # Retry-After hint (seconds) on 503s while the engine is dead and the
    # supervisor is reforming the deployment.
    "VDT_RETRY_AFTER_SECONDS": lambda: int(
        os.environ.get("VDT_RETRY_AFTER_SECONDS", "30")
    ),
    # In-process engine recovery (engine/supervisor.py): how many
    # executor rebuilds are attempted within the crash-loop window
    # before a control-plane death becomes terminal.  0 disables
    # recovery entirely (every HostFailure is fatal, the pre-supervisor
    # behavior).
    "VDT_MAX_ENGINE_RESTARTS": lambda: int(
        os.environ.get("VDT_MAX_ENGINE_RESTARTS", "3")
    ),
    # Exponential backoff between rebuild attempts: base * 2^attempt,
    # capped.  The /health Retry-After during RECOVERING derives from
    # the current delay.
    "VDT_ENGINE_RESTART_BACKOFF_SECONDS": lambda: float(
        os.environ.get("VDT_ENGINE_RESTART_BACKOFF_SECONDS", "1")
    ),
    "VDT_ENGINE_RESTART_BACKOFF_CAP_SECONDS": lambda: float(
        os.environ.get("VDT_ENGINE_RESTART_BACKOFF_CAP_SECONDS", "30")
    ),
    # Restarts older than this window are forgotten; more than
    # VDT_MAX_ENGINE_RESTARTS *within* it is a crash loop -> give up.
    "VDT_CRASH_LOOP_WINDOW_SECONDS": lambda: float(
        os.environ.get("VDT_CRASH_LOOP_WINDOW_SECONDS", "300")
    ),
    # Persistent per-host step streams (executor/multihost.py): per-step
    # control messages ride one one-way frame each way through a
    # long-lived run loop instead of dispatch/fetch request-reply pairs.
    # "0" falls back to the legacy two-phase RPC path.
    "VDT_STEP_STREAMS": lambda: os.environ.get(
        "VDT_STEP_STREAMS", "1"
    ).lower() not in ("", "0", "false", "off"),
    # Bound on each host's step-stream inbox (queued-but-undispatched
    # frames); the engine keeps at most max_concurrent_dispatches steps
    # in flight, so this only guards against a runaway driver.
    "VDT_STEP_STREAM_DEPTH": lambda: int(
        os.environ.get("VDT_STEP_STREAM_DEPTH", "8")
    ),
    # --- overload resilience (ISSUE 8) ---
    # Bounded admission: caps on the admission queue (waiting requests
    # not yet scheduled + adds still in the intake).  0 = unbounded —
    # the seed behavior.  Exceeding a cap rejects the request with a
    # typed EngineOverloadedError (HTTP 429 + Retry-After), never an
    # unbounded queue.
    "VDT_MAX_WAITING_REQUESTS": lambda: int(
        os.environ.get("VDT_MAX_WAITING_REQUESTS", "0")
    ),
    # Cap on queued PROMPT tokens awaiting prefill (same scope as
    # above); bounds admission memory independently of request count so
    # a few huge prompts can't evade the depth cap.  0 = unbounded.
    "VDT_MAX_QUEUED_TOKENS": lambda: int(
        os.environ.get("VDT_MAX_QUEUED_TOKENS", "0")
    ),
    # KV backpressure: reject admission when the prompt's estimated
    # page demand (prefix-cache-aware) would leave fewer than this
    # fraction of usable KV pages free.  0 = off.
    "VDT_KV_ADMISSION_WATERMARK": lambda: float(
        os.environ.get("VDT_KV_ADMISSION_WATERMARK", "0")
    ),
    # Server-default per-request deadline (milliseconds) when the
    # client sends none (X-VDT-Deadline-Ms header / deadline_ms body
    # field).  Expired waiting requests are shed before prefill;
    # expired running requests finish with finish_reason="timeout" and
    # partial output.  0 = no default deadline.
    "VDT_DEFAULT_DEADLINE_MS": lambda: int(
        os.environ.get("VDT_DEFAULT_DEADLINE_MS", "0")
    ),
    # Sustained-pressure preempt-to-shed: a request preempted more than
    # this many times while others still wait is finished with
    # finish_reason="overloaded" (HTTP 429 on the non-streaming path)
    # instead of thrashing the allocator with recompute cycles.
    # 0 = off (preempt/resume forever, the seed policy).
    "VDT_PREEMPT_SHED_THRESHOLD": lambda: int(
        os.environ.get("VDT_PREEMPT_SHED_THRESHOLD", "0")
    ),
    # Retry-After hint (seconds) on 429 overload rejections (distinct
    # from VDT_RETRY_AFTER_SECONDS, the dead/recovering 503 hint:
    # overload clears in ITL-scale time, a dead engine in restart-scale
    # time).
    "VDT_OVERLOAD_RETRY_AFTER_SECONDS": lambda: int(
        os.environ.get("VDT_OVERLOAD_RETRY_AFTER_SECONDS", "1")
    ),
    # Graceful drain: how long /drain (and the SIGTERM handler) lets
    # in-flight requests finish before journaling the rest.
    "VDT_DRAIN_TIMEOUT_SECONDS": lambda: float(
        os.environ.get("VDT_DRAIN_TIMEOUT_SECONDS", "30")
    ),
    # Where the drain journal is written (and loaded from at boot).
    # Empty = drain finishes by aborting unfinished requests instead of
    # journaling them.  Per-host: a replica's journal must never be
    # replicated onto its workers.
    "VDT_DRAIN_JOURNAL_PATH": lambda: os.environ.get(
        "VDT_DRAIN_JOURNAL_PATH", ""
    ),
    # --- tiered KV cache (ISSUE 14) ---
    # Host-DRAM spill tier size in KV pages: pages evicted from the HBM
    # pool spill to a bounded host pool (worker-side device_get) and
    # stream back ahead of a prefill resume instead of being
    # recomputed.  0 = off (the default; evictions discard KV exactly
    # like the seed prefix cache).  Only meaningful with
    # --enable-prefix-caching and the radix index.
    "VDT_KV_SPILL_HOST_PAGES": lambda: int(
        os.environ.get("VDT_KV_SPILL_HOST_PAGES", "0")
    ),
    # Restore-vs-recompute crossover: a host-resident run shorter than
    # this many tokens is recomputed instead of restored (below the
    # crossover a DMA round trip is taken to cost more than the prefill
    # it saves).  Not measured on the chip; set from a CPU sweep before
    # the round.  ROADMAP.md R-W4's cell will measure it.
    "VDT_KV_SPILL_RESTORE_MIN_TOKENS": lambda: int(
        os.environ.get("VDT_KV_SPILL_RESTORE_MIN_TOKENS", "32")
    ),
    # --- speculative decoding (ISSUE 11) ---
    # Max tokens the n-gram prompt-lookup proposer drafts per request
    # per step (--speculative-ngram-k); the model runner verifies all
    # drafts in one fused device pass and greedy accept/reject keeps
    # the matching prefix + one bonus token.  0 = off (the default);
    # greedy outputs are bit-identical either way.
    "VDT_SPEC_NGRAM_K": lambda: int(
        os.environ.get("VDT_SPEC_NGRAM_K", "0")
    ),
    # Tail n-gram match lengths the proposer tries, longest first.
    "VDT_SPEC_NGRAM_MAX": lambda: int(
        os.environ.get("VDT_SPEC_NGRAM_MAX", "3")
    ),
    "VDT_SPEC_NGRAM_MIN": lambda: int(
        os.environ.get("VDT_SPEC_NGRAM_MIN", "1")
    ),
    # --- multi-replica routing (ISSUE 10) ---
    # Stable identity of this serving replica, surfaced in /health, the
    # X-VDT-Replica-Id response header, and the vllm:replica_info gauge
    # so router logs/traces/bench can attribute per-replica behavior.
    # Empty = derived from the API server's host:port at boot.
    "VDT_REPLICA_ID": lambda: os.environ.get("VDT_REPLICA_ID", ""),
    # Router backend set: comma-separated replica base URLs
    # (e.g. "http://h1:8000,http://h2:8000").  The `vdt router`
    # --replica flag extends/overrides this.
    "VDT_ROUTER_REPLICAS": lambda: [
        u.strip().rstrip("/")
        for u in os.environ.get("VDT_ROUTER_REPLICAS", "").split(",")
        if u.strip()
    ],
    # Placement policy: "affinity" (prefix-cache affinity, falling back
    # to least-loaded), "least_loaded", or "round_robin" (the A/B
    # baseline bench-serve compares against).
    "VDT_ROUTER_POLICY": lambda: os.environ.get(
        "VDT_ROUTER_POLICY", "affinity"
    ),
    # Replica health-poll interval (seconds); each probe is
    # deadline-bounded by the connect/read timeouts below.
    "VDT_ROUTER_HEALTH_INTERVAL_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_HEALTH_INTERVAL_SECONDS", "2")
    ),
    # Affinity index granularity: tokens (or ~4-byte text chunks) per
    # hash-chain block — match the engine page_size so a router block
    # maps onto one cached KV page.
    "VDT_ROUTER_AFFINITY_BLOCK_TOKENS": lambda: int(
        os.environ.get("VDT_ROUTER_AFFINITY_BLOCK_TOKENS", "16")
    ),
    # Per-replica cap on remembered prefix blocks (LRU beyond it).
    "VDT_ROUTER_AFFINITY_CAPACITY": lambda: int(
        os.environ.get("VDT_ROUTER_AFFINITY_CAPACITY", "8192")
    ),
    # Minimum matched tokens before affinity outranks least-loaded
    # placement (below it the signal is noise, not a warm cache).
    "VDT_ROUTER_AFFINITY_MIN_TOKENS": lambda: int(
        os.environ.get("VDT_ROUTER_AFFINITY_MIN_TOKENS", "16")
    ),
    # How many times one request may be live-migrated (journal-replayed
    # onto another replica) before the router gives up on it.
    "VDT_ROUTER_MAX_MIGRATIONS": lambda: int(
        os.environ.get("VDT_ROUTER_MAX_MIGRATIONS", "3")
    ),
    # Upstream deadlines: TCP connect, and the per-read socket timeout
    # on proxied (SSE) responses.
    "VDT_ROUTER_CONNECT_TIMEOUT_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_CONNECT_TIMEOUT_SECONDS", "5")
    ),
    "VDT_ROUTER_READ_TIMEOUT_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_READ_TIMEOUT_SECONDS", "600")
    ),
    # --- crash-safe router (ISSUE 17) ---
    # Directory for the router's durable control-plane state (a bounded
    # write-ahead log of fleet membership, in-flight request journal
    # checkpoints, and QoS/placement config).  Empty (the default) =
    # no persistence: the router behaves exactly as before.  With a
    # state dir set, a restarted router re-adopts still-running managed
    # replicas instead of leaking or respawning them, and replays
    # journaled in-flight requests when their clients reconnect.
    "VDT_ROUTER_STATE_DIR": lambda: os.environ.get(
        "VDT_ROUTER_STATE_DIR", ""
    ),
    # WAL segment rotation threshold: when the live segment exceeds
    # this many bytes it is compacted (current membership + config +
    # live journals only) into a fresh segment via atomic rename, so
    # the on-disk state stays bounded regardless of uptime.
    "VDT_ROUTER_STATE_SEGMENT_BYTES": lambda: int(
        os.environ.get("VDT_ROUTER_STATE_SEGMENT_BYTES", "4194304")
    ),
    # Bounded fsync cadence: appended records are flushed to the OS on
    # every write but fsync'd at most this often (plus on rotation and
    # close) — a crash can lose at most this window of checkpoints,
    # never the membership records (those fsync immediately).
    "VDT_ROUTER_STATE_FSYNC_INTERVAL_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_STATE_FSYNC_INTERVAL_SECONDS", "0.2")
    ),
    # Per-request journal checkpoint cadence: a live stream's cumulative
    # journal (prompt ids + emitted tokens) is re-recorded at most this
    # often — NOT per token, which would make the WAL quadratic in
    # stream length.  Replays after a crash may therefore re-emit up to
    # this window's worth of tokens; the reconnecting client trims them
    # via X-VDT-Resume-Tokens.
    "VDT_ROUTER_STATE_CKPT_INTERVAL_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_STATE_CKPT_INTERVAL_SECONDS", "0.25")
    ),
    # Re-adoption grace window: a recovered replica enters the pool in
    # the `verifying` state and transport-level probe failures within
    # this window keep it there (with faster jittered re-probes)
    # instead of declaring it unreachable — a restart storm must not
    # mass-eject a healthy fleet that is briefly slow to answer.
    "VDT_ROUTER_STATE_VERIFY_WINDOW_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_STATE_VERIFY_WINDOW_SECONDS", "10")
    ),
    # How long recovered in-flight journals are held for client
    # reconnects after a router restart.  A client that reconnects with
    # X-VDT-Resume-Id inside the window finishes its generation
    # bit-identically; after it, the id gets a clean 503 (retry fresh).
    "VDT_ROUTER_STATE_RECOVERY_TTL_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_STATE_RECOVERY_TTL_SECONDS", "120")
    ),
    # --- disaggregated prefill/decode (ISSUE 15) ---
    # Role this serving replica announces in /health ("prefill" |
    # "decode" | "mixed").  The router places long prompts on the
    # prefill pool and hands their KV pages off to a decode-pool
    # replica at first token; "mixed" (the default) serves both phases
    # exactly as before — a fleet with no prefill-role replica never
    # takes the disagg path.
    "VDT_ROUTER_ROLE": lambda: os.environ.get(
        "VDT_ROUTER_ROLE", "mixed"
    ),
    # Prompt-length crossover (router-side): only prompts at/above this
    # many (estimated) tokens are prefilled on the prefill pool and
    # handed off; below it the transfer is taken to cost more than the
    # prefill it isolates, so the request is served on the decode/mixed
    # pool.  Not measured on the chip; set from a CPU sweep before the
    # round.  The disagg cell under ROADMAP.md D4 will measure it.
    "VDT_DISAGG_MIN_PROMPT_TOKENS": lambda: int(
        os.environ.get("VDT_DISAGG_MIN_PROMPT_TOKENS", "512")
    ),
    # KV-page streaming granularity: layers per /internal/kv chunk on
    # the prefill->decode hop (bounds per-frame memory on both sides of
    # the DCN transfer).
    "VDT_DISAGG_CHUNK_LAYERS": lambda: int(
        os.environ.get("VDT_DISAGG_CHUNK_LAYERS", "4")
    ),
    # How long a prefill-only request's KV pages stay held for export
    # after it finishes.  A router that dies mid-hand-off must never
    # leak pool pages forever: expired holds are swept at schedule
    # time and freed like a normal finish.
    "VDT_DISAGG_EXPORT_TTL_SECONDS": lambda: float(
        os.environ.get("VDT_DISAGG_EXPORT_TTL_SECONDS", "30")
    ),
    # Inbound /internal/kv frame-size bound (bytes): chunk frames whose
    # Content-Length exceeds it are rejected with a typed 413 BEFORE
    # buffering, so a misconfigured (or hostile) peer can't balloon the
    # import side's memory.  0 disables the check.
    "VDT_KV_MAX_FRAME_BYTES": lambda: int(
        os.environ.get("VDT_KV_MAX_FRAME_BYTES", "67108864")
    ),
    # --- resilient DCN data plane (ISSUE 19) ---
    # All default-off: with none of these set, every router->replica
    # call keeps its fixed ClientTimeout, retries are unbounded (the
    # pre-existing migration caps still apply), no hedges fire, and the
    # KV transfer protocol is byte-identical to ISSUE 15.
    # Consecutive transport failures/timeouts that trip a replica's
    # circuit breaker open (0 = consecutive-failure trip off).  Open
    # replicas are skipped by placement like unhealthy ones.
    "VDT_ROUTER_BREAKER_FAILURES": lambda: int(
        os.environ.get("VDT_ROUTER_BREAKER_FAILURES", "0")
    ),
    # Open -> half-open after this long; the half-open breaker admits
    # exactly one probe request (success closes, failure re-opens).
    "VDT_ROUTER_BREAKER_COOLDOWN_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_BREAKER_COOLDOWN_SECONDS", "5")
    ),
    # Windowed timeout-rate trip: the breaker also opens when at least
    # this fraction of the last window's outcomes were timeouts (needs
    # >= 10 samples in the window; 0 = rate trip off).
    "VDT_ROUTER_BREAKER_TIMEOUT_RATE": lambda: float(
        os.environ.get("VDT_ROUTER_BREAKER_TIMEOUT_RATE", "0")
    ),
    "VDT_ROUTER_BREAKER_WINDOW_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_BREAKER_WINDOW_SECONDS", "30")
    ),
    # Retry budget (global + per-replica, monotonic token accounting):
    # a retry or hedge is granted only while granted < min + ratio *
    # attempts, so retries can never amplify outbound load beyond the
    # ratio plus the fixed reserve.  0 = budget off (unbounded retries,
    # exactly as before).  Exhausted budget degrades to the existing
    # 503/migration paths instead of retrying.
    "VDT_ROUTER_RETRY_BUDGET_RATIO": lambda: float(
        os.environ.get("VDT_ROUTER_RETRY_BUDGET_RATIO", "0")
    ),
    "VDT_ROUTER_RETRY_BUDGET_MIN": lambda: float(
        os.environ.get("VDT_ROUTER_RETRY_BUDGET_MIN", "10")
    ),
    # Adaptive deadlines: per-endpoint EWMA latency quantiles replace
    # the fixed unary ClientTimeout totals (clamped to
    # [floor, ceiling]; ceiling 0 = the router read timeout), so a
    # slow-but-alive replica isn't declared dead and a hung one is cut
    # fast.  Streaming reads keep their fixed sock_read deadline.
    "VDT_ROUTER_ADAPTIVE_DEADLINE": lambda: int(
        os.environ.get("VDT_ROUTER_ADAPTIVE_DEADLINE", "0")
    ),
    "VDT_ROUTER_DEADLINE_FLOOR_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_DEADLINE_FLOOR_SECONDS", "1")
    ),
    "VDT_ROUTER_DEADLINE_CEILING_SECONDS": lambda: float(
        os.environ.get("VDT_ROUTER_DEADLINE_CEILING_SECONDS", "0")
    ),
    "VDT_ROUTER_DEADLINE_MULTIPLIER": lambda: float(
        os.environ.get("VDT_ROUTER_DEADLINE_MULTIPLIER", "3")
    ),
    # Hedged requests on idempotent read paths (/health, /metrics,
    # /slo scrapes and /internal/kv/export chunk pulls): after a
    # p95-based delay a duplicate request races the first, first winner
    # cancels the loser, hedges draw from the retry budget.
    "VDT_ROUTER_HEDGE": lambda: int(
        os.environ.get("VDT_ROUTER_HEDGE", "0")
    ),
    # Floor under the p95-based hedge delay (a cold or very fast
    # endpoint must not hedge instantly and double its load).
    "VDT_ROUTER_HEDGE_MIN_DELAY_MS": lambda: float(
        os.environ.get("VDT_ROUTER_HEDGE_MIN_DELAY_MS", "50")
    ),
    # Resumable KV transfer: per-chunk retry cap on the prefill->decode
    # page stream.  A dropped connection re-pulls only the missing
    # checksummed chunks (begin carries resume_from) instead of
    # aborting the hand-off to recompute; retries also draw from the
    # retry budget.  0 = single-attempt transfer, exactly as before.
    "VDT_ROUTER_KV_CHUNK_RETRIES": lambda: int(
        os.environ.get("VDT_ROUTER_KV_CHUNK_RETRIES", "0")
    ),
    # --- elastic fleet (ISSUE 13) ---
    # Command template the router's ReplicaManager launches managed
    # replicas with ({port} and {replica_id} placeholders, e.g.
    # "vdt serve MODEL --host 127.0.0.1 --port {port}").  Empty = fleet
    # mode needs --fleet-cmd.
    "VDT_FLEET_CMD": lambda: os.environ.get("VDT_FLEET_CMD", ""),
    # Health-gated warmup: how long a freshly spawned replica may take
    # to answer /health 200 before the spawn counts as a crash.  A
    # replica is never routable before its first healthy answer.
    "VDT_FLEET_WARMUP_TIMEOUT_SECONDS": lambda: float(
        os.environ.get("VDT_FLEET_WARMUP_TIMEOUT_SECONDS", "120")
    ),
    # Scale-down drain bound: how long the manager waits for a
    # replica's /drain (journal-migration of its in-flight streams)
    # before terminating it anyway.  Also bounds the router's SIGTERM
    # drain of the whole managed fleet.
    "VDT_FLEET_DRAIN_TIMEOUT_SECONDS": lambda: float(
        os.environ.get("VDT_FLEET_DRAIN_TIMEOUT_SECONDS", "30")
    ),
    # Reconcile/crash-poll cadence of the fleet supervisor loop.
    "VDT_FLEET_CHECK_INTERVAL_SECONDS": lambda: float(
        os.environ.get("VDT_FLEET_CHECK_INTERVAL_SECONDS", "0.5")
    ),
    # Crash-loop policy, mirroring the PR 3 engine supervisor: at most
    # this many restarts within the window (0 = never restart a crashed
    # replica), with exponential backoff between attempts.
    "VDT_FLEET_MAX_RESTARTS": lambda: int(
        os.environ.get("VDT_FLEET_MAX_RESTARTS", "3")
    ),
    "VDT_FLEET_RESTART_WINDOW_SECONDS": lambda: float(
        os.environ.get("VDT_FLEET_RESTART_WINDOW_SECONDS", "300")
    ),
    "VDT_FLEET_RESTART_BACKOFF_SECONDS": lambda: float(
        os.environ.get("VDT_FLEET_RESTART_BACKOFF_SECONDS", "1")
    ),
    "VDT_FLEET_RESTART_BACKOFF_CAP_SECONDS": lambda: float(
        os.environ.get("VDT_FLEET_RESTART_BACKOFF_CAP_SECONDS", "30")
    ),
    # --- autoscaler (ISSUE 13; --autoscale arms the loop) ---
    # Control-loop tick interval and replica-count bounds.
    "VDT_AUTOSCALE_INTERVAL_SECONDS": lambda: float(
        os.environ.get("VDT_AUTOSCALE_INTERVAL_SECONDS", "5")
    ),
    "VDT_AUTOSCALE_MIN_REPLICAS": lambda: int(
        os.environ.get("VDT_AUTOSCALE_MIN_REPLICAS", "1")
    ),
    "VDT_AUTOSCALE_MAX_REPLICAS": lambda: int(
        os.environ.get("VDT_AUTOSCALE_MAX_REPLICAS", "4")
    ),
    # Hysteresis watermarks on the primary signal (mean waiting-queue
    # depth per routable replica, the PR 7 admission gauge the pool
    # already scrapes): scale up above the high mark, down below the
    # low mark, hold in between.
    "VDT_AUTOSCALE_UP_WAITING": lambda: float(
        os.environ.get("VDT_AUTOSCALE_UP_WAITING", "4")
    ),
    "VDT_AUTOSCALE_DOWN_WAITING": lambda: float(
        os.environ.get("VDT_AUTOSCALE_DOWN_WAITING", "1")
    ),
    # Per-direction cooldowns: no two scale-ups (downs) closer than
    # this, so one burst can't slam the fleet to max and back.
    "VDT_AUTOSCALE_UP_COOLDOWN_SECONDS": lambda: float(
        os.environ.get("VDT_AUTOSCALE_UP_COOLDOWN_SECONDS", "15")
    ),
    "VDT_AUTOSCALE_DOWN_COOLDOWN_SECONDS": lambda: float(
        os.environ.get("VDT_AUTOSCALE_DOWN_COOLDOWN_SECONDS", "60")
    ),
    # Secondary scale-up triggers (0 = off): fleet 429 rate (rejections
    # per second over the tick window) and fleet ITL p99 (ms, from the
    # ISSUE 12 /router/slo merge) above which the fleet grows even if
    # queues look shallow.
    "VDT_AUTOSCALE_MAX_REJECT_RATE": lambda: float(
        os.environ.get("VDT_AUTOSCALE_MAX_REJECT_RATE", "0")
    ),
    "VDT_AUTOSCALE_ITL_P99_MS": lambda: float(
        os.environ.get("VDT_AUTOSCALE_ITL_P99_MS", "0")
    ),
    # --- QoS control plane (ISSUE 16) ---
    # SLO class registry: one entry per class,
    # "name:priority[:share[:weight]]", comma-separated (e.g.
    # "interactive:10:0.5,default:0:0.3,batch:-10:0:2.0").  Priority
    # orders admission and preemption (higher admits first, preempts
    # last); share is the class's guaranteed-minimum fraction of the
    # bounded-admission caps (work-conserving: spare capacity is
    # borrowable by any class); weight scales the preempt-to-shed
    # budget.  Empty (the default) disables the QoS control plane
    # entirely — seed scheduling is bit-identical.
    "VDT_QOS_CLASSES": lambda: os.environ.get("VDT_QOS_CLASSES", ""),
    # Chunked-prefill fairness budget: while any decode-bound request
    # of higher-or-equal class is running, prefill chunks may take at
    # most this fraction of the per-step token budget, bounding decode
    # ITL under a long concurrent prefill.  Work-conserving: with no
    # qualifying decode running, prefill uses the full budget.
    # 0 = off (the seed policy: prefill fills whatever budget is left).
    "VDT_QOS_PREFILL_SHARE": lambda: float(
        os.environ.get("VDT_QOS_PREFILL_SHARE", "0")
    ),
    # Router per-class placement: "shared" (seed behaviour — every
    # class places on every replica), "segregate" (disjoint replica
    # partition per class, proportional to admission shares), or
    # "reserve" (co-locate, but lower classes avoid the top class's
    # headroom replicas while alternatives exist).
    "VDT_QOS_PLACEMENT": lambda: os.environ.get(
        "VDT_QOS_PLACEMENT", "shared"
    ),
    # Per-class SLO-aware scale-up: grow the fleet when any class's
    # windowed goodput ratio (from the /router/slo merge) sags below
    # this floor (0 = trigger off), ignoring windows with fewer than
    # VDT_AUTOSCALE_GOODPUT_MIN_REQUESTS finished requests.
    "VDT_AUTOSCALE_GOODPUT_FLOOR": lambda: float(
        os.environ.get("VDT_AUTOSCALE_GOODPUT_FLOOR", "0")
    ),
    "VDT_AUTOSCALE_GOODPUT_MIN_REQUESTS": lambda: int(
        os.environ.get("VDT_AUTOSCALE_GOODPUT_MIN_REQUESTS", "20")
    ),
    # Per-role autoscaling of the disagg prefill pool (ISSUE 15): the
    # prefill-pool target tracks an EWMA of the long-prompt arrival
    # rate (prompts at/above VDT_DISAGG_MIN_PROMPT_TOKENS) divided by
    # the per-replica absorbable rate benched at the crossover.
    # 0 = off (the pool stays at --fleet-prefill).
    "VDT_AUTOSCALE_PREFILL_RPS": lambda: float(
        os.environ.get("VDT_AUTOSCALE_PREFILL_RPS", "0")
    ),
    "VDT_AUTOSCALE_PREFILL_EWMA_SECONDS": lambda: float(
        os.environ.get("VDT_AUTOSCALE_PREFILL_EWMA_SECONDS", "30")
    ),
    "VDT_AUTOSCALE_PREFILL_MIN": lambda: int(
        os.environ.get("VDT_AUTOSCALE_PREFILL_MIN", "0")
    ),
    "VDT_AUTOSCALE_PREFILL_MAX": lambda: int(
        os.environ.get("VDT_AUTOSCALE_PREFILL_MAX", "4")
    ),
    # --- observability ---
    # SLO targets for goodput accounting (engine/slo.py, ISSUE 12), in
    # milliseconds.  A bare number sets the "default" class; per-class:
    # "default:500,interactive:200,batch:5000".  Empty = no targets
    # (every class attains trivially; goodput == completed requests).
    "VDT_SLO_TTFT_MS": lambda: os.environ.get("VDT_SLO_TTFT_MS", ""),
    "VDT_SLO_ITL_MS": lambda: os.environ.get("VDT_SLO_ITL_MS", ""),
    # Flight recorder (engine/flight_recorder.py): per-step records
    # kept in the always-on ring (0 disables), and where the JSON
    # artifacts land on HostFailure/recovery/drain (per-host; empty =
    # <tmpdir>/vdt-flightrecorder).
    "VDT_FLIGHT_RECORDER_SIZE": lambda: int(
        os.environ.get("VDT_FLIGHT_RECORDER_SIZE", "512")
    ),
    "VDT_FLIGHT_RECORDER_DIR": lambda: os.environ.get(
        "VDT_FLIGHT_RECORDER_DIR", ""
    ),
    # Server-side jax.profiler captures (POST /debug/profile): artifact
    # directory; empty disables the endpoint (404).  --profile-dir
    # wins.  Per-host: a profile is local state like a drain journal.
    "VDT_PROFILE_DIR": lambda: os.environ.get("VDT_PROFILE_DIR", ""),
    # Per-request tracing (tracing.py): default off; the engine step
    # loop runs the no-op tracer path and /debug/traces answers 404.
    # Replicated to agents so worker-side RPC spans land in the same
    # trace as the driver's.
    "VDT_TRACING": lambda: os.environ.get("VDT_TRACING", "0").lower()
    not in ("", "0", "false", "off"),
    # Completed traces kept in memory (bounded ring; oldest evicted).
    "VDT_TRACE_RING_SIZE": lambda: int(
        os.environ.get("VDT_TRACE_RING_SIZE", "256")
    ),
    # --- fleet sentinel (ISSUE 20) ---
    # Unified event timeline (engine/sentinel.py): events kept per
    # component log (engine ring served at /debug/events, router ring
    # merged into /router/timeline).  0 disables event collection.
    "VDT_SENTINEL_EVENTS_SIZE": lambda: int(
        os.environ.get("VDT_SENTINEL_EVENTS_SIZE", "512")
    ),
    # SLO objective for burn-rate math: the target attainment ratio.
    # burn = error_rate / (1 - objective); at 0.99 a burn of 1.0 means
    # exactly 1% of requests are missing their targets.
    "VDT_SLO_OBJECTIVE": lambda: float(
        os.environ.get("VDT_SLO_OBJECTIVE", "0.99")
    ),
    # Multi-window burn-rate alert threshold: an alert fires when the
    # burn exceeds this on EVERY window (5m and 1h) simultaneously.
    "VDT_SENTINEL_BURN_THRESHOLD": lambda: float(
        os.environ.get("VDT_SENTINEL_BURN_THRESHOLD", "10")
    ),
    # Robust-z (median/MAD, sigma units) past which a replica's signal
    # marks it degraded (router/sentinel.py anomaly scoring).
    "VDT_SENTINEL_ANOMALY_THRESHOLD": lambda: float(
        os.environ.get("VDT_SENTINEL_ANOMALY_THRESHOLD", "4")
    ),
    # Let anomaly scores influence placement: outlier replicas are
    # DEPRIORITIZED (chosen only when no in-band replica can take the
    # request), never ejected.  Default off: scoring is observe-only.
    "VDT_SENTINEL_PLACEMENT": lambda: os.environ.get(
        "VDT_SENTINEL_PLACEMENT", "0"
    ).lower()
    not in ("", "0", "false", "off"),
    # --- per-host test/operator hooks (never replicated) ---
    # Install the deterministic FaultInjector on this process's RPC
    # transports (tests/test_fault_injection.py arms it over RPC).
    "VDT_FAULT_INJECTION": lambda: os.environ.get(
        "VDT_FAULT_INJECTION", ""
    )
    == "1",
    # Deterministic pre-dial delay in the agent (fault harness only).
    "VDT_FAULT_CONNECT_DELAY_SECONDS": lambda: float(
        os.environ.get("VDT_FAULT_CONNECT_DELAY_SECONDS", "0")
    ),
    # Pin this host's chip advertisement instead of probing jax in a
    # subprocess (operators/tests; both must be set to take effect).
    "VDT_ADVERTISE_NUM_CHIPS": lambda: os.environ.get(
        "VDT_ADVERTISE_NUM_CHIPS"
    ),
    "VDT_ADVERTISE_PLATFORM": lambda: os.environ.get(
        "VDT_ADVERTISE_PLATFORM"
    ),
    # --- engine ---
    "VDT_LOG_LEVEL": lambda: os.environ.get("VDT_LOG_LEVEL", "INFO"),
    # Persistent jax.export artifact cache for warm restarts (skips
    # trace+lower, not just XLA compile): "auto" = on for TPU,
    # "1" = always, "0" = off.  Artifacts live in aot/ under the
    # compile cache (compile_cache.py: $JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache).
    "VDT_AOT_CACHE": lambda: os.environ.get("VDT_AOT_CACHE", "auto"),
    "VDT_HBM_UTILIZATION": lambda: float(
        os.environ.get("VDT_HBM_UTILIZATION", "0.9")
    ),
    "VDT_HTTP_TIMEOUT_KEEP_ALIVE": lambda: int(
        os.environ.get("VDT_HTTP_TIMEOUT_KEEP_ALIVE", "5")
    ),
    # Kernel backend (ops.resolve_kernel_backend): "auto" = compiled
    # Pallas and an error off-TPU; "pallas" | "pallas_interpret" |
    # "reference" by name.
    "VDT_USE_PALLAS": lambda: os.environ.get("VDT_USE_PALLAS", "auto"),
    # MoE expert dispatch: "auto" picks per call site — dense-fused for
    # bandwidth-bound shapes (decode, or quantized experts whose
    # dequant fuses into the dense dot but not into ragged_dot),
    # ragged (sorted jax.lax.ragged_dot, ~k/E of the dense FLOPs) for
    # compute-bound prefill rows, "branch" (an expert behind a
    # conditional) for the few-row steps of a model that says how wide
    # its router is.  "ragged"/"dense"/"grouped"/"branch" force one
    # path (models/routed_experts.select_moe_impl).
    "VDT_MOE_IMPL": lambda: os.environ.get("VDT_MOE_IMPL", "auto"),
    # --- external, replicated for weight download ---
    "HF_TOKEN": lambda: os.environ.get("HF_TOKEN", ""),
    "HUGGING_FACE_HUB_TOKEN": lambda: os.environ.get("HUGGING_FACE_HUB_TOKEN", ""),
    "HF_HOME": lambda: os.environ.get("HF_HOME", ""),
}

# Per-host variables that must NOT be replicated to remote workers, the
# analog of the exclusion set at launch.py:62-69 ({VLLM_HOST_IP,
# VLLM_HOST_PORT, LOCAL_RANK, CUDA_VISIBLE_DEVICES}).
NON_REPLICATED_ENV_VARS = {
    "VDT_HOST_IP",
    "VDT_SERVER_PORT",
    "TPU_VISIBLE_DEVICES",
    "JAX_PLATFORMS",
    "LOCAL_RANK",
    "RANK",
    # Per-host test/operator hooks: the driver's values must never leak
    # onto remote hosts (arming faults fleet-wide, or pinning every
    # host's chip advertisement to the driver's, would be wrong).
    "VDT_FAULT_INJECTION",
    "VDT_FAULT_CONNECT_DELAY_SECONDS",
    "VDT_ADVERTISE_NUM_CHIPS",
    "VDT_ADVERTISE_PLATFORM",
    # A replica's drain journal is local state: replicating the path
    # onto remote workers would have every host writing (and on boot,
    # consuming) the same file.
    "VDT_DRAIN_JOURNAL_PATH",
    # Flight-recorder artifacts and profiler captures are local state
    # for the same reason (and workers run no engine loop to record).
    "VDT_FLIGHT_RECORDER_DIR",
    "VDT_PROFILE_DIR",
    # Replica identity and router knobs are per-process: replicating a
    # replica's id onto its workers (or a router's backend set onto
    # anything) would be meaningless at best and confusing in logs.
    "VDT_REPLICA_ID",
    "VDT_ROUTER_REPLICAS",
    "VDT_ROUTER_POLICY",
    "VDT_ROUTER_HEALTH_INTERVAL_SECONDS",
    "VDT_ROUTER_AFFINITY_BLOCK_TOKENS",
    "VDT_ROUTER_AFFINITY_CAPACITY",
    "VDT_ROUTER_AFFINITY_MIN_TOKENS",
    "VDT_ROUTER_MAX_MIGRATIONS",
    "VDT_ROUTER_CONNECT_TIMEOUT_SECONDS",
    "VDT_ROUTER_READ_TIMEOUT_SECONDS",
    # Crash-safe router state (ISSUE 17): the WAL is the ROUTER
    # process's local durable state — replicating the dir onto workers
    # or replicas would have every process writing (and on boot,
    # recovering) the same fleet.
    "VDT_ROUTER_STATE_DIR",
    "VDT_ROUTER_STATE_SEGMENT_BYTES",
    "VDT_ROUTER_STATE_FSYNC_INTERVAL_SECONDS",
    "VDT_ROUTER_STATE_CKPT_INTERVAL_SECONDS",
    "VDT_ROUTER_STATE_VERIFY_WINDOW_SECONDS",
    "VDT_ROUTER_STATE_RECOVERY_TTL_SECONDS",
    # Sentinel placement (ISSUE 20) is a router-process decision: the
    # anomaly scores live in the router; replicas have no pool to
    # deprioritize against.  (The other sentinel knobs DO replicate —
    # objective/threshold/log size are fleet-wide policy.)
    "VDT_SENTINEL_PLACEMENT",
    # Disaggregation (ISSUE 15): the role is per-replica identity like
    # VDT_REPLICA_ID; the crossover/chunking knobs configure the ROUTER
    # process's hand-off orchestration; export holds are driver-engine
    # state (workers hold no pages of their own to expire).
    "VDT_ROUTER_ROLE",
    "VDT_DISAGG_MIN_PROMPT_TOKENS",
    "VDT_DISAGG_CHUNK_LAYERS",
    "VDT_DISAGG_EXPORT_TTL_SECONDS",
    # Resilient data plane (ISSUE 19): breakers, retry budgets,
    # adaptive deadlines, hedging, and chunk-resume all configure the
    # ROUTER process's outbound HTTP behavior — replicating them onto
    # engine workers would be meaningless.  (VDT_KV_MAX_FRAME_BYTES is
    # replica-side server config and DOES replicate.)
    "VDT_ROUTER_BREAKER_FAILURES",
    "VDT_ROUTER_BREAKER_COOLDOWN_SECONDS",
    "VDT_ROUTER_BREAKER_TIMEOUT_RATE",
    "VDT_ROUTER_BREAKER_WINDOW_SECONDS",
    "VDT_ROUTER_RETRY_BUDGET_RATIO",
    "VDT_ROUTER_RETRY_BUDGET_MIN",
    "VDT_ROUTER_ADAPTIVE_DEADLINE",
    "VDT_ROUTER_DEADLINE_FLOOR_SECONDS",
    "VDT_ROUTER_DEADLINE_CEILING_SECONDS",
    "VDT_ROUTER_DEADLINE_MULTIPLIER",
    "VDT_ROUTER_HEDGE",
    "VDT_ROUTER_HEDGE_MIN_DELAY_MS",
    "VDT_ROUTER_KV_CHUNK_RETRIES",
    # Fleet lifecycle + autoscaler knobs configure the ROUTER process's
    # control loops; replicating them to engine workers (or to the
    # managed replicas themselves) would be meaningless.
    "VDT_FLEET_CMD",
    "VDT_FLEET_WARMUP_TIMEOUT_SECONDS",
    "VDT_FLEET_DRAIN_TIMEOUT_SECONDS",
    "VDT_FLEET_CHECK_INTERVAL_SECONDS",
    "VDT_FLEET_MAX_RESTARTS",
    "VDT_FLEET_RESTART_WINDOW_SECONDS",
    "VDT_FLEET_RESTART_BACKOFF_SECONDS",
    "VDT_FLEET_RESTART_BACKOFF_CAP_SECONDS",
    "VDT_AUTOSCALE_INTERVAL_SECONDS",
    "VDT_AUTOSCALE_MIN_REPLICAS",
    "VDT_AUTOSCALE_MAX_REPLICAS",
    "VDT_AUTOSCALE_UP_WAITING",
    "VDT_AUTOSCALE_DOWN_WAITING",
    "VDT_AUTOSCALE_UP_COOLDOWN_SECONDS",
    "VDT_AUTOSCALE_DOWN_COOLDOWN_SECONDS",
    "VDT_AUTOSCALE_MAX_REJECT_RATE",
    "VDT_AUTOSCALE_ITL_P99_MS",
    # QoS (ISSUE 16): placement and the goodput/per-role autoscale
    # knobs configure the ROUTER's control loops (the class registry
    # itself, VDT_QOS_CLASSES, and the engine-side fairness budget DO
    # replicate — every replica must agree on the class table).
    "VDT_QOS_PLACEMENT",
    "VDT_AUTOSCALE_GOODPUT_FLOOR",
    "VDT_AUTOSCALE_GOODPUT_MIN_REQUESTS",
    "VDT_AUTOSCALE_PREFILL_RPS",
    "VDT_AUTOSCALE_PREFILL_EWMA_SECONDS",
    "VDT_AUTOSCALE_PREFILL_MIN",
    "VDT_AUTOSCALE_PREFILL_MAX",
}

# Extra vars replicated even though they are not VDT_* (launch.py:70-72).
ADDITIONAL_REPLICATED_ENV_VARS = {
    "HF_TOKEN",
    "HUGGING_FACE_HUB_TOKEN",
    "HF_HOME",
}


def replication_env(environ: dict[str, str] | None = None) -> dict[str, str]:
    """Env vars to copy from the driver to a remote worker.

    Mirrors launch.py:198-208: everything in the registry that is actually
    set in the driver's environment, minus per-host vars, plus the HF vars.
    """
    environ = os.environ if environ is None else environ
    out: dict[str, str] = {}
    for name in environment_variables:
        if name in NON_REPLICATED_ENV_VARS:
            continue
        if name in environ:
            out[name] = environ[name]
    for name in ADDITIONAL_REPLICATED_ENV_VARS:
        if name in environ:
            out[name] = environ[name]
    return out


def __getattr__(name: str) -> Any:
    if name in environment_variables:
        return environment_variables[name]()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
