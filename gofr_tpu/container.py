"""Container — dependency-injection root owning all shared state.

Capability parity with ``pkg/gofr/container/container.go`` (Container struct
27-46; ``Create`` composition root 63-146: remote logger, metrics manager +
framework metrics, Redis, SQL, pub/sub backend switch from env, File;
framework metric catalog 158-190) and ``container/health.go`` (aggregated
deep health 8-66).

TPU addition (north star): the container owns a ``tpu`` executor datasource —
models resident in device HBM, AOT-compiled XLA executables, per-device
health — created when ``TPU_ENABLED`` is truthy, with a CPU-backed executor
as the test double (the "miniredis of XLA", SURVEY.md §4).
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional

from gofr_tpu.config import Config, MapConfig
from gofr_tpu.logging import Level, Logger, new_logger, new_silent_logger
from gofr_tpu.metrics import Manager, new_manager
from gofr_tpu.slo import SLOTracker
from gofr_tpu.trace import Tracer, new_tracer
from gofr_tpu.version import FRAMEWORK_VERSION


class Container:
    def __init__(self, config: Optional[Config] = None,
                 logger: Optional[Logger] = None):
        self.config: Config = config if config is not None else MapConfig()
        self.app_name = self.config.get_or_default("APP_NAME", "gofr-tpu-app")
        self.app_version = self.config.get_or_default("APP_VERSION", "dev")
        self.logger: Logger = logger if logger is not None else new_logger()
        self.metrics: Manager = new_manager(self.logger)
        self.tracer: Tracer = Tracer()
        self.services: Dict[str, Any] = {}
        # SLO accounting (windowed goodput/TTFT) + degradation watchdog;
        # the watchdog is created by App.start (it needs the event loop)
        self.slo = SLOTracker(self.metrics)
        self.watchdog = None

        # datasources (all optional; wired by create())
        self.sql = None
        self.redis = None
        self.pubsub = None
        self.mongo = None
        self.cassandra = None
        self.clickhouse = None
        self.file = None
        self.tpu = None
        self.tpu_batcher = None  # created by App.start when tpu is wired
        self.batch_lane = None   # pub/sub generation lane (BATCH_LANE_TOPIC)
        # disaggregated serving (ISSUE 8): ClusterRegistry of replica
        # roles, wired by the example/app when CLUSTER_ROLE/CLUSTER_PEERS
        # configure a prefill/decode split; folds into health() below
        self.cluster = None
        # the DisaggRouter serving that cluster, when one exists — the
        # clusterz/tracez pages discover it here (ISSUE 10)
        self.cluster_router = None
        # continuous telemetry plane (ISSUE 16): the bounded time-series
        # store + anomaly detector, created by App.start (TELEMETRY_*);
        # /debug/timez and the statusz sparkline section read it here
        self.telemetry = None
        # workload capture plane (ISSUE 17): the bounded shape-only
        # TrafficRecorder, created by App.start (TRAFFIC_REC_*);
        # /debug/workloadz and the replay harness read it here
        self.workload = None
        # SLO error-budget burn-rate plane (ISSUE 18): created by
        # App.start (SLO_BUDGET_*/SLO_OBJECTIVE_*); /debug/sloz and the
        # watchdog's budget_fn read it here
        self.slo_budget = None
        # worst-offender ring (ISSUE 18): top-K slowest requests per
        # window with finish-time diagnoses, created by App.start
        # (WHYZ_*); /debug/whyz and /debug/sloz read it here
        self.offenders = None
        # online operating-point auto-tuner (ISSUE 19): the cron-driven
        # controller, created by App.start (AUTOTUNE_*, opt-in);
        # /debug/tunez and the statusz autotune section read it here
        self.autotune = None

        self._start_time = time.time()

    # -- composition root (container.go:63-146) -----------------------------
    @classmethod
    def create(cls, config: Config, logger: Optional[Logger] = None) -> "Container":
        level = Level.parse(config.get_or_default("LOG_LEVEL", "INFO"))
        log = logger if logger is not None else new_logger(level)
        container = cls(config=config, logger=log)
        container.tracer = new_tracer(config, log)
        container.register_framework_metrics()

        # remote log level poller (container.go:73-75; remotelogger)
        remote_url = config.get("REMOTE_LOG_URL")
        if remote_url:
            from gofr_tpu.logging.remote_level import start_remote_level_poller
            interval = config.get_float("REMOTE_LOG_FETCH_INTERVAL", 15.0)
            start_remote_level_poller(log, remote_url, interval)

        # SQL (container.go:90)
        dialect = config.get("DB_DIALECT")
        if dialect:
            from gofr_tpu.datasource.sql import new_sql
            container.sql = new_sql(config, log, container.metrics)

        # Redis (container.go:88)
        if config.get("REDIS_HOST"):
            from gofr_tpu.datasource.redisx import new_redis
            container.redis = new_redis(config, log, container.metrics)

        # pub/sub backend switch (container.go:92-143)
        backend = (config.get("PUBSUB_BACKEND") or "").upper()
        if backend:
            from gofr_tpu.datasource.pubsub import new_pubsub
            container.pubsub = new_pubsub(backend, config, log,
                                          container.metrics,
                                          tracer=container.tracer)

        # file datasource (container.go:145)
        from gofr_tpu.datasource.file import LocalFileSystem
        container.file = LocalFileSystem(log)

        # TPU executor (north star; no reference analog)
        if config.get_bool("TPU_ENABLED", False):
            from gofr_tpu.tpu import new_executor
            container.tpu = new_executor(config, log, container.metrics)

        log.debug("container created for app %s@%s (framework %s)",
                  container.app_name, container.app_version, FRAMEWORK_VERSION)
        return container

    # -- framework metric catalog (container.go:158-190) --------------------
    def register_framework_metrics(self) -> None:
        metrics = self.metrics
        metrics.new_gauge("app_info", "application name/version info")
        metrics.new_gauge("threads_total", "live Python threads")
        metrics.new_gauge("memory_rss_bytes", "resident set size")
        metrics.new_gauge("gc_objects", "gen-0 tracked objects")
        metrics.new_gauge("uptime_seconds", "process uptime")
        metrics.new_histogram("app_http_response",
                              "inbound HTTP response time (s)",
                              (0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1, 3, 10, 30))
        metrics.new_histogram(
            "app_http_stream_self_seconds",
            "the server's own time in one streamed reply (s): head "
            "serialisation, framing and socket writes; time awaiting the "
            "producer is not in it",
            (0.0001, 0.0003, 0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1))
        metrics.new_histogram("app_http_service_response",
                              "outbound HTTP call time (s)",
                              (0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1, 3, 10, 30))
        metrics.new_histogram("app_redis_stats", "redis op time (s)",
                              (0.00005, 0.0001, 0.0003, 0.001, 0.003))
        metrics.new_histogram("app_sql_stats", "sql query time (s)",
                              (0.00005, 0.0001, 0.0005, 0.001, 0.01))
        # pushed by the SQL maintenance loop (sql.go:189-202 analog)
        metrics.new_gauge("app_sql_open_connections", "SQL connection up 0/1")
        metrics.new_gauge("app_sql_inuse_connections",
                          "SQL statements currently executing")
        metrics.new_counter("app_pubsub_publish_total_count", "publish attempts")
        metrics.new_counter("app_pubsub_publish_success_count", "publishes ok")
        metrics.new_counter("app_pubsub_subscribe_total_count", "receive attempts")
        metrics.new_counter("app_pubsub_subscribe_success_count", "receives ok")
        metrics.new_counter(
            "app_pubsub_consumer_paused_total",
            "consumer pause transitions per (topic, reason) — backpressure "
            "from the batch lane (admission_depth|kv_pages|degraded) or an "
            "explicit fetcher pause")
        # TPU catalog (north star: chip liveness + HBM pressure via metrics)
        metrics.new_histogram("app_tpu_execute", "XLA execute wall time (s)",
                              (0.0005, 0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1))
        metrics.new_histogram("app_tpu_batch_size", "dynamic batch sizes",
                              (1, 2, 4, 8, 16, 32, 64, 128, 256))
        metrics.new_gauge("app_tpu_hbm_bytes_in_use", "HBM bytes in use per device")
        metrics.new_gauge("app_tpu_device_up", "per-device liveness 0/1")
        metrics.new_counter("app_tpu_requests_total", "TPU predict requests")
        metrics.new_histogram(
            "app_tpu_ttft",
            "time to first generated token (s): admission wait + prefill "
            "(the first token is sampled inside the prefill executable)",
            (0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0))
        metrics.new_histogram(
            "app_tpu_request_phase_seconds",
            "a request's phases (s): app_tpu_ttft in its two parts, "
            "phase=queue (submit -> slot claimed) and phase=first_token "
            "(slot claimed -> first token published), and phase=stall "
            "(of its decode phase, the part the device spent in other "
            "requests' prefill groups)",
            (0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0))
        # SLO & saturation catalog (ISSUE 2): goodput vs raw throughput,
        # deadline outcome counts, device utilization, health transitions
        metrics.new_counter(
            "app_tpu_slo_total",
            "terminal requests by deadline outcome (ok|violated|expired)")
        # error-budget burn plane (ISSUE 18): derived from the labelled
        # app_tpu_slo_total series through the telemetry store — no
        # second counting path
        metrics.new_gauge(
            "app_tpu_slo_budget_remaining",
            "fraction of the (model, class) error budget left over the "
            "accounting window")
        metrics.new_gauge(
            "app_tpu_slo_burn_rate",
            "error-budget burn multiple per (model, class, window); 1.0 "
            "spends exactly the budget over the objective period")
        metrics.new_histogram(
            "app_tpu_deadline_violation_seconds",
            "how late past its deadline a violated request finished (s); "
            "bucket exemplars carry the trace id for /debug/whyz",
            (0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0))
        metrics.new_gauge("app_tpu_tokens_per_s",
                          "raw generated tokens/s over the rolling window")
        metrics.new_gauge(
            "app_tpu_goodput_tokens_per_s",
            "tokens/s of requests that completed within deadline")
        metrics.new_gauge(
            "app_tpu_slo_attainment",
            "fraction of windowed terminal requests that met their deadline")
        metrics.new_gauge(
            "app_tpu_duty_cycle",
            "fraction of the rolling window the device spent executing")
        metrics.new_gauge(
            "app_tpu_mfu",
            "model flops utilization vs TPU_PEAK_FLOPS over the window")
        metrics.new_gauge("app_tpu_hbm_occupancy",
                          "HBM bytes_in_use / bytes_limit per device")
        metrics.new_counter(
            "app_health_transitions_total",
            "watchdog READY<->DEGRADED flips, labeled by target state")
        # compile-plane & shape catalog (ISSUE 3): recompiles, padding
        # waste, bucket fit, flush causes, step-phase anatomy
        metrics.new_counter(
            "app_tpu_compile_total",
            "XLA compiles by cause (warmup|serving) and model — any "
            "cause=serving increment is a cold compile on the hot path")
        metrics.new_histogram(
            "app_tpu_compile_seconds", "one XLA lower+compile wall time (s)",
            (0.1, 0.3, 1, 3, 10, 30, 100, 300))
        metrics.new_gauge(
            "app_tpu_padding_ratio",
            "fraction of executed device rows that were padding, over the "
            "rolling window")
        metrics.new_gauge(
            "app_tpu_effective_mfu",
            "MFU counting only real (non-padding) rows' FLOPs")
        metrics.new_counter(
            "app_tpu_bucket_hits_total",
            "executes per (model, bucket) — the observed bucket ladder fit")
        metrics.new_counter(
            "app_tpu_flush_total",
            "dynamic-batcher flushes by cause (full|timer) and model")
        metrics.new_histogram(
            "app_tpu_batch_fill",
            "flushed batch size / max_batch per flush",
            (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
        metrics.new_histogram(
            "app_tpu_step_phase_seconds",
            "device-step phase split: serialize | stage | upload | enqueue "
            "| device_wait (host_prep replaces the first three with "
            "EXEC_STAGING=0)",
            (0.0001, 0.0003, 0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1, 3))
        # zero-copy data plane (ISSUE 9): every host→device transfer —
        # staged dispatch uploads, coalesced tick inputs, adopted KV —
        # lands here, so the gap between the served path and the device
        # is attributable per path
        metrics.new_updown_counter(
            "app_tpu_h2d_bytes_total",
            "host→device bytes shipped, per path "
            "(dispatch|rows|coalesced|mask|kv)")
        metrics.new_histogram(
            "app_tpu_h2d_seconds",
            "host→device transfer wall time, per path "
            "(dispatch|rows|coalesced|mask|kv)",
            (0.00003, 0.0001, 0.0003, 0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1))
        # prefix-KV reuse catalog (ISSUE 4): radix-cache hit rates and the
        # prompt tokens whose prefill FLOPs the cache avoided
        metrics.new_counter(
            "app_tpu_prefix_lookup_total",
            "prefix-cache lookups by result (hit|partial|miss)")
        metrics.new_updown_counter(
            "app_tpu_prefix_tokens_saved_total",
            "prompt tokens served from cached prefix KV instead of prefill")
        metrics.new_gauge(
            "app_tpu_prefix_cache_occupancy",
            "prefix-KV page pool: used pages / total pages")
        # unified paged KV catalog (ISSUE 6): one page pool backs prefill
        # output, the prefix cache, and decode — pool pressure and the
        # raggedness of what slots actually hold
        metrics.new_gauge("app_tpu_kv_pages_used",
                          "KV page pool: pages currently referenced")
        metrics.new_gauge("app_tpu_kv_pages_capacity",
                          "KV page pool: total pages in the pool")
        metrics.new_gauge("app_tpu_state_slots_claimed",
                          "per-slot cache kind: rows whose slot is claimed")
        metrics.new_gauge("app_tpu_state_slots_capacity",
                          "per-slot cache kind: rows (the engine's slots)")
        metrics.new_updown_counter(
            "app_tpu_kv_pages_written_total",
            "pool pages written by prefill/publish scatters — a prefix "
            "hit admits with zero new writes")
        metrics.new_counter(
            "app_tpu_kv_pages_stalled_total",
            "page allocations that failed after reclaim (admission "
            "backpressure / decode-growth stalls)")
        metrics.new_counter(
            "app_tpu_attn_kernel_total",
            "decode/verify dispatches per attention path "
            "(ragged|gather|dense) — which formulation served the tick")
        metrics.new_updown_counter(
            "app_tpu_step_counter_total",
            "what a model module's decode steps counted (its "
            "STEP_COUNTERS, e.g. moe.held_pairs), summed over ticks, per "
            "model and counter")
        # speculative decode catalog (ISSUE 7): draft-verify acceptance —
        # goodput comes from accepted draft tokens, so the acceptance rate
        # and the adaptive gamma it drives are the first dashboards to read
        metrics.new_updown_counter(
            "app_tpu_spec_proposed_total",
            "draft tokens proposed to the target verify step, per model")
        metrics.new_updown_counter(
            "app_tpu_spec_accepted_total",
            "draft tokens the target verify step accepted, per model")
        metrics.new_gauge(
            "app_tpu_spec_acceptance_rate",
            "accepted/proposed draft tokens over the adaptive-gamma window")
        metrics.new_gauge(
            "app_tpu_spec_gamma",
            "speculative draft length: per-tick gamma rung and the "
            "adaptive cap it is chosen under")
        # multi-model SLO-class scheduling catalog (ISSUE 7): weighted-fair
        # admission by deadline class, per-class shed, model lifecycle
        metrics.new_updown_counter(
            "app_tpu_sched_tokens_total",
            "generated tokens per (model, SLO class) — per-class goodput")
        metrics.new_counter(
            "app_tpu_sched_shed_total",
            "admissions shed at overflow, per (model, SLO class) — "
            "shedding is strictly within-class (newest first) before "
            "any cross-class impact")
        metrics.new_gauge(
            "app_tpu_admission_queue_depth",
            "admission backlog (pending + overflow) per (model, SLO class)")
        metrics.new_gauge(
            "app_tpu_model_state",
            "registry lifecycle per model: 0 LOADING, 1 WARMING, 2 READY, "
            "3 DRAINING, 4 UNLOADED")
        metrics.new_counter(
            "app_tpu_model_fallback_total",
            "requests routed to a fallback model (by source model and "
            "fallback taken) — non-zero means degraded or non-READY "
            "routing is active")
        # disaggregated serving catalog (ISSUE 8): the prefill→decode KV
        # handoff — how long the wire leg takes, how many bytes it ships,
        # and how many migrated requests each decode replica admitted
        metrics.new_histogram(
            "app_tpu_kv_transfer_seconds",
            "prefill→decode KV handoff wall time (pack + wire + unpack), "
            "by transport (inproc|http)",
            (0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1, 3, 10))
        metrics.new_updown_counter(
            "app_tpu_kv_transfer_bytes_total",
            "KV bytes adopted from remote prefill, per model")
        metrics.new_counter(
            "app_tpu_kv_adoptions_total",
            "migrated requests whose KV was admitted as page-table "
            "entries (zero decode-side prefill), per model")
        metrics.new_gauge(
            "app_tpu_replica_state",
            "cluster replica lifecycle per (replica, role): 2 READY, "
            "3 DRAINING — same encoding as app_tpu_model_state")
        metrics.new_gauge(
            "app_tpu_replica_inflight",
            "router-level in-flight requests per replica — what drain "
            "waits on")
        # fleet observability catalog (ISSUE 10): handoff-expiry loss,
        # device-time attribution, and the hbmz reconciliation gauges
        metrics.new_counter(
            "app_tpu_kv_handoff_expired_total",
            "packed KV handoffs dropped unclaimed from the prefill "
            "replica's table, by reason (expired = TTL lapsed, evicted = "
            "capacity pressure) — each one is a wasted prompt forward")
        metrics.new_updown_counter(
            "app_tpu_device_seconds_total",
            "device time attributed per (model, SLO class): a program's "
            "interval between landings on the engine's timeline, split "
            "evenly across its participants — disjoint intervals, so "
            "the sum over one device is at most wall time")
        metrics.new_gauge(
            "app_tpu_hbm_attributed_bytes",
            "device bytes the serving stack accounts for (params + KV "
            "page pool + staging slabs)")
        metrics.new_gauge(
            "app_tpu_hbm_unattributed_bytes",
            "backend bytes_in_use minus attributed bytes — XLA "
            "temporaries, executables, fragmentation; watch its growth")
        # fleet control plane catalog (ISSUE 12): prefix-affinity routing,
        # live decode→decode migration, and the cron autoscaler
        metrics.new_counter(
            "app_tpu_fleet_route_total",
            "decode routing decisions by result (affinity = longest "
            "resident prefix won, fallback = least-inflight pick)")
        metrics.new_histogram(
            "app_tpu_fleet_affinity_pages",
            "resident-prefix depth (pages) of each affinity-routed "
            "request — how much prefill the fleet index saved",
            (1, 2, 4, 8, 16, 32, 64))
        metrics.new_counter(
            "app_tpu_fleet_migrations_total",
            "live decode→decode session migrations by result (ok|error)")
        metrics.new_histogram(
            "app_tpu_fleet_migration_seconds",
            "migration downtime: source export start → target adopt done "
            "(the client stream's splice gap)",
            (0.001, 0.003, 0.01, 0.03, 0.1, 0.3, 1, 3, 10))
        metrics.new_counter(
            "app_tpu_fleet_autoscale_total",
            "autoscaler decisions by result (up|down|hold|cooldown|"
            "compile_guard|overlap)")
        metrics.new_gauge(
            "app_tpu_fleet_decode_replicas",
            "READY decode-serving replicas the autoscaler last observed")
        # online operating-point auto-tuner (ISSUE 19): guarded cron
        # controller retuning serving knobs from shadow-replay scores
        metrics.new_counter(
            "app_tpu_autotune_total",
            "auto-tuner decisions by result (applied|rejected|"
            "rolled_back|hold|proposed|probation|no_trace|cooldown|"
            "compile_guard|overlap|refused_brownout|refused_fast_burn|"
            "rollback_blocked|probation_ok)")
        metrics.new_gauge(
            "app_tpu_autotune_score",
            "shadow-replay score of the last APPLIED operating point "
            "(deterministic goodput-per-cost proxy over the recorded "
            "trace)")
        metrics.new_gauge(
            "app_tpu_autotune_generation",
            "operating-point generation counter on the engine — bumps "
            "on every guarded apply, including rollbacks")
        metrics.new_counter(
            "app_tpu_engine_compiles_total",
            "engine-side executable compiles per (cls, model): cls is "
            "warmup (charged inside warmup/prewarm) or serving (a "
            "jit-cache miss on the hot path — the recompile-storm "
            "signal the auto-tuner guard reads)")
        # chaos plane catalog (ISSUE 14): seeded fault injection and the
        # recovery machinery it exercises — retries, hedges, circuit
        # trials, resumable decode, quarantine, and the brownout ladder
        metrics.new_counter(
            "app_tpu_fault_injected_total",
            "seeded faults the FAULT_PLAN actually fired, by site — "
            "zero outside chaos runs")
        metrics.new_counter(
            "app_tpu_disagg_retry_total",
            "disaggregated-serving retries by leg (prefill|fetch) — "
            "each one is a transient failure the budget absorbed")
        metrics.new_counter(
            "app_tpu_disagg_hedge_total",
            "hedged backup dispatches by leg — the primary blew the "
            "hedge deadline and an idempotent backup raced it")
        metrics.new_counter(
            "app_tpu_circuit_state_total",
            "circuit-breaker transitions by state entered "
            "(open|half_open|closed) — half_open admits one trial "
            "in flight, its outcome closes or re-opens")
        metrics.new_gauge(
            "app_tpu_brownout_level",
            "brownout ladder rung per role: 0 healthy, 1 shed batch, "
            "2 cap speculation, 3 speculation off")
        metrics.new_counter(
            "app_tpu_slot_quarantine_total",
            "poisoned slots excised mid-tick per (model, reason) — "
            "reason is grammar (walker raised) or nan_logits "
            "(out-of-vocab token ids); the rest of the batch proceeds")
        metrics.new_counter(
            "app_tpu_adopt_dedup_total",
            "replayed KV adoptions answered from the dedupe ledger, "
            "per model — a retry/hedge landed twice and was deduped")
        # continuous telemetry plane (ISSUE 16): change-point detector
        # verdicts over the in-process time-series store — one increment
        # per anomaly *raised* (not per sample), so the counter rate is
        # the replica's regime-change rate, not its sampling rate
        metrics.new_counter(
            "app_tpu_anomaly_total",
            "telemetry anomalies raised by the change-point detector, "
            "per (signal, direction) — a goodput cliff or padding spike "
            "that survived the detector's hysteresis")
        metrics.new_counter(
            "app_tpu_fleet_resume_total",
            "mid-stream decode resumes by result (ok|no_ctx|budget|"
            "exhausted|no_replica|error) — ok means the stream was "
            "rebuilt from prompt + emitted tokens on a live replica")
        # workload capture & roofline attribution (ISSUE 17): the
        # shape-only traffic recorder's admission pulse, and the
        # per-executable-family twin of app_tpu_device_seconds_total —
        # same elapsed windows, keyed by compiled executable instead of
        # SLO class, so the two totals agree by construction
        metrics.new_counter(
            "app_tpu_workload_events_total",
            "requests admitted into the workload recorder's shape-only "
            "ring, per (model, SLO class) — token lengths and timings "
            "only, never token content")
        metrics.new_updown_counter(
            "app_tpu_executable_device_seconds_total",
            "device time per (model, compiled executable family): the "
            "same intervals as app_tpu_device_seconds_total, the "
            "roofline-attribution twin; their totals match")
        metrics.new_updown_counter("app_http_inflight",
                                   "inbound HTTP requests currently in flight")
        metrics.new_histogram("app_cron_duration", "cron job run time (s)",
                              (0.001, 0.01, 0.1, 1, 10, 60, 300))
        metrics.new_counter("app_cron_runs_total",
                            "cron job runs by job name and result")
        # async-task discipline (ISSUE 5 / graftcheck GT002): every
        # fire-and-forget spawn goes through gofr_tpu.aio.spawn_logged,
        # which counts tasks that died with an escaped exception here —
        # a crashed subscriber/serve/cron loop becomes a dashboard line
        metrics.new_counter(
            "app_async_task_failures_total",
            "background asyncio tasks that died with an escaped "
            "exception, by task name")
        # async inference lane (ISSUE 11): pub/sub batch generation jobs
        # into the WFQ batch class — job outcomes, host-side in-flight
        # bound, and whether backpressure currently has the lane paused
        metrics.new_counter(
            "app_tpu_batch_lane_jobs_total",
            "batch-lane jobs by outcome (ok|dead_letter) — a dead_letter "
            "is a committed job whose error envelope went to the "
            "dead-letter topic")
        metrics.new_gauge(
            "app_tpu_batch_lane_inflight",
            "batch-lane jobs currently generating, per topic (bounded by "
            "BATCH_LANE_MAX_INFLIGHT)")
        metrics.new_gauge(
            "app_tpu_batch_lane_paused",
            "1 while backpressure has the lane's consumer paused, per "
            "topic")

    # -- outbound services (container.go:150-152) ---------------------------
    def add_http_service(self, name: str, service: Any) -> None:
        self.services[name] = service

    def get_http_service(self, name: str) -> Any:
        return self.services.get(name)

    # -- aggregated health (container/health.go:8-66) -----------------------
    def health(self) -> Dict[str, Any]:
        details: Dict[str, Any] = {
            "name": self.app_name,
            "version": self.app_version,
            "framework": FRAMEWORK_VERSION,
            "uptime_seconds": round(time.time() - self._start_time, 3),
        }
        statuses = []
        for name in ("sql", "redis", "pubsub", "mongo", "cassandra",
                     "clickhouse", "tpu", "cluster"):
            source = getattr(self, name)
            if source is None:
                continue
            try:
                health = source.health_check()
            except Exception as exc:
                health = {"status": "DOWN", "details": {"error": repr(exc)}}
            details[name] = health
            statuses.append(health.get("status", "DOWN"))
        for name, service in self.services.items():
            try:
                health = service.health_check()
            except Exception as exc:
                health = {"status": "DOWN", "details": {"error": repr(exc)}}
            details.setdefault("services", {})[name] = health
            statuses.append(health.get("status", "DOWN"))
        details["status"] = "DEGRADED" if "DOWN" in statuses else "UP"
        # SLO watchdog override: a replica whose rolling-window attainment
        # or p99 TTFT crossed its thresholds reports DEGRADED so load
        # balancers drain it even while every datasource is UP
        if self.watchdog is not None:
            details["watchdog"] = self.watchdog.statusz()
            if self.watchdog.state == "DEGRADED":
                details["status"] = "DEGRADED"
        return details

    async def close(self) -> None:
        for name in ("sql", "redis", "pubsub", "tpu"):
            source = getattr(self, name)
            closer = getattr(source, "close", None)
            if closer is not None:
                try:
                    result = closer()
                    if hasattr(result, "__await__"):
                        await result
                except Exception:
                    pass
        # flush spans finished during shutdown (tracer.shutdown drains the
        # export queue before closing the exporter)
        try:
            self.tracer.shutdown()
        except Exception:
            pass


def new_mock_container(config: Optional[Dict[str, str]] = None) -> Container:
    """One-call test fixture: silent logger + in-memory everything
    (reference: container/mock_container.go:21-42 ``NewMockContainer``)."""
    container = Container(config=MapConfig(config or {}),
                         logger=new_silent_logger())
    container.register_framework_metrics()
    from gofr_tpu.datasource.file import LocalFileSystem
    from gofr_tpu.datasource.pubsub.inmem import InMemoryBroker
    from gofr_tpu.datasource.redisx import InMemoryRedis
    from gofr_tpu.datasource.sql import new_sql
    container.pubsub = InMemoryBroker(container.logger, container.metrics,
                                      tracer=container.tracer)
    # unsandboxed: tests hand the fixture absolute tmp paths; production
    # Container.create keeps the sandboxed default
    container.file = LocalFileSystem(container.logger, sandbox=False)
    container.redis = InMemoryRedis(container.logger, container.metrics)
    container.sql = new_sql(MapConfig({"DB_DIALECT": "sqlite",
                                       "DB_NAME": ":memory:"}),
                            container.logger, container.metrics)
    return container
