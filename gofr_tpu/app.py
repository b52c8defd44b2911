"""App — the single object wiring every entry point of the framework.

Capability parity with ``pkg/gofr/gofr.go`` (``App`` 34-52, ``New`` 62-96,
``NewCMD`` 99-109, ``Run`` 112-190: metrics + HTTP + gRPC servers and
subscriber loops joined under one lifecycle; route verbs 222-244;
``Subscribe`` 392-400; ``AddCronJob`` 422-430; ``Migrate`` 270-275;
``AddRESTHandlers`` 402-413; WebSocket DSL websocket.go:18-35;
``SubCommand`` 266-268; default ports default.go:3-7).

Original design: one asyncio event loop owns all servers (the reference uses
one goroutine per server joined by a WaitGroup); handlers may be async or
plain ``def`` (thread-pooled). The TPU executor's dynamic batcher lives on
the same loop, so request coalescing is allocation-free.
"""

from __future__ import annotations

import asyncio
import os
import signal
from typing import Any, Callable, Dict, List, Optional, Sequence

from gofr_tpu.config import Config, EnvConfig
from gofr_tpu.container import Container
from gofr_tpu.context import Context
from gofr_tpu.cron import Crontab
from gofr_tpu.handler import (
    Handler,
    catch_all_handler,
    favicon_handler,
    live_handler,
    make_health_handler,
    wrap_handler,
)
from gofr_tpu.http.middleware import (
    api_key_auth_middleware,
    basic_auth_middleware,
    cors_middleware,
    logging_middleware,
    metrics_middleware,
    oauth_middleware,
    tracing_middleware,
)
from gofr_tpu.http.request import Request
from gofr_tpu.http.router import Router
from gofr_tpu.http.server import HTTPServer
from gofr_tpu.logging import new_file_logger
from gofr_tpu.metrics.exposition import render_prometheus
from gofr_tpu.metrics.manager import system_metrics_refresh

DEFAULT_HTTP_PORT = 8000   # reference: default.go:3-7
DEFAULT_GRPC_PORT = 9000
DEFAULT_METRICS_PORT = 2121


class App:
    def __init__(self, config: Optional[Config] = None,
                 container: Optional[Container] = None):
        self.config: Config = config if config is not None else EnvConfig()
        self.container: Container = (
            container if container is not None
            else Container.create(self.config)
        )
        self.logger = self.container.logger
        self.router = Router()
        self.crontab = Crontab(self.container)
        self._subscriptions: Dict[str, Handler] = {}
        self._websocket_routes: Dict[str, Handler] = {}
        self._grpc_services: List[tuple] = []
        self._cli_commands: List[Any] = []
        self._request_timeout = self.config.get_float("REQUEST_TIMEOUT", 0.0)
        # How long stop() lets in-flight responses (incl. active SSE
        # generation streams) finish before force-closing their
        # connections. Operators serving long generations raise this.
        self._shutdown_grace = self.config.get_float(
            "SHUTDOWN_GRACE_PERIOD", 5.0)
        self.http_port = self.config.get_int("HTTP_PORT", DEFAULT_HTTP_PORT)
        self.grpc_port = self.config.get_int("GRPC_PORT", DEFAULT_GRPC_PORT)
        self.metrics_port = self.config.get_int("METRICS_PORT", DEFAULT_METRICS_PORT)
        self._http_server: Optional[HTTPServer] = None
        self._metrics_server: Optional[HTTPServer] = None
        self._grpc_server = None
        self._tasks: List[asyncio.Task] = []
        self._startup_hooks: List[Callable] = []
        self._shutdown_hooks: List[Callable] = []
        # debug-surface registry (ISSUE 18): every enable_* records its
        # path + one-line description here; /debug/ renders the index so
        # operators stop guessing endpoint names
        self._debug_surfaces: Dict[str, str] = {}
        self._shutdown: Optional[asyncio.Event] = None  # created in start()
        self._install_default_middleware()

    def on_startup(self, func: Callable) -> Callable:
        """Register a (possibly async) callable to run inside ``start()``
        before servers accept traffic — e.g. model warmup so the first
        request never pays a TPU compile. Returns ``func`` (decorator use)."""
        self._startup_hooks.append(func)
        return func

    def on_shutdown(self, func: Callable) -> Callable:
        """Register a (possibly async) callable to run first thing inside
        ``stop()``, while datasources are still open — e.g. logging the
        ``/debug/xlaz`` suggested bucket ladder so a run's observed traffic
        shape survives the process. Hook failures are logged, never raised
        (shutdown must finish). Returns ``func`` (decorator use)."""
        self._shutdown_hooks.append(func)
        return func

    # -- middleware chain (httpServer.go:24-30 order) -----------------------
    def _install_default_middleware(self) -> None:
        self.router.use_middleware(
            tracing_middleware(self.container.tracer),
            logging_middleware(self.logger),
            cors_middleware(self.config, self.router),
            metrics_middleware(self.container.metrics),
        )

    def use_middleware(self, *middlewares) -> None:
        self.router.use_middleware(*middlewares)

    # -- auth sugar (reference: EnableBasicAuth etc.) ----------------------
    def enable_basic_auth(self, users: Dict[str, str]) -> None:
        self.router.use_middleware(basic_auth_middleware(users=users))

    def enable_basic_auth_with_validator(self, validate: Callable) -> None:
        self.router.use_middleware(
            basic_auth_middleware(validate=validate, container=self.container))

    def enable_api_key_auth(self, *keys: str) -> None:
        self.router.use_middleware(api_key_auth_middleware(keys=keys))

    def enable_api_key_auth_with_validator(self, validate: Callable) -> None:
        self.router.use_middleware(
            api_key_auth_middleware(validate=validate, container=self.container))

    def enable_oauth(self, jwks_url: str, refresh_interval: float = 300.0) -> None:
        self.router.use_middleware(
            oauth_middleware(jwks_url=jwks_url, refresh_interval=refresh_interval))

    # -- route verbs (gofr.go:222-244) --------------------------------------
    def add_route(self, method: str, pattern: str, handler: Handler) -> None:
        wire = wrap_handler(handler, self.container,
                            timeout=self._request_timeout or None)
        self.router.add(method, pattern, wire)

    def get(self, pattern: str, handler: Handler) -> None:
        self.add_route("GET", pattern, handler)

    def post(self, pattern: str, handler: Handler) -> None:
        self.add_route("POST", pattern, handler)

    def put(self, pattern: str, handler: Handler) -> None:
        self.add_route("PUT", pattern, handler)

    def patch(self, pattern: str, handler: Handler) -> None:
        self.add_route("PATCH", pattern, handler)

    def delete(self, pattern: str, handler: Handler) -> None:
        self.add_route("DELETE", pattern, handler)

    def options(self, pattern: str, handler: Handler) -> None:
        self.add_route("OPTIONS", pattern, handler)

    def head(self, pattern: str, handler: Handler) -> None:
        self.add_route("HEAD", pattern, handler)

    def add_static_files(self, url_prefix: str, directory: str) -> None:
        self.router.add_static_files(url_prefix, directory)

    # -- CRUD scaffolding (gofr.go:402-413) --------------------------------
    def add_rest_handlers(self, entity_class: type) -> None:
        from gofr_tpu.crud import register_crud_routes
        register_crud_routes(self, entity_class)

    # -- pub/sub (gofr.go:392-400) ------------------------------------------
    def subscribe(self, topic: str, handler: Handler) -> None:
        if self.container.pubsub is None:
            self.logger.error(
                "subscribe(%r) ignored: no PUBSUB_BACKEND configured", topic)
            return
        self._subscriptions[topic] = handler

    # -- websocket DSL (websocket.go:18-35) ---------------------------------
    def websocket(self, pattern: str, handler: Handler) -> None:
        from gofr_tpu.websocket.upgrade import make_ws_route
        self.router.add("GET", pattern, make_ws_route(handler, self.container))

    # -- cron (gofr.go:422-430) ---------------------------------------------
    def add_cron_job(self, spec: str, name: str, func: Handler) -> None:
        self.crontab.add_job(spec, name, func)

    # -- migrations (gofr.go:270-275) ---------------------------------------
    def migrate(self, migrations: Dict[int, Any]) -> None:
        from gofr_tpu.migration import run_migrations
        try:
            run_migrations(self.container, migrations)
        except Exception as exc:
            self.logger.error("migration run failed: %r", exc)
            raise

    # -- gRPC (gofr.go:55-59 RegisterService) -------------------------------
    def register_grpc_service(self, register_fn: Callable, servicer: Any) -> None:
        """``register_fn`` is the protoc-generated ``add_*Servicer_to_server``;
        ``servicer`` the implementation."""
        self._grpc_services.append((register_fn, servicer))

    def register_grpc_unary(self, service: str, method: str,
                            handler: Handler) -> None:
        """Register a dynamic JSON unary RPC without protoc (original to this
        framework; see gofr_tpu/grpcx)."""
        self._grpc_services.append((("dynamic", service, method), handler))

    def register_grpc_stream(self, service: str, method: str,
                             handler: Handler) -> None:
        """Register a dynamic JSON server-streaming RPC: the handler returns
        an async iterator and each item is sent as its own message — the
        token-streaming serve surface (BASELINE.md config 3)."""
        self._grpc_services.append(
            (("dynamic_stream", service, method), handler))

    # -- CLI mode (gofr.go:266-268, cmd.go) ---------------------------------
    def sub_command(self, pattern: str, handler: Handler,
                    description: str = "", help_text: str = "") -> None:
        from gofr_tpu.cli.command import CLICommand
        self._cli_commands.append(
            CLICommand(pattern, handler, description, help_text))

    # -- profiler (no reference analog; profiler.py) ------------------------
    def enable_profiler(self, prefix: str = "/debug/profiler") -> None:
        from gofr_tpu.profiler import enable_profiler
        enable_profiler(self, prefix)
        self._note_debug_surface(
            prefix, "on-demand single-flight device trace capture")

    # -- flight recorder statusz (no reference analog; statusz.py) ----------
    def enable_statusz(self, prefix: str = "/debug/statusz") -> None:
        from gofr_tpu.statusz import enable_statusz
        enable_statusz(self, prefix)
        self._note_debug_surface(
            prefix, "live serving state: queues, slots, flight records, "
                    "watchdog, KV occupancy")

    # -- SLO/saturation varz (no reference analog; varz.py) -----------------
    def enable_varz(self, prefix: str = "/debug/varz") -> None:
        from gofr_tpu.varz import enable_varz
        enable_varz(self, prefix)
        self._note_debug_surface(
            prefix, "windowed SLO attainment, goodput, and device "
                    "saturation rates")

    # -- compile/shape-plane xlaz (no reference analog; xlaz.py) ------------
    def enable_xlaz(self, prefix: str = "/debug/xlaz") -> None:
        from gofr_tpu.xlaz import enable_xlaz
        enable_xlaz(self, prefix)
        self._note_debug_surface(
            prefix, "compile ledger, bucket ladders, and padding-optimal "
                    "ladder suggestions")

    # -- fleet rollup clusterz (no reference analog; clusterz.py) -----------
    def enable_clusterz(self, prefix: str = "/debug/clusterz") -> None:
        from gofr_tpu.clusterz import enable_clusterz
        enable_clusterz(self, prefix)
        self._note_debug_surface(
            prefix, "fleet rollup: per-replica health, per-role "
                    "aggregates, router stats")

    # -- cross-replica trace stitching (clusterz.py) ------------------------
    def enable_tracez(self, prefix: str = "/debug/tracez") -> None:
        from gofr_tpu.clusterz import enable_tracez
        enable_tracez(self, prefix)
        self._note_debug_surface(
            f"{prefix}/{{trace_id}}",
            "cross-replica stitched timeline for one trace id")

    # -- HBM attribution hbmz (no reference analog; hbmz.py) ----------------
    def enable_hbmz(self, prefix: str = "/debug/hbmz") -> None:
        from gofr_tpu.hbmz import enable_hbmz
        enable_hbmz(self, prefix)
        self._note_debug_surface(
            prefix, "HBM attribution: per-tenant KV pages, pools, "
                    "residual accounting")

    # -- time-series telemetry timez (no reference analog; timez.py) --------
    def enable_timez(self, prefix: str = "/debug/timez") -> None:
        from gofr_tpu.timez import enable_timez
        enable_timez(self, prefix)
        self._note_debug_surface(
            prefix, "multi-resolution time series, anomalies, and "
                    "sampled tick anatomy")

    # -- workload capture workloadz (no reference analog; workloadz.py) -----
    def enable_workloadz(self, prefix: str = "/debug/workloadz") -> None:
        from gofr_tpu.workloadz import enable_workloadz
        enable_workloadz(self, prefix)
        self._note_debug_surface(
            prefix, "shape-only workload capture and per-executable "
                    "roofline attribution")

    # -- error-budget burn rates sloz (ISSUE 18; sloz.py) -------------------
    def enable_sloz(self, prefix: str = "/debug/sloz") -> None:
        from gofr_tpu.sloz import enable_sloz
        enable_sloz(self, prefix)
        self._note_debug_surface(
            prefix, "error-budget burn rates per (model, SLO class) and "
                    "the worst-offender ring")

    # -- auto-tuner decision plane tunez (ISSUE 19; tunez.py) ---------------
    def enable_tunez(self, prefix: str = "/debug/tunez") -> None:
        from gofr_tpu.tunez import enable_tunez
        enable_tunez(self, prefix)
        self._note_debug_surface(
            prefix, "live operating point with provenance, candidate "
                    "ledger, and auto-tuner guard states")

    # -- slow-request diagnosis whyz (ISSUE 18; whyz.py) --------------------
    def enable_whyz(self, prefix: str = "/debug/whyz") -> None:
        from gofr_tpu.whyz import enable_whyz
        enable_whyz(self, prefix)
        self._note_debug_surface(
            f"{prefix}/{{trace_id}}",
            "automated root-cause verdicts for one slow request")

    # -- debug index (ISSUE 18): every enabled surface on one page ----------
    def _note_debug_surface(self, path: str, description: str) -> None:
        self._debug_surfaces[path] = description
        routes = set(self.router.registered_routes)
        if "GET /debug/" not in routes:
            self.get("/debug/", lambda ctx: self.debug_index())

    def debug_index(self) -> Dict[str, str]:
        """The ``/debug/`` index payload: every enabled debug surface
        with its one-line description, sorted by path."""
        return {path: self._debug_surfaces[path]
                for path in sorted(self._debug_surfaces)}

    # -- external DB injection (externalDB.go:5-39) -------------------------
    def add_mongo(self, client=None) -> None:
        if client is None:
            from gofr_tpu.datasource.mongo import new_mongo
            client = new_mongo(self.config, self.logger,
                               self.container.metrics)
        self.container.mongo = client

    def add_cassandra(self, client=None) -> None:
        if client is None:
            from gofr_tpu.datasource.nosql import new_cassandra
            client = new_cassandra(self.config, self.logger,
                                   self.container.metrics)
        self.container.cassandra = client

    def add_clickhouse(self, client=None) -> None:
        if client is None:
            from gofr_tpu.datasource.nosql import new_clickhouse
            client = new_clickhouse(self.config, self.logger,
                                    self.container.metrics)
        self.container.clickhouse = client

    # -- outbound services (gofr.go AddHTTPService) -------------------------
    def add_http_service(self, name: str, base_url: str, *options,
                         timeout: float = 30.0) -> None:
        from gofr_tpu.service import new_http_service
        service = new_http_service(
            base_url, self.logger, self.container.metrics,
            self.container.tracer, *options, timeout=timeout,
            service_name=name)
        self.container.add_http_service(name, service)

    # -- TPU model registration (north star) --------------------------------
    def add_model(self, name: str, fn, params=None, **kwargs) -> None:
        """Register a servable model (``fn(params, batch)``) with the
        container's TPU executor, creating the executor on first use."""
        if self.container.tpu is None:
            from gofr_tpu.tpu import new_executor
            self.container.tpu = new_executor(self.config, self.logger,
                                              self.container.metrics)
        self.container.tpu.register(name, fn, params, **kwargs)

    # -- dispatch -----------------------------------------------------------
    async def _dispatch(self, request: Request):
        handler, params, other_method, template = self.router.lookup(
            request.method, request.path)
        request.route = template
        if handler is None:
            if other_method:
                from gofr_tpu.http.errors import MethodNotAllowed
                from gofr_tpu.http.responder import Responder
                wire = self.router.wrap(
                    lambda req: _error_response(MethodNotAllowed()))
                return await wire(request)
            wire = self.router.wrap(catch_all_handler)
            return await wire(request)
        request.path_params = params
        return await self.router.wrap(handler)(request)

    def _register_default_routes(self) -> None:
        """/.well-known + favicon + openapi (gofr.go:133-146)."""
        routes = set(self.router.registered_routes)
        if "GET /.well-known/health" not in routes:
            self.router.add("GET", "/.well-known/health",
                            make_health_handler(self.container))
        if "GET /.well-known/alive" not in routes:
            self.router.add("GET", "/.well-known/alive", live_handler)
        if "GET /favicon.ico" not in routes:
            self.router.add("GET", "/favicon.ico", favicon_handler)
        openapi_path = os.path.join("static", "openapi.json")
        if os.path.isfile(openapi_path):
            from gofr_tpu.openapi import make_openapi_handlers
            spec_handler, ui_handler, asset_handler = \
                make_openapi_handlers(openapi_path)
            self.router.add("GET", "/.well-known/openapi.json", spec_handler)
            self.router.add("GET", "/.well-known/swagger", ui_handler)
            self.router.add("GET", "/.well-known/swagger/{asset}",
                            asset_handler)

    async def _metrics_dispatch(self, request: Request):
        if request.path in ("/metrics", "/"):
            system_metrics_refresh(self.container.metrics,
                                   self.container.app_name,
                                   self.container.app_version)
            # windowed SLO rates + device saturation refresh per scrape,
            # same idiom as the runtime gauges above
            self.container.slo.export_gauges()
            if self.container.tpu is not None \
                    and hasattr(self.container.tpu, "saturation"):
                try:
                    self.container.tpu.saturation()
                except Exception as exc:
                    self.logger.error("saturation refresh failed: %r", exc)
            body = render_prometheus(self.container.metrics).encode()
            return 200, {"Content-Type": "text/plain; version=0.0.4"}, body
        return 404, {}, b"not found"

    # -- subscriber loops (subscriber.go:27-57) -----------------------------
    async def _subscriber_loop(self, topic: str, handler: Handler) -> None:
        pubsub = self.container.pubsub
        while True:
            try:
                message = await pubsub.subscribe(topic)
            except asyncio.CancelledError:
                raise
            except Exception as exc:
                self.logger.error("subscriber %s receive error: %r", topic, exc)
                await asyncio.sleep(1.0)
                continue
            if message is None:
                return
            ctx = Context(message, self.container)
            # continue the publisher's trace when the broker carried a
            # traceparent header (kafka envelope / inmem metadata)
            from gofr_tpu.trace import extract_traceparent
            remote = None
            try:
                remote = extract_traceparent(
                    message.header("traceparent") or "")
            except Exception:
                remote = None
            with self.container.tracer.start_span(
                    "pubsub.consume", remote_parent=remote) as span:
                span.set_attribute("topic", topic)
                try:
                    result = handler(ctx)
                    if asyncio.iscoroutine(result):
                        await result
                    message.commit()  # commit-on-success (subscriber.go:51-53)
                except asyncio.CancelledError:
                    raise
                except Exception as exc:
                    self.logger.error(
                        "subscriber %s handler panicked: %r", topic, exc)

    # -- lifecycle (gofr.go:112-190) ----------------------------------------
    async def start(self) -> None:
        self._shutdown = asyncio.Event()
        self._register_default_routes()

        for hook in self._startup_hooks:
            result = hook()
            if asyncio.iscoroutine(result):
                await result

        # workload capture plane (ISSUE 17): bounded shape-only traffic
        # recorder (TRAFFIC_REC_ENABLED, default on) feeding
        # /debug/workloadz and the replay harness (tpu/workload.py).
        # Built before the batcher so the enqueue hook can ride its
        # constructor; the engine admission hook attaches via
        # attach_workload.
        from gofr_tpu.tpu.workload import new_traffic_recorder
        self.container.workload = new_traffic_recorder(
            self.config, metrics=self.container.metrics)
        if (self.container.workload is not None
                and self.container.tpu is not None
                and hasattr(self.container.tpu, "attach_workload")):
            self.container.tpu.attach_workload(self.container.workload)

        # dynamic batcher on the serving loop (north star: coalesce
        # concurrent requests into one XLA execute)
        if self.container.tpu is not None:
            from gofr_tpu.tpu import DynamicBatcher
            self.container.tpu_batcher = DynamicBatcher(
                self.container.tpu,
                max_batch=self.config.get_int("TPU_MAX_BATCH", 32),
                max_delay_ms=self.config.get_float("TPU_BATCH_DELAY_MS", 2.0),
                logger=self.logger, tracer=self.container.tracer,
                slo=self.container.slo, metrics=self.container.metrics,
                workload=self.container.workload)

        # chaos plane (ISSUE 14): FAULT_PLAN installs a seeded
        # fault-injection plan over the serving layers' named sites.
        # Unset (the production default) leaves the no-op singleton — the
        # injection sites cost one attribute load plus a dict miss.
        from gofr_tpu.tpu import faults
        plan = faults.plan_from_env(metrics=self.container.metrics)
        if plan is not None:
            faults.install(plan)
            self.logger.warn("fault injection ACTIVE: FAULT_PLAN=%r "
                             "(seed %d)", os.environ.get("FAULT_PLAN"),
                             plan.seed)

        # continuous telemetry plane (ISSUE 16): bounded time-series
        # store + sampler over the serving signals (TELEMETRY_ENABLED,
        # default on). Built before the watchdog so the change-point
        # detector can feed it a health signal; the engine's sampled
        # tick anatomy attaches to the same store.
        from gofr_tpu.metrics.timeseries import new_timeseries
        self.container.telemetry = new_timeseries(
            self.config, slo=self.container.slo, tpu=self.container.tpu,
            container=self.container, metrics=self.container.metrics,
            logger=self.logger)
        if self.container.telemetry is not None:
            if self.container.tpu is not None and \
                    hasattr(self.container.tpu, "attach_telemetry"):
                self.container.tpu.attach_telemetry(
                    self.container.telemetry,
                    every=self.container.telemetry.tick_sample)
            self.container.telemetry.start()

        # degradation watchdog over the SLO rolling windows (slo.py);
        # SLO_WATCHDOG_ENABLED=false opts out entirely. The executor's
        # compile ledger (when present) feeds its recompile-storm signal.
        from gofr_tpu.slo import new_brownout, new_watchdog
        self.container.watchdog = new_watchdog(
            self.config, self.container.slo, metrics=self.container.metrics,
            logger=self.logger,
            ledger=getattr(self.container.tpu, "ledger", None))
        if self.container.watchdog is not None:
            if self.container.telemetry is not None:
                # watch-listed telemetry anomalies (goodput cliff,
                # padding spike) become named watchdog reasons
                self.container.watchdog.anomaly_fn = \
                    self.container.telemetry.watchdog_reasons
            # brownout ladder (ISSUE 14): graduated shedding fed by the
            # watchdog's evaluations, enforced by the engine — only wired
            # when the serving engine can actually act on a level
            self.container.watchdog.brownout = new_brownout(
                self.config, self.container.tpu,
                metrics=self.container.metrics, logger=self.logger)

        # error-budget burn-rate plane (ISSUE 18): multi-window burn
        # evaluation differencing the labelled app_tpu_slo_total series
        # through the telemetry store. Feeds the watchdog (DEGRADED
        # names the burning class/window) and gates brownout escalation
        # on a fast window actually burning.
        from gofr_tpu.slo_budget import new_error_budget
        self.container.slo_budget = new_error_budget(
            self.config, self.container.telemetry, self.container.metrics,
            logger=self.logger)
        if self.container.slo_budget is not None \
                and self.container.watchdog is not None:
            self.container.watchdog.budget_fn = \
                self.container.slo_budget.watchdog_reasons
            if self.container.watchdog.brownout is not None:
                self.container.watchdog.brownout.escalation_gate = \
                    self.container.slo_budget.fast_burning
        if self.container.slo_budget is not None \
                and self.container.tpu is not None \
                and hasattr(self.container.tpu, "stats"):
            # same attachment pattern as telemetry: the in-proc cluster
            # probe reads the engine, so the fleet rollup sees burn rates
            self.container.tpu.slo_budget = self.container.slo_budget
        if self.container.watchdog is not None:
            self.container.watchdog.start()

        # online operating-point auto-tuner (ISSUE 19): cron-driven
        # controller that retunes the engine's serving knobs from live
        # signals + shadow replay of the recorded workload. Opt-in
        # (AUTOTUNE_ENABLED, default off) and built after the budget
        # plane so its fast-burn standoff gate can be wired.
        from gofr_tpu.tpu.autotune import new_autotuner
        self.container.autotune = new_autotuner(
            self.config, self.container.tpu,
            workload=self.container.workload,
            telemetry=self.container.telemetry,
            metrics=self.container.metrics, logger=self.logger,
            fast_burn_fn=(self.container.slo_budget.fast_burning
                          if self.container.slo_budget is not None
                          else None))
        if self.container.autotune is not None:
            self.add_cron_job(
                self.config.get("AUTOTUNE_CRON") or "* * * * *",
                "autotune", self.container.autotune)

        # worst-offender ring (ISSUE 18): top-K slowest requests per
        # window, diagnosed at finish time against the live window
        # context — attached to every flight recorder the serving layer
        # wired (engine, or registry of engines).
        from gofr_tpu.tpu.diagnose import build_window_context, new_offenders
        tpu = self.container.tpu
        engine = tpu if tpu is not None and hasattr(tpu, "stats") else None
        ledger = getattr(tpu, "ledger", None) if tpu is not None else None
        xledger = getattr(tpu, "exec_ledger", None) if tpu is not None \
            else None
        context_fn = (lambda: build_window_context(
            engine=engine, store=self.container.telemetry,
            ledger=ledger, xledger=xledger))
        self.container.offenders = new_offenders(
            self.config, context_fn=context_fn, logger=self.logger)
        if self.container.offenders is not None and tpu is not None:
            recorders = []
            if getattr(tpu, "recorder", None) is not None:
                recorders.append(tpu.recorder)
            else:
                for entry in (getattr(tpu, "_entries", None) or {}).values():
                    recorder = getattr(entry.engine, "recorder", None)
                    if recorder is not None:
                        recorders.append(recorder)
            for recorder in recorders:
                recorder.offenders = self.container.offenders

        # async inference lane (ISSUE 11): BATCH_LANE_TOPIC turns the
        # pub/sub broker into a generation-job source feeding the WFQ
        # batch class. An app may pre-wire container.batch_lane itself
        # (e.g. to attach tokenizer encode/decode hooks) — then this only
        # starts it; otherwise the lane is built from config here, after
        # the watchdog exists so backpressure can see DEGRADED.
        if self.container.batch_lane is None \
                and self.config.get("BATCH_LANE_TOPIC") \
                and self.container.pubsub is not None \
                and self.container.tpu is not None \
                and (hasattr(self.container.tpu, "generate")
                     or hasattr(self.container.tpu, "route")):
            from gofr_tpu.tpu.batch_lane import new_batch_lane
            self.container.batch_lane = new_batch_lane(
                self.config, self.container.tpu, self.container)
        if self.container.batch_lane is not None:
            if getattr(self.container.batch_lane, "watchdog", None) is None:
                self.container.batch_lane.watchdog = self.container.watchdog
            await self.container.batch_lane.start()

        self._metrics_server = HTTPServer(
            self._metrics_dispatch, self.metrics_port, logger=self.logger)
        await self._metrics_server.start()

        self._http_server = HTTPServer(
            self._dispatch, self.http_port, logger=self.logger)
        await self._http_server.start()

        if self._grpc_services:
            from gofr_tpu.grpcx.server import GRPCServer
            self._grpc_server = GRPCServer(
                self.container, self.grpc_port, logger=self.logger)
            for spec, servicer in self._grpc_services:
                self._grpc_server.register(spec, servicer)
            await self._grpc_server.start()

        from gofr_tpu.aio import spawn_logged
        for topic, handler in self._subscriptions.items():
            self._tasks.append(spawn_logged(
                self._subscriber_loop(topic, handler), self.logger,
                f"pubsub.subscriber.{topic}",
                metrics=self.container.metrics))

        self.crontab.start()
        self.logger.info("app %s started (http=:%d metrics=:%d%s)",
                         self.container.app_name, self.http_port,
                         self.metrics_port,
                         f" grpc=:{self.grpc_port}" if self._grpc_server else "")

    async def stop(self) -> None:
        for hook in self._shutdown_hooks:
            try:
                result = hook()
                if asyncio.iscoroutine(result):
                    await result
            except Exception as exc:
                self.logger.error("shutdown hook failed: %r", exc)
        self.crontab.stop()
        if self.container.batch_lane is not None:
            # stop pulling jobs and let in-flight generations land before
            # the engines underneath them shut down
            await self.container.batch_lane.stop(
                grace_s=self._shutdown_grace)
        if self.container.watchdog is not None:
            await self.container.watchdog.stop()
        if self.container.telemetry is not None:
            await self.container.telemetry.stop()
        for task in self._tasks:
            task.cancel()
        self._tasks.clear()
        if self._http_server is not None:
            await self._http_server.shutdown(drain_grace=self._shutdown_grace)
        if self._metrics_server is not None:
            await self._metrics_server.shutdown()
        if self._grpc_server is not None:
            await self._grpc_server.stop()
        await self.container.close()
        if self._shutdown is not None:
            self._shutdown.set()

    async def serve(self) -> None:
        await self.start()
        loop = asyncio.get_running_loop()
        stop_requested = asyncio.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop_requested.set)
            except (NotImplementedError, RuntimeError):
                pass
        await stop_requested.wait()
        self.logger.info("shutdown signal received")
        await self.stop()

    def run(self) -> None:
        """Blocking entry point (gofr.go:112). CLI apps dispatch to the
        command router instead (cmd.go:32-72)."""
        if self._cli_commands:
            from gofr_tpu.cli.runner import run_cli
            run_cli(self)
            return
        try:
            asyncio.run(self.serve())
        except KeyboardInterrupt:
            pass

    # test helper: bound ports after start()
    @property
    def bound_http_port(self) -> int:
        return self._http_server.bound_port if self._http_server else self.http_port


async def _error_response(error):
    from gofr_tpu.http.responder import Responder
    return Responder().respond(None, error, "GET")


def new_app(config_dir: str = "./configs") -> App:
    """Server app factory (reference: gofr.go:62-96 ``New``)."""
    return App(config=EnvConfig(config_dir))


def new_cmd(config_dir: str = "./configs") -> App:
    """CLI app factory: logs to file so stdout stays clean for command output
    (reference: gofr.go:99-109 ``NewCMD``)."""
    config = EnvConfig(config_dir)
    log_file = config.get_or_default("CMD_LOGS_FILE", "")
    container = Container.create(
        config, logger=new_file_logger(log_file) if log_file else None)
    app = App(config=config, container=container)
    return app
