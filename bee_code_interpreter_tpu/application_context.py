"""Composition root: wires config → storage → executors → servers.

Same role and shape as the reference's ApplicationContext
(application_context.py:36-125): lazy ``cached_property`` singletons, logging
dictConfig + request-id filter installed at construction, pod-pool warmup
kicked off on first access to the Kubernetes executor.
"""

from __future__ import annotations

import asyncio
import logging.config
from functools import cached_property

from bee_code_interpreter_tpu.config import Config
from bee_code_interpreter_tpu.observability import (
    ContinuousProfiler,
    DemandTracker,
    DeviceMonitor,
    DeviceProfiler,
    FleetJournal,
    FlightRecorder,
    Forecaster,
    LoopMonitor,
    ServingMonitor,
    ServingProfiler,
    SloEngine,
    TelemetryExporter,
    Tracer,
    TraceStore,
    parse_objectives,
)
from bee_code_interpreter_tpu.services.custom_tool_executor import CustomToolExecutor
from bee_code_interpreter_tpu.services.storage import Storage
from bee_code_interpreter_tpu.utils.metrics import Registry
from bee_code_interpreter_tpu.utils.request_id import install_request_id_filter


class _LazyAdmission:
    """Late-binding admission handle for the quota-lease client: the real
    AdmissionController is a cached_property that itself consumes the lease
    cache, so the client (constructed first) must not materialize it."""

    def __init__(self, ctx: "ApplicationContext") -> None:
        self._ctx = ctx

    def quota_tenants(self) -> list[str]:
        return self._ctx.admission.quota_tenants()


class ApplicationContext:
    def __init__(self, config: Config | None = None) -> None:
        self.config = config or Config.from_env()
        # resolved config applies APP_LOG_FORMAT=json (structured one-line
        # records); the request-id filter also stamps trace/span ids now.
        logging.config.dictConfig(self.config.resolved_logging_config())
        install_request_id_filter()
        self.metrics = Registry()
        # Tenant-label cardinality bound (docs/tenancy.md "Cardinality"):
        # applied before any tenant-labeled metric registers.
        self.metrics.bound_label(
            "tenant", self.config.metrics_max_tenant_labels
        )
        # Tenant table + usage meter shared by both edges (docs/tenancy.md).
        # Always constructed: with no APP_TENANTS declared, every request
        # shares one unlimited `default` tenant and behavior is unchanged —
        # but the bci_tenant_* surface exists from first scrape.
        from bee_code_interpreter_tpu.tenancy import TenantRegistry

        self.tenancy = TenantRegistry.from_config(
            self.config, metrics=self.metrics
        )
        # Fleet-wide quota leases (docs/tenancy.md "Fleet-wide tenancy"):
        # with APP_QUOTA_LEASE_URLS set, this replica's rate quotas become
        # leased slices of each tenant's FLEET-wide quota, refreshed from
        # the router tier in the background. The cache is constructed
        # eagerly (the admission gate reads it synchronously); the client
        # loop starts in start_observability / on demand. Unset: leasing
        # off, local quotas enforced in full — the pre-fleet behavior.
        self.quota_leases = None
        self.quota_lease_client = None
        if self.config.quota_lease_urls:
            from bee_code_interpreter_tpu.tenancy import (
                QuotaLeaseCache,
                QuotaLeaseClient,
            )

            self.quota_leases = QuotaLeaseCache()
            self.quota_lease_client = QuotaLeaseClient(
                self.quota_leases,
                # late-bound: self.admission is a cached_property that
                # itself consumes self.quota_leases
                _LazyAdmission(self),
                replica=self.config.replica_name
                or self.config.http_listen_addr,
                router_urls=[
                    u.strip()
                    for u in self.config.quota_lease_urls.split(",")
                    if u.strip()
                ],
                interval_s=self.config.quota_lease_interval_s,
                metrics=self.metrics,
            )
        # One tracer + retention store shared by both transports: a trace is
        # a service-level object, whichever edge rooted it.
        self.trace_store = TraceStore(
            max_traces=self.config.trace_max_traces,
            slowest_keep=self.config.trace_slowest_keep,
        )
        self.tracer = Tracer(store=self.trace_store, metrics=self.metrics)
        # One fleet journal for the whole service: the pool backend records
        # sandbox transitions into it; both transports serve it.
        self.fleet = FleetJournal(
            metrics=self.metrics, max_events=self.config.fleet_max_events
        )
        # Pool supervisor (resilience/supervisor.py): created with the pool
        # executor it reconciles, None for the pool-less local backend.
        self.supervisor = None
        # Capacity observability (docs/autoscaling.md): per-second demand
        # telemetry fed by the shared admission gate and the fleet journal,
        # and the forecaster over it. Constructed unconditionally (their
        # gauges must exist either way); the PoolAutoscaler consuming them
        # is created with the pool executor in _wrap_pool_executor (None
        # for the pool-less local backend).
        self.demand = DemandTracker(
            window_s=self.config.demand_window_s,
            spawn_samples=self.config.demand_spawn_samples,
            metrics=self.metrics,
        )
        self.fleet.add_sink(self.demand.on_fleet_event)
        self.forecaster = Forecaster(
            self.demand,
            alpha=self.config.demand_ewma_alpha,
            beta=self.config.demand_trend_beta,
            metrics=self.metrics,
        )
        self.autoscaler = None
        # SLO engine: objectives come from config (APP_SLO_AVAILABILITY /
        # APP_SLO_LATENCY_MS); with none declared it is inert and /v1/slo
        # answers honestly empty. Both edges record into the ONE engine.
        self.slo = SloEngine(
            parse_objectives(
                self.config.slo_availability, self.config.slo_latency_ms
            ),
            metrics=self.metrics,
            bucket_s=self.config.slo_window_bucket_s,
            # Per-tenant SLO slices share the tenant-label bound the
            # registry and usage meter use (docs/tenancy.md "Cardinality").
            max_tenants=self.config.metrics_max_tenant_labels,
        )
        # Flight recorder (docs/observability.md "Flight recorder"): ONE
        # canonical wide event per execution / session op / stream / loop
        # stall, fed by a tracer sink so both edges emit identically.
        self.flight = FlightRecorder(
            max_events=self.config.events_max,
            dir=self.config.events_dir,
            segment_bytes=self.config.events_segment_bytes,
            max_segments=self.config.events_segments,
            metrics=self.metrics,
        )
        self.tracer.add_sink(self.flight.record_trace)
        # Event-loop health: lag probe + stall detector (task-stack dumps
        # land in the flight recorder); started by __main__ with the loop.
        self.loopmon = LoopMonitor(
            interval_s=self.config.loop_lag_interval_s,
            stall_threshold_s=self.config.loop_lag_stall_s,
            recorder=self.flight,
            metrics=self.metrics,
        )
        # Continuous profiler: constructed unconditionally (its metric must
        # exist either way); the sampler thread starts only when enabled.
        self.contprof = ContinuousProfiler(
            hz=self.config.contprof_hz,
            window_s=self.config.contprof_window_s,
            max_windows=self.config.contprof_windows,
            active_trace_ids=self.tracer.active_trace_ids,
            metrics=self.metrics,
        )
        # Serving-engine deep observability (docs/observability.md "Serving
        # observability"): per-request lifecycle traces into the shared
        # trace store, kind="serving" wide events into the flight recorder,
        # a bounded step-record ring behind GET /v1/serving. Constructed
        # unconditionally (its metrics must exist either way); an engine
        # binds later via attach_serving_engine, which also arms the
        # serving profiler (POST /v1/profile target=serving answers 501
        # until then).
        self.serving = ServingMonitor(
            metrics=self.metrics,
            store=self.trace_store,
            recorder=self.flight,
            max_steps=self.config.serving_step_records,
            max_requests=self.config.serving_request_records,
        )
        self.serving_profiler = ServingProfiler(self.serving)
        # Accelerator observability (docs/observability.md "Accelerator
        # observability"): compile/retrace wide events + counters, the
        # device-memory sampler, per-mesh-shape step timing. Constructed
        # unconditionally (its metrics must exist either way) but inert:
        # a chip belongs to one process, and on the execute path that is
        # the sandbox child — so nothing here imports or initializes jax
        # until attach_serving_engine binds an in-process engine, whose
        # batcher hands the memory rows and the profiler trace in.
        self.device = DeviceMonitor(
            metrics=self.metrics,
            recorder=self.flight,
            sample_interval_s=self.config.device_sample_interval_s,
            max_compiles=self.config.device_compile_records,
        )
        # POST /v1/profile target=device: the attached engine's steps
        # under its own jax.profiler trace; 501 while none is attached.
        self.device_profiler = DeviceProfiler(self.serving)
        # Telemetry export: with APP_OTLP_ENDPOINT set, finished traces and
        # metric snapshots are pushed OTLP/JSON to the collector by a
        # background exporter (started by __main__ once the loop runs).
        self.exporter = None
        if self.config.otlp_endpoint:
            from bee_code_interpreter_tpu.resilience import RetryPolicy

            self.exporter = TelemetryExporter(
                self.config.otlp_endpoint,
                self.metrics,
                flush_interval_s=self.config.otlp_flush_interval_s,
                queue_max=self.config.otlp_queue_max,
                batch_max=self.config.otlp_batch_max,
                retry=RetryPolicy(
                    attempts=self.config.otlp_retry_attempts,
                    wait_min_s=self.config.otlp_retry_wait_min_s,
                    wait_max_s=self.config.otlp_retry_wait_max_s,
                ),
                timeout_s=self.config.otlp_timeout_s,
            )
            self.tracer.add_sink(self.exporter.enqueue_trace)
            # Wide events ride the logs signal through the same exporter
            # (drop-not-block queue, exact drop accounting).
            self.flight.add_sink(self.exporter.enqueue_log)

    @cached_property
    def storage(self) -> Storage:
        # Backend selected by APP_STORAGE_BACKEND (docs/fleet.md): local
        # replica-private directory by default, shared mounted volume or an
        # S3-shaped store when snapshots must resolve fleet-wide. The
        # backend's init sweep reaps crash-orphaned .tmp-* writer temps,
        # counted and logged once.
        return Storage.from_config(self.config)

    def start_storage_sweeper(self) -> asyncio.Task | None:
        """Periodic TTL sweep of stored objects when storage_max_age_s is set
        (must be called from a running loop; __main__ does)."""
        if self.config.storage_max_age_s is None:
            return None

        async def sweeper() -> None:
            log = logging.getLogger(__name__)
            while True:
                try:
                    removed = await self.storage.sweep(self.config.storage_max_age_s)
                    if removed:
                        log.info("Storage sweep removed %d expired objects", removed)
                except Exception:
                    log.exception("Storage sweep failed")
                await asyncio.sleep(self.config.storage_sweep_interval_s)

        self._storage_sweeper_task = asyncio.create_task(sweeper())
        return self._storage_sweeper_task

    def start_telemetry_exporter(self):
        """Start the background OTLP export loop when one is configured
        (must be called from a running loop; __main__ does)."""
        if self.exporter is not None:
            self.exporter.start()
        return self.exporter

    def start_observability(self) -> None:
        """Start the flight recorder's disk flusher (when a segment dir is
        configured), the event-loop lag probe, and the continuous profiler
        (must be called from a running loop; __main__ does)."""
        self.flight.start()
        self.loopmon.start()
        # the serving monitor's wide events must reach the recorder's loop
        # even when its hooks fire from a worker thread (profiler captures)
        # and the engine was attached before the loop existed
        self.serving.arm_loop()
        if self.config.device_monitor_enabled:
            # periodic device-memory sampler + compile-event loop binding
            self.device.start()
        else:
            self.device.arm_loop()
        if self.config.contprof_enabled:
            self.contprof.start()
        if self.quota_lease_client is not None:
            self.quota_lease_client.start()

    def attach_serving_engine(self, engine) -> None:
        """Bind a ``models.engine.Engine`` (or bare ``ContinuousBatcher``)
        to the serving monitor: per-request lifecycle traces/wide events
        start flowing, ``GET /v1/serving`` reports it, and ``POST
        /v1/profile`` target=serving captures real batcher steps instead of
        answering 501. Construct the engine with ``metrics=ctx.metrics`` so
        its aggregate gauges land in the same registry. The device monitor
        attaches too: the batcher's tracked jits start reporting compiles
        and its steps land in the per-mesh-shape aggregates."""
        self.serving.attach(engine)
        self.device.attach(engine)

    def autoscale_snapshot(self) -> dict:
        """The ``GET /v1/autoscale`` document both edges serve — demand
        telemetry, the forecast, and the autoscaler's target + decision log
        (null section for the pool-less local backend)."""
        from bee_code_interpreter_tpu.resilience import autoscale_snapshot

        return autoscale_snapshot(
            demand=self.demand,
            forecaster=self.forecaster,
            autoscaler=self.autoscaler,
        )

    def build_debug_bundle(self) -> dict:
        """The one-call incident snapshot both edges serve — built here so
        HTTP and gRPC can never disagree about what a bundle contains."""
        from bee_code_interpreter_tpu.observability import build_debug_bundle

        return build_debug_bundle(
            tracer=self.tracer,
            fleet=self.fleet,
            slo=self.slo,
            metrics=self.metrics,
            config=self.config,
            executor=self.__dict__.get("code_executor"),
            supervisor=self.supervisor,
            drain=self.drain,
            exporter=self.exporter,
            recorder=self.flight,
            loopmon=self.loopmon,
            contprof=self.contprof,
            serving=self.serving,
            device=self.device,
            autoscale=self.autoscale_snapshot,
            tenancy=self.tenancy,
        )

    @cached_property
    def drain(self):
        """Graceful-drain state shared by both transports and ``__main__``:
        one flag, one in-flight count, one grace wait for the whole service."""
        from bee_code_interpreter_tpu.resilience import DrainController

        return DrainController(
            metrics=self.metrics,
            retry_after_s=self.config.admission_retry_after_s,
        )

    def begin_drain(self) -> None:
        """Flip the service into draining mode (SIGTERM does this via
        ``__main__``): edges reject new work retryably, gRPC health goes
        NOT_SERVING, the supervisor stops replenishing the pool. In-flight
        executions keep running; await ``drain.wait_idle(grace)`` for them."""
        self.drain.begin()

    async def aclose(self) -> None:
        """Deterministic teardown for the drain path: stop the supervisor
        and storage sweeper, then close the executor backend (awaited —
        never a fire-and-forget task a dying loop can cancel)."""
        sweeper = getattr(self, "_storage_sweeper_task", None)
        if sweeper is not None:
            sweeper.cancel()
        if self.quota_lease_client is not None:
            await self.quota_lease_client.stop()
        sessions = self.__dict__.get("sessions")
        if sessions is not None:
            # Leases end BEFORE the executor closes: each teardown journals
            # its reason and returns the sandbox through the backend while
            # the backend is still alive to do it.
            await sessions.stop()
            await sessions.close_all("shutdown")
        if self.exporter is not None:
            # Final best-effort flush (retry-bounded) before teardown.
            await self.exporter.stop()
        self.contprof.stop()
        self.device.stop()
        await self.loopmon.stop()
        # After the exporter: its final flush may still have drained wide
        # events; the recorder's stop writes its own pending disk segment.
        await self.flight.stop()
        if self.supervisor is not None:
            await self.supervisor.stop()
        executor = self.__dict__.get("code_executor")
        if executor is not None:
            from bee_code_interpreter_tpu.observability import unwrap_executor

            backend = unwrap_executor(executor)
            aclose = getattr(backend, "aclose", None)
            if aclose is not None:
                await aclose()
            elif hasattr(backend, "shutdown"):
                backend.shutdown()
        storage = self.__dict__.get("storage")
        if storage is not None:
            # After the executor: snapshots may still move during teardown
            # (lease checkpoints). No-op for directory backends; closes the
            # s3 backend's HTTP client.
            await storage.aclose()

    def _wrap_pool_executor(self, executor):
        """Shared pool-backend wiring: the replay/hedge front, the
        SLO-aware predictive autoscaler (docs/autoscaling.md), and the pool
        supervisor (owned per executor; its loop starts only when one runs —
        mirroring the warmup deferral below)."""
        from bee_code_interpreter_tpu.resilience import (
            HedgingExecutor,
            PoolAutoscaler,
            PoolSupervisor,
        )

        cfg = self.config
        self.autoscaler = PoolAutoscaler(
            executor,
            self.forecaster,
            self.demand,
            mode=cfg.autoscale_mode,
            min_size=cfg.autoscale_min,
            max_size=cfg.autoscale_max,
            idle_s=cfg.autoscale_idle_s,
            cooldown_s=cfg.autoscale_cooldown_s,
            base_target=cfg.executor_pod_queue_target_length,
            slo=self.slo,
            recorder=self.flight,
            metrics=self.metrics,
        )
        self.supervisor = PoolSupervisor(
            executor,
            interval_s=cfg.supervisor_interval_s,
            execute_hard_cap_s=cfg.resolved_execution_hard_cap_s(),
            metrics=self.metrics,
            drain=self.drain,
            autoscaler=self.autoscaler,
        )
        if cfg.supervisor_interval_s > 0:
            try:
                asyncio.get_running_loop()
            except RuntimeError:
                pass
            else:
                self.supervisor.start()
        return HedgingExecutor(
            executor,
            replay_max=cfg.execution_replay_max,
            hedge_delay_s=cfg.hedge_delay_s,
            metrics=self.metrics,
        )

    @cached_property
    def sessions(self):
        """Session-lease manager shared by both transports
        (docs/sessions.md): one lease table, one expiry sweep, one cap for
        the whole service. Its background sweep starts with the first
        access inside a running loop (tests drive ``sweep_once`` by hand)."""
        from bee_code_interpreter_tpu.sessions import SessionManager

        cfg = self.config
        manager = SessionManager(
            self.code_executor,
            self.storage,
            max_sessions=cfg.session_max,
            ttl_s=cfg.session_ttl_s,
            idle_s=cfg.session_idle_s,
            sweep_interval_s=cfg.session_sweep_interval_s,
            drain_grace_s=cfg.session_drain_grace_s,
            retry_after_s=cfg.admission_retry_after_s,
            metrics=self.metrics,
            drain=self.drain,
            recorder=self.flight,
        )
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            pass
        else:
            manager.start()
        return manager

    @cached_property
    def analyzer(self):
        """Edge static-analysis gate shared by both transports (None when
        APP_ANALYSIS_ENABLED=false): one policy, one metrics surface, one
        dep-prediction behavior — the two edges can never disagree about
        what gets refused."""
        from bee_code_interpreter_tpu.analysis import WorkloadAnalyzer

        return WorkloadAnalyzer.from_config(self.config, metrics=self.metrics)

    @cached_property
    def admission(self):
        """Edge admission gate shared by the HTTP and gRPC servers: one
        in-flight/queue budget for the whole service, not per transport."""
        from bee_code_interpreter_tpu.resilience import AdmissionController

        return AdmissionController(
            max_in_flight=self.config.admission_max_in_flight,
            max_queue=self.config.admission_max_queue,
            retry_after_s=self.config.admission_retry_after_s,
            metrics=self.metrics,
            # The one chokepoint both transports share is also the demand
            # sensor: arrivals/sheds/queue-waits feed the capacity tracker.
            demand=self.demand,
            # Opt-in: the analyzer's cost_class hint bounds heavy work
            # (docs/analysis.md "Cost classes").
            cost_aware=self.config.admission_cost_aware,
            # Per-tenant WFQ + quotas (docs/tenancy.md): with no tenant
            # table declared this is one unlimited default lane.
            tenancy=self.tenancy,
            # Fleet-wide quota leases: rate refills consult the leased
            # slice (or its fail-safe 1/N fallback) when leasing is on.
            quota_leases=self.quota_leases,
        )

    def _build_local_executor(self):
        from bee_code_interpreter_tpu.services.local_code_executor import (
            LocalCodeExecutor,
        )

        return LocalCodeExecutor(
            storage=self.storage,
            workspace_root=self.config.local_workspace_root,
            disable_dep_install=self.config.disable_dep_install,
            execution_timeout_s=self.config.execution_timeout_s,
            shim_dir=self.config.resolved_shim_dir(),
        )

    @cached_property
    def code_executor(self):
        if self.config.executor_backend == "local":
            # With a native binary configured, sandboxes are real executor-server
            # processes (the single-TPU-VM deployment mode — full wire contract,
            # no cluster); otherwise the pure-Python in-process core.
            if self.config.local_executor_binary:
                from bee_code_interpreter_tpu.services.native_process_code_executor import (
                    NativeProcessCodeExecutor,
                )

                executor = NativeProcessCodeExecutor(
                    storage=self.storage,
                    config=self.config,
                    metrics=self.metrics,
                    journal=self.fleet,
                )
                self._register_pool_gauges(executor)
                try:
                    asyncio.get_running_loop()
                except RuntimeError:
                    pass
                else:
                    # anchored on the executor's task set (loop refs are weak)
                    executor._spawn_background(executor.fill_sandbox_queue())
                return self._wrap_pool_executor(executor)
            return self._build_local_executor()
        from bee_code_interpreter_tpu.resilience import ResilientCodeExecutor
        from bee_code_interpreter_tpu.services.kubectl import Kubectl
        from bee_code_interpreter_tpu.services.kubernetes_code_executor import (
            KubernetesCodeExecutor,
        )

        executor = KubernetesCodeExecutor(
            kubectl=Kubectl(kubectl_path=self.config.kubectl_path),
            storage=self.storage,
            config=self.config,
            metrics=self.metrics,
            journal=self.fleet,
        )
        self._register_pool_gauges(executor)
        self._register_breaker_gauges(executor)
        # Pool warmup starts as soon as the executor exists (reference
        # application_context.py:83). Outside a running loop (e.g. tests
        # constructing the context), warmup is deferred — the pool refills on
        # first use anyway.
        try:
            asyncio.get_running_loop()
        except RuntimeError:
            pass
        else:
            # anchored on the executor's task set (loop refs are weak)
            executor._spawn_background(executor.fill_executor_pod_queue())
        # Graceful degradation: with APP_FALLBACK_TO_LOCAL=true, requests are
        # served by the local in-process executor while the Kubernetes
        # backend's breaker is open (docs/resilience.md).
        fallback = self._build_local_executor() if self.config.fallback_to_local else None
        return ResilientCodeExecutor(
            primary=self._wrap_pool_executor(executor),
            fallback=fallback,
            metrics=self.metrics,
        )

    def _register_pool_gauges(self, executor) -> None:
        self.metrics.gauge(
            "bci_executor_pool_ready",
            "Warm executor sandboxes ready in the pool",
            lambda: executor.pool_ready_count,
        )
        self.metrics.gauge(
            "bci_executor_pool_spawning",
            "Executor sandboxes currently being spawned",
            lambda: executor.pool_spawning_count,
        )

    def _register_breaker_gauges(self, executor) -> None:
        for breaker in (executor.spawn_breaker, executor.http_breaker):
            self.metrics.gauge(
                "bci_breaker_state",
                "Circuit breaker state (0=closed, 1=open, 2=half-open)",
                (lambda b: lambda: int(b.state))(breaker),
                breaker=breaker.name,
            )

    @cached_property
    def custom_tool_executor(self) -> CustomToolExecutor:
        return CustomToolExecutor(code_executor=self.code_executor)

    @cached_property
    def http_server(self):
        from bee_code_interpreter_tpu.api.http_server import create_http_server

        return create_http_server(
            code_executor=self.code_executor,
            custom_tool_executor=self.custom_tool_executor,
            metrics=self.metrics,
            admission=self.admission,
            request_deadline_s=self.config.request_deadline_s,
            tracer=self.tracer,
            fleet=self.fleet,
            drain=self.drain,
            supervisor=self.supervisor,
            slo=self.slo,
            debug_bundle=self.build_debug_bundle,
            analyzer=self.analyzer,
            sessions=self.sessions,
            recorder=self.flight,
            loopmon=self.loopmon,
            contprof=self.contprof,
            serving=self.serving,
            profiler=self.serving_profiler,
            device=self.device,
            device_profiler=self.device_profiler,
            autoscale=self.autoscale_snapshot,
            tenancy=self.tenancy,
        )

    @cached_property
    def grpc_server(self):
        from bee_code_interpreter_tpu.api.grpc_server import GrpcServer

        return GrpcServer(
            code_executor=self.code_executor,
            custom_tool_executor=self.custom_tool_executor,
            tls_cert=self.config.grpc_tls_cert,
            tls_cert_key=self.config.grpc_tls_cert_key,
            tls_ca_cert=self.config.grpc_tls_ca_cert,
            admission=self.admission,
            request_deadline_s=self.config.request_deadline_s,
            metrics=self.metrics,
            tracer=self.tracer,
            fleet=self.fleet,
            drain=self.drain,
            slo=self.slo,
            debug_bundle=self.build_debug_bundle,
            analyzer=self.analyzer,
            sessions=self.sessions,
            recorder=self.flight,
            loopmon=self.loopmon,
            contprof=self.contprof,
            serving=self.serving,
            device=self.device,
            autoscale=self.autoscale_snapshot,
            tenancy=self.tenancy,
        )
