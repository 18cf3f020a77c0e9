"""Test doubles for the cluster seam (SURVEY.md §4: the fake kubectl / fake
executor the reference never had)."""

from __future__ import annotations

import asyncio
import json
import socket
from pathlib import Path

from aiohttp import web

from bee_code_interpreter_tpu.runtime.executor_core import ExecutorCore
from bee_code_interpreter_tpu.runtime.executor_server import create_app


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class FakeExecutorPods:
    """Real executor HTTP servers, one per simulated pod, each on its own
    loopback IP (127.1.0.x) sharing a single port — so the executor driver can
    address them exactly like pods on a pod network.

    Set ``self.faults`` to a ``tests.chaos.FaultPlan`` to inject scripted
    data-plane failures (5xx, hangs, connection resets) on the upload /
    execute / download routes — the deterministic chaos seam the resilience
    tests drive (tests/chaos.py)."""

    def __init__(
        self, workspace_root: Path, port: int | None = None, faults=None
    ) -> None:
        self.workspace_root = workspace_root
        self.port = port or free_port()
        self.faults = faults
        # Anchors fire-and-forget pod-kill tasks (the loop holds only weak
        # refs; an unanchored task can be GC-cancelled before it runs).
        self._background_tasks: set[asyncio.Task] = set()
        self._runners: dict[str, web.AppRunner] = {}
        self.cores: dict[str, ExecutorCore] = {}
        self.execute_counts: dict[str, int] = {}
        # data-plane requests by kind, over all pods: "execute", "upload"
        # (a file restored into a workspace), "download" (a changed file
        # snapshotted out of one)
        self.op_counts = {"execute": 0, "upload": 0, "download": 0}
        self._next_ip = 1

    async def start_pod(self, manifest: dict | None = None) -> str:
        ip = f"127.1.0.{self._next_ip}"
        self._next_ip += 1
        core = ExecutorCore(
            workspace=self.workspace_root / ip, disable_dep_install=True,
            default_timeout_s=30.0,
        )
        app = create_app(core)

        @web.middleware
        async def count_executes(request, handler):
            # /execute and its streaming twin /execute/stream both count.
            if request.path.startswith("/execute"):
                self.execute_counts[ip] = self.execute_counts.get(ip, 0) + 1
            return await handler(request)

        @web.middleware
        async def inject_faults(request, handler):
            op = None
            if request.path.startswith("/execute"):
                op = "execute"
            elif request.path.startswith("/workspace"):
                op = "upload" if request.method == "PUT" else "download"
            if op is not None:
                self.op_counts[op] += 1
                if self.faults is not None:
                    response = await self.faults.apply_http(
                        # kill lets DieMidExecute take this whole pod down,
                        # not just the one connection.
                        op, request, kill=lambda: self._kill_pod(ip)
                    )
                    if response is not None:
                        return response
            return await handler(request)

        app.middlewares.append(count_executes)
        app.middlewares.append(inject_faults)
        # Short shutdown grace: stop_pod()/close() must not wait out a
        # scripted Hang(...) still sleeping in a handler.
        runner = web.AppRunner(app, shutdown_timeout=0.1)
        await runner.setup()
        site = web.TCPSite(runner, ip, self.port)
        await site.start()
        self._runners[ip] = runner
        self.cores[ip] = core
        return ip

    def _kill_pod(self, ip: str) -> None:
        """Schedule a pod's death (DieMidExecute), anchored so GC cannot
        cancel the teardown before it runs."""
        task = asyncio.ensure_future(self.stop_pod(ip))
        self._background_tasks.add(task)
        task.add_done_callback(self._background_tasks.discard)

    async def stop_pod(self, ip: str) -> None:
        """Simulate preemption: the pod's server vanishes mid-pool."""
        runner = self._runners.pop(ip, None)
        if runner is not None:
            await runner.cleanup()

    async def close(self) -> None:
        for runner in self._runners.values():
            await runner.cleanup()


class FakeKubectl:
    """In-memory kubectl: create/get/wait/delete on pod manifests, backed by
    FakeExecutorPods for pod IPs."""

    def __init__(self, pods: FakeExecutorPods) -> None:
        self._backend = pods
        self.pods: dict[str, dict] = {}
        self.deleted: list[str] = []
        self.created_manifests: list[dict] = []
        self.fail_create_names: set[str] = set()  # pods whose creation errors
        self.fail_ready_names: set[str] = set()  # pods that never become Ready

    async def create(self, *args, _input=None, **kwargs):
        manifest = json.loads(_input)
        name = manifest["metadata"]["name"]
        self.created_manifests.append(manifest)
        if name in self.fail_create_names:
            raise RuntimeError(f"fake: create {name} failed")
        # Backends get the manifest so they can honor the container env the
        # control plane baked in (the full-stack distributed test applies it
        # to real server processes; most backends ignore it).
        ip = await self._backend.start_pod(manifest)
        self.pods[name] = {
            "metadata": manifest["metadata"],
            "spec": manifest["spec"],
            "status": {"podIP": ip, "phase": "Running"},
        }
        return self.pods[name]

    async def get(self, kind, name, **kwargs):
        assert kind == "pod"
        if name not in self.pods:
            raise RuntimeError(f"fake: pod {name} not found")
        return self.pods[name]

    async def wait(self, target, **kwargs):
        name = target.removeprefix("pod/")
        if name in self.fail_ready_names or name not in self.pods:
            raise RuntimeError(f"fake: pod {name} never Ready")
        return self.pods[name]

    async def delete(self, kind, name, **kwargs):
        self.deleted.append(name)
        self.pods.pop(name, None)
        return {}


class ReplicaStack:
    """One COMPLETE in-process replica for fleet-tier tests and chaos
    scenario 14 (docs/fleet.md): the real HTTP edge over the real
    KubernetesCodeExecutor against its own fake-pod cluster, with its own
    SessionManager / SLO engine / admission / drain — sharing a
    SharedDirectoryBackend snapshot root with its siblings, served on a
    real localhost socket. Production fleet shape minus kubectl.

    Imports are deferred to ``start()`` so importing tests.fakes stays
    cheap for the many suites that only want the fake cluster."""

    def __init__(
        self,
        name: str,
        tmp_path,
        shared_root,
        faults=None,
        tenants: str | None = None,
        lease_router_urls: list[str] | None = None,
        autoscale_window_s: float | None = None,
    ) -> None:
        self.name = name
        self.tmp_path = Path(tmp_path)
        self.shared_root = shared_root
        self.faults = faults
        self.tenants = tenants  # APP_TENANTS spec for this replica's edge
        # Fleet-wide quota leasing (docs/tenancy.md "Fleet-wide tenancy"):
        # router base URLs this replica leases rate-quota slices from.
        self.lease_router_urls = lease_router_urls
        # Capacity observability (docs/capacity.md): a short demand window
        # wires the DemandTracker/Forecaster pair into this replica's edge
        # so GET /v1/autoscale answers — short so chaos tests see the
        # recommendation converge in test-scale seconds, not 60s windows.
        self.autoscale_window_s = autoscale_window_s
        self.demand = None
        self.forecaster = None
        self.lease_client = None
        self.quota_leases = None
        self.stopped = False

    async def start(self) -> "ReplicaStack":
        from bee_code_interpreter_tpu.api.http_server import create_http_server
        from bee_code_interpreter_tpu.config import Config
        from bee_code_interpreter_tpu.observability import (
            FlightRecorder,
            SloEngine,
            Tracer,
            parse_objectives,
        )
        from bee_code_interpreter_tpu.resilience import (
            AdmissionController,
            DrainController,
        )
        from bee_code_interpreter_tpu.services.custom_tool_executor import (
            CustomToolExecutor,
        )
        from bee_code_interpreter_tpu.services.kubernetes_code_executor import (
            KubernetesCodeExecutor,
        )
        from bee_code_interpreter_tpu.services.storage import (
            SharedDirectoryBackend,
            Storage,
        )
        from bee_code_interpreter_tpu.sessions import SessionManager
        from bee_code_interpreter_tpu.utils.metrics import Registry

        self.pods = FakeExecutorPods(
            self.tmp_path / f"pods-{self.name}", faults=self.faults
        )
        self.storage = Storage(
            backend=SharedDirectoryBackend(self.shared_root)
        )
        config = Config(
            executor_backend="kubernetes",
            executor_port=self.pods.port,
            executor_pod_queue_target_length=1,
            pod_ready_timeout_s=5,
            executor_retry_attempts=1,
            session_drain_grace_s=30.0,
        )
        self.metrics = Registry()
        self.k8s = KubernetesCodeExecutor(
            kubectl=FakeKubectl(self.pods),
            storage=self.storage,
            config=config,
            metrics=self.metrics,
            ip_poll_interval_s=0.02,
        )
        await self.k8s.fill_executor_pod_queue()
        self.drain = DrainController()
        self.slo = SloEngine(parse_objectives(99.5, None), metrics=self.metrics)
        self.sessions = SessionManager(
            self.k8s,
            self.storage,
            max_sessions=4,
            ttl_s=120.0,
            idle_s=120.0,
            sweep_interval_s=0.2,
            drain_grace_s=30.0,
            drain=self.drain,
            metrics=self.metrics,
        )
        self.tenancy = None
        if self.tenants is not None:
            from bee_code_interpreter_tpu.tenancy import (
                TenantRegistry,
                parse_tenants,
            )

            self.tenancy = TenantRegistry(
                parse_tenants(self.tenants), metrics=self.metrics
            )
        if self.lease_router_urls:
            from bee_code_interpreter_tpu.tenancy import (
                QuotaLeaseCache,
                QuotaLeaseClient,
            )

            self.quota_leases = QuotaLeaseCache()
        autoscale = None
        if self.autoscale_window_s is not None:
            from bee_code_interpreter_tpu.observability import (
                DemandTracker,
                Forecaster,
            )
            from bee_code_interpreter_tpu.resilience.autoscaler import (
                autoscale_snapshot,
            )

            window = self.autoscale_window_s
            self.demand = DemandTracker(
                window_s=window, metrics=self.metrics
            )
            self.forecaster = Forecaster(
                self.demand,
                peak_window_s=min(window, 5.0),
                max_horizon_s=2.0,
                metrics=self.metrics,
            )
            self.k8s.journal.add_sink(self.demand.on_fleet_event)
            autoscale = lambda: autoscale_snapshot(  # noqa: E731
                demand=self.demand,
                forecaster=self.forecaster,
                slo=self.slo,
            )
        self.admission = AdmissionController(
            max_in_flight=8,
            max_queue=16,
            retry_after_s=0.2,
            metrics=self.metrics,
            tenancy=self.tenancy,
            quota_leases=self.quota_leases,
            demand=self.demand,
        )
        if self.lease_router_urls:
            self.lease_client = QuotaLeaseClient(
                self.quota_leases,
                self.admission,
                replica=self.name,
                router_urls=list(self.lease_router_urls),
                interval_s=0.2,
                metrics=self.metrics,
            )
            self.lease_client.start()
        self.recorder = FlightRecorder(max_events=4096, metrics=self.metrics)
        tracer = Tracer(metrics=self.metrics)
        tracer.add_sink(self.recorder.record_trace)
        app = create_http_server(
            code_executor=self.k8s,
            custom_tool_executor=CustomToolExecutor(code_executor=self.k8s),
            metrics=self.metrics,
            admission=self.admission,
            request_deadline_s=30.0,
            tracer=tracer,
            fleet=self.k8s.journal,
            drain=self.drain,
            slo=self.slo,
            sessions=self.sessions,
            tenancy=self.tenancy,
            recorder=self.recorder,
            autoscale=autoscale,
        )
        self.runner = web.AppRunner(app)
        await self.runner.setup()
        self.port = free_port()
        await web.TCPSite(self.runner, "127.0.0.1", self.port).start()
        self.base_url = f"http://127.0.0.1:{self.port}"
        return self

    async def stop(self, hard: bool = False) -> None:
        """``hard=True`` is the replica-kill: listener and backend torn
        down with leases left wherever they are (a fleet router must have
        moved them first)."""
        if self.stopped:
            return
        self.stopped = True
        if self.lease_client is not None:
            await self.lease_client.stop()
        await self.sessions.stop()
        if not hard:
            await self.sessions.close_all()
        await self.runner.cleanup()
        await self.k8s.aclose()
        await self.pods.close()


class FakeS3:
    """In-process S3-shaped object store for the ``S3HttpBackend``
    conformance suite (docs/fleet.md "Storage backends"): path-style
    ``PUT/GET/HEAD /{bucket}/{key}`` over an in-memory dict. Multiple
    backend instances pointed at the same FakeS3 share one "bucket" —
    exactly the replica-agnosticism the fleet tier relies on."""

    def __init__(self, port: int | None = None) -> None:
        self.port = port or free_port()
        self.objects: dict[tuple[str, str], bytes] = {}
        self.put_count = 0
        self.fail_next = 0  # next N PUT/GETs answer 503 (retry/error paths)
        self._runner: web.AppRunner | None = None

    @property
    def endpoint(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def _maybe_fail(self) -> web.Response | None:
        if self.fail_next > 0:
            self.fail_next -= 1
            return web.json_response({"detail": "slow down"}, status=503)
        return None

    async def _put(self, request: web.Request) -> web.Response:
        if (fail := self._maybe_fail()) is not None:
            return fail
        key = (request.match_info["bucket"], request.match_info["key"])
        self.objects[key] = await request.read()
        self.put_count += 1
        return web.Response(status=200)

    async def _get(self, request: web.Request) -> web.Response:
        if (fail := self._maybe_fail()) is not None:
            return fail
        key = (request.match_info["bucket"], request.match_info["key"])
        body = self.objects.get(key)
        if body is None:
            return web.Response(status=404)
        return web.Response(body=body)

    async def _head(self, request: web.Request) -> web.Response:
        key = (request.match_info["bucket"], request.match_info["key"])
        return web.Response(status=200 if key in self.objects else 404)

    async def _delete(self, request: web.Request) -> web.Response:
        key = (request.match_info["bucket"], request.match_info["key"])
        self.objects.pop(key, None)
        return web.Response(status=204)

    async def start(self) -> "FakeS3":
        app = web.Application(client_max_size=1 << 28)
        app.router.add_put("/{bucket}/{key}", self._put)
        app.router.add_route("HEAD", "/{bucket}/{key}", self._head)
        app.router.add_get("/{bucket}/{key}", self._get, allow_head=False)
        app.router.add_delete("/{bucket}/{key}", self._delete)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        await web.TCPSite(self._runner, "127.0.0.1", self.port).start()
        return self

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None


class FakeCollector:
    """In-process OTLP/HTTP collector double for the telemetry exporter:
    records every JSON payload POSTed to ``/v1/traces`` / ``/v1/metrics`` /
    ``/v1/logs``. ``fail_next`` makes the next N posts answer 503 (retry
    coverage); ``stop()`` kills the listener mid-run (the chaos scenario)."""

    def __init__(self, port: int | None = None) -> None:
        self.port = port or free_port()
        self.trace_batches: list[dict] = []
        self.metric_batches: list[dict] = []
        self.log_batches: list[dict] = []
        self.requests = 0
        self.fail_next = 0
        self._runner: web.AppRunner | None = None

    @property
    def endpoint(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def span_trace_ids(self) -> set[str]:
        """Every traceId seen across all received span batches."""
        return {
            span["traceId"]
            for batch in self.trace_batches
            for rs in batch.get("resourceSpans", [])
            for ss in rs.get("scopeSpans", [])
            for span in ss.get("spans", [])
        }

    def log_records(self) -> list[dict]:
        """Every logRecord seen across all received logs batches (the wide
        events the flight recorder exported)."""
        return [
            record
            for batch in self.log_batches
            for rl in batch.get("resourceLogs", [])
            for sl in rl.get("scopeLogs", [])
            for record in sl.get("logRecords", [])
        ]

    async def _handle(self, request: web.Request, sink: list) -> web.Response:
        self.requests += 1
        if self.fail_next > 0:
            self.fail_next -= 1
            return web.json_response({"detail": "collector overloaded"}, status=503)
        sink.append(json.loads(await request.read()))
        return web.json_response({})

    async def start(self) -> "FakeCollector":
        app = web.Application(client_max_size=1 << 26)

        async def traces(request):
            return await self._handle(request, self.trace_batches)

        async def metrics(request):
            return await self._handle(request, self.metric_batches)

        async def logs(request):
            return await self._handle(request, self.log_batches)

        app.router.add_post("/v1/traces", traces)
        app.router.add_post("/v1/metrics", metrics)
        app.router.add_post("/v1/logs", logs)
        self._runner = web.AppRunner(app)
        await self._runner.setup()
        await web.TCPSite(self._runner, "127.0.0.1", self.port).start()
        return self

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
