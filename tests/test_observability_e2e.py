"""End-to-end observability through the fake-Kubernetes path (ISSUE 2
acceptance): one Execute yields ONE trace — admission→spawn→upload→execute→
download under a single trace_id — retrievable at /v1/traces/{trace_id},
with the same id in the pod-side (fake executor) log records and in the
response's timing breakdown, and stage durations consistent with the
end-to-end Prometheus histogram."""

import asyncio
import logging
import re

from aiohttp.test_utils import TestClient, TestServer

from bee_code_interpreter_tpu.api.http_server import create_http_server
from bee_code_interpreter_tpu.config import Config
from bee_code_interpreter_tpu.observability import Tracer, format_traceparent
from bee_code_interpreter_tpu.resilience import AdmissionController
from bee_code_interpreter_tpu.services.custom_tool_executor import (
    CustomToolExecutor,
)
from bee_code_interpreter_tpu.services.kubernetes_code_executor import (
    KubernetesCodeExecutor,
)
from bee_code_interpreter_tpu.utils.metrics import (
    PROMETHEUS_CONTENT_TYPE,
    Registry,
)
from bee_code_interpreter_tpu.utils.request_id import RequestIdLoggingFilter
from tests.fakes import FakeExecutorPods, FakeKubectl

POD_LOGGER = "bee_code_interpreter_tpu.runtime.executor_server"
EDGE_LOGGER = "bee_code_interpreter_tpu.api.http_server"


def make_stack(pods, storage, metrics, tracer):
    """(app, executor): the aiohttp edge over the REAL KubernetesCodeExecutor
    against the fake cluster — the executor is returned so tests can reach
    its fleet journal / pool directly."""
    config = Config(
        executor_backend="kubernetes",
        executor_port=pods.port,
        executor_pod_queue_target_length=0,  # every request spawns on demand
        pod_ready_timeout_s=5,
    )
    executor = KubernetesCodeExecutor(
        kubectl=FakeKubectl(pods),
        storage=storage,
        config=config,
        metrics=metrics,
        ip_poll_interval_s=0.02,
    )
    app = create_http_server(
        code_executor=executor,
        custom_tool_executor=CustomToolExecutor(code_executor=executor),
        metrics=metrics,
        admission=AdmissionController(metrics=metrics),
        tracer=tracer,
    )
    return app, executor


def make_app(pods, storage, metrics, tracer):
    return make_stack(pods, storage, metrics, tracer)[0]


async def with_client(app, fn):
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        return await fn(client)
    finally:
        await client.close()


def _histogram_sum(text: str, name: str, route: str) -> float:
    pattern = re.compile(
        rf'^{name}_sum{{route="{re.escape(route)}"}} ([0-9.e+-]+)$', re.M
    )
    m = pattern.search(text)
    return float(m.group(1)) if m else 0.0


async def test_single_execute_yields_one_complete_trace(
    tmp_path, storage, caplog
):
    pods = FakeExecutorPods(tmp_path / "pods")
    metrics = Registry()
    tracer = Tracer(metrics=metrics)
    app = make_app(pods, storage, metrics, tracer)
    pod_logger = logging.getLogger(POD_LOGGER)
    log_filter = RequestIdLoggingFilter()
    pod_logger.addFilter(log_filter)

    async def go(client: TestClient):
        # request 1 creates a file so request 2 exercises BOTH upload (files
        # in) and download (changed files out)
        r1 = await (
            await client.post(
                "/v1/execute",
                json={"source_code": "open('state.txt', 'w').write('x')"},
            )
        ).json()
        assert set(r1["files"]) == {"/workspace/state.txt"}

        before = _histogram_sum(
            await (await client.get("/metrics")).text(),
            "bci_http_request_seconds",
            "/v1/execute",
        )
        caplog.clear()
        with caplog.at_level(logging.INFO, logger=POD_LOGGER):
            resp = await client.post(
                "/v1/execute",
                json={
                    # sleep makes the execute stage dominate, so the
                    # stage-sum-vs-histogram bound below is not noise-bound
                    "source_code": (
                        "import time; time.sleep(0.2)\n"
                        "print(open('state.txt').read())\n"
                        "open('out.txt', 'w').write('y')"
                    ),
                    "files": r1["files"],
                },
            )
        body = await resp.json()
        assert resp.status == 200
        assert body["stdout"] == "x\n"

        # --- response carries the trace id + per-stage breakdown ---
        trace_id = body["trace_id"]
        assert trace_id and len(trace_id) == 32
        timings = body["timings_ms"]
        assert {"admission", "spawn", "upload", "execute", "download"} <= set(
            timings
        )
        assert timings["execute"] >= 200.0  # the sleep is visible

        # --- the same trace is retrievable from the inspection API ---
        listed = await (await client.get("/v1/traces")).json()
        assert trace_id in {t["trace_id"] for t in listed["traces"]}
        detail = await (await client.get(f"/v1/traces/{trace_id}")).json()
        assert detail["trace_id"] == trace_id
        assert detail["name"] == "/v1/execute"
        names = {s["name"] for s in detail["spans"]}
        assert {
            "/v1/execute", "admission", "spawn", "upload", "execute",
            "download",
        } <= names
        # one trace: every span under the single trace_id
        assert {s["trace_id"] for s in detail["spans"]} == {trace_id}
        missing = await client.get("/v1/traces/" + "deadbeef" * 4)
        assert missing.status == 404

        # --- stage durations agree with the end-to-end histogram ---
        after = _histogram_sum(
            await (await client.get("/metrics")).text(),
            "bci_http_request_seconds",
            "/v1/execute",
        )
        end_to_end_ms = (after - before) * 1000.0
        stage_sum_ms = sum(
            timings[k]
            for k in ("admission", "spawn", "upload", "execute", "download")
        )
        assert stage_sum_ms <= end_to_end_ms * 1.001
        assert stage_sum_ms >= end_to_end_ms * 0.9

        # --- the pod-side executor logs carry the SAME correlation ids ---
        rid = resp.headers["X-Request-Id"]
        pod_records = [
            r for r in caplog.records if r.name == POD_LOGGER
        ]
        assert pod_records, "fake executor produced no log records"
        executing = [
            r for r in pod_records if "Executing sandboxed code" in r.message
        ]
        assert executing
        for r in executing:
            assert r.request_id == rid
            assert r.trace_id == trace_id

        # spans also fed the shared stage histogram (Prometheus and traces
        # agree on what stages exist)
        text = await (await client.get("/metrics")).text()
        for stage in ("admission", "spawn", "upload", "execute", "download"):
            assert f'bci_stage_seconds_count{{stage="{stage}"}}' in text

    try:
        await with_client(app, go)
    finally:
        pod_logger.removeFilter(log_filter)
        await pods.close()


async def test_inbound_traceparent_continues_the_trace(tmp_path, storage):
    pods = FakeExecutorPods(tmp_path / "pods")
    metrics = Registry()
    tracer = Tracer(metrics=metrics)
    app = make_app(pods, storage, metrics, tracer)

    async def go(client: TestClient):
        upstream_trace = "ab" * 16
        upstream_span = "cd" * 8
        resp = await client.post(
            "/v1/execute",
            json={"source_code": "print(1)"},
            headers={
                "traceparent": format_traceparent(upstream_trace, upstream_span)
            },
        )
        body = await resp.json()
        assert body["trace_id"] == upstream_trace
        detail = await (
            await client.get(f"/v1/traces/{upstream_trace}")
        ).json()
        root = next(s for s in detail["spans"] if s["name"] == "/v1/execute")
        assert root["parent_id"] == upstream_span

    try:
        await with_client(app, go)
    finally:
        await pods.close()


async def test_concurrent_executes_do_not_cross_contaminate_ids(
    tmp_path, storage, caplog
):
    """Two in-flight executes interleaving on the loop: each one's edge log
    records must carry its own request/trace ids (satellite: log-correlation
    coverage at the service level, not just the contextvar level)."""
    pods = FakeExecutorPods(tmp_path / "pods")
    metrics = Registry()
    tracer = Tracer(metrics=metrics)
    app = make_app(pods, storage, metrics, tracer)
    edge_logger = logging.getLogger(EDGE_LOGGER)
    log_filter = RequestIdLoggingFilter()
    edge_logger.addFilter(log_filter)

    async def go(client: TestClient):
        async def run(tag: str):
            resp = await client.post(
                "/v1/execute",
                json={
                    "source_code": (
                        f"import time; time.sleep(0.05); print('{tag}')"
                    )
                },
            )
            return tag, await resp.json()

        with caplog.at_level(logging.INFO, logger=EDGE_LOGGER):
            results = dict(
                await asyncio.gather(run("alpha"), run("bravo"))
            )
        assert results["alpha"]["stdout"] == "alpha\n"
        assert results["bravo"]["stdout"] == "bravo\n"
        assert results["alpha"]["trace_id"] != results["bravo"]["trace_id"]

        # every edge record mentioning a tag must carry that request's ids
        by_tag = {}
        for r in caplog.records:
            if r.name != EDGE_LOGGER:
                continue
            for tag in ("alpha", "bravo"):
                if tag in r.message:
                    by_tag.setdefault(tag, set()).add(r.trace_id)
        for tag in ("alpha", "bravo"):
            assert by_tag[tag] == {results[tag]["trace_id"]}, (
                f"log records for {tag} leaked another request's trace id"
            )

    try:
        await with_client(app, go)
    finally:
        edge_logger.removeFilter(log_filter)
        await pods.close()


async def test_fleet_usage_and_metrics_tell_one_requests_full_story(
    tmp_path, storage
):
    """ISSUE 3 acceptance: after one request through the fake-k8s path,
    /v1/fleet/events shows the serving pod's spawn→assigned→executing→
    released transitions, ExecuteResponse.usage reports nonzero cpu/wall/
    byte figures that match the trace span's usage.* attributes, and
    /metrics exposes the new pool + execution histograms."""
    pods = FakeExecutorPods(tmp_path / "pods")
    metrics = Registry()
    tracer = Tracer(metrics=metrics)
    app, _executor = make_stack(pods, storage, metrics, tracer)

    async def go(client: TestClient):
        seed = await (
            await client.post(
                "/v1/execute",
                json={"source_code": "open('in.txt', 'w').write('z' * 64)"},
            )
        ).json()
        resp = await client.post(
            "/v1/execute",
            json={
                "source_code": (
                    "print(open('in.txt').read()[:1])\n"
                    "open('out.txt', 'w').write('y' * 128)"
                ),
                "files": seed["files"],
            },
        )
        body = await resp.json()
        assert resp.status == 200

        # --- usage: nonzero cpu/wall/byte figures in the response ---
        usage = body["usage"]
        assert usage["cpu_user_s"] > 0
        assert usage["wall_s"] > 0
        assert usage["max_rss_bytes"] > 0
        assert usage["uploaded_bytes"] == 64
        assert usage["downloaded_bytes"] == 128
        assert usage["workspace_bytes_written"] >= 128

        # --- ...matching the trace root span's usage.* attributes ---
        detail = await (
            await client.get(f"/v1/traces/{body['trace_id']}")
        ).json()
        root = next(s for s in detail["spans"] if s["parent_id"] is None)
        for key in (
            "cpu_user_s", "wall_s", "max_rss_bytes",
            "uploaded_bytes", "downloaded_bytes",
        ):
            assert root["attributes"][f"usage.{key}"] == str(usage[key])

        # --- fleet journal: the serving pod's full story ---
        events = (
            await (await client.get("/v1/fleet/events?limit=50")).json()
        )["events"]
        pod_names = {e["pod"] for e in events}
        assert len(pod_names) == 2  # one pod per request
        by_pod = {}
        for e in reversed(events):  # chronological
            by_pod.setdefault(e["pod"], []).append(e["state"])
        for states in by_pod.values():
            assert states == [
                "spawning", "ready", "assigned", "executing", "released",
            ]
        snap = await (await client.get("/v1/fleet")).json()
        assert snap["live"] == 0  # single-use: nothing outlives its request
        assert snap["executions_total"] == 2
        assert snap["lifetime"]["released"] == 2

        # --- the new pool + execution metrics are exposed ---
        text = await (await client.get("/metrics")).text()
        assert "bci_pool_spawn_seconds_count 2" in text
        assert "bci_pool_utilization 0" in text
        assert "bci_execution_cpu_seconds_count 2" in text
        assert "bci_execution_peak_rss_bytes_count 2" in text

    try:
        await with_client(app, go)
    finally:
        await pods.close()


async def test_traces_endpoint_supports_limit_and_min_duration(
    tmp_path, storage
):
    pods = FakeExecutorPods(tmp_path / "pods")
    metrics = Registry()
    tracer = Tracer(metrics=metrics)
    app = make_app(pods, storage, metrics, tracer)

    async def go(client: TestClient):
        fast = await (
            await client.post("/v1/execute", json={"source_code": "pass"})
        ).json()
        slow = await (
            await client.post(
                "/v1/execute",
                json={"source_code": "import time; time.sleep(0.3)"},
            )
        ).json()

        listed = await (await client.get("/v1/traces")).json()
        assert len(listed["traces"]) == 2

        limited = await (await client.get("/v1/traces?limit=1")).json()
        assert len(limited["traces"]) == 1
        # newest first: the slow request came second
        assert limited["traces"][0]["trace_id"] == slow["trace_id"]

        slow_only = await (
            await client.get("/v1/traces?min_duration_ms=250")
        ).json()
        assert {t["trace_id"] for t in slow_only["traces"]} == {
            slow["trace_id"]
        }
        assert fast["trace_id"] not in {
            t["trace_id"] for t in slow_only["traces"]
        }

        both = await (
            await client.get("/v1/traces?limit=5&min_duration_ms=0")
        ).json()
        assert len(both["traces"]) == 2

        for bad in (
            "/v1/traces?limit=banana",
            "/v1/traces?min_duration_ms=soup",
            "/v1/traces?limit=-1",
        ):
            assert (await client.get(bad)).status == 400

    try:
        await with_client(app, go)
    finally:
        await pods.close()


async def test_healthz_verbose_reports_pool_breakers_and_fleet(
    tmp_path, storage
):
    pods = FakeExecutorPods(tmp_path / "pods")
    metrics = Registry()
    tracer = Tracer(metrics=metrics)
    app = make_app(pods, storage, metrics, tracer)

    async def go(client: TestClient):
        plain = await (await client.get("/healthz")).json()
        assert plain == {"status": "ok"}  # terse view unchanged
        explicit_off = await (await client.get("/healthz?verbose=0")).json()
        assert explicit_off == {"status": "ok"}  # =0 is not truthy

        await client.post("/v1/execute", json={"source_code": "print(1)"})
        verbose = await (await client.get("/healthz?verbose=1")).json()
        assert verbose["status"] == "ok"
        # `target` is the live refill target (docs/autoscaling.md): the
        # static config length until an act-mode autoscaler overrides it.
        assert verbose["pool"] == {"ready": 0, "spawning": 0, "target": 0}
        assert verbose["breakers"] == {
            "k8s-spawn": "closed", "k8s-http": "closed",
        }
        assert verbose["fleet"]["executions_total"] == 1
        assert verbose["fleet"]["live"] == 0

    try:
        await with_client(app, go)
    finally:
        await pods.close()


async def test_profile_sandbox_injects_trace_dir_and_reports_artifacts(
    local_executor,
):
    app = create_http_server(
        code_executor=local_executor,
        custom_tool_executor=CustomToolExecutor(code_executor=local_executor),
    )

    async def go(client: TestClient):
        resp = await client.post(
            "/v1/profile",
            json={
                "source_code": (
                    "import os\n"
                    "d = os.environ['BCI_PROFILE_DIR']\n"
                    "print(d)\n"
                    "os.makedirs(os.path.basename(d), exist_ok=True)\n"
                    "open(os.path.join(os.path.basename(d), 'trace.pb'),"
                    " 'w').write('fake-trace')"
                ),
            },
        )
        body = await resp.json()
        assert resp.status == 200
        # the shim's env trigger was injected...
        assert body["stdout"] == "/workspace/.bci-profile\n"
        assert body["profile_dir"] == "/workspace/.bci-profile"
        # ...and artifacts written under it ride the changed-file map
        assert body["profile_files"] == [
            "/workspace/.bci-profile/trace.pb"
        ]
        assert set(body["files"]) == {"/workspace/.bci-profile/trace.pb"}
        assert body["usage"]["cpu_user_s"] > 0

        # missing source_code for sandbox target is a validation error
        resp = await client.post("/v1/profile", json={"target": "sandbox"})
        assert resp.status == 422
        # serving target without an attached engine is explicit
        resp = await client.post("/v1/profile", json={"target": "serving"})
        assert resp.status == 501

    await with_client(app, go)


async def test_profile_serving_captures_engine_steps(tmp_path, local_executor):
    from bee_code_interpreter_tpu.observability import ServingProfiler

    import jax.profiler

    class Stepper:
        steps = 0
        profiler_trace = staticmethod(jax.profiler.trace)

        def step(self):
            Stepper.steps += 1

    app = create_http_server(
        code_executor=local_executor,
        custom_tool_executor=CustomToolExecutor(code_executor=local_executor),
        profiler=ServingProfiler(Stepper(), trace_root=tmp_path),
    )

    async def go(client: TestClient):
        resp = await client.post(
            "/v1/profile", json={"target": "serving", "steps": 4}
        )
        body = await resp.json()
        assert resp.status == 200
        assert body["target"] == "serving"
        assert body["steps"] == 4
        assert Stepper.steps == 4
        assert body["trace_dir"].startswith(str(tmp_path))

    await with_client(app, go)


async def test_grpc_fleet_service_serves_snapshot_and_events(
    tmp_path, storage
):
    """The gRPC spelling of /v1/fleet: JSON-bytes FleetService methods
    backed by the same journal the executor records into."""
    import grpc.aio

    from bee_code_interpreter_tpu.api.grpc_server import GrpcServer, fleet_stubs

    pods = FakeExecutorPods(tmp_path / "pods")
    config = Config(
        executor_backend="kubernetes",
        executor_port=pods.port,
        executor_pod_queue_target_length=0,
        pod_ready_timeout_s=5,
    )
    executor = KubernetesCodeExecutor(
        kubectl=FakeKubectl(pods),
        storage=storage,
        config=config,
        ip_poll_interval_s=0.02,
    )
    server = GrpcServer(
        code_executor=executor,
        custom_tool_executor=CustomToolExecutor(code_executor=executor),
    )
    port = await server.start("127.0.0.1:0")
    try:
        await executor.execute("print('hi')")
        import json as _json

        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as channel:
            stubs = fleet_stubs(channel)
            snap = _json.loads(await stubs["GetFleet"](b""))
            assert snap["executions_total"] == 1
            events = _json.loads(
                await stubs["GetFleetEvents"](_json.dumps({"limit": 2}).encode())
            )["events"]
            assert len(events) == 2
            assert events[0]["state"] == "released"
    finally:
        await server.stop(grace=0.1)
        await pods.close()


async def test_metrics_content_type_negotiates_exposition_format(
    local_executor,
):
    """Regression for BOTH negotiation paths: the classic Prometheus text
    format stays the default; ``Accept: application/openmetrics-text`` gets
    OpenMetrics 1.0 with the ``# EOF`` terminator."""
    from bee_code_interpreter_tpu.utils.metrics import (
        OPENMETRICS_CONTENT_TYPE,
    )

    app = create_http_server(
        code_executor=local_executor,
        custom_tool_executor=CustomToolExecutor(code_executor=local_executor),
    )

    async def go(client: TestClient):
        # default (no Accept preference): classic Prometheus text format
        resp = await client.get("/metrics")
        assert resp.status == 200
        assert resp.headers["Content-Type"] == PROMETHEUS_CONTENT_TYPE
        body = await resp.text()
        assert "# EOF" not in body

        # a Prometheus-style Accept chain asking for OpenMetrics first
        resp = await client.get(
            "/metrics",
            headers={
                "Accept": (
                    "application/openmetrics-text; version=1.0.0, "
                    "text/plain;version=0.0.4;q=0.5"
                )
            },
        )
        assert resp.status == 200
        assert resp.headers["Content-Type"] == OPENMETRICS_CONTENT_TYPE
        body = await resp.text()
        assert body.rstrip().endswith("# EOF")

        # an explicit text/plain Accept keeps the classic format
        resp = await client.get(
            "/metrics", headers={"Accept": "text/plain; version=0.0.4"}
        )
        assert resp.headers["Content-Type"] == PROMETHEUS_CONTENT_TYPE

        # q=0 means "not acceptable" (RFC 9110): a client explicitly
        # REFUSING OpenMetrics must get the classic format
        resp = await client.get(
            "/metrics",
            headers={
                "Accept": "application/openmetrics-text;q=0, text/plain"
            },
        )
        assert resp.headers["Content-Type"] == PROMETHEUS_CONTENT_TYPE

    await with_client(app, go)


async def test_export_traces_and_exemplars_tell_one_story(tmp_path, storage):
    """ISSUE 5 acceptance: one executed request produces an OTLP/JSON span
    batch whose trace_id matches both /v1/traces/{id} and the exemplar on
    the bci_stage_seconds OpenMetrics exposition — collector, inspection
    API, and Prometheus all point at the same trace."""
    import json as _json

    from bee_code_interpreter_tpu.observability import TelemetryExporter
    from bee_code_interpreter_tpu.resilience import RetryPolicy

    pods = FakeExecutorPods(tmp_path / "pods")
    metrics = Registry()
    tracer = Tracer(metrics=metrics)
    sent: list[tuple[str, dict]] = []

    async def transport(path, body):
        sent.append((path, _json.loads(body)))

    exporter = TelemetryExporter(
        "http://collector.invalid:4318",
        metrics,
        transport=transport,
        flush_interval_s=60.0,  # the test flushes explicitly
        retry=RetryPolicy(attempts=1, wait_min_s=0.001, wait_max_s=0.002),
    )
    tracer.add_sink(exporter.enqueue_trace)
    app = make_app(pods, storage, metrics, tracer)

    async def go(client: TestClient):
        body = await (
            await client.post(
                "/v1/execute", json={"source_code": "print('exported')"}
            )
        ).json()
        trace_id = body["trace_id"]

        # --- the exported OTLP batch carries the SAME trace ---
        await exporter.flush_once()
        trace_posts = [p for p in sent if p[0] == "/v1/traces"]
        assert len(trace_posts) == 1
        spans = trace_posts[0][1]["resourceSpans"][0]["scopeSpans"][0]["spans"]
        assert {s["traceId"] for s in spans} == {trace_id}
        exported_names = {s["name"] for s in spans}
        # (no files in/out on this request, so no upload/download stages)
        assert {"/v1/execute", "spawn", "execute"} <= exported_names

        # --- which is retrievable from the inspection API ---
        detail = await (await client.get(f"/v1/traces/{trace_id}")).json()
        assert {s["name"] for s in detail["spans"]} == exported_names

        # --- and is the exemplar on the stage histogram ---
        om = await (
            await client.get(
                "/metrics",
                headers={"Accept": "application/openmetrics-text"},
            )
        ).text()
        execute_exemplars = re.findall(
            r'^bci_stage_seconds_bucket\{le="[^"]+",stage="execute"\} \d+ '
            r'# \{trace_id="([0-9a-f]{32})"',
            om,
            re.M,
        )
        assert execute_exemplars == [trace_id]

        # drop accounting stayed clean on the happy path
        assert "bci_telemetry_dropped_total" not in re.sub(
            r"# (HELP|TYPE)[^\n]*", "", om
        )

    try:
        await with_client(app, go)
    finally:
        await pods.close()


async def test_debug_bundle_is_one_complete_document(tmp_path, storage):
    """ISSUE 5 acceptance: GET /v1/debug/bundle returns traces, fleet
    events, SLO state, service health, and the metrics dump in ONE JSON
    document."""
    from bee_code_interpreter_tpu.observability import (
        SloEngine,
        parse_objectives,
    )

    pods = FakeExecutorPods(tmp_path / "pods")
    metrics = Registry()
    tracer = Tracer(metrics=metrics)
    slo = SloEngine(parse_objectives(99.5, "2000:99"), metrics=metrics)
    pods_app, executor = make_stack(pods, storage, metrics, tracer)
    app = create_http_server(
        code_executor=executor,
        custom_tool_executor=CustomToolExecutor(code_executor=executor),
        metrics=metrics,
        tracer=tracer,
        slo=slo,
    )

    async def go(client: TestClient):
        body = await (
            await client.post("/v1/execute", json={"source_code": "print(1)"})
        ).json()

        resp = await client.get("/v1/debug/bundle")
        assert resp.status == 200
        bundle = await resp.json()
        assert bundle["generated_unix"] > 0

        # traces: the request is in the recent summaries and (being the
        # only one) in the slowest full dumps
        recent_ids = {t["trace_id"] for t in bundle["traces"]["recent"]}
        assert body["trace_id"] in recent_ids
        assert bundle["traces"]["slowest"][0]["spans"]

        # fleet: the serving pod's lifecycle is in the same document
        states = {e["state"] for e in bundle["fleet"]["events"]}
        assert {"spawning", "ready", "executing", "released"} <= states
        assert bundle["fleet"]["snapshot"]["executions_total"] == 1

        # slo: the request was sampled
        availability = next(
            o
            for o in bundle["slo"]["objectives"]
            if o["name"] == "availability"
        )
        assert availability["windows"]["5m"]["total"] == 1

        # service health + full metrics dump round out the snapshot
        assert bundle["service"]["breakers"] == {
            "k8s-spawn": "closed", "k8s-http": "closed",
        }
        assert "bci_stage_seconds" in bundle["metrics"]

    try:
        await with_client(app, go)
    finally:
        await pods.close()
