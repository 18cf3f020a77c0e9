"""Accelerator observability plane (ISSUE 20): TrackedJit exactly-once
compile detection, DeviceMonitor's three signals (compile/retrace wide
events + counters + backdated trace spans, CPU-degraded memory accounting,
mesh-shaped step telemetry), the forced-retrace e2e on a real tiny batcher,
the `GET /v1/accelerator` + `POST /v1/profile target=device` HTTP edges and
their gRPC mirrors (400 ↔ INVALID_ARGUMENT parity), and the serving-bench
overhead A/B with the device monitor riding the instrumented arm."""

import dataclasses
import json

import grpc.aio
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from aiohttp.test_utils import TestClient, TestServer

from bee_code_interpreter_tpu.api.grpc_server import (
    GrpcServer,
    observability_stubs,
)
from bee_code_interpreter_tpu.api.http_server import create_http_server
from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.engine import Engine
from bee_code_interpreter_tpu.models.serving import ContinuousBatcher
from bee_code_interpreter_tpu.observability import (
    DeviceMonitor,
    DeviceProfiler,
    FlightRecorder,
    ServingMonitor,
    TraceStore,
)
from bee_code_interpreter_tpu.observability.tracing import (
    Trace,
    activate_trace,
)
from bee_code_interpreter_tpu.parallel.mesh import (
    mesh_descriptor,
    mesh_shape_key,
)
from bee_code_interpreter_tpu.services.custom_tool_executor import (
    CustomToolExecutor,
)
from bee_code_interpreter_tpu.utils.jitwatch import (
    TrackedJit,
    abstract_signature,
)
from bee_code_interpreter_tpu.utils.metrics import Registry

CFG = dataclasses.replace(
    T.TransformerConfig.tiny(), dtype=jnp.float32, n_kv_heads=2
)
PARAMS = T.init_params(CFG, jax.random.PRNGKey(0))


def counter_value(metrics: Registry, needle: str) -> float:
    for line in metrics.expose().splitlines():
        if line.startswith(needle + " ") or line.startswith(needle + "{"):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def monitored_stack():
    """Registry + recorder + trace store + both monitors over a tiny
    engine — the chaos scenario 19 wiring in miniature. page_size=4 so a
    3-token prompt pads to one page and a 6-token prompt to two: the
    second prefill shape forces a retrace during live serving."""
    metrics = Registry()
    store = TraceStore()
    recorder = FlightRecorder(metrics=metrics, max_events=256)
    serving = ServingMonitor(metrics=metrics, store=store, recorder=recorder)
    device = DeviceMonitor(metrics=metrics, recorder=recorder)
    batcher = ContinuousBatcher(
        PARAMS, CFG, max_batch=2, n_pages=16, page_size=4,
        max_pages_per_seq=4, metrics=metrics,
    )
    engine = Engine(batcher, max_queue=4, metrics=metrics)
    serving.attach(engine)
    device.attach(engine)
    return engine, device, serving, metrics, store, recorder


# ------------------------------------------------------------- TrackedJit


def test_tracked_jit_reports_each_compile_exactly_once():
    compiles = []

    class Hook:
        def on_compile(self, name, *, signature, duration_ms, trigger):
            compiles.append(
                {"name": name, "signature": signature, "trigger": trigger,
                 "duration_ms": duration_ms}
            )

    hook = Hook()
    fn = TrackedJit(jax.jit(lambda x: x * 2), "double", lambda: hook)
    a = jnp.ones((4,), jnp.float32)
    np.testing.assert_allclose(np.asarray(fn(a)), 2.0)
    assert [c["trigger"] for c in compiles] == ["first_call"]
    assert compiles[0]["name"] == "double"
    assert "float32[4]" in compiles[0]["signature"]
    assert compiles[0]["duration_ms"] > 0.0

    # same signature: cached executable, NO new report
    fn(jnp.zeros((4,), jnp.float32))
    assert len(compiles) == 1

    # new shape: one retrace, reported exactly once
    fn(jnp.ones((8,), jnp.float32))
    fn(jnp.ones((8,), jnp.float32))
    assert [c["trigger"] for c in compiles] == ["first_call", "retrace"]
    assert "float32[8]" in compiles[1]["signature"]


def test_tracked_jit_unmonitored_path_and_passthrough():
    fn = TrackedJit(jax.jit(lambda x: x + 1), "inc", lambda: None)
    assert int(fn(jnp.int32(1))) == 2  # no monitor: plain call
    assert callable(fn.lower)  # AOT attribute passthrough to the jit
    assert abstract_signature((jnp.ones((2, 3)),), {"n": 4}) == (
        "(float32[2,3], n=4)"
    )


# ----------------------------------------------------------- DeviceMonitor


def test_on_compile_event_counter_and_backdated_span_share_trace_id():
    metrics = Registry()
    recorder = FlightRecorder(metrics=metrics)
    monitor = DeviceMonitor(metrics=metrics, recorder=recorder)
    trace = Trace(None, "request", request_id="req-1")

    with activate_trace(trace):
        monitor.on_compile(
            "decode_step", signature="(float32[2,4])", duration_ms=120.0,
            trigger="retrace",
        )

    events = recorder.events(kind="compile")
    assert len(events) == 1
    event = events[0]
    assert event["function"] == "decode_step"
    assert event["trigger"] == "retrace"
    assert event["trace_id"] == trace.trace_id
    assert event["request_id"] == "req-1"

    spans = [s for s in trace.spans if s.name == "xla.compile"]
    assert len(spans) == 1
    # backdated: the span covers the stall that already happened
    assert spans[0].duration_ms == pytest.approx(120.0, rel=0.05)
    assert spans[0].attributes["trigger"] == "retrace"

    assert counter_value(metrics, 'bci_compile_total{trigger="retrace"}') == 1
    snap = monitor.snapshot()
    assert snap["compile"]["total"] == 1
    assert snap["compile"]["by_trigger"] == {"retrace": 1}
    assert snap["compile"]["recent"][0]["trace_id"] == trace.trace_id
    fn = snap["compile"]["functions"]["decode_step"]
    assert fn["compiles"] == 1 and fn["signatures"] == ["(float32[2,4])"]


def test_compile_without_ambient_trace_has_no_trace_id():
    recorder = FlightRecorder(metrics=Registry())
    monitor = DeviceMonitor(recorder=recorder)  # metrics=None path too
    monitor.on_compile(
        "prefill", signature="(int32[8])", duration_ms=5.0,
        trigger="first_call",
    )
    (event,) = recorder.events(kind="compile")
    assert "trace_id" not in event
    assert monitor.snapshot()["compile"]["by_trigger"] == {"first_call": 1}


def test_cpu_memory_degradation_snapshot():
    """No memory_stats() on the CPU backend: rows come from the attached
    batcher's live-buffer estimate, marked estimated, peak is a running
    max, limit unknown."""
    engine, monitor, *_ = monitored_stack()
    rows = monitor.sample_memory()
    assert rows, "no devices visible"
    assert all(r["estimated"] for r in rows)
    assert all(r["limit_bytes"] is None for r in rows)
    pool_bytes = sum(x.nbytes for x in engine.batcher.cache.values())
    assert sum(r["live_bytes"] for r in rows) >= pool_bytes

    snap = monitor.snapshot()
    assert snap["attached"] is True
    assert snap["memory"]["estimated"] is True
    assert snap["memory"]["reason"] is None
    assert snap["memory"]["samples"] >= 2  # attach takes an eager one

    fleet = monitor.fleet_summary()
    assert fleet["hbm"]["estimated"] is True
    assert fleet["hbm"]["limit_bytes"] is None
    assert fleet["hbm"]["live_bytes"] >= pool_bytes


def test_unattached_monitor_reports_no_engine_and_no_memory():
    """A process with no engine attached does not hold the device: no
    memory rows, no HBM gauges, and the snapshot says why — never a CPU
    estimate presented as the accelerator's memory."""
    metrics = Registry()
    monitor = DeviceMonitor(metrics=metrics)
    assert monitor.sample_memory() == []
    snap = monitor.snapshot()
    assert snap["attached"] is False
    assert snap["memory"]["devices"] == []
    assert snap["memory"]["samples"] == 0
    assert snap["memory"]["estimated"] is None
    assert "no in-process engine" in snap["memory"]["reason"]
    assert snap["kv_pool"] is None and snap["mesh"] is None
    assert "bci_device_hbm_bytes" not in metrics.expose()

    fleet = monitor.fleet_summary()
    assert fleet["hbm"] == {
        "live_bytes": 0, "limit_bytes": None, "estimated": None,
    }
    assert fleet["mesh"] is None and fleet["compiles"] == 0


def test_step_telemetry_aggregates_per_mesh_shape():
    monitor = DeviceMonitor(metrics=Registry())
    monitor.record_step(10.0)  # no mesh: the single-device "1" bucket
    monitor.set_mesh(mesh_descriptor(None))
    monitor.record_step(20.0)
    monitor.record_step(30.0, shape="dp=2,tp=4")

    shapes = monitor.snapshot()["steps"]["by_shape"]
    assert shapes["1"]["steps"] == 2
    assert shapes["1"]["total_ms"] == pytest.approx(30.0)
    assert shapes["1"]["min_ms"] == pytest.approx(10.0)
    assert shapes["1"]["max_ms"] == pytest.approx(20.0)
    assert shapes["dp=2,tp=4"] == {
        "steps": 1, "total_ms": 30.0, "min_ms": 30.0, "max_ms": 30.0,
        "last_ms": 30.0,
    }


def test_mesh_shape_key_and_descriptor():
    assert mesh_shape_key(None) == "1"
    desc = mesh_descriptor(None)
    assert desc["shape"] == "1" and desc["axes"] == {}
    from bee_code_interpreter_tpu.parallel import make_mesh

    n = len(jax.devices())
    if n >= 2:
        mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
        assert mesh_shape_key(mesh) == "dp=2"
        d = mesh_descriptor(mesh)
        assert d["axes"] == {"dp": 2} and d["n_devices"] == 2
        assert d["platform"] == jax.devices()[0].platform


# ------------------------------------------------- e2e: retrace under load


def test_forced_retrace_during_serving_lands_in_all_three_surfaces():
    """Chaos scenario 19's core as a tier-1 test: a prompt that needs a new
    prefill page count retraces mid-serving — exactly one compile event,
    one counter increment, and one backdated xla.compile span inside the
    REQUEST's trace, all naming the same trace_id."""
    engine, device, serving, metrics, store, recorder = monitored_stack()

    t_a = engine.submit([1, 2, 3], 4)  # pads to 1 page: first_call compiles
    engine.run_to_completion()
    assert len(engine.result(t_a)) == 4
    baseline = device.snapshot()["compile"]["by_trigger"].get("retrace", 0)
    assert baseline == 0

    t_b = engine.submit([5, 3, 7, 2, 9, 11], 4)  # 2 pages: prefill retrace
    engine.run_to_completion()
    assert len(engine.result(t_b)) == 4

    retraces = [
        e for e in recorder.events(kind="compile")
        if e.get("trigger") == "retrace"
    ]
    assert retraces, "the page-count change must force a retrace"
    snap = device.snapshot()
    assert snap["attached"] is True
    assert snap["compile"]["by_trigger"]["retrace"] == len(retraces)
    assert counter_value(
        metrics, 'bci_compile_total{trigger="retrace"}'
    ) == len(retraces)
    # one compile event per compile overall, not just retraces
    all_compile_events = recorder.events(kind="compile")
    assert snap["compile"]["total"] == len(all_compile_events)

    # attribution: every retrace fired under request B's live trace
    trace_ids = {e.get("trace_id") for e in retraces}
    assert len(trace_ids) == 1 and None not in trace_ids
    trace = store.get(trace_ids.pop())
    assert trace is not None
    compile_spans = [s for s in trace.spans if s.name == "xla.compile"]
    assert len(compile_spans) == len(retraces)

    # step telemetry rode along, bucketed under the single-device shape
    assert snap["steps"]["by_shape"]["1"]["steps"] > 0
    # KV-pool occupancy joined from the live batcher
    assert snap["kv_pool"]["pages_total"] == 15


# --------------------------------------------------------- HTTP/gRPC twins


def make_app(local_executor, *, device=None, device_profiler=None):
    return create_http_server(
        code_executor=local_executor,
        custom_tool_executor=CustomToolExecutor(code_executor=local_executor),
        metrics=Registry(),
        device=device,
        device_profiler=device_profiler,
    )


async def with_client(app, fn):
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await fn(client)
    finally:
        await client.close()


async def test_http_accelerator_endpoint(local_executor):
    device = DeviceMonitor(metrics=Registry())
    device.on_compile(
        "decode_step", signature="(f32[1])", duration_ms=3.0,
        trigger="first_call",
    )
    app = make_app(local_executor, device=device)

    async def go(client):
        resp = await client.get("/v1/accelerator")
        assert resp.status == 200
        snap = await resp.json()
        assert sorted(snap) == [
            "attached", "compile", "kv_pool", "memory", "mesh", "steps",
        ]
        assert snap["compile"]["total"] == 1
        assert snap["memory"]["devices"] == []  # no engine attached
        assert "no in-process engine" in snap["memory"]["reason"]
        trimmed = await (
            await client.get("/v1/accelerator", params={"recent": "0"})
        ).json()
        assert trimmed["compile"]["recent"] == []
        for bad in ({"recent": "nope"}, {"recent": "-1"}):
            assert (
                await client.get("/v1/accelerator", params=bad)
            ).status == 400

    await with_client(app, go)


async def test_http_accelerator_unwired_and_fleet_summary(local_executor):
    async def go_unwired(client):
        assert (await client.get("/v1/accelerator")).status == 501

    await with_client(make_app(local_executor), go_unwired)

    device = DeviceMonitor(metrics=Registry())
    app = make_app(local_executor, device=device)

    async def go_fleet(client):
        fleet = await (await client.get("/v1/fleet")).json()
        accel = fleet["accelerator"]
        assert accel["compiles"] == 0
        assert accel["hbm"]["estimated"] is None  # no engine: no rows

    await with_client(app, go_fleet)


async def test_http_device_profile(local_executor, tmp_path):
    """With an engine attached the device target traces its steps; the
    trace is the batcher's own (jax.profiler), handed in through the
    serving monitor."""
    engine, _device, serving, *_ = monitored_stack()
    ticket = engine.submit([1, 2, 3], 4)
    profiler = DeviceProfiler(serving, trace_root=tmp_path)
    app = make_app(local_executor, device_profiler=profiler)

    async def go(client):
        resp = await client.post(
            "/v1/profile", json={"target": "device", "steps": 2}
        )
        assert resp.status == 200, await resp.text()
        body = await resp.json()
        assert body["target"] == "device"
        assert body["source"] == "serving"
        assert body["steps"] == 2 and body["duration_ms"] >= 0
        assert body["trace_dir"].startswith(str(tmp_path))
        assert body["files"], "no profiler artifacts captured"

    await with_client(app, go)
    engine.run_to_completion()
    assert len(engine.result(ticket)) == 4


async def test_http_device_profile_without_engine_is_501(local_executor):
    """No engine attached: 501 with the reason, and no probe computation —
    this process must not take the device from the sandbox that owns it."""
    profiler = DeviceProfiler(ServingMonitor(metrics=Registry()))
    app = make_app(local_executor, device_profiler=profiler)

    async def go(client):
        resp = await client.post(
            "/v1/profile", json={"target": "device", "steps": 2}
        )
        assert resp.status == 501
        assert "no in-process engine" in (await resp.json())["detail"]

    await with_client(app, go)


async def test_http_device_profile_unwired_is_501(local_executor):
    async def go(client):
        resp = await client.post("/v1/profile", json={"target": "device"})
        assert resp.status == 501
        assert "device profiling unavailable" in (await resp.json())["detail"]

    await with_client(make_app(local_executor), go)


async def test_grpc_get_accelerator_twin(local_executor):
    device = DeviceMonitor(metrics=Registry())
    device.on_compile(
        "prefill_forward", signature="(i32[4])", duration_ms=7.0,
        trigger="first_call",
    )
    server = GrpcServer(
        code_executor=local_executor,
        custom_tool_executor=CustomToolExecutor(code_executor=local_executor),
        metrics=Registry(),
        device=device,
    )
    port = await server.start("127.0.0.1:0")
    try:
        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as channel:
            obs = observability_stubs(channel)
            snap = json.loads(await obs["GetAccelerator"](b""))
            assert sorted(snap) == [
                "attached", "compile", "kv_pool", "memory", "mesh", "steps",
            ]
            assert snap["compile"]["functions"]["prefill_forward"][
                "compiles"
            ] == 1
            trimmed = json.loads(
                await obs["GetAccelerator"](b'{"recent": 0}')
            )
            assert trimmed["compile"]["recent"] == []
            # 400 ↔ INVALID_ARGUMENT parity with the HTTP edge
            for payload in (b"not json", b'{"recent": -1}', b'{"recent": "x"}'):
                with pytest.raises(grpc.aio.AioRpcError) as excinfo:
                    await obs["GetAccelerator"](payload)
                assert (
                    excinfo.value.code() == grpc.StatusCode.INVALID_ARGUMENT
                )
    finally:
        await server.stop(None)


async def test_grpc_get_accelerator_unimplemented_without_monitor(
    local_executor,
):
    server = GrpcServer(
        code_executor=local_executor,
        custom_tool_executor=CustomToolExecutor(code_executor=local_executor),
        metrics=Registry(),
    )
    port = await server.start("127.0.0.1:0")
    try:
        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as channel:
            obs = observability_stubs(channel)
            with pytest.raises(grpc.aio.AioRpcError) as excinfo:
                await obs["GetAccelerator"](b"")
            assert excinfo.value.code() == grpc.StatusCode.UNIMPLEMENTED
    finally:
        await server.stop(None)


# ------------------------------------------------------------- overhead A/B


@pytest.mark.slow
def test_serving_bench_overhead_includes_device_monitor():
    """The bench's instrumented arm now carries the DeviceMonitor too, so
    its measured overhead prices compile tracking + per-step telemetry.
    Budget enforcement stays the bench artifact's job (CI boxes are too
    noisy for a hard < 5% assert here); this pins the fields and that the
    instrumented arm still produces tokens."""
    from bee_code_interpreter_tpu.models.serving_bench import (
        run_serving_bench,
    )

    result = run_serving_bench(
        n_requests=2, max_new_tokens=8, repeats=2, inner=1, max_batch=2
    )
    assert result["tokens_per_s"] > 0
    assert result["uninstrumented_tokens_per_s"] > 0
    assert result["overhead_pct"] >= 0.0
    assert result["overhead_budget_pct"] == 5.0
    assert isinstance(result["overhead_ok"], bool)
