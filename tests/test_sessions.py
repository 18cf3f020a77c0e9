"""Sessions acceptance (ISSUE 7): leased sandboxes over the fake-pod stack,
checkpoint/rollback through content-addressed storage, live output
streaming on both paths, and the supervisor/drain/chaos integration that
keeps leases honest.

The fake-pod stack is the REAL KubernetesCodeExecutor + real SessionManager
against in-process executor servers (tests/fakes.py) — production wiring
minus kubectl, exactly like the chaos suites."""

import asyncio
import json
import time

import pytest

from bee_code_interpreter_tpu.config import Config
from bee_code_interpreter_tpu.resilience import (
    PoolSupervisor,
    SandboxTransientError,
)
from bee_code_interpreter_tpu.services.kubernetes_code_executor import (
    KubernetesCodeExecutor,
)
from bee_code_interpreter_tpu.sessions import (
    SessionLimitExceeded,
    SessionManager,
    SessionNotFound,
    streamed_events,
)
from bee_code_interpreter_tpu.utils.metrics import Registry
from tests.chaos import FaultPlan, ManualClock
from tests.fakes import FakeExecutorPods, FakeKubectl

pytestmark = pytest.mark.chaos


@pytest.fixture
def faults():
    return FaultPlan()


@pytest.fixture
def pods(tmp_path, faults):
    return FakeExecutorPods(tmp_path / "pods", faults=faults)


def make_k8s(pods, storage, *, metrics=None, queue_len=1, **overrides):
    config = Config(
        executor_backend="kubernetes",
        executor_port=pods.port,
        executor_pod_queue_target_length=queue_len,
        pod_ready_timeout_s=5,
        executor_retry_attempts=1,
        **overrides,
    )
    return KubernetesCodeExecutor(
        kubectl=FakeKubectl(pods),
        storage=storage,
        config=config,
        metrics=metrics,
        ip_poll_interval_s=0.02,
    )


def make_manager(executor, storage, **kwargs):
    kwargs.setdefault("max_sessions", 4)
    kwargs.setdefault("ttl_s", 60.0)
    kwargs.setdefault("idle_s", 60.0)
    return SessionManager(executor, storage, **kwargs)


# ------------------------------------------------- lease over the fake pods


async def test_one_lease_serves_many_executes_on_one_sandbox(
    pods, storage
):
    """The acceptance core: one lease, 3 executes with a checkpoint +
    rollback in between, all on a SINGLE sandbox (the fleet journal shows
    exactly one assignment), with workspace state persisting across
    executes and rollback undoing post-checkpoint changes."""
    k8s = make_k8s(pods, storage)
    manager = make_manager(k8s, storage)
    try:
        await k8s.fill_executor_pod_queue()
        session = await manager.create()

        _, o1 = await manager.execute(
            session.session_id,
            "open('state.txt', 'w').write('v1')\nprint('one')",
        )
        assert o1.stdout == "one\n" and o1.exit_code == 0
        assert "/workspace/state.txt" in o1.changed_paths

        _, checkpoint = await manager.checkpoint(session.session_id)
        assert set(checkpoint.files) == {"/workspace/state.txt"}
        # The checkpoint map is real content-addressed storage objects.
        assert (await storage.read(checkpoint.files["/workspace/state.txt"])) == b"v1"

        _, o2 = await manager.execute(
            session.session_id,
            "open('state.txt', 'w').write('v2')\n"
            "open('stray.txt', 'w').write('x')\nprint('two')",
        )
        assert o2.stdout == "two\n"

        await manager.rollback(session.session_id, checkpoint.checkpoint_id)

        _, o3 = await manager.execute(
            session.session_id,
            "import os\n"
            "print(open('state.txt').read(), os.path.exists('stray.txt'))",
        )
        assert o3.stdout == "v1 False\n"  # content restored, stray evicted

        events = k8s.journal.events()
        assigned = [e for e in events if e["state"] == "assigned"]
        assert len(assigned) == 1, assigned  # ONE sandbox for the whole lease
        leased = [e for e in events if e["state"] == "leased"]
        assert leased and leased[-1]["session"] == session.session_id

        await manager.release(session.session_id)
        terminal = [
            e
            for e in k8s.journal.events()
            if e["state"] in ("released", "lease_expired", "reaped")
        ]
        assert [(e["state"], e.get("reason")) for e in terminal] == [
            ("released", "lease_released")
        ]
        with pytest.raises(SessionNotFound):
            manager.get(session.session_id)
    finally:
        await manager.close_all()
        await pods.close()


async def test_in_session_execute_skips_what_the_stateless_path_pays(
    pods, storage
):
    """The point of the lease: executes inside it skip checkout, restore and
    snapshot, which the stateless path pays for the SAME payload on the same
    stack every time. Held as counts of what each path did (the sandboxes
    the fleet journal assigned, the files the pods saw move), not as a race
    between two medians on a host that six test workers share."""
    k8s = make_k8s(pods, storage, queue_len=2)
    manager = make_manager(k8s, storage)
    # The payload writes a file so the stateless path pays a real snapshot
    # download per execute — exactly the tax sessions amortize.
    payload = "open('out.bin', 'wb').write(b'x' * 65536)\nprint('ok')"

    def assigned():
        return sum(e["state"] == "assigned" for e in k8s.journal.events())

    try:
        await k8s.fill_executor_pod_queue()
        for n in range(1, 6):
            result = await k8s.execute(payload)
            assert result.stdout == "ok\n"
            # a sandbox of its own and a download of the changed file, each
            assert assigned() == n
            assert pods.op_counts == {"execute": n, "upload": 0, "download": n}
            await asyncio.sleep(0.05)  # let the refill land
        session = await manager.create()
        before = dict(pods.op_counts)
        for n in range(1, 7):
            _, outcome = await manager.execute(session.session_id, payload)
            assert outcome.stdout == "ok\n"
            assert "/workspace/out.bin" in outcome.changed_paths
            # no restore, no snapshot download, no second sandbox
            assert pods.op_counts == {**before, "execute": before["execute"] + n}
        assert assigned() == 6  # the lease's one, for all six executes
        assert sorted(pods.execute_counts.values()) == [1, 1, 1, 1, 1, 6]
    finally:
        await manager.close_all()
        await pods.close()


async def test_lease_cap_and_bad_restore(pods, storage):
    k8s = make_k8s(pods, storage, queue_len=2)
    metrics = Registry()
    manager = make_manager(k8s, storage, max_sessions=1, metrics=metrics)
    try:
        await k8s.fill_executor_pod_queue()
        session = await manager.create()
        with pytest.raises(SessionLimitExceeded):
            await manager.create()
        await manager.release(session.session_id)
        # A create whose initial restore fails must not leak its lease.
        with pytest.raises(Exception):
            await manager.create(files={"/workspace/a": "0" * 64})
        assert manager.active_count == 0
        ends = metrics.metrics["bci_session_expirations_total"]._values
        assert ends.get((("reason", "sandbox_died"),), 0) == 1
        session2 = await manager.create()  # the slot is actually free again
        assert manager.active_count == 1
        await manager.release(session2.session_id)
    finally:
        await manager.close_all()
        await pods.close()


# ------------------------------------------------------------ expiry sweeps


async def test_ttl_idle_and_drain_expiry(pods, storage):
    clock = ManualClock()
    metrics = Registry()
    k8s = make_k8s(pods, storage, queue_len=2)
    manager = make_manager(
        k8s, storage, ttl_s=100.0, idle_s=30.0, metrics=metrics, clock=clock
    )
    try:
        await k8s.fill_executor_pod_queue()
        idle_victim = await manager.create()
        await manager.execute(idle_victim.session_id, "print(1)")
        survivor = await manager.create()

        clock.advance(31.0)  # idle_victim past idle; survivor just created?
        # survivor was created at t=0 too — touch it so only idle matters
        await manager.execute(survivor.session_id, "print(2)")
        expired = await manager.sweep_once()
        assert expired == 1 and manager.active_count == 1
        assert (
            manager.get(survivor.session_id).session_id
            == survivor.session_id
        )
        with pytest.raises(SessionNotFound):
            manager.get(idle_victim.session_id)

        clock.advance(80.0)  # survivor's TTL (100s) now exceeded
        await manager.execute(survivor.session_id, "print(3)")  # active but old
        assert await manager.sweep_once() == 1
        events = [
            (e.get("reason"))
            for e in k8s.journal.events()
            if e["state"] == "lease_expired"
        ]
        assert sorted(events) == ["idle", "ttl"]
        ends = metrics.metrics["bci_session_expirations_total"]._values
        assert ends.get((("reason", "idle"),), 0) == 1
        assert ends.get((("reason", "ttl"),), 0) == 1
    finally:
        await manager.close_all()
        await pods.close()


async def test_drain_bounds_lease_lifetimes(pods, storage):
    from bee_code_interpreter_tpu.resilience import DrainController

    drain = DrainController()
    metrics = Registry()
    k8s = make_k8s(pods, storage)
    manager = make_manager(k8s, storage, metrics=metrics, drain=drain)
    try:
        await k8s.fill_executor_pod_queue()
        session = await manager.create()
        assert await manager.sweep_once() == 0  # healthy lease, no drain
        drain.begin()
        assert await manager.sweep_once() == 1  # drain reclaims it NOW
        assert manager.active_count == 0
        events = [
            e
            for e in k8s.journal.events()
            if e["state"] == "lease_expired" and e.get("reason") == "drain"
        ]
        assert len(events) == 1
        ends = metrics.metrics["bci_session_expirations_total"]._values
        assert ends.get((("reason", "drain"),), 0) == 1
        assert session.closed
    finally:
        await manager.close_all()
        await pods.close()


# ------------------------------------------- supervisor/watchdog integration


async def test_leased_idle_sandbox_survives_supervisor_sweep(pods, storage):
    """A leased, healthy-but-idle sandbox is OWNED, not stuck: the
    supervisor's idle reaper (which probes only queued inventory) and the
    stuck-execution watchdog (which sees only in-flight executes) must both
    leave it alone — while a genuinely wedged leased execute still dies."""
    k8s = make_k8s(pods, storage, queue_len=1)
    manager = make_manager(k8s, storage)
    supervisor = PoolSupervisor(k8s, interval_s=60, execute_hard_cap_s=0.3)
    try:
        await k8s.fill_executor_pod_queue()
        session = await manager.create()
        await manager.execute(session.session_id, "print('warm')")
        swept = await supervisor.sweep_once()
        assert swept["reaped"] == 0 and swept["watchdog_killed"] == 0
        # The lease is alive and still serves.
        _, outcome = await manager.execute(session.session_id, "print('still')")
        assert outcome.stdout == "still\n"
        reaps = [e for e in k8s.journal.events() if e["state"] == "reaped"]
        assert reaps == []
    finally:
        await manager.close_all()
        await pods.close()


async def test_watchdog_kills_wedged_leased_execute(pods, storage, faults):
    metrics = Registry()
    k8s = make_k8s(pods, storage, queue_len=1)
    manager = make_manager(k8s, storage, metrics=metrics)
    supervisor = PoolSupervisor(k8s, interval_s=60, execute_hard_cap_s=0.2)
    try:
        await k8s.fill_executor_pod_queue()
        session = await manager.create()
        faults.hang_execute(30.0)
        request = asyncio.ensure_future(
            manager.execute(session.session_id, "print('wedged')")
        )
        await asyncio.sleep(0.3)
        swept = await supervisor.sweep_once()
        assert swept["watchdog_killed"] == 1
        with pytest.raises(SandboxTransientError):
            await request
        # The kill ended the lease: reaped with the watchdog's reason, the
        # session is gone, and the end is accounted.
        assert manager.active_count == 0
        reaped = [
            e
            for e in k8s.journal.events()
            if e["state"] == "reaped" and e.get("reason") == "hung_execute"
        ]
        assert len(reaped) == 1
        ends = metrics.metrics["bci_session_expirations_total"]._values
        assert ends.get((("reason", "sandbox_died"),), 0) == 1
    finally:
        await manager.close_all()
        await pods.close()


# ----------------------------------------------------- chaos: scenario 10


async def test_vanished_stream_client_lease_reaped_on_ttl(pods, storage):
    """Chaos scenario 10a/10b in tier-1: a streaming client vanishes
    mid-chunk — the lease survives the disconnect and the TTL sweep reaps
    it; the pool refills; accounting is exact."""
    clock = ManualClock()
    metrics = Registry()
    k8s = make_k8s(pods, storage, queue_len=1)
    manager = make_manager(
        k8s, storage, ttl_s=5.0, idle_s=60.0, metrics=metrics, clock=clock
    )
    try:
        await k8s.fill_executor_pod_queue()
        session = await manager.create()
        got_chunk = asyncio.Event()

        async def on_event(kind, text):
            got_chunk.set()

        vanished = asyncio.ensure_future(
            manager.execute(
                session.session_id,
                "import time\nprint('c', flush=True)\ntime.sleep(20)",
                on_event=on_event,
            )
        )
        await asyncio.wait_for(got_chunk.wait(), timeout=10)
        vanished.cancel()
        with pytest.raises(asyncio.CancelledError):
            await vanished
        assert manager.active_count == 1  # the lease survives the client

        clock.advance(6.0)  # past the TTL
        assert await manager.sweep_once() == 1
        for _ in range(300):  # lease end kicks a refill fire-and-forget
            if k8s.pool_ready_count >= 1:
                break
            await asyncio.sleep(0.01)
        assert k8s.pool_ready_count >= 1
        ttl_ends = [
            e
            for e in k8s.journal.events()
            if e["state"] == "lease_expired" and e.get("reason") == "ttl"
        ]
        assert len(ttl_ends) == 1
        ends = metrics.metrics["bci_session_expirations_total"]._values
        assert ends == {(("reason", "ttl"),): 1}
    finally:
        await manager.close_all()
        await pods.close()


async def test_sandbox_death_mid_lease_and_terminal_error_event(
    pods, storage, faults
):
    """Chaos scenario 10c/10d in tier-1: the sandbox dies mid-lease (the
    session ends as reaped/died_mid_lease, pool refills) and a stateless
    stream whose pod dies delivers a terminal error event."""
    metrics = Registry()
    k8s = make_k8s(pods, storage, queue_len=1)
    manager = make_manager(k8s, storage, metrics=metrics)
    try:
        await k8s.fill_executor_pod_queue()
        session = await manager.create()
        faults.die_mid_execute()
        with pytest.raises(SandboxTransientError):
            await manager.execute(session.session_id, "print('x')")
        assert manager.active_count == 0
        died = [
            e
            for e in k8s.journal.events()
            if e["state"] == "reaped" and e.get("reason") == "died_mid_lease"
        ]
        assert len(died) == 1
        ends = metrics.metrics["bci_session_expirations_total"]._values
        assert ends.get((("reason", "sandbox_died"),), 0) == 1

        faults.die_mid_execute()

        async def run(on_event):
            return await k8s.execute_stream("print('doomed')", on_event=on_event)

        events = [item async for item in streamed_events(run)]
        assert events and events[-1].get("event") == "error"
        assert isinstance(events[-1]["error"], SandboxTransientError)
    finally:
        await manager.close_all()
        await pods.close()


# ------------------------------------------------------------- HTTP edge


def make_app(executor, storage, metrics, manager=None, tracer=None, **kwargs):
    from bee_code_interpreter_tpu.api.http_server import create_http_server
    from bee_code_interpreter_tpu.services.custom_tool_executor import (
        CustomToolExecutor,
    )

    return create_http_server(
        code_executor=executor,
        custom_tool_executor=CustomToolExecutor(code_executor=executor),
        metrics=metrics,
        tracer=tracer,
        sessions=manager,
        **kwargs,
    )


async def sse_events(resp):
    """[(event, parsed data), ...] from an SSE response body."""
    out = []
    event = None
    async for raw in resp.content:
        line = raw.decode().rstrip("\n")
        if line.startswith("event: "):
            event = line[len("event: "):]
        elif line.startswith("data: "):
            out.append((event, json.loads(line[len("data: "):])))
    return out


async def test_http_sse_streams_chunks_with_matching_trace(pods, storage):
    """Acceptance: an SSE client observes >=2 stdout chunks before the
    terminal event, and the terminal envelope's trace_id resolves in
    /v1/traces."""
    from aiohttp.test_utils import TestClient, TestServer

    from bee_code_interpreter_tpu.observability import Tracer, TraceStore

    metrics = Registry()
    tracer = Tracer(store=TraceStore(), metrics=metrics)
    k8s = make_k8s(pods, storage, metrics=metrics)
    manager = make_manager(k8s, storage, metrics=metrics)
    app = make_app(k8s, storage, metrics, manager, tracer=tracer)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await k8s.fill_executor_pod_queue()
        resp = await client.post(
            "/v1/execute?stream=1",
            json={
                "source_code": (
                    "import time\n"
                    "print('alpha', flush=True)\n"
                    "time.sleep(0.25)\n"
                    "print('omega', flush=True)\n"
                )
            },
        )
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        events = await sse_events(resp)
        stdout_chunks = [d["text"] for e, d in events if e == "stdout"]
        terminals = [d for e, d in events if e == "result"]
        assert len(stdout_chunks) >= 2, events
        assert events[-1][0] == "result" and len(terminals) == 1
        result = terminals[0]
        assert result["stdout"] == "alpha\nomega\n"
        assert result["exit_code"] == 0
        # chunks arrived BEFORE the terminal event carried the total
        assert "".join(stdout_chunks) == result["stdout"]
        trace = await client.get(f"/v1/traces/{result['trace_id']}")
        assert trace.status == 200
        assert (await trace.json())["trace_id"] == result["trace_id"]
    finally:
        await client.close()
        await manager.close_all()
        await pods.close()


async def test_http_session_routes_end_to_end(pods, storage):
    from aiohttp.test_utils import TestClient, TestServer

    from bee_code_interpreter_tpu.analysis import WorkloadAnalyzer

    metrics = Registry()
    k8s = make_k8s(pods, storage, metrics=metrics, queue_len=2)
    manager = make_manager(k8s, storage, max_sessions=1, metrics=metrics)
    app = make_app(
        k8s, storage, metrics, manager, analyzer=WorkloadAnalyzer(metrics=metrics)
    )
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await k8s.fill_executor_pod_queue()
        resp = await client.post("/v1/sessions", json={})
        assert resp.status == 200
        created = await resp.json()
        sid = created["session_id"]
        assert created["expires_at"] > time.time()

        # cap: the second lease sheds with Retry-After, like admission
        resp = await client.post("/v1/sessions", json={})
        assert resp.status == 429 and "Retry-After" in resp.headers

        resp = await client.post(
            f"/v1/sessions/{sid}/execute",
            json={"source_code": "open('f.txt','w').write('1')\nprint('a')"},
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["stdout"] == "a\n" and body["execution"] == 1
        assert body["changed_paths"] == ["/workspace/f.txt"]
        assert body["session_id"] == sid and body["trace_id"]

        # the syntax gate fail-fasts without burning a lease execute
        execs_before = k8s.journal.executions_total
        resp = await client.post(
            f"/v1/sessions/{sid}/execute", json={"source_code": "def broken(:"}
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["exit_code"] == 1 and "SyntaxError" in body["stderr"]
        assert k8s.journal.executions_total == execs_before

        resp = await client.post(f"/v1/sessions/{sid}/checkpoint")
        checkpoint = await resp.json()
        assert resp.status == 200
        assert list(checkpoint["files"]) == ["/workspace/f.txt"]

        resp = await client.post(
            f"/v1/sessions/{sid}/execute",
            json={"source_code": "open('f.txt','w').write('2')\nprint('b')"},
        )
        assert resp.status == 200

        resp = await client.post(
            f"/v1/sessions/{sid}/rollback",
            json={"checkpoint_id": checkpoint["checkpoint_id"]},
        )
        assert resp.status == 200

        resp = await client.post(
            f"/v1/sessions/{sid}/execute",
            json={"source_code": "print(open('f.txt').read())"},
        )
        assert (await resp.json())["stdout"] == "1\n"

        # unknown checkpoint and unknown session → 404
        resp = await client.post(
            f"/v1/sessions/{sid}/rollback", json={"checkpoint_id": "nope"}
        )
        assert resp.status == 404
        resp = await client.post(
            "/v1/sessions/sess-missing/execute",
            json={"source_code": "print(1)"},
        )
        assert resp.status == 404

        # /v1/fleet shows the leased sandbox with its owner + lease age
        snap = await (await client.get("/v1/fleet")).json()
        leased_pods = [p for p in snap["pods"] if p["state"] == "leased"]
        assert len(leased_pods) == 1
        assert leased_pods[0]["session"] == sid
        assert leased_pods[0]["lease_age_s"] >= 0
        # 4 POSTs, but the syntax fail-fast never touched the sandbox
        assert leased_pods[0]["executions"] == 3
        assert snap["sessions"]["active"] == 1

        resp = await client.delete(f"/v1/sessions/{sid}")
        assert resp.status == 200 and (await resp.json())["released"]
        resp = await client.delete(f"/v1/sessions/{sid}")
        assert resp.status == 404
    finally:
        await client.close()
        await manager.close_all()
        await pods.close()


async def test_http_sessionful_sse_and_drain(pods, storage):
    from aiohttp.test_utils import TestClient, TestServer

    from bee_code_interpreter_tpu.resilience import DrainController

    metrics = Registry()
    drain = DrainController()
    k8s = make_k8s(pods, storage, metrics=metrics)
    manager = make_manager(k8s, storage, metrics=metrics, drain=drain)
    app = make_app(k8s, storage, metrics, manager, drain=drain)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await k8s.fill_executor_pod_queue()
        sid = (await (await client.post("/v1/sessions", json={})).json())[
            "session_id"
        ]
        resp = await client.post(
            f"/v1/sessions/{sid}/execute?stream=1",
            json={
                "source_code": (
                    "import time\n"
                    "print('s1', flush=True)\n"
                    "time.sleep(0.25)\n"
                    "print('s2', flush=True)\n"
                )
            },
        )
        events = await sse_events(resp)
        chunks = [d["text"] for e, d in events if e == "stdout"]
        assert len(chunks) >= 2
        terminal = events[-1]
        assert terminal[0] == "result"
        assert terminal[1]["session_id"] == sid
        assert terminal[1]["stdout"] == "s1\ns2\n"

        # drain: no new leases, no session executes; existing lease expires
        drain.begin()
        resp = await client.post("/v1/sessions", json={})
        assert resp.status == 503
        resp = await client.post(
            f"/v1/sessions/{sid}/execute", json={"source_code": "print(1)"}
        )
        assert resp.status == 503
        assert await manager.sweep_once() == 1
        ends = metrics.metrics["bci_session_expirations_total"]._values
        assert ends.get((("reason", "drain"),), 0) == 1
    finally:
        await client.close()
        await manager.close_all()
        await pods.close()


async def test_http_sse_mid_stream_failure_burns_slo_budget(
    pods, storage, faults
):
    """SSE spends its 200 at prepare time, so a mid-stream sandbox death is
    an in-band error event — but the SLI sample must still be bad, exactly
    like the buffered path's 500 and the gRPC ExecuteStream twin."""
    from aiohttp.test_utils import TestClient, TestServer

    class SloSpy:
        def __init__(self):
            self.samples = []

        def record(self, ok, duration_s):
            self.samples.append(ok)

    metrics = Registry()
    slo = SloSpy()
    k8s = make_k8s(pods, storage, metrics=metrics)
    app = make_app(k8s, storage, metrics, slo=slo)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await k8s.fill_executor_pod_queue()
        resp = await client.post(
            "/v1/execute?stream=1", json={"source_code": "print('ok')"}
        )
        events = await sse_events(resp)
        assert events[-1][0] == "result"
        assert slo.samples == [True]

        faults.die_mid_execute()
        resp = await client.post(
            "/v1/execute?stream=1", json={"source_code": "print('doomed')"}
        )
        assert resp.status == 200  # the status was already spent
        events = await sse_events(resp)
        assert events[-1][0] == "error"
        assert slo.samples == [True, False]
    finally:
        await client.close()
        await pods.close()


# ------------------------------------------------------------- gRPC edge


async def test_grpc_session_service_and_execute_stream(pods, storage):
    import grpc.aio

    from bee_code_interpreter_tpu.analysis import WorkloadAnalyzer
    from bee_code_interpreter_tpu.api.grpc_server import (
        GrpcServer,
        execute_stream_stub,
        session_stubs,
    )
    from bee_code_interpreter_tpu.services.custom_tool_executor import (
        CustomToolExecutor,
    )

    metrics = Registry()
    k8s = make_k8s(pods, storage, metrics=metrics, queue_len=2)
    manager = make_manager(k8s, storage, metrics=metrics)
    server = GrpcServer(
        k8s,
        CustomToolExecutor(code_executor=k8s),
        metrics=metrics,
        request_deadline_s=30,
        sessions=manager,
        analyzer=WorkloadAnalyzer(metrics=metrics),
    )
    port = await server.start("127.0.0.1:0")
    try:
        await k8s.fill_executor_pod_queue()
        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as channel:
            stubs = session_stubs(channel)
            created = json.loads(await stubs["CreateSession"](b"{}"))
            sid = created["session_id"]

            result = json.loads(
                await stubs["ExecuteInSession"](
                    json.dumps(
                        {
                            "session_id": sid,
                            "source_code": (
                                "open('g.txt','w').write('g1')\nprint('go')"
                            ),
                        }
                    ).encode()
                )
            )
            assert result["stdout"] == "go\n" and result["execution"] == 1

            checkpoint = json.loads(
                await stubs["Checkpoint"](
                    json.dumps({"session_id": sid}).encode()
                )
            )
            assert list(checkpoint["files"]) == ["/workspace/g.txt"]

            rolled = json.loads(
                await stubs["Rollback"](
                    json.dumps(
                        {
                            "session_id": sid,
                            "checkpoint_id": checkpoint["checkpoint_id"],
                        }
                    ).encode()
                )
            )
            assert rolled["checkpoint_id"] == checkpoint["checkpoint_id"]

            # policy/deny parity: gRPC session execute aborts INVALID_ARGUMENT
            # for a denied import exactly like the stateless RPC
            server_analyzer_denied = False
            try:
                await stubs["ExecuteInSession"](
                    json.dumps(
                        {"session_id": sid, "source_code": "def broken(:"}
                    ).encode()
                )
            except grpc.aio.AioRpcError:
                server_analyzer_denied = True
            assert not server_analyzer_denied  # syntax error is a normal reply

            # sessionful server stream: >=2 chunks then a terminal result
            call = execute_stream_stub(channel)(
                json.dumps(
                    {
                        "session_id": sid,
                        "source_code": (
                            "import time\n"
                            "print('g1', flush=True)\n"
                            "time.sleep(0.25)\n"
                            "print('g2', flush=True)\n"
                        ),
                    }
                ).encode()
            )
            events = [json.loads(raw) async for raw in call]
            chunks = [e for e in events if e.get("stream") == "stdout"]
            assert len(chunks) >= 2
            assert events[-1]["event"] == "result"
            assert events[-1]["session_id"] == sid
            assert events[-1]["stdout"] == "g1\ng2\n"

            # stateless stream through the same RPC (no session_id)
            events = [
                json.loads(raw)
                async for raw in execute_stream_stub(channel)(
                    json.dumps({"source_code": "print('solo')"}).encode()
                )
            ]
            assert events[-1]["event"] == "result"
            assert events[-1]["stdout"] == "solo\n"

            released = json.loads(
                await stubs["DeleteSession"](
                    json.dumps({"session_id": sid}).encode()
                )
            )
            assert released["released"] is True
            with pytest.raises(grpc.aio.AioRpcError) as err:
                await stubs["ExecuteInSession"](
                    json.dumps(
                        {"session_id": sid, "source_code": "print(1)"}
                    ).encode()
                )
            assert err.value.code() == grpc.StatusCode.NOT_FOUND
    finally:
        await server.stop(grace=0.5)
        await manager.close_all()
        await pods.close()


async def test_grpc_create_session_rejects_malformed_lease_params(
    pods, storage
):
    """The JSON-bytes gRPC edge has no pydantic message, so the manager is
    the validation backstop — a malformed ttl_s/files must answer
    INVALID_ARGUMENT (the twin of HTTP's 422, SLI-good) BEFORE any sandbox
    is checked out, never UNKNOWN."""
    import grpc.aio

    from bee_code_interpreter_tpu.api.grpc_server import (
        GrpcServer,
        session_stubs,
    )
    from bee_code_interpreter_tpu.services.custom_tool_executor import (
        CustomToolExecutor,
    )

    metrics = Registry()
    k8s = make_k8s(pods, storage, metrics=metrics)
    manager = make_manager(k8s, storage, metrics=metrics)
    server = GrpcServer(
        k8s,
        CustomToolExecutor(code_executor=k8s),
        metrics=metrics,
        request_deadline_s=30,
        sessions=manager,
    )
    port = await server.start("127.0.0.1:0")
    try:
        await k8s.fill_executor_pod_queue()
        async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as channel:
            stubs = session_stubs(channel)
            for body in (
                {"ttl_s": "abc"},
                {"ttl_s": -5},
                {"idle_s": 0},
                {"files": [1, 2]},
                {"files": {"/workspace/a.txt": 7}},
            ):
                with pytest.raises(grpc.aio.AioRpcError) as err:
                    await stubs["CreateSession"](json.dumps(body).encode())
                assert (
                    err.value.code() == grpc.StatusCode.INVALID_ARGUMENT
                ), body
        # rejected before checkout: no lease leaked, no sandbox consumed
        assert manager.active_count == 0
        assert not [
            e for e in k8s.journal.events() if e["state"] == "leased"
        ]
    finally:
        await server.stop(grace=0.5)
        await manager.close_all()
        await pods.close()


# ------------------------------------------------------ local-backend lease


async def test_local_backend_sessions(local_executor, storage):
    """Sessions work over the in-process backend too (the dev/e2e python
    path): persistent workspace, checkpoint/rollback, streaming."""
    manager = make_manager(local_executor, storage)
    session = await manager.create()
    try:
        _, o1 = await manager.execute(
            session.session_id, "open('l.txt','w').write('L1')\nprint('one')"
        )
        assert o1.stdout == "one\n"
        _, checkpoint = await manager.checkpoint(session.session_id)
        assert set(checkpoint.files) == {"/workspace/l.txt"}
        await manager.execute(
            session.session_id,
            "open('l.txt','w').write('L2')\nopen('s.txt','w').write('s')",
        )
        await manager.rollback(session.session_id, checkpoint.checkpoint_id)
        _, o2 = await manager.execute(
            session.session_id,
            "import os\nprint(open('l.txt').read(), os.path.exists('s.txt'))",
        )
        assert o2.stdout == "L1 False\n"

        chunks = []

        async def on_event(kind, text):
            chunks.append((kind, text))

        _, streamed = await manager.execute(
            session.session_id,
            "import time\nprint('x', flush=True)\ntime.sleep(0.2)\nprint('y')",
            on_event=on_event,
        )
        assert streamed.stdout == "x\ny\n"
        assert any(kind == "stdout" for kind, _ in chunks)
    finally:
        await manager.close_all()


# --------------------------------------------------- core streaming contract


async def test_executor_core_stream_timeout_matches_buffered_contract(tmp_path):
    from bee_code_interpreter_tpu.runtime.executor_core import (
        EXECUTION_TIMED_OUT,
        ExecutorCore,
    )

    core = ExecutorCore(workspace=tmp_path / "ws", disable_dep_install=True)
    seen = []
    outcome = None
    gen = core.execute_stream(
        "import time\nprint('pre', flush=True)\ntime.sleep(30)",
        timeout_s=0.5,
    )
    async for kind, payload in gen:
        if kind == "end":
            outcome = payload
        else:
            seen.append((kind, payload))
    # chunks delivered before the timeout stay delivered (boundaries are
    # whatever the pipe carried); the envelope mirrors the buffered path's
    # timeout contract exactly
    assert "pre\n" in "".join(t for k, t in seen if k == "stdout")
    assert outcome.exit_code == -1
    assert outcome.stdout == "" and outcome.stderr == EXECUTION_TIMED_OUT


async def test_executor_core_abandoned_stream_reaps_child(tmp_path):
    from bee_code_interpreter_tpu.runtime.executor_core import ExecutorCore

    core = ExecutorCore(workspace=tmp_path / "ws", disable_dep_install=True)
    marker = tmp_path / "ws" / "still-running.txt"
    gen = core.execute_stream(
        "import time\n"
        "print('started', flush=True)\n"
        "time.sleep(3)\n"
        "open('still-running.txt', 'w').write('leaked')\n",
        timeout_s=30,
    )
    async for kind, payload in gen:
        if kind == "stdout":
            break  # consumer vanishes after the first chunk
    await gen.aclose()
    # the child was killed with the stream: it never got to write the marker
    await asyncio.sleep(0.3)
    assert not marker.exists()


async def test_executor_core_cancelled_execute_reaps_child(tmp_path):
    """The buffered twin of the abandoned-stream contract: cancelling an
    in-flight execute (vanished client, watchdog kill) must not leave the
    user process mutating the workspace — under a lease that workspace
    survives the call, and an orphan would corrupt the next REPL turn."""
    from bee_code_interpreter_tpu.runtime.executor_core import ExecutorCore

    core = ExecutorCore(workspace=tmp_path / "ws", disable_dep_install=True)
    marker = tmp_path / "ws" / "still-running.txt"
    task = asyncio.ensure_future(
        core.execute(
            "import time\n"
            "time.sleep(1)\n"
            "open('still-running.txt', 'w').write('leaked')\n",
            timeout_s=30,
        )
    )
    await asyncio.sleep(0.4)  # let the child start its sleep
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task
    # an orphan would write the marker ~0.6s from now; a killed child never
    # does — wait past that point so a leak cannot pass silently
    await asyncio.sleep(1.2)
    assert not marker.exists()
