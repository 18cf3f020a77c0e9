"""Fleet observability (ISSUE 3): the sandbox lifecycle journal, the
per-execution usage accounting, and the profiling plumbing — at the unit
level and driven through the real executors against the fake cluster with
scripted chaos (tests/chaos.py)."""

import asyncio

import pytest

from bee_code_interpreter_tpu.config import Config
from bee_code_interpreter_tpu.observability import (
    FleetJournal,
    ProfilerUnavailable,
    ServingProfiler,
    collect_transfer,
    inject_profile_env,
    merge_worker_usage,
    record_transfer,
)
from bee_code_interpreter_tpu.services.kubernetes_code_executor import (
    KubernetesCodeExecutor,
)
from bee_code_interpreter_tpu.utils.metrics import Registry
from tests.chaos import ChaosKubectl, Fail, FaultPlan
from tests.fakes import FakeExecutorPods, FakeKubectl


# ------------------------------------------------------------ journal units


def test_journal_records_full_lifecycle_and_snapshot():
    journal = FleetJournal()
    journal.record("pod-a", "spawning", workers=2)
    journal.record("pod-a", "ready")
    journal.record("pod-a", "assigned", reason="warm_pop")
    journal.record("pod-a", "executing")
    snap = journal.snapshot()
    assert snap["live"] == 1
    assert snap["by_state"] == {"executing": 1}
    assert snap["utilization"] == 1.0
    assert snap["executions_total"] == 1
    (pod,) = snap["pods"]
    assert pod["workers"] == 2
    assert pod["executions"] == 1
    assert pod["spawn_s"] is not None

    journal.record("pod-a", "released", reason="single_use")
    snap = journal.snapshot()
    assert snap["live"] == 0
    assert snap["utilization"] == 0.0  # empty pool reads 0, never NaN
    # terminal events carry the pod's served-execution count and age
    released = journal.events(limit=1)[0]
    assert released["state"] == "released"
    assert released["executions"] == 1
    assert released["age_s"] >= 0

    states = [e["state"] for e in reversed(journal.events())]
    assert states == ["spawning", "ready", "assigned", "executing", "released"]


def test_journal_event_ring_is_bounded_and_limit_filters():
    journal = FleetJournal(max_events=8)
    for i in range(20):
        journal.record(f"pod-{i}", "spawning")
    assert len(journal) == 8
    events = journal.events(limit=3)
    assert len(events) == 3
    assert events[0]["pod"] == "pod-19"  # newest first
    # lifetime counters survive ring eviction
    assert journal.counts["spawning"] == 20


def test_journal_rejects_unknown_states():
    with pytest.raises(ValueError, match="unknown fleet state"):
        FleetJournal().record("pod-a", "meditating")


def test_journal_feeds_pool_metrics():
    metrics = Registry()
    journal = FleetJournal(metrics=metrics)
    journal.record("pod-a", "spawning")
    journal.record("pod-a", "ready")
    journal.record("pod-a", "assigned")
    journal.record("pod-b", "spawning")
    journal.record("pod-b", "failed", reason="spawn_failed", detail="apiserver down")
    journal.record("pod-c", "spawning")
    journal.record("pod-c", "ready")
    journal.record("pod-c", "reaped", reason="unhealthy")
    text = metrics.expose()
    assert "bci_pool_spawn_seconds_count 2" in text
    # the label stays CATEGORICAL (free text would mint unbounded series);
    # the free text lives on the journal event as `detail`
    assert 'bci_pod_reaped_total{reason="spawn_failed"} 1' in text
    assert 'bci_pod_reaped_total{reason="unhealthy"} 1' in text
    failed = next(e for e in journal.events() if e["state"] == "failed")
    assert failed["detail"] == "apiserver down"
    # one live pod (assigned), so utilization is 1.0
    assert "bci_pool_utilization 1" in text


# ------------------------------------------------------- accounting units


def test_merge_worker_usage_sums_cpu_maxes_rss():
    merged = merge_worker_usage(
        [
            {"cpu_user_s": 1.0, "cpu_system_s": 0.5, "max_rss_bytes": 100,
             "wall_s": 2.0, "workspace_bytes_written": 10,
             "files_changed": 1, "deps_installed": ["numpy"]},
            None,  # worker with an old server: no block
            {"cpu_user_s": 2.0, "cpu_system_s": 0.5, "max_rss_bytes": 300,
             "wall_s": 1.5, "workspace_bytes_written": 5,
             "files_changed": 2, "deps_installed": ["numpy", "pandas"]},
        ]
    )
    assert merged["cpu_user_s"] == 3.0
    assert merged["cpu_system_s"] == 1.0
    assert merged["max_rss_bytes"] == 300
    assert merged["wall_s"] == 2.0
    assert merged["workspace_bytes_written"] == 15
    assert merged["files_changed"] == 3
    assert merged["deps_installed"] == ["numpy", "pandas"]


def test_transfer_accounting_is_task_scoped():
    async def go():
        async def one(n):
            with collect_transfer() as acct:
                await asyncio.sleep(0.01)
                record_transfer("upload", n)
                await asyncio.sleep(0.01)
                record_transfer("download", n * 2)
            return acct

        a, b = await asyncio.gather(one(100), one(7))
        assert (a.uploaded_bytes, a.downloaded_bytes) == (100, 200)
        assert (b.uploaded_bytes, b.downloaded_bytes) == (7, 14)
        assert a.uploaded_files == a.downloaded_files == 1

    asyncio.run(go())
    # outside any scope, reporting is a no-op
    record_transfer("upload", 123)


# ------------------------------------ executors against the fake cluster


def make_executor(pods, storage, kubectl, metrics=None, **overrides):
    config = Config(
        executor_backend="kubernetes",
        executor_port=pods.port,
        executor_pod_queue_target_length=0,
        pod_ready_timeout_s=5,
        executor_retry_attempts=1,
        executor_retry_wait_min_s=0.01,
        executor_retry_wait_max_s=0.05,
        **overrides,
    )
    return KubernetesCodeExecutor(
        kubectl=kubectl,
        storage=storage,
        config=config,
        metrics=metrics,
        ip_poll_interval_s=0.02,
    )


async def test_usage_flows_through_fake_driver(tmp_path, storage):
    pods = FakeExecutorPods(tmp_path / "pods")
    executor = make_executor(pods, storage, FakeKubectl(pods))
    try:
        object_id = await storage.write(b"x" * 1000)
        result = await executor.execute(
            "print(open('in.txt').read()[:1])\n"
            "open('out.txt', 'w').write('y' * 500)",
            files={"/workspace/in.txt": object_id},
        )
        assert result.exit_code == 0
        usage = result.usage
        assert usage["cpu_user_s"] > 0
        assert usage["wall_s"] > 0
        assert usage["max_rss_bytes"] > 0
        assert usage["workspace_bytes_written"] >= 500
        assert usage["files_changed"] == 1
        # the driver's byte accounting saw both directions
        assert usage["uploaded_bytes"] == 1000
        assert usage["uploaded_files"] == 1
        assert usage["downloaded_bytes"] == 500
        assert usage["downloaded_files"] == 1
    finally:
        await pods.close()


async def test_spawn_failures_land_in_journal_under_chaos(tmp_path, storage):
    faults = FaultPlan()
    pods = FakeExecutorPods(tmp_path / "pods", faults=faults)
    faults.script("pod_create", Fail("apiserver down"))
    executor = make_executor(
        pods, storage, ChaosKubectl(pods, faults)
    )
    try:
        with pytest.raises(RuntimeError):
            await executor.spawn_pod_group()
        events = executor.journal.events()
        failed = [e for e in events if e["state"] == "failed"]
        assert len(failed) == 1
        assert failed[0]["reason"] == "spawn_failed"
        assert "apiserver down" in failed[0]["detail"]
        # then a healthy spawn journals spawning -> ready with latency
        group = await executor.spawn_pod_group()
        ready = [e for e in executor.journal.events() if e["state"] == "ready"]
        assert ready and ready[0]["pod"] == group.name
        assert ready[0]["spawn_s"] >= 0
    finally:
        await pods.close()


async def test_unhealthy_warm_group_is_journaled_as_reaped(tmp_path, storage):
    pods = FakeExecutorPods(tmp_path / "pods")
    metrics = Registry()
    executor = make_executor(
        pods, storage, FakeKubectl(pods), metrics=metrics
    )
    try:
        group = await executor.spawn_pod_group()
        executor._queue.append(group)
        # preempt the pod out from under the warm queue
        for ip in group.pod_ips:
            await pods.stop_pod(ip)
        result = await executor.execute("print('ok')")
        assert result.stdout == "ok\n"
        events = executor.journal.events()
        reaped = [e for e in events if e["state"] == "reaped"]
        assert [e["pod"] for e in reaped] == [group.name]
        assert reaped[0]["reason"] == "unhealthy"
        assert 'bci_pod_reaped_total{reason="unhealthy"} 1' in metrics.expose()
        # the replacement pod's full story is in the journal too
        served = [
            e
            for e in events
            if e["state"] == "released" and e["executions"] == 1
        ]
        assert served
    finally:
        await pods.close()


async def test_native_spawn_failure_closes_journal_record(tmp_path, storage):
    """Regression: a native spawn that dies anywhere (here: the 'binary'
    exits immediately, so readiness fails) must record 'failed' — never
    leave a phantom 'spawning' pod live in the journal forever."""
    from bee_code_interpreter_tpu.services.native_process_code_executor import (
        NativeProcessCodeExecutor,
    )

    config = Config(
        executor_backend="local",
        local_workspace_root=str(tmp_path / "ws"),
        pod_ready_timeout_s=0.5,
        executor_retry_attempts=1,
        disable_dep_install=True,
    )
    executor = NativeProcessCodeExecutor(
        storage=storage, config=config, binary="/bin/true"
    )
    try:
        with pytest.raises(RuntimeError, match="exited at startup"):
            await executor.spawn_sandbox()
        events = executor.journal.events()
        assert [e["state"] for e in reversed(events)] == ["spawning", "failed"]
        assert events[0]["reason"] == "spawn_failed"
        assert "exited at startup" in events[0]["detail"]
        assert executor.journal.snapshot()["live"] == 0  # no phantom pod
    finally:
        executor.shutdown()


def test_application_context_wires_one_shared_journal(tmp_path):
    """Regression: an EMPTY FleetJournal is falsy (len()==0); `journal or
    FleetJournal()` in an executor would silently record into a twin and
    leave /v1/fleet permanently empty. The context's journal must be the
    very object the executor records into."""
    from bee_code_interpreter_tpu.application_context import ApplicationContext

    ctx = ApplicationContext(
        Config(
            executor_backend="kubernetes",
            file_storage_path=str(tmp_path / "objects"),
            local_workspace_root=str(tmp_path / "ws"),
            disable_dep_install=True,
        )
    )
    assert ctx.code_executor.primary.journal is ctx.fleet


# ------------------------------------------------------------- profiling


def test_inject_profile_env_defaults_and_respects_caller():
    env = inject_profile_env({"FOO": "1"})
    assert env["BCI_PROFILE_DIR"] == "/workspace/.bci-profile"
    assert env["FOO"] == "1"
    env = inject_profile_env({"BCI_PROFILE_DIR": "/workspace/custom"})
    assert env["BCI_PROFILE_DIR"] == "/workspace/custom"


def test_serving_profiler_captures_steps(tmp_path):
    import jax.profiler

    class Stepper:
        profiler_trace = staticmethod(jax.profiler.trace)

        def __init__(self):
            self.steps = 0

        def step(self):
            import jax.numpy as jnp

            self.steps += 1
            jnp.zeros(4).block_until_ready()

    stepper = Stepper()
    profiler = ServingProfiler(stepper, trace_root=tmp_path)
    result = profiler.capture(3)
    assert stepper.steps == 3
    assert result["steps"] == 3
    assert result["duration_ms"] >= 0
    # jax's profiler wrote trace artifacts under the returned directory
    assert result["trace_dir"].startswith(str(tmp_path))
    assert result["files"], "no profiler artifacts captured"
    assert not profiler.capturing

    with pytest.raises(ValueError):
        profiler.capture(0)


def test_serving_profiler_rejects_overlapping_captures(tmp_path):
    """jax.profiler is process-global; a second capture arriving (on another
    thread — the HTTP handler runs captures via asyncio.to_thread) while one
    is in flight must be refused, not corrupt the first."""
    import threading
    import time

    started = threading.Event()

    import contextlib

    class SlowStepper:
        def profiler_trace(self, trace_dir):
            return contextlib.nullcontext()

        def step(self):
            started.set()
            time.sleep(0.3)

    profiler = ServingProfiler(SlowStepper(), trace_root=tmp_path)
    background_errors = []

    def bg():
        try:
            profiler.capture(1)
        except Exception as e:  # pragma: no cover - would fail the assert
            background_errors.append(e)

    t = threading.Thread(target=bg)
    t.start()
    assert started.wait(5.0)
    with pytest.raises(ProfilerUnavailable, match="already in progress"):
        profiler.capture(1)
    t.join()
    assert not background_errors
    assert not profiler.capturing
