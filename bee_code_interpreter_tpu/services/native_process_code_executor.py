"""Native-process code executor: warm pool of local C++ executor servers.

The k8s-free deployment mode for a single TPU VM: the control plane and the
sandboxes share one host, with each sandbox being a fresh instance of the
native executor server (executor/src/server.cpp — the TPU-native counterpart
of the reference's in-pod Rust server, executor/server.rs) listening on a
loopback port with its own throwaway workspace directory.

Pool semantics mirror the Kubernetes backend (and through it the reference's
pod pool, kubernetes_code_executor.py:151-264): a deque of warm, /healthz-ready
server processes kept at a target length with spawning-count accounting;
sandboxes are single-use — after one execution the process is killed and its
workspace deleted, so no state survives a run except through the returned
file map. The data plane is the shared HTTP wire contract (ExecutorHttpDriver),
byte-identical to what the pod network carries.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import functools
import logging
import os
import secrets
import shutil
import socket
import subprocess
import sys
import tempfile
from collections import deque
from contextlib import asynccontextmanager
from dataclasses import dataclass
from pathlib import Path

import httpx

from bee_code_interpreter_tpu.config import Config
from bee_code_interpreter_tpu.observability import (
    FleetJournal,
    collect_transfer,
    merge_worker_usage,
    span,
)
from bee_code_interpreter_tpu.resilience import (
    Deadline,
    InflightRegistry,
    RetryPolicy,
    SandboxTransientError,
    journal_sandbox_teardown,
    retryable,
)
from bee_code_interpreter_tpu.services.code_executor import LeaseHandle, Result
from bee_code_interpreter_tpu.services.executor_http_driver import ExecutorHttpDriver
from bee_code_interpreter_tpu.services.storage import Storage
from bee_code_interpreter_tpu.utils.jaxcache import CHECKOUT_CACHE_DIR
from bee_code_interpreter_tpu.utils.validation import AbsolutePath, Hash

logger = logging.getLogger(__name__)

REPO_EXECUTOR_DIR = Path(__file__).resolve().parent.parent.parent / "executor"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# Orphan protection (the local analogue of the reference's ownerReferences
# cascade-GC, kubernetes_code_executor.py:215-224): the C++ server sets
# PR_SET_PDEATHSIG on itself and watches APP_PARENT_PID when
# APP_DIE_WITH_PARENT=1 (executor/src/server.cpp main()), so warm sandboxes
# never outlive the control plane even on SIGKILL. Doing it in the child
# instead of a preexec_fn lets Popen use vfork instead of a classic fork of
# the (large) service process — the fork was measured blocking the event loop
# ~35 ms per pool refill, which showed up directly in in-flight request p50.
# (CPython only takes the posix_spawn path with close_fds=False, which a
# sandbox must not use — service fds would leak into user code.)


@dataclass
class NativeSandbox:
    """One warm native executor-server process."""

    proc: subprocess.Popen
    addr: str  # 127.0.0.1:port
    workspace: Path
    name: str = ""  # fleet-journal identity, e.g. "native-43117-a1b2"
    # Dispatched at first-healthy, before its warm worker finished
    # preloading: the server gates the execute internally, so the preload
    # tail counts against the HTTP request and needs timeout headroom.
    overlap_dispatch: bool = False
    # /healthz "warm_error": why the server's warm-up or a preload failed
    warm_error: str = ""

    def destroy(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        shutil.rmtree(self.workspace, ignore_errors=True)


class NativeProcessCodeExecutor(ExecutorHttpDriver):
    def __init__(
        self,
        storage: Storage,
        config: Config,
        binary: str | Path | None = None,
        http_client: httpx.AsyncClient | None = None,
        metrics=None,
        journal: FleetJournal | None = None,
    ) -> None:
        self._storage = storage
        self._config = config
        # Lifecycle journal (docs/observability.md): same transition
        # vocabulary as the Kubernetes pool, one process per "pod".
        # `is None`, not truthiness: an empty journal is len()==0 — falsy —
        # and replacing the injected one would strand /v1/fleet on a twin.
        self.journal = (
            journal if journal is not None else FleetJournal(metrics=metrics)
        )
        self._binary = Path(binary or config.local_executor_binary or "")
        if not self._binary.is_file():
            raise FileNotFoundError(
                f"native executor binary not found: {self._binary} "
                "(build with `make -C executor`)"
            )
        self._http = http_client or httpx.AsyncClient(
            timeout=config.executor_http_timeout_s
        )
        self._workspace_root = Path(config.local_workspace_root)
        self._queue: deque[NativeSandbox] = deque()
        self._spawning_count = 0
        self._fill_lock = asyncio.Lock()
        # Background refills are CPU-bound (each spawn boots a python warm
        # worker through its preload imports); unbounded concurrency lets a
        # burst of refills starve the serving path's event loop — on a
        # small host that showed up as multi-second acquire stalls and
        # inflated control-plane overhead. Request-blocking spawns (pool
        # empty) bypass this gate on purpose: the waiting request IS the
        # priority.
        self._refill_gate = asyncio.Semaphore(
            max(1, (os.cpu_count() or 2) - 1)
        )
        # Dynamic warm-pool target (docs/autoscaling.md): the PoolAutoscaler
        # writes this in APP_AUTOSCALE_MODE=act; None means the static
        # configured target. Every refill reads `pool_target`.
        self.pool_target_override: int | None = None
        self._closed = False
        # The event loop holds only weak refs to tasks; fire-and-forget refills
        # must be anchored here or GC can cancel them mid-spawn.
        self._background_tasks: set[asyncio.Task] = set()
        # Executions in flight, killable by the supervisor's stuck-execution
        # watchdog (resilience/supervisor.py).
        self.inflight = InflightRegistry()
        # Dedicated spawn thread: PR_SET_PDEATHSIG fires when the spawning
        # *thread* exits (prctl(2)), so sandboxes must not be forked from
        # default-executor workers whose lifetime we don't control. This
        # thread lives exactly as long as the pool.
        self._spawn_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="sandbox-spawn"
        )
        self._stdlib_file_path: str | None = None
        self._stdlib_lock = asyncio.Lock()
        # Native sandboxes are local: startup/IPC failures settle fast, so the
        # backoff floor is 20x tighter than the pod path's.
        self._execute_retry = RetryPolicy(
            attempts=config.executor_retry_attempts,
            wait_min_s=0.2,
            wait_max_s=2.0,
            retry_on=(SandboxTransientError,),
        )
        self._spawn_retry = RetryPolicy(
            attempts=config.executor_retry_attempts,
            wait_min_s=0.2,
            wait_max_s=2.0,
            retry_on=(RuntimeError,),
        )
        # Per-request phase breakdown of the most recent execute() (diagnostic
        # surface for bench.py / scripts/measure-latency.py: lets a latency
        # regression be attributed to acquire/upload/server/download/overhead
        # instead of guessed at). Overwritten per request; read it before
        # issuing the next one.
        self.last_execute_phases: dict[str, float | bool] = {}

    async def _stdlib_file(self) -> str | None:
        """Stdlib module list for the dep guesser, generated once per service
        process by asking the *sandbox* interpreter (same APP_PYTHON
        resolution the C++ server uses — its stdlib can differ from the
        control plane venv's); sandboxes read the file instead of each paying
        a python startup to ask. None when dep-install is disabled (the list
        is never consulted). The probe runs off-loop (a python startup must
        not stall in-flight requests), lands in a private per-process runtime
        dir — NOT under workspace_root, where sandboxed user code could
        overwrite it via ``../`` and poison later guesses — and is fresh
        every service start so an interpreter upgrade can't serve a stale
        list. Falls back to this interpreter's own list if the probe fails.
        (The executor image pregenerates /stdlib_names.txt the same way.)"""
        if self._config.disable_dep_install:
            return None
        async with self._stdlib_lock:
            if self._stdlib_file_path is None:
                python = os.environ.get("APP_PYTHON", "python3")
                probe = "import sys; print('\\n'.join(sorted(sys.stdlib_module_names)))"

                def generate() -> str:
                    try:
                        return subprocess.run(
                            [python, "-c", probe],
                            capture_output=True, text=True, timeout=30, check=True,
                        ).stdout
                    except (OSError, subprocess.SubprocessError):
                        return "\n".join(sorted(sys.stdlib_module_names)) + "\n"

                names = await asyncio.get_running_loop().run_in_executor(
                    self._spawn_pool, generate
                )
                runtime_dir = Path(tempfile.mkdtemp(prefix="bci-runtime-"))
                path = runtime_dir / "stdlib_names.txt"
                path.write_text(names)
                self._stdlib_file_path = str(path)
        return self._stdlib_file_path

    @property
    def pool_ready_count(self) -> int:
        return len(self._queue)

    @property
    def pool_spawning_count(self) -> int:
        return self._spawning_count

    @property
    def pool_target(self) -> int:
        """The refill target: the autoscaler's override when one is
        actuated, the static configured length otherwise."""
        if self.pool_target_override is not None:
            return self.pool_target_override
        return self._config.executor_pod_queue_target_length

    # ------------------------------------------------------------- execution

    @retryable("_execute_retry", op="execute")
    async def execute(
        self,
        source_code: str,
        files: dict[AbsolutePath, Hash] | None = None,
        env: dict[str, str] | None = None,
        timeout_s: float | None = None,
        deadline: Deadline | None = None,
    ) -> Result:
        files = files or {}
        env = env or {}
        if deadline is not None:
            deadline.check("execute")
        perf = asyncio.get_running_loop().time
        t_start = perf()
        was_warm = bool(self._queue)
        # Ambient byte-accounting scope for this execution (sync contextvars;
        # the driver's upload/download calls report into it).
        with collect_transfer() as transfer:
            return await self._execute_on_sandbox(
                source_code, files, env, timeout_s, deadline,
                transfer, perf, t_start, was_warm,
            )

    async def _execute_on_sandbox(
        self, source_code, files, env, timeout_s, deadline,
        transfer, perf, t_start, was_warm,
    ) -> Result:
        async with self.sandbox(deadline=deadline) as box:
            t_acquired = perf()
            await asyncio.gather(
                *(
                    self._upload_file(box.addr, path, object_id, deadline=deadline)
                    for path, object_id in files.items()
                )
            )
            t_uploaded = perf()
            self.journal.record(box.name, "executing")
            # Tracked so the supervisor watchdog can kill a wedged sandbox:
            # the process kill resets this call's transport, and the task
            # cancel converts to a transient failure (hung_execute).
            with self.inflight.track(
                box.name, kill=lambda: self._kill_sandbox(box)
            ):
                response = await self._post_execute(
                    box.addr,
                    source_code,
                    env,
                    self._effective_timeout(timeout_s),
                    # preload budget (matches the pooled warm-wait bound) on
                    # top of the client timeout for overlap-dispatched
                    # sandboxes — a near-limit execution must not lose its
                    # margin to the preload it overlapped
                    client_timeout_s=(
                        self._config.executor_http_timeout_s + 15.0
                        if box.overlap_dispatch
                        else None
                    ),
                    deadline=deadline,
                )
            t_executed = perf()
            out_files: dict[str, str] = {}
            for path, object_id in zip(
                response["files"],
                await asyncio.gather(
                    *(
                        self._download_file(box.addr, p, deadline=deadline)
                        for p in response["files"]
                    )
                ),
            ):
                out_files[path] = object_id
            t_done = perf()
            # sandbox_ms is the server-reported subprocess wall time; the gap
            # post_execute_ms − sandbox_ms is pure control-plane + HTTP
            # overhead — where event-loop contention (e.g. pool refills)
            # shows up.
            sandbox_ms = float(response.get("duration_ms") or 0.0)
            self.last_execute_phases = {
                "acquire_ms": (t_acquired - t_start) * 1000,
                "warm_pop": was_warm,
                "upload_ms": (t_uploaded - t_acquired) * 1000,
                "post_execute_ms": (t_executed - t_uploaded) * 1000,
                "sandbox_ms": sandbox_ms,
                "overhead_ms": (t_executed - t_uploaded) * 1000 - sandbox_ms,
                "download_ms": (t_done - t_executed) * 1000,
                "total_ms": (t_done - t_start) * 1000,
            }
            # The C++ server doesn't measure usage (its response has no
            # block); the Python server does — merge handles either, and the
            # driver's byte counts are always present.
            usage = merge_worker_usage([response.get("usage")])
            usage.update(transfer.as_dict())
            return Result(
                stdout=response["stdout"],
                stderr=response["stderr"],
                exit_code=response["exit_code"],
                files=out_files,
                usage=usage,
            )

    # ------------------------------------------------------------------ pool

    async def _checkout_sandbox(
        self, deadline: Deadline | None = None
    ) -> NativeSandbox:
        """Pop a live warm server (discarding corpses) or spawn one, journal
        the assignment, kick a refill — the acquisition half shared by the
        single-use execute path and session leases."""
        box = None
        while self._queue:
            candidate = self._queue.popleft()
            if candidate.proc.poll() is None:
                box = candidate
                self.journal.record(box.name, "assigned", reason="warm_pop")
                break
            logger.warning("Warm sandbox on %s died in queue; discarding", candidate.addr)
            self.journal.record(
                candidate.name,
                "reaped",
                reason="died_in_queue",
                detail=f"exit {candidate.proc.returncode}",
            )
            candidate.destroy()
        if box is None:
            # Pool drained: dispatch at first healthy instead of polling for
            # preload-done — the server queues the execute until its warm
            # worker is ready (or falls back cold), so the request overlaps
            # with the tail of the preload rather than waiting it out here.
            with span("spawn"):
                spawn = self.spawn_sandbox(wait_warm=False)
                box = await (
                    deadline.run(spawn, what="sandbox spawn")
                    if deadline
                    else spawn
                )
            self.journal.record(box.name, "assigned", reason="cold_spawn")
        self._spawn_background(self.fill_sandbox_queue())
        return box

    @asynccontextmanager
    async def sandbox(self, deadline: Deadline | None = None):
        """Pop a warm server or spawn one; single-use teardown + async refill.
        A sandbox whose process died while queued (OOM, crash) is discarded,
        not handed to a request."""
        box = await self._checkout_sandbox(deadline)
        try:
            yield box
        except BaseException as e:
            # Mirror of the pod-group path: a transient failure mid-execute
            # means the sandbox process is presumed dead/wedged, and the
            # journal reason is what replay observability keys on.
            journal_sandbox_teardown(self.journal, box.name, e)
            raise
        else:
            journal_sandbox_teardown(self.journal, box.name, None)
        finally:
            # Teardown must not block the response (reference deletes pods
            # fire-and-forget, kubernetes_code_executor.py:262-264).
            asyncio.get_running_loop().run_in_executor(None, box.destroy)

    def _spawn_background(self, coro) -> None:
        task = asyncio.ensure_future(coro)
        self._background_tasks.add(task)
        task.add_done_callback(self._background_tasks.discard)

    def _kill_sandbox(self, box: NativeSandbox) -> None:
        """Watchdog teardown of a wedged sandbox (sync, fire-and-forget):
        killing the process resets the in-flight HTTP call's transport."""
        asyncio.get_running_loop().run_in_executor(None, box.destroy)

    # ---------------------------------------------------------------- leases

    async def checkout_for_lease(
        self, deadline: Deadline | None = None
    ) -> LeaseHandle:
        """Check a warm server out of the pool for a session lease
        (docs/sessions.md): popped out of the queue, so the supervisor's
        idle reaper never probes it while the session holds it."""
        box = await self._checkout_sandbox(deadline)
        return LeaseHandle(
            name=box.name,
            addrs=[box.addr],
            kill=lambda: self._kill_sandbox(box),
            handle=box,
        )

    def release_lease(
        self,
        lease: LeaseHandle,
        state: str = "released",
        reason: str = "lease_released",
        detail: str | None = None,
    ) -> None:
        """End a lease: terminal journal event, sandbox torn down, refill
        kicked (mirror of the Kubernetes backend)."""
        self.journal.record(lease.name, state, reason=reason, detail=detail)
        lease.kill()
        self._spawn_background(self.fill_sandbox_queue())

    async def execute_stream(
        self,
        source_code: str,
        files: dict[AbsolutePath, Hash] | None = None,
        env: dict[str, str] | None = None,
        timeout_s: float | None = None,
        on_event=None,  # async (kind, text) -> None per stdout/stderr chunk
        deadline: Deadline | None = None,
    ) -> Result:
        """Streaming execute over a single-use native sandbox: output chunks
        forward to ``on_event`` as the server produces them; workspace
        restore before / snapshot after are unchanged. No retry/replay wraps
        this path — delivered chunks cannot be un-delivered."""
        files = files or {}
        env = env or {}
        if deadline is not None:
            deadline.check("execute")
        with collect_transfer() as transfer:
            async with self.sandbox(deadline=deadline) as box:
                await asyncio.gather(
                    *(
                        self._upload_file(box.addr, path, object_id, deadline=deadline)
                        for path, object_id in files.items()
                    )
                )
                self.journal.record(box.name, "executing")
                with self.inflight.track(
                    box.name, kill=lambda: self._kill_sandbox(box)
                ):
                    response = await self._post_execute_stream(
                        box.addr,
                        source_code,
                        env,
                        self._effective_timeout(timeout_s),
                        on_event=on_event,
                        deadline=deadline,
                    )
                out_files: dict[str, str] = {}
                for path, object_id in zip(
                    response["files"],
                    await asyncio.gather(
                        *(
                            self._download_file(box.addr, p, deadline=deadline)
                            for p in response["files"]
                        )
                    ),
                ):
                    out_files[path] = object_id
                usage = merge_worker_usage([response.get("usage")])
                usage.update(transfer.as_dict())
                return Result(
                    stdout=response["stdout"],
                    stderr=response["stderr"],
                    exit_code=response["exit_code"],
                    files=out_files,
                    usage=usage,
                )

    async def _sandbox_healthy(self, box: NativeSandbox) -> bool:
        """The process is alive AND its /healthz answers — a live-but-wedged
        server (stuck preload, leaked lock) is as dead as a crashed one."""
        if box.proc.poll() is not None:
            return False
        try:
            response = await self._http.get(
                f"http://{box.addr}/healthz",
                timeout=self._config.health_probe_timeout_s,
            )
            return response.status_code == 200
        except httpx.HTTPError:
            return False

    def trim_excess_warm(self) -> int:
        """Supervisor hook for the autoscaler's act-mode shrink
        (docs/autoscaling.md): reap queued warm servers beyond the current
        refill target (mirror of the Kubernetes backend — a scale-down
        must shrink the live pool, not just stop refills)."""
        trimmed = 0
        while len(self._queue) > self.pool_target:
            box = self._queue.pop()
            self.journal.record(box.name, "reaped", reason="scaled_down")
            self._kill_sandbox(box)
            trimmed += 1
        return trimmed

    async def reap_unhealthy_idle(self) -> int:
        """Supervisor hook: probe every queued warm sandbox and reap the
        ones that died or wedged in place. Returns the number reaped."""
        candidates = list(self._queue)
        if not candidates:
            return 0
        # Probe the whole queue concurrently: a mass-death event must not
        # cost one probe timeout PER corpse before healing starts.
        results = await asyncio.gather(
            *(self._sandbox_healthy(b) for b in candidates)
        )
        reaped = 0
        for box, healthy in zip(candidates, results):
            if healthy:
                continue
            try:
                self._queue.remove(box)
            except ValueError:
                continue  # checked out by a request while we probed
            exited = box.proc.poll() is not None
            detail = (
                f"exit {box.proc.returncode}" if exited else "healthz probe failed"
            )
            logger.warning(
                "Supervisor reaping unhealthy idle sandbox %s (%s)",
                box.name,
                detail,
            )
            self.journal.record(
                box.name, "reaped", reason="unhealthy_idle", detail=detail
            )
            self._kill_sandbox(box)
            reaped += 1
        return reaped

    async def fill_sandbox_queue(self) -> None:
        if self._closed:
            return
        async with self._fill_lock:
            missing = self.pool_target - len(self._queue) - self._spawning_count
            if missing <= 0:
                return
            self._spawning_count += missing
        # Each spawn settles its own accounting — a failed spawn must never
        # abandon its siblings or leave a phantom spawning count behind.
        results = await asyncio.gather(
            *(self._spawn_into_queue() for _ in range(missing))
        )
        if not all(results):
            logger.warning(
                "Sandbox pool refill finished with failures: %d/%d spawned",
                sum(results),
                missing,
            )

    async def _spawn_into_queue(self) -> bool:
        try:
            async with self._refill_gate:
                box = await self.spawn_sandbox()
        except Exception:
            logger.exception("Sandbox spawn failed")
            return False
        finally:
            self._spawning_count -= 1
        if self._closed:
            # raced with shutdown: don't repopulate a dead pool
            self.journal.record(box.name, "reaped", reason="shutdown")
            box.destroy()
            return False
        self._queue.append(box)
        return True

    @retryable("_spawn_retry", op="spawn")
    async def spawn_sandbox(self, wait_warm: bool = True) -> NativeSandbox:
        port = _free_port()
        # The port alone is NOT unique: _free_port() releases its probe
        # socket before the sandbox binds, so concurrent spawns can draw the
        # same number — two journal records must never share one identity.
        name = f"native-{port}-{secrets.token_hex(2)}"
        self.journal.record(name, "spawning")
        try:
            return await self._spawn_sandbox(port, name, wait_warm)
        except BaseException as e:
            # EVERY spawn failure — mkdir, the stdlib probe, Popen, the
            # readiness wait, a deadline cancellation — must close the
            # journal record, or the pod sits in _live as a phantom
            # 'spawning' forever (and a persistently failing refill loop
            # would accumulate phantoms without bound).
            self.journal.record(
                name,
                "failed",
                reason="spawn_failed",
                detail=(str(e) or type(e).__name__)[:200],
            )
            raise

    async def _spawn_sandbox(
        self, port: int, name: str, wait_warm: bool
    ) -> NativeSandbox:
        cfg = self._config
        addr = f"127.0.0.1:{port}"
        workspace = self._workspace_root / secrets.token_hex(8)
        workspace.mkdir(parents=True, exist_ok=True)

        env = dict(os.environ)
        env.update(
            APP_LISTEN_ADDR=addr,
            APP_WORKSPACE=str(workspace),
            APP_EXECUTION_TIMEOUT_S=str(cfg.execution_timeout_s),
            APP_REQUIREMENTS=str(REPO_EXECUTOR_DIR / "requirements.txt"),
            APP_REQUIREMENTS_SKIP=str(REPO_EXECUTOR_DIR / "requirements-skip.txt"),
            APP_PYPI_MAP=str(REPO_EXECUTOR_DIR / "pypi_map.tsv"),
        )
        if cfg.disable_dep_install:
            env["APP_DISABLE_DEP_INSTALL"] = "1"
        shim = cfg.resolved_shim_dir()
        if shim:
            env["APP_SHIM_DIR"] = str(shim)
        # Sandboxes on this host share one compile cache: the operator's
        # directory, else the checkout's fixed one (the server still lets an
        # inherited JAX_COMPILATION_CACHE_DIR win).
        env["APP_JAX_CACHE_DIR"] = cfg.jax_cache_dir or CHECKOUT_CACHE_DIR
        env["APP_DIE_WITH_PARENT"] = "1"  # server watches us via PDEATHSIG+ppid
        env["APP_PARENT_PID"] = str(os.getpid())
        stdlib_file = await self._stdlib_file()
        if stdlib_file:
            env["APP_STDLIB_FILE"] = stdlib_file

        argv: list[str] = [str(self._binary)]
        if cfg.sandbox_unshare:
            # Mount-namespace hardening: the server (and every python child
            # it spawns) sees an empty tmpfs where the object-storage root
            # is, so user code cannot read other sessions' files. Mount-ns
            # only: a net namespace would cut the loopback HTTP transport,
            # and a pid namespace breaks the APP_PARENT_PID watchdog (k8s
            # mode provides those via pod isolation instead).
            #
            # The process holding the namespace has CAP_SYS_ADMIN over it
            # (real or userns-mapped root), so user code could umount2() the
            # tmpfs and uncover the real directory — after the mount, the
            # capability bounding set is emptied (setpriv) so no descendant
            # can ever regain it; verified by the umount-bypass test. If
            # setpriv is missing the overmount still guards against
            # accidental access but a deliberate umount bypasses it — warn.
            storage_root = Path(cfg.file_storage_path).resolve()
            storage_root.mkdir(parents=True, exist_ok=True)  # mount target
            env["BCI_HIDE_DIR"] = str(storage_root)
            lockdown = (
                ["setpriv", "--bounding-set", "-all"]
                if shutil.which("setpriv")
                else []
            )
            if not lockdown:
                logger.warning(
                    "sandbox_unshare: setpriv not found - the storage "
                    "overmount cannot be capability-locked and deliberate "
                    "user code could umount it"
                )
            argv = [
                "unshare",
                "--mount",
                *([] if os.geteuid() == 0 else ["--map-root-user"]),
                "sh",
                "-c",
                'mount -t tmpfs tmpfs "$BCI_HIDE_DIR" && exec "$@"',
                "sh",
                *lockdown,
                str(self._binary),
            ]

        # Off-loop spawn: even vfork costs ~ms, and refills run concurrently
        # with in-flight requests.
        proc = await asyncio.get_running_loop().run_in_executor(
            self._spawn_pool,
            functools.partial(
                subprocess.Popen,
                argv,
                env=env,
                stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL,
            ),
        )
        box = NativeSandbox(
            proc=proc, addr=addr, workspace=workspace, name=name,
            overlap_dispatch=not wait_warm,
        )
        try:
            loop = asyncio.get_running_loop()
            deadline = loop.time() + cfg.pod_ready_timeout_s
            warm_deadline: float | None = None  # set at first healthy
            while True:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"native executor exited at startup (code {proc.returncode})"
                    )
                try:
                    response = await self._http.get(f"http://{addr}/healthz")
                    if response.status_code == 200:
                        health = response.json()
                        warm_error = health.get("warm_error")
                        if warm_error and not box.warm_error:
                            # the server's warm-up or a preload failed (e.g.
                            # the chip is held by another process): the
                            # sandbox still serves, so say it here too
                            box.warm_error = warm_error
                            logger.warning(
                                "Sandbox %s warm-up failed: %s", name, warm_error
                            )
                        # Best-effort: hold the sandbox back until its warm
                        # worker finished preloading, so requests never pay
                        # the preload wait. A slow preload (up to 15 s past
                        # healthy, or the ready deadline if sooner) queues the
                        # healthy-but-cold sandbox anyway — the server's own
                        # warm-wait/cold-fallback covers it.
                        if not wait_warm:
                            return self._spawned_ready(box)
                        if warm_deadline is None:
                            warm_deadline = min(loop.time() + 15.0, deadline)
                        if health.get("warm", True):
                            return self._spawned_ready(box)
                        if loop.time() > warm_deadline:
                            return self._spawned_ready(box)
                except (httpx.TransportError, ValueError):
                    pass
                if loop.time() > deadline:
                    raise RuntimeError(
                        f"native executor on {addr} never became ready"
                    )
                await asyncio.sleep(0.05)
        except BaseException:
            # BaseException: a deadline-driven cancel must also reap the
            # half-started sandbox process, not leak it. (The caller's
            # journal guard records the 'failed' event.)
            box.destroy()
            raise

    def _spawned_ready(self, box: NativeSandbox) -> NativeSandbox:
        self.journal.record(box.name, "ready")
        return box

    def shutdown(self, close_http: bool = True) -> None:
        """Kill every warm sandbox (no idle processes left behind).

        Sets the closed flag first so refills already in flight destroy their
        sandboxes instead of repopulating a dead pool.
        """
        self._closed = True
        while self._queue:
            box = self._queue.popleft()
            self.journal.record(box.name, "reaped", reason="shutdown")
            box.destroy()
        # The spawn thread's exit triggers PDEATHSIG in any sandbox it forked
        # — including one currently serving a request. That is the intended
        # contract: shutdown() terminates the backend; an execution still in
        # flight dies with it (its handler is being torn down with the loop
        # anyway). Queued sandboxes were destroyed above; in-flight refills
        # see the closed flag and destroy their own.
        self._spawn_pool.shutdown(wait=False)
        if not close_http:
            return
        # Legacy sync path: the aclose can only be scheduled, and a loop shut
        # down right after may cancel it before it runs. The drain path uses
        # the deterministic ``aclose()`` instead.
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            pass
        else:
            task = loop.create_task(self._http.aclose())
            self._background_tasks.add(task)
            task.add_done_callback(self._background_tasks.discard)

    async def aclose(self) -> None:
        """Deterministic drain-path shutdown: tear the pool down, then close
        the HTTP client *awaited in-loop* — not as a fire-and-forget task the
        closing loop could cancel before it ever ran."""
        self.shutdown(close_http=False)
        await self._http.aclose()
