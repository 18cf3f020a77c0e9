"""In-sandbox execution engine: the behavior behind ``POST /execute``.

Pure-Python reference implementation of the sandbox executor's core loop,
mirrored by the native C++ server (executor/server.cpp). The reference
implements this in Rust (executor/server.rs:120-179): write script → guess deps
→ pip install new ones → run under xonsh with timeout → scan changed files.

Deliberate TPU-first departures from the reference:

- **Plain python, not xonsh** — the reference notes ~80 ms/exec startup cost of
  xonsh as a TODO (server.rs:152); we never pay it. Shell escapes are not part
  of the capability surface we preserve (LLM code that needs a shell can use
  subprocess).
- **Recursive changed-file scan by (mtime_ns, size) snapshot diff** — the
  reference scans only the workspace top level and compares ctime to a start
  timestamp (server.rs:98-118), missing nested files and files rewritten with
  preserved timestamps. We snapshot before and diff after.
- **TPU env plumbing** — the child process inherits the pod's TPU topology env
  (TPU_WORKER_ID, TPU_WORKER_HOSTNAMES, coordinator address; SURVEY.md §2
  "Parallelism strategies") so ``jax.distributed.initialize()`` works out of the
  box on multi-host slices, and PYTHONPATH is prefixed with the runtime shim dir
  so the sitecustomize display/XLA patches load (reference sitecustomize.py:1-31).
- **Warm-up option** — pre-heats libtpu/XLA before the server listens
  (SURVEY.md §7 hard part (c)); see ``warmup()``.
"""

from __future__ import annotations

import asyncio
import codecs
import dataclasses
import logging
import os
import signal
import sys
import tempfile
from pathlib import Path
from typing import AsyncIterator

from bee_code_interpreter_tpu.observability.accounting import UsageMeter
from bee_code_interpreter_tpu.runtime import dep_guess

# Env the executor forwards from its own environment into every user process,
# so JAX/libtpu sees the slice topology the scheduler provisioned, by prefix:
# the accelerator stack's vars are open-ended (libtpu TPU_*, jax JAX_*, XLA_*,
# plus LIBTPU_*/MEGASCALE_* for multi-slice), and missing one silently strands
# the sandbox on host CPU — the exact failure the transparent reroute exists
# to prevent.
TPU_PASSTHROUGH_PREFIXES = ("TPU_", "JAX_", "XLA_", "LIBTPU_", "MEGASCALE_")

# Kubernetes service links (enableServiceLinks) auto-inject FOO_SERVICE_HOST /
# FOO_PORT / FOO_PORT_80_TCP-style vars for every Service in the namespace; a
# Service named tpu-* or jax-* would land inside the prefixes above and leak
# cluster addresses into untrusted user code. But real accelerator topology
# vars share the port-suffix shape (libtpu's TPU_PROCESS_PORT, multi-slice
# MEGASCALE_PORT) — filtering on suffix alone silently strands the sandbox on
# host CPU, the exact failure this passthrough exists to prevent. So port-
# shaped keys are dropped only when the definitive service-link signature is
# present: a sibling FOO_SERVICE_HOST in the same environment (k8s always
# injects the pair together; libtpu never sets *_SERVICE_HOST).


def _is_passthrough_env(key: str, environ=None) -> bool:
    if not key.startswith(TPU_PASSTHROUGH_PREFIXES):
        return False
    if "_SERVICE_" in key:
        return False
    if key.endswith("_PORT"):
        base = key[:-len("_PORT")]
    elif "_PORT_" in key:
        base = key[: key.index("_PORT_")]
    else:
        return True
    env = os.environ if environ is None else environ
    return f"{base}_SERVICE_HOST" not in env

EXECUTION_TIMED_OUT = "Execution timed out"

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class ExecutionOutcome:
    """Wire shape of the ``POST /execute`` response (minus serialization)."""

    stdout: str
    stderr: str
    exit_code: int
    files: list[str]  # logical absolute paths, e.g. "/workspace/plot.png"
    # Resource accounting (docs/observability.md): getrusage-children deltas,
    # wall clock, workspace byte deltas, deps installed for THIS execution.
    usage: dict | None = None


def snapshot_workspace(root: Path) -> dict[str, tuple[int, int]]:
    """{relative path: (mtime_ns, size)} for every regular file under root."""
    snap: dict[str, tuple[int, int]] = {}
    for dirpath, _dirnames, filenames in os.walk(root):
        for name in filenames:
            p = Path(dirpath) / name
            try:
                st = p.stat()
            except OSError:
                continue
            snap[str(p.relative_to(root))] = (st.st_mtime_ns, st.st_size)
    return snap


def changed_files(before: dict[str, tuple[int, int]], after: dict[str, tuple[int, int]]) -> list[str]:
    return sorted(rel for rel, sig in after.items() if before.get(rel) != sig)


class ExecutorCore:
    """One sandbox's execution engine, bound to a workspace directory.

    ``logical_prefix`` is the path the *client* sees ("/workspace"); the real
    directory may live anywhere (a tempdir in local mode, /workspace in a pod).
    """

    def __init__(
        self,
        workspace: str | Path,
        logical_prefix: str = "/workspace",
        preinstalled: frozenset[str] = frozenset(),
        disable_dep_install: bool = False,
        default_timeout_s: float = 60.0,
        python_executable: str | None = None,
        shim_dir: str | Path | None = None,
        installed_cache: set[str] | None = None,
        jax_cache_dir: str | None = None,
    ) -> None:
        self.workspace = Path(workspace)
        self.workspace.mkdir(parents=True, exist_ok=True)
        self.logical_prefix = logical_prefix.rstrip("/")
        self.preinstalled = preinstalled
        self.disable_dep_install = disable_dep_install
        self.default_timeout_s = default_timeout_s
        self.python = python_executable or sys.executable
        self.shim_dir = str(shim_dir) if shim_dir else None
        # Compile-cache directory for user processes when neither the
        # environment nor APP_JAX_CACHE_DIR names one (the local backend
        # passes the checkout's fixed path — utils/jaxcache.py; in a pod it
        # stays None: no cache unless the operator mounts one).
        self.jax_cache_dir = jax_cache_dir
        # May be shared across per-execution cores (LocalCodeExecutor) so a dep
        # installed once isn't re-installed on every request.
        self._installed_this_session: set[str] = (
            installed_cache if installed_cache is not None else set()
        )

    # ---- logical path mapping (PUT/GET /workspace/{path}) ----

    def resolve(self, logical_path: str) -> Path:
        """Map a client path to a real file path, refusing escapes.

        Accepts "/workspace/foo", "workspace/foo", or bare "foo" — the reference
        strips the "/workspace/" prefix on upload (kubernetes_code_executor.py:103)
        and its executor joins paths as-is (server.rs:69-88); we additionally
        reject traversal outside the workspace root.
        """
        p = logical_path
        for prefix in (self.logical_prefix + "/", self.logical_prefix.lstrip("/") + "/"):
            if p.startswith(prefix):
                p = p[len(prefix):]
                break
        p = p.lstrip("/")
        real = (self.workspace / p).resolve()
        if not real.is_relative_to(self.workspace.resolve()):
            raise ValueError(f"path escapes workspace: {logical_path!r}")
        return real

    def logical(self, rel: str) -> str:
        return f"{self.logical_prefix}/{rel}"

    # ---- dependency install ----

    async def ensure_dependencies(
        self, source_code: str, predicted_deps: list[str] | None = None
    ) -> tuple[list[str], str]:
        """Guess + install missing deps. Returns (installed, stderr_notes).

        With an edge prediction attached to the request (docs/analysis.md),
        the sandbox's own AST scan is skipped entirely — the prediction is
        only re-filtered against THIS image's preinstalled/skip sets, which
        the edge cannot know."""
        if predicted_deps is not None:
            deps = dep_guess.filter_predicted(predicted_deps, self.preinstalled)
        else:
            deps = dep_guess.guess_dependencies(source_code, self.preinstalled)
        deps = [d for d in deps if d not in self._installed_this_session]
        if not deps or self.disable_dep_install:
            return [], ""
        proc = await asyncio.create_subprocess_exec(
            self.python, "-m", "pip", "install", "--no-cache-dir", *deps,
            stdout=asyncio.subprocess.DEVNULL,
            stderr=asyncio.subprocess.PIPE,
        )
        _, stderr = await proc.communicate()
        if proc.returncode == 0:
            self._installed_this_session.update(deps)
            return deps, ""
        # Match the reference's behavior of surfacing install failures in-band
        # (server.rs:140-147): execution proceeds; the user import error + pip
        # stderr tell the story.
        return [], stderr.decode(errors="replace")

    # ---- execution ----

    def _child_env(self, request_env: dict[str, str]) -> dict[str, str]:
        env = {
            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "HOME": os.environ.get("HOME", str(self.workspace)),
            "LANG": "C.UTF-8",
            "PYTHONUNBUFFERED": "1",
        }
        for key, value in os.environ.items():
            if _is_passthrough_env(key):
                env[key] = value
        if self.shim_dir:
            existing = os.environ.get("PYTHONPATH", "")
            env["PYTHONPATH"] = self.shim_dir + (os.pathsep + existing if existing else "")
        elif "PYTHONPATH" in os.environ:
            env["PYTHONPATH"] = os.environ["PYTHONPATH"]
        # Persistent XLA compile cache: single-use sandboxes then pay each
        # unique program's compile once per deployment instead of once per
        # request. The environment's own JAX_COMPILATION_CACHE_DIR (passed
        # through above) wins, then the operator's APP_JAX_CACHE_DIR, then
        # the backend's default.
        jax_cache = os.environ.get("APP_JAX_CACHE_DIR") or self.jax_cache_dir
        if jax_cache and "JAX_COMPILATION_CACHE_DIR" not in env:
            env["JAX_COMPILATION_CACHE_DIR"] = jax_cache
        env.update(request_env)  # request env wins (reference server.rs:154)
        # ...except the shim must survive a request-supplied PYTHONPATH: it is
        # part of the sandbox platform (reroute/display patches), not a
        # default the request replaces. (BCI_XLA_REROUTE=0 is the opt-out.)
        # Component comparison, not substring (/opt/shim vs /opt/shim2).
        if self.shim_dir:
            existing = env.get("PYTHONPATH", "")
            if self.shim_dir not in existing.split(os.pathsep):
                env["PYTHONPATH"] = self.shim_dir + (
                    os.pathsep + existing if existing else ""
                )
        return env

    async def execute(
        self,
        source_code: str,
        env: dict[str, str] | None = None,
        timeout_s: float | None = None,
        predicted_deps: list[str] | None = None,
    ) -> ExecutionOutcome:
        env = env or {}
        timeout_s = timeout_s or self.default_timeout_s
        # Off-loop walk: the workspace scan is sync filesystem I/O, and in the
        # pod server it would otherwise stall every concurrent data-plane
        # request for the duration of the walk.
        before = await asyncio.to_thread(snapshot_workspace, self.workspace)
        # The meter opens before the dep install on purpose: pip time/CPU is
        # part of what this execution cost the sandbox.
        meter = UsageMeter()

        installed, pip_notes = await self.ensure_dependencies(
            source_code, predicted_deps
        )

        with tempfile.TemporaryDirectory(prefix="exec-") as td:
            script = Path(td) / "script.py"
            script.write_text(source_code)
            # start_new_session puts the script in its own process group so a
            # timeout kill reaps grandchildren too — user code is allowed to
            # spawn subprocesses, and a surviving orphan would keep writing into
            # a torn-down workspace (or hold the pod's TPU via libtpu).
            proc = await asyncio.create_subprocess_exec(
                self.python, str(script),
                cwd=self.workspace,
                env=self._child_env(env),
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.PIPE,
                start_new_session=True,
            )
            try:
                stdout_b, stderr_b = await asyncio.wait_for(
                    proc.communicate(), timeout=timeout_s
                )
                exit_code = proc.returncode
                stdout = stdout_b.decode(errors="replace")
                stderr = stderr_b.decode(errors="replace")
            except asyncio.TimeoutError:
                # Reference behavior: kill, exit_code -1, fixed stderr message
                # (server.rs:151-169); the kill targets the whole group.
                self._kill_process_group(proc)
                await proc.wait()
                stdout, stderr, exit_code = "", EXECUTION_TIMED_OUT, -1
            finally:
                if proc.returncode is None:
                    # Cancelled mid-run (vanished client, watchdog kill): the
                    # user process must not outlive the execute that owns it.
                    # Under a lease the workspace survives this call, so an
                    # orphan would keep mutating state the next REPL turn (or
                    # a checkpoint) reads; the streaming twin already kills in
                    # its finally for the same reason.
                    self._kill_process_group(proc)
                    await proc.wait()

        if pip_notes:
            stderr = pip_notes + ("\n" + stderr if stderr else "")

        after = await asyncio.to_thread(snapshot_workspace, self.workspace)
        changed = changed_files(before, after)
        usage = meter.finish(
            workspace_bytes_written=sum(after[rel][1] for rel in changed),
            files_changed=len(changed),
            deps_installed=installed,
        )
        files = [self.logical(rel) for rel in changed]
        return ExecutionOutcome(
            stdout=stdout, stderr=stderr, exit_code=exit_code, files=files,
            usage=usage,
        )

    @staticmethod
    def _kill_process_group(proc) -> None:
        """Kill the whole process group (user code may spawn subprocesses; a
        surviving orphan would keep writing into a torn-down workspace)."""
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()

    async def execute_stream(
        self,
        source_code: str,
        env: dict[str, str] | None = None,
        timeout_s: float | None = None,
        predicted_deps: list[str] | None = None,
    ) -> AsyncIterator[tuple[str, object]]:
        """Streaming twin of :meth:`execute`: an async generator yielding
        ``("stdout"|"stderr", text_chunk)`` as the child produces output,
        then a final ``("end", ExecutionOutcome)`` with the same envelope
        the non-streaming path returns.

        Contract notes:

        - Chunk boundaries are whatever the pipe delivered; multi-byte UTF-8
          sequences split across reads are held by an incremental decoder so
          chunks are always valid text.
        - On timeout the process group is killed and the final outcome
          mirrors :meth:`execute` exactly (stdout "", stderr
          ``EXECUTION_TIMED_OUT``, exit_code -1) — chunks already delivered
          stay delivered; the envelope is authoritative.
        - An abandoned generator (consumer gone mid-stream) kills the
          process group in its ``finally`` — a vanished client must never
          leave user code running against a workspace nothing will snapshot.
        """
        env = env or {}
        timeout_s = timeout_s or self.default_timeout_s
        before = await asyncio.to_thread(snapshot_workspace, self.workspace)
        meter = UsageMeter()

        installed, pip_notes = await self.ensure_dependencies(
            source_code, predicted_deps
        )
        if pip_notes:
            # Surfaced in-band ahead of user output, matching execute()'s
            # prepend; the final envelope re-prepends so both views agree.
            yield ("stderr", pip_notes + "\n")

        proc = None
        pumps: list[asyncio.Task] = []
        timed_out = False
        stdout = stderr = ""
        exit_code: int = -1
        try:
            with tempfile.TemporaryDirectory(prefix="exec-") as td:
                script = Path(td) / "script.py"
                script.write_text(source_code)
                proc = await asyncio.create_subprocess_exec(
                    self.python, str(script),
                    cwd=self.workspace,
                    env=self._child_env(env),
                    stdout=asyncio.subprocess.PIPE,
                    stderr=asyncio.subprocess.PIPE,
                    start_new_session=True,
                )
                loop = asyncio.get_running_loop()
                hard_deadline = loop.time() + timeout_s
                queue: asyncio.Queue[tuple[str, str | None]] = asyncio.Queue()

                async def pump(stream, kind: str) -> None:
                    decoder = codecs.getincrementaldecoder("utf-8")("replace")
                    while True:
                        chunk = await stream.read(1 << 16)
                        if not chunk:
                            tail = decoder.decode(b"", True)
                            if tail:
                                await queue.put((kind, tail))
                            break
                        text = decoder.decode(chunk)
                        if text:
                            await queue.put((kind, text))
                    await queue.put((kind, None))  # EOF marker

                pumps = [
                    asyncio.ensure_future(pump(proc.stdout, "stdout")),
                    asyncio.ensure_future(pump(proc.stderr, "stderr")),
                ]
                parts: dict[str, list[str]] = {"stdout": [], "stderr": []}
                eofs = 0
                while eofs < 2:
                    remaining = hard_deadline - loop.time()
                    if remaining <= 0:
                        timed_out = True
                        break
                    try:
                        kind, text = await asyncio.wait_for(
                            queue.get(), timeout=remaining
                        )
                    except asyncio.TimeoutError:
                        timed_out = True
                        break
                    if text is None:
                        eofs += 1
                        continue
                    parts[kind].append(text)
                    yield (kind, text)
                if timed_out:
                    self._kill_process_group(proc)
                    await proc.wait()
                    stdout, stderr, exit_code = "", EXECUTION_TIMED_OUT, -1
                    # The envelope is authoritative, but a live consumer
                    # deserves the reason in-band too.
                    yield ("stderr", EXECUTION_TIMED_OUT)
                else:
                    await proc.wait()
                    exit_code = proc.returncode
                    stdout = "".join(parts["stdout"])
                    stderr = "".join(parts["stderr"])
        finally:
            for task in pumps:
                task.cancel()
            if proc is not None and proc.returncode is None:
                # Abandoned mid-stream (GeneratorExit lands here): reap the
                # child before the workspace goes away.
                self._kill_process_group(proc)
                await proc.wait()

        if pip_notes:
            stderr = pip_notes + ("\n" + stderr if stderr else "")

        after = await asyncio.to_thread(snapshot_workspace, self.workspace)
        changed = changed_files(before, after)
        usage = meter.finish(
            workspace_bytes_written=sum(after[rel][1] for rel in changed),
            files_changed=len(changed),
            deps_installed=installed,
        )
        yield (
            "end",
            ExecutionOutcome(
                stdout=stdout,
                stderr=stderr,
                exit_code=exit_code,
                files=[self.logical(rel) for rel in changed],
                usage=usage,
            ),
        )

    async def warmup(self) -> str | None:
        """Pre-heat the interpreter/XLA path so the first request doesn't pay it.

        In the TPU pod this runs at container start, before the server
        listens: import jax, touch the device, trigger libtpu init — in a
        throwaway interpreter that exits (and releases the chip, which
        belongs to one process at a time) before any request runs.
        Analogous in spirit to the reference image's matplotlib font-cache
        warmup at build time (executor/Dockerfile:103), but for the XLA
        client. Returns None on success, else the reason it failed — logged
        here and carried by ``/healthz`` as ``warm_error``, never swallowed:
        a pod that cannot reach its accelerator must say so."""
        outcome = await self.execute(
            "import jax\njax.numpy.zeros(8).block_until_ready()\n",
            timeout_s=120.0,
        )
        if outcome.exit_code == 0:
            return None
        lines = [ln for ln in outcome.stderr.splitlines() if ln.strip()]
        # the traceback line naming the exception (jax appends a
        # traceback-filtering notice after it), else the last line
        named = [ln for ln in lines if "Error" in ln or "Exception" in ln]
        reason = (
            f"accelerator warm-up exited {outcome.exit_code}: "
            f"{(named or lines or ['no output'])[-1]}"
        )
        logger.warning("%s\n%s", reason, outcome.stderr)
        return reason
