"""In-process code executor: fresh workspace per execution, no cluster.

The minimum runnable slice (SURVEY.md §7 step 3): same contract as the
Kubernetes backend — restore the client's {path → object id} map into a fresh
workspace, run the code through ``ExecutorCore``, snapshot changed files back
into content-addressed storage — but everything happens in this process on this
host (including its TPU chips, if any). Preserves the reference's single-use
hygiene (kubernetes_code_executor.py:262-264): each execution gets a brand-new
workspace directory, torn down afterwards; state only survives through the
returned file map.
"""

from __future__ import annotations

import asyncio
import logging
import secrets
import shutil
from pathlib import Path

from bee_code_interpreter_tpu.analysis.context import predicted_deps
from bee_code_interpreter_tpu.observability import span
from bee_code_interpreter_tpu.resilience import Deadline
from bee_code_interpreter_tpu.runtime.executor_core import ExecutorCore
from bee_code_interpreter_tpu.services.code_executor import Result
from bee_code_interpreter_tpu.services.storage import Storage
from bee_code_interpreter_tpu.utils.jaxcache import CHECKOUT_CACHE_DIR
from bee_code_interpreter_tpu.utils.validation import AbsolutePath, Hash

logger = logging.getLogger(__name__)


class LocalCodeExecutor:
    def __init__(
        self,
        storage: Storage,
        workspace_root: str | Path = "./.tmp/workspaces",
        disable_dep_install: bool = True,
        execution_timeout_s: float = 60.0,
        shim_dir: str | Path | None = None,
    ) -> None:
        self._storage = storage
        self._workspace_root = Path(workspace_root)
        self._disable_dep_install = disable_dep_install
        self._execution_timeout_s = execution_timeout_s
        self._shim_dir = shim_dir
        # Shared across executions so an installed dep is installed once.
        self._installed_cache: set[str] = set()
        self._preinstalled: frozenset[str] | None = None

    def _preinstalled_set(self) -> frozenset[str]:
        """Distributions already importable in this interpreter (lazy, once).

        The pod executor loads this from the image's requirements.txt; in-process
        we ask importlib.metadata so `import numpy` never triggers pip.
        """
        if self._preinstalled is None:
            import importlib.metadata

            self._preinstalled = frozenset(
                d.metadata["Name"] for d in importlib.metadata.distributions()
                if d.metadata["Name"]
            )
        return self._preinstalled

    def _clamp_timeout(self, timeout_s: float | None) -> float | None:
        """A request may shorten the deadline, never extend past the
        service-configured bound."""
        if timeout_s is None or timeout_s <= 0:
            return None
        return min(timeout_s, self._execution_timeout_s)

    async def execute(
        self,
        source_code: str,
        files: dict[AbsolutePath, Hash] | None = None,
        env: dict[str, str] | None = None,
        timeout_s: float | None = None,
        deadline: Deadline | None = None,
    ) -> Result:
        files = files or {}
        if deadline is not None:
            # The subprocess timeout shrinks to the remaining request budget,
            # so a late-arriving execution can't run past the edge promise.
            deadline.check("execute")
            timeout_s = deadline.clamp(
                self._clamp_timeout(timeout_s) or self._execution_timeout_s
            )
        workspace = self._workspace_root / secrets.token_hex(8)
        core = self._make_core(workspace)
        try:
            # Restore the client's workspace snapshot (reference
            # kubernetes_code_executor.py:100-113, via HTTP PUT; here direct
            # I/O). Stage spans: restore/execute/snapshot are this backend's
            # analogue of the pod path's upload/execute/download — and the
            # byte counts land in the same usage-block keys.
            with span("restore", files=str(len(files))):
                restored_bytes = await self._restore_files(core, files)

            with span("execute"):
                outcome = await core.execute(
                    source_code,
                    env=env,
                    timeout_s=self._clamp_timeout(timeout_s),
                    # The edge's ambient dep prediction (docs/analysis.md)
                    # reaches the in-process core directly — no wire hop.
                    predicted_deps=predicted_deps(),
                )

            # Snapshot changed files back (reference :126-142).
            with span("snapshot", files=str(len(outcome.files))):
                out_files, snapshot_bytes = await self._snapshot_files(
                    core, outcome.files
                )
            usage = dict(outcome.usage or {})
            usage.update(
                uploaded_bytes=restored_bytes,
                uploaded_files=len(files),
                downloaded_bytes=snapshot_bytes,
                downloaded_files=len(out_files),
            )
            return Result(
                stdout=outcome.stdout,
                stderr=outcome.stderr,
                exit_code=outcome.exit_code,
                files=out_files,
                usage=usage,
            )
        finally:
            shutil.rmtree(workspace, ignore_errors=True)

    async def _restore_files(self, core: ExecutorCore, files: dict) -> int:
        """Restore the snapshot map into the workspace, all files
        concurrently (the serial per-file loop was pure added latency for
        multi-file workspaces); returns total bytes restored."""

        async def restore_one(logical_path: str, object_id: str) -> int:
            moved = 0
            real = core.resolve(logical_path)
            real.parent.mkdir(parents=True, exist_ok=True)
            with open(real, "wb") as f:
                async with self._storage.reader(object_id) as r:
                    async for chunk in r:
                        moved += len(chunk)
                        f.write(chunk)
            return moved

        return sum(
            await asyncio.gather(
                *(restore_one(p, oid) for p, oid in files.items())
            )
        )

    async def _snapshot_files(
        self, core: ExecutorCore, logical_paths
    ) -> tuple[dict[str, str], int]:
        """Snapshot changed files into content-addressed storage, all files
        concurrently — the post-execute half of the satellite overlap work
        (ISSUE 7): the snapshot no longer serializes file-by-file ahead of
        the response. Returns ({logical path: object id}, total bytes)."""

        async def snapshot_one(logical_path: str) -> tuple[str, str, int]:
            moved = 0
            real = core.resolve(logical_path)
            async with self._storage.writer() as w:
                with open(real, "rb") as f:
                    while chunk := f.read(1 << 20):
                        moved += len(chunk)
                        await w.write(chunk)
            return logical_path, w.hash, moved

        snapshots = await asyncio.gather(
            *(snapshot_one(p) for p in logical_paths)
        )
        out_files = {path: object_id for path, object_id, _ in snapshots}
        return out_files, sum(moved for _, _, moved in snapshots)

    async def execute_stream(
        self,
        source_code: str,
        files: dict[AbsolutePath, Hash] | None = None,
        env: dict[str, str] | None = None,
        timeout_s: float | None = None,
        on_event=None,  # async (kind, text) -> None per stdout/stderr chunk
        deadline: Deadline | None = None,
    ) -> Result:
        """Streaming execute (docs/sessions.md): same fresh-workspace
        lifecycle as :meth:`execute`, with output chunks forwarded to
        ``on_event`` as the child produces them."""
        files = files or {}
        if deadline is not None:
            deadline.check("execute")
            timeout_s = deadline.clamp(
                self._clamp_timeout(timeout_s) or self._execution_timeout_s
            )
        workspace = self._workspace_root / secrets.token_hex(8)
        core = self._make_core(workspace)
        try:
            with span("restore", files=str(len(files))):
                restored_bytes = await self._restore_files(core, files)
            outcome = None
            with span("execute", stream="1"):
                gen = core.execute_stream(
                    source_code,
                    env=env,
                    timeout_s=self._clamp_timeout(timeout_s),
                    predicted_deps=predicted_deps(),
                )
                try:
                    async for kind, payload in gen:
                        if kind == "end":
                            outcome = payload
                        elif on_event is not None:
                            await on_event(kind, payload)
                finally:
                    await gen.aclose()
            with span("snapshot", files=str(len(outcome.files))):
                out_files, snapshot_bytes = await self._snapshot_files(
                    core, outcome.files
                )
            usage = dict(outcome.usage or {})
            usage.update(
                uploaded_bytes=restored_bytes,
                uploaded_files=len(files),
                downloaded_bytes=snapshot_bytes,
                downloaded_files=len(out_files),
            )
            return Result(
                stdout=outcome.stdout,
                stderr=outcome.stderr,
                exit_code=outcome.exit_code,
                files=out_files,
                usage=usage,
            )
        finally:
            shutil.rmtree(workspace, ignore_errors=True)

    # ---------------------------------------------------------------- leases

    async def checkout_for_lease(self, deadline: Deadline | None = None):
        """Session lease over the in-process backend: a PERSISTENT workspace
        + core that live until the lease ends — the one place this backend
        deliberately departs from its fresh-workspace-per-execute hygiene
        (state is the entire point of a session)."""
        from bee_code_interpreter_tpu.services.code_executor import LeaseHandle

        workspace = self._workspace_root / f"session-{secrets.token_hex(8)}"
        core = self._make_core(workspace)
        return LeaseHandle(
            name=f"local-{workspace.name}",
            kill=lambda: shutil.rmtree(workspace, ignore_errors=True),
            handle=workspace,
            core=core,
        )

    def release_lease(
        self, lease, state: str = "released", reason: str = "lease_released",
        detail: str | None = None,
    ) -> None:
        lease.kill()

    def _make_core(self, workspace: Path) -> ExecutorCore:
        return ExecutorCore(
            workspace=workspace,
            preinstalled=(
                frozenset()
                if self._disable_dep_install
                else self._preinstalled_set()
            ),
            disable_dep_install=self._disable_dep_install,
            default_timeout_s=self._execution_timeout_s,
            shim_dir=self._shim_dir,
            installed_cache=self._installed_cache,
            # this backend runs from the checkout: sandboxes share its
            # fixed compile-cache directory unless the environment or the
            # operator (APP_JAX_CACHE_DIR) names another
            jax_cache_dir=CHECKOUT_CACHE_DIR,
        )
