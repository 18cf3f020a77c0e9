"""On-demand ``jax.profiler`` capture behind ``POST /v1/profile``.

The trace-inspection API (``/v1/traces``) finds the slow *stage*; this
module drills into the slow *op* without redeploying anything:

- **Sandbox executions**: the in-pod shim already starts a profiler trace
  when ``BCI_PROFILE_DIR`` is set (``runtime/shim/sitecustomize.py``), but
  until now only an operator editing request env could use it. The edge
  injects :data:`SANDBOX_PROFILE_DIR` into the request env and the trace
  artifacts ride back through the ordinary changed-file snapshot — no new
  download channel.
- **The serving engine**: :class:`ServingProfiler` wraps a stepper — the
  ``ServingMonitor`` once an engine is attached — and captures N steps
  under the trace the stepper itself opens (``profiler_trace``, which the
  batcher implements with ``jax.profiler``) into a local directory the
  operator can pull into TensorBoard/XProf.

This module never imports ``jax``: only the process that holds the chip
can trace it, and a control plane serving the executor path holds none —
its sandbox children do. No engine attached means no profiler (501).
"""

from __future__ import annotations

import contextlib
import os
import shutil
import tempfile
import threading
import time
from pathlib import Path

# Where a profiled sandbox execution writes its trace; lives under the
# workspace so the artifacts come back via the changed-file map.
PROFILE_DIR_ENV = "BCI_PROFILE_DIR"
SANDBOX_PROFILE_DIR = "/workspace/.bci-profile"


class ProfilerUnavailable(RuntimeError):
    """No engine to trace through, its profiler backend refused to start,
    or a capture is already running."""


def inject_profile_env(env: dict[str, str] | None) -> dict[str, str]:
    """Request env with the shim's profile trigger set (a caller's
    NON-EMPTY value wins, so a client may point the trace elsewhere in the
    workspace). Empty counts as unset: the shim ignores an empty dir, and
    a "" profile_dir would make every changed file look like an artifact
    (prefix "/" matches all workspace paths)."""
    out = dict(env or {})
    if not out.get(PROFILE_DIR_ENV):
        out[PROFILE_DIR_ENV] = SANDBOX_PROFILE_DIR
    return out


def profile_artifacts(files: dict[str, str], profile_dir: str) -> list[str]:
    """The changed-file paths that are profiler trace artifacts."""
    prefix = profile_dir.rstrip("/") + "/"
    return sorted(p for p in files if p.startswith(prefix))


class ServingProfiler:
    """Captures batcher/engine steps under the stepper's profiler trace.

    ``stepper`` is anything with ``step()`` and ``profiler_trace(dir)`` (a
    context manager around the capture — ``ContinuousBatcher``'s is
    ``jax.profiler.trace``). Overlapping captures are rejected internally
    (atomic check-and-set under a lock) — ``jax.profiler`` is
    process-global and two concurrent traces would corrupt each other, and
    the HTTP handler runs captures off-loop in a thread pool where two
    requests CAN race.
    """

    _dir_prefix = "bci-profile-"

    def __init__(self, stepper, trace_root: str | Path | None = None) -> None:
        self._stepper = stepper
        self._trace_root = str(trace_root) if trace_root else None
        self._capturing = False
        self._lock = threading.Lock()

    @property
    def capturing(self) -> bool:
        return self._capturing

    @property
    def available(self) -> bool:
        """True when the stepper can actually step. A stepper may expose
        its own ``available`` (the ServingMonitor reports False until an
        engine attaches — the edge answers 501, not a capture error);
        steppers without the attribute are assumed ready."""
        return bool(getattr(self._stepper, "available", True))

    def capture(self, steps: int) -> dict:
        """Run ``steps`` stepper steps under a profiler trace; returns
        ``{trace_dir, files, steps, duration_ms}`` with ``files`` relative
        to ``trace_dir``. Raises :class:`ProfilerUnavailable` if a capture
        is already running or the trace cannot start."""
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        with self._lock:
            if self._capturing:
                raise ProfilerUnavailable("a capture is already in progress")
            self._capturing = True
        # EVERY exit path below must reset the flag — a stuck True would
        # 503 all future serving captures until process restart.
        try:
            trace_dir = tempfile.mkdtemp(
                prefix=self._dir_prefix, dir=self._trace_root
            )
            t0 = time.monotonic()
            with contextlib.ExitStack() as stack:
                try:
                    stack.enter_context(
                        self._stepper.profiler_trace(trace_dir)
                    )
                except Exception as e:
                    # Nothing was captured: don't leak an empty trace dir
                    # per failed attempt on hosts without a profiler.
                    shutil.rmtree(trace_dir, ignore_errors=True)
                    raise ProfilerUnavailable(
                        f"profiler trace cannot start: {e}"
                    ) from e
                for _ in range(steps):
                    self._stepper.step()
        finally:
            self._capturing = False
        files = sorted(
            str(Path(root, name).relative_to(trace_dir))
            for root, _dirs, names in os.walk(trace_dir)
            for name in names
        )
        return {
            "trace_dir": trace_dir,
            "files": files,
            "steps": steps,
            "duration_ms": (time.monotonic() - t0) * 1000.0,
        }


class DeviceProfiler(ServingProfiler):
    """``POST /v1/profile target=device``: the same capture, kept as its
    own route for the raw device timeline (XLA ops, transfers, compiles —
    docs/observability.md "Accelerator observability"). It traces the
    attached engine's steps; with no engine attached the edge answers 501,
    because this process does not hold the device and a probe computation
    here would take it from the sandbox that does."""

    _dir_prefix = "bci-device-profile-"

    def capture(self, steps: int = 8) -> dict:
        return {**super().capture(steps), "source": "serving"}
