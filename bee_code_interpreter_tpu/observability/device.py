"""Accelerator observability (docs/observability.md "Accelerator
observability").

The control plane has been watchable end to end since PR 2–18; the
accelerator tree was runtime-blind. :class:`DeviceMonitor` is the
runtime-signal layer for ``models/``/``ops/``/``parallel/`` — three
signals, all CPU-deterministic so tier-1 needs no TPU:

- **Compile/retrace tracking**: the batcher's jit entry points are wrapped
  in :class:`~bee_code_interpreter_tpu.utils.jitwatch.TrackedJit`, which
  duck-calls :meth:`on_compile` on every XLA compilation. Each one becomes
  exactly one ``kind="compile"`` wide event in the flight recorder, a
  ``bci_compile_total{trigger}`` increment, a ``bci_compile_seconds``
  observation, and — when it fired under an active request trace (the
  batcher activates the request's trace around admission) — a backdated
  ``xla.compile`` span inside that request's span tree, all naming the
  same trace_id. A TTFT spike caused by a mid-stream retrace is therefore
  visible in three correlated places, not zero.
- **Device-memory accounting**: a periodic sampler over the ATTACHED
  batcher's ``device_memory()`` — ``device.memory_stats()`` where the
  backend provides it (TPU), a live-buffer byte estimate on CPU (rows
  marked ``estimated``) — published as ``bci_device_hbm_bytes{kind=
  live|peak|limit}`` per device. A chip belongs to one process, so a
  process with no engine attached (the execute-path control plane, whose
  sandbox children own the chip) never imports jax and reports no memory
  rows. The paged-KV pool occupancy joins the snapshot from the batcher's
  ``kv_telemetry()`` (PR 9 ``pool_telemetry``) so "how full is HBM" and
  "how full is the KV pool" read from one call.
- **Mesh-aware step telemetry**: the batcher (and the MULTICHIP dryrun)
  report per-step wall time tagged with the mesh's shape key
  (``parallel.mesh.mesh_shape_key``), aggregated per shape — the
  tokens/sec-vs-mesh-shape curve ROADMAP item 4 is verified against.

Served at ``GET /v1/accelerator`` (+ gRPC
``ObservabilityService/GetAccelerator``, a debug-bundle section, and an
``accelerator`` summary on ``/v1/fleet`` for router placement). Like
``ServingMonitor``, the monitor is duck-typed from the models/ side: the
batcher calls hooks when one is attached and pays a single None check
otherwise, so ``models/`` never imports this package.
"""

from __future__ import annotations

import asyncio
import threading
import time
from collections import deque

from bee_code_interpreter_tpu.observability.tracing import current_trace

# why a process with no engine attached reports no device memory
NO_ENGINE = (
    "no in-process engine attached: the accelerator belongs to the "
    "process that runs one (here, the sandbox children)"
)

# histogram buckets for compile wall time: compiles run 10 ms (tiny CPU
# programs) to minutes (big sharded models) — the serving-latency buckets
# top out far too low to see them
COMPILE_SECONDS_BUCKETS = (
    0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)


class DeviceMonitor:
    """Compile/retrace tracking, device-memory accounting, and per-mesh-
    shape step telemetry. Constructed by the composition root next to the
    other monitors (metrics register immediately); :meth:`attach` binds a
    ``models.engine.Engine`` or bare ``ContinuousBatcher``, injects the
    monitor into its tracked jits and takes the first memory sample —
    until then nothing here touches the device.
    """

    def __init__(
        self,
        *,
        metrics=None,
        recorder=None,  # flightrecorder.FlightRecorder
        sample_interval_s: float = 10.0,
        max_compiles: int = 256,
    ) -> None:
        self._recorder = recorder
        self._metrics = metrics
        self._sample_interval_s = sample_interval_s
        self._lock = threading.Lock()
        self._compiles: deque[dict] = deque(maxlen=max(1, max_compiles))
        self._compile_seq = 0
        self._compile_by_trigger: dict[str, int] = {}
        # function name -> per-function compile ledger; the signature list
        # is the per-function signature SET (insertion-ordered), so a
        # retrace names the shape/dtype that caused it next to every shape
        # seen before
        self._functions: dict[str, dict] = {}
        self._mesh: dict | None = None
        self._shapes: dict[str, dict] = {}
        self._memory: list[dict] = []
        self._memory_unix: float | None = None
        self._memory_samples = 0
        self._peak_estimate: dict[str, int] = {}
        self._gauged: set[tuple[str, str]] = set()
        self._engine = None
        self._batcher = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._sampler_task: asyncio.Task | None = None
        self._compile_total = None
        self._compile_seconds = None
        self._step_seconds = None
        if metrics is not None:
            self._compile_total = metrics.counter(
                "bci_compile_total",
                "XLA compilations observed by the tracked jits, by trigger "
                "(first_call|retrace)",
            )
            self._compile_seconds = metrics.histogram(
                "bci_compile_seconds",
                "Wall time of one XLA compilation (the stall the caller felt)",
                buckets=COMPILE_SECONDS_BUCKETS,
            )
            self._step_seconds = metrics.histogram(
                "bci_device_step_seconds",
                "Batcher/dryrun step wall time, by mesh shape",
            )

    # ------------------------------------------------------------ wiring

    def attach(self, target) -> None:
        """Bind a ``models.engine.Engine`` (or a bare ``ContinuousBatcher``)
        so its tracked jits report compiles here, its step timings land in
        the per-shape aggregates, and the snapshot joins its KV-pool
        telemetry + mesh descriptor."""
        batcher = getattr(target, "batcher", target)
        self._engine = target if batcher is not target else None
        self._batcher = batcher
        batcher.set_device_monitor(self)
        try:
            from bee_code_interpreter_tpu.parallel.mesh import mesh_descriptor

            self.set_mesh(mesh_descriptor(getattr(batcher, "mesh", None)))
        except Exception:
            # descriptor is best-effort: a mock batcher (tests) or an
            # import-stripped image must not break attachment
            pass
        try:
            self._loop = asyncio.get_running_loop()
        except RuntimeError:
            pass
        # the HBM gauges must exist (and the snapshot must be complete)
        # before — or without — the background sampler
        self.sample_memory()

    @property
    def available(self) -> bool:
        return self._batcher is not None

    def set_mesh(self, descriptor: dict | None) -> None:
        """Record the current mesh context (``parallel.mesh
        .mesh_descriptor``); subsequent compiles and step records carry its
        shape key."""
        with self._lock:
            self._mesh = descriptor

    def arm_loop(self, loop: asyncio.AbstractEventLoop | None = None) -> None:
        """Bind the loop wide events are delivered on when ``on_compile``
        fires off-loop (profiler capture threads, the bench) — same
        contract as ``ServingMonitor.arm_loop``."""
        self._loop = loop if loop is not None else asyncio.get_running_loop()

    def start(self) -> None:
        """Start the periodic memory sampler (must be called from a running
        loop; ``ApplicationContext.start_observability`` does). Also arms
        the event-delivery loop."""
        self.arm_loop()
        if self._sampler_task is None or self._sampler_task.done():
            self._sampler_task = asyncio.get_running_loop().create_task(
                self._sample_loop(), name="device-monitor-sampler"
            )

    def stop(self) -> None:
        if self._sampler_task is not None:
            self._sampler_task.cancel()
            self._sampler_task = None

    async def _sample_loop(self) -> None:
        while True:
            await asyncio.sleep(self._sample_interval_s)
            # off the loop: memory_stats is a backend call and the CPU
            # degradation walks every live buffer
            await asyncio.to_thread(self.sample_memory)

    # ----------------------------------------------------- compile hook

    def on_compile(
        self,
        name: str,
        *,
        signature: str,
        duration_ms: float,
        trigger: str,
    ) -> None:
        """One XLA compilation happened (TrackedJit calls this). Exactly one
        wide event + one counter increment + (when a request trace is
        active) one backdated ``xla.compile`` span, all naming the same
        trace_id."""
        trace = current_trace()
        trace_id = request_id = None
        if trace is not None:
            duration_s = duration_ms / 1000.0
            s = trace.start_span(
                "xla.compile",
                parent_id=trace.root.span_id,
                attributes={
                    "function": name,
                    "signature": signature,
                    "trigger": trigger,
                },
            )
            # backdate: the compile already happened (the wrapper timed it)
            s.start_mono -= duration_s
            s.start_unix -= duration_s
            trace.end_span(s)
            trace_id, request_id = trace.trace_id, trace.request_id
        with self._lock:
            self._compile_seq += 1
            self._compile_by_trigger[trigger] = (
                self._compile_by_trigger.get(trigger, 0) + 1
            )
            fn = self._functions.setdefault(
                name,
                {
                    "compiles": 0,
                    "triggers": {},
                    "signatures": [],
                    "last_compile_ms": None,
                },
            )
            fn["compiles"] += 1
            fn["triggers"][trigger] = fn["triggers"].get(trigger, 0) + 1
            if signature not in fn["signatures"]:
                fn["signatures"].append(signature)
            fn["last_compile_ms"] = duration_ms
            mesh_shape = self._mesh["shape"] if self._mesh else None
            record = {
                "seq": self._compile_seq,
                "ts": time.time(),
                "function": name,
                "signature": signature,
                "trigger": trigger,
                "duration_ms": duration_ms,
                "mesh": mesh_shape,
                "trace_id": trace_id,
            }
            self._compiles.append(record)
        if self._compile_total is not None:
            self._compile_total.inc(trigger=trigger)
        if self._compile_seconds is not None:
            # observed while the request's trace is still ambient, so the
            # OpenMetrics exemplar names the same trace_id as the event
            self._compile_seconds.observe(duration_ms / 1000.0)
        event: dict = {
            "kind": "compile",
            "name": "xla.compile",
            "outcome": "ok",
            "function": name,
            "signature": signature,
            "trigger": trigger,
            "duration_ms": duration_ms,
            "mesh": mesh_shape,
        }
        if trace_id is not None:
            event["trace_id"] = trace_id
        if request_id is not None:
            event["request_id"] = request_id
        self._emit(event)

    # -------------------------------------------------------- step hook

    def record_step(self, duration_ms: float, shape: str | None = None) -> None:
        """One batcher/dryrun step finished under mesh shape ``shape``
        (default: the attached mesh's shape key). Aggregated per shape —
        the raw ring stays the ServingMonitor's job."""
        with self._lock:
            if shape is None:
                shape = self._mesh["shape"] if self._mesh else "1"
            agg = self._shapes.setdefault(
                shape,
                {
                    "steps": 0,
                    "total_ms": 0.0,
                    "min_ms": None,
                    "max_ms": 0.0,
                    "last_ms": 0.0,
                },
            )
            agg["steps"] += 1
            agg["total_ms"] += duration_ms
            agg["min_ms"] = (
                duration_ms
                if agg["min_ms"] is None
                else min(agg["min_ms"], duration_ms)
            )
            agg["max_ms"] = max(agg["max_ms"], duration_ms)
            agg["last_ms"] = duration_ms
        if self._step_seconds is not None:
            self._step_seconds.observe(duration_ms / 1000.0, mesh=shape)

    # ----------------------------------------------------- memory sampler

    def sample_memory(self) -> list[dict]:
        """One device-memory sample through the attached batcher's
        ``device_memory()``; estimated (CPU) rows get their peak tracked
        here as a running max. No engine attached: no rows, and nothing is
        imported or initialized. Registers the per-(device, kind)
        ``bci_device_hbm_bytes`` gauge series on first sight."""
        if self._batcher is None:
            return []
        rows = self._batcher.device_memory()
        for row in rows:
            if row["estimated"]:
                peak = max(
                    self._peak_estimate.get(row["device"], 0),
                    row["live_bytes"],
                )
                self._peak_estimate[row["device"]] = peak
                row["peak_bytes"] = peak
        with self._lock:
            self._memory = rows
            self._memory_unix = time.time()
            self._memory_samples += 1
        if self._metrics is not None:
            for row in rows:
                for kind in ("live", "peak", "limit"):
                    gauge_key = (row["device"], kind)
                    if gauge_key in self._gauged:
                        continue
                    self._gauged.add(gauge_key)
                    self._metrics.gauge(
                        "bci_device_hbm_bytes",
                        "Device memory bytes by kind (live|peak|limit) on "
                        "the attached engine's devices; live-buffer "
                        "estimate on backends without memory_stats",
                        (
                            lambda d=row["device"], k=kind: float(
                                self._memory_value(d, k)
                            )
                        ),
                        device=row["device"],
                        kind=kind,
                    )
        return rows

    def _memory_value(self, device: str, kind: str) -> int:
        with self._lock:
            for row in self._memory:
                if row["device"] == device:
                    value = row.get(f"{kind}_bytes")
                    return int(value) if value is not None else 0
        return 0

    # ------------------------------------------------------------ queries

    def snapshot(self, recent: int = 16) -> dict:
        """The ``GET /v1/accelerator`` body: compile totals + per-function
        ledgers + the last ``recent`` compile records, the latest memory
        sample (``estimated`` marks the CPU degradation; no rows and a
        ``reason`` while no engine is attached), the KV-pool occupancy
        joined from the attached batcher, the mesh descriptor, and the
        per-shape step aggregates. Pure host bookkeeping — safe on every
        scrape."""
        with self._lock:
            functions = {
                name: {
                    "compiles": fn["compiles"],
                    "triggers": dict(fn["triggers"]),
                    "signatures": list(fn["signatures"]),
                    "last_compile_ms": fn["last_compile_ms"],
                }
                for name, fn in self._functions.items()
            }
            memory_rows = [dict(row) for row in self._memory]
            body: dict = {
                "attached": self._batcher is not None,
                "compile": {
                    "total": self._compile_seq,
                    "by_trigger": dict(self._compile_by_trigger),
                    "functions": functions,
                    "recent": (
                        list(self._compiles)[-recent:] if recent > 0 else []
                    ),
                },
                "memory": {
                    "sampled_unix": self._memory_unix,
                    "samples": self._memory_samples,
                    "estimated": (
                        any(row["estimated"] for row in memory_rows)
                        if memory_rows
                        else None
                    ),
                    "devices": memory_rows,
                    "reason": (
                        None if self._batcher is not None else NO_ENGINE
                    ),
                },
                "mesh": dict(self._mesh) if self._mesh else None,
                "steps": {
                    "by_shape": {
                        shape: dict(agg)
                        for shape, agg in self._shapes.items()
                    }
                },
            }
        body["kv_pool"] = (
            self._batcher.kv_telemetry() if self._batcher is not None else None
        )
        return body

    def fleet_summary(self) -> dict:
        """The compact ``accelerator`` section of ``GET /v1/fleet`` — the
        compile-pressure and HBM-headroom numbers a fleet router's refresh
        loop reads for placement, without the per-function ledgers."""
        with self._lock:
            limits = [
                row["limit_bytes"]
                for row in self._memory
                if row["limit_bytes"] is not None
            ]
            return {
                "compiles": self._compile_seq,
                "retraces": self._compile_by_trigger.get("retrace", 0),
                "mesh": self._mesh["shape"] if self._mesh else None,
                "hbm": {
                    "live_bytes": sum(
                        row["live_bytes"] for row in self._memory
                    ),
                    "limit_bytes": sum(limits) if limits else None,
                    "estimated": (
                        any(row["estimated"] for row in self._memory)
                        if self._memory
                        else None
                    ),
                },
            }

    # ------------------------------------------------------------ private

    def _emit(self, event: dict) -> None:
        if self._recorder is None:
            return
        try:
            # remember the loop whenever one is running here, so compiles
            # that later fire off-loop know where to deliver
            self._loop = asyncio.get_running_loop()
        except RuntimeError:
            # off-loop caller (profiler capture thread, bench): hand the
            # event to the recorder's loop — its follower queues are
            # asyncio objects a foreign thread must not poke directly
            loop = self._loop
            if loop is not None and loop.is_running():
                loop.call_soon_threadsafe(self._recorder.record, event)
                return
            # no loop was ever armed: nothing async can be following the
            # recorder either, so the direct call only touches the ring
        self._recorder.record(event)
