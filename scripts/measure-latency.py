#!/usr/bin/env python
"""Measure /v1/execute latency percentiles (BASELINE.json metric: /v1/execute p50).

Drives the trivial health-check payload (``print(21 * 2)``) through two
execution backends and reports p50/p95/p99 PER STAGE (spawn/upload/execute/
download on the warm path; restore/execute/snapshot on the cold path) from
the tracing subsystem's per-request stage spans (docs/observability.md) —
a latency regression is attributed to the stage that moved, not guessed at
from a single end-to-end number.

- **warm**: NativeProcessCodeExecutor — warm pool of C++ sandbox servers, the
  TPU-native analogue of the reference's warm pod queue
  (kubernetes_code_executor.py:151-264). This is what a client observes when
  the pool keeps up.
- **cold**: LocalCodeExecutor — a fresh interpreter spawned per request; the
  pool-empty worst case (analogous to the reference's cold pod spawn, minus
  the k8s scheduling delay which depends on the cluster).

Usage: python scripts/measure-latency.py [N]    (default 30 requests each)
"""

from __future__ import annotations

import asyncio
import statistics
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

PAYLOAD = "print(21 * 2)"

# Stage display order; stages a backend never produced are omitted.
STAGE_ORDER = (
    "spawn", "restore", "upload", "execute", "snapshot", "download",
)


def pct(samples: list[float], q: float) -> float:
    return statistics.quantiles(samples, n=100)[int(q) - 1]


def report_stages(name: str, stages: list[dict[str, float]],
                  totals_ms: list[float]) -> None:
    """p50/p95/p99 per stage (milliseconds). A request that skipped a stage
    (warm pop → no spawn; no files → no upload/download) contributes 0 to
    that stage, so the percentiles describe what clients actually pay."""
    seen = [s for s in STAGE_ORDER if any(s in d for d in stages)]
    print(f"{name}: n={len(totals_ms)}  (stage ms, then total)")
    for stage in [*seen, "total"]:
        vals = (
            totals_ms if stage == "total"
            else [float(d.get(stage, 0.0)) for d in stages]
        )
        print(
            f"  {stage:>9}: p50={pct(vals, 50):8.1f}  "
            f"p95={pct(vals, 95):8.1f}  p99={pct(vals, 99):8.1f}"
        )


async def bench_warm(n: int) -> tuple[list[dict], list[float]]:
    from bee_code_interpreter_tpu.config import Config
    from bee_code_interpreter_tpu.observability import Tracer
    from bee_code_interpreter_tpu.services.native_process_code_executor import (
        NativeProcessCodeExecutor,
    )
    from bee_code_interpreter_tpu.services.storage import Storage

    tmp = Path(tempfile.mkdtemp(prefix="lat-warm-"))
    config = Config(
        file_storage_path=str(tmp / "objects"),
        local_workspace_root=str(tmp / "ws"),
        executor_pod_queue_target_length=4,
        disable_dep_install=True,
    )
    executor = NativeProcessCodeExecutor(
        storage=Storage(tmp / "objects"),
        config=config,
        binary=REPO / "executor" / "build" / "executor-server",
    )
    tracer = Tracer()
    try:
        await executor.fill_sandbox_queue()
        stages: list[dict] = []
        totals: list[float] = []
        phases: list[dict] = []
        for i in range(n):
            if i:
                # measure request latency, not saturated throughput: give the
                # refill pipeline room so pops hit preload-complete sandboxes
                await asyncio.sleep(0.35)
            t0 = time.perf_counter()
            with tracer.trace("measure-latency") as t:
                r = await executor.execute(PAYLOAD)
            assert r.stdout == "42\n", r.stderr
            totals.append((time.perf_counter() - t0) * 1000)
            stages.append(t.stage_ms())
            phases.append(dict(executor.last_execute_phases))
        # the native backend's own internal phase probe, complementary to
        # the trace stages (it sees inside the HTTP call: sandbox vs
        # control-plane overhead)
        keys = ("acquire_ms", "upload_ms", "post_execute_ms", "sandbox_ms",
                "overhead_ms", "download_ms")
        for q in (50, 90):
            row = {
                k: pct([float(p.get(k, 0.0)) for p in phases], q)
                for k in keys
            }
            print(
                f"warm phases p{q}: "
                + "  ".join(f"{k}={v:.1f}" for k, v in row.items())
            )
        return stages, totals
    finally:
        executor.shutdown()


async def bench_cold(n: int) -> tuple[list[dict], list[float]]:
    from bee_code_interpreter_tpu.observability import Tracer
    from bee_code_interpreter_tpu.services.local_code_executor import (
        LocalCodeExecutor,
    )
    from bee_code_interpreter_tpu.services.storage import Storage

    tmp = Path(tempfile.mkdtemp(prefix="lat-cold-"))
    executor = LocalCodeExecutor(
        storage=Storage(tmp / "objects"),
        workspace_root=tmp / "ws",
        disable_dep_install=True,
    )
    tracer = Tracer()
    stages: list[dict] = []
    totals: list[float] = []
    for _ in range(n):
        t0 = time.perf_counter()
        with tracer.trace("measure-latency") as t:
            r = await executor.execute(PAYLOAD)
        assert r.stdout == "42\n", r.stderr
        totals.append((time.perf_counter() - t0) * 1000)
        stages.append(t.stage_ms())
    return stages, totals


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 30
    import subprocess

    subprocess.run(["make", "-C", str(REPO / "executor"), "-s"], check=True)
    for name, fn in (("warm", bench_warm), ("cold", bench_cold)):
        stages, totals = asyncio.run(fn(n))
        report_stages(name, stages, totals)


if __name__ == "__main__":
    main()
