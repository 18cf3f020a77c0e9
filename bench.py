#!/usr/bin/env python
"""Benchmark: dense-matmul GFLOPS/chip driven through /v1/execute, plus the
host-plane phases of the service (latency, surge, router, fairness, serving
stack, streaming).

The device phases — the bf16 matmul chain (the BASELINE.json north-star
payload shape) and the Pallas flash-attention kernel against XLA's own
attention — are submitted through the service's real execution path (the
sandbox executor with the TPU runtime shim) and run ONCE. They assert the
platform they expect: without a TPU they FAIL. There is no probe loop, no
CPU substitute and no recorded number; every result names the
platform/device_kind it ran on. The host-plane phases say nothing about the
device and say so (``platform: host``).

A failed phase is listed under ``failed_phases`` with its error and the exit
code is non-zero. Prints ONE JSON line:
    {"metric": ..., "value": N | null, "unit": "GFLOPS", "device": {...},
     "failed_phases": {...}, "latency_warm_p50_ms": N | null, ...,
     "serving": {...} the continuous-batching stack through the service path
     (tokens/sec, TTFT p50/p95, inter-token latency, and a measured
     instrumentation on/off overhead — models/serving_bench.py; CPU-pinned
     by design and labelled so)}

Extra detail lines go to stderr. This parent never imports jax: a chip
belongs to one process at a time, and here that is the sandbox child.
Sandboxes keep their compile cache where utils/jaxcache.py says.
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
SHIM_DIR = REPO / "bee_code_interpreter_tpu" / "runtime" / "shim"

N = 32768
ITERS = 16


def matmul_chain_payload(platform: str = "tpu") -> str:
    """The measured payload: a bf16 matmul chain under jit, the shape of
    work the MXU exists for. Chained with a data dependency (no loop
    hoisting), one device->host readback at the end. Written the way a
    sandbox user writes JAX. n=32768 keeps each matmul MXU-bound long enough
    to amortize loop/dispatch overhead; the one-time 1/128 pre-scale keeps
    the chain's magnitudes roughly stable without paying a per-iteration
    epilogue.

    The payload FAILS unless it runs on ``platform`` — bench.py always asks
    for "tpu"; chip_smoke.py's CPU tier-1 test asks for "cpu" and gets a
    mechanics-only size. The result line names the device it ran on."""
    return f"""
import json, time
t0 = time.time()
import jax, jax.numpy as jnp
from jax import lax

device = jax.devices()[0]
init_s = time.time() - t0  # import + backend init
assert device.platform == {platform!r}, (
    f"matmul chain expected a {platform} backend, found {{device.platform}}"
)
n, iters = ({N}, {ITERS}) if device.platform == "tpu" else (1024, 4)
a = jax.random.normal(jax.random.PRNGKey(0), (n, n), dtype=jnp.bfloat16)

@jax.jit
def chain(a):
    a = a * jnp.bfloat16(1 / 128)
    def body(i, x):
        return a @ x
    return lax.fori_loop(0, iters, body, a).sum()

t0 = time.time()
float(chain(a))  # compile + warm
first = time.time() - t0
best = float("inf")
for _ in range(3):
    t0 = time.time()
    float(chain(a))
    best = min(best, time.time() - t0)
print("RESULT_MATMUL", json.dumps({{
    "gflops": round(2 * n**3 * iters / best / 1e9, 1),
    "platform": device.platform, "device_kind": device.device_kind,
    "device_count": len(jax.devices()), "n": n, "iters": iters,
    "init_s": round(init_s, 1), "first_call_s": round(first, 2),
    "best_s": round(best, 4),
}}))
"""


# Host-CPU comparison: the same kernel as the matmul chain — one-time 1/128
# pre-scale, then a pure data-dependent matmul chain with a single readback —
# through plain numpy (f32; numpy has no bf16), sized down (self-timed wall
# clock, as the reference's own benchmark payload does). A HOST number: it
# runs with the reroute opted out and is reported as platform "host".
CPU_PAYLOAD = """
import time
import numpy as np

n, iters = 2048, 8
a = np.random.rand(n, n).astype(np.float32) * np.float32(1 / 128)
x = a
t0 = time.time()
for _ in range(iters):
    x = a @ x
s = float(x.sum())
dt = time.time() - t0
print(f"RESULT_GFLOPS {2 * n**3 * iters / dt / 1e9:.1f}")
"""

LATENCY_PAYLOAD = "print(21 * 2)"

#: HARD budget for the edge static-analysis gate on the warm path
#: (docs/analysis.md "Observability"): < 1 ms p50 added per execute, now
#: including the dataflow pass AND the accelerator cost classifier.
ANALYSIS_BUDGET_MS = 1.0


def check_analysis_budget(phases_p50: dict) -> None:
    """HARD budget, not a report: failing the whole latency phase is
    deliberate — a silently regressed gate would otherwise ride along
    inside a number nobody decomposes. Split out of measure_latency so
    tests/test_bench.py can pin the raise itself (the guard must keep
    firing as classifiers accrete on the gate)."""
    if phases_p50["analysis_ms"] >= ANALYSIS_BUDGET_MS:
        raise RuntimeError(
            f"analysis gate over budget: p50 {phases_p50['analysis_ms']:.3f} ms"
            f" >= {ANALYSIS_BUDGET_MS:g} ms — the static-analysis pass "
            "regressed the warm path"
        )

# The Pallas flash-attention kernel vs XLA's own attention
# (reference_attention compiled by XLA — a plain einsum+softmax, NOT a tuned
# fused lowering), through the same execution path. Timing by the
# (t_N - t_1)/(N-1) chain difference (utils/benchclock.py), which cancels
# the fixed per-call cost exactly. TPU only: the payload fails elsewhere.
FLASH_PAYLOAD = """
import json, time
import jax, jax.numpy as jnp
from jax import lax
from bee_code_interpreter_tpu.ops.flash_attention import flash_attention
from bee_code_interpreter_tpu.parallel.ring_attention import reference_attention
from bee_code_interpreter_tpu.utils.benchclock import chain_diff

device = jax.devices()[0]
assert device.platform == "tpu", (
    f"flash phase expected a tpu backend, found {device.platform}"
)
B, H, L, D = 4, 16, 4096, 128
N = 32
q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, L, D), jnp.bfloat16)
           for i in range(3))

def chain(attn, length):
    @jax.jit
    def f(q, k, v):
        def body(c, _):
            return attn(c, k, v), None
        c, _ = lax.scan(body, q, None, length=length)
        return c.astype(jnp.float32).sum()
    return f

def per_call(attn, what):
    def best_of(f):
        float(f(q, k, v))
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            float(f(q, k, v))
            best = min(best, time.perf_counter() - t0)
        return best
    return chain_diff(best_of(chain(attn, N)), best_of(chain(attn, 1)), N, what)

t_fl = per_call(lambda q, k, v: flash_attention(q, k, v, True), "flash")
t_xl = per_call(
    lambda q, k, v: reference_attention(q, k, v, causal=True).astype(q.dtype),
    "xla",
)
flops = 2 * B * H * L * L * D  # causal: half of 4*B*H*L*L*D
print("RESULT_FLASH", json.dumps({
    "tflops": round(flops / t_fl / 1e12, 2),
    "xla_ref_tflops": round(flops / t_xl / 1e12, 2),
    "shape": "B4 H16 L4096 D128 bf16 causal",
    "platform": device.platform, "device_kind": device.device_kind,
}))
"""

# Serving phase through the service path (ROADMAP item 4: "a tokens/sec +
# TTFT trajectory alongside warm-execute p50"): a continuous-batching run
# on already-compiled programs, measured with the full observability stack
# attached AND bare, so every artifact carries tokens/sec, TTFT p50/p95,
# inter-token latency, and the MEASURED instrumentation overhead. The
# arithmetic lives in models/serving_bench.py (shared with the tier-1
# suite); arm-equality and pass-to-pass determinism are asserted inside.
# CPU-pinned BY DESIGN (tiny model): a trajectory of the serving stack's
# host code, labelled platform "cpu" — never a device number.
SERVING_PAYLOAD = """
import json
from bee_code_interpreter_tpu.models.serving_bench import run_serving_bench
print("RESULT_SERVING_JSON", json.dumps(run_serving_bench()))
"""


class PayloadError(RuntimeError):
    """Payload failure carrying the sandbox stderr for the bench artifact."""

    def __init__(self, msg: str, stderr: str = "") -> None:
        super().__init__(msg)
        self.stderr = stderr


async def run_payload(
    source: str, env: dict[str, str], timeout_s: float,
    marker: str = "RESULT_GFLOPS",
) -> float:
    values = await run_payload_values(source, env, timeout_s, marker)
    return values[0]


async def run_payload_values(
    source: str, env: dict[str, str], timeout_s: float, marker: str
) -> list[float]:
    """Execute through the service path; return the floats following
    ``marker`` on the payload's result line."""
    return (await run_payload_multi(source, env, timeout_s, (marker,)))[marker]


async def _run_payload_result(source: str, env: dict[str, str], timeout_s: float):
    """One execution through the service path — the scaffold the marker
    parsers below share; raises PayloadError (stderr attached) on a
    nonzero exit."""
    from bee_code_interpreter_tpu.services.local_code_executor import (
        LocalCodeExecutor,
    )
    from bee_code_interpreter_tpu.services.storage import Storage

    tmp = tempfile.mkdtemp(prefix="bench-")
    executor = LocalCodeExecutor(
        storage=Storage(Path(tmp) / "objects"),
        workspace_root=Path(tmp) / "ws",
        disable_dep_install=True,
        execution_timeout_s=timeout_s,
        shim_dir=SHIM_DIR,
    )
    result = await executor.execute(source, env=env)
    if result.exit_code != 0:
        print(result.stderr, file=sys.stderr)
        raise PayloadError(
            f"payload failed (exit {result.exit_code})", stderr=result.stderr
        )
    return result


async def run_payload_multi(
    source: str, env: dict[str, str], timeout_s: float,
    markers: tuple[str, ...],
) -> dict[str, list[float]]:
    """Execute ONCE through the service path; return the floats following
    each ``marker`` line (one executor run can carry several measurements —
    scripts/bench-mfu.py's train + decode share a payload)."""
    result = await _run_payload_result(source, env, timeout_s)
    out: dict[str, list[float]] = {}
    for line in result.stdout.splitlines():
        for marker in markers:
            if line.startswith(marker):
                out[marker] = [float(tok) for tok in line.split()[1:]]
    missing = [m for m in markers if m not in out]
    if missing:
        raise PayloadError(
            f"no {missing} in stdout: {result.stdout!r}"
        )
    return out


async def run_payload_json(
    source: str, env: dict[str, str], timeout_s: float, marker: str
) -> dict:
    """Execute through the service path; return the JSON object following
    ``marker`` on the payload's result line (structured measurements — the
    serving phase reports a whole dict, not a float tuple)."""
    result = await _run_payload_result(source, env, timeout_s)
    for line in result.stdout.splitlines():
        if line.startswith(marker):
            return json.loads(line[len(marker):])
    raise PayloadError(f"no {marker} in stdout: {result.stdout!r}")


def ensure_native_binary() -> Path | None:
    """Build the C++ executor from the sources in this checkout —
    synchronously, OUTSIDE any event loop (a blocking subprocess inside a
    coroutine would stall the loop and defeat the asyncio.wait_for guard
    around the latency measurement). Always through make, so a binary that
    predates the sources is rebuilt rather than trusted."""
    binary = REPO / "executor" / "build" / "executor-server"
    try:
        build = subprocess.run(
            ["make", "-C", str(REPO / "executor"), "-s"],
            capture_output=True,
            timeout=180,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        print(f"executor build failed ({e})", file=sys.stderr)
        return None
    if build.returncode != 0 or not binary.exists():
        print("no native executor binary", file=sys.stderr)
        return None
    return binary


async def measure_warm_latency_p50_ms(
    binary: Path, n: int = 20
) -> tuple[float, dict] | None:
    """p50 of a trivial execute through the warm native-executor pool, plus a
    per-phase p50 breakdown (analysis / acquire / upload / POST / in-sandbox /
    overhead / download) so a regressed number names its phase instead of
    inviting guesses about host load (VERDICT r2 weak #2). The edge
    static-analysis gate (docs/analysis.md) runs before each execute exactly
    as the API edge does, so the BENCH trajectory records what the gate
    COSTS the warm path, not just what it saves (< 1ms p50 is the budget).
    scripts/measure-latency.py is the full percentile harness."""
    from bee_code_interpreter_tpu.analysis import WorkloadAnalyzer
    from bee_code_interpreter_tpu.config import Config
    from bee_code_interpreter_tpu.services.native_process_code_executor import (
        NativeProcessCodeExecutor,
    )
    from bee_code_interpreter_tpu.services.storage import Storage

    tmp = Path(tempfile.mkdtemp(prefix="bench-lat-"))
    config = Config(
        file_storage_path=str(tmp / "objects"),
        local_workspace_root=str(tmp / "ws"),
        executor_pod_queue_target_length=4,
        disable_dep_install=True,
    )
    executor = NativeProcessCodeExecutor(
        storage=Storage(tmp / "objects"), config=config, binary=binary
    )
    analyzer = WorkloadAnalyzer()  # default (empty) policy: the gate's floor cost
    # The capacity tracker rides the fleet journal exactly as the
    # composition root wires it (docs/autoscaling.md), so this p50 INCLUDES
    # the demand-sampling cost — the <5% acceptance budget is measured on
    # every artifact, not asserted blind.
    from bee_code_interpreter_tpu.observability import DemandTracker

    executor.journal.add_sink(DemandTracker().on_fleet_event)
    try:
        await executor.fill_sandbox_queue()
        samples: list[float] = []
        phase_samples: list[dict] = []
        for i in range(n):
            if i:
                # Pace requests: this measures warm-pool REQUEST latency, not
                # saturated throughput (back-to-back requests outrun the
                # refill pipeline and every pop hits a sandbox whose warm
                # interpreter is still preloading — that's a throughput
                # ceiling, a different metric). The sleep is excluded from
                # the samples.
                await asyncio.sleep(0.35)
            t0 = time.perf_counter()
            # The edge gate runs first, exactly as /v1/execute does; its
            # cost is inside the sample AND reported as its own phase.
            verdict = analyzer.analyze(LATENCY_PAYLOAD)
            analysis_ms = (time.perf_counter() - t0) * 1000.0
            if verdict.syntax_error is not None or verdict.denials:
                raise RuntimeError("latency payload refused by the gate?!")
            result = await executor.execute(LATENCY_PAYLOAD)
            if result.stdout != "42\n":
                raise RuntimeError(f"latency payload failed: {result.stderr}")
            samples.append(time.perf_counter() - t0)
            phase_samples.append(
                {**executor.last_execute_phases, "analysis_ms": analysis_ms}
            )
        phases_p50 = {
            key: round(
                statistics.median(
                    float(p.get(key, 0.0)) for p in phase_samples
                ),
                1 if key != "analysis_ms" else 3,
            )
            for key in (
                "analysis_ms",
                "acquire_ms",
                "upload_ms",
                "post_execute_ms",
                "sandbox_ms",
                "overhead_ms",
                "download_ms",
            )
        }
        phases_p50["warm_pop_rate"] = round(
            sum(1 for p in phase_samples if p.get("warm_pop")) / len(phase_samples),
            2,
        )
        check_analysis_budget(phases_p50)
        return statistics.median(samples) * 1000, phases_p50
    finally:
        executor.shutdown()


async def measure_surge(binary: Path) -> dict | None:
    """The `surge` phase (docs/autoscaling.md): a load step against the
    native warm pool, A/B with the predictive autoscaler in ``act`` vs
    ``off``. Reports time-to-absorb (seconds from the step until a whole
    burst pops warm again, warm_pop_ratio >= 0.95) and how many requests
    the admission gate shed while the pool was cold — the two numbers the
    capacity loop exists to improve. Starts the surge trajectory next to
    warm p50 and tokens/sec in the BENCH artifact."""
    from bee_code_interpreter_tpu.config import Config
    from bee_code_interpreter_tpu.observability import DemandTracker, Forecaster
    from bee_code_interpreter_tpu.resilience import (
        AdmissionController,
        AdmissionRejected,
        PoolAutoscaler,
        PoolSupervisor,
    )
    from bee_code_interpreter_tpu.services.native_process_code_executor import (
        NativeProcessCodeExecutor,
    )
    from bee_code_interpreter_tpu.services.storage import Storage

    # Burst/pace sized for a 1-core bench box: the native refill pipeline
    # produces ~5 sandboxes/s there (serialized CPU-bound spawns), so the
    # sustained demand (~2.7/s) must sit under it or even a perfectly
    # scaled pool can never catch up and the A/B measures host load.
    BURST, MAX_ROUNDS, PACE_S = 4, 8, 1.5

    async def arm(mode: str) -> dict:
        tmp = Path(tempfile.mkdtemp(prefix=f"bench-surge-{mode}-"))
        config = Config(
            file_storage_path=str(tmp / "objects"),
            local_workspace_root=str(tmp / "ws"),
            executor_pod_queue_target_length=2,
            disable_dep_install=True,
        )
        executor = NativeProcessCodeExecutor(
            storage=Storage(tmp / "objects"), config=config, binary=binary
        )
        demand = DemandTracker()
        executor.journal.add_sink(demand.on_fleet_event)
        forecaster = Forecaster(demand)
        admission = AdmissionController(
            max_in_flight=8, max_queue=0, retry_after_s=0.1, demand=demand
        )
        autoscaler = PoolAutoscaler(
            executor, forecaster, demand,
            mode=mode, min_size=1, max_size=8, idle_s=60.0, cooldown_s=0.0,
            base_target=2,
        )
        supervisor = PoolSupervisor(
            executor, interval_s=0.2, autoscaler=autoscaler
        )

        async def one_request() -> bool:
            try:
                async with admission.admit():
                    result = await executor.execute(LATENCY_PAYLOAD)
                    return result.exit_code == 0
            except AdmissionRejected:
                return False

        def assigned_counts() -> tuple[int, int]:
            warm = cold = 0
            for e in executor.journal.events():
                if e["state"] == "assigned":
                    if e.get("reason") == "warm_pop":
                        warm += 1
                    else:
                        cold += 1
            return warm, cold

        try:
            await executor.fill_sandbox_queue()
            supervisor.start()
            for _ in range(3):  # steady trickle: baseline demand + spawns
                await one_request()
                await asyncio.sleep(0.3)
            t_step = time.perf_counter()
            absorb_s: float | None = None
            for _ in range(MAX_ROUNDS):
                warm0, cold0 = assigned_counts()
                await asyncio.gather(*(one_request() for _ in range(BURST)))
                warm1, cold1 = assigned_counts()
                popped = (warm1 - warm0) + (cold1 - cold0)
                ratio = (warm1 - warm0) / popped if popped else 1.0
                if absorb_s is None and ratio >= 0.95:
                    absorb_s = time.perf_counter() - t_step
                    break
                await asyncio.sleep(PACE_S)
            return {
                "absorb_s": round(absorb_s, 2) if absorb_s is not None else None,
                "sheds": demand.sheds_total,
                "pool_target_final": executor.pool_target,
                "decisions": len(autoscaler.decisions()),
            }
        finally:
            await supervisor.stop()
            # Let in-flight refills land before teardown: a spawn racing
            # aclose() would just die noisily against the closed pool.
            for _ in range(100):
                if executor.pool_spawning_count == 0:
                    break
                await asyncio.sleep(0.05)
            await executor.aclose()

    on = await arm("act")
    off = await arm("off")
    return {
        "burst": BURST,
        "pace_s": PACE_S,
        "autoscaler_on": on,
        "autoscaler_off": off,
    }


async def measure_fairness(binary: Path) -> dict | None:
    """The `fairness` phase (docs/tenancy.md): victim-tenant p50 with and
    without an abusive tenant flooding 100x its rate quota through the
    tenant-aware admission gate over the native warm pool. The isolation
    budget is < 10% victim degradation at 100x abuse — published as a
    measured number on every artifact, not asserted blind."""
    from bee_code_interpreter_tpu.config import Config
    from bee_code_interpreter_tpu.resilience import (
        AdmissionController,
        AdmissionRejected,
    )
    from bee_code_interpreter_tpu.services.native_process_code_executor import (
        NativeProcessCodeExecutor,
    )
    from bee_code_interpreter_tpu.services.storage import Storage
    from bee_code_interpreter_tpu.tenancy import TenantRegistry, parse_tenants

    tmp = Path(tempfile.mkdtemp(prefix="bench-fair-"))
    config = Config(
        file_storage_path=str(tmp / "objects"),
        local_workspace_root=str(tmp / "ws"),
        executor_pod_queue_target_length=3,
        disable_dep_install=True,
    )
    executor = NativeProcessCodeExecutor(
        storage=Storage(tmp / "objects"), config=config, binary=binary
    )
    registry = TenantRegistry(
        parse_tenants("abuser:weight=1:rps=2:burst=2,victim:weight=4")
    )
    admission = AdmissionController(
        max_in_flight=4, max_queue=8, retry_after_s=0.1, tenancy=registry
    )
    N_ABUSE = 200  # 100x the abuser's burst-2 token bucket

    async def victim_request() -> float:
        t0 = time.perf_counter()
        async with admission.admit(tenant=registry.resolve("victim")):
            result = await executor.execute(LATENCY_PAYLOAD)
            if result.exit_code != 0:
                raise RuntimeError(f"victim payload failed: {result.stderr}")
        return time.perf_counter() - t0

    async def abuser_request() -> None:
        try:
            async with admission.admit(tenant=registry.resolve("abuser")):
                await executor.execute(LATENCY_PAYLOAD)
        except AdmissionRejected:
            pass  # the quota's verdict — exactly the isolation mechanism

    try:
        await executor.fill_sandbox_queue()
        solo: list[float] = []
        for _ in range(12):
            solo.append(await victim_request())
            await asyncio.sleep(0.25)
        flood = [
            asyncio.ensure_future(abuser_request()) for _ in range(N_ABUSE)
        ]
        under: list[float] = []
        for _ in range(12):
            under.append(await victim_request())
            await asyncio.sleep(0.25)
        await asyncio.gather(*flood)
        p50_solo = statistics.median(solo) * 1000.0
        p50_abuse = statistics.median(under) * 1000.0
        lanes = admission.tenant_snapshot()
        return {
            "victim_p50_solo_ms": round(p50_solo, 1),
            "victim_p50_under_abuse_ms": round(p50_abuse, 1),
            "degradation_pct": round((p50_abuse / p50_solo - 1.0) * 100.0, 1),
            "budget_ok": p50_abuse <= p50_solo * 1.10,  # the < 10% budget
            "abuse_requests": N_ABUSE,
            "abuser_sheds": sum(lanes["abuser"]["sheds"].values()),
            "abuser_admitted": lanes["abuser"]["admitted"],
            "victim_sheds": sum(lanes["victim"]["sheds"].values()),
        }
    finally:
        await executor.aclose()


async def measure_router(binary: Path) -> dict | None:
    """The `router` phase (docs/fleet.md): p50 of the SAME warm execute
    direct-to-replica vs through the fleet-router edge — the routing tax,
    budgeted < 2 ms added p50 — plus the consistent-hash warm-affinity hit
    rate on repeat-client traffic (>= 90% expected: repeat keys must keep
    landing where their snapshot chain is warm). Two complete replicas
    (real HTTP edge over the native pool) share one snapshot root; samples
    alternate arms so host drift cancels."""
    import socket
    import statistics as stats

    import httpx
    from aiohttp import web

    from bee_code_interpreter_tpu.api.http_server import create_http_server
    from bee_code_interpreter_tpu.config import Config
    from bee_code_interpreter_tpu.fleet import FleetRouter, create_router_app
    from bee_code_interpreter_tpu.services.custom_tool_executor import (
        CustomToolExecutor,
    )
    from bee_code_interpreter_tpu.services.native_process_code_executor import (
        NativeProcessCodeExecutor,
    )
    from bee_code_interpreter_tpu.services.storage import (
        SharedDirectoryBackend,
        Storage,
    )

    def free_port() -> int:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    ROUNDS, KEYS = 24, 4
    tmp = Path(tempfile.mkdtemp(prefix="bench-router-"))
    shared_root = tmp / "objects"
    replicas: list[tuple] = []
    router = None
    router_runner = None
    client = None
    try:
        for i in range(2):
            storage = Storage(backend=SharedDirectoryBackend(shared_root))
            config = Config(
                file_storage_path=str(shared_root),
                local_workspace_root=str(tmp / f"ws-{i}"),
                executor_pod_queue_target_length=2,
                disable_dep_install=True,
            )
            executor = NativeProcessCodeExecutor(
                storage=storage, config=config, binary=binary
            )
            await executor.fill_sandbox_queue()
            app = create_http_server(
                code_executor=executor,
                custom_tool_executor=CustomToolExecutor(code_executor=executor),
            )
            runner = web.AppRunner(app)
            await runner.setup()
            port = free_port()
            await web.TCPSite(runner, "127.0.0.1", port).start()
            replicas.append((executor, runner, f"http://127.0.0.1:{port}"))
        # No background refresh: the view is refreshed manually while the
        # fleet is idle, so this LATENCY bench can't trip the overload-spill
        # path by having a refresh catch the (sequentially driven) owner
        # mid-request — spill behavior is chaos/tier-1 territory
        # (tests/test_fleet_router.py), the bench measures tax + affinity.
        router = FleetRouter(
            [(f"r{i}", r[2]) for i, r in enumerate(replicas)],
            refresh_interval_s=30.0,
        )
        router_runner = web.AppRunner(create_router_app(router))
        await router_runner.setup()
        router_port = free_port()
        await web.TCPSite(router_runner, "127.0.0.1", router_port).start()
        router_url = f"http://127.0.0.1:{router_port}"
        await router.refresh_once()

        seed_storage = Storage(backend=SharedDirectoryBackend(shared_root))
        seeds = []
        for i in range(KEYS):
            object_id = await seed_storage.write(f"router-chain-{i}".encode())
            seeds.append({"/workspace/seed.txt": object_id})

        client = httpx.AsyncClient(timeout=30.0)

        async def timed(url: str, files: dict) -> float:
            t0 = time.perf_counter()
            response = await client.post(
                f"{url}/v1/execute",
                json={"source_code": "print('ok')", "files": files},
            )
            if response.status_code != 200 or response.json()["exit_code"] != 0:
                raise RuntimeError(f"router bench execute failed: {response.text}")
            return (time.perf_counter() - t0) * 1000.0

        from bee_code_interpreter_tpu.fleet import affinity_key

        def owner_url(files: dict) -> str:
            # "direct-to-replica" is the ideal client that already knows
            # where its snapshot chain is warm: the key's ring owner — the
            # same replica the router should pick, so both arms measure the
            # same replica in the same state and the difference IS the tax.
            owner = router.ring.owner(affinity_key(files))
            return dict(
                (f"r{i}", r[2]) for i, r in enumerate(replicas)
            )[owner]

        # PACE_S between requests lets the pool refill land, so every
        # sample pops warm: a random cold spawn is tens of ms of noise
        # against a single-digit-ms tax.
        PACE_S = 0.15

        async def timed_paced(url: str, files: dict) -> float:
            sample = await timed(url, files)
            await asyncio.sleep(PACE_S)
            return sample

        # Warm both arms (pool probe + first-touch costs land here).
        for files in seeds:
            await timed_paced(owner_url(files), files)
            await timed_paced(router_url, files)
        await router.refresh_once()  # idle fleet: placement view settles
        direct_ms: list[float] = []
        routed_ms: list[float] = []
        for i in range(ROUNDS):
            files = seeds[i % KEYS]
            # alternate arm ORDER per round so drift cancels
            if i % 2 == 0:
                direct_ms.append(await timed_paced(owner_url(files), files))
                routed_ms.append(await timed_paced(router_url, files))
            else:
                routed_ms.append(await timed_paced(router_url, files))
                direct_ms.append(await timed_paced(owner_url(files), files))
        keyed = (
            router.affinity_totals["warm"] + router.affinity_totals["spill"]
        )
        direct_p50 = stats.median(direct_ms)
        router_p50 = stats.median(routed_ms)
        # The tax is the MEDIAN OF PAIRED same-key differences, not the
        # difference of medians: pairing cancels per-key and drift effects,
        # and the median shrugs off any residual cold-pop outlier.
        tax = stats.median(r - d for d, r in zip(direct_ms, routed_ms))
        # Per-stage p50 breakdown of where the tax goes, from the router's
        # own stage spans (docs/observability.md "Fleet observability"):
        # placement decision, breaker gate, retry attempt, proxied call.
        # The proxy stage CONTAINS the replica's work — only placement +
        # breaker (plus attempt minus proxy) are router-added time, so the
        # breakdown attributes the <2ms budget rather than re-measuring it.
        by_stage: dict[str, list[float]] = {}
        for trace in router.trace_store.traces():
            for stage, ms in trace.stage_ms().items():
                by_stage.setdefault(stage, []).append(ms)
        stage_p50 = {
            stage: round(stats.median(samples), 3)
            for stage, samples in sorted(by_stage.items())
        }
        return {
            "requests_per_arm": ROUNDS,
            "direct_p50_ms": round(direct_p50, 2),
            "router_p50_ms": round(router_p50, 2),
            "router_tax_ms": round(tax, 2),
            "router_stage_p50_ms": stage_p50,
            "warm_pop_rate": round(
                router.affinity_totals["warm"] / keyed if keyed else 0.0, 3
            ),
        }
    finally:
        if client is not None:
            await client.aclose()
        if router_runner is not None:
            await router_runner.cleanup()
        if router is not None:
            await router.stop()
        for executor, runner, _url in replicas:
            await runner.cleanup()
            await executor.aclose()


async def measure_session_latency_p50_ms(
    binary: Path, n: int = 12
) -> float | None:
    """Sessionful warm path (docs/sessions.md): p50 of execute №2..N inside
    ONE lease over the native pool — no workspace restore, snapshot
    deferred — the number to hold against ``latency_warm_p50_ms`` (each of
    whose executes pays a fresh checkout + full snapshot round-trip)."""
    from bee_code_interpreter_tpu.config import Config
    from bee_code_interpreter_tpu.services.native_process_code_executor import (
        NativeProcessCodeExecutor,
    )
    from bee_code_interpreter_tpu.services.storage import Storage
    from bee_code_interpreter_tpu.sessions import SessionManager

    tmp = Path(tempfile.mkdtemp(prefix="bench-sess-"))
    config = Config(
        file_storage_path=str(tmp / "objects"),
        local_workspace_root=str(tmp / "ws"),
        executor_pod_queue_target_length=2,
        disable_dep_install=True,
    )
    storage = Storage(tmp / "objects")
    executor = NativeProcessCodeExecutor(
        storage=storage, config=config, binary=binary
    )
    manager = SessionManager(
        executor, storage, max_sessions=1, ttl_s=300, idle_s=300
    )
    try:
        await executor.fill_sandbox_queue()
        session = await manager.create()
        samples: list[float] = []
        for i in range(n):
            if i:
                # REPL pacing: the server re-warms its interpreter after
                # each claim; a real session's think-time overlaps that
                # preload, so back-to-back hammering would measure a
                # throughput ceiling, not the REPL turn latency (same
                # rationale as the stateless measurement's pacing).
                await asyncio.sleep(0.2)
            t0 = time.perf_counter()
            _, outcome = await manager.execute(
                session.session_id, LATENCY_PAYLOAD
            )
            if outcome.stdout != "42\n":
                raise RuntimeError(f"session payload failed: {outcome.stderr}")
            if i:  # execute №1 pays the cold in-lease warmup; 2..N is the REPL rate
                samples.append(time.perf_counter() - t0)
        await manager.release(session.session_id)
        return statistics.median(samples) * 1000
    finally:
        await manager.close_all()
        executor.shutdown()


TTFB_PAYLOAD = (
    "import time\nprint('first', flush=True)\ntime.sleep(0.5)\nprint('last')"
)


async def measure_streaming_ttfb_ms() -> float | None:
    """Time-to-first-stdout-byte through the streaming path (in-process
    executor: the chunked read loop itself, no pool noise): the payload
    flushes immediately then sleeps, so TTFB << total proves chunks flow
    while the run is still going."""
    from bee_code_interpreter_tpu.services.local_code_executor import (
        LocalCodeExecutor,
    )
    from bee_code_interpreter_tpu.services.storage import Storage

    tmp = Path(tempfile.mkdtemp(prefix="bench-ttfb-"))
    executor = LocalCodeExecutor(
        storage=Storage(tmp / "objects"),
        workspace_root=tmp / "ws",
        disable_dep_install=True,
        execution_timeout_s=30.0,
    )
    first_chunk_at: list[float] = []
    t0 = time.perf_counter()

    async def on_event(kind: str, _text: str) -> None:
        if kind == "stdout" and not first_chunk_at:
            first_chunk_at.append(time.perf_counter())

    result = await executor.execute_stream(TTFB_PAYLOAD, on_event=on_event)
    total = time.perf_counter() - t0
    if result.exit_code != 0 or not first_chunk_at:
        raise RuntimeError(f"ttfb payload failed: {result.stderr}")
    ttfb = (first_chunk_at[0] - t0) * 1000
    if ttfb >= total * 1000 * 0.9:
        # The first byte arrived with the end of the run: that is buffered
        # delivery wearing a streaming hat, not a TTFB.
        raise RuntimeError(f"no early chunk: ttfb {ttfb:.0f}ms of {total * 1000:.0f}ms total")
    return ttfb


CAPACITY_ARTIFACT = REPO / "CAPACITY_r01.json"
# The at-SLO p99 threshold for the knee search: generous against the warm
# execute p50 (tens of ms) so the knee marks queueing collapse, not jitter.
CAPACITY_SLO_P99_MS = 1500.0
CAPACITY_PROBE_S = 4.0


def _capacity_free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


async def _capacity_replica(binary: Path, tmp: Path, shared_root: Path, index: int) -> dict:
    """One COMPLETE capacity-instrumented replica over the native pool:
    real HTTP edge + admission + SLO engine + DemandTracker/Forecaster
    wired into GET /v1/autoscale — the production edge shape the loadgen
    measures, sharing a snapshot root with its siblings."""
    from aiohttp import web

    from bee_code_interpreter_tpu.api.http_server import create_http_server
    from bee_code_interpreter_tpu.config import Config
    from bee_code_interpreter_tpu.observability import (
        DemandTracker,
        Forecaster,
        SloEngine,
        parse_objectives,
    )
    from bee_code_interpreter_tpu.resilience import AdmissionController
    from bee_code_interpreter_tpu.resilience.autoscaler import autoscale_snapshot
    from bee_code_interpreter_tpu.services.custom_tool_executor import (
        CustomToolExecutor,
    )
    from bee_code_interpreter_tpu.services.native_process_code_executor import (
        NativeProcessCodeExecutor,
    )
    from bee_code_interpreter_tpu.services.storage import (
        SharedDirectoryBackend,
        Storage,
    )
    from bee_code_interpreter_tpu.sessions import SessionManager
    from bee_code_interpreter_tpu.utils.metrics import Registry

    metrics = Registry()
    demand = DemandTracker(window_s=30.0, metrics=metrics)
    forecaster = Forecaster(
        demand, peak_window_s=10.0, max_horizon_s=5.0, metrics=metrics
    )
    storage = Storage(backend=SharedDirectoryBackend(shared_root))
    config = Config(
        file_storage_path=str(shared_root),
        local_workspace_root=str(tmp / f"ws-{index}"),
        executor_pod_queue_target_length=2,
        disable_dep_install=True,
    )
    executor = NativeProcessCodeExecutor(
        storage=storage, config=config, binary=binary, metrics=metrics
    )
    executor.journal.add_sink(demand.on_fleet_event)
    await executor.fill_sandbox_queue()
    slo = SloEngine(parse_objectives(99.5, None), metrics=metrics)
    admission = AdmissionController(
        max_in_flight=8,
        max_queue=16,
        retry_after_s=0.2,
        metrics=metrics,
        demand=demand,
    )
    sessions = SessionManager(
        executor, storage, max_sessions=4, ttl_s=300, idle_s=300,
        metrics=metrics,
    )
    app = create_http_server(
        code_executor=executor,
        custom_tool_executor=CustomToolExecutor(code_executor=executor),
        metrics=metrics,
        admission=admission,
        slo=slo,
        sessions=sessions,
        fleet=executor.journal,
        autoscale=lambda: autoscale_snapshot(
            demand=demand, forecaster=forecaster, slo=slo
        ),
    )
    runner = web.AppRunner(app)
    await runner.setup()
    port = _capacity_free_port()
    await web.TCPSite(runner, "127.0.0.1", port).start()
    return {
        "name": f"r{index}",
        "url": f"http://127.0.0.1:{port}",
        "executor": executor,
        "runner": runner,
        "sessions": sessions,
    }


def _capacity_point(point: dict) -> dict:
    """One p99-vs-load curve point for the artifact: the verdict plus the
    quantiles that explain it, without the full per-sample dump."""
    result = point.get("result") or {}
    latency = result.get("latency_ms") or {}
    rec = point.get("recommendation") or {}

    def r1(value):
        return None if value is None else round(value, 1)

    warm = point.get("warm_pop_ratio")
    return {
        "offered_rps": round(point["offered_rps"], 2),
        "achieved_rps": r1(result.get("achieved_rps")),
        "sustained": point["sustained"],
        "reasons": point["reasons"],
        "p50_ms": r1(latency.get("p50")),
        "p95_ms": r1(latency.get("p95")),
        "p99_ms": r1(latency.get("p99")),
        "sheds": result.get("sheds"),
        "errors": result.get("errors"),
        "warm_pop_ratio": None if warm is None else round(warm, 3),
        "recommended_replicas": rec.get("target_replicas"),
    }


async def _capacity_probe_config(
    client, base_url: str, *, replicas: int, router=None, hi_rps: float
) -> dict:
    """Knee-search one configuration, then hold a 10x flash crowd against
    it and record what the observability plane said while it burned."""
    from bee_code_interpreter_tpu.loadgen import (
        CapacityReporter,
        FlashCrowd,
        OpenLoopGenerator,
        TrafficMix,
        find_knee,
    )

    session_ids: list[str] = []
    response = await client.post(f"{base_url}/v1/sessions", json={})
    if response.status_code == 200:
        session_ids.append(response.json()["session_id"])
    kinds = (
        (("execute", 8.0), ("session", 1.0), ("stream", 1.0))
        if session_ids
        else (("execute", 9.0), ("stream", 1.0))
    )
    generator = OpenLoopGenerator(
        client, base_url, mix=TrafficMix(kinds=kinds), session_ids=session_ids
    )
    reporter = CapacityReporter(client, base_url, router=router)
    knee, probes = await find_knee(
        generator,
        lo_rps=1.0,
        hi_rps=hi_rps,
        duration_s=CAPACITY_PROBE_S,
        p99_ms=CAPACITY_SLO_P99_MS,
        reporter=reporter,
        iterations=5,
        settle_s=1.0,
        drain_timeout_s=20.0,
        on_probe=lambda p: print(
            f"capacity probe {p['offered_rps']:.2f} rps: "
            f"{'sustained' if p['sustained'] else p['reasons']}",
            file=sys.stderr,
        ),
    )
    base = max(1.0, knee / 2.0)
    crowd = await generator.run(
        FlashCrowd(
            base_rps=base,
            duration_s=8.0,
            crowd_start_s=2.0,
            crowd_s=2.0,
            multiplier=10.0,
        ),
        label="flash-crowd",
        drain_timeout_s=30.0,
    )
    scrape = await reporter.scrape()
    config = {
        "replicas": replicas,
        "router": router is not None,
        "max_sustained_rps": round(knee, 2),
        "curve": [_capacity_point(p) for p in probes],
        "flash_crowd": {
            **crowd.to_dict(),
            "shed_ledger": crowd.shed_ledger(),
            "warm_pop_ratio": scrape.get("warm_pop_ratio"),
            "recommendation": scrape.get("recommendation"),
            "fast_burn": scrape.get("fast_burn"),
        },
    }
    stage_p50 = reporter.stage_p50_ms()
    if stage_p50:
        config["router_stage_p50_ms"] = stage_p50
    return config


async def measure_capacity(binary: Path) -> dict:
    """The `capacity` phase (docs/capacity.md): max-sustained-rps-at-SLO
    for (a) one replica hit directly and (b) three replicas behind the
    real FleetRouter — measured by the open-loop generator, judged by the
    federated SLO/autoscale plane, published as CAPACITY_r01.json."""
    import httpx
    from aiohttp import web

    from bee_code_interpreter_tpu.fleet import FleetRouter, create_router_app

    configs: dict[str, dict] = {}
    client = httpx.AsyncClient(timeout=30.0)
    try:
        # --- config A: one replica, clients hit its edge directly
        tmp = Path(tempfile.mkdtemp(prefix="bench-capacity-solo-"))
        replica = await _capacity_replica(binary, tmp, tmp / "objects", 0)
        try:
            configs["replica-1"] = await _capacity_probe_config(
                client, replica["url"], replicas=1, hi_rps=10.0
            )
        finally:
            await replica["sessions"].close_all()
            await replica["runner"].cleanup()
            await replica["executor"].aclose()

        # --- config B: three replicas behind the fleet router (live
        # background refresh: the production edge shape, router tax and
        # retry policy included in every sample)
        tmp = Path(tempfile.mkdtemp(prefix="bench-capacity-fleet-"))
        replicas = [
            await _capacity_replica(binary, tmp, tmp / "objects", i)
            for i in range(3)
        ]
        router = FleetRouter(
            [(r["name"], r["url"]) for r in replicas],
            refresh_interval_s=1.0,
            dead_after_s=5.0,
        )
        router_runner = web.AppRunner(create_router_app(router))
        await router_runner.setup()
        router_port = _capacity_free_port()
        await web.TCPSite(router_runner, "127.0.0.1", router_port).start()
        await router.refresh_once()
        router.start()
        try:
            configs["router-3"] = await _capacity_probe_config(
                client,
                f"http://127.0.0.1:{router_port}",
                replicas=3,
                router=router,
                hi_rps=16.0,
            )
        finally:
            await router.stop()
            await router_runner.cleanup()
            for r in replicas:
                await r["sessions"].close_all()
                await r["runner"].cleanup()
                await r["executor"].aclose()
    finally:
        await client.aclose()
    return configs


def capacity_main() -> None:
    """`python bench.py capacity`: measure the SLO-vs-load curves and
    write the CAPACITY_r01.json artifact (plus one summary line on
    stdout, same one-line contract as the main bench)."""
    binary = ensure_native_binary()
    if binary is None:
        print(
            json.dumps({"error": "no native executor binary; capacity "
                        "phase needs `make -C executor`"}),
            flush=True,
        )
        sys.exit(1)
    t0 = time.time()
    configs = asyncio.run(
        asyncio.wait_for(measure_capacity(binary), timeout=540.0)
    )
    artifact = {
        "version": "r01",
        "generated_at": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        ),
        "host": {"platform": sys.platform, "cpus": os.cpu_count()},
        "slo": {
            "availability_pct": 99.5,
            "p99_ms": CAPACITY_SLO_P99_MS,
            "error_budget": 0.005,
            "shed_budget": 0.01,
        },
        "probe": {
            "duration_s": CAPACITY_PROBE_S,
            "mix": "execute 8 : session 1 : stream 1, heavy-tail cost classes",
            "method": "bisection on the sustained predicate (docs/capacity.md)",
        },
        "configs": configs,
        "wall_s": round(time.time() - t0, 1),
    }
    CAPACITY_ARTIFACT.write_text(json.dumps(artifact, indent=2) + "\n")
    print(
        json.dumps(
            {
                "metric": "max sustained rps at SLO (p99<=1500ms, err<=0.5%, shed<=1%)",
                "configs": {
                    name: c["max_sustained_rps"]
                    for name, c in configs.items()
                },
                "artifact": CAPACITY_ARTIFACT.name,
            }
        ),
        flush=True,
    )


def main() -> None:
    result: dict = {}
    failed: dict[str, str] = {}

    def phase(name: str, run):
        """Run one phase once. A failure is listed, never substituted."""
        try:
            value = run()
        except Exception as e:
            failed[name] = f"{type(e).__name__}: {e}"[:400]
            stderr_tail = getattr(e, "stderr", "")
            if stderr_tail:
                failed[name] += " | " + stderr_tail.strip()[-400:]
            print(f"{name}: FAILED {failed[name]}", file=sys.stderr)
            return None
        print(f"{name}: {value}", file=sys.stderr)
        return value

    # --- 1. device phases (run once; the payloads fail without a TPU). The
    # ambient accelerator env flows through the executor's passthrough so
    # the payload sees the real chip; JAX_PLATFORMS=tpu makes a missing or
    # busy chip an error instead of a quiet CPU run.
    device_env = {"JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "tpu")}
    matmul = phase("matmul", lambda: asyncio.run(run_payload_json(
        matmul_chain_payload(), device_env, timeout_s=600.0,
        marker="RESULT_MATMUL",
    )))
    flash = phase("flash_attention", lambda: asyncio.run(run_payload_json(
        # PYTHONPATH carries the repo into the sandbox: the payload imports
        # the bundled library (the executor image installs it)
        FLASH_PAYLOAD, {**device_env, "PYTHONPATH": str(REPO)},
        timeout_s=600.0, marker="RESULT_FLASH",
    )))

    # --- 2. host phases: the service's own overheads. None of these is a
    # device number; each is labelled with the platform it ran on.
    cpu_gflops = phase("cpu_matmul", lambda: asyncio.run(run_payload(
        CPU_PAYLOAD, {"JAX_PLATFORMS": "cpu", "BCI_XLA_REROUTE": "0"},
        timeout_s=90.0,
    )))
    def build_executor() -> Path:
        built = ensure_native_binary()
        if built is None:
            raise RuntimeError("make -C executor produced no binary")
        return built

    binary = phase("executor_build", build_executor)
    latency = session_p50_ms = surge = router_phase = fairness = None
    if binary is not None:
        # warm-pool execute latency + per-phase breakdown
        latency = phase("latency", lambda: asyncio.run(asyncio.wait_for(
            measure_warm_latency_p50_ms(binary), timeout=90.0)))
        # sessionful warm path (docs/sessions.md — the lease amortizes the
        # snapshot tax the stateless number pays per execute)
        session_p50_ms = phase("session_latency", lambda: asyncio.run(
            asyncio.wait_for(
                measure_session_latency_p50_ms(binary), timeout=90.0)))
        # load step absorbed by the predictive autoscaler vs the static pool
        # (docs/autoscaling.md)
        surge = phase("surge", lambda: asyncio.run(asyncio.wait_for(
            measure_surge(binary), timeout=150.0)))
        # p50 through the fleet router vs direct-to-replica (docs/fleet.md)
        router_phase = phase("router", lambda: asyncio.run(asyncio.wait_for(
            measure_router(binary), timeout=150.0)))
        # victim-tenant p50 with vs without an abusive flood
        # (docs/tenancy.md)
        fairness = phase("fairness", lambda: asyncio.run(asyncio.wait_for(
            measure_fairness(binary), timeout=150.0)))
    streaming_ttfb_ms = phase("streaming_ttfb", lambda: asyncio.run(
        asyncio.wait_for(measure_streaming_ttfb_ms(), timeout=60.0)))
    # serving stack through the service path: tokens/sec + TTFT p50/p95 +
    # inter-token latency with a measured instrumentation on/off A/B
    # (models/serving_bench.py). CPU-pinned tiny model by design — the
    # stack's host code, not the chip.
    serving = phase("serving", lambda: asyncio.run(run_payload_json(
        SERVING_PAYLOAD,
        {"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)},
        timeout_s=420.0,
        marker="RESULT_SERVING_JSON",
    )))

    result["metric"] = (
        "dense matmul GFLOPS/chip via /v1/execute (bf16 32768^3 jit chain)"
    )
    result["value"] = matmul["gflops"] if matmul else None
    result["unit"] = "GFLOPS"
    result["device"] = (
        {
            "platform": matmul["platform"],
            "kind": matmul["device_kind"],
            "count": matmul["device_count"],
        }
        if matmul
        else None
    )
    if matmul:
        result["matmul"] = matmul
    if flash:
        # The comparator is reference_attention compiled by XLA (a naive
        # einsum+softmax), NOT a tuned fused-attention lowering — the field
        # name says exactly that.
        flash["speedup_vs_xla_ref"] = round(
            flash["tflops"] / flash["xla_ref_tflops"], 2
        )
        result["flash_attention"] = flash
    result["host"] = {"platform": "host", "cpus": os.cpu_count()}
    result["cpu_matmul_gflops"] = (
        round(cpu_gflops, 1) if cpu_gflops is not None else None
    )
    latency_p50_ms, latency_phases = latency if latency else (None, None)
    result["latency_warm_p50_ms"] = (
        round(latency_p50_ms, 1) if latency_p50_ms is not None else None
    )
    if latency_phases is not None:
        result["latency_phases_p50"] = latency_phases
    # Sessionful warm path (execute №2..N inside one lease, restore skipped
    # and snapshot deferred) next to the stateless number it undercuts, and
    # time-to-first-stdout-byte through the streaming path.
    result["latency_session_p50_ms"] = (
        round(session_p50_ms, 1) if session_p50_ms is not None else None
    )
    result["streaming_ttfb_ms"] = (
        round(streaming_ttfb_ms, 1) if streaming_ttfb_ms is not None else None
    )
    if surge is not None:
        result["surge"] = surge
    if router_phase is not None:
        result["router"] = router_phase
    if fairness is not None:
        result["fairness"] = fairness
    if serving is not None:
        result["serving"] = {**serving, "platform": "cpu"}
    result["failed_phases"] = failed
    print(json.dumps(result), flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "capacity":
        capacity_main()
    else:
        main()
