"""Bring-up invariants (ISSUE 21), all CPU-only: a control plane with no
engine attached never imports jax; the compile cache lives in ONE resolved
place; warm-ups say what they could not reach."""

import asyncio
import subprocess
import sys
from pathlib import Path

from bee_code_interpreter_tpu.runtime.executor_core import ExecutorCore
from bee_code_interpreter_tpu.utils import jaxcache

REPO = Path(__file__).resolve().parent.parent


def run_python(source: str, **env) -> subprocess.CompletedProcess:
    import os

    return subprocess.run(
        [sys.executable, "-c", source], cwd=REPO, capture_output=True,
        text=True, timeout=120, env={**os.environ, **env},
    )


def test_booting_the_context_leaves_jax_unimported(tmp_path):
    """A chip belongs to one process, and on the execute path that is the
    sandbox child: constructing the composition root, starting its
    observability (device sampler included), scraping /v1/accelerator's
    snapshot and asking for a device profile must not import jax."""
    probe = f"""
import asyncio, sys
from bee_code_interpreter_tpu.application_context import ApplicationContext
from bee_code_interpreter_tpu.config import Config
from bee_code_interpreter_tpu.observability import ProfilerUnavailable

async def main():
    ctx = ApplicationContext(Config(
        executor_backend="local", file_storage_path={str(tmp_path / "f")!r},
        local_workspace_root={str(tmp_path / "ws")!r},
        device_sample_interval_s=0.01,
    ))
    ctx.start_observability()
    ctx.http_server, ctx.grpc_server  # both edges wired
    await asyncio.sleep(0.1)          # several sampler ticks
    snap = ctx.device.snapshot()
    assert snap["attached"] is False and snap["memory"]["devices"] == [], snap
    assert ctx.device.sample_memory() == []
    assert not ctx.device_profiler.available
    try:
        ctx.device_profiler.capture(1)
    except (ProfilerUnavailable, RuntimeError) as e:
        print("profile refused:", e)
    await ctx.aclose()

asyncio.run(main())
print("jax imported:", any(m == "jax" or m.startswith("jax.") for m in sys.modules))
"""
    out = run_python(probe)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "jax imported: False" in out.stdout, out.stdout
    assert "profile refused:" in out.stdout


def test_attaching_an_engine_is_what_brings_jax_in(tmp_path):
    """...and with an engine attached the same snapshot reports its
    devices: the decision is what the code can see, not a switch."""
    probe = f"""
import sys
from bee_code_interpreter_tpu.application_context import ApplicationContext
from bee_code_interpreter_tpu.config import Config
ctx = ApplicationContext(Config(
    executor_backend="local", file_storage_path={str(tmp_path / "f")!r},
    local_workspace_root={str(tmp_path / "ws")!r},
))
assert "jax" not in sys.modules
import dataclasses, jax, jax.numpy as jnp
from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.engine import Engine
from bee_code_interpreter_tpu.models.serving import ContinuousBatcher
cfg = dataclasses.replace(T.TransformerConfig.tiny(), dtype=jnp.float32)
engine = Engine(ContinuousBatcher(
    T.init_params(cfg, jax.random.PRNGKey(0)), cfg, max_batch=2, n_pages=8,
    page_size=4, max_pages_per_seq=2,
))
ctx.attach_serving_engine(engine)
snap = ctx.device.snapshot()
assert snap["attached"] and snap["memory"]["reason"] is None
print("devices:", [row["device"] for row in snap["memory"]["devices"]])
print("profiler available:", ctx.device_profiler.available)
"""
    out = run_python(probe, JAX_PLATFORMS="cpu")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "devices: ['cpu:0']" in out.stdout
    assert "profiler available: True" in out.stdout


def test_cache_dir_environment_wins():
    assert jaxcache.jax_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/x/y"}) == "/x/y"


def test_cache_dir_default_is_one_fixed_path_in_the_checkout():
    """Never a temp name, a pid or a timestamp: the path is part of the
    cache key, so it must be identical from every process, every run."""
    default = jaxcache.jax_cache_dir({})
    assert default == jaxcache.CHECKOUT_CACHE_DIR == str(REPO / ".jax_cache")
    assert jaxcache.jax_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""}) == default
    other = run_python(
        "from bee_code_interpreter_tpu.utils.jaxcache import jax_cache_dir;"
        "import os; os.environ.pop('JAX_COMPILATION_CACHE_DIR', None);"
        "print(jax_cache_dir())"
    )
    assert other.stdout.strip() == default
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_cache_resolver_is_stdlib_only():
    out = run_python(
        "import sys; import bee_code_interpreter_tpu.utils.jaxcache;"
        "print(sorted(m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'numpy')))"
    )
    assert out.stdout.strip() == "[]", out.stdout + out.stderr


async def _child_cache_dir(core: ExecutorCore) -> str:
    out = await core.execute(
        "import os; print(os.environ.get('JAX_COMPILATION_CACHE_DIR'))"
    )
    return out.stdout.strip()


async def test_sandbox_cache_dir_precedence(tmp_path, monkeypatch):
    """Environment > APP_JAX_CACHE_DIR (the operator's override) > the
    backend's default; with none of the three, no cache is set in code."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("APP_JAX_CACHE_DIR", raising=False)
    bare = ExecutorCore(workspace=tmp_path / "a", disable_dep_install=True)
    assert await _child_cache_dir(bare) == "None"
    core = ExecutorCore(
        workspace=tmp_path / "b", disable_dep_install=True,
        jax_cache_dir="/backend/default",
    )
    assert await _child_cache_dir(core) == "/backend/default"
    monkeypatch.setenv("APP_JAX_CACHE_DIR", "/operator/override")
    assert await _child_cache_dir(core) == "/operator/override"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/outside")
    assert await _child_cache_dir(core) == "/from/outside"


async def test_local_backend_sandboxes_use_the_checkout_cache(
    local_executor, monkeypatch
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("APP_JAX_CACHE_DIR", raising=False)
    result = await local_executor.execute(
        "import os; print(os.environ['JAX_COMPILATION_CACHE_DIR'])"
    )
    assert result.stdout.strip() == jaxcache.CHECKOUT_CACHE_DIR


async def test_warmup_reports_its_failure_instead_of_swallowing_it(
    tmp_path, monkeypatch
):
    """The pod-side warm-up runs `import jax; zeros(8)` in a throwaway
    interpreter; a backend that cannot come up (here: an unknown platform,
    on a chip host: the chip held by another process) is returned, logged
    and served as /healthz warm_error — never `except: pass`."""
    from aiohttp.test_utils import TestClient, TestServer

    from bee_code_interpreter_tpu.runtime.executor_server import create_app

    core = ExecutorCore(workspace=tmp_path / "ws", disable_dep_install=True)
    monkeypatch.setenv("JAX_PLATFORMS", "bci_no_such_platform")
    reason = await core.warmup()
    assert reason.startswith("accelerator warm-up exited 1:")
    assert "bci_no_such_platform" in reason
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert await core.warmup() is None

    for warm_error in (reason, None):
        client = TestClient(TestServer(create_app(core, warm_error=warm_error)))
        await client.start_server()
        try:
            body = await (await client.get("/healthz")).json()
        finally:
            await client.close()
        assert body["status"] == "ok"
        assert body.get("warm_error") == warm_error


def test_probe_and_patience_machinery_is_gone():
    """The access path these existed for is gone; so are they."""
    for gone in (
        "scripts/tpu-oneshot.py", "scripts/capture-on-healthy.py",
        "bee_code_interpreter_tpu/utils/evidence.py",
        "bee_code_interpreter_tpu/utils/envscrub.py",
        "BASELINE.md", "VERDICT.md", "capture-r5.log", "BENCH_r05.json",
    ):
        assert not (REPO / gone).exists(), gone
    bench = (REPO / "bench.py").read_text()
    for name in (
        "probe_tpu", "patient_tpu_capture", "_attempt_tpu_payload",
        "_install_kill_safe_emit", "diagnose_tpu_failure", "compact_probes", "hardware_evidence",
        "record_evidence", "RECORDED_CPU_GFLOPS", "CPU fallback",
    ):
        assert name not in bench, name
    for path in ("bee_code_interpreter_tpu/runtime/executor_core.py",
                 "executor/src/server.cpp"):
        source = (REPO / path).read_text()
        assert "BCI_SCRUB_ACCELERATOR" not in source
        assert '"PALLAS_"' not in source  # plug-in prefixes left the lists


def test_no_legacy_jax_branches_left():
    for path in (REPO / "bee_code_interpreter_tpu").rglob("*.py"):
        source = path.read_text()
        assert 'hasattr(jax, "shard_map")' not in source, path
        assert "shard_map_compat" not in source, path
        assert "check_rep" not in source, path
