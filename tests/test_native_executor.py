"""Drives the native C++ executor server end-to-end over its wire contract,
including full control-plane interop (KubernetesCodeExecutor with fake kubectl
pointing pods at real native-server processes)."""

import asyncio
import json
import socket
import subprocess
import sys
import time
from pathlib import Path

import httpx
import pytest

from bee_code_interpreter_tpu.services.native_process_code_executor import (
    _free_port as free_port,
)

REPO = Path(__file__).resolve().parent.parent
EXECUTOR_DIR = REPO / "executor"
BINARY = EXECUTOR_DIR / "build" / "executor-server"


@pytest.fixture(autouse=True)
def _require_native(native_binary):
    # native_binary (shared session fixture) builds the server exactly once.
    if native_binary is None:
        pytest.skip("native toolchain unavailable")


class NativeExecutor:
    def __init__(
        self,
        workspace: Path,
        ip: str = "127.0.0.1",
        port: int | None = None,
        extra_env: dict[str, str] | None = None,
    ):
        self.ip = ip
        self.port = port or free_port()
        self.workspace = workspace
        self.proc = subprocess.Popen(
            [str(BINARY)],
            env={
                "PATH": "/usr/local/bin:/usr/bin:/bin",
                "APP_LISTEN_ADDR": f"{ip}:{self.port}",
                "APP_WORKSPACE": str(workspace),
                "APP_DISABLE_DEP_INSTALL": "1",
                "APP_PYPI_MAP": str(EXECUTOR_DIR / "pypi_map.tsv"),
                **(extra_env or {}),
            },
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        self.base = f"http://{ip}:{self.port}"
        deadline = time.time() + 10
        while time.time() < deadline:
            try:
                if httpx.get(self.base + "/healthz", timeout=1).status_code == 200:
                    return
            except httpx.HTTPError:
                time.sleep(0.05)
        raise RuntimeError("native executor did not become healthy")

    def stop(self):
        self.proc.kill()
        self.proc.wait()


@pytest.fixture
def native(tmp_path):
    server = NativeExecutor(tmp_path / "ws")
    yield server
    server.stop()


def test_healthz(native):
    body = httpx.get(native.base + "/healthz").json()
    assert body["status"] == "ok"
    # "warm" reports whether the pre-started worker finished preloading;
    # it flips true (and stays true) within the preload budget
    assert isinstance(body["warm"], bool)
    deadline = time.time() + 30
    while not httpx.get(native.base + "/healthz").json()["warm"]:
        assert time.time() < deadline, "worker never reported warm"
        time.sleep(0.1)


def strip_diagnostics(response: dict) -> dict:
    """Drop additive diagnostic fields, asserting their shape; what remains is
    the reference wire contract and is compared exactly."""
    duration = response.pop("duration_ms")
    assert isinstance(duration, (int, float)) and duration >= 0
    return response


def test_execute_basic(native):
    r = httpx.post(
        native.base + "/execute", json={"source_code": "print(21 * 2)"}
    ).json()
    assert strip_diagnostics(r) == {
        "stdout": "42\n", "stderr": "", "exit_code": 0, "files": [],
    }


def test_upload_execute_download_roundtrip(native):
    data = bytes(range(256)) * 100
    assert (
        httpx.put(native.base + "/workspace/sub/in.bin", content=data).status_code
        == 204
    )
    r = httpx.post(
        native.base + "/execute",
        json={
            "source_code": "raw = open('sub/in.bin','rb').read()\n"
            "open('out.bin','wb').write(raw[::-1])"
        },
    ).json()
    assert r["exit_code"] == 0
    assert r["files"] == ["/workspace/out.bin"]
    out = httpx.get(native.base + "/workspace/out.bin")
    assert out.content == data[::-1]


def test_env_and_unicode(native):
    r = httpx.post(
        native.base + "/execute",
        json={
            "source_code": "import os\nprint(os.environ['GREETING'])",
            "env": {"GREETING": "héllo ✓ wörld"},
        },
    ).json()
    assert r["stdout"] == "héllo ✓ wörld\n"


def test_timeout_kills_group(native):
    r = httpx.post(
        native.base + "/execute",
        json={
            "source_code": "import subprocess, sys, time\n"
            "subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(60)'])\n"
            "time.sleep(60)",
            "timeout": 1,
        },
        timeout=30,
    ).json()
    assert r["exit_code"] == -1
    assert r["stderr"] == "Execution timed out"


def test_path_escape_rejected(native):
    # raw socket: clients like httpx normalize "..", the server must not rely on that
    with socket.create_connection((native.ip, native.port)) as sock:
        sock.sendall(
            b"PUT /workspace/../../etc/evil HTTP/1.1\r\n"
            b"Host: x\r\nContent-Length: 1\r\nConnection: close\r\n\r\nx"
        )
        status = b""
        while chunk := sock.recv(4096):
            status += chunk
    assert b"400" in status.split(b"\r\n", 1)[0]
    # encoded traversal through a real client
    assert (
        httpx.put(
            native.base + "/workspace/%2e%2e/%2e%2e/etc/evil2", content=b"x"
        ).status_code
        == 400
    )


def test_download_missing_404(native):
    assert httpx.get(native.base + "/workspace/nope.txt").status_code == 404


def test_crash_propagates_exit_code(native):
    r = httpx.post(
        native.base + "/execute", json={"source_code": "raise SystemExit(9)"}
    ).json()
    assert r["exit_code"] == 9


async def test_chunked_streaming_upload(native):
    # the control plane streams uploads with an async generator => chunked
    # transfer-encoding; the native server must decode it
    async def body():
        for i in range(64):
            yield bytes([i]) * 1024

    async with httpx.AsyncClient() as client:
        resp = await client.put(native.base + "/workspace/chunked.bin", content=body())
        assert resp.status_code == 204
    r = httpx.post(
        native.base + "/execute",
        json={"source_code": "import os\nprint(os.path.getsize('chunked.bin'))"},
    ).json()
    assert r["stdout"] == f"{64 * 1024}\n"


async def test_control_plane_against_native_pods(tmp_path, storage):
    """KubernetesCodeExecutor drives real native-server 'pods' (distinct
    loopback IPs, one shared port) through the full upload/execute/download
    flow — the reference's boundary (c) (SURVEY.md §3.5) with our C++ server."""
    from bee_code_interpreter_tpu.config import Config
    from bee_code_interpreter_tpu.services.kubernetes_code_executor import (
        KubernetesCodeExecutor,
    )
    from tests.fakes import FakeKubectl

    port = free_port()
    servers: list[NativeExecutor] = []

    class NativeBackend:
        port_ = port

        def __init__(self):
            self.port = port
            self._next = 1

        async def start_pod(self, manifest=None) -> str:
            ip = f"127.1.1.{self._next}"
            self._next += 1
            server = await asyncio.to_thread(
                NativeExecutor, tmp_path / f"pod-{self._next}", ip, port
            )
            servers.append(server)
            return ip

    config = Config(
        executor_backend="kubernetes",
        executor_port=port,
        executor_pod_queue_target_length=1,
        tpu_hosts_per_slice=2,
    )
    executor = KubernetesCodeExecutor(
        kubectl=FakeKubectl(NativeBackend()), storage=storage, config=config
    )
    try:
        r1 = await executor.execute("open('state.json','w').write('{\"n\": 1}')")
        assert r1.exit_code == 0
        assert set(r1.files) == {"/workspace/state.json"}
        r2 = await executor.execute(
            "import json\nprint(json.load(open('state.json'))['n'] + 1)",
            files=r1.files,
        )
        assert r2.stdout == "2\n"
    finally:
        for s in servers:
            s.stop()


def test_warm_worker_traceback_matches_plain_python(native):
    # The pre-started interpreter's bootstrap frame must never appear in user
    # tracebacks — errors render exactly as `python script.py` would.
    r = httpx.post(
        native.base + "/execute",
        json={"source_code": "def boom():\n    raise ValueError('xyz')\nboom()"},
    ).json()
    assert r["exit_code"] == 1
    assert "ValueError: xyz" in r["stderr"]
    assert 'File "<string>"' not in r["stderr"]
    assert "bootstrap" not in r["stderr"]
    # frames point at the script, like plain python
    assert 'in boom' in r["stderr"]


def test_consecutive_executes_after_warm_worker_consumed(native):
    # Request 1 consumes the pre-started worker; request 2 must fall back to
    # a cold interpreter with identical semantics (sandboxes are single-use
    # in production, but the server itself must not require that).
    for expected in ("first", "second", "third"):
        r = httpx.post(
            native.base + "/execute",
            json={"source_code": f"print('{expected}')"},
        ).json()
        assert strip_diagnostics(r) == {
            "stdout": f"{expected}\n", "stderr": "", "exit_code": 0, "files": [],
        }


def test_prestart_disabled_parity(tmp_path):
    server = NativeExecutor(tmp_path / "ws", extra_env={"APP_PRESTART": "0"})
    try:
        r = httpx.post(
            server.base + "/execute",
            json={
                "source_code": "import os\nprint(os.environ['X'], 21 * 2)",
                "env": {"X": "y"},
            },
        ).json()
        assert strip_diagnostics(r) == {
            "stdout": "y 42\n", "stderr": "", "exit_code": 0, "files": [],
        }
    finally:
        server.stop()


def test_warm_worker_timeout_kill(native):
    # Timeout enforcement must hold on the pre-started worker path too
    # (process-group SIGKILL reaches grandchildren).
    t0 = time.time()
    r = httpx.post(
        native.base + "/execute",
        json={"source_code": "import time\ntime.sleep(60)", "timeout": 1.0},
        timeout=30,
    ).json()
    assert r["exit_code"] == -1
    assert r["stderr"] == "Execution timed out"
    assert time.time() - t0 < 20


def test_warm_worker_request_pythonpath(native, tmp_path):
    # Request-env PYTHONPATH must reach imports on the warm path too, even
    # though the interpreter started before the request arrived.
    lib = tmp_path / "lib"
    lib.mkdir()
    (lib / "reqmod.py").write_text("VALUE = 'from-request-path'\n")
    r = httpx.post(
        native.base + "/execute",
        json={
            "source_code": "import reqmod\nprint(reqmod.VALUE)",
            "env": {"PYTHONPATH": str(lib)},
        },
    ).json()
    assert r["stdout"] == "from-request-path\n", r["stderr"]


def test_workspace_import_parity_warm_vs_cold(native):
    # `python script.py` does NOT put the workspace on sys.path (the script
    # lives in a tempdir); the warm-worker path must behave identically, so
    # `import helper` fails the same way on request 1 (warm) and 2 (cold).
    httpx.put(native.base + "/workspace/helper.py", content=b"VALUE = 1\n")
    results = [
        httpx.post(
            native.base + "/execute", json={"source_code": "import helper"}
        ).json()
        for _ in range(2)
    ]
    for r in results:
        assert r["exit_code"] == 1
        assert "ModuleNotFoundError" in r["stderr"]


def test_prestart_imports_env_reaches_worker(tmp_path):
    # APP_PRESTART_IMPORTS must actually reach the warm worker; a module with
    # an import-time side effect proves it ran at preload, and its noise is
    # muted out of the request's captured output.
    lib = tmp_path / "lib"
    lib.mkdir()
    (lib / "preloadmark.py").write_text(
        "import sys\nsys._preloaded_mark = True\nprint('preload noise')\n"
    )
    server = NativeExecutor(
        tmp_path / "ws",
        extra_env={
            "APP_PRESTART_IMPORTS": "preloadmark",
            "PYTHONPATH": str(lib),
        },
    )
    try:
        r = httpx.post(
            server.base + "/execute",
            json={
                "source_code": "import sys\n"
                "print(getattr(sys, '_preloaded_mark', False))"
            },
        ).json()
        assert r["stdout"] == "True\n", r
        assert "preload noise" not in r["stdout"]
        assert r["stderr"] == ""
    finally:
        server.stop()


def test_tpu_warm_preload_initializes_backend(tmp_path):
    # bci_tpu_warm in APP_PRESTART_IMPORTS brings the XLA backend up inside
    # the warm worker before the request arrives (CPU backend here; the TPU
    # image points it at the pod's chips). The executed code proves both that
    # the preload ran (module already in sys.modules) and that the backend
    # was initialized ahead of user code.
    server = NativeExecutor(
        tmp_path / "ws",
        extra_env={
            "APP_PYTHON": sys.executable,  # the interpreter that has jax
            "APP_PRESTART_IMPORTS": "numpy,bci_tpu_warm",
            "APP_SHIM_DIR": str(
                REPO / "bee_code_interpreter_tpu" / "runtime" / "shim"
            ),
            "HOME": str(tmp_path),
            "JAX_PLATFORMS": "cpu",
        },
    )
    try:
        r = httpx.post(
            server.base + "/execute",
            json={
                "source_code": (
                    "import sys\n"
                    "print('bci_tpu_warm' in sys.modules)\n"
                    "import jax\n"
                    "from jax._src import xla_bridge\n"
                    "print(bool(xla_bridge._backends))\n"
                    "print(jax.devices()[0].platform)"
                ),
                "timeout": 120,
            },
            timeout=130,
        ).json()
        assert r["stdout"] == "True\nTrue\ncpu\n", (r["stdout"], r["stderr"][-400:])
    finally:
        server.stop()


def test_hung_preload_falls_back_cold(tmp_path):
    # A preload that never finishes (unreachable accelerator) must not turn
    # every request into an execution timeout: the guard kills the worker at
    # the deadline and the request runs on the cold path instead.
    lib = tmp_path / "lib"
    lib.mkdir()
    (lib / "hangmod.py").write_text("import time\ntime.sleep(3600)\n")
    server = NativeExecutor(
        tmp_path / "ws",
        extra_env={
            "APP_PRESTART_IMPORTS": "hangmod",
            "APP_PRESTART_PRELOAD_TIMEOUT_S": "1",
            "PYTHONPATH": str(lib),
        },
    )
    try:
        t0 = time.time()
        r = httpx.post(
            server.base + "/execute",
            json={"source_code": "print('survived')", "timeout": 30},
            timeout=60,
        ).json()
        assert r["stdout"] == "survived\n", r
        assert r["exit_code"] == 0
        assert time.time() - t0 < 25
    finally:
        server.stop()


def test_hung_preload_mid_request_falls_back_cold(tmp_path):
    # The harder variant: the request is handed to the worker BEFORE the
    # preload guard fires. The started-byte protocol tells the server user
    # code never ran, so the cold retry is safe, bounded by the remaining
    # request budget.
    lib = tmp_path / "lib"
    lib.mkdir()
    (lib / "hangmod2.py").write_text("import time\ntime.sleep(3600)\n")
    server = NativeExecutor(
        tmp_path / "ws",
        extra_env={
            "APP_PRESTART_IMPORTS": "hangmod2",
            "APP_PRESTART_PRELOAD_TIMEOUT_S": "6",
            "PYTHONPATH": str(lib),
        },
    )
    try:
        t0 = time.time()
        r = httpx.post(
            server.base + "/execute",
            json={"source_code": "print('survived-midflight')", "timeout": 30},
            timeout=60,
        ).json()
        elapsed = time.time() - t0
        assert r["stdout"] == "survived-midflight\n", r
        assert r["exit_code"] == 0
        # waited out the guard (~6s from server start), then ran cold
        assert elapsed < 25, elapsed
    finally:
        server.stop()


def test_warm_path_request_env_optout_deproxies_numpy(tmp_path):
    # ADVICE round 1 (medium): the warm worker preloads numpy — installing the
    # reroute proxies — before the request env exists. A request opting out
    # via BCI_XLA_REROUTE=0 must still get a fully de-proxied numpy (the
    # bootstrap uninstalls after applying the request env).
    server = NativeExecutor(
        tmp_path / "ws",
        extra_env={
            "APP_PYTHON": sys.executable,
            "APP_PRESTART_IMPORTS": "numpy",
            "APP_SHIM_DIR": str(
                REPO / "bee_code_interpreter_tpu" / "runtime" / "shim"
            ),
            "HOME": str(tmp_path),
            "JAX_PLATFORMS": "cpu",
        },
    )
    try:
        r = httpx.post(
            server.base + "/execute",
            json={
                "source_code": (
                    "import sys\n"
                    "assert 'numpy' in sys.modules  # proves warm path\n"
                    "import numpy as np\n"
                    "print(bool(getattr(np, '__bci_xla_rerouted__', False)))\n"
                    "print(type(np.random.rand(2_000_000)).__name__)\n"
                ),
                "env": {"BCI_XLA_REROUTE": "0"},
                "timeout": 60,
            },
            timeout=70,
        ).json()
        assert r["stdout"] == "False\nndarray\n", (r["stdout"], r["stderr"][-500:])
        assert r["exit_code"] == 0
    finally:
        server.stop()


def test_warm_path_pythonpath_ordering_matches_cold(tmp_path):
    # ADVICE round 1 (low): a request-supplied PYTHONPATH entry must resolve
    # in the same relative position warm and cold: [script_dir, shim,
    # request paths...]. A request path shadowing a shim-visible module name
    # must NOT win over the shim on the warm path.
    req_lib = tmp_path / "reqlib"
    req_lib.mkdir()
    shim = str(REPO / "bee_code_interpreter_tpu" / "runtime" / "shim")
    probe = (
        "import sys\n"
        f"shim_i = sys.path.index({shim!r})\n"
        f"req_i = sys.path.index({str(req_lib)!r})\n"
        "print(shim_i < req_i)\n"
    )
    for prestart in ("1", "0"):
        server = NativeExecutor(
            tmp_path / f"ws-{prestart}",
            extra_env={
                "APP_PYTHON": sys.executable,
                "APP_PRESTART": prestart,
                "APP_PRESTART_IMPORTS": "numpy",
                "APP_SHIM_DIR": shim,
                "HOME": str(tmp_path),
                "JAX_PLATFORMS": "cpu",
            },
        )
        try:
            r = httpx.post(
                server.base + "/execute",
                json={
                    "source_code": probe,
                    "env": {"PYTHONPATH": str(req_lib)},
                    "timeout": 60,
                },
                timeout=70,
            ).json()
            assert r["stdout"] == "True\n", (prestart, r["stdout"], r["stderr"][-500:])
        finally:
            server.stop()


async def test_pod_group_runs_cross_process_collective(tmp_path, storage):
    """Full-stack multi-host composition (round-1 weak #7): the gang scheduler
    spawns 2 REAL native-server 'pods', the manifest env it baked in
    (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID) is applied
    to the actual server processes, and the submitted payload brings up
    jax.distributed and runs a cross-process collective. Worker-0 stdout
    proves the 2-process world rendezvoused end-to-end through
    kubernetes_code_executor -> executor server -> sandbox -> parallel.mesh."""
    from bee_code_interpreter_tpu.config import Config
    from bee_code_interpreter_tpu.services.kubernetes_code_executor import (
        KubernetesCodeExecutor,
    )
    from tests.fakes import FakeKubectl

    port = free_port()
    servers: list[NativeExecutor] = []

    class DistributedNativeBackend:
        """Starts a real executor-server per 'pod', honoring the manifest's
        container env — the exact plumbing the fake-pod tests bypass."""

        def __init__(self):
            self.port = port
            self._next = 1

        async def start_pod(self, manifest=None) -> str:
            ip = f"127.1.2.{self._next}"
            self._next += 1
            manifest_env = {
                e["name"]: e["value"]
                for e in (manifest or {"spec": {"containers": [{"env": []}]}})[
                    "spec"
                ]["containers"][0]["env"]
                if not e["name"].startswith("APP_")
            }
            server = await asyncio.to_thread(
                NativeExecutor,
                tmp_path / f"dpod-{self._next}",
                ip,
                port,
                {
                    "APP_PYTHON": sys.executable,
                    "APP_PRESTART": "0",  # collectives need fresh env per run
                    "HOME": str(tmp_path),
                    "PYTHONPATH": str(REPO),
                    "JAX_PLATFORMS": "cpu",
                    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
                    **manifest_env,
                },
            )
            servers.append(server)
            return ip

    config = Config(
        executor_backend="kubernetes",
        executor_port=port,
        executor_pod_queue_target_length=1,
        tpu_hosts_per_slice=2,
        execution_timeout_s=120.0,
    )
    executor = KubernetesCodeExecutor(
        kubectl=FakeKubectl(DistributedNativeBackend()),
        storage=storage,
        config=config,
    )
    payload = (
        "import jax\n"
        "from bee_code_interpreter_tpu.parallel import initialize_distributed\n"
        "assert initialize_distributed(), 'pod-group env missing'\n"
        "assert jax.process_count() == 2, jax.process_count()\n"
        "import numpy as np\n"
        "from jax.experimental import multihost_utils\n"
        "g = multihost_utils.process_allgather(np.array([jax.process_index()]))\n"
        "print('GANG', sorted(int(x) for x in np.asarray(g).ravel()))\n"
    )
    try:
        result = await executor.execute(payload)
        if "Multiprocess computations aren't implemented" in result.stderr:
            # The 2-process world DID rendezvous (initialize_distributed and
            # process_count()==2 passed before this point in the payload);
            # this jax build's CPU backend just can't run the collective math.
            pytest.skip("jax CPU backend lacks multiprocess collectives")
        assert result.exit_code == 0, result.stderr[-800:]
        # jax's CPU collective backend (gloo) logs a connection banner to
        # stdout; the line that matters proves both processes contributed.
        assert "GANG [0, 1]" in result.stdout, result.stdout
    finally:
        for s in servers:
            s.stop()


def test_guess_cli_matches_python_oracle(tmp_path):
    # The native guesser and the Python oracle must agree — including on
    # namespace packages, where first-dot truncation used to make every
    # google.* map row unreachable (ADVICE r2).
    from bee_code_interpreter_tpu.runtime.dep_guess import guess_dependencies

    sources = [
        "import numpy\nimport cv2\nfrom PIL import Image\nimport cowsay\n",
        "import google.protobuf\nfrom google.protobuf import json_format\n",
        "from google.cloud import storage, bigquery\nimport google\n",
        "from google import auth\nimport google.generativeai as genai\n",
        "import yaml, requests\nfrom bs4 import BeautifulSoup\n",
        "from google.cloud import (storage, bigquery)\n",
        "from google.cloud import (storage)\n",
        "from google.cloud import (\n    storage,\n    bigquery,\n)\n",
        # an unbalanced '(' inside a string literal must not swallow the
        # genuine import on the next line
        'print("to import, call f(x")\nimport numpy\n',
        "from numpy import(array)\n",  # no space after import
    ]
    stdlib_file = tmp_path / "stdlib_names.txt"
    stdlib_file.write_text("\n".join(sorted(sys.stdlib_module_names)) + "\n")
    for source in sources:
        out = subprocess.run(
            [str(BINARY), "--guess"],
            input=source,
            capture_output=True,
            text=True,
            timeout=30,
            env={
                "PATH": "/usr/local/bin:/usr/bin:/bin",
                "APP_PYPI_MAP": str(EXECUTOR_DIR / "pypi_map.tsv"),
                "APP_STDLIB_FILE": str(stdlib_file),
                "APP_PRESTART": "0",
                "APP_WORKSPACE": str(tmp_path / "ws"),
            },
        )
        assert out.returncode == 0, out.stderr
        native_deps = [l for l in out.stdout.splitlines() if l]
        assert native_deps == guess_dependencies(source), source


def test_warm_exit_report_flushes_unclosed_files(native):
    # The warm worker reports its exit code before interpreter finalization;
    # a module-global file handle user code never closed must still have its
    # buffered bytes on disk when the server snapshots the workspace.
    r = httpx.post(
        native.base + "/execute",
        json={
            "source_code": (
                "f = open('left-open.txt', 'w')\n"
                "f.write('buffered data that only finalization would flush')\n"
            )
        },
    ).json()
    assert r["exit_code"] == 0
    assert r["files"] == ["/workspace/left-open.txt"]
    body = httpx.get(native.base + "/workspace/left-open.txt")
    assert body.text == "buffered data that only finalization would flush"


def test_stdio_closed_payload_still_bounded_by_timeout(native):
    # User code that closes its own stdout/stderr EOFs both pipes instantly;
    # the server must still enforce the execution timeout instead of blocking
    # forever on the reap (review r3 finding).
    t0 = time.time()
    r = httpx.post(
        native.base + "/execute",
        json={
            "source_code": (
                "import os, time\n"
                "os.close(1)\nos.close(2)\n"
                "time.sleep(60)\n"
            ),
            "timeout": 2,
        },
        timeout=30,
    ).json()
    assert r["exit_code"] == -1
    assert r["stderr"] == "Execution timed out"
    assert time.time() - t0 < 15


def test_os_exit_payload_reports_real_code(native):
    # os._exit skips atexit (no exit-code report line); the fallback reap
    # must still return the real code promptly.
    r = httpx.post(
        native.base + "/execute",
        json={"source_code": "import os\nos._exit(5)"},
        timeout=30,
    ).json()
    assert r["exit_code"] == 5


def _wait_warm(server) -> dict:
    deadline = time.time() + 30
    while True:
        body = httpx.get(server.base + "/healthz").json()
        if body["warm"]:
            return body
        assert time.time() < deadline, "worker never reported warm"
        time.sleep(0.1)


def test_preload_failure_is_reported_not_swallowed(tmp_path):
    # A preload that fails (on a chip host: bci_tpu_warm when another process
    # holds the chip) reaches the server's log and /healthz "warm_error"
    # over the status pipe; the worker stays usable and the request's own
    # stderr stays clean.
    server = NativeExecutor(
        tmp_path / "ws",
        extra_env={"APP_PRESTART_IMPORTS": "json,bci_no_such_preload"},
    )
    try:
        body = _wait_warm(server)
        assert "preload bci_no_such_preload failed" in body["warm_error"]
        assert "ModuleNotFoundError" in body["warm_error"]
        r = httpx.post(
            server.base + "/execute",
            json={"source_code": "print('still serving')"},
            timeout=60,
        ).json()
        assert (r["stdout"], r["stderr"], r["exit_code"]) == (
            "still serving\n", "", 0,
        )
    finally:
        server.stop()
    assert b"preload bci_no_such_preload failed" in server.proc.stdout.read()


def test_healthy_preload_reports_no_warm_error(tmp_path):
    server = NativeExecutor(
        tmp_path / "ws", extra_env={"APP_PRESTART_IMPORTS": "json"}
    )
    try:
        assert "warm_error" not in _wait_warm(server)
    finally:
        server.stop()


def test_warmup_failure_is_reported_and_precedes_the_prestart(tmp_path):
    # APP_WARMUP=1 runs its throwaway interpreter to completion BEFORE the
    # pre-started worker exists (a chip belongs to one process at a time),
    # and a failed warm-up is said, not swallowed. An unknown platform makes
    # backend init fail the way a chip held by another process does.
    server = NativeExecutor(
        tmp_path / "ws",
        extra_env={
            "APP_PYTHON": sys.executable,  # the interpreter that has jax
            "APP_WARMUP": "1",
            "APP_PRESTART_IMPORTS": "json",
            "JAX_PLATFORMS": "bci_no_such_platform",
        },
    )
    try:
        body = _wait_warm(server)
        assert body["warm_error"].startswith("accelerator warm-up exited 1:")
        assert "bci_no_such_platform" in body["warm_error"]
    finally:
        server.stop()


def _python_children(pid: int) -> list[int]:
    out = subprocess.run(
        ["pgrep", "-P", str(pid)], capture_output=True, text=True
    ).stdout
    return [int(p) for p in out.split()]


def test_lease_rewarm_waits_for_the_request_process_to_exit(tmp_path):
    # Under a lease (execute #2..N on one server) the replacement warm worker
    # is spawned only after the request's process has exited: while a
    # request runs, the server has exactly ONE interpreter child — a
    # replacement preloading an accelerator runtime would otherwise
    # initialize the backend while the request owns the chip.
    import threading

    lib = tmp_path / "lib"
    lib.mkdir()
    (lib / "preloadmark.py").write_text("")
    server = NativeExecutor(
        tmp_path / "ws",
        extra_env={
            "APP_PRESTART_IMPORTS": "preloadmark",
            "PYTHONPATH": str(lib),
        },
    )

    def post(src: str) -> dict:
        return httpx.post(
            server.base + "/execute", json={"source_code": src}, timeout=60
        ).json()

    try:
        _wait_warm(server)
        assert post("print(1)")["exit_code"] == 0  # claim #1: no re-warm
        # (a jax-free worker reports its exit early and finalizes behind
        # the response: give it a moment to be reaped)
        deadline = time.time() + 10
        while _python_children(server.proc.pid):
            assert time.time() < deadline, "turn #1's worker never exited"
            time.sleep(0.05)
        seen: list[list[int]] = []
        done = threading.Event()

        def watch():
            while not done.is_set():
                seen.append(_python_children(server.proc.pid))
                time.sleep(0.05)

        watcher = threading.Thread(target=watch)
        watcher.start()
        try:
            # claim #2: cold (turn #1 consumed the only worker)
            r = post("import time\ntime.sleep(1.0)\nprint(2)")
        finally:
            done.set()
            watcher.join(timeout=10)
        assert not watcher.is_alive()
        assert r["stdout"] == "2\n"
        assert seen and max(len(children) for children in seen) == 1, seen
        # ...and once it has exited, the server re-warms for turn #3
        deadline = time.time() + 10
        while len(_python_children(server.proc.pid)) != 1:
            assert time.time() < deadline, "no replacement worker after exit"
            time.sleep(0.05)
        r3 = post("import sys\nprint('preloadmark' in sys.modules)")
        assert r3["stdout"] == "True\n", r3
    finally:
        server.stop()


def test_accelerator_interpreter_has_exited_when_the_response_returns(tmp_path):
    # A warm worker that loaded jax holds the accelerator until it has
    # exited, so it skips the early exit report: by the time the response
    # returns its process is gone (and with it the chip), with the real exit
    # code.
    import os

    server = NativeExecutor(
        tmp_path / "ws",
        extra_env={
            "APP_PYTHON": sys.executable,  # the interpreter that has jax
            "APP_PRESTART_IMPORTS": "json",
            "JAX_PLATFORMS": "cpu",
        },
    )
    try:
        _wait_warm(server)
        r = httpx.post(
            server.base + "/execute",
            json={
                "source_code": "import os, sys\nimport jax\n"
                "print(os.getpid())\nsys.exit(7)",
            },
            timeout=120,
        ).json()
    finally:
        server.stop()
    assert r["exit_code"] == 7, r
    with pytest.raises(ProcessLookupError):
        os.kill(int(r["stdout"]), 0)


def _vm_hwm_kib(pid: int) -> int:
    """Peak resident set (VmHWM) of a process, in KiB."""
    for line in Path(f"/proc/{pid}/status").read_text().splitlines():
        if line.startswith("VmHWM:"):
            return int(line.split()[1])
    raise RuntimeError("VmHWM not found")


def test_large_upload_streams_to_disk_constant_memory(native):
    """A 128 MiB PUT must not cost its size in server memory: the body
    streams to a part-file as it arrives and publishes by atomic rename
    (parity with the reference's chunked-to-disk uploads, server.rs:83-86).
    The old buffer-then-write path would push VmHWM past the body size."""
    size = 128 * 1024 * 1024
    chunk = bytes(range(256)) * 256  # 64 KiB pattern

    def body():
        sent = 0
        while sent < size:
            yield chunk
            sent += len(chunk)

    resp = httpx.put(
        native.base + "/workspace/big.bin", content=body(), timeout=120
    )
    assert resp.status_code == 204
    target = native.workspace / "big.bin"
    assert target.stat().st_size == size
    # spot-check content round-trips (first + last chunk via ranges on disk)
    with open(target, "rb") as f:
        assert f.read(len(chunk)) == chunk
        f.seek(size - len(chunk))
        assert f.read() == chunk
    # no torn part-files left behind
    assert [p.name for p in native.workspace.iterdir()] == ["big.bin"]
    hwm_mib = _vm_hwm_kib(native.proc.pid) / 1024
    assert hwm_mib < 96, (
        f"server peak RSS {hwm_mib:.0f} MiB for a 128 MiB upload — "
        "body appears to be buffered in memory, not streamed"
    )


def test_content_length_upload_also_streams(native):
    """The non-chunked (Content-Length) path streams too."""
    size = 96 * 1024 * 1024
    data = b"\xab" * size
    resp = httpx.put(
        native.base + "/workspace/len.bin", content=data, timeout=120
    )
    assert resp.status_code == 204
    assert (native.workspace / "len.bin").stat().st_size == size
    hwm_mib = _vm_hwm_kib(native.proc.pid) / 1024
    assert hwm_mib < 72, f"peak RSS {hwm_mib:.0f} MiB — not streamed"


def test_streamed_upload_overwrites_existing_file(native):
    httpx.put(native.base + "/workspace/f.txt", content=b"old contents")
    httpx.put(native.base + "/workspace/f.txt", content=b"new")
    assert (native.workspace / "f.txt").read_bytes() == b"new"


def test_guess_parity_over_the_full_map(tmp_path):
    """The C++ guesser and the Python oracle must agree on EVERY entry in
    pypi_map.tsv — one synthetic source importing all of them (dotted
    namespace keys included) swept through both implementations."""
    from bee_code_interpreter_tpu.runtime.dep_guess import (
        PYPI_MAP,
        guess_dependencies,
    )

    source = "".join(f"import {name}\n" for name in sorted(PYPI_MAP))
    stdlib_file = tmp_path / "stdlib_names.txt"
    stdlib_file.write_text("\n".join(sorted(sys.stdlib_module_names)) + "\n")
    out = subprocess.run(
        [str(BINARY), "--guess"],
        input=source,
        capture_output=True,
        text=True,
        timeout=60,
        env={
            "PATH": "/usr/local/bin:/usr/bin:/bin",
            "APP_PYPI_MAP": str(EXECUTOR_DIR / "pypi_map.tsv"),
            "APP_STDLIB_FILE": str(stdlib_file),
            "APP_PRESTART": "0",
            "APP_WORKSPACE": str(tmp_path / "ws"),
        },
    )
    assert out.returncode == 0, out.stderr
    native_deps = [l for l in out.stdout.splitlines() if l]
    oracle_deps = guess_dependencies(source)
    assert native_deps == oracle_deps
    # the sweep is not vacuous: nearly the whole map must surface (only
    # SKIP-guarded accelerator aliases drop out)
    assert len(oracle_deps) > len(PYPI_MAP) * 0.9


def test_guess_parity_on_azure_namespace(tmp_path):
    from bee_code_interpreter_tpu.runtime.dep_guess import guess_dependencies

    source = (
        "import azure\n"
        "from azure.identity import DefaultAzureCredential\n"
        "from azure.storage.blob import BlobServiceClient\n"
        "from azure.keyvault.secrets import SecretClient\n"
        "import azure.mgmt.compute\n"
        "import azure.cosmos\n"
    )
    stdlib_file = tmp_path / "stdlib_names.txt"
    stdlib_file.write_text("\n".join(sorted(sys.stdlib_module_names)) + "\n")
    out = subprocess.run(
        [str(BINARY), "--guess"],
        input=source,
        capture_output=True,
        text=True,
        timeout=30,
        env={
            "PATH": "/usr/local/bin:/usr/bin:/bin",
            "APP_PYPI_MAP": str(EXECUTOR_DIR / "pypi_map.tsv"),
            "APP_STDLIB_FILE": str(stdlib_file),
            "APP_PRESTART": "0",
            "APP_WORKSPACE": str(tmp_path / "ws"),
        },
    )
    assert out.returncode == 0, out.stderr
    native_deps = [l for l in out.stdout.splitlines() if l]
    assert native_deps == guess_dependencies(source) == [
        "azure-cosmos", "azure-identity", "azure-keyvault-secrets",
        "azure-mgmt-compute", "azure-storage-blob",
    ]


def _raw_http(native, payload: bytes, recv_bytes: int = 4096) -> bytes:
    with socket.create_connection((native.ip, native.port), timeout=10) as s:
        s.sendall(payload)
        s.settimeout(10)
        out = b""
        try:
            while len(out) < recv_bytes:
                chunk = s.recv(4096)
                if not chunk:
                    break
                out += chunk
        except (socket.timeout, ConnectionResetError, BrokenPipeError):
            pass  # dropping a hostile connection (even mid-send) is legal
        return out


def test_malformed_requests_do_not_kill_the_server(native):
    """Parser hostility battery: garbage request lines, absurd and
    non-numeric Content-Length, garbage chunk-size lines, oversized
    headers. Each must at worst drop that connection — the server (a
    detached-thread-per-connection design where an escaped exception
    would abort the whole process) stays healthy throughout."""
    cases = [
        b"NONSENSE\r\n\r\n",                                  # no method/path
        b"GET /healthz HTTP/1.1\r\nContent-Length: banana\r\n\r\n",
        b"POST /execute HTTP/1.1\r\nContent-Length: 99999999999999999999\r\n\r\n",
        b"POST /execute HTTP/1.1\r\nTransfer-Encoding: chunked\r\n\r\nZZZ\r\n",
        b"PUT /workspace/x HTTP/1.1\r\nTransfer-Encoding: chunked\r\n\r\n-5\r\n",
        b"GET /" + b"A" * (2 << 20) + b" HTTP/1.1\r\n\r\n",   # header flood
        b"POST /execute HTTP/1.1\r\nContent-Length: 10\r\n\r\nshort",  # truncated
    ]
    for payload in cases:
        _raw_http(native, payload)
        # server must still answer a well-formed request afterwards
        r = httpx.get(native.base + "/healthz", timeout=5)
        assert r.status_code == 200, payload[:40]


def test_keepalive_pipelined_requests(native):
    """Two requests on one connection (keep-alive): both answered, bytes
    carried over between requests parse correctly."""
    req = (
        b"GET /healthz HTTP/1.1\r\nHost: x\r\n\r\n"
        b"GET /healthz HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n"
    )
    out = _raw_http(native, req, recv_bytes=1 << 16)
    assert out.count(b"HTTP/1.1 200") == 2


def test_streamed_upload_interrupted_leaves_no_part_file(native):
    """A client that dies mid-upload must not leave a torn part-file (or a
    phantom destination) in the workspace."""
    with socket.create_connection((native.ip, native.port), timeout=10) as s:
        s.sendall(
            b"PUT /workspace/torn.bin HTTP/1.1\r\n"
            b"Content-Length: 1000000\r\n\r\n" + b"x" * 1000
        )
        # abandon the connection with 999000 bytes owed
    deadline = time.time() + 5
    while time.time() < deadline:
        leftovers = list(native.workspace.iterdir())
        if not leftovers:
            break
        time.sleep(0.1)
    assert list(native.workspace.iterdir()) == []
    assert httpx.get(native.base + "/healthz").status_code == 200
