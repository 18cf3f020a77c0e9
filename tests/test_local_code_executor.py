from bee_code_interpreter_tpu.services.local_code_executor import LocalCodeExecutor


async def test_execute_basic(local_executor: LocalCodeExecutor):
    result = await local_executor.execute("print(21 * 2)")
    # health-check contract (reference health_check.py:25-53)
    assert result.stdout == "42\n"
    assert result.exit_code == 0


async def test_file_roundtrip_across_executions(local_executor: LocalCodeExecutor):
    # The session-continuity mechanism: file map out of one execution feeds the
    # next (reference test_http.py:47-85; SURVEY.md §5 checkpoint/resume).
    r1 = await local_executor.execute("open('data.txt', 'w').write('persisted state')")
    assert set(r1.files) == {"/workspace/data.txt"}
    r2 = await local_executor.execute(
        "print(open('data.txt').read())", files=r1.files
    )
    assert r2.stdout == "persisted state\n"
    assert r2.exit_code == 0
    # unchanged restored file is not re-reported
    assert r2.files == {}


async def test_workspace_isolated_between_executions(local_executor: LocalCodeExecutor):
    await local_executor.execute("open('leak.txt', 'w').write('x')")
    r = await local_executor.execute("import os; print(os.path.exists('leak.txt'))")
    assert r.stdout == "False\n"


async def test_env_forwarded(local_executor: LocalCodeExecutor):
    r = await local_executor.execute(
        "import os; print(os.environ['FOO'])", env={"FOO": "bar"}
    )
    assert r.stdout == "bar\n"


async def test_binary_file_roundtrip(local_executor: LocalCodeExecutor):
    r1 = await local_executor.execute(
        "open('blob.bin','wb').write(bytes(range(256)))"
    )
    r2 = await local_executor.execute(
        "data = open('blob.bin','rb').read()\nprint(len(data), data[:4].hex())",
        files=r1.files,
    )
    assert r2.stdout == "256 00010203\n"


async def test_mnist_dp_8chip_example_end_to_end(storage, tmp_path):
    # BASELINE.json north star: the 8-chip data-parallel MNIST training job
    # submitted through the execution path completes end-to-end. Runs the
    # actual example payload on 8 virtual CPU devices (SURVEY.md §4's
    # simulated multi-chip strategy); on a real pod the same payload lands on
    # the slice's physical chips. Uses the runtime shim (as the executor image
    # does) so the sandbox can import the bundled model library.
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    source = (repo / "examples" / "mnist-dp-8chip.py").read_text()
    executor = LocalCodeExecutor(
        storage=storage,
        workspace_root=tmp_path / "workspaces",
        disable_dep_install=True,
        execution_timeout_s=120.0,
        shim_dir=repo / "bee_code_interpreter_tpu" / "runtime" / "shim",
    )
    r = await executor.execute(
        source,
        env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
    )
    assert r.exit_code == 0, r.stderr
    assert "trained data-parallel over 8 device(s)" in r.stdout
    # loss decreased over the 20 steps
    losses = [
        float(line.rsplit(" ", 1)[1])
        for line in r.stdout.splitlines()
        if line.startswith("step ")
    ]
    assert losses[-1] < losses[0], r.stdout


async def test_per_request_timeout(local_executor: LocalCodeExecutor):
    # A request may shorten the deadline below the service default...
    r = await local_executor.execute(
        "import time\ntime.sleep(30)", timeout_s=0.5
    )
    assert r.exit_code == -1
    assert r.stderr == "Execution timed out"


async def test_per_request_timeout_clamped_to_service_bound(storage, tmp_path):
    # ...but can never extend past it.
    executor = LocalCodeExecutor(
        storage=storage,
        workspace_root=tmp_path / "workspaces",
        disable_dep_install=True,
        execution_timeout_s=0.5,
    )
    r = await executor.execute("import time\ntime.sleep(30)", timeout_s=9999)
    assert r.exit_code == -1
    assert r.stderr == "Execution timed out"
