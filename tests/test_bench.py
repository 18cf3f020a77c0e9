"""bench.py: device phases run once and fail without a TPU, a failed phase is
listed and the exit code is non-zero, every result names the platform it ran
on — plus the payload/marker mechanics the phases share."""

import importlib.util
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
bench = importlib.util.module_from_spec(spec)
sys.modules["bench"] = bench
spec.loader.exec_module(bench)


def test_matmul_payload_fails_off_its_platform_and_names_its_device():
    import asyncio

    import pytest

    # asked for a TPU on this CPU harness: the payload FAILS (no CPU
    # substitute, no recorded number) ...
    with pytest.raises(bench.PayloadError) as excinfo:
        asyncio.run(bench.run_payload_json(
            bench.matmul_chain_payload("tpu"), {"JAX_PLATFORMS": "cpu"},
            timeout_s=120.0, marker="RESULT_MATMUL",
        ))
    assert "expected a tpu backend, found cpu" in excinfo.value.stderr
    # ... asked for the platform it is on, it runs at the mechanics size and
    # names the device in its result
    got = asyncio.run(bench.run_payload_json(
        bench.matmul_chain_payload("cpu"), {"JAX_PLATFORMS": "cpu"},
        timeout_s=120.0, marker="RESULT_MATMUL",
    ))
    assert got["platform"] == "cpu" and got["device_kind"]
    assert got["n"] == 1024 and got["gflops"] > 0


def test_main_lists_failed_phases_and_exits_nonzero(monkeypatch, capsys):
    """No TPU here: the device phases fail, are LISTED with their error,
    the headline value is null (never a CPU number) and the exit code is
    non-zero. Host phases are stubbed — their own tests cover them."""
    import json

    import pytest

    async def host_stub(*_a, **_k):
        raise RuntimeError("stubbed host phase")

    for name in (
        "measure_warm_latency_p50_ms", "measure_session_latency_p50_ms",
        "measure_surge", "measure_router", "measure_fairness",
        "measure_streaming_ttfb_ms",
    ):
        monkeypatch.setattr(bench, name, host_stub)
    monkeypatch.setattr(bench, "ensure_native_binary", lambda: None)

    async def payload_stub(source, env, timeout_s, marker):
        if marker in ("RESULT_MATMUL", "RESULT_FLASH"):
            assert env["JAX_PLATFORMS"] == "cpu"  # conftest's ambient pin
            raise bench.PayloadError(
                "payload failed (exit 1)",
                stderr="AssertionError: expected a tpu backend, found cpu",
            )
        return {"tokens_per_s": 1.0}

    async def cpu_stub(source, env, timeout_s, marker="RESULT_GFLOPS"):
        return 98.0

    monkeypatch.setattr(bench, "run_payload_json", payload_stub)
    monkeypatch.setattr(bench, "run_payload", cpu_stub)
    with pytest.raises(SystemExit) as excinfo:
        bench.main()
    assert excinfo.value.code == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["value"] is None and out["device"] is None
    assert "expected a tpu backend" in out["failed_phases"]["matmul"]
    assert "flash_attention" in out["failed_phases"]
    assert "executor_build" in out["failed_phases"]
    assert out["cpu_matmul_gflops"] == 98.0  # a host number, named as one
    assert out["host"]["platform"] == "host"
    assert out["serving"]["platform"] == "cpu"
    assert "CPU fallback" not in json.dumps(out)


def test_payloads_are_valid_python():
    # The matmul/flash payloads only execute on a chip — a syntax error would
    # otherwise surface for the first time inside a chip run.
    compile(bench.matmul_chain_payload(), "<matmul>", "exec")
    for name in ("CPU_PAYLOAD", "FLASH_PAYLOAD", "SERVING_PAYLOAD"):
        compile(getattr(bench, name), f"<{name}>", "exec")


def test_run_payload_values_parses_marker_floats():
    import asyncio

    src = "print('RESULT_FLASH 12.5 3.25')"
    vals = asyncio.run(
        bench.run_payload_values(src, {}, timeout_s=30.0, marker="RESULT_FLASH")
    )
    assert vals == [12.5, 3.25]


def test_run_payload_json_parses_marker_object():
    import asyncio

    src = "import json; print('RESULT_X', json.dumps({'a': 1.5, 'b': None}))"
    got = asyncio.run(
        bench.run_payload_json(src, {}, timeout_s=30.0, marker="RESULT_X")
    )
    assert got == {"a": 1.5, "b": None}


def test_serving_payload_imports_library_code():
    # The serving phase's arithmetic lives in models/serving_bench.py and
    # is covered by the tier-1 test_serving_trace suite; this module only
    # pins the payload↔library seam (the payload runs inside a sandbox
    # whose import path is the request's PYTHONPATH, not the host's).
    assert "serving_bench import run_serving_bench" in bench.SERVING_PAYLOAD
    assert "RESULT_SERVING_JSON" in bench.SERVING_PAYLOAD


def test_benchclock_chain_diff_guard():
    # The shared chained-clock: exact difference when the chain dominates,
    # loud failure when jitter swamps it (a floored difference would print
    # absurd TFLOPS as a result).
    import pytest

    from bee_code_interpreter_tpu.utils.benchclock import chain_diff

    assert abs(chain_diff(1.0, 0.1, 10) - 0.1) < 1e-12
    with pytest.raises(AssertionError, match="clock failed"):
        chain_diff(0.105, 0.100, 10)


def test_analysis_budget_guard_still_raises():
    """The warm-path < 1 ms p50 budget must stay a HARD raise with the
    accelerator classifier active — not drift into a report nobody reads
    (docs/analysis.md "Observability")."""
    import pytest

    bench.check_analysis_budget({"analysis_ms": 0.4})  # under: silent
    with pytest.raises(RuntimeError, match="analysis gate over budget"):
        bench.check_analysis_budget(
            {"analysis_ms": bench.ANALYSIS_BUDGET_MS}
        )


def test_jax_free_payload_stays_inside_analysis_budget():
    """The accelerator cost classifier is a set intersection over facts
    the one AST pass already collected — a jax-free submission (the bench
    latency payload) must stay an order of magnitude inside the 1 ms
    budget, while an accelerator payload classifies without any extra
    pass either."""
    import statistics
    import time

    from bee_code_interpreter_tpu.analysis import WorkloadAnalyzer

    analyzer = WorkloadAnalyzer()
    samples = []
    for _ in range(60):
        t0 = time.perf_counter()
        verdict = analyzer.analyze(bench.LATENCY_PAYLOAD)
        samples.append((time.perf_counter() - t0) * 1000.0)
        assert verdict.cost_class == "cheap"
    p50 = statistics.median(samples)
    assert p50 < bench.ANALYSIS_BUDGET_MS, f"analysis p50 {p50:.3f} ms"
    accel = analyzer.analyze("import jax\nprint(jax.devices())\n")
    assert accel.cost_class == "accelerator"
