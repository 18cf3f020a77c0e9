"""chip_smoke.py: the parent stays off jax, and its phases — importable
functions ``phase(config, platform, **sizes)`` — run end to end here with
``TransformerConfig.tiny()`` on ``cpu``. The command line always passes
``tpu``; there is no path on which it passes without a chip."""

import ast
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

from bee_code_interpreter_tpu.models.transformer import (  # noqa: E402
    TransformerConfig,
)


def test_module_level_imports_no_jax():
    """The parent holds no chip: nothing at module level (or in main's own
    path) may import jax — only the phase functions, in their children."""
    tree = ast.parse((REPO / "chip_smoke.py").read_text())
    top_level = set()
    for node in tree.body:
        if isinstance(node, ast.Import):
            top_level |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            top_level.add(node.module.split(".")[0])
    assert not top_level & {"jax", "jaxlib", "numpy", "bee_code_interpreter_tpu"}
    probe = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; import bench; "
         "chip_smoke.child_env('tpu'); print('jax' in sys.modules)"],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    assert probe.stdout.strip() == "False", probe.stderr


def test_phases_are_importable_functions_of_config_and_platform():
    import inspect

    assert set(chip_smoke.PHASES) == {
        "service", "serving", "kernels", "tp4", "replicas",
    }
    for phase in chip_smoke.PHASES.values():
        assert list(inspect.signature(phase).parameters)[:2] == [
            "config", "platform",
        ]
    # and the command line hard-codes the platform it accepts
    source = (REPO / "chip_smoke.py").read_text()
    assert 'PHASES[name](config, "tpu")' in source


def test_result_line_has_exactly_the_contract_keys():
    """The last stdout line: ``ok`` and ``device`` and nothing else, the
    device exactly platform / kind (text) and count (a whole number) — the
    timings, compile counts and ``"claim": null`` ride the SUMMARY line
    before it, never this one."""
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "init_s": 7.1}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    source = (REPO / "chip_smoke.py").read_text()
    tail = source[source.index('print("SUMMARY"'):]
    assert tail.index('"claim": None') < tail.index("print(result_line(")
    assert tail.count("print(") == 2  # nothing follows the result line


def test_fails_without_a_chip_and_prints_no_result():
    """On this host there is no TPU: the default invocation exits non-zero,
    names the phase, and its stdout carries no result object."""
    run = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
    )
    assert run.returncode != 0
    assert "phase service FAILED" in run.stderr
    for line in run.stdout.splitlines():
        assert not line.startswith("{"), line


def test_fails_outside_a_checkout(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo:
    non-zero exit, nothing on stdout."""
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text()
    )
    run = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
        env={"PATH": "/usr/bin:/bin"},
    )
    assert run.returncode != 0 and run.stdout == ""
    assert "not inside a checkout" in run.stderr


def test_phase_kernels_tiny_cpu():
    out = chip_smoke.phase_kernels(
        TransformerConfig.tiny(), "cpu", batch=1, seq_len=128, page_size=8,
        pages_per_seq=8,
    )
    assert out["device"]["platform"] == "cpu"
    assert out["lowering"] == "pallas interpreter"
    assert set(out["kernels"]) == {
        "flash_fwd", "flash_bwd", "paged_decode", "paged_decode_tp4_shard",
    }
    assert out["kernels"]["paged_decode"]["lengths"] == [1, 21, 32, 58]
    assert out["kernels"]["paged_decode_tp4_shard"]["shape"]["kvh"] == 1
    # the form the decode program calls: the stacked leaf at a layer index,
    # the new token written in place, bit for bit paged_append's pool
    for name in ("paged_decode", "paged_decode_tp4_shard"):
        assert out["kernels"][name]["shape"]["layers"] == 2
        assert out["kernels"][name]["shape"]["layer"] == 1
        assert out["kernels"][name]["pool_bitwise_equal"] is True


def test_phase_serving_tiny_cpu(tmp_path):
    out = chip_smoke.phase_serving(
        TransformerConfig.tiny(), "cpu", n_layers=2,
        prompt_lens=(5, 11, 19, 30), n_requests=6, new_tokens=6,
        max_batch=4, page_size=8, max_pages_per_seq=8, n_pages=64,
        trace_dir=tmp_path / "trace",
    )
    assert out["device"]["platform"] == "cpu"
    assert out["model"]["reduced"] is None  # tiny IS two layers
    assert out["requests"]["solo_rerun_equal"] is True
    assert out["compiles"]["by_trigger"]["first_call"] >= 2
    assert any(p.startswith("/host:CPU") for p in out["trace"]["planes"])
    assert out["decode_steps"] > 0
    assert out["timing_note"] == "smoke, not a measurement"
    json.dumps(out)  # the child prints it as one JSON line


def test_phase_serving_refuses_the_wrong_platform():
    with pytest.raises(chip_smoke.SmokeFailure, match="expected a tpu backend"):
        chip_smoke.phase_serving(TransformerConfig.tiny(), "tpu")


def test_four_chip_phases_tiny_cpu():
    """The second mode's phases over the suite's virtual CPU devices:
    tensor-parallel over four, and four one-device replicas each on its own
    device."""
    sizes = dict(
        n_layers=2, new_tokens=4, max_batch=2, page_size=8, n_pages=32,
    )
    tp4 = chip_smoke.phase_tp4(
        TransformerConfig.tiny(), "cpu", prompt_lens=(5, 11), n_requests=2,
        max_pages_per_seq=4, **sizes,
    )
    assert tp4["mesh"]["shape"] == "tp=4" and tp4["mesh"]["n_devices"] == 4
    replicas = chip_smoke.phase_replicas(
        TransformerConfig.tiny(), "cpu", prompt_len=5, n_requests=8,
        max_pages_per_seq=4, **sizes,
    )
    assert replicas["replicas_used"] == [0, 1, 2, 3]


def test_phase_service_cpu():
    """The service phase end to end on the CPU backend: the README boot, the
    four requests from sandbox children, a control plane that never loads
    jax, no surviving executor-server, and the image's warm configuration
    on one server."""
    out = chip_smoke.phase_service(None, "cpu")
    assert out["device"]["platform"] == "cpu"
    assert set(out["requests"]) == {
        "matmul", "reroute", "continuous_batching", "grpc_execute",
    }
    assert out["requests"]["reroute"]["probe"]["platform"] == "cpu"
    assert [t["warm"] for t in out["image_warm_configuration"]["turns"]] == [
        True, False, True,
    ]
