"""The benchmark's harness end to end, on the CPU, at a tiny size.

``benchmarks/run.py`` refuses any platform but ``tpu``; here
``harness.run_cell`` is called as a library with ``platform="cpu"`` over a
copy of ``benchmarks/`` that holds two tiny configurations under the real
traffic mixes, readers and references. Nothing timed here is a device
number: the tests look at the shape of the result, at what fails by name,
and at whether a new configuration, mix, per-layer metric and cell are
picked up from new files and entries alone.
"""

from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

TINY_DENSE = {
    "name": "tiny-dense", "source": "a test", "reference": "mistral",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "max_position_embeddings": 4608, "rope_theta": 1000000.0,
    "rms_norm_eps": 1e-05, "sliding_window": None,
    "reduced": [], "assumed": [], "deployment": "a test", "chips": 1,
    "mesh": None,
    "pool": {"max_batch": 32, "page_size": 16, "max_pages_per_seq": 288,
             "n_pages": 2560},
    "transformer_config": {
        "vocab_size": 256, "d_model": 64, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 128, "max_seq_len": 4608,
        "rope_theta": 1000000.0, "sliding_window": None, "dtype": "bfloat16",
    },
}


def tiny_moe() -> dict:
    cfg = copy.deepcopy(TINY_DENSE)
    cfg.update(name="tiny-moe", reference="mixtral", num_local_experts=4,
               num_experts_per_tok=2)
    cfg["pool"]["max_batch"] = 16
    cfg["transformer_config"].update(
        n_experts=4, moe_top_k=2, moe_dropless=True, moe_group_size=1024
    )
    return cfg


def write_root(root: Path, configs: list[dict], workloads: list[dict]) -> dict:
    """A checkout in miniature: the real ``benchmarks/`` tree and a
    ``BENCHMARK.json`` with the real metrics over the given cells."""
    if not (root / "benchmarks").exists():
        shutil.copytree(
            ROOT / "benchmarks", root / "benchmarks",
            ignore=shutil.ignore_patterns("__pycache__", "*.pb"),
        )
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = []
    for cfg in configs:
        path = f"benchmarks/configs/{cfg['name']}.json"
        (root / path).write_text(json.dumps(cfg))
        bench["configs"].append({
            "name": cfg["name"], "source": "a test", "file": path,
            "reduced": [], "why": "a test",
        })
    bench["workloads"] = workloads
    for metric in bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [w["name"] for w in workloads]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def ticking_clock(tick_s: float = 0.007):
    """A clock that advances by ``tick_s`` every time it is read. The loop
    reads it about five times a step, so a window of 2 s is some sixty steps
    however loaded the machine is, and a seed gives one schedule: the tests count, they do not
    time."""
    import itertools

    ticks = itertools.count()
    return lambda: next(ticks) * tick_s


CELLS = [
    {"name": "tiny_chat", "config": "tiny-dense", "traffic": "chat",
     "chips": 1, "why": "a test"},
    {"name": "tiny_docqa", "config": "tiny-moe", "traffic": "docqa",
     "chips": 1, "why": "a test"},
]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench_root")
    write_root(root, [TINY_DENSE, tiny_moe()], CELLS)
    return root


@pytest.fixture(scope="module")
def results(tiny_root) -> dict:
    """Each tiny cell run once untraced and once traced (module scope: the
    runs are the expensive part, the tests below read them)."""
    from benchmarks.lib import harness

    logs: list[str] = []
    out = {}
    for cell, seed in (("tiny_chat", 11), ("tiny_docqa", 12)):
        for trace in (False, True):
            out[cell, trace] = harness.run_cell(
                tiny_root, cell, seed, 2.0, trace, platform="cpu",
                log=logs.append, clock=ticking_clock(),
            )
    out["logs"] = logs
    return out


@pytest.mark.parametrize("cell", ["tiny_chat", "tiny_docqa"])
@pytest.mark.parametrize("trace", [False, True])
def test_result_has_exactly_the_contracts_keys(results, cell, trace):
    result = results[cell, trace]
    want = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result) == want | ({"breakdown"} if trace else set())
    assert result["correct"] is True, [
        line for line in results["logs"] if line.startswith("PROBLEM")
    ]
    assert result["failed"] == 0 and result["attempted"] > 0
    device = {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(result["device"]) == device | (
        {"busy_s", "window_s"} if trace else set()
    )
    for value in result["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], (int, float))
    json.dumps(result)  # one JSON object, as it will be printed


@pytest.mark.parametrize("cell", ["tiny_chat", "tiny_docqa"])
def test_untraced_run_reports_the_cells_end_to_end_metrics(results, cell):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(results[cell, False]["metrics"]) == {
        m["name"] for m in bench["end_to_end"]
    }
    assert all(v["value"] > 0 for v in results[cell, False]["metrics"].values())


@pytest.mark.parametrize("cell", ["tiny_chat", "tiny_docqa"])
def test_traced_run_reports_per_layer_metrics_and_a_breakdown(results, cell):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    result = results[cell, True]
    names = set(result["metrics"])
    assert names <= {m["name"] for m in bench["per_layer"]}
    # what a CPU run can count; the roofline shares need the chip's peaks
    assert {"batch_occupancy", "decode_step_ms_p50", "compiles_in_window",
            "decode_device_ms_p50", "device_idle_share"} <= names
    assert "decode_step_roofline" not in names and "hbm_peak_gb" not in names
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] >= result["device"]["busy_s"]
    for key in ("device_ops", "idle_gaps"):
        rows = result["breakdown"][key]
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)


def test_what_only_the_chips_trace_names_is_left_out_here(results):
    """``moe_flops_over_routed`` reads the expert matmuls' shapes from the
    device's instruction names: the CPU's stand-in threads carry none, so
    the reader finds nothing and the metric is left out, never guessed
    (tests/benchmark/test_benchmark_opcount.py has it on a made-up slice)."""
    for cell in ("tiny_chat", "tiny_docqa"):
        assert "moe_flops_over_routed" not in results[cell, True]["metrics"]
        assert "flash_fwd_roofline" not in results[cell, True]["metrics"]
        # the recorded tail comes from the harness's own stamps
        assert results[cell, True]["metrics"]["itl_ms_p99_rec"]["value"] > 0


def test_both_loop_occupancies(results):
    # chat: 32 callers over 32 rows; docqa: 16 callers over the MoE's 16
    for cell in ("tiny_chat", "tiny_docqa"):
        occupancy = results[cell, True]["metrics"]["batch_occupancy"]["value"]
        assert 90.0 <= occupancy <= 100.0


@pytest.mark.parametrize("broken, message", [
    ({"workload": "nope"}, "unknown workload 'nope'"),
    ({"config": "nope"}, "names config 'nope'"),
    ({"traffic": "nope"}, "names traffic mix 'nope'"),
    ({"metric": "nope"}, "no reader for per-layer metric 'nope'"),
    ({"reference": "nope"}, "reference 'nope'"),
])
def test_an_unknown_name_fails_by_name(tiny_root, tmp_path, broken, message):
    from benchmarks.lib import harness, spec

    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]
    name = broken.get("workload", cell["name"])
    cell.update({k: v for k, v in broken.items() if k in ("config", "traffic")})
    if "metric" in broken:
        bench["per_layer"].append({
            "name": broken["metric"], "unit": "x", "better": "higher",
            "source": "program_counter", "layer": "x", "moves": "out_tok_s",
        })
    if "reference" in broken:
        path = root / bench["configs"][0]["file"]
        cfg = json.loads(path.read_text())
        cfg["reference"] = broken["reference"]
        path.write_text(json.dumps(cfg))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError, match=message):
        harness.run_cell(root, name, 1, 1.0, True, platform="cpu")


def test_an_open_loop_is_refused_by_name(tiny_root, tmp_path):
    from benchmarks.lib import harness, traffic

    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    mix = json.loads((root / "benchmarks/traffic/chat.json").read_text())
    mix.update(name="chat_open", loop="open")
    (root / "benchmarks/traffic/chat_open.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"][0]["traffic"] = "chat_open"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(traffic.TrafficError, match="'chat_open' asks for loop 'open'"):
        harness.run_cell(root, "tiny_chat", 1, 1.0, False, platform="cpu")


def test_a_mix_longer_than_the_block_table_is_refused(tiny_root, tmp_path):
    from benchmarks.lib import harness

    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    path = root / "benchmarks/configs/tiny-dense.json"
    cfg = json.loads(path.read_text())
    cfg["pool"]["max_pages_per_seq"] = 32  # 512 slots; chat needs 1024
    path.write_text(json.dumps(cfg))
    with pytest.raises(harness.CellError, match="holds 512 a row"):
        harness.run_cell(root, "tiny_chat", 1, 1.0, False, platform="cpu")


def test_a_configuration_that_contradicts_its_published_keys_is_refused():
    from benchmarks.lib import harness
    from bee_code_interpreter_tpu.models import transformer as T

    cfg = copy.deepcopy(TINY_DENSE)
    cfg["transformer_config"]["d_ff"] = 256
    with pytest.raises(harness.CellError, match="intermediate_size=128"):
        harness.transformer_config(T, cfg)
    cfg = copy.deepcopy(TINY_DENSE)
    cfg["rms_norm_eps"] = 1e-6
    with pytest.raises(harness.CellError, match="rms_norm_eps"):
        harness.transformer_config(T, cfg)


def test_new_files_and_entries_add_a_cell_with_no_edit(tiny_root, tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell, each
    added as a new file or a new entry: nothing that was there is touched."""
    from benchmarks.lib import harness

    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    before = {
        p: p.read_bytes() for p in (root / "benchmarks").rglob("*") if p.is_file()
    }
    cfg = copy.deepcopy(TINY_DENSE)
    cfg.update(name="tiny-short", max_position_embeddings=256)
    cfg["pool"] = {"max_batch": 4, "page_size": 16, "max_pages_per_seq": 16,
                   "n_pages": 128}
    cfg["transformer_config"]["max_seq_len"] = 256
    (root / "benchmarks/configs/tiny-short.json").write_text(json.dumps(cfg))
    (root / "benchmarks/traffic/pairs.json").write_text(json.dumps({
        "name": "pairs", "loop": "closed", "clients": 3,
        "prompt_tokens": {"values": [16, 48], "weights": [0.5, 0.5]},
        "output_tokens": {"values": [8, 16], "weights": [0.5, 0.5]},
        "deck": 4, "sampled_share": 0.34,
        "sampling": {"temperature": 1.0, "top_p": 0.9},
        "first_budget_fraction": [0.5, 1.0], "why": "a test",
    }))
    (root / "benchmarks/layer_metrics/steps_in_window.py").write_text(
        'LAYER = "scheduler"\nUNIT = "count"\nMOVES = "out_tok_s"\n'
        'SOURCE = "monitor"\n\n\ndef read(run):\n    return len(run.steps)\n'
    )
    (root / "benchmarks/layer_metrics/never_there.py").write_text(
        'LAYER = "x"\nUNIT = "x"\nMOVES = "out_tok_s"\nSOURCE = "trace"\n\n\n'
        "def read(run):\n    return None\n"
    )
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-short", "source": "a test",
        "file": "benchmarks/configs/tiny-short.json", "reduced": [],
        "why": "a test",
    })
    bench["workloads"].append({
        "name": "tiny_pairs", "config": "tiny-short", "traffic": "pairs",
        "chips": 1, "why": "a test",
    })
    for name in ("steps_in_window", "never_there"):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "scheduler",
            "moves": "out_tok_s", "workloads": ["tiny_pairs"],
        })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    result = harness.run_cell(
        root, "tiny_pairs", 5, 1.5, True, platform="cpu", log=lambda _: None,
        clock=ticking_clock(),
    )
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["steps_in_window"]["value"] > 0
    # a reader that finds nothing returns nothing: left out of the line
    assert "never_there" not in result["metrics"]
    # the cell-only metrics of other cells are not reported here
    assert "moe_flops_over_routed" not in result["metrics"]
    assert all(p.read_bytes() == data for p, data in before.items())


def _run_command(cwd: Path, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "tiny_chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_the_command_refuses_a_directory_without_the_repository(tiny_root):
    """BENCHMARK.json and the files under ``paths`` alone are not a
    checkout: a non-zero exit code and no result."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = _run_command(tiny_root, env)
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert "not in a checkout" in done.stderr


def test_the_command_refuses_any_platform_but_the_tpu(tiny_root, tmp_path):
    """Where jax finds no TPU the command prints no result and fails: a
    CPU number is never written under the name of a device metric."""
    root = tmp_path / "root"
    shutil.copytree(tiny_root, root)
    os.symlink(ROOT / "bee_code_interpreter_tpu", root / "bee_code_interpreter_tpu")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = _run_command(root, env)
    assert done.returncode != 0
    assert not any(line.startswith("{") for line in done.stdout.splitlines())
    assert "needs 1 tpu chip(s)" in done.stderr
