"""``BENCHMARK.json`` against the contract the driver holds it to, and
against the files it names: what is refused before a single run is caught
here, without a chip."""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
# a layer's name: as a name, but it may start with ``_``; no space, no ``/``
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what ``reduced`` may never name: a width
WIDTH = re.compile(
    r"(hidden|intermediate|latent|state|proj)\w*size|_dim$|_rank$|head_size|"
    r"expansion|experts_per_tok"
)


def test_top_level_keys_are_exactly_the_contracts():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    # a full check with the full 24 cells has to fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_command_and_paths():
    assert 1 <= len(BENCH["paths"]) <= 16
    for path in BENCH["paths"]:
        assert PLAIN_PATH.match(path) and not path.startswith("/") and ".." not in path
        assert (ROOT / path).is_dir()
    command = BENCH["command"]
    assert 1 <= len(command) <= 32 and all(isinstance(c, str) for c in command)
    for arg in command[1:]:
        assert not arg.startswith("/") and ".." not in arg
        if "/" in arg:  # a file of the repo: under one of paths
            assert any(arg.startswith(p + "/") for p in BENCH["paths"])
            assert (ROOT / arg).is_file()


def test_every_file_under_paths_has_a_plain_name():
    for path in BENCH["paths"]:
        for file in (ROOT / path).rglob("*"):
            if "__pycache__" in file.parts or not file.is_file():
                continue
            assert PLAIN_PATH.match(str(file.relative_to(ROOT))), file


def test_names_are_plain_and_used_once():
    names = [
        x["name"]
        for key in ("configs", "workloads", "end_to_end", "per_layer")
        for x in BENCH[key]
    ]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert len(x["why"]) <= 200


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file_states_its_source_and_its_cuts(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["source"].startswith("https://")
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    # the entry's ``reduced`` is the file's, key by key, and names no width
    assert entry["reduced"] == [r["key"] for r in cfg["reduced"]]
    for cut in cfg["reduced"]:
        assert set(cut) == {"key", "published", "run", "why"}
        assert not WIDTH.search(cut["key"])
        assert cfg[cut["key"]] == cut["run"] != cut["published"]
    assert cfg["assumed"] and cfg["deployment"]
    pool = cfg["pool"]
    assert set(pool) == {"max_batch", "page_size", "max_pages_per_seq", "n_pages"}
    assert cfg["chips"] in (1, 4) and (cfg["mesh"] is None) == (cfg["chips"] == 1)
    assert any(
        (ROOT / p / "reference" / f"{cfg['reference']}.py").is_file()
        for p in BENCH["paths"]
    )


@pytest.mark.parametrize(
    "name", ["mistral-7b-v02", "mixtral-8x7b", "mistral-7b-v02-tp4"]
)
def test_mistral_family_configurations_keep_the_published_widths(name):
    cfg = json.loads((ROOT / "benchmarks" / "configs" / f"{name}.json").read_text())
    assert (cfg["hidden_size"], cfg["intermediate_size"]) == (4096, 14336)
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"]) == (32, 8)
    assert cfg["vocab_size"] == 32000 and cfg["rope_theta"] == 1e6
    assert cfg["sliding_window"] is None and cfg["rms_norm_eps"] == 1e-5
    if name == "mixtral-8x7b":
        assert (cfg["num_local_experts"], cfg["num_experts_per_tok"]) == (8, 2)
        assert cfg["transformer_config"]["moe_dropless"] is True
        assert cfg["transformer_config"]["moe_group_size"] == 1024
    pool = cfg["pool"]
    assert pool["max_pages_per_seq"] * pool["page_size"] == cfg["max_position_embeddings"]
    assert {c["key"] for c in cfg["reduced"]} <= {
        "num_hidden_layers", "max_position_embeddings"
    }


def test_configuration_files_are_not_shared_and_each_is_used():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files)) and 1 <= len(files) <= 24
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_cells():
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    chips = {c["name"]: json.loads((ROOT / c["file"]).read_text())["chips"]
             for c in BENCH["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and w["chips"] == chips[w["config"]]
        assert (ROOT / "benchmarks" / "traffic" / f"{w['traffic']}.json").is_file()
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_end_to_end_metrics_and_their_bounds():
    metrics = BENCH["end_to_end"]
    assert 1 <= len(metrics) <= 16
    by_name = {m["name"]: m for m in metrics}
    assert by_name["setup_s"]["bound"] == 0.1 and by_name["setup_s"]["unit"] == "s"
    for m in metrics:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["better"] in ("higher", "lower")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    # every cell reports set-up and at least one other end-to-end metric
    for w in BENCH["workloads"]:
        mine = [m for m in metrics if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_that_agrees_with_its_entry(metric):
    assert set(metric) <= {
        "name", "unit", "better", "source", "layer", "moves", "workloads"
    }
    assert metric["source"] in SOURCES and metric["better"] in ("higher", "lower")
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert LAYER.match(metric["layer"])
    for cell in metric.get("workloads", []):
        assert cell in {w["name"] for w in BENCH["workloads"]}
    path = ROOT / "benchmarks" / "layer_metrics" / f"{metric['name']}.py"
    spec = importlib.util.spec_from_file_location(f"_reader_{metric['name']}", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.UNIT == metric["unit"] and reader.LAYER == metric["layer"]
    assert reader.MOVES == metric["moves"] and callable(reader.read)
    assert reader.SOURCE in ("monitor", "trace", "shapes", "memory_stats", "harness")
    # a kernel's share of its roofline: <kernel>_roofline, in %
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%" and reader.BOUND


def test_every_cell_reports_a_per_layer_metric_and_no_reader_is_orphaned():
    assert 1 <= len(BENCH["per_layer"]) <= 128
    for w in BENCH["workloads"]:
        assert any(
            w["name"] in m.get("workloads", [w["name"]]) for m in BENCH["per_layer"]
        )
    readers = {
        p.stem for p in (ROOT / "benchmarks" / "layer_metrics").glob("*.py")
        if p.stem != "__init__"
    }
    assert readers == {m["name"] for m in BENCH["per_layer"]}


def test_the_ignore_file_lists_what_a_run_leaves_behind():
    ignored = (ROOT / ".gitignore").read_text().split()
    for left_behind in (".jax_cache/", ".bench_out/", "chiprun_out/", "__pycache__/"):
        assert left_behind in ignored
