"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``. Its configuration is the entry of
``configs`` with that name and the file it points at; its traffic mix is
``<path>/traffic/<mix>.json``, its per-layer metrics are the entries of
``per_layer`` (all, or those that list the cell under ``workloads``) with a
reader at ``<path>/layer_metrics/<metric>.py``, and its reference is
``<path>/reference/<name>.py``, each looked for under every directory of
``paths``. Adding a cell, a mix, a metric, a configuration or a reference
is adding files and entries; nothing here knows a name.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path


class SpecError(LookupError):
    """``BENCHMARK.json`` names something that is not there."""


def load_benchmark(root: str | Path) -> dict:
    path = Path(root) / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"no BENCHMARK.json in {root}") from None


def find(root: Path, bench: dict, kind: str, filename: str) -> Path:
    """``<root>/<path>/<kind>/<filename>`` under the first of ``paths``
    that has it."""
    tried = []
    for path in bench["paths"]:
        candidate = Path(root) / path / kind / filename
        if candidate.is_file():
            return candidate
        tried.append(str(candidate.relative_to(root)))
    raise SpecError(f"no {kind} file {filename!r}: looked for {tried}")


def cell(root: str | Path, bench: dict, name: str) -> dict:
    """Everything one cell names, resolved: ``workload`` (its entry),
    ``config`` (its file's contents), ``mix_path``, and the two metric
    lists."""
    root = Path(root)
    workloads = {w["name"]: w for w in bench["workloads"]}
    if name not in workloads:
        raise SpecError(
            f"unknown workload {name!r}; BENCHMARK.json has {sorted(workloads)}"
        )
    workload = workloads[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if workload["config"] not in configs:
        raise SpecError(
            f"workload {name!r} names config {workload['config']!r}; "
            f"BENCHMARK.json has {sorted(configs)}"
        )
    config_path = root / configs[workload["config"]]["file"]
    if not config_path.is_file():
        raise SpecError(
            f"config {workload['config']!r}: no file {configs[workload['config']]['file']}"
        )
    try:
        mix_path = find(root, bench, "traffic", workload["traffic"] + ".json")
    except SpecError as e:
        raise SpecError(
            f"workload {name!r} names traffic mix {workload['traffic']!r}: {e}"
        ) from None

    def metrics(kind: str) -> list[dict]:
        return [
            m for m in bench[kind]
            if "workloads" not in m or name in m["workloads"]
        ]

    return {
        "workload": workload,
        "config": json.loads(config_path.read_text()),
        "mix_path": mix_path,
        "end_to_end": metrics("end_to_end"),
        "per_layer": metrics("per_layer"),
    }


def load_module(path: Path, name: str):
    """Import one file as a module of its own (readers and references are
    found by path, so a fixture or a later PR's file needs no registry)."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_named(root: str | Path, bench: dict, kind: str, name: str, what: str):
    """The module ``<path>/<kind>/<name>.py``; ``what`` names it in the
    error where no directory of ``paths`` has it."""
    try:
        path = find(Path(root), bench, kind, name + ".py")
    except SpecError as e:
        raise SpecError(f"{what} {name!r}: {e}") from None
    return load_module(path, f"_bench_{kind}_{name.replace('-', '_')}")


def layer_metric_reader(root: str | Path, bench: dict, metric: str):
    return load_named(
        root, bench, "layer_metrics", metric, "no reader for per-layer metric"
    )


def reference(root: str | Path, bench: dict, name: str):
    return load_named(root, bench, "reference", name, "reference")
