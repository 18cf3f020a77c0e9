"""The readers of the program's own spans and phase records.

``ContinuousBatcher`` draws ``serve.*`` spans on the profiler's clock and
keeps ``phase_ms`` in its step records; six per-layer readers read them. The
tiny cells of ``test_benchmark_harness.py`` are run traced once more here,
with one reader added that hands the ``RunData`` over, so that the spans are
looked at where the readers find them; then each trace reader is held to a
hand-made trace whose answer is worked out beside it. Nothing timed here is
a device number.
"""

from __future__ import annotations

import importlib
import json
import sys
import types

import pytest
from test_benchmark_harness import (
    CELLS,
    ROOT,
    TINY_DENSE,
    ticking_clock,
    tiny_moe,
    write_root,
)

from benchmarks.lib.xplane import EPS, Device, Event, Trace

PROGRAM_SPAN = ("host_sample_ms_p50", "logits_pull_ms_p50", "step_unspanned_ms_p50")
DEVICE_TRACE = (
    "idle_in_program_spans_share", "admit_host_ms_per_ktok",
    "decode_program_ms_p50",
)
SPY = (
    'LAYER = "scheduler"\nUNIT = "count"\nMOVES = "out_tok_s"\n'
    'SOURCE = "harness"\nRUNS = []\n\n\n'
    "def read(run):\n    RUNS.append(run)\n    return None\n"
)


def reader(name: str):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


@pytest.fixture(scope="module")
def traced(tmp_path_factory) -> dict:
    """Each tiny cell run once with ``--trace 1``: the result and the
    ``RunData`` its readers were given."""
    from benchmarks.lib import harness

    root = tmp_path_factory.mktemp("bench_root")
    write_root(root, [TINY_DENSE, tiny_moe()], CELLS)
    (root / "benchmarks/layer_metrics/run_spy.py").write_text(SPY)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "run_spy", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "scheduler", "moves": "out_tok_s",
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = {}
    for cell, seed in (("tiny_chat", 21), ("tiny_docqa", 22)):
        result = harness.run_cell(
            root, cell, seed, 2.0, True, platform="cpu",
            log=lambda _: None, clock=ticking_clock(),
        )
        (run,) = sys.modules["_bench_layer_metrics_run_spy"].RUNS
        out[cell] = (result, run)
    return out


def inside(child: Event, parents: list[Event]) -> bool:
    return any(
        p.start - EPS <= child.start and child.end <= p.end + EPS
        for p in parents
    )


@pytest.mark.parametrize("cell", ["tiny_chat", "tiny_docqa"])
def test_serve_spans_lie_inside_the_harness_step_and_each_other(traced, cell):
    _, run = traced[cell]
    by_name: dict[str, list[Event]] = {}
    for e in run.trace.host:
        by_name.setdefault(e.name, []).append(e)
    harness_steps = by_name["bench.engine_step"]
    assert by_name["serve.step"] and by_name["serve.admit"]
    for name, events in by_name.items():
        if not name.startswith("serve."):
            continue
        parent = name.rpartition(".")[0]
        parents = harness_steps if parent == "serve" else by_name[parent]
        assert all(inside(e, parents) for e in events), name
    # every phase of the plain decode step, and every part of an admission
    assert {
        "serve.step.upload", "serve.step.dispatch", "serve.step.wait",
        "serve.step.pull", "serve.step.sample", "serve.admit.prefill",
        "serve.admit.seed_pool", "serve.admit.pull", "serve.admit.activate",
    } <= set(by_name)
    steps = [dict(e.stats) for e in by_name["serve.step"]]
    assert all(int(s["rows"]) <= run.pool["max_batch"] for s in steps)
    numbers = [int(s["n"]) for s in steps]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    traffic = {c["name"]: c["traffic"] for c in CELLS}[cell]
    mix = json.loads((ROOT / "benchmarks/traffic" / f"{traffic}.json").read_text())
    for e in by_name["serve.admit"]:
        stats = dict(e.stats)
        assert int(stats["prompt_tokens"]) in mix["prompt_tokens"]["values"]
        assert int(stats["pages"]) > 0 and int(stats["req"]) >= 0
    assert all(int(dict(e.stats)["bytes"]) > 0 for e in by_name["serve.step.pull"])


@pytest.mark.parametrize("cell", ["tiny_chat", "tiny_docqa"])
def test_program_span_readers_split_the_decode_step(traced, cell):
    result, run = traced[cell]
    values = {name: reader(name).read(run) for name in PROGRAM_SPAN}
    assert all(isinstance(v, float) and v >= 0.0 for v in values.values())
    for name, value in values.items():
        assert result["metrics"][name] == {"value": value, "unit": "ms"}
    step = result["metrics"]["decode_step_ms_p50"]["value"]
    assert values["host_sample_ms_p50"] + values["logits_pull_ms_p50"] < step
    # the spans cover the step: what they leave is a small part of it
    assert values["step_unspanned_ms_p50"] < 0.1 * step


@pytest.mark.parametrize("cell", ["tiny_chat", "tiny_docqa"])
def test_readers_of_the_chips_lines_find_nothing_on_the_cpu(traced, cell):
    result, run = traced[cell]
    assert not any(d.modules for d in run.trace.devices)
    for name in ("admit_host_ms_per_ktok", "decode_program_ms_p50"):
        assert reader(name).read(run) is None
        assert name not in result["metrics"]
    # the stand-in threads do let the intersection be exercised
    share = result["metrics"]["idle_in_program_spans_share"]["value"]
    assert 0.0 < share <= 100.0


def a_run(devices, host, steps=(), slice_=(0.0, 10.0)):
    spans = {}
    for e in host:
        if e.name.startswith("bench."):
            spans.setdefault(e.name, []).append(e)
    return types.SimpleNamespace(
        trace=Trace(devices, spans, sorted(host, key=lambda e: (e.start, -e.end))),
        slice=slice_, steps=list(steps),
    )


def test_idle_share_counts_exact_overlap_with_innermost_spans():
    """Slice 0..10 s. The device runs in 1..3 and 6..7, so it idles in 0..1,
    3..6 and 7..10: 7 s. ``serve.step`` 0..9 holds ``serve.step.dispatch``
    0..1.5 and ``serve.step.sample`` 4..8; only those two hold no other
    span. Idle inside them: 0..1, 4..6 and 7..8 = 4 s. The idle second 3..4
    lies under ``serve.step`` alone, and 8..10 under it or under nothing:
    not explained. 4 / 7 = 57.142857 %. (A midpoint rule would have given
    the whole gap 3..6 to ``serve.step.sample``.)"""
    device = Device("/device:TPU:0", [
        Event("%fusion.1", 1.0, 3.0), Event("%fusion.2", 6.0, 7.0),
    ], [])
    host = [
        Event("serve.step", 0.0, 9.0),
        Event("serve.step.dispatch", 0.0, 1.5),
        Event("serve.step.sample", 4.0, 8.0),
    ]
    value = reader("idle_in_program_spans_share").read(a_run([device], host))
    assert value == pytest.approx(100.0 * 4.0 / 7.0)


def test_admit_host_time_is_the_idle_time_inside_admissions_per_ktok():
    """Slice 0..10 s. One ``serve.admit`` 0.5..5 of 2,000 prompt tokens; the
    chip runs the prefill in 1..3 and a scatter in 3.5..4, so inside the
    admission nothing runs in 0.5..1, 3..3.5 and 4..5: 2 s, which is
    1,000 ms per 1,000 tokens. A second admission 9..11 ends after the
    slice and is left out with its tokens."""
    device = Device("/device:TPU:0", [
        Event("%fusion.1", 1.0, 3.0), Event("%scatter.2", 3.5, 4.0),
    ], [Event("jit_prefill_forward(7)", 1.0, 3.0)])
    host = [
        Event("serve.admit", 0.5, 5.0,
              (("req", 3), ("prompt_tokens", 2000), ("pages", 130))),
        Event("serve.admit", 9.0, 11.0,
              (("req", 4), ("prompt_tokens", 512), ("pages", 40))),
    ]
    value = reader("admit_host_ms_per_ktok").read(a_run([device], host))
    assert value == pytest.approx(1000.0)
    # no chip's lines (the stand-in of the tests), or no admission: nothing
    stand_in = Device("cpu-stand-in", device.ops, [])
    assert reader("admit_host_ms_per_ktok").read(a_run([stand_in], host)) is None
    assert reader("admit_host_ms_per_ktok").read(a_run([device], host[1:])) is None


def test_decode_program_time_is_the_slowest_chips_median():
    """Chip 0 runs the decode program for 100 and 102 ms and a prefill for
    500 ms: its median is 101 ms, the prefill does not count. Chip 1 runs
    it for 110 and 104 ms: 107 ms. The slowest chip's median is 107 ms."""
    chip0 = Device("/device:TPU:0", [], [
        Event("jit_decode_step_paged(11)", 0.0, 0.100),
        Event("jit_decode_step_paged(11)", 1.0, 1.102),
        Event("jit_prefill_forward(12)", 2.0, 2.5),
    ])
    chip1 = Device("/device:TPU:1", [], [
        Event("jit_decode_step_paged(11)", 0.0, 0.110),
        Event("jit_decode_step_paged(11)", 1.0, 1.104),
    ])
    value = reader("decode_program_ms_p50").read(a_run([chip0, chip1], []))
    assert value == pytest.approx(107.0)


@pytest.mark.parametrize("name", PROGRAM_SPAN + DEVICE_TRACE)
def test_reader_finds_nothing_in_a_program_without_spans(name):
    """The parent commit's program: step records without ``phase_ms``, a
    trace with the harness's spans alone and programs called
    ``jit__unknown``. Every reader returns None and none raises."""
    device = Device("/device:TPU:0", [Event("%fusion.1", 1.0, 3.0)],
                    [Event("jit__unknown(5)", 1.0, 3.0)])
    host = [Event("bench.engine_step", 0.0, 5.0, (("i", 0),))]
    steps = [{"duration_ms": 170.0, "decode_tokens": 32, "prefill_tokens": 0}]
    assert reader(name).read(a_run([device], host, steps)) is None
    assert reader(name).read(
        types.SimpleNamespace(trace=None, slice=None, steps=[])
    ) is None
