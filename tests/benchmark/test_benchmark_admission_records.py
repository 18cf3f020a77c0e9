"""The readers of the admission's record and of the prefill program.

While a ``ServingMonitor`` is attached, a blocking admission is one record
(``ContinuousBatcher._admit_observed``) that rides on the next step record
under ``admissions``; three per-layer readers read it over the whole
window, a fourth reads the step records for steps that stalled, and a fifth
pairs the prefill program's ``XLA Modules`` events with the
``serve.admit.prefill`` spans' ``padded_tokens``. A tiny cell of
``test_benchmark_harness.py`` is run traced once more here, with one reader
added that hands the ``RunData`` over, so that the records are looked at
where the readers find them and held against the slice's ``serve.admit``
spans; then each reader is held to hand-made records and a hand-made trace
whose answer is worked out beside it. Nothing timed here is a device number.
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

from benchmarks.lib.xplane import Device, Event, Trace

RECORD = (
    "admit_added_ms_per_ktok", "admit_activate_ms_p50", "admit_queue_ms_p50",
    "step_stall_ms_per_s",
)
PROGRAM = "prefill_program_ms_per_ktok"
ADMIT_TOP = (
    "prefill", "seed_window", "seed_pool", "seed_state", "land", "pull",
    "activate",
)
SPY = (
    'LAYER = "scheduler"\nUNIT = "count"\nMOVES = "out_tok_s"\n'
    'SOURCE = "harness"\nRUNS = []\n\n\n'
    "def read(run):\n    RUNS.append(run)\n    return None\n"
)


def reader(name: str):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """``tiny_chat`` run once with ``--trace 1``: the result and the
    ``RunData`` its readers were given."""
    from benchmarks.lib import harness

    root = tmp_path_factory.mktemp("bench_root")
    write_root(root, [TINY_DENSE, tiny_moe()], CELLS)
    (root / "benchmarks/layer_metrics/records_spy.py").write_text(SPY)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "records_spy", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "scheduler", "moves": "out_tok_s",
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    result = harness.run_cell(
        root, "tiny_chat", 37, 2.0, True, platform="cpu",
        log=lambda _: None, clock=ticking_clock(),
    )
    (run,) = sys.modules["_bench_layer_metrics_records_spy"].RUNS
    return result, run


def admissions(run) -> list[dict]:
    return [a for s in run.steps for a in s.get("admissions", ())]


@pytest.mark.parametrize("name", RECORD)
def test_record_readers_read_the_whole_window(traced, name):
    result, run = traced
    value = reader(name).read(run)
    assert isinstance(value, float) and value >= 0.0
    assert result["metrics"][name] == {"value": value, "unit": "ms"}
    records = admissions(run)
    # the window admits more than its traced slice does
    lo, hi = run.slice
    in_slice = [
        e for e in run.trace.host
        if e.name == "serve.admit" and e.start >= lo and e.end <= hi
    ]
    assert len(records) > len(in_slice) > 0
    if name == "admit_added_ms_per_ktok":
        tokens = sum(a["prompt_tokens"] for a in records)
        assert value == pytest.approx(sum(
            a["duration_ms"] - a["phase_ms"].get("land", 0.0) for a in records
        ) / tokens * 1000.0)
    if name == "admit_queue_ms_p50":
        assert all(a["queued_ms"] >= 0.0 for a in records)


def test_every_admission_span_of_the_slice_pairs_with_its_record(traced):
    _, run = traced
    by_req = {a["req"]: a for a in admissions(run)}
    lo, hi = run.slice
    spans = [
        e for e in run.trace.host
        if e.name == "serve.admit" and e.start >= lo and e.end <= hi
    ]
    assert spans
    for span in spans:
        stats = dict(span.stats)
        record = by_req[int(stats["req"])]
        assert record["prompt_tokens"] == int(stats["prompt_tokens"])
        assert record["pages"] == int(stats["pages"])
        # one clock read apart at either end (a loaded machine can put a
        # millisecond between the two)
        length = 1000.0 * span.seconds
        assert record["duration_ms"] == pytest.approx(
            length, abs=max(2.0, 0.05 * length)
        )
    for record in by_req.values():
        # every cell admits through the one-shot program: page-padded, no
        # windows, and the top-level phases leave next to nothing
        assert record["windows"] == 0
        assert record["padded_tokens"] == -(-record["prompt_tokens"] // 16) * 16
        phases = record["phase_ms"]
        # (``gc``: a full collection that fell inside it, beside the phases)
        assert [
            k for k in phases if not k.startswith("land_") and k != "gc"
        ] == [k for k in ADMIT_TOP if k in phases]
        assert {"prefill", "seed_pool", "pull", "activate"} <= set(phases)
        assert record["landed_step"] == ("land" in phases)
        left = record["duration_ms"] - sum(phases.get(k, 0.0) for k in ADMIT_TOP)
        assert 0.0 <= left < 0.25 * record["duration_ms"]
        assert 0 <= record["decoding_rows"] <= run.pool["max_batch"]


def test_the_prefill_spans_carry_their_padded_width_and_the_cpu_no_program(traced):
    result, run = traced
    prefills = [e for e in run.trace.host if e.name == "serve.admit.prefill"]
    assert prefills
    assert all(int(dict(e.stats)["padded_tokens"]) % 16 == 0 for e in prefills)
    # no ``XLA Modules`` line off the chip: nothing to pair, nothing printed
    assert reader(PROGRAM).read(run) is None
    assert PROGRAM not in result["metrics"]


def test_the_benchmark_gained_five_entries_and_lost_none():
    """What ``test_benchmark_kexaone.py``'s case of the same name held before
    PR 37 appended to ``per_layer`` (``tests/conftest.py`` ``RETIRED_CASES``),
    with the five at the end, in every cell."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [c["name"] for c in bench["configs"]][-1] == "k-exaone-236b-a23b"
    assert [w["name"] for w in bench["workloads"]][-1] == "kexaone_reason48"
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-8:] == [
        "swa_ring_roofline", "full_layers_decode_roofline",
        "admit_window_ms_p50", *RECORD, PROGRAM,
    ]
    assert (len(bench["configs"]), len(bench["workloads"]), len(names)) == (
        6, 7, 32
    )
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert not any("workloads" in m for m in bench["per_layer"][-5:])


def a_run(devices, host, steps=(), slice_=(0.0, 10.0), window=(0.0, 10.0)):
    return types.SimpleNamespace(
        trace=Trace(devices, {}, sorted(host, key=lambda e: (e.start, -e.end))),
        slice=slice_, steps=list(steps), window=window,
    )


def a_step(ms: float, admissions=None, prefill_tokens: int = 0) -> dict:
    step = {"duration_ms": ms, "decode_tokens": 32,
            "prefill_tokens": prefill_tokens}
    if admissions is not None:
        step["admissions"] = admissions
    return step


def test_record_readers_on_records_worked_out_by_hand():
    """Three admissions on two step records. 1,000 tokens in 60 ms of which
    10 landed the step in flight; 500 tokens in 30 ms with no step to land;
    500 tokens in 45 ms of which 5 landed one: (50 + 30 + 40) ms over 2,000
    tokens is 60 ms a thousand. Their ``activate`` 2, 4 and 9 ms: median 4.
    Two waited in a queue, 0.2 and 30 ms: median 15.1 (the third was
    admitted without an ``Engine``). Decode steps of 10, 10, 10, 12 and
    130 ms (and one of 500 ms that prefilled, left out): the median is 10,
    one step lies above 30 and lost 120 ms, over a window of 10 s: 12 ms a
    second."""
    one = {"prompt_tokens": 1000, "duration_ms": 60.0, "queued_ms": 0.2,
           "phase_ms": {"prefill": 1.0, "land": 10.0, "pull": 40.0,
                        "activate": 2.0}}
    two = {"prompt_tokens": 500, "duration_ms": 30.0, "queued_ms": 30.0,
           "phase_ms": {"prefill": 1.0, "pull": 20.0, "activate": 4.0}}
    three = {"prompt_tokens": 500, "duration_ms": 45.0,
             "phase_ms": {"prefill": 1.0, "land": 5.0, "activate": 9.0}}
    run = a_run([], [], [
        a_step(10.0, [one, two]), a_step(10.0), a_step(10.0, [three]),
        a_step(12.0), a_step(130.0), a_step(500.0, prefill_tokens=16),
    ])
    assert reader("admit_added_ms_per_ktok").read(run) == pytest.approx(60.0)
    assert reader("admit_activate_ms_p50").read(run) == pytest.approx(4.0)
    assert reader("admit_queue_ms_p50").read(run) == pytest.approx(15.1)
    assert reader("step_stall_ms_per_s").read(run) == pytest.approx(12.0)


def prefill_span(start: float, padded: int | None) -> Event:
    stats = () if padded is None else (("padded_tokens", padded),)
    return Event("serve.admit.prefill", start, start + 0.001, stats)


def test_prefill_program_time_pairs_events_with_spans_in_dispatch_order():
    """Slice 0..10 s. Three admissions of 256, 1,024 and 256 padded tokens;
    chip 0 runs their programs for 12, 40 and 12 ms, chip 1 for 12, 44 and
    12: 68 ms over 1,536 tokens on the slowest chip is 44.2708 ms a
    thousand. A decode program between them does not count, and a fourth
    admission after the slice is left out with its event."""
    def chip(name, ms):
        return Device(name, [], [
            Event("jit_prefill_forward(7)", 1.0, 1.0 + ms[0] / 1e3),
            Event("jit_decode_step_paged(3)", 2.0, 2.013),
            Event("jit_prefill_forward(9)", 3.0, 3.0 + ms[1] / 1e3),
            Event("jit_prefill_forward(7)", 5.0, 5.0 + ms[2] / 1e3),
            Event("jit_prefill_forward(7)", 10.5, 10.512),
        ])

    chips = [chip("/device:TPU:0", (12, 40, 12)), chip("/device:TPU:1", (12, 44, 12))]
    host = [
        prefill_span(0.9, 256), prefill_span(2.9, 1024), prefill_span(4.9, 256),
        prefill_span(10.4, 256),
    ]
    module = reader(PROGRAM)
    run = a_run(chips, host)
    assert module.read(run) == pytest.approx(68.0 / 1.536)
    assert module.pairs(run)["/device:TPU:1"] == [
        (256, pytest.approx(12.0)), (1024, pytest.approx(44.0)),
        (256, pytest.approx(12.0)),
    ]
    # a span with no event (or an event with no span): nothing to pair by
    assert module.read(a_run(chips, host[1:])) is None
    assert module.read(a_run(chips, [*host, prefill_span(7.0, 512)])) is None
    # a chip without the program's events (the CPU's stand-in) is left out
    stand_in = Device("cpu-stand-in", [Event("%fusion.1", 1.0, 3.0)], [])
    assert module.read(a_run([stand_in, chips[0]], host)) == pytest.approx(
        64.0 / 1.536
    )


@pytest.mark.parametrize("name", (*RECORD[:3], PROGRAM))
def test_reader_finds_nothing_in_a_program_without_the_record(name):
    """The parent commit's program: step records without ``admissions``,
    ``serve.admit.prefill`` spans without ``padded_tokens``. The readers
    return None and none raises; ``step_stall_ms_per_s`` reads fields the
    step record has had since PR 24, and finds nothing only in a window
    without a decode step."""
    device = Device("/device:TPU:0", [Event("%fusion.1", 1.0, 3.0)],
                    [Event("jit_prefill_forward(5)", 1.0, 3.0)])
    host = [
        Event("serve.admit", 0.5, 4.0,
              (("req", 3), ("prompt_tokens", 2000), ("pages", 130))),
        prefill_span(0.6, None),
    ]
    assert reader(name).read(a_run([device], host, [a_step(170.0)])) is None
    assert reader(name).read(types.SimpleNamespace(
        trace=None, slice=None, steps=[], window=(0.0, 1.0),
    )) is None


def test_stall_reader_finds_nothing_in_a_window_without_a_decode_step():
    assert reader("step_stall_ms_per_s").read(
        a_run([], [], [a_step(500.0, prefill_tokens=16)])
    ) is None
    assert reader("step_stall_ms_per_s").read(a_run([], [], [a_step(9.0)])) == 0.0
