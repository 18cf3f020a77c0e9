"""The reduction from a profiler trace to numbers (benchmarks/lib/xplane.py).

Two kinds of check. The interval arithmetic is checked on made-up intervals
small enough to do by hand. The reading of a real trace is checked on
``benchmarks/fixtures/v5e_mistral7b_docqa_steps_32_35.xplane.pb``: four
engine steps cut out of a trace this PR recorded on a TPU v5e (three that
only decode, one that also admits a 512-token prompt), against numbers that
were worked out from the same file by other means (a sweep over sorted
endpoints; ``benchmarks/fixtures/README.md``).
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

from benchmarks.lib import driver, xplane
from benchmarks.lib.xplane import Device, Event, Trace

FIXTURES = Path(__file__).resolve().parents[2] / "benchmarks" / "fixtures"
RECORDED = FIXTURES / "v5e_mistral7b_docqa_steps_32_35.xplane.pb"
EXPECTED = json.loads(
    (FIXTURES / "v5e_mistral7b_docqa_steps_32_35.expected.json").read_text()
)


def ev(name: str, start: float, end: float, **stats) -> Event:
    return Event(name, start, end, tuple(stats.items()))


# ------------------------------------------------------------- by hand


def test_union_merges_overlap_nesting_and_touching():
    assert xplane.union([(5, 6), (0, 2), (1, 3), (3, 4), (0.5, 1)]) == [
        (0, 4), (5, 6)
    ]
    assert xplane.union([(1, 1), (2, 1)]) == []  # empty and inverted: nothing
    assert xplane.measure([(0, 4), (5, 6)]) == 5


def test_clip_gaps_and_intersect():
    merged = [(0, 4), (5, 6), (8, 9)]
    assert xplane.clip(merged, 3, 8.5) == [(3, 4), (5, 6), (8, 8.5)]
    assert xplane.gaps(merged, 3, 10) == [(4, 5), (6, 8), (9, 10)]
    assert xplane.gaps(merged, 1, 2) == []
    assert xplane.gaps([], 1, 2) == [(1, 2)]
    assert xplane.intersect([(0, 4), (5, 9)], [(3, 6), (8, 12)]) == [
        (3, 4), (5, 6), (8, 9)
    ]


def test_self_seconds_takes_the_children_out():
    # while [0,10] contains a [1,4] and b [4,6]; a contains c [2,3]; d apart
    events = sorted(
        [ev("while", 0, 10), ev("a", 1, 4), ev("c", 2, 3), ev("b", 4, 6),
         ev("d", 12, 13)],
        key=lambda e: (e.start, -e.end),
    )
    assert xplane.self_seconds(events, 0, 20) == {
        "while": 5, "a": 2, "c": 1, "b": 2, "d": 1
    }
    # clipped to [2.5, 5]: c 0.5, a 1.5 less c's 0.5, b 1, while 2.5 less 2.5
    clipped = xplane.self_seconds(events, 2.5, 5)
    assert clipped == {"c": 0.5, "a": 1.0, "b": 1.0, "while": 0.0}
    assert [e.name for e in xplane.leaves(events)] == ["c", "b", "d"]


def test_exposed_collective_time_is_what_no_compute_covers():
    ops = sorted([
        ev("%while.1 = (...)", 0, 10),
        ev("%fusion.1 = f32[8] fusion(...)", 0, 3),
        ev("%all-reduce.1 = f32[8] all-reduce(...)", 3, 5),  # nothing beside it
        ev("%fusion.2 = f32[8] fusion(...)", 5, 8),
        ev("%all-gather-done.2 = f32[8] all-gather-done(...)", 8, 9),
    ], key=lambda e: (e.start, -e.end))
    # the while contains everything and is no leaf, so it hides nothing
    assert xplane.exposed_collective_seconds(Device("d", ops, []), 0, 10) == 3
    assert xplane.exposed_collective_seconds(Device("d", ops, []), 4, 8.5) == 1.5
    assert xplane.COLLECTIVE.search("%fusion.3 = f32[2] fusion(%all-reduce.1)") is None


def test_idle_gaps_are_named_by_the_host_event_under_the_step_span():
    ops = [ev("op", 1, 2), ev("op", 4, 5), ev("op", 11, 12)]
    host = sorted([
        ev(driver.SPAN_STEP, 0, 8, i=7),
        ev("PjitFunction(decode_step_paged)", 0.2, 0.9),
        ev("np.asarray(jax.Array)", 2.5, 3.5),
        ev(driver.SPAN_POLL, 8, 10),
        ev(driver.SPAN_STEP, 10.5, 13, i=8),
    ], key=lambda e: (e.start, -e.end))
    trace = Trace(
        [Device("d", ops, [])],
        {driver.SPAN_STEP: [host[0], host[4]], driver.SPAN_POLL: [host[3]]},
        host,
    )
    rows = xplane.idle_attribution(trace, trace.devices[0], 0, 13, driver.SPAN_STEP)
    assert [(r["name"], r["seconds"]) for r in rows] == [
        ("bench.engine_step (before first op) > PjitFunction(decode_step_paged)", 1),
        ("bench.engine_step (between ops) > np.asarray(jax.Array)", 2),
        # the gap [5, 11] is cut where the harness's spans begin and end
        ("bench.engine_step (after last op)", 3),
        ("bench.poll", 2),
        ("no host event", 0.5),
        ("bench.engine_step (before first op)", 0.5),
        ("bench.engine_step (after last op)", 1),
    ]
    assert xplane.step_spans(trace, driver.SPAN_STEP)[8].start == 10.5
    assert xplane.step_busy_seconds(trace, driver.SPAN_STEP, [7, 8, 9]) == {
        7: 2, 8: 1
    }
    out = xplane.breakdown(trace, 0, 13, driver.SPAN_STEP)
    assert out["device_ops"] == [["op", 3]]
    assert out["idle_gaps"][0] == ["bench.engine_step (after last op)", 4]


def test_short_name_keeps_the_instruction_and_its_shape():
    text = (
        "%copy.74 = bf16[32,8,288,16,128]{4,3,2,1,0:T(8,128)(2,1)} "
        "copy(bf16[32,8,288,16,128]{4,1,3,2,0:T(8,128)(2,1)} %bitcast.199)"
    )
    assert xplane.short_name(text) == "copy.74 bf16[32,8,288,16,128]"
    kernel = (
        "%closed_call.11 = (bf16[8,4,512,128]{3,2,1,0:T(8,128)(2,1)S(1)}, "
        "f32[8,4,512,1]{3,2,1,0:T(8,128)}) custom-call(bf16[8,4,512,128]{3,2,1,0} "
        '%x), custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    )
    assert xplane.short_name(kernel) == "closed_call.11 bf16[8,4,512,128] [pallas]"
    assert xplane.short_name("ThunkExecutor::Execute") == "ThunkExecutor::Execute"


# ------------------------------------------------- the recorded v5e trace


@pytest.fixture(scope="module")
def recorded() -> Trace:
    return xplane.load(RECORDED, "tpu")


def test_recorded_trace_has_one_device_plane_and_the_harness_spans(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    device = recorded.devices[0]
    assert len(device.ops) == EXPECTED["n_ops"]
    assert len(device.modules) == EXPECTED["n_modules"]
    spans = xplane.step_spans(recorded, driver.SPAN_STEP)
    assert sorted(spans) == [32, 33, 34, 35]
    for i, span in spans.items():
        assert span.seconds == pytest.approx(EXPECTED["step_span_s"][str(i)], abs=1e-9)


def test_recorded_busy_union_and_idle_gaps(recorded):
    lo, hi = EXPECTED["slice"]
    assert xplane.busy_seconds(recorded, lo, hi) == [
        pytest.approx(EXPECTED["busy_s"], abs=1e-9)
    ]
    idle = xplane.gaps(recorded.devices[0].busy, lo, hi)
    assert len(idle) == EXPECTED["n_gaps"]
    assert xplane.measure(idle) == pytest.approx(EXPECTED["idle_s"], abs=1e-9)
    longest = sorted(((b - a, a) for a, b in idle), reverse=True)[:4]
    for (seconds, start), (want_s, want_start) in zip(longest, EXPECTED["longest_gaps"]):
        assert seconds == pytest.approx(want_s, abs=1e-9)
        assert start == pytest.approx(want_start, abs=1e-9)


def test_recorded_device_time_per_step(recorded):
    got = xplane.step_busy_seconds(recorded, driver.SPAN_STEP, [32, 33, 34, 35])
    for i, seconds in got.items():
        assert seconds == pytest.approx(EXPECTED["step_busy_s"][str(i)], abs=1e-9)
    # three steps only decode (101 ms of device work each); step 33 also
    # admits a 512-token prompt: its prefill and pool copies add 33 ms
    assert got[33] - got[32] == pytest.approx(0.0330, abs=5e-4)


def test_recorded_programs_by_name(recorded):
    totals: dict[str, float] = {}
    for m in recorded.devices[0].modules:
        totals[m.name] = totals.get(m.name, 0.0) + m.seconds
    assert totals == pytest.approx(EXPECTED["module_s"], abs=1e-9)
    # the batcher jits functools.partial objects: the decode program and the
    # prefill are both called jit__unknown, told apart only by fingerprint
    assert sum(1 for name in totals if name.startswith("jit__unknown(")) == 2


def test_recorded_flash_kernel_is_found_by_what_it_returns(recorded):
    from benchmarks.layer_metrics.flash_fwd_roofline import FLASH_FWD

    lo, hi = EXPECTED["slice"]
    device = recorded.devices[0]
    calls = [e for e in device.ops if FLASH_FWD.search(e.name)]
    assert len(calls) == EXPECTED["flash_fwd_calls"] == 16  # one a layer
    assert xplane.matching_seconds(device, FLASH_FWD, lo, hi) == pytest.approx(
        EXPECTED["flash_fwd_s"], abs=1e-9
    )


def test_recorded_self_time_of_a_container_and_of_a_leaf(recorded):
    lo, hi = EXPECTED["slice"]
    own = xplane.self_seconds(recorded.devices[0].ops, lo, hi)
    by_short: dict[str, float] = {}
    for name, seconds in own.items():
        key = xplane.short_name(name)
        by_short[key] = by_short.get(key, 0.0) + seconds
    # the layer scan's while spends its time in what it contains
    assert by_short["while.2 s32[]"] == pytest.approx(EXPECTED["while2_self_s"], abs=1e-9)
    # a leaf's own time is its duration: the gather's transposing copy,
    # 64 times (4 decode steps x 16 layers)
    assert by_short["copy.74 bf16[32,8,288,16,128]"] == pytest.approx(
        EXPECTED["copy74_s"], abs=1e-9
    )
    assert not any(xplane.COLLECTIVE.search(e.name) for e in recorded.devices[0].ops)


def test_recorded_breakdown_names_the_gather_and_the_host(recorded):
    lo, hi = EXPECTED["slice"]
    out = xplane.breakdown(recorded, lo, hi, driver.SPAN_STEP)
    assert len(out["device_ops"]) == 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"][0][0] == "copy.74 bf16[32,8,288,16,128]"
    name, seconds = out["idle_gaps"][0]
    assert name == "bench.engine_step (after last op)"
    assert re.fullmatch(r"bench\.engine_step \(.*", name) and seconds > 0.1
    assert sum(s for _, s in out["idle_gaps"]) <= EXPECTED["idle_s"] + 1e-9
