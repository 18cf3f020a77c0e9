"""From a profiler trace (``.xplane.pb``) to numbers.

``jax.profiler.ProfileData`` reads the file with nothing but jax: planes,
their lines, and events with a start and a duration in nanoseconds. What a
TPU v5e trace of this repository looks like (looked at by hand, PR 22;
PERF.md section 3 has the listing):

- one plane per chip, ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one
  event per executed HLO instruction, nested by time where an instruction
  contains others (a ``while`` around a layer scan contains the layer's
  instructions); its line ``XLA Modules`` holds one event per executed
  program, named ``jit_<function>(<fingerprint>)``. The batcher jits
  ``functools.partial`` objects, so its decode, prefill and window programs
  are all called ``jit__unknown``: nothing here tells programs apart by
  name. What belongs to a decode step or to a prefill is decided by the
  harness's own spans instead;
- ``/host:CPU`` holds the host threads. The harness's
  ``jax.profiler.TraceAnnotation`` spans (``bench.*``) are events on the
  main thread's line, on the same clock as the device planes.

Busy time is the measure of the union of the instruction intervals, so
nesting and overlap count once. All times here are seconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# On the CPU backend (tests only) there is no device plane: the threads that
# execute XLA's programs stand in, so that the plumbing can be exercised.
# Nothing read from them is ever called a device number.
CPU_STAND_IN_LINES = ("tf_XLAEigen", "tf_XLAPjRtCpuClient")

# Starts and ends are nanosecond counts turned into float seconds: an event
# that ends where the next begins may come out a rounding error later. Half a
# nanosecond decides "contains" from "is followed by".
EPS = 5e-10

# HLO opcodes of collectives, with the -start/-done halves of async ones
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)"
)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float
    stats: tuple = ()  # ((key, value), ...) — kept for named spans only

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    ops: list  # [Event], sorted by start
    modules: list  # [Event], sorted by start

    @functools.cached_property
    def busy(self) -> list[tuple[float, float]]:
        """Where an instruction ran: the union of the instruction intervals
        (made once: every reader asks for it)."""
        return union((e.start, e.end) for e in self.ops)


@dataclasses.dataclass
class Trace:
    devices: list  # [Device]
    spans: dict  # name -> [Event] (host events named ``bench.*``)
    host: list  # [Event] of the thread that carries the spans, sorted


# ------------------------------------------------------------------ reading


def find_xplane(trace_dir: str | Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _events(line, keep_stats: bool = False) -> list[Event]:
    out = []
    for e in line.events:
        start = float(e.start_ns) * 1e-9
        stats = tuple((k, v) for k, v in e.stats) if keep_stats else ()
        out.append(
            Event(e.name, start, start + float(e.duration_ns) * 1e-9, stats)
        )
    out.sort(key=lambda ev: (ev.start, -ev.end))
    return out


def load(path: str | Path, platform: str = "tpu",
         span_prefix: str = "bench.") -> Trace:
    """Read one ``.xplane.pb``. ``platform`` says which planes are devices:
    ``tpu`` takes ``/device:TPU:<n>``; anything else takes the CPU
    backend's executor threads as one stand-in device (tests)."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    devices: list[Device] = []
    spans: dict[str, list[Event]] = defaultdict(list)
    host: list[Event] = []
    stand_in: list[Event] = []
    for plane in data.planes:
        if platform == "tpu" and plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue  # a plane of the chip without instructions
            devices.append(Device(
                plane.name,
                _events(lines[OPS_LINE]),
                _events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
            ))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if platform != "tpu" and line.name.startswith(
                    CPU_STAND_IN_LINES
                ):
                    stand_in.extend(_events(line))
                    continue
                events = _events(line, keep_stats=True)
                named = [e for e in events if e.name.startswith(span_prefix)]
                if named:
                    for e in named:
                        spans[e.name].append(e)
                    host = events
    if platform != "tpu" and stand_in:
        stand_in.sort(key=lambda ev: (ev.start, -ev.end))
        devices.append(Device("cpu-stand-in", stand_in, []))
    devices.sort(key=lambda d: d.name)
    return Trace(devices, dict(spans), host)


def layout(path: str | Path, top: int = 8) -> dict:
    """Planes, lines, event counts and the names that took most time: what
    to look at by hand before trusting a reduction."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            totals: dict[str, float] = defaultdict(float)
            n = 0
            sample = None
            for e in line.events:
                totals[e.name] += float(e.duration_ns) * 1e-9
                n += 1
                if sample is None:
                    sample = {str(k): str(v)[:80] for k, v in e.stats}
            names = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
            lines[line.name] = {
                "events": n, "top": names, "sample_stats": sample,
            }
        out[plane.name] = lines
    return out


# ---------------------------------------------------------------- intervals


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def measure(merged) -> float:
    return sum(hi - lo for lo, hi in merged)


def clip(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    return [
        (max(a, lo), min(b, hi)) for a, b in merged if b > lo and a < hi
    ]


def intersect(a, b) -> list[tuple[float, float]]:
    """Of two sorted disjoint lists."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """Where, inside [lo, hi], ``merged`` covers nothing."""
    out = []
    cursor = lo
    for a, b in clip(merged, lo, hi):
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < hi:
        out.append((cursor, hi))
    return out


def leaves(events: list[Event]) -> list[Event]:
    """Events that contain no other event of the same line (an instruction
    such as ``while`` or ``conditional`` contains the ones it runs)."""
    out = []
    for i, e in enumerate(events):  # sorted by (start, -end)
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt.start >= e.end - EPS:
            out.append(e)
    return out


def self_seconds(events: list[Event], lo: float, hi: float) -> dict:
    """Per name, the time inside [lo, hi] spent in an event itself and not
    in one it contains."""
    totals: dict[str, float] = defaultdict(float)
    stack: list[list] = []  # [event, covered-by-children seconds]

    def close(until: float) -> None:
        while stack and stack[-1][0].end <= until + EPS:
            event, covered = stack.pop()
            span = max(0.0, min(event.end, hi) - max(event.start, lo))
            totals[event.name] += max(0.0, span - covered)
            if stack:
                stack[-1][1] += span

    for e in events:
        if e.end <= lo or e.start >= hi:
            continue
        close(e.start)
        stack.append([e, 0.0])
    close(float("inf"))
    return dict(totals)


# --------------------------------------------------------------- reductions


def busy_seconds(trace: Trace, lo: float, hi: float) -> list[float]:
    """Per device, the seconds inside [lo, hi] in which an instruction ran."""
    return [measure(clip(d.busy, lo, hi)) for d in trace.devices]


def step_spans(trace: Trace, name: str) -> dict[int, Event]:
    """The harness's per-step spans by their step index (stat ``i``)."""
    out = {}
    for e in trace.spans.get(name, []):
        stats = dict(e.stats)
        if "i" in stats:
            out[int(stats["i"])] = e
    return out


def step_busy_seconds(trace: Trace, name: str, indices) -> dict[int, float]:
    """Per step index, the seconds inside that step's span in which an
    instruction ran on the device, mean over devices. Every step of the
    engine ends in a sync, so what a step dispatched has run by its end."""
    spans = step_spans(trace, name)
    merged = [d.busy for d in trace.devices]
    out = {}
    for i in indices:
        if i in spans and merged:
            lo, hi = spans[i].start, spans[i].end
            out[i] = sum(measure(clip(m, lo, hi)) for m in merged) / len(merged)
    return out


def matching_seconds(device: Device, pattern, lo: float, hi: float) -> float:
    """Summed duration of the device's leaf instructions whose name matches
    ``pattern``, inside [lo, hi]."""
    return sum(
        min(e.end, hi) - max(e.start, lo)
        for e in leaves(device.ops)
        if pattern.search(e.name) and e.end > lo and e.start < hi
    )


def exposed_collective_seconds(device: Device, lo: float, hi: float) -> float:
    """Time inside [lo, hi] in which a collective instruction runs on this
    device and no other instruction does."""
    collective, compute = [], []
    for e in leaves(device.ops):
        (collective if COLLECTIVE.search(e.name) else compute).append(
            (e.start, e.end)
        )
    collective = clip(union(collective), lo, hi)
    hidden = intersect(collective, clip(union(compute), lo, hi))
    return measure(collective) - measure(hidden)


def innermost(host: list[Event], starts: list[float], t: float):
    """The host event containing ``t`` that started last (events of one
    thread nest, so that is the innermost)."""
    i = bisect.bisect_right(starts, t) - 1
    scanned = 0
    while i >= 0 and scanned < 4096:
        if host[i].end >= t:
            return host[i]
        i -= 1
        scanned += 1
    return None


def idle_attribution(trace: Trace, device: Device, lo: float, hi: float,
                     step_span: str) -> list[dict]:
    """Every idle gap of ``device`` inside [lo, hi], cut where a harness
    span begins or ends, each piece labelled by what the host was doing at
    its midpoint: the innermost host event there, under the harness span it
    falls in. Inside a step span the label also says whether the piece lies
    before the step's first device instruction, after its last, or between
    two."""
    merged = device.busy
    starts = [e.start for e in trace.host]
    steps = sorted(trace.spans.get(step_span, []), key=lambda e: e.start)
    step_starts = [e.start for e in steps]
    cuts = sorted({
        t for events in trace.spans.values() for e in events
        for t in (e.start, e.end)
    })
    rows = []
    for gap_lo, gap_hi in gaps(merged, lo, hi):
        inner = cuts[bisect.bisect_right(cuts, gap_lo):bisect.bisect_left(cuts, gap_hi)]
        edges = [gap_lo, *inner, gap_hi]
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            event = innermost(trace.host, starts, mid)
            label = event.name if event is not None else "no host event"
            j = bisect.bisect_right(step_starts, mid) - 1
            if j >= 0 and steps[j].end >= mid:
                inside = clip(merged, steps[j].start, steps[j].end)
                if not inside or b <= inside[0][0]:
                    where = "before first op"
                elif a >= inside[-1][1]:
                    where = "after last op"
                else:
                    where = "between ops"
                label = (
                    f"{step_span} ({where})" if label == step_span
                    else f"{step_span} ({where}) > {label}"
                )
            rows.append({"name": label, "start": a, "seconds": b - a})
    return rows


_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])")


def short_name(name: str) -> str:
    """A device instruction's event is named by its whole HLO text: keep
    the instruction's name and the (first) shape it produces, and say
    where it is a Pallas kernel."""
    m = _HLO.match(name)
    if m is None:
        return name[:96]
    kernel = " [pallas]" if 'custom_call_target="tpu_custom_call"' in name else ""
    return f"{m.group(1)} {m.group(2)}{kernel}"


def breakdown(trace: Trace, lo: float, hi: float, step_span: str,
              top: int = 10) -> dict:
    """The contract's ``breakdown``: the device instructions that took most
    time (their own, not their children's; mean over chips) and the idle
    time by what the host was doing (on the chip that idled most)."""
    n = max(1, len(trace.devices))
    ops: dict[str, float] = defaultdict(float)
    for device in trace.devices:
        for name, seconds in self_seconds(device.ops, lo, hi).items():
            ops[short_name(name)] += seconds / n
    idlest = min(
        trace.devices, key=lambda d: measure(clip(d.busy, lo, hi)),
        default=None,
    )
    idle: dict[str, float] = defaultdict(float)
    if idlest is not None:
        for row in idle_attribution(trace, idlest, lo, hi, step_span):
            idle[row["name"]] += row["seconds"]

    def ranked(totals):
        return [
            [name, seconds] for name, seconds in
            sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        ]

    return {"device_ops": ranked(ops), "idle_gaps": ranked(idle)}
