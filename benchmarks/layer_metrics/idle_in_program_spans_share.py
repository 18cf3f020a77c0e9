"""How much of the device's idle time the program's own spans explain: of
the seconds of the traced slice in which no instruction ran, on the chip
that idled most, the share that lies inside a ``serve.*`` span holding no
other ``serve.*`` span (``serve.step.sample``, ``serve.admit.pull``, ...;
not what a ``serve.step`` or ``serve.admit`` leaves uncovered). Idle
intervals and spans are intersected exactly, not labelled by a midpoint.
``None`` where the trace holds no ``serve.*`` span."""

from benchmarks.lib import xplane

LAYER = "scheduler"
UNIT = "%"
MOVES = "out_tok_s"
SOURCE = "trace"


def read(run):
    if run.trace is None or run.slice is None or not run.trace.devices:
        return None
    lo, hi = run.slice
    spans = [e for e in run.trace.host if e.name.startswith("serve.")]
    idlest = min(
        run.trace.devices,
        key=lambda d: xplane.measure(xplane.clip(d.busy, lo, hi)),
    )
    idle = xplane.gaps(idlest.busy, lo, hi)
    if not spans or not idle:
        return None
    innermost = xplane.union((e.start, e.end) for e in xplane.leaves(spans))
    explained = xplane.measure(xplane.intersect(idle, innermost))
    return 100.0 * explained / xplane.measure(idle)
