"""Share of the traced slice in which a collective runs on a chip and
nothing else does, on the chip where that is longest."""

from benchmarks.lib import xplane

LAYER = "mesh"
UNIT = "%"
MOVES = "itl_ms_p50"
SOURCE = "trace"


def read(run):
    if run.trace is None or run.slice is None or run.chips < 2:
        return None
    lo, hi = run.slice
    exposed = max(
        xplane.exposed_collective_seconds(d, lo, hi) for d in run.trace.devices
    )
    return 100.0 * exposed / (hi - lo)
