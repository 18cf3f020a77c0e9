"""Share of the traced slice in which no instruction ran on the device, on
the chip that idled most. With fewer layers than the published model the
host's part of a step weighs more than in a deployment: read it beside the
configuration's ``reduced``."""

from benchmarks.lib import xplane

LAYER = "device"
UNIT = "%"
MOVES = "out_tok_s"
SOURCE = "trace"


def read(run):
    if run.trace is None or run.slice is None:
        return None
    lo, hi = run.slice
    return 100.0 * (1.0 - min(xplane.busy_seconds(run.trace, lo, hi)) / (hi - lo))
