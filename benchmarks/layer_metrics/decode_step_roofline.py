"""The decode step's share of its roofline. The least time a step could
take is the bytes it must move (every weight once and the K/V of every
live token once, per chip; ``opcount.decode_step_min_bytes``) over the
chip's HBM bandwidth: decode is bound by HBM, not by the MXU. Over the
median device time of a decode step from the trace."""

import statistics

from benchmarks.lib import driver, opcount

LAYER = "kernels"
UNIT = "%"
MOVES = "itl_ms_p50"
SOURCE = "trace"
BOUND = "HBM bandwidth"


def read(run):
    steps = run.decode_only_steps
    if not steps or run.peaks is None:
        return None
    live = statistics.mean(driver.live_tokens(run.flights, s.t_start) for s in steps)
    least_s = (
        opcount.decode_step_min_bytes(run.dims, int(live)) / run.chips
        / run.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / statistics.median(run.step_busy[s.index] for s in steps)
