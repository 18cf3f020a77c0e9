"""The recurrent state's share of its roofline in a decode step. A decode
step over mamba layers must read every decoding row's state once and write
it once (``run.counts.ssm_bytes_per_row`` in every mamba layer, as the pool
keeps it): the least time that takes is those bytes over the chip's HBM
bandwidth. Over the median, across the
traced decode-only steps, of the time of the device instructions that touch
the state: a leaf instruction inside the step whose HLO text has a bf16 or
float32 operand or result (as stored, or as the step computes on it) with
as many elements as one layer's state of the pool's rows, or as all layers'
(as ``moe_flops_over_routed`` finds expert matmuls by shape; counted by
elements, so that a reshape of the leaf does not hide it). ``None`` where
the configuration keeps no such state or no instruction is shaped so."""

import statistics

from benchmarks.lib import driver, xplane

LAYER = "ssm"
UNIT = "%"
MOVES = "itl_ms_p50"
SOURCE = "trace"
BOUND = "HBM bandwidth"

STATE_DTYPES = ("bf16", "f32")  # as stored and as computed on


def read(run):
    steps = run.decode_only_steps
    per_row = getattr(run.counts, "ssm_elements_per_row", None)
    if not steps or run.peaks is None or run.trace is None or per_row is None:
        return None
    row_bytes = run.counts.ssm_bytes_per_row(run.dims)
    layers = run.counts.n_mamba(run.dims)
    one_layer = run.pool["max_batch"] * per_row(run.dims)
    sizes = {one_layer, layers * one_layer}
    spans = xplane.step_spans(run.trace, driver.SPAN_STEP)
    state_ops = [
        [
            e for e in xplane.leaves(d.ops)
            if any(run.counts.touches(e.name, kind, sizes) for kind in STATE_DTYPES)
        ]
        for d in run.trace.devices
    ]
    seconds = [
        statistics.mean(
            sum(
                e.seconds for e in ops
                if spans[s.index].start <= e.start and e.end <= spans[s.index].end
            )
            for ops in state_ops
        )
        for s in steps if s.index in spans
    ]
    if not seconds or not statistics.median(seconds) > 0:
        return None
    rows = statistics.mean(driver.live_rows(run.flights, s.t_start) for s in steps)
    least_s = (
        2 * rows * layers * row_bytes / run.chips
        / run.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / statistics.median(seconds)
