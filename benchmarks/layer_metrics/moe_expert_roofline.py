"""The held experts' matmuls' share of their roofline in a decode step. At
a decode batch an expert's three matrices are read for a handful of tokens,
so the least time the expert matmuls take is the bytes of the held experts
that at least one row keeps (``run.counts.experts_touched``, in expectation
under even routing, in every expert layer) over the chip's HBM bandwidth.
Over the median, across the traced decode-only steps, of the time of the
expert matmuls: the device instructions that take the held experts' weights
as an operand and give rows of ``d_model`` or of the experts' width
(``run.counts.expert_matmul_rows``: the grouped matmul's Mosaic call, or a
fusion over the stack). ``None`` where the configuration holds no share of
experts or no instruction is shaped so."""

import statistics

from benchmarks.lib import driver, xplane

LAYER = "moe"
UNIT = "%"
MOVES = "itl_ms_p50"
SOURCE = "trace"
BOUND = "HBM bandwidth"


def read(run):
    steps = run.decode_only_steps
    rows_of = getattr(run.counts, "expert_matmul_rows", None)
    if (
        not steps or run.peaks is None or run.trace is None or rows_of is None
        or not run.dims.get("n_experts")
    ):
        return None
    spans = xplane.step_spans(run.trace, driver.SPAN_STEP)
    expert_ops = [
        [e for e in xplane.leaves(d.ops) if rows_of(e.name, run.dims) is not None]
        for d in run.trace.devices
    ]
    seconds = [
        statistics.mean(
            sum(
                e.seconds for e in ops
                if spans[s.index].start <= e.start and e.end <= spans[s.index].end
            )
            for ops in expert_ops
        )
        for s in steps if s.index in spans
    ]
    if not seconds or not statistics.median(seconds) > 0:
        return None
    rows = statistics.mean(driver.live_rows(run.flights, s.t_start) for s in steps)
    counts = run.counts
    least_s = (
        counts.n_expert_layers(run.dims) * counts.experts_touched(run.dims, rows)
        * counts.expert_elements(run.dims) * counts.BF16
        / run.chips / run.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / statistics.median(seconds)
