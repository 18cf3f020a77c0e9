"""The full layers' decode attention's share of its roofline, beside window
layers that keep rings. A decode step must read the K/V of every live token
once in each FULL layer (``run.counts.full_layers_kv_bytes``; the window
layers' are ``swa_ring_roofline``'s): the least time that takes is those
bytes over the chip's HBM bandwidth. Over the median, across the traced
decode-only steps, of the time of the device instructions that attend over
the pages: the Pallas kernel by its name (``paged_decode_attention``), or,
where the program takes the slice path, every leaf instruction with a bf16 or
float32 array as large as one layer's slice of the page pool (K or V) or as
the gather of the block tables' width. ``None`` where the configuration tells
no full layers from window layers (another count module, a parent commit) or
no instruction is so named or shaped."""

import math
import statistics

from benchmarks.lib import driver, xplane

LAYER = "swa"
UNIT = "%"
MOVES = "itl_ms_p50"
SOURCE = "trace"
BOUND = "HBM bandwidth"

KERNEL = "paged_decode_attention"
DTYPES = ("bf16", "f32")  # as stored and as the einsums read it


def read(run):
    steps = run.decode_only_steps
    kv_bytes = getattr(run.counts, "full_layers_kv_bytes", None)
    if not steps or run.peaks is None or run.trace is None or kv_bytes is None:
        return None
    pool, dims = run.pool, run.dims
    token = dims["n_kv_heads"] * dims["head_dim"]  # a token's K (or V) in a layer
    sizes = {
        pool["n_pages"] * pool["page_size"] * token,
        pool["max_batch"] * pool["max_pages_per_seq"] * pool["page_size"] * token,
    }

    def attends(text):
        return KERNEL in text or any(
            kind in DTYPES and math.prod(shape) in sizes
            for kind, shape in run.counts.hlo_arrays(text)
        )

    spans = xplane.step_spans(run.trace, driver.SPAN_STEP)
    attend_ops = [
        [e for e in xplane.leaves(d.ops) if attends(e.name)]
        for d in run.trace.devices
    ]
    seconds = [
        statistics.mean(
            sum(
                e.seconds for e in ops
                if spans[s.index].start <= e.start and e.end <= spans[s.index].end
            )
            for ops in attend_ops
        )
        for s in steps if s.index in spans
    ]
    if not seconds or not statistics.median(seconds) > 0:
        return None
    live = statistics.mean(driver.live_tokens(run.flights, s.t_start) for s in steps)
    least_s = kv_bytes(dims, live) / run.chips / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / statistics.median(seconds)
