"""The window layers' rings' share of their roofline in a decode step. A
window layer keeps ``sliding_window`` slots a row (``ops/paged_kv_cache.py``:
``wk`` / ``wv`` [window layers, rows, kv heads, window, head size]); a decode
step must read each decoding row's live slots once a layer and write one
(``run.counts.ring_step_bytes``): the least time that takes is those bytes
over the chip's HBM bandwidth. Over the median, across the traced decode-only
steps, of the time of the device instructions that touch a ring: the scopes
``attn.window`` do not reach the reduced trace (an event is the text of its
HLO instruction), so they are found by shape, a leaf instruction that names a
bf16 or float32 array as large as one layer's ring of the pool's rows, or as
the stack of them, in rows of the head's size (``run.counts.touches_ring``). ``None`` where the configuration keeps no ring
(another count module, a parent commit) or no instruction is shaped so."""

import statistics

from benchmarks.lib import driver, xplane

LAYER = "swa"
UNIT = "%"
MOVES = "itl_ms_p50"
SOURCE = "trace"
BOUND = "HBM bandwidth"


def read(run):
    steps = run.decode_only_steps
    touches = getattr(run.counts, "touches_ring", None)
    if not steps or run.peaks is None or run.trace is None or touches is None:
        return None
    spans = xplane.step_spans(run.trace, driver.SPAN_STEP)
    ring_ops = [
        [e for e in xplane.leaves(d.ops) if touches(e.name, run.dims, run.pool)]
        for d in run.trace.devices
    ]
    seconds = [
        statistics.mean(
            sum(
                e.seconds for e in ops
                if spans[s.index].start <= e.start and e.end <= spans[s.index].end
            )
            for ops in ring_ops
        )
        for s in steps if s.index in spans
    ]
    if not seconds or not statistics.median(seconds) > 0:
        return None
    live = statistics.mean(driver.live_tokens(run.flights, s.t_start) for s in steps)
    rows = statistics.mean(driver.live_rows(run.flights, s.t_start) for s in steps)
    least_s = (
        run.counts.ring_step_bytes(run.dims, live, rows) / run.chips
        / run.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / statistics.median(seconds)
