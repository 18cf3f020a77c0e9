"""The prefill's chunked scan over the mamba layers: its share of its
roofline in the admissions of the traced slice. The least time is the
larger of its operations over the MXU's bf16 peak and its bytes over the
HBM bandwidth (``run.counts.ssd_prefill_flops`` and ``ssd_prefill_bytes``
for each admitted prompt at the width it is padded to: at a chunk of 256
it is just under the ridge, bound by its bytes). Over the time of the scan's
instructions. The compiler fuses the scan into a few instructions with no
name of their own (the masked decay matrix never exists as an array), so
they are found by what they touch: a leaf instruction inside the prefill
program (``jit_prefill_forward`` on the ``XLA Modules`` line) that takes no
stacked weight and has a float32 operand or result with as many elements as
one of the scan's arrays for an admitted width: x or y over the chunks
(tokens x inner), the per-head decays (tokens x heads), a chunk's scores
(chunk x chunk a chunk), a chunk's state or all of a prompt's. That also
takes in the elementwise passes right behind the scan (y + D x, the gate's
copy), which lowers the share a little and never raises it. ``None`` where
the configuration has no such scan or the slice no such instruction."""

import math

from benchmarks.lib import xplane

LAYER = "ssm"
UNIT = "%"
MOVES = "ttft_ms_p50_mix"
SOURCE = "trace"
BOUND = "the larger of bf16 MXU peak and HBM bandwidth"

PREFILL = "prefill_forward"


def scan_sizes(run, widths) -> set:
    """Element counts of the scan's float32 arrays, for prompts padded to
    ``widths``."""
    dims = run.dims
    heads = dims["mamba_n_heads"]
    state = run.counts.ssm_elements_per_row(dims)
    sizes = {state}
    for width in widths:
        chunk, n = run.counts.scan_chunks(dims, width)
        tokens = n * chunk
        sizes |= {
            n * state, tokens * heads * dims["mamba_d_head"], tokens * heads,
            tokens * chunk,
        }
    return sizes


def read(run):
    flops = getattr(run.counts, "ssd_prefill_flops", None)
    if run.trace is None or run.slice is None or run.peaks is None or flops is None:
        return None
    lo, hi = run.slice
    page = run.pool["page_size"]
    widths = [
        -(-p // page) * page for s in run.slice_steps for p in s.admitted_prompt_tokens
    ]
    if not widths:
        return None
    sizes = scan_sizes(run, widths)
    stacks = {run.dims["n_layers"], run.counts.n_mamba(run.dims)}

    def is_scan(name: str) -> bool:
        touches = False
        for dtype, shape in run.counts.hlo_arrays(name):
            if dtype != "f32" and len(shape) == 3 and shape[0] in stacks:
                return False  # a matmul against a layer-stacked weight
            touches |= dtype == "f32" and math.prod(shape) in sizes
        return touches

    seconds = []
    for d in run.trace.devices:
        prefills = xplane.union(
            (max(m.start, lo), min(m.end, hi)) for m in d.modules
            if PREFILL in m.name and m.end > lo and m.start < hi
        )
        seconds.append(sum(
            xplane.measure(xplane.intersect([(e.start, e.end)], prefills))
            for e in xplane.leaves(d.ops)
            if e.end > lo and e.start < hi and is_scan(e.name)
        ))
    if not seconds or not min(seconds) > 0:
        return None
    least_s = sum(
        max(
            flops(run.dims, w) / run.peaks["bf16_flops_per_s"],
            run.counts.ssd_prefill_bytes(run.dims, w) / run.peaks["hbm_bytes_per_s"],
        )
        for w in widths
    ) / run.chips
    return 100.0 * least_s / (sum(seconds) / len(seconds))
