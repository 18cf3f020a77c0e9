"""Latent attention's share of its roofline in a decode step. A decode step
must read every live token's latent once a layer
(``run.counts.latent_layer_bytes``: the published 576 values, whatever the
pool pads a slot to) and score and weigh it for every head in the absorbed
form (``run.counts.latent_attention_flops``): the least time a layer takes
is the larger of those bytes at the chip's HBM bandwidth and those
operations at its bf16 peak. Over the median, across the traced decode-only
steps, of the time of the device instructions that attend over the latent:
the Pallas kernel by its name (``paged_decode_attention``), or, where the
program takes the slice path, every leaf instruction with a bf16 or float32
array as large as one layer's slice of the pool or as the gather of the
block tables' width. ``None`` where the configuration keeps no latent or no
instruction is so named or shaped."""

import statistics

from benchmarks.lib import driver, xplane

LAYER = "mla"
UNIT = "%"
MOVES = "itl_ms_p50"
SOURCE = "trace"
BOUND = "HBM bandwidth or bf16 MXU peak, whichever takes longer"

KERNEL = "paged_decode_attention"
DTYPES = ("bf16", "f32")  # as stored and as the einsums read it


def read(run):
    steps = run.decode_only_steps
    flops = getattr(run.counts, "latent_attention_flops", None)
    if (
        not steps or run.peaks is None or run.trace is None or flops is None
        or not run.dims.get("kv_lora_rank")
    ):
        return None
    pool, width = run.pool, run.counts.latent_slot_width(run.dims)
    sizes = {
        pool["n_pages"] * pool["page_size"] * width,
        pool["max_batch"] * pool["max_pages_per_seq"] * pool["page_size"] * width,
    }
    spans = xplane.step_spans(run.trace, driver.SPAN_STEP)
    attend_ops = [
        [
            e for e in xplane.leaves(d.ops)
            if KERNEL in e.name
            or any(run.counts.touches(e.name, kind, sizes) for kind in DTYPES)
        ]
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
    rows = statistics.mean(driver.live_rows(run.flights, s.t_start) for s in steps)
    least_s = run.dims["n_layers"] * max(
        run.counts.latent_layer_bytes(run.dims, live) / run.peaks["hbm_bytes_per_s"],
        flops(run.dims, rows, live) / run.peaks["bf16_flops_per_s"],
    ) / run.chips
    return 100.0 * least_s / statistics.median(seconds)
