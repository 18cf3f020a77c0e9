"""The 99th percentile of the gap between tokens, recorded and not judged.

In a 51 s window it is the third- or fourth-slowest step: in
``mistral7b_chat`` it swung between 233 and 288 ms over untraced runs of
the same code (a spread of 11 % in one set of four; PERF.md section 6), so
no bound under the contract's limit holds it there. ``itl_ms_p95`` is the
judged tail; this stays on the record because in the long-prompt cells it is
the stall of a 4096-token admission.

Per-layer metrics are printed by traced runs only, and starting and stopping
the profiler each stall the loop for longer than any admission does. So the
gaps that touch the profiler's span (from before it is started to the end of
the first step after it is stopped) are left out: what is read is the
caller's clock over the rest of the window, about nine tenths of it."""

from benchmarks.lib import driver

LAYER = "scheduler"
UNIT = "ms"
MOVES = "itl_ms_p95"
SOURCE = "harness"  # its own stamps at the Engine boundary


def read(run):
    gaps = driver.token_gaps(run.flights, *run.window)
    if run.profiler_span is not None:
        lo, hi = run.profiler_span
        gaps = [g for g in gaps if g[1] <= lo or g[0] >= hi]
    return driver.itl_ms(gaps, 99) if gaps else None
