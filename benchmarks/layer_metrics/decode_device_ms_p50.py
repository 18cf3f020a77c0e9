"""Median device-busy time inside one engine step that admitted nothing:
the decode program and the small programs around it, from the trace."""

import statistics

LAYER = "model_programs"
UNIT = "ms"
MOVES = "itl_ms_p50"
SOURCE = "trace"


def read(run):
    busy = [run.step_busy[s.index] for s in run.decode_only_steps]
    return 1000.0 * statistics.median(busy) if busy else None
