"""Median wall time of a batcher step that decoded and prefilled nothing:
the host clock around work that ends in a sync (ServingMonitor step
records). Blocking admissions run outside ``ContinuousBatcher.step``, so
they are never inside these."""

import statistics

LAYER = "scheduler"
UNIT = "ms"
MOVES = "itl_ms_p50"
SOURCE = "monitor"


def read(run):
    decode = [
        s["duration_ms"] for s in run.steps
        if s["decode_tokens"] and not s["prefill_tokens"]
    ]
    return statistics.median(decode) if decode else None
