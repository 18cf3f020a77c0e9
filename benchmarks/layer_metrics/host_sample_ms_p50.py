"""Median time of the per-row host loop of a batcher step that decoded and
prefilled nothing: ``phase_ms["sample"]`` of the step record, the
``serve.step.sample`` span (``choose_host``, ``logprob_of``, the append and
the retirement of every active row). Same records as ``decode_step_ms_p50``;
``None`` where the program records no phases."""

import statistics

LAYER = "scheduler"
UNIT = "ms"
MOVES = "itl_ms_p50"
SOURCE = "monitor"


def read(run):
    sample = [
        s["phase_ms"]["sample"] for s in run.steps
        if s["decode_tokens"] and not s["prefill_tokens"]
        and "sample" in (s.get("phase_ms") or {})
    ]
    return statistics.median(sample) if sample else None
