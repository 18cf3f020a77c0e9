"""Median time of the device-to-host copies of a batcher step that decoded
and prefilled nothing: ``phase_ms["pull"]`` of the step record, the
``serve.step.pull`` span (the ``[B]`` argmax ids and, when a row samples or
records log-probabilities, the ``[B, V]`` float32 rows), after the wait for
the device has ended. Same records as ``decode_step_ms_p50``; ``None``
where the program records no phases."""

import statistics

LAYER = "scheduler"
UNIT = "ms"
MOVES = "itl_ms_p50"
SOURCE = "monitor"


def read(run):
    pull = [
        s["phase_ms"]["pull"] for s in run.steps
        if s["decode_tokens"] and not s["prefill_tokens"]
        and "pull" in (s.get("phase_ms") or {})
    ]
    return statistics.median(pull) if pull else None
