"""Median of what the phases leave of a batcher step that decoded and
prefilled nothing: the step record's ``duration_ms`` less the sum of its
top-level ``phase_ms`` (``sample_choose`` and ``sample_logprob`` lie inside
``sample``). It is the check on the other phase readers: host work that no
``serve.step.*`` span covers shows up here, as does a whole step of a path
that has no spans (the speculative ones). Same records as
``decode_step_ms_p50``; ``None`` where the program records no phases."""

import statistics

LAYER = "scheduler"
UNIT = "ms"
MOVES = "itl_ms_p50"
SOURCE = "monitor"

TOP_LEVEL = ("prefills", "upload", "dispatch", "wait", "pull", "sample")


def read(run):
    left = [
        s["duration_ms"] - sum(s["phase_ms"].get(k, 0.0) for k in TOP_LEVEL)
        for s in run.steps
        if s["decode_tokens"] and not s["prefill_tokens"]
        and s.get("phase_ms") is not None
    ]
    return statistics.median(left) if left else None
