"""Decode steps that took far longer than a decode step takes, as
milliseconds lost a second: over the window's step records that landed
tokens and prefilled nothing (``decode_step_ms_p50``'s set), the sum of
``duration_ms`` less the median over the records above ``STALL`` times the
median, per second of the window. A blocking admission runs outside
``ContinuousBatcher.step`` and is never in these: what is counted is a step
that waited (``phase_ms["wait"]``) long after the device had finished, or
whose host phases were held up. ``None`` where the window has no such
record."""

import statistics

LAYER = "scheduler"
UNIT = "ms"  # per second of window
MOVES = "out_tok_s"
SOURCE = "monitor"

STALL = 3.0  # times the median


def read(run):
    decode = [
        s["duration_ms"] for s in run.steps
        if s["decode_tokens"] and not s["prefill_tokens"]
    ]
    if not decode:
        return None
    median = statistics.median(decode)
    lost = sum(ms - median for ms in decode if ms > STALL * median)
    t_open, t_close = run.window
    return lost / (t_close - t_open)
