"""Median duration of the decode program itself: the ``XLA Modules`` events
named ``jit_decode_step_paged(...)`` inside the traced slice, the slowest
chip's median. ``decode_device_ms_p50`` reads the same step from outside
(device-busy time inside the harness's step span) and so includes the small
eager programs around it. ``None`` where no device has such an event: a
program that does not name its jits, or the CPU of the tests, which has no
``XLA Modules`` line."""

import statistics

LAYER = "model_programs"
UNIT = "ms"
MOVES = "itl_ms_p50"
SOURCE = "trace"

PROGRAM = "jit_decode_step_paged"


def read(run):
    if run.trace is None or run.slice is None:
        return None
    lo, hi = run.slice
    medians = []
    for device in run.trace.devices:
        ms = [
            1000.0 * e.seconds for e in device.modules
            if e.name.startswith(PROGRAM) and e.start >= lo and e.end <= hi
        ]
        if ms:
            medians.append(statistics.median(ms))
    return max(medians) if medians else None
