"""The queue a request waits in before its admission: the median
``queued_ms`` of the window's admission records (``admissions`` on the
``ServingMonitor`` step records), ``Engine.submit`` to the admission's
start as the monitor's ticket hooks time it. In a closed loop a caller
submits into a row it has just freed, so this is the time to the next
``Engine.step``, or one admission's length where two land in one step.
``None`` where the program's step records carry no ``admissions`` (a parent
commit) or none of them a ``queued_ms`` (a batcher driven without an
``Engine``)."""

import statistics

LAYER = "scheduler"
UNIT = "ms"
MOVES = "ttft_ms_p50_mix"
SOURCE = "monitor"


def read(run):
    queued = [
        a["queued_ms"]
        for s in run.steps for a in s.get("admissions", ())
        if a.get("queued_ms") is not None
    ]
    return statistics.median(queued) if queued else None
