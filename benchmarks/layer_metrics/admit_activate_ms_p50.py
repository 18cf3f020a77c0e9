"""The first token picked on the host with the chip idle: the median
``phase_ms["activate"]`` of the window's admission records (``admissions``
on the ``ServingMonitor`` step records; the ``serve.admit.activate`` span:
``choose_host`` on the pulled logits row, then ``_activate_row``'s
bookkeeping). ``None`` where the program's step records carry no
``admissions`` (a parent commit)."""

import statistics

LAYER = "scheduler"
UNIT = "ms"
MOVES = "ttft_ms_p50_mix"
SOURCE = "monitor"


def read(run):
    activate = [
        a["phase_ms"]["activate"]
        for s in run.steps for a in s.get("admissions", ())
        if "activate" in a["phase_ms"]
    ]
    return statistics.median(activate) if activate else None
