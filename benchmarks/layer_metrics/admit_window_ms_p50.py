"""What seeding a row's rings costs an admission: the median length of the
``serve.admit.seed_window`` spans inside the traced slice
(``ContinuousBatcher._phase``: the dispatch of the donating program that
replaces the row's rings with the window layers' K/V of the prompt's last
``sliding_window`` positions, inside ``serve.admit``). ``None`` where the
program has no such span (a configuration without window layers' rings, a
parent commit) or the slice holds no admission."""

import statistics

LAYER = "swa"
UNIT = "ms"
MOVES = "ttft_ms_p50_mix"
SOURCE = "trace"

SPAN = "serve.admit.seed_window"


def read(run):
    if run.trace is None or run.slice is None:
        return None
    lo, hi = run.slice
    spans = [
        e.seconds for e in run.trace.host
        if e.name == SPAN and e.start >= lo and e.end <= hi
    ]
    return 1000.0 * statistics.median(spans) if spans else None
