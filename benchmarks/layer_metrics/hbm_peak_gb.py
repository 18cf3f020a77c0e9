"""Peak bytes in use on the fullest chip over the whole run
(``memory_stats()["peak_bytes_in_use"]``): guards the pool size, and shows
what the admission path's second copy of the pool costs."""

LAYER = "device"
UNIT = "GB"
MOVES = "out_tok_s"
SOURCE = "memory_stats"


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
