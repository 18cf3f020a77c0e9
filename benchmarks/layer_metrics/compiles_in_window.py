"""Programs the tracked jits compiled inside the window (DeviceMonitor's
compile total after the window less before). Must read 0: the warm-up owns
every shape. Eager programs compile untracked (PERF.md section 7)."""

LAYER = "scheduler"
UNIT = "count"
MOVES = "itl_ms_p95"
SOURCE = "monitor"


def read(run):
    return run.compiles_in_window
