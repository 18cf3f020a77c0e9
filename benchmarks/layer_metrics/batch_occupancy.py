"""Rows decoding over rows the batch has, mean over the window's steps."""

LAYER = "scheduler"
UNIT = "%"
MOVES = "out_tok_s"
SOURCE = "monitor"  # ServingMonitor step records: active_rows, max_batch


def read(run):
    if not run.steps:
        return None
    shares = [s["active_rows"] / s["max_batch"] for s in run.steps]
    return 100.0 * sum(shares) / len(shares)
