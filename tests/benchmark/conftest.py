"""The benchmark's tests beside the repository's own.

These files compile for the TPU, run the plain references and drive tiny
cells end to end: XLA spreads that over every core it finds, and tier-1
runs six workers on the same machine, some of them inside tests that time
a sandbox against another (``tests/test_sessions.py``). Run beside the
benchmark's tests at full width those failed now and then; they never did
without them. So each module here is held to two cores, chosen by the
worker it runs on, and the worker gets its cores back when the module ends.
Nothing here reads a clock, so the only cost is the module's own time.
"""

from __future__ import annotations

import os

import pytest

CORES = 2


def _hold_every_thread_to(cores: set[int]) -> None:
    for task in os.listdir("/proc/self/task"):
        try:
            os.sched_setaffinity(int(task), cores)
        except OSError:  # the thread ended meanwhile
            pass


@pytest.fixture(scope="module", autouse=True)
def few_cores():
    if not hasattr(os, "sched_setaffinity") or not os.path.isdir("/proc/self/task"):
        yield
        return
    allowed = os.sched_getaffinity(0)
    ordered = sorted(allowed)
    worker = os.environ.get("PYTEST_XDIST_WORKER", "gw0")
    first = int(worker[2:] or 0) * CORES if worker[2:].isdigit() else 0
    mine = {ordered[(first + i) % len(ordered)] for i in range(CORES)}
    # threads started from here on inherit the mask; those that exist get it
    _hold_every_thread_to(mine)
    yield
    _hold_every_thread_to(allowed)
