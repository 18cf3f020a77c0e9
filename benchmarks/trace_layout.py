#!/usr/bin/env python3
"""Print what is in an ``.xplane.pb``: planes, lines, event counts, the
names that took most time on each line and one event's stats.

    python3 benchmarks/trace_layout.py <file.xplane.pb> [names-per-line]

Look at a trace with this before trusting, or changing, a reduction in
``benchmarks/lib/xplane.py``: the reduction finds device planes, lines and
kernels by the names printed here. (``run.py --keep-trace PATH`` keeps the
trace of a ``--trace 1`` run.)
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv) -> int:
    if not 2 <= len(argv) <= 3:
        print(__doc__, file=sys.stderr)
        return 2
    from benchmarks.lib import xplane

    top = int(argv[2]) if len(argv) == 3 else 12
    print(json.dumps(xplane.layout(argv[1], top=top), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
