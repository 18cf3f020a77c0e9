#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the TPU this process finds.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: it fails (non-zero exit code, no result) without a TPU,
with fewer chips than the cell asks for, or outside a checkout of the
repository; builds the cell's weights on the device from ``--seed``; warms
only that cell's shapes; checks the outputs outside the window; measures
for ``--seconds``; prints the result as the last line of its standard
output and exits 0. ``benchmarks/lib/harness.py`` has the order of a run,
``PERF.md`` what the numbers mean.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics (and ``device.busy_s``,
``device.window_s`` and ``breakdown`` from a profiler trace of a slice of
the window). ``--keep-trace PATH`` copies that trace's ``.xplane.pb`` to
PATH before it is deleted (``benchmarks/trace_layout.py`` prints what one
holds).

The compile cache is where ``JAX_COMPILATION_CACHE_DIR`` says, else the
checkout's fixed ``.jax_cache`` (bee_code_interpreter_tpu/utils/jaxcache.py),
and holds every program, however small: a second run of a cell compiles
nothing.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--keep-trace", default=None)
    args = parser.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    try:
        from bee_code_interpreter_tpu.utils.jaxcache import ENV_VAR, jax_cache_dir
    except ImportError as e:
        print(f"benchmarks/run.py: not in a checkout of the repository: {e}",
              file=sys.stderr)
        return 2
    # a missing chip is an error, never a CPU run: the platform is pinned
    # unless the environment already names one, and checked either way
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    os.environ[ENV_VAR] = jax_cache_dir()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    # libtpu would log under /tmp/tpu_logs, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from benchmarks.lib import harness

    # no backend, too few chips, an unknown name or device kind: the
    # exception ends the process with a traceback, a non-zero code and no
    # result
    result = harness.run_cell(
        ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
        platform="tpu", t_process_start=T_PROCESS_START,
        keep_trace=args.keep_trace,
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
