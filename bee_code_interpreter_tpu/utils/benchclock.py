"""The chained clock every chip-facing script shares.

Kernels are timed as N data-dependent applications chained inside one jit
with a single scalar readback, and the per-call time is the difference of an
N-long and a 1-long chain: ``(t_N - t_1) / (N - 1)`` cancels the fixed
per-call cost (dispatch + the readback) exactly, so short kernels are not
read as slow. On a local chip ``block_until_ready``/a scalar readback is the
barrier; the difference only removes what is constant per call.

``chain_diff`` is THE single copy of that difference plus its sanity guard:
if jitter swamps the chain (t_N not meaningfully above t_1), the measurement
must fail loudly — a floored difference silently prints absurd TFLOPS as a
result. Used by scripts/bench-flash-attention.py, scripts/bench-decode.py,
scripts/bench-mfu.py and bench.py's in-sandbox flash payload.

(How such a script starts — fail without a TPU, stamp every result with the
device — is ``parallel.mesh.require_tpu``.)
"""

from __future__ import annotations

MARGIN = 1.2  # t_N must exceed t_1 by at least this factor


def chain_diff(t_n: float, t_1: float, n: int, what: str = "chain") -> float:
    """Per-call seconds from an n-long vs 1-long chain measurement."""
    if not t_n > t_1 * MARGIN:
        raise AssertionError(
            f"clock failed ({what}): {n}-chain {t_n * 1e3:.1f} ms not "
            f"meaningfully above 1-chain {t_1 * 1e3:.1f} ms — jitter "
            "swamped the kernel; raise the chain length or the shape"
        )
    return (t_n - t_1) / (n - 1)
