"""Published peaks of one chip, keyed by the ``device_kind`` jax reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip has 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s. A device that is not
in the table is an error, not a default: a roofline share against a guessed
peak is not a measurement. (The bf16 figure is copied from
``scripts/bench-mfu.py``'s ``PEAK_BF16_FLOPS``, which PERF.md lists for a
later PR to delete.)
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it "
            "(with its source) to benchmarks/lib/peaks.py"
        ) from None
