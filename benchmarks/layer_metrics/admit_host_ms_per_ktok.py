"""What a blocking admission costs beside its device work: over the
``serve.admit`` spans inside the traced slice, the seconds in which no
instruction ran on the chip that idled most, per 1,000 of the spans' own
``prompt_tokens``. ``prefill_device_ms_per_ktok`` is the other part of an
admission. ``None`` where the slice holds no admission, and off the chip
(the CPU of the tests, where the host's own threads stand in for a device
and "no instruction ran" is no host time)."""

from benchmarks.lib import xplane

LAYER = "scheduler"
UNIT = "ms"  # per 1000 prompt tokens
MOVES = "ttft_ms_p50_mix"
SOURCE = "trace"


def read(run):
    if run.trace is None or run.slice is None:
        return None
    lo, hi = run.slice
    chips = [
        d for d in run.trace.devices
        if d.name.startswith(xplane.DEVICE_PLANE_PREFIX)
    ]
    admits = [
        e for e in run.trace.host
        if e.name == "serve.admit" and e.start >= lo and e.end <= hi
    ]
    if not chips or not admits:
        return None
    idlest = min(
        chips, key=lambda d: xplane.measure(xplane.clip(d.busy, lo, hi))
    )
    idle_s = sum(
        xplane.measure(xplane.gaps(idlest.busy, e.start, e.end)) for e in admits
    )
    tokens = sum(int(dict(e.stats)["prompt_tokens"]) for e in admits)
    return 1000.0 * idle_s / (tokens / 1000.0)
