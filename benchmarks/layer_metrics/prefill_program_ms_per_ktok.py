"""The prefill program itself, per 1,000 tokens it ran: the ``XLA Modules``
events named ``jit_prefill_forward(...)`` inside the traced slice, the k-th
paired with the k-th ``serve.admit.prefill`` span's ``padded_tokens`` in
dispatch order (one admission, one program, on one stream); the sum of the
events' milliseconds over the sum of the padded tokens, on the slowest
chip. ``prefill_device_ms_per_ktok`` reads the same work from outside
(device-busy time inside the harness's step span less a median decode
step) and so holds the eager seeding and a decode program's tail. ``None``
where a chip's events and the spans differ in number (nothing to pair
by), where the spans carry no ``padded_tokens`` (a parent commit), and
where no device has such an event: the CPU of the tests, which has no
``XLA Modules`` line."""

LAYER = "model_programs"
UNIT = "ms"  # per 1000 padded prompt tokens
MOVES = "ttft_ms_p50_mix"
SOURCE = "trace"

PROGRAM = "jit_prefill_forward"
SPAN = "serve.admit.prefill"


def pairs(run):
    """By chip, ``[(padded tokens, program ms), ...]`` in dispatch order;
    ``None`` where nothing can be paired."""
    if run.trace is None or run.slice is None:
        return None
    lo, hi = run.slice
    padded = [
        dict(e.stats).get("padded_tokens") for e in run.trace.host
        if e.name == SPAN and e.start >= lo and e.end <= hi
    ]
    if not padded or None in padded:
        return None
    by_chip = {}
    for device in run.trace.devices:
        ms = [
            1000.0 * e.seconds for e in device.modules
            if e.name.startswith(PROGRAM) and e.start >= lo and e.end <= hi
        ]
        if not ms:
            continue
        if len(ms) != len(padded):
            return None
        by_chip[device.name] = list(zip(map(int, padded), ms))
    return by_chip or None


def read(run):
    by_chip = pairs(run)
    if by_chip is None:
        return None
    return max(
        sum(ms for _, ms in chip) / (sum(n for n, _ in chip) / 1000.0)
        for chip in by_chip.values()
    )
