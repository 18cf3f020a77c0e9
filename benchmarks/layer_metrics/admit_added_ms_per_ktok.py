"""What a blocking admission adds to the step it rides in, per 1,000 prompt
tokens: over every admission record of the window (``admissions`` on the
``ServingMonitor`` step records: ``ContinuousBatcher._admit_observed``), the
sum of ``duration_ms`` less ``phase_ms["land"]`` (the drain of the step in
flight, which that step would have cost anyway) over the sum of
``prompt_tokens``. It is what the admission's ``decoding_rows`` wait, read
over the whole window and not the traced slice. ``None`` where the program's
step records carry no ``admissions`` (a parent commit)."""

LAYER = "scheduler"
UNIT = "ms"  # per 1000 prompt tokens
MOVES = "itl_ms_p95"
SOURCE = "monitor"


def read(run):
    records = [a for s in run.steps for a in s.get("admissions", ())]
    tokens = sum(a["prompt_tokens"] for a in records)
    if not tokens:
        return None
    added = sum(
        a["duration_ms"] - a["phase_ms"].get("land", 0.0) for a in records
    )
    return added / (tokens / 1000.0)
