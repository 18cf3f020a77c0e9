"""Device-busy time of admissions over the prompt tokens they prefilled:
in each traced step that admitted requests, the busy time less the median
of the steps that admitted none (the decode step it also ran)."""

import statistics

LAYER = "model_programs"
UNIT = "ms"  # per 1000 prompt tokens
MOVES = "ttft_ms_p50_mix"
SOURCE = "trace"


def read(run):
    admitting = [
        s for s in run.slice_steps
        if s.admitted_prompt_tokens and s.index in run.step_busy
    ]
    decode = [run.step_busy[s.index] for s in run.decode_only_steps]
    if not admitting or not decode:
        return None
    decode_s = statistics.median(decode)
    tokens = sum(sum(s.admitted_prompt_tokens) for s in admitting)
    prefill_s = sum(max(0.0, run.step_busy[s.index] - decode_s) for s in admitting)
    return 1000.0 * prefill_s / (tokens / 1000.0)
