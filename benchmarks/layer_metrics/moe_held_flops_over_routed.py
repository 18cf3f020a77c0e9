"""Expert-matmul operations the device executed over the operations of the
(token, expert) pairs routed to the experts HELD on this chip, in the
traced slice.

Executed: every device instruction of the slice that takes the held
experts' weights as an operand and gives rows of the model's or the
experts' width is an expert matmul, and its operations follow from the rows
in its name (``run.counts.expert_matmul_flops``: the sorted buffer's rows,
whatever the groups cover of them), summed over the chips. Routed: the
program's own count, ``held_expert_pairs`` of its step records (the
expectation under even routing: tokens x top-k x held / routed, a layer;
PERF.md section 3), per token the window's records put through the model,
times the tokens the slice's steps put through it (each admitted prompt and
each token a decode pass delivered), each pair three ``d_model`` x expert
width matmuls. 1.0 is a grouped matmul over the routed pairs alone; the
sorted buffer is a quarter larger than the expected pairs; 16 would be every
held expert for every token. ``None`` where the records carry no such
count (a program without the sorted dispatch)."""

from benchmarks.lib import xplane

LAYER = "moe"
UNIT = "x"
MOVES = "ttft_ms_p50_mix"
SOURCE = "trace"


def read(run):
    flops_of = getattr(run.counts, "expert_matmul_flops", None)
    if run.trace is None or run.slice is None or flops_of is None:
        return None
    counted = [s for s in run.steps if "held_expert_pairs" in s]
    through = sum(s["decode_tokens"] + s["prefill_tokens"] for s in counted)
    if not through:
        return None
    pairs_per_token = sum(s["held_expert_pairs"] for s in counted) / through
    lo, hi = run.slice
    executed = sum(
        flops_of(e.name, run.dims) or 0.0
        for d in run.trace.devices for e in xplane.leaves(d.ops)
        if lo <= e.start and e.end <= hi
    )
    tokens = sum(
        sum(s.admitted_prompt_tokens) + s.delivered - len(s.admitted_prompt_tokens)
        for s in run.slice_steps
    )
    routed = run.counts.routed_pair_flops(run.dims, tokens * pairs_per_token)
    return executed / routed if executed and routed else None
