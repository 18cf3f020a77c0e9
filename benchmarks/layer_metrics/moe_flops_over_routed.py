"""Expert-matmul operations the device executed over the operations of the
experts tokens were routed to, in the traced slice.

Executed: every device instruction of the slice that takes an expert weight
as an operand is an expert matmul, and its operations follow from the
shapes in its name (``opcount.expert_matmul_flops``), summed over the
chips. Routed: the tokens the slice's steps put through the model, each
admitted prompt and each token a decode pass delivered (a request's first
delivery holds the prefill's token, which its prompt's pass made), and a
token needs ``moe_top_k`` experts of three ``d_model`` x ``d_ff`` matmuls
in every layer. 4.0 at 8 experts, top-2, dropless in groups, where every expert is
computed for every token (more while rows of the batch stand empty); 1.0
would be a grouped matmul over the routed tokens alone (ROADMAP D5)."""

from benchmarks.lib import opcount, xplane

LAYER = "moe"
UNIT = "x"
MOVES = "ttft_ms_p50_mix"
SOURCE = "trace"


def read(run):
    if run.trace is None or run.slice is None or not run.dims.get("n_experts"):
        return None
    lo, hi = run.slice
    executed = sum(
        opcount.expert_matmul_flops(e.name, run.dims) or 0.0
        for d in run.trace.devices for e in xplane.leaves(d.ops)
        if lo <= e.start and e.end <= hi
    )
    tokens = sum(
        sum(s.admitted_prompt_tokens) + s.delivered - len(s.admitted_prompt_tokens)
        for s in run.slice_steps
    )
    routed = (
        tokens * run.dims.get("moe_top_k", 2) * run.dims["n_layers"]
        * 3 * 2.0 * run.dims["d_model"] * run.dims["d_ff"]
    )
    return executed / routed if executed and routed else None
