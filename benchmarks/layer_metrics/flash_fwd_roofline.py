"""The flash forward kernel's share of its roofline, from the trace.

The kernel (``ops/flash_attention.py``, one Mosaic call per layer and
prefill) has no name of its own in a trace yet: its event is the text of an
HLO ``custom-call`` with ``custom_call_target="tpu_custom_call"``. It is
told from other Pallas kernels by what it returns, the attention output and
the float32 log-sum-exp with a last dimension of 1 (or, once kernels carry a
stable name, by ``flash`` and ``fwd`` in it). The operations are those of
causal attention over each admitted prompt (``opcount.attention_flops``,
per chip); the bound is the MXU's bf16 peak, since at a head size of 128
causal attention over hundreds of tokens and more is bound by compute.
"""

import re

from benchmarks.lib import opcount, xplane

LAYER = "kernels"
UNIT = "%"
MOVES = "ttft_ms_p50_mix"
SOURCE = "trace"
BOUND = "bf16 MXU peak"

FLASH_FWD = re.compile(
    r"flash\w*fwd|fwd\w*flash"
    # (out, lse): a tuple whose second member is float32 [..., 1]
    r"|^%?[\w.\-]+ = \(\w+\[[\d,]+\]\{[^}]*\}, f32\[[\d,]+,1\]\{[^}]*\}\) "
    r'custom-call\(.*custom_call_target="tpu_custom_call"',
    re.IGNORECASE,
)


def read(run):
    if run.trace is None or run.slice is None or run.peaks is None:
        return None
    lo, hi = run.slice
    seconds = [
        xplane.matching_seconds(d, FLASH_FWD, lo, hi) for d in run.trace.devices
    ]
    prompts = [
        p for s in run.slice_steps for p in s.admitted_prompt_tokens
    ]
    if not prompts or not min(seconds) > 0:
        return None
    flops = sum(
        run.dims["n_layers"] * opcount.attention_flops(
            1, run.dims["n_heads"], p, opcount.head_dim(run.dims), causal=True
        )
        for p in prompts
    ) / run.chips
    least_s = flops / run.peaks["bf16_flops_per_s"]
    return 100.0 * least_s / (sum(seconds) / len(seconds))
