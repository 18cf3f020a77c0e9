"""What one chip's share of granite-4.0-h-small must move and compute: the
hybrid of ``granitehybrid.py`` (Mamba-2 mixers with state by row, attention
with K/V by token in the layers ``layer_types`` names ``attention``, a head
tied to the embedding), with an expert layer under every mixer and every
attention layer: a router over every expert, the experts HELD here and a
shared MLP of ``moe_shared_experts`` experts' widths.

The first two functions are the contract every count module keeps
(``benchmarks/opcount/decoder.py``); the rest is what the ``moe`` readers
count with (``expert_matmul_rows``, ``experts_touched``, ``expert_elements``,
``n_expert_layers``, ``held_pairs``: the same contract as ``sarvam_mla.py``,
whose expert layer this is under another router), and the ``ssm`` readers
with the mixers' state and scan, granite-4.0-h-micro's at twice its width
(``ssm_elements_per_row``, ``scan_chunks``, ``ssd_prefill_flops``,
``ssd_prefill_bytes``: ``granitehybrid.py``'s). Sizes come from the
configuration's ``transformer_config`` group (``dims``). Weights, K/V and
what a row keeps are bf16: 2 bytes an element.
"""

# the mixer, attention, state and K/V are granite-4.0-h-micro's
from benchmarks.opcount.granitehybrid import (  # noqa: F401
    attention_weight_elements,
    kv_bytes_per_token,
    mixer_weight_elements,
    n_attention,
    n_mamba,
    prefill_attention_flops,
    scan_chunks,
    ssd_prefill_bytes,
    ssd_prefill_flops,
    ssm_bytes_per_row,
    ssm_elements_per_row,
    state_bytes_per_row,
    touches,
)

# the held experts are counted as sarvam-105b's: the same sorted layer
from benchmarks.opcount.sarvam_mla import (  # noqa: F401
    BF16,
    expert_elements,
    expert_matmul_flops,
    expert_matmul_rows,
    expert_width,
    experts_touched,
    held,
    held_pairs,
    hlo_arrays,
    n_expert_layers,
    routed_pair_flops,
)


def shared_width(dims: dict) -> int:
    """The shared MLP's width: ``moe_shared_experts`` experts' widths in one
    SwiGLU (1536 for 2 x 768)."""
    return dims.get("moe_shared_experts", 0) * expert_width(dims)


def outside_experts_elements(dims: dict) -> int:
    """Every matrix element but the held experts': the mixers, attention,
    each layer's router and shared MLP, and the vocabulary rows held here
    (the tied embedding is the head)."""
    d = dims["d_model"]
    per_layer = d * dims["n_experts"] + 3 * d * shared_width(dims)
    return (
        n_mamba(dims) * mixer_weight_elements(dims)
        + n_attention(dims) * attention_weight_elements(dims)
        + n_expert_layers(dims) * per_layer
        + dims["vocab_size"] * d
    )


def weight_elements(dims: dict) -> int:
    """Everything this chip holds: the weights outside the experts and every
    held expert of every layer."""
    return outside_experts_elements(dims) + (
        n_expert_layers(dims) * held(dims) * expert_elements(dims)
    )


def decode_step_min_bytes(dims: dict, live_tokens: int, rows: int) -> int:
    """The bytes one decode step must move from HBM: the weights outside the
    experts once, in every layer the held experts at least one of the
    ``rows`` rows keeps (in expectation under even routing), the K/V of every
    live token in the attention layers once, and the state of the ``rows``
    rows read once and written once."""
    experts = n_expert_layers(dims) * experts_touched(dims, rows) * expert_elements(dims)
    return int(
        (outside_experts_elements(dims) + experts) * BF16
        + live_tokens * kv_bytes_per_token(dims)
        + 2 * rows * state_bytes_per_row(dims)
    )
