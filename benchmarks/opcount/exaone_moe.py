"""What one chip's share of K-EXAONE-236B-A23B must move and compute: K and V
per head in every layer, kept by PAGE in the full layers and in a RING of
``sliding_window`` slots a row in the window layers (``layer_types``), a dense
SwiGLU in the leading layers, and in the others a router over every expert,
the experts HELD here and a shared expert.

The first two functions are the contract every count module keeps
(``benchmarks/opcount/decoder.py``); the rest is what the ``swa`` readers
count with, and the ``moe`` readers (``expert_matmul_rows``,
``experts_touched``, ``expert_elements``, ``n_expert_layers``, ``held_pairs``:
the same contract as ``sarvam_mla.py``). Sizes come from the configuration's
``transformer_config`` group (``dims``). The head's width is
``dims["head_dim"]``, a published key of its own (64 heads of 128 on a hidden
size of 6144): it is never derived, so nothing here calls
``benchmarks/lib/opcount.head_dim``, which divides. Weights, pages and rings
are bf16: 2 bytes an element.
"""

import math

# the expert layer is ``sarvam-105b``'s (the same router, held share and
# shared expert), and so is what the ``moe`` readers count with
from benchmarks.opcount.sarvam_mla import (  # noqa: F401
    BF16,
    expert_elements,
    expert_matmul_flops,
    expert_matmul_rows,
    experts_touched,
    held,
    held_pairs,
    hlo_arrays,
    n_expert_layers,
    routed_pair_flops,
)


def layer_kinds(dims: dict) -> list[str]:
    return list(dims["layer_types"])


def n_window_layers(dims: dict) -> int:
    return layer_kinds(dims).count("sliding_attention")


def n_full_layers(dims: dict) -> int:
    return dims["n_layers"] - n_window_layers(dims)


def attention_weight_elements(dims: dict) -> int:
    """wq, wk, wv and wo of one layer (norm scales left out)."""
    d, dh = dims["d_model"], dims["head_dim"]
    return 2 * d * dims["n_heads"] * dh + 2 * d * dims["n_kv_heads"] * dh


def expert_layer_elements(dims: dict, rows: float) -> float:
    """What one expert layer reads of its weights in a decode step of
    ``rows`` rows: attention, the router (and its bias), the shared experts
    and the held experts touched."""
    return (
        attention_weight_elements(dims)
        + dims["d_model"] * dims["n_experts"] + dims["n_experts"]
        + dims.get("moe_shared_experts", 0) * expert_elements(dims)
        + experts_touched(dims, rows) * expert_elements(dims)
    )


def dense_layer_elements(dims: dict) -> int:
    return attention_weight_elements(dims) + 3 * dims["d_model"] * dims["d_ff"]


def kv_bytes_per_token_layer(dims: dict) -> int:
    """A token's K and V in one layer."""
    return 2 * dims["n_kv_heads"] * dims["head_dim"] * BF16


def full_layers_kv_bytes(dims: dict, live_tokens: float) -> float:
    """The full layers' K/V of the live tokens, read once."""
    return n_full_layers(dims) * live_tokens * kv_bytes_per_token_layer(dims)


def ring_step_bytes(dims: dict, live_tokens: float, rows: float) -> float:
    """What the window layers must move in a decode step: each row's live
    slots read, ``min(live, sliding_window)`` of them (``live_tokens`` the
    sum over the ``rows`` rows: at most ``sliding_window`` a row, and no
    more than the row has), and one slot a row written."""
    slots = min(live_tokens, rows * dims["sliding_window"]) + rows
    return n_window_layers(dims) * slots * kv_bytes_per_token_layer(dims)


def decode_step_min_bytes(dims: dict, live_tokens: int, rows: int) -> int:
    """The bytes one decode step must move from HBM: every weight outside
    the experts once (the leading dense layers, attention, routers, shared
    experts, the head's rows held here), the held experts touched in
    expectation, the full layers' K/V of the live tokens, and each window
    layer's live slots read and one written a row."""
    weights = (
        dims.get("n_dense_layers", 0) * dense_layer_elements(dims)
        + n_expert_layers(dims) * expert_layer_elements(dims, rows)
        + dims["vocab_size"] * dims["d_model"]
    )
    return int(
        weights * BF16 + full_layers_kv_bytes(dims, live_tokens)
        + ring_step_bytes(dims, live_tokens, rows)
    )


def prefill_attention_flops(dims: dict, prompt_tokens: int) -> float:
    """The flash kernel's operations for one admitted prompt: what must be
    computed, so that a kernel that skips the blocks out of a window cannot
    read over 100 %. A full layer is causal over the whole prompt: heads x
    L^2 x 2 x head_dim (q.k and p.v at 2 a multiply-add, half of it masked).
    A window layer scores each query against the ``sliding_window`` keys at
    and before it (fewer at the start): heads x (sum over i of min(i + 1,
    window)) x 4 x head_dim."""
    window = dims["sliding_window"]
    pairs_full = prompt_tokens * (prompt_tokens + 1) / 2
    short = min(prompt_tokens, window)
    pairs_window = short * (short + 1) / 2 + (prompt_tokens - short) * window
    return float(
        dims["n_heads"] * 4 * dims["head_dim"]
        * (n_full_layers(dims) * pairs_full + n_window_layers(dims) * pairs_window)
    )


def ring_elements(dims: dict, pool: dict) -> tuple[int, int]:
    """Elements of one window layer's ring leaf over the pool's rows (K or
    V), and of the leaf stacked over the window layers."""
    one = (
        pool["max_batch"] * dims["n_kv_heads"] * dims["sliding_window"]
        * dims["head_dim"]
    )
    return one, n_window_layers(dims) * one


def touches_ring(text: str, dims: dict, pool: dict) -> bool:
    """Whether an instruction names a bf16 or float32 array that is a window
    layer's ring over the pool's rows, or the stack of them, under any view
    (the leaf is [.., rows, window, kv_heads, head_dim]; the einsums read a
    layer's turned to [rows, kv_heads, window, head_dim]): as many elements,
    and rows of head_dim. ``wk``'s weight [d_model, kv_heads x head_dim] has as many
    elements at 48 rows (48 x 128 = 6144) and rows of 1,024 or 6,144."""
    counts = ring_elements(dims, pool)
    return any(
        kind in ("bf16", "f32") and math.prod(sizes) in counts
        and sizes[-1] == dims["head_dim"]
        for kind, sizes in hlo_arrays(text)
    )
