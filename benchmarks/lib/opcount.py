"""Operations and bytes, computed from shapes.

Nothing here runs on a device or reads a clock: these are counts, and a
roofline share is such a count over a time taken from the device trace.
What the algorithm needs comes from the sizes of
``benchmarks/configs/<config>.json``'s ``transformer_config`` group
(``dims``), so that a new configuration needs no new code; what the program
executed comes from the shapes in an instruction's name in the trace
(``expert_matmul_flops``), never from a restatement of the program's rules.
"""

from __future__ import annotations

import math
import re

BF16 = 2  # bytes


def attention_flops(B: int, H: int, L: int, D: int, causal: bool) -> float:
    """QK^T and PV: two matmuls of 2*B*H*L*L*D operations each; a causal
    mask needs half. (Copied from scripts/bench-flash-attention.py.)"""
    flops = 2 * 2 * B * H * L * L * D
    return flops / 2 if causal else flops


def head_dim(dims: dict) -> int:
    return dims["d_model"] // dims["n_heads"]


def kv_heads(dims: dict) -> int:
    return dims.get("n_kv_heads") or dims["n_heads"]


def layer_weight_elements(dims: dict) -> int:
    """Matrix elements of one decoder layer (norm scales left out: 2*d)."""
    d, ff = dims["d_model"], dims["d_ff"]
    dh, nh, kvh = head_dim(dims), dims["n_heads"], kv_heads(dims)
    attention = d * nh * dh + 2 * d * kvh * dh + nh * dh * d
    mlp = 3 * d * ff
    experts = dims.get("n_experts", 0)
    if experts:
        return attention + experts * mlp + d * experts
    return attention + mlp


def weight_elements(dims: dict) -> int:
    """All matrix elements: the layers, the embedding and the output head."""
    return (
        dims["n_layers"] * layer_weight_elements(dims)
        + 2 * dims["vocab_size"] * dims["d_model"]
    )


def kv_bytes_per_token(dims: dict, bytes_per_element: int = BF16) -> int:
    """K and V of one token over every layer, as the pool stores them."""
    return (
        2 * dims["n_layers"] * kv_heads(dims) * head_dim(dims)
        * bytes_per_element
    )


def decode_step_min_bytes(
    dims: dict, live_tokens: int, bytes_per_element: int = BF16
) -> int:
    """The bytes one decode step MUST move from HBM: every layer's weights
    and the output head once (the embedding is read a row per sequence,
    which is left out), and the K/V of every live token once. An expert
    model at these batch sizes touches every expert of every layer, so all
    of them count. What the program moves beyond this (the gather's copy,
    dead table slots, f32 K) is what the share falls short by."""
    weights = (
        dims["n_layers"] * layer_weight_elements(dims)
        + dims["vocab_size"] * dims["d_model"]
    ) * bytes_per_element
    return weights + live_tokens * kv_bytes_per_token(dims, bytes_per_element)


_SHAPE = re.compile(r"\b[a-z]+\d+\[([\d,]*)\]")
_OPCODE = re.compile(r" (fusion|convolution|custom-call)\(")


def expert_matmul_flops(hlo_text: str, dims: dict) -> float | None:
    """The operations of one executed expert matmul, read from the text of
    its HLO instruction as a device trace names it (result shape, opcode,
    operands with their shapes), or None where the instruction is no
    expert matmul. It is one where a ``fusion``, ``convolution`` or
    ``custom-call`` takes an expert weight as an operand: a shape that,
    without dimensions of 1 and a leading layer axis, is ``n_experts`` x
    ``d_model`` x ``d_ff`` in some order. A matmul is 2 x (elements of the
    result) x (the contracted width), and the contracted width is
    ``d_model`` where the result has a ``d_ff`` axis (gate, up) and
    ``d_ff`` where it has not (down). So the rows counted are the rows the
    program computed, whatever it padded them to. An instruction whose
    result is itself shaped like the weight (a layer's slice of the stack,
    a transpose) moves the weight and multiplies nothing."""
    opcode = _OPCODE.search(hlo_text)
    if opcode is None or not dims.get("n_experts"):
        return None
    result = _SHAPE.search(hlo_text[:opcode.start()])
    if result is None:
        return None
    d, ff = dims["d_model"], dims["d_ff"]
    weight = sorted([dims["n_experts"], d, ff])
    for operand in _SHAPE.finditer(hlo_text, opcode.end()):
        sizes = [int(x) for x in operand.group(1).split(",") if x and x != "1"]
        if sorted(sizes) == weight or (
            sizes[:1] == [dims["n_layers"]] and sorted(sizes[1:]) == weight
        ):
            break
    else:
        return None
    out = [int(x) for x in result.group(1).split(",") if x and x != "1"]
    if sorted(out) == weight or not (d in out or ff in out):
        return None
    return 2.0 * math.prod(out) * (d if ff in out else ff)
