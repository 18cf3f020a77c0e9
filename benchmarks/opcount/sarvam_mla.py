"""What one chip's share of sarvam-105b must move and compute: latent
attention in every layer (a token keeps one latent of ``kv_lora_rank`` and
one rotary key of ``qk_rope_head_dim``), a dense SwiGLU in the leading
layers, and in the others a router over every expert, the experts HELD here
and a shared expert.

The first two functions are the contract every count module keeps
(``benchmarks/opcount/decoder.py``); the rest is what the ``mla`` and ``moe``
readers count with. Sizes come from the configuration's ``transformer_config``
group (``dims``). Weights and the latent are bf16: 2 bytes an element.
"""

import math
import re

BF16 = 2

_ARRAY = re.compile(r"\b([a-z]+\d+)\[([\d,]+)\]")
_OPCODE = re.compile(r" (fusion|convolution|custom-call)\(")


def n_expert_layers(dims: dict) -> int:
    return dims["n_layers"] - dims.get("n_dense_layers", 0)


def held(dims: dict) -> int:
    held_here = dims.get("moe_held_experts")
    return dims["n_experts"] if held_here is None else held_here


def expert_width(dims: dict) -> int:
    return dims.get("moe_d_ff") or dims["d_ff"]


def attention_weight_elements(dims: dict) -> int:
    """wq, w_kva, w_kvb and wo of one layer (norm scales left out)."""
    d, heads, rank = dims["d_model"], dims["n_heads"], dims["kv_lora_rank"]
    nope, rot, v = (
        dims["qk_nope_head_dim"], dims["qk_rope_head_dim"], dims["v_head_dim"]
    )
    return (
        d * heads * (nope + rot) + d * (rank + rot)
        + rank * heads * (nope + v) + heads * v * d
    )


def expert_elements(dims: dict) -> int:
    """One expert's three matrices."""
    return 3 * dims["d_model"] * expert_width(dims)


def experts_touched(dims: dict, rows: float) -> float:
    """Held experts at least one of ``rows`` tokens keeps, in expectation
    under even routing: held x (1 - (1 - top_k / routed)^rows)."""
    share = dims["moe_top_k"] / dims["n_experts"]
    return held(dims) * (1.0 - (1.0 - share) ** rows)


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


def latent_bytes_per_token(dims: dict) -> int:
    """What a token keeps over all layers, at the PUBLISHED width (the
    latent and the rotary key), whatever the pool pads a slot to."""
    return (
        dims["n_layers"] * (dims["kv_lora_rank"] + dims["qk_rope_head_dim"])
        * BF16
    )


def decode_step_min_bytes(dims: dict, live_tokens: int, rows: int) -> int:
    """The bytes one decode step must move from HBM: the leading dense
    layers, the expert layers as ``expert_layer_elements`` counts them for
    the ``rows`` rows decoding, the head's rows held here, and the latent of
    every live token once."""
    weights = (
        dims.get("n_dense_layers", 0) * dense_layer_elements(dims)
        + n_expert_layers(dims) * expert_layer_elements(dims, rows)
        + dims["vocab_size"] * dims["d_model"]
    )
    return int(weights * BF16) + live_tokens * latent_bytes_per_token(dims)


def prefill_attention_flops(dims: dict, prompt_tokens: int) -> float:
    """The flash kernel's operations for one admitted prompt: causal
    attention in every layer at a q.k of qk_nope + qk_rope and a value of
    v_head_dim: heads x L^2 x (q.k width + v width) (2 a multiply-add, half
    of it masked)."""
    qk = dims["qk_nope_head_dim"] + dims["qk_rope_head_dim"]
    return float(
        dims["n_layers"] * dims["n_heads"] * prompt_tokens ** 2
        * (qk + dims["v_head_dim"])
    )


def latent_attention_flops(dims: dict, rows: float, live_tokens: float) -> float:
    """The absorbed form's operations in one decode step, a layer: every
    head of every row scores its latent-wide query against each of the row's
    live tokens (kv_lora_rank + qk_rope) and weighs their latents
    (kv_lora_rank): heads x live x (576 + 512) x 2. ``live_tokens`` is the
    sum over the rows."""
    rank, rot = dims["kv_lora_rank"], dims["qk_rope_head_dim"]
    return 2.0 * dims["n_heads"] * live_tokens * (2 * rank + rot)


def latent_layer_bytes(dims: dict, live_tokens: float) -> float:
    """One layer's latents of the live tokens, read once."""
    return live_tokens * (dims["kv_lora_rank"] + dims["qk_rope_head_dim"]) * BF16


def hlo_arrays(text: str) -> list[tuple[str, list[int]]]:
    """(dtype, dimensions) of every array the text of an HLO instruction
    names, result first, as a device trace carries it."""
    return [
        (m.group(1), [int(x) for x in m.group(2).split(",")])
        for m in _ARRAY.finditer(text)
    ]


def _is_expert_stack(sizes: list[int], dims: dict) -> bool:
    """Whether an array is the held experts' weights, of one layer or of
    all (layers and experts apart, or merged into the grouped matmul's
    groups): d_model x expert width x (held, or layers x held) elements in
    those dimensions."""
    d, f = dims["d_model"], expert_width(dims)
    sizes = [x for x in sizes if x != 1]
    if sorted(sizes[-2:]) != sorted([d, f]):
        return False
    groups = math.prod(sizes[:-2])
    return groups in (held(dims), n_expert_layers(dims) * held(dims))


def expert_matmul_rows(text: str, dims: dict) -> int | None:
    """The rows one executed expert matmul computed, read from its HLO
    instruction as a device trace names it, or None where the instruction
    is no expert matmul: a ``fusion``, ``convolution`` or ``custom-call``
    (the grouped matmul is a Mosaic call) that takes the held experts'
    weights as an operand and whose result is [rows, d_model] or [rows,
    expert width]. The rows are the sorted buffer's, whatever the groups
    cover of it. An instruction whose result is itself shaped like the
    weights moves them and multiplies nothing."""
    opcode = _OPCODE.search(text)
    if opcode is None:
        return None
    result = _ARRAY.search(text[:opcode.start()])
    if result is None:
        return None
    out = [int(x) for x in result.group(2).split(",") if x != "1"]
    operands = hlo_arrays(text[opcode.end():])
    if not any(_is_expert_stack(sizes, dims) for _, sizes in operands):
        return None
    if _is_expert_stack(out, dims) or len(out) != 2:
        return None
    if out[1] not in (dims["d_model"], expert_width(dims)):
        return None
    return out[0]


def expert_matmul_flops(text: str, dims: dict) -> float | None:
    """``expert_matmul_rows`` as operations: 2 x rows x d_model x expert
    width, whichever of the three matmuls it is."""
    rows = expert_matmul_rows(text, dims)
    if rows is None:
        return None
    return 2.0 * rows * dims["d_model"] * expert_width(dims)


def routed_pair_flops(dims: dict, pairs: float) -> float:
    """The operations of ``pairs`` (token, expert) pairs through an
    expert's three matmuls."""
    return pairs * 3 * 2.0 * dims["d_model"] * expert_width(dims)


def held_pairs(dims: dict, tokens: float) -> float:
    """The pairs ``tokens`` route to the held experts over all expert
    layers, in expectation under even routing (the count the program's step
    record and ``serve.admit`` span carry as ``held_expert_pairs``)."""
    return (
        tokens * n_expert_layers(dims) * dims["moe_top_k"]
        * held(dims) / dims["n_experts"]
    )


def touches(text: str, dtype: str, element_counts) -> bool:
    """Whether an instruction names an array of ``dtype`` with one of
    ``element_counts`` elements."""
    return any(
        kind == dtype and math.prod(sizes) in element_counts
        for kind, sizes in hlo_arrays(text)
    )


def latent_slot_width(dims: dict) -> int:
    """A slot of the pool: the published width padded to whole lane tiles
    of 128 (``TransformerConfig.latent_width``)."""
    return -(-(dims["kv_lora_rank"] + dims["qk_rope_head_dim"]) // 128) * 128
