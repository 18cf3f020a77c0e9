"""What a hybrid of Mamba-2 and attention layers must move and compute
(granite-4.0-h-micro): a SwiGLU MLP in every layer, attention with K/V by
token in the layers ``layer_types`` names ``attention``, a Mamba-2 mixer
with state by row in the ones it names ``mamba``, and a head tied to the
embedding.

The first two functions are the contract every count module keeps
(``benchmarks/opcount/decoder.py``); the rest is what the ``ssm`` readers
count with. Sizes come from the configuration's ``transformer_config``
group (``dims``). Weights, K/V and what a row keeps (the recurrent state,
the conv tail) are in the model's dtype, bf16: 2 bytes an element. The
prefill's scan carries its state in float32 and hands it back so.
"""

import math
import re

from benchmarks.lib import opcount

BF16, F32 = 2, 4
# the widest chunk of the prefill's scan (``mamba_chunk_size`` where the
# group leaves it out)
CHUNK = 256


def n_mamba(dims: dict) -> int:
    return dims["layer_types"].count("mamba")


def n_attention(dims: dict) -> int:
    return dims["layer_types"].count("attention")


def inner(dims: dict) -> int:
    return dims["mamba_n_heads"] * dims["mamba_d_head"]


def conv_channels(dims: dict) -> int:
    """x, B and C go through the conv together."""
    return inner(dims) + 2 * dims.get("mamba_n_groups", 1) * dims["mamba_d_state"]


def mixer_weight_elements(dims: dict) -> int:
    """One mamba layer's mixer: in_proj (z, xBC, dt), out_proj, the conv
    and its bias, A_log, D, dt_bias and the gated norm's scale."""
    d, heads = dims["d_model"], dims["mamba_n_heads"]
    channels = conv_channels(dims)
    return (
        d * (inner(dims) + channels + heads) + inner(dims) * d
        + dims.get("mamba_d_conv", 4) * channels + channels
        + 3 * heads + inner(dims)
    )


def attention_weight_elements(dims: dict) -> int:
    d = dims["d_model"]
    dh, nh, kvh = opcount.head_dim(dims), dims["n_heads"], opcount.kv_heads(dims)
    return d * nh * dh + 2 * d * kvh * dh + nh * dh * d


def weight_elements(dims: dict) -> int:
    """Every matrix element: the layers (norm scales ln1, ln2 left out, as
    ``lib/opcount.py`` leaves them) and the embedding, once: it is the head
    too."""
    mlp = 3 * dims["d_model"] * dims["d_ff"]
    return (
        n_mamba(dims) * (mixer_weight_elements(dims) + mlp)
        + n_attention(dims) * (attention_weight_elements(dims) + mlp)
        + dims["vocab_size"] * dims["d_model"]
    )


def kv_bytes_per_token(dims: dict) -> int:
    """K and V of one token over the attention layers, as the pool stores
    them."""
    return (
        2 * n_attention(dims) * opcount.kv_heads(dims) * opcount.head_dim(dims)
        * BF16
    )


def ssm_elements_per_row(dims: dict) -> int:
    """One mamba layer's recurrent state for one row: heads x head size x
    state."""
    return dims["mamba_n_heads"] * dims["mamba_d_head"] * dims["mamba_d_state"]


def ssm_bytes_per_row(dims: dict) -> int:
    """The same state as the pool keeps it."""
    return ssm_elements_per_row(dims) * BF16


def state_bytes_per_row(dims: dict) -> int:
    """What one row keeps over all mamba layers: the state and the conv
    tail (the last d_conv - 1 conv inputs), both bf16."""
    tail = (dims.get("mamba_d_conv", 4) - 1) * conv_channels(dims)
    return n_mamba(dims) * (ssm_bytes_per_row(dims) + tail * BF16)


def decode_step_min_bytes(dims: dict, live_tokens: int, rows: int) -> int:
    """The bytes one decode step must move from HBM: every weight once (the
    tied embedding as the head), the K/V of every live token in the
    attention layers once, and the state of the ``rows`` rows decoding read
    once and written once."""
    return (
        weight_elements(dims) * BF16
        + live_tokens * kv_bytes_per_token(dims)
        + 2 * rows * state_bytes_per_row(dims)
    )


def prefill_attention_flops(dims: dict, prompt_tokens: int) -> float:
    """The attention kernel's operations for one admitted prompt: causal
    attention in the attention layers alone."""
    return n_attention(dims) * opcount.attention_flops(
        1, dims["n_heads"], prompt_tokens, opcount.head_dim(dims), causal=True
    )


def scan_chunks(dims: dict, width: int) -> tuple[int, int]:
    """(chunk length, number of chunks) of the prefill's scan over a prompt
    padded to ``width``: one chunk of the whole width up to the chunk size,
    chunks of that size beyond."""
    chunk = min(dims.get("mamba_chunk_size", CHUNK), width)
    return chunk, -(-width // chunk)


def ssd_prefill_flops(dims: dict, width: int) -> float:
    """The operations of the chunked scan's three matmuls that carry heads x
    head size, for one prompt padded to ``width`` over all mamba layers: in
    a chunk the masked scores times x (2 Q a token and inner element), what
    a chunk adds to the state and what the state it began with gives (2 N
    each). The scores themselves (C B^T, shared by a group's heads) are a
    sixty-fourth of that and are left out, here and in the time."""
    chunk, n = scan_chunks(dims, width)
    return float(
        n_mamba(dims) * n * chunk * inner(dims)
        * 2 * (chunk + 2 * dims["mamba_d_state"])
    )


def ssd_prefill_bytes(dims: dict, width: int) -> int:
    """The bytes the scan must move: x in and y out in bf16, B and C, and
    the state after the last chunk written once in float32. (The chunks'
    states between need not leave the chip: what the program writes and
    reads of them is what its share falls short by.)"""
    chunk, n = scan_chunks(dims, width)
    groups_state = dims.get("mamba_n_groups", 1) * dims["mamba_d_state"]
    return n_mamba(dims) * (
        n * chunk * (2 * inner(dims) + 2 * groups_state) * BF16
        + ssm_elements_per_row(dims) * F32
    )


_ARRAY = re.compile(r"\b([a-z]+\d+)\[([\d,]+)\]")


def hlo_arrays(text: str) -> list[tuple[str, list[int]]]:
    """(dtype, dimensions) of every array the text of an HLO instruction
    names, result and operands alike, as a device trace carries it."""
    return [
        (m.group(1), [int(x) for x in m.group(2).split(",")])
        for m in _ARRAY.finditer(text)
    ]


def touches(text: str, dtype: str, element_counts) -> bool:
    """Whether an instruction names an array of ``dtype`` with one of
    ``element_counts`` elements: counted by elements, so that a reshape of
    the array does not hide it."""
    return any(
        kind == dtype and math.prod(dims) in element_counts
        for kind, dims in hlo_arrays(text)
    )
