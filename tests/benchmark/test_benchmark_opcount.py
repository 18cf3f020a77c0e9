"""Counts of operations and bytes from shapes (benchmarks/lib/opcount.py)
against counts made by hand, and the table of peaks."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmarks.lib import opcount
from benchmarks.lib.peaks import peaks_for

CONFIGS = Path(__file__).resolve().parents[2] / "benchmarks" / "configs"


def dims_of(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())["transformer_config"]


def test_attention_flops_by_hand():
    # QK^T and PV: 2 x (2 * 32 * 1024 * 1024 * 128), halved by the mask
    assert opcount.attention_flops(1, 32, 1024, 128, causal=True) == 2 * 32 * 1024**2 * 128
    assert opcount.attention_flops(2, 4, 8, 16, causal=False) == 4 * 2 * 4 * 8 * 8 * 16


def test_mistral_7b_weights_by_hand():
    dims = dims_of("mistral-7b-v02")
    # q and o 4096x4096, k and v 4096x1024; gate, up and down 4096x14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert opcount.layer_weight_elements(dims) == layer == 218_103_808
    assert opcount.weight_elements(dims) == 16 * layer + 2 * 32000 * 4096
    assert opcount.weight_elements(dims) == pytest.approx(3.75e9, rel=0.002)
    assert opcount.weight_elements(dims_of("mistral-7b-v02-tp4")) == pytest.approx(
        7.24e9, rel=0.002
    )


def test_mixtral_layer_holds_eight_experts_and_a_router():
    dims = dims_of("mixtral-8x7b")
    attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert opcount.layer_weight_elements(dims) == (
        attention + 8 * 3 * 4096 * 14336 + 4096 * 8
    )
    # 2.9 GB a layer in bf16, 12.1 GB with the embeddings at 4 layers
    assert 2 * opcount.layer_weight_elements(dims) == pytest.approx(2.9e9, rel=0.01)
    assert 2 * opcount.weight_elements(dims) == pytest.approx(12.1e9, rel=0.01)


def test_decode_step_bytes_for_mistral_7b_by_hand():
    dims = dims_of("mistral-7b-v02")
    # K and V, 16 layers, 8 heads of 128, bf16: 64 KiB a token
    assert opcount.kv_bytes_per_token(dims) == 2 * 16 * 8 * 128 * 2 == 65536
    weights = (16 * 218_103_808 + 32000 * 4096) * 2  # the embedding is not streamed
    assert opcount.decode_step_min_bytes(dims, 0) == weights == 7_241_465_856
    assert opcount.decode_step_min_bytes(dims, 10_000) == weights + 10_000 * 65536
    # 8.8 ms at 819 GB/s: the floor under a decode step of this configuration
    floor_ms = 1000 * weights / peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    assert floor_ms == pytest.approx(8.84, abs=0.01)


# An expert matmul as a v5e trace names it (my chip runs, PR 22): the text of
# the HLO instruction, result first, operands with their shapes.
LAYOUT = "{2,1,0:T(8,128)(2,1)}"


def instruction(result, *operands, opcode="fusion", name="fusion.213"):
    args = ", ".join(f"{o}{LAYOUT} %p.{i}" for i, o in enumerate(operands))
    return f"%{name} = {result}{LAYOUT} {opcode}({args}), kind=kOutput, calls=%fc"


def routed_flops(tokens: int, dims: dict) -> float:
    # a token: top_k experts of three d_model x d_ff matmuls
    return tokens * dims["moe_top_k"] * 3 * 2.0 * dims["d_model"] * dims["d_ff"]


@pytest.mark.parametrize("tokens, rows, ratio", [
    # dropless in groups of 1024: every expert computes every token
    (4096, "4,8,1024", 4.0),   # a prefill: 4 groups x 8 experts x 1024 slots
    (16, "8,16", 4.0),         # a decode step over 16 rows, one group
    (4, "8,8", 8.0),           # fewer rows than the 8 slots an expert gets
    # the moe_exact stance, a group per token: 8 slots an expert for 2 used
    (4096, "4096,8,8", 32.0),
    # a grouped matmul over the routed tokens alone (ROADMAP D5)
    (4096, "8,1024", 1.0),
])
def test_expert_matmul_operations_over_routed_by_hand(tokens, rows, ratio):
    """One layer's three expert matmuls as the trace names them, over what
    the routed tokens need: the count follows the rows the program
    computed, so a program change moves it."""
    dims = dims_of("mixtral-8x7b")
    stack, down = "bf16[4,8,4096,14336]", "bf16[4,8,14336,4096]"
    layer = [
        instruction(f"bf16[{rows},14336]", f"bf16[{rows},4096]", stack, "s32[]"),
        instruction(f"bf16[{rows},14336]", f"bf16[{rows},4096]", stack, "s32[]",
                    f"bf16[{rows},14336]"),
        instruction(f"bf16[{rows},4096]", f"bf16[{rows},14336]", down, "s32[]"),
    ]
    executed = sum(opcount.expert_matmul_flops(text, dims) for text in layer)
    assert executed / routed_flops(tokens, dims) == ratio


def test_expert_matmuls_are_found_by_the_weight_they_take():
    dims = dims_of("mixtral-8x7b")
    flops = 2.0 * 8 * 16 * 4096 * 14336
    # whatever the layout of the result, a sliced or a stacked weight, a
    # trailing axis of 1, a convolution or a kernel
    for text in (
        instruction("bf16[8,14336,16]", "bf16[8,16,4096]", "bf16[8,4096,14336]"),
        instruction("bf16[8,16,14336]", "bf16[8,4096,14336,1]", "bf16[8,16,4096]",
                    opcode="convolution", name="convolution-base-dilated.23"),
        instruction("bf16[8,4096,16]", "bf16[8,16,14336]", "bf16[4,8,14336,4096]"),
        instruction("bf16[8,16,4096]", "bf16[8,16,14336]", "bf16[8,14336,4096]",
                    opcode="custom-call", name="custom-call.7"),
    ):
        assert opcount.expert_matmul_flops(text, dims) == flops, text
    # what moves a weight or never sees one multiplies nothing: the layer's
    # slice out of the stack, its transpose, the router, attention, a copy
    for text in (
        instruction("bf16[8,4096,14336]", "bf16[4,8,4096,14336]", "s32[]"),
        instruction("bf16[8,14336,4096]", "bf16[8,4096,14336]"),
        instruction("f32[16,8]", "f32[16,4096]", "f32[4096,8]"),
        instruction("bf16[16,4096]", "bf16[16,4096]", "bf16[4096,4096]"),
        "%copy.78 = bf16[8,4096,14336]{2,1,0} copy(bf16[8,4096,14336]{2,1,0} %x)",
        "bench.engine_step",
    ):
        assert opcount.expert_matmul_flops(text, dims) is None, text
    # a dense configuration has no expert weight to look for
    assert opcount.expert_matmul_flops(
        instruction("bf16[8,14336,16]", "bf16[8,16,4096]", "bf16[8,4096,14336]"),
        dims_of("mistral-7b-v02"),
    ) is None


def test_the_reader_counts_the_slices_expert_matmuls_over_its_routed_tokens():
    """``moe_flops_over_routed`` end to end on a made-up slice: one step
    that admits a 1024-token prompt and decodes 12 rows, one that decodes
    13, each running the layer's three matmuls in each of 4 layers."""
    import importlib.util

    from benchmarks.lib import driver, harness
    from benchmarks.lib.xplane import Device, Event, Trace

    path = CONFIGS.parent / "layer_metrics" / "moe_flops_over_routed.py"
    spec = importlib.util.spec_from_file_location("_reader_moe", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    cfg = json.loads((CONFIGS / "mixtral-8x7b.json").read_text())
    stack, down = "bf16[4,8,4096,14336]", "bf16[4,8,14336,4096]"

    def layer(rows):
        return [
            instruction(f"bf16[{rows},14336]", f"bf16[{rows},4096]", stack),
            instruction(f"bf16[{rows},14336]", f"bf16[{rows},4096]", stack),
            instruction(f"bf16[{rows},4096]", f"bf16[{rows},14336]", down),
        ]

    names = (
        ["%while.3 = (s32[]) while((s32[]) %t), body=%b"]  # contains the rest
        + (layer("1,8,1024") + layer("8,16")) * 4 + layer("8,16") * 4
        + ["%copy.78 = bf16[16,8,288,16,128]{4,3,2,1,0} copy(bf16[16,8,288,16,128]{4,3,2,1,0} %x)"]
    )
    ops = [Event(names[0], 0.0, 50.0)] + [
        Event(name, 1.0 + i, 1.5 + i) for i, name in enumerate(names[1:])
    ]
    ops.append(Event(layer("8,16")[0], 60.0, 61.0))  # after the slice
    step = lambda i, admitted, delivered: driver.Step(  # noqa: E731
        i, 0.0, 0.0, admitted, delivered, live=16
    )
    run = harness.RunData(
        cfg=cfg, chips=1, peaks=None, memory_peak_bytes=0, e2e={},
        window=(0.0, 51.0), flights=[], loop_steps=[], steps=[],
        compiles_in_window=0, trace=Trace([Device("/device:TPU:0", ops, [])], {}, []),
        slice=(0.0, 55.0),
        # the admitted request's first delivery is the prefill's token
        slice_steps=[step(7, [1024], 1 + 12), step(8, [], 13)],
    )
    executed_rows = 8 * 1024 + 8 * 16 + 8 * 16  # per layer
    routed_rows = 2 * (1024 + 12 + 13)
    assert reader.read(run) == pytest.approx(executed_rows / routed_rows)
    # no trace, or a dense configuration: nothing to read
    run.trace = None
    assert reader.read(run) is None


def test_peaks_are_the_published_v5e_figures_and_nothing_is_guessed():
    peaks = peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["hbm_bytes"] == 16e9
    assert "TPU v5e" in peaks["source"]
    with pytest.raises(KeyError, match="no published peaks for device_kind 'cpu'"):
        peaks_for("cpu")
