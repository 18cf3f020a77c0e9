"""What PR 33 added to the benchmark: the configuration ``sarvam-105b``
against the catalog's copy of its published ``config.json``, its count
module against the arithmetic by hand, the three ``mla`` / ``moe`` readers on
made-up instruction names, the mix ``assist64``, and a tiny cell of the same
architecture through the harness on the CPU (new files and entries alone).
"""

from __future__ import annotations

import copy
import dataclasses
import json
import types
from pathlib import Path

import numpy as np
import pytest

from benchmarks.lib import driver, harness, spec, traffic, xplane

from test_benchmark_harness import ticking_clock, write_root

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CFG = json.loads((ROOT / "benchmarks/configs/sarvam-105b.json").read_text())
DIMS = CFG["transformer_config"]
COUNTS = spec.opcount(ROOT, BENCH, "sarvam_mla")

YARN = {
    "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
    "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
    "type": "deepseek_yarn",
}
# the ``config`` of the catalog's row (model-configs guide,
# architectures.jsonl), verbatim
CATALOG = {
    "attn_implementation": None, "default_theta": 10000,
    "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "sarvam_mla",
    "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_shared_experts": 1, "q_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": YARN, "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
    "vocab_size": 262144,
}
REDUCED = {
    "num_hidden_layers": (32, 6), "num_experts": (128, 32),
    "vocab_size": (262144, 65536), "max_position_embeddings": (131072, 3072),
}


# --------------------------------------------------- the configuration file


def test_the_file_keeps_every_published_key_but_the_four_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "sarvam-105b")
    assert entry["reduced"] == list(REDUCED) == [r["key"] for r in CFG["reduced"]]
    assert entry["source"] == CFG["source"] == (
        "https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json"
    )
    for key, value in CATALOG.items():
        if key not in REDUCED:
            assert CFG[key] == value, key
    for cut in CFG["reduced"]:
        assert (cut["published"], cut["run"]) == REDUCED[cut["key"]]
        assert CATALOG[cut["key"]] == cut["published"] and CFG[cut["key"]] == cut["run"]
    # the floors of a cut: a whole period and four layers past the dense one,
    # 8 experts, an eighth of the vocabulary; the router keeps its width
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] >= 4
    assert CFG["num_experts"] >= 8 and CFG["vocab_size"] * 8 >= 262144
    assert CFG["router_width"] == 128 == DIMS["n_experts"]
    assert CFG["pool"] == {
        "max_batch": 64, "page_size": 16, "max_pages_per_seq": 192, "n_pages": 12289
    }
    assert CFG["pool"]["max_pages_per_seq"] * CFG["pool"]["page_size"] == 3072
    assert CFG["reference"] == CFG["opcount"] == "sarvam_mla"
    assert CFG["chips"] == 1 and CFG["mesh"] is None
    assert "FOUR" in CFG["deployment"] and "experts 0 to 31" in CFG["deployment"]
    said = " ".join(CFG["assumed"])
    for reading in ("sigmoid", "use_qk_norm", "rotate-half", "LAST ROW",
                    "one routing group", "640", "first_k_dense_replace"):
        assert reading in said, reading


def test_every_new_field_is_held_to_its_published_key():
    from bee_code_interpreter_tpu.models import transformer as T

    base = {f.name for f in dataclasses.fields(T.TransformerConfig)}
    new = {
        "rms_norm_eps", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "qk_norm", "rope_yarn", "n_dense_layers",
        "moe_held_experts", "moe_held_from", "moe_d_ff", "moe_shared_experts",
        "moe_routed_scaling", "moe_router_bias",
    }
    assert new <= base
    held = set({**harness.KEYMAP, **CFG["keymap"]}.values())
    assert new <= held and new <= set(DIMS)
    config = harness.transformer_config(T, CFG)
    assert hash(config) and config.yarn == YARN
    assert (config.held_experts, config.n_experts, config.moe_top_k) == (32, 128, 8)
    assert (config.qk_head_dim, config.latent_width, config.expert_ff_dim) == (192, 640, 2048)
    assert config.moe_exact and config.rms_norm_eps == 1e-6
    assert T._score_scale(config) == pytest.approx(0.13523, abs=1e-5)
    # a group that says otherwise than the published key is refused by name
    for field, wrong_value, words in (
        ("kv_lora_rank", 256, "kv_lora_rank=512 but"),
        ("moe_held_experts", 16, "num_experts=32 but"),
        ("moe_routed_scaling", 1.0, "routed_scaling_factor=2.5 but"),
        ("rope_yarn", {**YARN, "factor": 4}, "rope_scaling=.* but"),
    ):
        wrong = copy.deepcopy(CFG)
        wrong["transformer_config"][field] = wrong_value
        with pytest.raises(harness.CellError, match=words):
            harness.transformer_config(T, wrong)


def test_config_from_hf_takes_the_catalogs_keys():
    from bee_code_interpreter_tpu.models import transformer as T
    from bee_code_interpreter_tpu.models.hf_loader import config_from_hf

    loaded = config_from_hf(CATALOG)
    assert (loaded.n_layers, loaded.vocab_size, loaded.max_seq_len) == (32, 262144, 131072)
    assert loaded.held_experts == loaded.n_experts == 128
    # cut as the file cuts it, it is the configuration the cell runs
    assert dataclasses.replace(
        loaded, n_layers=6, vocab_size=65536, max_seq_len=3072,
        moe_held_experts=32,
    ) == harness.transformer_config(T, CFG)
    for key, value, words in (
        ("q_lora_rank", 1536, "q_lora_rank 1536 unsupported"),
        ("n_group", 8, "n_group 8 unsupported"),
        ("rope_scaling", {"type": "linear", "factor": 4}, "rope_scaling type 'linear'"),
    ):
        with pytest.raises(ValueError, match=words):
            config_from_hf({**CATALOG, key: value})


def test_an_epsilon_is_accepted_where_the_group_states_it_and_refused_elsewhere():
    """What the frozen case ``test_what_the_program_fixes_is_refused_until_
    it_has_the_field[rms_norm_eps...]`` says of a later program, held
    against the program itself now that it has the field (the case's first
    half asserts the field away: ``tests/conftest.py`` ``RETIRED_CASES``)."""
    from bee_code_interpreter_tpu.models import transformer as T
    from test_benchmark_counts import tiny

    stated = harness.transformer_config(
        T, tiny({"rms_norm_eps": 1e-6}, rms_norm_eps=1e-6)
    )
    assert stated.rms_norm_eps == 1e-6
    # published as 1e-6, and the group sets none or another
    with pytest.raises(harness.CellError, match="rms_norm_eps 1e-06 is not the 1e-05"):
        harness.transformer_config(T, tiny(rms_norm_eps=1e-6))
    with pytest.raises(
        harness.CellError, match="rms_norm_eps=.* but transformer_config"
    ):
        harness.transformer_config(
            T, tiny({"rms_norm_eps": 1e-5}, rms_norm_eps=1e-6)
        )
    # the value the program had fixed passes, set or left out
    assert harness.transformer_config(T, tiny(rms_norm_eps=1e-5)).rms_norm_eps == 1e-5
    untold = tiny()
    untold.pop("rms_norm_eps", None)
    assert harness.transformer_config(T, untold).rms_norm_eps == 1e-5


# ------------------------------------------------- the counts, by hand


def test_counts_are_the_arithmetic_by_hand():
    attention = 4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256 + 64 * 128 * 4096
    assert COUNTS.attention_weight_elements(DIMS) == attention == 94_633_984
    expert = 3 * 4096 * 2048
    assert COUNTS.expert_elements(DIMS) == expert == 25_165_824
    dense = attention + 3 * 4096 * 16384
    assert COUNTS.dense_layer_elements(DIMS) == dense == 295_960_576
    # one expert layer here with every held expert read: ISSUE 33's table
    router = 4096 * 128 + 128
    whole = attention + router + expert + 32 * expert
    assert whole / 1e6 == pytest.approx(925.6, abs=0.05)
    vocabulary = 65536 * 4096
    assert (dense + 5 * whole + 2 * vocabulary) / 1e9 == pytest.approx(5.461, abs=0.001)
    assert 2 * (dense + 5 * whole + 2 * vocabulary) / 2**30 == pytest.approx(10.17, abs=0.01)
    # at 64 rows 98.4 % of the held experts are touched
    touched = 32 * (1 - (1 - 8 / 128) ** 64)
    assert COUNTS.experts_touched(DIMS, 64) == pytest.approx(touched)
    assert touched / 32 == pytest.approx(0.984, abs=0.001)
    assert COUNTS.experts_touched(DIMS, 0) == 0
    # 6,912 bytes a live token: the published 576, not the pool's 640
    assert COUNTS.latent_bytes_per_token(DIMS) == 6 * 576 * 2 == 6912
    assert COUNTS.latent_slot_width(DIMS) == 640
    step = COUNTS.decode_step_min_bytes(DIMS, 83_000, 64)
    weights = dense + 5 * (attention + router + expert + touched * expert) + vocabulary
    assert step == int(2 * weights) + 83_000 * 6912
    # 10.26 GB of weights (ISSUE 33's 10.39 reads all 32 held experts, 8.05
    # GB of it; 98.4 % of them is 7.93) beside 0.57 GB of latents
    assert step / 1e9 == pytest.approx(10.83, abs=0.02)
    assert 2 * (weights + 5 * (32 - touched) * expert) / 1e9 == pytest.approx(10.39, abs=0.02)
    assert 2 * 5 * touched * expert / 1e9 == pytest.approx(7.93, abs=0.02)
    assert 83_000 * 6912 / 1e9 == pytest.approx(0.57, abs=0.01)
    # the flash kernel: heads x L^2 x (192 + 128), 6 layers
    assert COUNTS.prefill_attention_flops(DIMS, 2048) == 6 * 64 * 2048**2 * 320
    # the absorbed form, a layer: heads x live x (576 + 512) x 2
    assert COUNTS.latent_attention_flops(DIMS, 64, 83_000) == 2 * 64 * 83_000 * 1088
    assert COUNTS.latent_layer_bytes(DIMS, 83_000) == 83_000 * 1152
    # a token's pairs at the held experts: 5 layers x 8 x 32 / 128
    assert COUNTS.held_pairs(DIMS, 1000) == 10_000
    assert COUNTS.routed_pair_flops(DIMS, 1) == 3 * 2 * 4096 * 2048


def test_assist64_fits_a_row_and_the_pool():
    mix = traffic.load_mix(ROOT / "benchmarks/traffic/assist64.json")
    pool = CFG["pool"]
    assert traffic.longest_request(mix) == 3072 == pool["max_pages_per_seq"] * pool["page_size"]
    assert mix["clients"] == pool["max_batch"] == 64
    assert traffic.prompt_lengths(mix) == [512, 1024, 2048]
    assert not any(p % pool["page_size"] for p in traffic.prompt_lengths(mix))
    # a full batch of the longest requests fits the pages (one is scratch)
    assert 64 * 192 <= pool["n_pages"] - 1
    entry = next(w for w in BENCH["workloads"] if w["name"] == "sarvam105b_assist64")
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("sarvam-105b", "assist64", 1)
    for metric in ("mla_decode_roofline", "moe_expert_roofline", "moe_held_flops_over_routed"):
        listed = next(m for m in BENCH["per_layer"] if m["name"] == metric)
        assert listed["workloads"] == ["sarvam105b_assist64"]
        assert listed["source"] == "device_trace"


# --------------------------------------- the readers, on made-up traces


def reader(metric: str):
    return spec.layer_metric_reader(ROOT, BENCH, metric)


TILED = "{1,0:T(8,128)(2,1)}"
# the grouped matmuls as XLA names them on the chip: the sorted buffer, the
# stack of 5 layers x 32 experts merged into the groups
GATE = (
    "%ragged-dot-none.1 = bf16[192,2048]" + TILED + " custom-call(s32[1]{0} %n, "
    "s32[161]{0} %o, s32[167]{0} %g, s32[167]{0} %t, s32[1]{0} %n, "
    "bf16[192,4096]" + TILED + " %x, bf16[160,4096,2048]{2,1,0:T(8,128)(2,1)} %w), "
    'custom_call_target="tpu_custom_call"'
)
DOWN = (
    "%ragged-dot-none = bf16[192,4096]" + TILED + " custom-call(s32[1]{0} %n, "
    "bf16[192,2048]" + TILED + " %y, bf16[160,2048,4096]{2,1,0:T(8,128)(2,1)} %w), "
    'custom_call_target="tpu_custom_call"'
)
KERNEL = (
    "%paged_decode_attention.3 = (bf16[64,1,64,512]{3,2,1,0}, "
    "bf16[6,12289,1,16,640]{4,3,2,1,0}) custom-call(s32[64,192]{1,0} %bt, "
    'bf16[6,12289,1,16,640]{4,3,2,1,0} %ckv), custom_call_target="tpu_custom_call"'
)


def event(name, start, seconds, **stats):
    return xplane.Event(name, start, start + seconds, tuple(stats.items()))


def made_up_run(ops, steps=(), admitted=None, counts=COUNTS, peaks=True,
                records=(), live=1000, cfg=CFG):
    """A traced run of the cell as the readers see it: ``steps`` are the
    indices of decode-only steps of a second each (step i spans [i, i+1)),
    ``admitted`` maps a step's index to the prompts it admitted; 64 rows
    decode, each with ``live`` tokens in the pool."""
    admitted = admitted or {}
    indices = sorted(set(steps) | set(admitted))
    spans = [event(driver.SPAN_STEP, float(i), 1.0, i=i) for i in indices]
    trace = xplane.Trace(
        [xplane.Device("/device:TPU:0", sorted(ops, key=lambda e: (e.start, -e.end)), [])],
        {driver.SPAN_STEP: spans}, [],
    )
    slice_steps = [
        driver.Step(i, float(i), i + 0.9, admitted.get(i, []), 64, live=64)
        for i in indices
    ]
    flights = [
        driver.Flight(
            request=types.SimpleNamespace(prompt=np.zeros(live - 1, np.int32)),
            ticket=n, logprobs=False, t_submit=-2.0, t_first=-1.0,
            deliveries=[(-1.0, 1)],
        )
        for n in range(64)
    ]
    return harness.RunData(
        cfg=cfg, chips=1,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9} if peaks else None,
        memory_peak_bytes=0, e2e={}, window=(0.0, 60.0), flights=flights,
        loop_steps=slice_steps, steps=list(records), compiles_in_window=0,
        trace=trace,
        slice=(float(indices[0]), float(indices[-1] + 1)) if indices else None,
        slice_steps=slice_steps, counts=counts,
    )


def test_an_expert_matmul_is_told_by_the_held_experts_stack():
    assert COUNTS.expert_matmul_rows(GATE, DIMS) == 192
    assert COUNTS.expert_matmul_rows(DOWN, DIMS) == 192
    assert COUNTS.expert_matmul_flops(GATE, DIMS) == 2 * 192 * 4096 * 2048
    # one layer's experts, layers and experts apart, a fusion over the stack
    one = GATE.replace("bf16[160,4096,2048]", "bf16[32,4096,2048]")
    apart = GATE.replace("bf16[160,4096,2048]", "bf16[5,32,4096,2048]")
    fused = GATE.replace("custom-call(", "fusion(")
    assert [COUNTS.expert_matmul_rows(t, DIMS) for t in (one, apart, fused)] == [192] * 3
    for not_one in (
        # the shared expert and the dense layer multiply other weights
        "%fusion.5 = bf16[64,2048]" + TILED + " fusion(bf16[64,4096]{1,0} %x, bf16[5,4096,2048]{2,1,0} %ws)",
        "%fusion.6 = bf16[64,16384]" + TILED + " fusion(bf16[64,4096]{1,0} %x, bf16[1,4096,16384]{2,1,0} %w)",
        # the metadata of the grouped matmul; a copy of the stack; the kernel
        "%ragged-dot-metadata = (s32[161]{0}, s32[167]{0}) custom-call(s32[160]{0} %gs)",
        "%copy.1 = bf16[160,4096,2048]{2,1,0} copy(bf16[160,4096,2048]{2,1,0} %w)",
        KERNEL,
    ):
        assert COUNTS.expert_matmul_rows(not_one, DIMS) is None, not_one


def test_moe_expert_roofline_times_the_expert_matmuls_of_decode_steps():
    touched = 32 * (1 - (1 - 8 / 128) ** 64)
    least_s = 5 * touched * 25_165_824 * 2 / 819e9  # 9.68 ms
    ops = []
    for step in (3, 4, 5):
        ops += [
            event(GATE, step + 0.1, 0.004), event(GATE.replace(".1 =", ".2 ="), step + 0.2, 0.004),
            event(DOWN, step + 0.3, 0.004),
            event("%fusion.9 = bf16[64,65536]{1,0} fusion(bf16[4096,65536]{1,0} %head)", step + 0.5, 0.1),
        ]
    run = made_up_run(ops, steps=(3, 4, 5))
    assert reader("moe_expert_roofline").read(run) == pytest.approx(
        100.0 * least_s / 0.012, rel=1e-9
    )
    assert least_s * 1e3 == pytest.approx(9.68, abs=0.01)


def test_mla_decode_roofline_times_the_kernel_or_the_slice_path():
    read = reader("mla_decode_roofline").read
    live = 64 * 1000
    by_bytes = 6 * live * 1152 / 819e9
    by_flops = 6 * 2 * 64 * live * 1088 / 197e12
    assert by_bytes > by_flops  # the latent is read at two operations a byte a head
    kernel = [event(KERNEL, s + 0.1 * i, 0.0005) for s in (3, 4) for i in range(1, 7)]
    assert read(made_up_run(kernel, steps=(3, 4))) == pytest.approx(
        100.0 * by_bytes / 0.003, rel=1e-9
    )
    # the slice path: a layer's slice scattered into, the table's width gathered
    sliced = [
        event("%scatter.1 = bf16[12289,16,640]{2,1,0} scatter(bf16[12289,16,640]{2,1,0} %s)", 3.1, 0.002),
        event("%fusion.2 = f32[64,1,3072,640]{3,2,1,0} fusion(bf16[12289,16,640]{2,1,0} %s)", 3.2, 0.004),
        event("%fusion.3 = bf16[64,12288]{1,0} fusion(bf16[64,4096]{1,0} %x)", 3.4, 0.1),
    ]
    assert read(made_up_run(sliced, steps=(3,))) == pytest.approx(
        100.0 * by_bytes / 0.006, rel=1e-9
    )


def test_moe_held_flops_over_routed_counts_the_sorted_buffers_rows():
    read = reader("moe_held_flops_over_routed").read
    # the program's records: 10 pairs a token (5 layers x 8 x 32 / 128)
    records = [
        {"decode_tokens": 64, "prefill_tokens": 0, "held_expert_pairs": 640},
        {"decode_tokens": 64, "prefill_tokens": 512, "held_expert_pairs": 5760},
    ]
    prefill = GATE.replace("[192,", "[1312,")
    ops = []
    for step in (3, 4):  # a decode step: 3 matmuls x 5 layers at 192 rows
        ops += [event(GATE, step + 0.01 * i, 0.001) for i in range(15)]
    ops += [event(prefill, 5.0 + 0.01 * i, 0.002) for i in range(15)]
    run = made_up_run(ops, steps=(3, 4), admitted={5: [512]}, records=records)
    executed = (2 * 15 * 192 + 15 * 1312) * 2 * 4096 * 2048
    # 64 tokens a decode step, and the admitting step its prompt and 63 more
    tokens = 64 + 64 + 512 + 63
    routed = tokens * 10 * 3 * 2 * 4096 * 2048
    assert read(run) == pytest.approx(executed / routed, rel=1e-9)
    assert 1.0 < executed / routed < 1.5


def test_the_three_readers_read_nothing_where_there_is_nothing_to_read():
    records = [{"decode_tokens": 64, "prefill_tokens": 0, "held_expert_pairs": 640}]
    decoder = spec.opcount(ROOT, BENCH, "decoder")
    dense_cfg = json.loads((ROOT / "benchmarks/configs/mistral-7b-v02.json").read_text())
    for metric, op in (
        ("mla_decode_roofline", KERNEL), ("moe_expert_roofline", GATE),
        ("moe_held_flops_over_routed", GATE),
    ):
        read = reader(metric).read
        assert read(made_up_run([event(op, 3.1, 0.004)], steps=(3,), records=records)) > 0
        other = event("%fusion.1 = bf16[64,4096]{1,0} fusion(bf16[64,4096]{1,0} %x)", 3.1, 0.1)
        assert read(made_up_run([other], steps=(3,), records=records)) is None
        # a parent's program, a decoder: another count module, no such counter
        assert read(made_up_run(
            [event(op, 3.1, 0.004)], steps=(3,), counts=decoder, cfg=dense_cfg
        )) is None
        run = made_up_run([event(op, 3.1, 0.004)], steps=(3,), records=records)
        assert read(dataclasses.replace(run, trace=None)) is None
    for metric, op in (("mla_decode_roofline", KERNEL), ("moe_expert_roofline", GATE)):
        read = reader(metric).read
        assert read(made_up_run([event(op, 3.1, 0.004)], admitted={3: [512]})) is None
        assert read(made_up_run([event(op, 3.1, 0.004)], steps=(3,), peaks=False)) is None
    # the program's records carry no pairs (a parent commit): nothing
    assert reader("moe_held_flops_over_routed").read(
        made_up_run([event(GATE, 3.1, 0.004)], steps=(3,), records=[
            {"decode_tokens": 64, "prefill_tokens": 0}
        ])
    ) is None


# ------------------------- a tiny cell of the architecture through the harness


def tiny_sarvam() -> dict:
    yarn = {**YARN, "original_max_position_embeddings": 64}
    published = {
        "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_hidden_layers": 3, "vocab_size": 256,
        "max_position_embeddings": 192, "rms_norm_eps": 1e-06,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "q_head_dim": 24, "v_head_dim": 16, "use_qk_norm": True,
        "rope_theta": 10000, "rope_scaling": yarn, "first_k_dense_replace": 1,
        "num_experts": 4, "router_width": 16, "experts_held_from": 4,
        "num_experts_per_tok": 4, "num_shared_experts": 1,
        "routed_scaling_factor": 2.5, "moe_router_enable_expert_bias": True,
    }
    return {
        "name": "tiny-sarvam", "source": "a test", "reference": "sarvam_mla",
        "opcount": "sarvam_mla", **published,
        "reduced": [], "assumed": [], "deployment": "a test", "chips": 1, "mesh": None,
        "pool": {"max_batch": 8, "page_size": 16, "max_pages_per_seq": 12, "n_pages": 97},
        "transformer_config": {
            "vocab_size": 256, "d_model": 64, "n_layers": 3, "n_heads": 4,
            "d_ff": 128, "max_seq_len": 192, "rope_theta": 10000,
            "dtype": "bfloat16", "rms_norm_eps": 1e-06, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "qk_norm": True, "rope_yarn": yarn, "n_dense_layers": 1,
            "n_experts": 16, "moe_top_k": 4, "moe_scoring": "sigmoid",
            "moe_held_experts": 4, "moe_held_from": 4, "moe_d_ff": 32,
            "moe_shared_experts": 1, "moe_routed_scaling": 2.5,
            "moe_router_bias": True,
        },
        "keymap": CFG["keymap"],
    }


TINY_MIX = {
    "name": "tiny_assist", "loop": "closed", "clients": 8,
    "prompt_tokens": {"values": [32, 64, 96], "weights": [0.3, 0.5, 0.2]},
    "output_tokens": {"values": [16, 32, 64], "weights": [0.25, 0.5, 0.25]},
    "deck": 20, "sampled_share": 0.5,
    "sampling": {"temperature": 0.8, "top_p": 0.95},
    "first_budget_fraction": [0.1, 1.0], "who": "a test", "why": "a test",
}


@pytest.fixture(scope="module")
def tiny_results(tmp_path_factory) -> dict:
    root = tmp_path_factory.mktemp("sarvam_root")
    write_root(root, [tiny_sarvam()], [{
        "name": "tiny_assist8", "config": "tiny-sarvam", "traffic": "tiny_assist",
        "chips": 1, "why": "a test",
    }])
    # assist64 at a hundredth of its lengths, for a CPU
    (root / "benchmarks/traffic/tiny_assist.json").write_text(json.dumps(TINY_MIX))
    logs: list[str] = []
    out = {
        trace: harness.run_cell(
            # a seed beyond 32 signed bits, as the driver's are
            root, "tiny_assist8", 2**31 + 33, 1.0, trace, platform="cpu",
            log=logs.append, clock=ticking_clock(),
        )
        for trace in (False, True)
    }
    out["logs"] = logs
    return out


@pytest.mark.parametrize("trace", [False, True])
def test_the_tiny_cell_runs_correct_with_no_request_failed(tiny_results, trace):
    result = tiny_results[trace]
    assert result["correct"] is True and result["failed"] == 0, tiny_results["logs"]
    assert result["attempted"] >= 8
    median, limit = result["compared"]["logprob_diff_median"]
    assert 0 < median < limit
    # the sorted dispatch gives a row the same tokens alone and in a batch
    assert result["compared"]["solo_rerun_differs"] == [0, 0]


def test_the_tiny_cell_reports_what_it_can_off_the_chip(tiny_results):
    untraced, traced = tiny_results[False]["metrics"], tiny_results[True]["metrics"]
    assert set(untraced) == {m["name"] for m in BENCH["end_to_end"]}
    # the two shares need a chip's peaks, the third a device's instructions
    for metric in ("mla_decode_roofline", "moe_expert_roofline", "moe_held_flops_over_routed"):
        assert metric not in traced
    assert traced["compiles_in_window"]["value"] == 0
    assert traced["batch_occupancy"]["value"] > 80
