"""What PR 35 added to the benchmark: the configuration
``k-exaone-236b-a23b`` against the catalog's copy of its published
``config.json``, its count module against the arithmetic by hand, the three
``swa`` readers on made-up instruction names, the mix ``reason48``, and a tiny
cell of the same architecture through the harness on the CPU (new files and
entries alone).
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
CFG = json.loads((ROOT / "benchmarks/configs/k-exaone-236b-a23b.json").read_text())
DIMS = CFG["transformer_config"]
POOL = CFG["pool"]
COUNTS = spec.opcount(ROOT, BENCH, "exaone_moe")
SOURCE = "https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json"

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the ``config`` of the catalog's row (model-configs guide,
# architectures.jsonl), verbatim
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432, "layer_types": PERIOD * 12,
    "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47, "model_type": "exaone_moe",
    "moe_intermediate_size": 2048, "mtp_layer_types": ["full_attention"],
    "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "sliding_windows": [128, 128, 128, 0] * 12, "tie_word_embeddings": False,
    "topk_group": 1, "vocab_size": 153600,
}
REDUCED = {
    "num_hidden_layers": (48, 5), "num_experts": (128, 16),
    "vocab_size": (153600, 19200), "max_position_embeddings": (262144, 12288),
    "num_nextn_predict_layers": (1, 0),
}


# --------------------------------------------------- the configuration file


def test_the_catalogs_row_is_what_this_file_copied():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("the catalog is not on this machine")
    row = next(
        json.loads(line) for line in catalog.read_text().splitlines()
        if '"K-EXAONE-236B-A23B"' in line
    )
    assert row["config"] == CATALOG and row["source_url"] == SOURCE


def test_the_file_keeps_every_published_key_but_the_five_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "k-exaone-236b-a23b")
    assert entry["reduced"] == list(REDUCED) == [r["key"] for r in CFG["reduced"]]
    assert entry["source"] == CFG["source"] == SOURCE
    for key, value in CATALOG.items():
        if key not in REDUCED:  # nested groups whole, the lists of 48 among them
            assert CFG[key] == value, key
    for cut in CFG["reduced"]:
        assert (cut["published"], cut["run"]) == REDUCED[cut["key"]]
        assert CATALOG[cut["key"]] == cut["published"] and CFG[cut["key"]] == cut["run"]
    # the floors of a cut: a whole period and four layers past the dense one,
    # 8 experts, an eighth of the vocabulary; the router keeps its width
    depth = CFG["num_hidden_layers"]
    assert depth - CFG["first_k_dense_replace"] >= 4
    assert DIMS["layer_types"] == CATALOG["layer_types"][:depth]
    assert DIMS["layer_types"][1:] == [
        "sliding_attention", "sliding_attention", "full_attention", "sliding_attention",
    ]
    assert CFG["num_experts"] >= 8 and CFG["vocab_size"] * 8 >= 153600
    assert CFG["router_width"] == 128 == DIMS["n_experts"]
    assert POOL == {
        "max_batch": 48, "page_size": 16, "max_pages_per_seq": 768, "n_pages": 30721
    }
    assert POOL["max_pages_per_seq"] * POOL["page_size"] == 12288
    assert CFG["reference"] == CFG["opcount"] == "exaone_moe"
    assert CFG["chips"] == 1 and CFG["mesh"] is None
    assert "EIGHT" in CFG["deployment"] and "experts 0 to 15" in CFG["deployment"]
    assert "3.712e9" in CFG["deployment"] and "7.42 GB" in CFG["deployment"]
    said = " ".join(CFG["assumed"])
    for reading in ("pre-norm", "use_qk_norm", "rope_window", "rotate-half",
                    "sigmoid", "LAST ROW", "one routing group", "ln_q and ln_k",
                    "position mod 128", "first_k_dense_replace"):
        assert reading in said, reading
    # no width is cut: ``reduced`` names depth, shares and the context alone
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "num_attention_heads", "num_key_value_heads",
              "num_experts_per_tok", "sliding_window")
    assert not set(widths) & set(REDUCED)


def test_every_new_field_is_held_to_its_published_key():
    from bee_code_interpreter_tpu.models import transformer as T

    base = {f.name for f in dataclasses.fields(T.TransformerConfig)}
    new = {"head_dim", "sliding_window", "position_embedding", "qk_norm",
           "n_dense_layers", "moe_held_experts", "moe_d_ff"}
    assert new <= base
    held = set({**harness.KEYMAP, **CFG["keymap"]}.values())
    assert new <= held and new | {"layer_types"} <= set(DIMS)
    config = harness.transformer_config(T, CFG)
    assert hash(config)
    assert (config.d_model, config.n_heads, config.kv_heads, config.head_dim) == (
        6144, 64, 8, 128
    )
    assert config.head_dim != config.d_model // config.n_heads == 96
    assert (config.ff_dim, config.expert_ff_dim, config.sliding_window) == (
        18432, 2048, 128
    )
    assert (config.held_experts, config.n_experts, config.moe_top_k) == (16, 128, 8)
    assert (config.window_layers, config.paged_layers) == ((0, 1, 2, 4), (3,))
    assert (config.layer_period, config.n_dense_layers) == (4, 1)
    assert config.moe_exact and config.paged_window is None
    assert T._score_scale(config) == pytest.approx(128 ** -0.5)
    # a group that says otherwise than the published key is refused by name
    for field, wrong_value, words in (
        ("head_dim", 96, "head_dim=128 but"),
        ("sliding_window", 256, "sliding_window=128 but"),
        ("moe_held_experts", 8, "num_experts=16 but"),
        ("position_embedding", "rope", "position_embedding='rope_window' but"),
        ("qk_norm", False, "use_qk_norm=True but"),
    ):
        wrong = copy.deepcopy(CFG)
        wrong["transformer_config"][field] = wrong_value
        with pytest.raises(harness.CellError, match=words):
            harness.transformer_config(T, wrong)


def test_config_from_hf_takes_the_catalogs_keys():
    from bee_code_interpreter_tpu.models import transformer as T
    from bee_code_interpreter_tpu.models.hf_loader import config_from_hf

    loaded = config_from_hf(CATALOG)
    assert (loaded.n_layers, loaded.vocab_size, loaded.max_seq_len) == (48, 153600, 262144)
    assert loaded.held_experts == loaded.n_experts == 128
    # it loads WITHOUT the multi-token prediction block: 48 layers, no field
    assert len(loaded.window_layers) == 36 and len(loaded.paged_layers) == 12
    assert (loaded.layer_period, T._n_periods(loaded)) == (4, (11, 3))
    # cut as the file cuts it, it is the configuration the cell runs
    assert dataclasses.replace(
        loaded, n_layers=5, layer_types=loaded.layer_types[:5], vocab_size=19200,
        max_seq_len=12288, moe_held_experts=16,
    ) == harness.transformer_config(T, CFG)
    for key, value, words in (
        ("n_group", 8, "n_group 8 / topk_group 1 unsupported"),
        ("topk_group", 4, "topk_group 4 unsupported"),
        ("scoring_func", "softmax", "scoring_func 'softmax' unsupported"),
        ("norm_topk_prob", False, "norm_topk_prob false unsupported"),
        ("rope_parameters", {"rope_type": "yarn", "rope_theta": 1e6},
         "rope_parameters type 'yarn'"),
        ("mlp_layer_types", ["sparse"] * 48, "mlp_layer_types unsupported"),
    ):
        with pytest.raises(ValueError, match=words):
            config_from_hf({**CATALOG, key: value})


# ------------------------------------------------- the counts, by hand


def test_counts_are_the_arithmetic_by_hand():
    attention = 2 * 6144 * 64 * 128 + 2 * 6144 * 8 * 128
    assert COUNTS.attention_weight_elements(DIMS) == attention == 113_246_208
    expert = 3 * 6144 * 2048
    assert COUNTS.expert_elements(DIMS) == expert == 37_748_736
    dense = attention + 3 * 6144 * 18432
    assert COUNTS.dense_layer_elements(DIMS) == dense == 452_984_832
    # one expert layer here with every held expert read: ISSUE 35's arithmetic
    router = 6144 * 128 + 128
    whole = attention + router + expert + 16 * expert
    assert whole / 1e6 == pytest.approx(755.8, abs=0.05)
    vocabulary = 19200 * 6144
    assert (dense + 4 * whole + 2 * vocabulary) / 1e9 == pytest.approx(3.712, abs=0.001)
    assert 2 * (dense + 4 * whole + 2 * vocabulary) / 1e9 == pytest.approx(7.42, abs=0.01)
    assert (COUNTS.n_window_layers(DIMS), COUNTS.n_full_layers(DIMS)) == (4, 1)
    assert COUNTS.n_expert_layers(DIMS) == 4
    # at 48 rows 95.5 % of the held experts are touched: 15.3 of 16
    touched = 16 * (1 - (1 - 8 / 128) ** 48)
    assert COUNTS.experts_touched(DIMS, 48) == pytest.approx(touched)
    assert touched == pytest.approx(15.28, abs=0.01)
    assert COUNTS.experts_touched(DIMS, 0) == 0
    # 4,096 bytes a token a layer: 8 KV heads x 128 x K and V x 2 bytes
    assert COUNTS.kv_bytes_per_token_layer(DIMS) == 4096
    assert COUNTS.full_layers_kv_bytes(DIMS, 280_000) == 280_000 * 4096
    # a ring: 128 slots read and one written a row a layer
    assert COUNTS.ring_step_bytes(DIMS, 280_000, 48) == 4 * 48 * 129 * 4096
    # a row that has not filled its window reads what it has
    assert COUNTS.ring_step_bytes(DIMS, 48 * 10, 48) == 4 * 48 * 11 * 4096
    step = COUNTS.decode_step_min_bytes(DIMS, 280_000, 48)
    outside = dense + 4 * (attention + router + expert) + vocabulary
    assert 2 * outside / 1e9 == pytest.approx(2.36, abs=0.01)
    assert 2 * 4 * touched * expert / 1e9 == pytest.approx(4.61, abs=0.01)
    assert step == int(
        2 * (outside + 4 * touched * expert) + 280_000 * 4096 + 4 * 48 * 129 * 4096
    )
    assert step / 1e9 == pytest.approx(8.22, abs=0.02)  # 10.0 ms at 819 GB/s
    assert step / 819e9 * 1e3 == pytest.approx(10.0, abs=0.1)
    # the flash kernel: the full layer over the whole prompt, the four window
    # layers over 128 keys a query
    pairs_full = 4096 * 4097 / 2
    pairs_window = 128 * 129 / 2 + (4096 - 128) * 128
    assert COUNTS.prefill_attention_flops(DIMS, 4096) == 64 * 4 * 128 * (
        pairs_full + 4 * pairs_window
    )
    assert COUNTS.prefill_attention_flops(DIMS, 100) == 64 * 4 * 128 * 5 * 100 * 101 / 2
    # never more than five causal layers at the derived 96 would have been
    assert COUNTS.prefill_attention_flops(DIMS, 4096) < 5 * 64 * 4096**2 * 2 * 128
    # a token's pairs at the held experts: 4 layers x 8 x 16 / 128
    assert COUNTS.held_pairs(DIMS, 1000) == 4000
    assert COUNTS.routed_pair_flops(DIMS, 1) == 3 * 2 * 6144 * 2048
    assert COUNTS.ring_elements(DIMS, POOL) == (48 * 8 * 128 * 128, 4 * 48 * 8 * 128 * 128)


def test_reason48_fits_a_row_and_the_pool():
    mix = traffic.load_mix(ROOT / "benchmarks/traffic/reason48.json")
    assert traffic.longest_request(mix) == 12288 == POOL["max_pages_per_seq"] * POOL["page_size"]
    assert mix["clients"] == POOL["max_batch"] == 48
    assert mix["prompt_tokens"] == {"values": [1024, 4096, 8192], "weights": [0.3, 0.4, 0.3]}
    assert mix["output_tokens"] == {"values": [1024, 2048, 4096], "weights": [0.25, 0.5, 0.25]}
    assert (mix["loop"], mix["deck"], mix["sampled_share"]) == ("closed", 20, 0.5)
    assert mix["sampling"] == {"temperature": 0.8, "top_p": 0.95}
    assert mix["first_budget_fraction"] == [0.1, 1.0]
    # every prompt is at least 8 windows long, and a whole number of pages
    assert min(traffic.prompt_lengths(mix)) >= 8 * CFG["sliding_window"]
    assert not any(p % POOL["page_size"] for p in traffic.prompt_lengths(mix))
    # the pool is the ONE full layer's pages: 491,520 slots of 4,096 bytes
    slots = (POOL["n_pages"] - 1) * POOL["page_size"]
    assert slots == 491_520 and slots * 4096 / 1e9 == pytest.approx(2.01, abs=0.01)
    # what the 48 rows hold in expectation fits it with half as much again
    expected = 48 * (0.3 * 1024 + 0.4 * 4096 + 0.3 * 8192 + 0.25 * 1024 + 0.5 * 2048 + 0.25 * 4096)
    assert expected == pytest.approx(48 * 6707.2) and 1.5 * expected < slots
    # unwindowed, five layers of them would not fit beside 7.42 GB of weights
    assert 5 * expected * 4096 / 1e9 + 7.42 > 13.9
    entry = next(w for w in BENCH["workloads"] if w["name"] == "kexaone_reason48")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "k-exaone-236b-a23b", "reason48", 1
    )
    for metric, source in (
        ("swa_ring_roofline", "device_trace"),
        ("full_layers_decode_roofline", "device_trace"),
        ("admit_window_ms_p50", "program_span"),
    ):
        listed = next(m for m in BENCH["per_layer"] if m["name"] == metric)
        assert listed["workloads"] == ["kexaone_reason48"]
        assert (listed["layer"], listed["source"]) == ("swa", source)


def test_the_benchmark_gained_entries_and_lost_none():
    assert [c["name"] for c in BENCH["configs"]][-1] == "k-exaone-236b-a23b"
    assert [w["name"] for w in BENCH["workloads"]][-1] == "kexaone_reason48"
    assert [m["name"] for m in BENCH["per_layer"]][-3:] == [
        "swa_ring_roofline", "full_layers_decode_roofline", "admit_window_ms_p50",
    ]
    assert (len(BENCH["configs"]), len(BENCH["workloads"]), len(BENCH["per_layer"])) == (6, 7, 27)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


# --------------------------------------- the readers, on made-up traces


def reader(metric: str):
    return spec.layer_metric_reader(ROOT, BENCH, metric)


RING = "bf16[4,48,128,8,128]{4,3,2,1,0}"
ONE_RING = "bf16[1,48,128,8,128]{4,2,3,1,0}"
# a window layer's step as XLA names it: the slots scattered into the stack of
# rings in place, the layer's ring cut out of it and turned head-major, the
# scores over it
WRITE = f"%fusion.29 = {RING} fusion({RING} %wk, s32[48,3]{{1,0}} %at, bf16[48,8,128]{{2,1,0}} %k), kind=kCustom"
CUT = f"%fusion.7 = {ONE_RING} fusion({RING} %wk, s32[] %layer), kind=kLoop"
SCORES = f"%fusion.8 = f32[48,8,8,128]{{3,2,1,0}} fusion(bf16[48,8,8,128]{{3,2,1,0}} %q, {ONE_RING} %k), kind=kOutput"
KERNEL = (
    "%paged_decode_attention.3 = (bf16[48,8,8,128]{3,2,1,0}, "
    "bf16[1,30721,8,16,128]{4,3,2,1,0}, bf16[1,30721,8,16,128]{4,3,2,1,0}) "
    "custom-call(s32[48,768]{1,0} %bt, bf16[1,30721,8,16,128]{4,3,2,1,0} %k), "
    'custom_call_target="tpu_custom_call"'
)
# as many elements as a ring (48 x 128 = 6144), and no ring
WK_WEIGHT = "%fusion.2 = bf16[48,1024]{1,0} fusion(bf16[48,6144]{1,0} %x, bf16[4,6144,1024]{2,1,0} %wk)"


def event(name, start, seconds, **stats):
    return xplane.Event(name, start, start + seconds, tuple(stats.items()))


def made_up_run(ops, steps=(), admitted=None, counts=COUNTS, peaks=True,
                live=5000, cfg=CFG, host=()):
    """A traced run of the cell as the readers see it: ``steps`` are the
    indices of decode-only steps of a second each (step i spans [i, i+1)),
    ``admitted`` maps a step's index to the prompts it admitted; 48 rows
    decode, each with ``live`` tokens in the pool."""
    admitted = admitted or {}
    indices = sorted(set(steps) | set(admitted))
    spans = [event(driver.SPAN_STEP, float(i), 1.0, i=i) for i in indices]
    trace = xplane.Trace(
        [xplane.Device("/device:TPU:0", sorted(ops, key=lambda e: (e.start, -e.end)), [])],
        {driver.SPAN_STEP: spans}, sorted(host, key=lambda e: e.start),
    )
    slice_steps = [
        driver.Step(i, float(i), i + 0.9, admitted.get(i, []), 48, live=48)
        for i in indices
    ]
    flights = [
        driver.Flight(
            request=types.SimpleNamespace(prompt=np.zeros(live - 1, np.int32)),
            ticket=n, logprobs=False, t_submit=-2.0, t_first=-1.0,
            deliveries=[(-1.0, 1)],
        )
        for n in range(48)
    ]
    return harness.RunData(
        cfg=cfg, chips=1,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9} if peaks else None,
        memory_peak_bytes=0, e2e={}, window=(0.0, 60.0), flights=flights,
        loop_steps=slice_steps, steps=[], compiles_in_window=0,
        trace=trace,
        slice=(float(indices[0]), float(indices[-1] + 1)) if indices else None,
        slice_steps=slice_steps, counts=counts,
    )


def test_a_ring_is_told_by_its_shape_and_not_by_its_element_count():
    for text in (WRITE, CUT, SCORES,
                 SCORES.replace(ONE_RING, "f32[384,128,128]{2,1,0}"),
                 WRITE.replace(RING, "bf16[196608,128]{1,0}")):
        assert COUNTS.touches_ring(text, DIMS, POOL), text
    for text in (
        WK_WEIGHT, KERNEL,
        "%fusion.5 = bf16[48,6144]{1,0} fusion(bf16[48,6144]{1,0} %x)",
        # an integer array of a ring's size is no ring
        "%fusion.6 = s32[48,8,128,128]{3,2,1,0} fusion(s32[48]{0} %pos)",
    ):
        assert not COUNTS.touches_ring(text, DIMS, POOL), text
    assert 48 * 8 * 128 * 128 == 6144 * 1024  # why a count alone would not do


def test_swa_ring_roofline_times_what_touches_a_ring_in_decode_steps():
    least_s = 4 * 48 * 129 * 4096 / 819e9  # 0.124 ms
    ops = []
    for step in (3, 4, 5):
        ops += [
            event(WRITE, step + 0.1, 0.0001), event(CUT, step + 0.2, 0.0001),
            event(SCORES, step + 0.3, 0.0002),
            event(WK_WEIGHT, step + 0.5, 0.1), event(KERNEL, step + 0.7, 0.1),
        ]
    run = made_up_run(ops, steps=(3, 4, 5))
    assert reader("swa_ring_roofline").read(run) == pytest.approx(
        100.0 * least_s / 0.0004, rel=1e-9
    )
    assert least_s * 1e3 == pytest.approx(0.124, abs=0.001)


def test_full_layers_decode_roofline_times_the_kernel_or_the_slice_path():
    read = reader("full_layers_decode_roofline").read
    by_bytes = 48 * 5000 * 4096 / 819e9
    ops = [event(KERNEL, s + 0.1, 0.002) for s in (3, 4)]
    ops += [event(SCORES, s + 0.4, 0.1) for s in (3, 4)]  # a ring: not its
    assert read(made_up_run(ops, steps=(3, 4))) == pytest.approx(
        100.0 * by_bytes / 0.002, rel=1e-9
    )
    # the slice path: a layer's slice scattered into, the table's width gathered
    sliced = [
        event("%scatter.1 = bf16[30721,8,16,128]{3,2,1,0} scatter(bf16[30721,8,16,128]{3,2,1,0} %s)", 3.1, 0.002),
        event("%fusion.2 = f32[48,8,12288,128]{3,2,1,0} fusion(bf16[30721,8,16,128]{3,2,1,0} %s)", 3.2, 0.004),
        event(SCORES, 3.4, 0.1),
    ]
    assert read(made_up_run(sliced, steps=(3,))) == pytest.approx(
        100.0 * by_bytes / 0.006, rel=1e-9
    )


def test_admit_window_ms_p50_is_the_median_seed_window_span():
    read = reader("admit_window_ms_p50").read
    host = [
        event("serve.admit.seed_window", 3.1, 0.0004, rows=1),
        event("serve.admit.seed_window", 4.1, 0.0006, rows=1),
        event("serve.admit.seed_window", 5.1, 0.0030, rows=1),
        event("serve.admit.seed_window", 9.1, 0.5, rows=1),  # out of the slice
        event("serve.admit.seed_pool", 3.2, 0.2),
    ]
    run = made_up_run([event(KERNEL, 3.5, 0.001)], steps=(3, 4, 5), host=host)
    assert read(run) == pytest.approx(0.6)
    assert read(made_up_run([event(KERNEL, 3.5, 0.001)], steps=(3,))) is None


def test_the_three_readers_read_nothing_where_there_is_nothing_to_read():
    decoder = spec.opcount(ROOT, BENCH, "decoder")
    dense_cfg = json.loads((ROOT / "benchmarks/configs/mistral-7b-v02.json").read_text())
    for metric, op in (
        ("swa_ring_roofline", SCORES), ("full_layers_decode_roofline", KERNEL),
    ):
        read = reader(metric).read
        assert read(made_up_run([event(op, 3.1, 0.004)], steps=(3,))) > 0
        other = event("%fusion.1 = bf16[48,6144]{1,0} fusion(bf16[48,6144]{1,0} %x)", 3.1, 0.1)
        assert read(made_up_run([other], steps=(3,))) is None
        # a parent's program, a decoder: another count module
        assert read(made_up_run(
            [event(op, 3.1, 0.004)], steps=(3,), counts=decoder, cfg=dense_cfg
        )) is None
        run = made_up_run([event(op, 3.1, 0.004)], steps=(3,))
        assert read(dataclasses.replace(run, trace=None)) is None
        assert read(made_up_run([event(op, 3.1, 0.004)], admitted={3: [1024]})) is None
        assert read(made_up_run([event(op, 3.1, 0.004)], steps=(3,), peaks=False)) is None
    run = made_up_run([event(KERNEL, 3.1, 0.004)], steps=(3,))
    assert reader("admit_window_ms_p50").read(dataclasses.replace(run, trace=None)) is None


def test_the_readers_that_list_no_cell_count_this_one_through_its_module():
    """``decode_step_roofline`` and ``flash_fwd_roofline`` read every cell:
    here with this configuration's bytes and operations."""
    step = COUNTS.decode_step_min_bytes(DIMS, 48 * 5000, 48)
    ops = [event(KERNEL, s + 0.1, 0.0125) for s in (3, 4)]
    run = made_up_run(ops, steps=(3, 4))
    assert reader("decode_step_roofline").read(run) == pytest.approx(
        100.0 * step / 819e9 / 0.0125, rel=1e-9
    )
    flash = (
        "%flash.1 = (bf16[1,64,4096,128]{3,2,1,0}, f32[1,64,4096,1]{3,2,1,0}) "
        'custom-call(bf16[1,64,4096,128]{3,2,1,0} %q), custom_call_target="tpu_custom_call"'
    )
    admitted = made_up_run(
        [event(flash, 3.1 + 0.01 * i, 0.004) for i in range(5)], admitted={3: [4096]}
    )
    share = reader("flash_fwd_roofline").read(admitted)
    assert share == pytest.approx(
        100.0 * COUNTS.prefill_attention_flops(DIMS, 4096) / 197e12 / 0.020, rel=1e-9
    )
    assert 0 < share < 100


# ------------------------- a tiny cell of the architecture through the harness


def tiny_kexaone() -> dict:
    kinds = ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    published = {
        "hidden_size": 48, "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "num_hidden_layers": 5, "vocab_size": 256,
        "max_position_embeddings": 192, "rms_norm_eps": 1e-05,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "layer_types": kinds * 2, "sliding_window": 16,
        "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
        "rope_theta": 10000, "first_k_dense_replace": 1,
        "num_experts": 4, "router_width": 16, "experts_held_from": 4,
        "num_experts_per_tok": 4, "num_shared_experts": 1,
        "routed_scaling_factor": 2.5, "moe_router_enable_expert_bias": True,
        "use_qk_norm": True, "position_embedding": "rope_window",
    }
    return {
        "name": "tiny-kexaone", "source": "a test", "reference": "exaone_moe",
        "opcount": "exaone_moe", **published,
        "reduced": [], "assumed": [], "deployment": "a test", "chips": 1, "mesh": None,
        "pool": {"max_batch": 8, "page_size": 16, "max_pages_per_seq": 12, "n_pages": 97},
        "transformer_config": {
            "vocab_size": 256, "d_model": 48, "n_layers": 5, "n_heads": 4,
            "n_kv_heads": 2, "head_dim": 8, "d_ff": 96, "max_seq_len": 192,
            "rope_theta": 10000, "dtype": "bfloat16", "rms_norm_eps": 1e-05,
            "sliding_window": 16, "layer_types": kinds,
            "position_embedding": "rope_window", "qk_norm": True,
            "n_dense_layers": 1, "n_experts": 16, "moe_top_k": 4,
            "moe_scoring": "sigmoid", "moe_held_experts": 4, "moe_held_from": 4,
            "moe_d_ff": 32, "moe_shared_experts": 1, "moe_routed_scaling": 2.5,
            "moe_router_bias": True,
        },
        "keymap": CFG["keymap"],
    }


# reason48 at a sixty-fourth of its lengths, for a CPU: every prompt at least
# a window of 16 long, and the check's 9 tokens after 32 wrap the ring
TINY_MIX = {
    "name": "tiny_reason", "loop": "closed", "clients": 8,
    "prompt_tokens": {"values": [32, 64, 96], "weights": [0.3, 0.4, 0.3]},
    "output_tokens": {"values": [16, 32, 64], "weights": [0.25, 0.5, 0.25]},
    "deck": 20, "sampled_share": 0.5,
    "sampling": {"temperature": 0.8, "top_p": 0.95},
    "first_budget_fraction": [0.1, 1.0], "who": "a test", "why": "a test",
}


@pytest.fixture(scope="module")
def tiny_results(tmp_path_factory) -> dict:
    root = tmp_path_factory.mktemp("kexaone_root")
    write_root(root, [tiny_kexaone()], [{
        "name": "tiny_reason8", "config": "tiny-kexaone", "traffic": "tiny_reason",
        "chips": 1, "why": "a test",
    }])
    (root / "benchmarks/traffic/tiny_reason.json").write_text(json.dumps(TINY_MIX))
    logs: list[str] = []
    out = {
        trace: harness.run_cell(
            # a seed beyond 32 signed bits, as the driver's are
            root, "tiny_reason8", 2**31 + 35, 1.0, trace, platform="cpu",
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
    # the two shares need a chip's peaks; the span is the program's, and read
    for metric in ("swa_ring_roofline", "full_layers_decode_roofline"):
        assert metric not in traced
    assert traced["admit_window_ms_p50"]["value"] > 0
    assert traced["compiles_in_window"]["value"] == 0
    assert traced["batch_occupancy"]["value"] > 80
