"""What PR 39 added to the benchmark: the configuration
``granite-4.0-h-small`` against the catalog's copy of its published
``config.json``, its count module against the arithmetic by hand, the
``moe`` and ``ssm`` readers its cell is listed on, read through that module
on made-up instruction names, the reference's limits against the chip's
readings, the compile rehearsal's arithmetic for its pool, and a tiny cell
of the same architecture through the harness on the CPU.
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
CFG = json.loads((ROOT / "benchmarks/configs/granite-4.0-h-small.json").read_text())
DIMS = CFG["transformer_config"]
POOL = CFG["pool"]
COUNTS = spec.opcount(ROOT, BENCH, "granitemoehybrid")
SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the ``config`` of the catalog's row (model-configs guide,
# architectures.jsonl), verbatim
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 72,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352,
}
REDUCED = {
    "num_hidden_layers": (40, 10), "num_local_experts": (72, 36),
    "vocab_size": (100352, 50176), "max_position_embeddings": (131072, 3072),
}


# --------------------------------------------------- the configuration file


def test_the_catalogs_row_is_what_this_file_copied():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("the catalog is not on this machine")
    row = next(
        json.loads(line) for line in catalog.read_text().splitlines()
        if '"granite-4.0-h-small"' in line
    )
    assert row["config"] == CATALOG and row["source_url"] == SOURCE


def test_the_file_keeps_every_published_key_but_the_four_cut():
    entry = next(c for c in BENCH["configs"] if c["name"] == "granite-4.0-h-small")
    assert entry["reduced"] == list(REDUCED) == [r["key"] for r in CFG["reduced"]]
    assert entry["source"] == CFG["source"] == SOURCE
    for key, value in CATALOG.items():
        if key not in REDUCED:  # the list of 40 layer kinds among them, whole
            assert CFG[key] == value, key
    for cut in CFG["reduced"]:
        assert (cut["published"], cut["run"]) == REDUCED[cut["key"]]
        assert CATALOG[cut["key"]] == cut["published"] and CFG[cut["key"]] == cut["run"]
    # the floors of a cut: a whole period and four layers, 8 experts, an
    # eighth of the vocabulary; the router keeps its width
    depth = CFG["num_hidden_layers"]
    assert DIMS["layer_types"] == CATALOG["layer_types"][:depth] == PERIOD
    assert depth >= 4 and CFG["num_local_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= CATALOG["vocab_size"]
    assert CFG["router_width"] == 72 == DIMS["n_experts"]
    assert POOL == {
        "max_batch": 64, "page_size": 16, "max_pages_per_seq": 192, "n_pages": 12289
    }
    assert POOL["max_pages_per_seq"] * POOL["page_size"] == 3072
    # every row's longest request has its pages, and the scratch page one more
    assert POOL["n_pages"] == POOL["max_batch"] * POOL["max_pages_per_seq"] + 1
    assert CFG["reference"] == CFG["opcount"] == "granitemoehybrid"
    assert CFG["chips"] == 1 and CFG["mesh"] is None
    for words in ("TWO", "experts 0 to 35", "4.757e9", "9.51 GB", "8.9 tokens",
                  "two chips' worth", "pipeline stages"):
        assert words in CFG["deployment"], words
    said = " ".join(CFG["assumed"])
    for reading in ("moe_shared_experts 2", "[gate | up]", "GraniteMoeHybridTopKGating",
                    "1/128", "seeded", "18.4 MiB", "moe_exact"):
        assert reading in said, reading
    # no width is cut: ``reduced`` names depth, shares and the context alone
    widths = ("hidden_size", "intermediate_size", "shared_intermediate_size",
              "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_expand",
              "num_attention_heads", "num_key_value_heads", "num_experts_per_tok")
    assert not set(widths) & set(REDUCED)


def test_harness_builds_it_with_its_keymap():
    from bee_code_interpreter_tpu.models import transformer as T

    config = harness.transformer_config(T, CFG)
    assert hash(config)
    assert (config.d_model, config.n_heads, config.kv_heads, config.head_dim) == (
        4096, 32, 8, 128
    )
    assert (config.mamba_n_heads, config.mamba_d_head, config.mamba_d_state) == (
        128, 64, 128
    )
    assert config.mamba_d_inner == 2 * config.d_model
    assert (config.held_experts, config.n_experts, config.moe_top_k) == (36, 72, 10)
    # the shared MLP: 2 experts' widths in one SwiGLU, the published 1536
    assert config.expert_ff_dim * config.moe_shared_experts == 1536
    assert config.sorted_experts and config.moe_scoring == "softmax"
    assert config.moe_exact and config.layer_period == 10
    assert (config.n_mamba_layers, config.n_attention_layers) == (9, 1)
    assert T._score_scale(config) == pytest.approx(1 / 128)
    # a group that says otherwise than the published key is refused by name
    for field, wrong_value, words in (
        ("moe_held_experts", 72, "num_local_experts=36 but"),
        ("n_experts", 36, "router_width=72 but"),
        ("mamba_n_heads", 64, "mamba_n_heads=128 but"),
        ("logits_scaling", 8, "logits_scaling=16 but"),
        ("d_ff", 1536, "intermediate_size=768 but"),
    ):
        wrong = copy.deepcopy(CFG)
        wrong["transformer_config"][field] = wrong_value
        with pytest.raises(harness.CellError, match=words):
            harness.transformer_config(T, wrong)


def test_config_from_hf_takes_the_catalogs_keys():
    from bee_code_interpreter_tpu.models import transformer as T
    from bee_code_interpreter_tpu.models.hf_loader import config_from_hf

    loaded = config_from_hf(CATALOG)
    assert (loaded.n_layers, loaded.vocab_size, loaded.max_seq_len) == (40, 100352, 131072)
    assert loaded.held_experts == loaded.n_experts == 72
    assert loaded.sorted_experts and loaded.moe_shared_experts == 2
    # cut as the file cuts it, it is the configuration the cell runs
    assert dataclasses.replace(
        loaded, n_layers=10, layer_types=loaded.layer_types[:10], vocab_size=50176,
        max_seq_len=3072, moe_held_experts=36, moe_held_from=0,
    ) == harness.transformer_config(T, CFG)
    for key, value, words in (
        ("num_experts_per_tok", 0, "num_local_experts 72 unsupported"),
        ("shared_intermediate_size", 1000, "shared_intermediate_size 1000 unsupported"),
    ):
        with pytest.raises(ValueError, match=words):
            config_from_hf({**CATALOG, key: value})


# ------------------------------------------------- the counts, by hand


def test_counts_are_the_arithmetic_by_hand():
    d = 4096
    mixer = d * (8192 + 8448 + 128) + 8192 * d + 4 * 8448 + 8448 + 3 * 128 + 8192
    assert COUNTS.mixer_weight_elements(DIMS) == mixer == 102_286_976
    attention = 2 * d * 32 * 128 + 2 * d * 8 * 128
    assert COUNTS.attention_weight_elements(DIMS) == attention == 41_943_040
    expert = 3 * d * 768
    assert COUNTS.expert_elements(DIMS) == expert == 9_437_184
    router, shared = d * 72, 3 * d * 1536
    assert COUNTS.shared_width(DIMS) == 1536
    beside = router + shared + 36 * expert  # each layer's expert half here
    assert (mixer + beside) / 1e6 == pytest.approx(461.2, abs=0.05)
    assert (attention + beside) / 1e6 == pytest.approx(400.9, abs=0.05)
    vocabulary = 50176 * d
    whole = 9 * (mixer + beside) + attention + beside + vocabulary
    assert COUNTS.weight_elements(DIMS) == whole
    assert whole / 1e9 == pytest.approx(4.757, abs=0.001)
    assert 2 * whole / 1e9 == pytest.approx(9.51, abs=0.01)
    # the state: 9 mamba layers x (2 MiB + the conv tail) a row
    per_layer = 128 * 64 * 128 * 2 + 3 * 8448 * 2
    assert COUNTS.state_bytes_per_row(DIMS) == 9 * per_layer
    assert 9 * per_layer / 2**20 == pytest.approx(18.4, abs=0.05)
    # 50 held pairs a token: 10 layers x 10 x 36 / 72
    assert COUNTS.held_pairs(DIMS, 1) == 50 and COUNTS.n_expert_layers(DIMS) == 10
    # at 64 rows every held expert is touched in expectation
    touched = 36 * (1 - (1 - 10 / 72) ** 64)
    assert COUNTS.experts_touched(DIMS, 64) == pytest.approx(touched)
    assert touched > 35.99
    # a decode step of 64 rows: ISSUE 39's arithmetic
    outside = whole - 10 * 36 * expert
    assert 2 * outside / 1e9 == pytest.approx(2.72, abs=0.005)
    assert 2 * 10 * touched * expert / 1e9 == pytest.approx(6.79, abs=0.005)
    assert 2 * 64 * 9 * per_layer / 1e9 == pytest.approx(2.47, abs=0.005)
    assert COUNTS.kv_bytes_per_token(DIMS) == 4096  # 8 KV heads x 128 x K, V x bf16
    live = 64 * 1700
    step = COUNTS.decode_step_min_bytes(DIMS, live, 64)
    assert step == int(2 * (outside + 10 * touched * expert) + live * 4096
                       + 2 * 64 * 9 * per_layer)
    held_share = 2 * 10 * touched * expert / step
    state_share = 2 * 64 * 9 * per_layer / step
    assert held_share == pytest.approx(0.55, abs=0.025)  # 57 % before the K/V
    assert state_share == pytest.approx(0.20, abs=0.015)
    # the one attention layer's flash kernel: 32 heads, causal, head 128
    assert COUNTS.prefill_attention_flops(DIMS, 1024) == pytest.approx(
        32 * 1024 * 1025 / 2 * 4 * 128, rel=0.01
    )


def test_the_scan_counts_are_the_micros_at_twice_its_width():
    """What the ``ssm`` readers count with: one layer's state a row (128
    heads x 64 x 128), and the prefill scan's chunks, operations and bytes
    over the 9 mamba layers."""
    assert COUNTS.ssm_elements_per_row(DIMS) == 128 * 64 * 128 == 1_048_576
    assert COUNTS.ssm_bytes_per_row(DIMS) == 2 * 2**20
    assert COUNTS.n_mamba(DIMS) == 9
    assert COUNTS.scan_chunks(DIMS, 512) == (256, 2)
    assert COUNTS.scan_chunks(DIMS, 2048) == (256, 8)
    assert COUNTS.scan_chunks(DIMS, 128) == (128, 1)
    inner = 2 * 4096
    assert COUNTS.ssd_prefill_flops(DIMS, 1024) == 9 * 4 * 256 * inner * 2 * (256 + 2 * 128)
    assert COUNTS.ssd_prefill_bytes(DIMS, 1024) == 9 * (
        1024 * (2 * inner + 2 * 128) * 2 + 1_048_576 * 4
    )
    # the scan is bound by its bytes at the chunk of 256: under the v5e's ridge
    ridge = 197e12 / 819e9
    assert COUNTS.ssd_prefill_flops(DIMS, 1024) / COUNTS.ssd_prefill_bytes(DIMS, 1024) < ridge


def test_the_rehearsals_arithmetic_fits_the_chip():
    """What ``test_benchmark_v5e_compile.py`` reckons for this pool: the
    weights beside the pool held twice (an admission's transient) under the
    15.75 GiB a v5e gives a process."""
    import jax

    from bee_code_interpreter_tpu.models import transformer as T
    from bee_code_interpreter_tpu.ops.paged_kv_cache import alloc_paged_cache

    config = harness.transformer_config(T, CFG)
    pool = jax.eval_shape(lambda: alloc_paged_cache(config, **{
        k: v for k, v in POOL.items() if k != "max_pages_per_seq"
    }))
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    state = 64 * COUNTS.state_bytes_per_row(DIMS)
    pages = 12289 * 16 * COUNTS.kv_bytes_per_token(DIMS)
    assert pool_bytes == state + pages
    assert (state / 2**30, pages / 2**30) == pytest.approx((1.152, 0.750), abs=0.001)
    weights = 2 * COUNTS.weight_elements(DIMS)
    assert (weights + 2 * pool_bytes) / 2**30 == pytest.approx(12.66, abs=0.01)
    assert weights + 2 * pool_bytes < 15.75 * 2**30
    # and the floor of a cell: the weights alone fill more than 25 % of a chip
    assert weights > 0.25 * 16e9


def test_assist64_fits_a_row_and_the_pool():
    mix = traffic.load_mix(ROOT / "benchmarks/traffic/assist64.json")
    assert traffic.longest_request(mix) == 3072 == POOL["max_pages_per_seq"] * 16
    assert mix["clients"] == POOL["max_batch"] == 64
    entry = next(w for w in BENCH["workloads"] if w["name"] == "granite4hs_assist64")
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "granite-4.0-h-small", "assist64", 1
    )
    for words in ("57%", "21%", "8.9 tokens", "2x"):
        assert words in entry["why"], words
    # the accepted metrics of the held experts and of the mixers' state, the
    # cell appended to each list; nothing else lists it
    listing = {
        "moe_expert_roofline": "sarvam105b_assist64",
        "moe_held_flops_over_routed": "sarvam105b_assist64",
        "ssm_state_roofline": "granite4h_chat48",
        "ssd_prefill_roofline": "granite4h_chat48",
        "admit_state_ms_p50": "granite4h_chat48",
    }
    for metric in BENCH["per_layer"]:
        if metric["name"] in listing:
            assert metric["workloads"] == [listing[metric["name"]], "granite4hs_assist64"]
        else:
            assert "granite4hs_assist64" not in metric.get("workloads", [])


def test_sarvams_assist64_fits_and_its_metrics_list_it_first():
    """What ``test_benchmark_sarvam.py``'s case of the same cell held before
    PR 39 appended granite4hs_assist64 to two of its metrics
    (``tests/conftest.py`` ``RETIRED_CASES``)."""
    sarvam = json.loads((ROOT / "benchmarks/configs/sarvam-105b.json").read_text())
    mix = traffic.load_mix(ROOT / "benchmarks/traffic/assist64.json")
    pool = sarvam["pool"]
    assert traffic.longest_request(mix) == 3072 == pool["max_pages_per_seq"] * pool["page_size"]
    assert mix["clients"] == pool["max_batch"] == 64
    assert traffic.prompt_lengths(mix) == [512, 1024, 2048]
    assert not any(p % pool["page_size"] for p in traffic.prompt_lengths(mix))
    assert 64 * 192 <= pool["n_pages"] - 1
    entry = next(w for w in BENCH["workloads"] if w["name"] == "sarvam105b_assist64")
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("sarvam-105b", "assist64", 1)
    for metric in ("mla_decode_roofline", "moe_expert_roofline", "moe_held_flops_over_routed"):
        listed = next(m for m in BENCH["per_layer"] if m["name"] == metric)
        assert listed["workloads"][0] == "sarvam105b_assist64"
        assert listed["source"] == "device_trace"


def test_the_benchmark_gained_two_entries_and_lost_none():
    """What ``test_benchmark_admission_records.py``'s case of the same kind
    held before PR 39 appended (``tests/conftest.py`` ``RETIRED_CASES``), with
    the new counts and the new entries last: no metric was added."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert [c["name"] for c in BENCH["configs"]][-2:] == [
        "k-exaone-236b-a23b", "granite-4.0-h-small",
    ]
    assert [w["name"] for w in BENCH["workloads"]][-2:] == [
        "kexaone_reason48", "granite4hs_assist64",
    ]
    assert names[-1] == "prefill_program_ms_per_ktok"
    assert (len(BENCH["configs"]), len(BENCH["workloads"]), len(names)) == (7, 8, 32)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert not any("workloads" in m for m in BENCH["per_layer"][-5:])


# --------------------------------------- the readers, on made-up traces


def reader(metric: str):
    return spec.layer_metric_reader(ROOT, BENCH, metric)


TILED = "{1,0:T(8,128)(2,1)}"
# the grouped matmuls as XLA names them on the chip: the sorted buffer of a
# decode step at 64 rows (320 pairs expected, a quarter more and 32: 432), the
# stack of 10 layers x 36 experts merged into the groups
GATE = (
    "%ragged-dot-none.1 = bf16[432,768]" + TILED + " custom-call(s32[1]{0} %n, "
    "bf16[432,4096]" + TILED + " %x, bf16[360,4096,768]{2,1,0:T(8,128)(2,1)} %w), "
    'custom_call_target="tpu_custom_call"'
)
DOWN = (
    "%ragged-dot-none = bf16[432,4096]" + TILED + " custom-call(s32[1]{0} %n, "
    "bf16[432,768]" + TILED + " %y, bf16[360,768,4096]{2,1,0:T(8,128)(2,1)} %w), "
    'custom_call_target="tpu_custom_call"'
)
# the shared MLP's weights stacked over the 10 layers: no expert stack
SHARED = (
    "%fusion.5 = bf16[64,1536]" + TILED + " fusion(bf16[64,4096]{1,0} %x, "
    "bf16[10,4096,1536]{2,1,0} %ws)"
)


def event(name, start, seconds, **stats):
    return xplane.Event(name, start, start + seconds, tuple(stats.items()))


def made_up_run(ops, steps=(), admitted=None, counts=COUNTS, peaks=True,
                records=(), live=1000, cfg=CFG, modules=(), host=()):
    """A traced run of the cell as the readers see it: ``steps`` are the
    indices of decode-only steps of a second each (step i spans [i, i+1)),
    ``admitted`` maps a step's index to the prompts it admitted; 64 rows
    decode, each with ``live`` tokens in the pool. ``modules`` are the
    device's programs, ``host`` the host's spans."""
    admitted = admitted or {}
    indices = sorted(set(steps) | set(admitted))
    spans = [event(driver.SPAN_STEP, float(i), 1.0, i=i) for i in indices]
    trace = xplane.Trace(
        [xplane.Device(
            "/device:TPU:0", sorted(ops, key=lambda e: (e.start, -e.end)), list(modules)
        )],
        {driver.SPAN_STEP: spans}, sorted(host, key=lambda e: (e.start, -e.end)),
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
    assert COUNTS.expert_matmul_rows(GATE, DIMS) == 432
    assert COUNTS.expert_matmul_rows(DOWN, DIMS) == 432
    assert COUNTS.expert_matmul_flops(GATE, DIMS) == 2 * 432 * 4096 * 768
    apart = GATE.replace("bf16[360,4096,768]", "bf16[10,36,4096,768]")
    assert COUNTS.expert_matmul_rows(apart, DIMS) == 432
    assert COUNTS.expert_matmul_rows(SHARED, DIMS) is None


def test_moe_expert_roofline_times_this_cells_expert_matmuls_of_decode_steps():
    touched = 36 * (1 - (1 - 10 / 72) ** 64)
    least_s = 10 * touched * 9_437_184 * 2 / 819e9  # 8.30 ms
    ops = []
    for step in (3, 4, 5):
        ops += [
            event(GATE, step + 0.1, 0.004), event(GATE.replace(".1 =", ".2 ="), step + 0.2, 0.004),
            event(DOWN, step + 0.3, 0.004), event(SHARED, step + 0.4, 0.1),
        ]
    run = made_up_run(ops, steps=(3, 4, 5))
    assert reader("moe_expert_roofline").read(run) == pytest.approx(
        100.0 * least_s / 0.012, rel=1e-9
    )
    assert least_s * 1e3 == pytest.approx(8.30, abs=0.01)


def test_moe_held_flops_over_routed_counts_this_cells_sorted_buffers_rows():
    read = reader("moe_held_flops_over_routed").read
    # the program's records: 50 pairs a token
    records = [
        {"decode_tokens": 64, "prefill_tokens": 0, "held_expert_pairs": 3200},
        {"decode_tokens": 64, "prefill_tokens": 512, "held_expert_pairs": 28800},
    ]
    prefill = GATE.replace("[432,", "[3232,")
    ops = []
    for step in (3, 4):  # a decode step: 3 matmuls x 10 layers at 432 rows
        ops += [event(GATE, step + 0.01 * i, 0.001) for i in range(30)]
    ops += [event(prefill, 5.0 + 0.01 * i, 0.002) for i in range(30)]
    run = made_up_run(ops, steps=(3, 4), admitted={5: [512]}, records=records)
    executed = (2 * 30 * 432 + 30 * 3232) * 2 * 4096 * 768
    tokens = 64 + 64 + 512 + 63
    routed = tokens * 50 * 3 * 2 * 4096 * 768
    assert read(run) == pytest.approx(executed / routed, rel=1e-9)
    assert 1.0 < executed / routed < 1.5


def test_the_two_readers_read_nothing_where_there_is_nothing_to_read():
    records = [{"decode_tokens": 64, "prefill_tokens": 0, "held_expert_pairs": 3200}]
    decoder = spec.opcount(ROOT, BENCH, "decoder")
    dense_cfg = json.loads((ROOT / "benchmarks/configs/mistral-7b-v02.json").read_text())
    for metric in ("moe_expert_roofline", "moe_held_flops_over_routed"):
        read = reader(metric).read
        assert read(made_up_run([event(GATE, 3.1, 0.004)], steps=(3,), records=records)) > 0
        assert read(made_up_run([event(SHARED, 3.1, 0.1)], steps=(3,), records=records)) is None
        # a parent's program over a decoder's count module: nothing
        assert read(made_up_run(
            [event(GATE, 3.1, 0.004)], steps=(3,), counts=decoder, cfg=dense_cfg
        )) is None
        run = made_up_run([event(GATE, 3.1, 0.004)], steps=(3,), records=records)
        assert read(dataclasses.replace(run, trace=None)) is None
    # the program's records carry no pairs (a program without the sorted
    # layer under a softmax router): nothing
    assert reader("moe_held_flops_over_routed").read(
        made_up_run([event(GATE, 3.1, 0.004)], steps=(3,), records=[
            {"decode_tokens": 64, "prefill_tokens": 0}
        ])
    ) is None


# one layer's state of the pool's 64 rows, as the decode step holds it
STATE = "bf16[64,128,64,128]{3,2,1,0}"


def test_ssm_state_roofline_times_this_cells_state_and_not_its_experts():
    least_s = 2 * 64 * 9 * 2**21 / 819e9  # 64 rows read and written: 2.95 ms
    ops = []
    for step in (3, 4, 5):
        ops += [
            event(f"%fusion.1 = {STATE} fusion({STATE} %p, f32[64,128]{{1,0}} %dt)", step + 0.1, 0.004),
            # all 9 layers' state, as the stacked leaf: counted
            event(f"%fusion.2 = bf16[9,64,128,64,128] fusion({STATE} %s)", step + 0.2, 0.004),
            event("%fusion.3 = f32[64,128,8192]{2,1,0} fusion(f32[64,128,64,128] %s)", step + 0.3, 0.004),
            # the held experts, the shared MLP: not the state
            event(GATE, step + 0.4, 0.01), event(SHARED, step + 0.5, 0.01),
        ]
    run = made_up_run(ops, steps=(3, 4, 5))
    assert reader("ssm_state_roofline").read(run) == pytest.approx(
        100.0 * least_s / 0.012, rel=1e-9
    )
    assert least_s * 1e3 == pytest.approx(2.95, abs=0.005)
    # without an instruction shaped like the state: nothing
    assert reader("ssm_state_roofline").read(
        made_up_run([event(GATE, 3.1, 0.01)], steps=(3,))
    ) is None


def test_ssd_prefill_roofline_times_this_cells_scan_and_not_its_router():
    modules = [
        event("jit_prefill_forward(123)", 7.2, 0.5),
        event("jit_decode_step_paged(456)", 7.75, 0.2),
    ]
    ops = [
        # a 512-token prompt scans in two chunks of 256: x over the chunks
        # (tokens x 8192), a chunk's scores, the chunks' states
        event("%fusion.7 = f32[2,256,128,64]{3,2,1,0} fusion(f32[2,256,256]{2,1,0} %s)", 7.21, 0.002),
        event("%fusion.8 = f32[2,128,64,128]{3,2,1,0} fusion(bf16[2,256,128]{2,1,0} %b)", 7.22, 0.001),
        # the router's float32 logits, the held experts' grouped matmul
        event("%fusion.9 = f32[512,72]{1,0} fusion(bf16[512,4096]{1,0} %y, bf16[4096,72]{1,0} %r)", 7.25, 0.01),
        event(GATE.replace("[432,", "[3232,"), 7.3, 0.05),
        # shaped like the scan's state, but in the decode program
        event("%fusion.12 = f32[1,128,64,128]{3,2,1,0} fusion()", 7.8, 0.1),
    ]
    run = made_up_run(ops, modules=modules, admitted={7: [512]})
    inner = 8192
    flops = 9 * 2 * 256 * inner * 2 * (256 + 256)
    bytes_ = 9 * (512 * (2 * inner + 2 * 128) * 2 + 2**20 * 4)
    assert flops / 197e12 < bytes_ / 819e9  # under the ridge: by its bytes
    assert reader("ssd_prefill_roofline").read(run) == pytest.approx(
        100.0 * (bytes_ / 819e9) / 0.003, rel=1e-9
    )


def test_admit_state_ms_p50_reads_this_cells_seed_state_spans():
    read = reader("admit_state_ms_p50").read
    row = COUNTS.state_bytes_per_row(DIMS)
    host = [
        event("serve.admit", 7.1, 0.05, req=1, prompt_tokens=512, pages=32),
        event("serve.admit.seed_state", 7.12, 0.0008, rows=1, bytes=row),
        event("serve.admit.seed_state", 7.32, 0.0012, rows=1, bytes=row),
        event("serve.admit.seed_state", 7.52, 0.0009, rows=1, bytes=row),
    ]
    assert read(made_up_run([], host=host, admitted={7: [512]})) == pytest.approx(0.9)
    assert read(made_up_run([], host=host[:1], admitted={7: [512]})) is None


# ------------------------------------- the reference's limits, against the chip


def requests(diffs_of_each, gap=0.004):
    """Greedy and sampled requests in turn, of 9 positions each, with the
    given differences of the log-probability and every router margin
    ``gap`` (as close as the chip's, where nearly every position is)."""
    return [
        {
            "logprob_diff": list(diffs), "tie_gap": [gap] * len(diffs),
            "margin": [0.0] * len(diffs) if i % 2 == 0 else None,
        }
        for i, diffs in enumerate(diffs_of_each)
    ]


@pytest.mark.parametrize("reading, sound", [
    # the sound runs' worst (PERF.md section 6): a median of 0.00174, the
    # largest position 0.0088; a position above 0.012 would be the first
    ("sound_worst", True),
    ("sound_one_stray", True),
    # the MLP half through float8_e4m3: a median of 0.00364 to 0.00423, 6
    # to 13 positions beyond 0.012
    ("float8_mlp", False),
    # float8 with the median held at the sound runs' worst: the positions
    # beyond 0.012 refuse it alone
    ("float8_tail_alone", False),
    ("shared_mlp_dropped", False),
])
def test_the_limits_part_the_sound_runs_from_the_controls(reading, sound):
    from benchmarks.reference import granitemoehybrid as ref
    from benchmarks.lib import check

    base = [0.0016] * 72 + [0.00175] * 72
    base[0] = 0.0088
    diffs = {
        "sound_worst": base,
        "sound_one_stray": base[:-1] + [0.015],
        "float8_mlp": [0.0036] * 72 + [0.0042] * 66 + [0.013, 0.014, 0.016, 0.018, 0.021, 0.029],
        "float8_tail_alone": base[:-6] + [0.013, 0.014, 0.016, 0.018, 0.021, 0.029],
        "shared_mlp_dropped": [0.0766] * 144,
    }[reading]
    out = check.verdict(requests([diffs[i:i + 9] for i in range(0, 144, 9)]), ref.TOLERANCE)
    assert out["positions"] == 144 == ref.TOLERANCE["requests"] * check.NEW_TOKENS
    assert (not out["problems"]) is sound, out["problems"]


# ------------------------- a tiny cell of the architecture through the harness


def tiny_granite_small() -> dict:
    kinds = ["mamba", "attention", "mamba"]
    published = {
        "hidden_size": 64, "intermediate_size": 16, "shared_intermediate_size": 32,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 3, "vocab_size": 256, "max_position_embeddings": 192,
        "rms_norm_eps": 1e-05, "hidden_act": "silu", "tie_word_embeddings": True,
        "layer_types": kinds * 2, "mamba_n_heads": 8, "mamba_d_head": 16,
        "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_chunk_size": 32, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "attention_multiplier": 0.0625,
        "logits_scaling": 8, "position_embedding_type": "nope",
        "num_local_experts": 4, "router_width": 16, "experts_held_from": 4,
        "num_experts_per_tok": 4,
    }
    return {
        "name": "tiny-granite-small", "source": "a test",
        "reference": "granitemoehybrid", "opcount": "granitemoehybrid", **published,
        "reduced": [], "assumed": [], "deployment": "a test", "chips": 1, "mesh": None,
        "pool": {"max_batch": 8, "page_size": 16, "max_pages_per_seq": 12, "n_pages": 97},
        "transformer_config": {
            "vocab_size": 256, "d_model": 64, "n_layers": 3, "n_heads": 4,
            "n_kv_heads": 2, "d_ff": 16, "max_seq_len": 192, "rope_theta": 10000,
            "dtype": "bfloat16", "layer_types": kinds, "mamba_n_heads": 8,
            "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
            "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 32,
            "embedding_multiplier": 12, "residual_multiplier": 0.22,
            "attention_multiplier": 0.0625, "logits_scaling": 8,
            "position_embedding": "nope", "tie_embeddings": True,
            "n_experts": 16, "moe_top_k": 4, "moe_scoring": "softmax",
            "moe_held_experts": 4, "moe_held_from": 4, "moe_shared_experts": 2,
        },
        "keymap": CFG["keymap"],
    }


# assist64 at a thirtieth of its lengths, for a CPU
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
    root = tmp_path_factory.mktemp("granite_small_root")
    write_root(root, [tiny_granite_small()], [{
        "name": "tiny_hybrid_assist8", "config": "tiny-granite-small",
        "traffic": "tiny_assist", "chips": 1, "why": "a test",
    }])
    (root / "benchmarks/traffic/tiny_assist.json").write_text(json.dumps(TINY_MIX))
    logs: list[str] = []
    out = {
        trace: harness.run_cell(
            # a seed beyond 32 signed bits, as the driver's are
            root, "tiny_hybrid_assist8", 2**31 + 39, 1.0, trace, platform="cpu",
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
    assert result["compared"]["solo_rerun_differs"] == [0, 0]


def test_the_tiny_cell_reports_what_it_can_off_the_chip(tiny_results):
    untraced, traced = tiny_results[False]["metrics"], tiny_results[True]["metrics"]
    assert set(untraced) == {m["name"] for m in BENCH["end_to_end"]}
    # these need a device's instructions (and the rooflines a chip's peaks)
    for metric in ("moe_expert_roofline", "moe_held_flops_over_routed",
                   "ssm_state_roofline", "ssd_prefill_roofline"):
        assert metric not in traced
    assert traced["compiles_in_window"]["value"] == 0
    assert traced["batch_occupancy"]["value"] > 80
