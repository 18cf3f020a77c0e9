"""What PR 29 added to the benchmark: the configuration
``granite-4.0-h-micro`` against the catalog's copy of its published
``config.json``, its count module against the arithmetic by hand, the three
``ssm`` readers on made-up instruction names and spans, the mix ``chat48``,
and a tiny hybrid cell through the harness on the CPU (new files and entries
alone, as ``test_benchmark_harness.py`` runs its two).
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
CFG = json.loads((ROOT / "benchmarks/configs/granite-4.0-h-micro.json").read_text())
DIMS = CFG["transformer_config"]
COUNTS = spec.opcount(ROOT, BENCH, "granitehybrid")

PATTERN = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
# the ``config`` of the catalog's row (model-configs guide,
# architectures.jsonl), verbatim
CATALOG = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PATTERN, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}


# --------------------------------------------------- the configuration file


def test_the_file_keeps_every_published_key_but_the_context():
    entry = next(c for c in BENCH["configs"] if c["name"] == "granite-4.0-h-micro")
    assert entry["reduced"] == ["max_position_embeddings"]
    for key, value in CATALOG.items():
        if key != "max_position_embeddings":
            assert CFG[key] == value, key
    assert CFG["max_position_embeddings"] == 704
    assert CFG["pool"] == {
        "max_batch": 48, "page_size": 16, "max_pages_per_seq": 44, "n_pages": 2112
    }
    assert CFG["pool"]["max_pages_per_seq"] * CFG["pool"]["page_size"] == 704
    # what a row keeps is in the model's dtype, and the file says what the
    # check can and cannot tell of it
    assert any("model's dtype" in line and "state" in line for line in CFG["assumed"])
    assert CFG["reference"] == CFG["opcount"] == "granitehybrid"


def test_every_new_field_is_held_to_its_published_key():
    from bee_code_interpreter_tpu.models import transformer as T

    base = {f.name for f in dataclasses.fields(T.TransformerConfig)}
    new = {
        "layer_types", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
        "mamba_n_groups", "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
        "embedding_multiplier", "residual_multiplier", "attention_multiplier",
        "logits_scaling", "position_embedding", "tie_embeddings",
    }
    assert new <= base
    held = set({**harness.KEYMAP, **CFG["keymap"]}.values())
    assert new <= held and new <= set(DIMS)
    config = harness.transformer_config(T, CFG)
    assert config.layer_types == tuple(PATTERN) and hash(config)
    assert (config.layer_period, config.n_mamba_layers, config.n_attention_layers) == (10, 36, 4)
    assert config.position_embedding == "nope" and config.tie_embeddings
    # a group that says otherwise than the published key is refused by name
    wrong = copy.deepcopy(CFG)
    wrong["transformer_config"]["mamba_d_state"] = 64
    with pytest.raises(harness.CellError, match="mamba_d_state=128 but"):
        harness.transformer_config(T, wrong)


def test_config_from_hf_takes_the_catalogs_keys():
    from bee_code_interpreter_tpu.models import transformer as T
    from bee_code_interpreter_tpu.models.hf_loader import config_from_hf

    loaded = config_from_hf(CATALOG)
    assert dataclasses.replace(loaded, max_seq_len=704) == (
        harness.transformer_config(T, CFG)
    )
    assert loaded.max_seq_len == 131072
    with pytest.raises(ValueError, match="num_local_experts 8 unsupported"):
        config_from_hf({**CATALOG, "num_local_experts": 8})
    with pytest.raises(ValueError, match="mamba_proj_bias True unsupported"):
        config_from_hf({**CATALOG, "mamba_proj_bias": True})


# ------------------------------------------------- the counts, by hand


def test_counts_are_the_arithmetic_by_hand():
    mixer = 2048 * (4096 + 4352 + 64) + 4096 * 2048 + (4 * 4352 + 4352) + 3 * 64 + 4096
    mlp = 3 * 2048 * 8192
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert COUNTS.mixer_weight_elements(DIMS) == mixer == 25_847_232
    assert mixer + mlp == 76_178_880 and attention + mlp == 60_817_408
    total = 36 * (mixer + mlp) + 4 * (attention + mlp) + 100352 * 2048
    # ISSUE 29 writes 3,191.4M from its rounded partial sums: 6.38 GB either way
    assert COUNTS.weight_elements(DIMS) == total == 3_191_230_208
    # 1 MiB a row and layer
    assert COUNTS.ssm_bytes_per_row(DIMS) == COUNTS.ssm_elements_per_row(DIMS) * 2 == 2**20
    assert COUNTS.kv_bytes_per_token(DIMS) == 8 * 2**10  # 8 KiB a token
    per_row = 36 * (2**20 + 3 * 4352 * 2)
    assert COUNTS.state_bytes_per_row(DIMS) == per_row
    assert per_row / 2**20 == pytest.approx(36.9, abs=0.05)
    assert COUNTS.decode_step_min_bytes(DIMS, 0, 0) == 2 * total
    assert COUNTS.decode_step_min_bytes(DIMS, 1000, 48) == (
        2 * total + 1000 * 8192 + 2 * 48 * per_row
    )
    # the state alone is 36 % of a full batch's step of 10.2 GB
    full = COUNTS.decode_step_min_bytes(DIMS, 12_000, 48)
    assert full / 1e9 == pytest.approx(10.2, abs=0.05)
    assert 2 * 48 * per_row / full == pytest.approx(0.36, abs=0.01)
    # causal attention in the 4 attention layers at a head of 64
    assert COUNTS.prefill_attention_flops(DIMS, 432) == 4 * (2 * 2 * 32 * 432 * 432 * 64 / 2)
    # the scan: one chunk up to 256 tokens, chunks of 256 beyond
    assert COUNTS.scan_chunks(DIMS, 112) == (112, 1)
    assert COUNTS.scan_chunks(DIMS, 432) == (256, 2)
    assert COUNTS.ssd_prefill_flops(DIMS, 432) == 36 * 512 * 4096 * 2 * (256 + 256)
    assert COUNTS.ssd_prefill_bytes(DIMS, 432) == 36 * (
        512 * (2 * 4096 + 2 * 128) * 2 + 2 * 2**20
    )
    # near the ridge at a chunk of 256: 200 operations a byte against the
    # chip's 240
    intensity = COUNTS.ssd_prefill_flops(DIMS, 512) / COUNTS.ssd_prefill_bytes(DIMS, 512)
    assert intensity == pytest.approx(200, abs=1) and 197e12 / 819e9 == pytest.approx(240.5, abs=0.1)


def test_chat48_fits_a_row_and_pads_short_of_every_page():
    mix = traffic.load_mix(ROOT / "benchmarks/traffic/chat48.json")
    pool = CFG["pool"]
    assert traffic.longest_request(mix) == 676 <= pool["max_pages_per_seq"] * pool["page_size"]
    assert mix["clients"] == pool["max_batch"] == 48
    assert all(p % pool["page_size"] for p in traffic.prompt_lengths(mix))
    # a full batch of the longest requests fits the pages
    assert 48 * -(-676 // 16) <= pool["n_pages"] - 1


# --------------------------------------- the readers, on made-up traces


def reader(metric: str):
    return spec.layer_metric_reader(ROOT, BENCH, metric)


# a layer's state as the step computes on it, and the pool's leaf as stored
STATE = "f32[48,64,64,128]{3,2,1,0:T(8,128)}"
STACK = "bf16[36,48,64,64,128]{4,3,2,1,0:T(8,128)(2,1)}"


def event(name, start, seconds, **stats):
    return xplane.Event(name, start, start + seconds, tuple(stats.items()))


def made_up_run(ops, modules=(), host=(), steps=(), admitted=None, counts=COUNTS,
                peaks=True):
    """A traced run of the cell as the readers see it: ``steps`` are the
    indices of decode-only steps of a second each (step i spans [i, i+1)),
    ``admitted`` maps a step's index to the prompts it admitted."""
    admitted = admitted or {}
    indices = sorted(set(steps) | set(admitted))
    spans = [event(driver.SPAN_STEP, float(i), 1.0, i=i) for i in indices]
    trace = xplane.Trace(
        [xplane.Device("/device:TPU:0", sorted(ops, key=lambda e: (e.start, -e.end)),
                       list(modules))],
        {driver.SPAN_STEP: spans}, list(host),
    )
    slice_steps = [
        driver.Step(i, float(i), i + 0.9, admitted.get(i, []), 48, live=48)
        for i in indices
    ]
    flights = [
        driver.Flight(
            request=types.SimpleNamespace(prompt=np.zeros(100, np.int32)),
            ticket=n, logprobs=False, t_submit=-2.0, t_first=-1.0,
            deliveries=[(-1.0, 1)],
        )
        for n in range(48)
    ]
    return harness.RunData(
        cfg=CFG, chips=1,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9} if peaks else None,
        memory_peak_bytes=0, e2e={}, window=(0.0, 60.0), flights=flights,
        loop_steps=slice_steps, steps=[], compiles_in_window=0, trace=trace,
        slice=(float(indices[0]), float(indices[-1] + 1)) if indices else None,
        slice_steps=slice_steps, counts=counts,
    )


def test_ssm_state_roofline_times_the_instructions_shaped_like_the_state():
    least_s = 2 * 48 * 36 * 2**20 / 819e9  # 48 rows read and written: 4.42 ms
    ops = []
    for step in (3, 4, 5):
        ops += [
            # one layer's state, the stacked leaf, a reshape of it: counted
            event(f"%fusion.1 = {STATE} fusion({STATE} %p, f32[48,64]{{1,0}} %dt)", step + 0.1, 0.004),
            event(f"%fusion.2 = {STACK} fusion({STACK} %c, {STATE} %s), kind=kLoop", step + 0.2, 0.004),
            event("%fusion.3 = bf16[48,64,64]{2,1,0} fusion(f32[48,64,8192]{2,1,0} %s)", step + 0.3, 0.004),
            # a matmul, and the state's size in a dtype it never has: not counted
            event("%fusion.4 = bf16[48,8512]{1,0} fusion(bf16[48,2048]{1,0} %x)", step + 0.4, 0.1),
            event("%copy.9 = s8[48,64,64,128]{3,2,1,0} copy(s8[48,64,64,128]{3,2,1,0} %k)", step + 0.6, 0.1),
        ]
    run = made_up_run(ops, steps=(3, 4, 5))
    assert reader("ssm_state_roofline").read(run) == pytest.approx(
        100.0 * least_s / 0.012, rel=1e-9
    )
    # an instruction that straddles the step's end is outside it
    late = made_up_run(ops + [event(f"%f = {STATE} fusion()", 3.95, 0.2)], steps=(3, 4, 5))
    assert reader("ssm_state_roofline").read(late) == pytest.approx(
        100.0 * least_s / 0.012, rel=1e-9
    )


def test_ssm_state_roofline_reads_nothing_where_there_is_nothing_to_read():
    read = reader("ssm_state_roofline").read
    state_op = event(f"%fusion.1 = {STATE} fusion({STATE} %p)", 3.1, 0.004)
    assert read(made_up_run([state_op], steps=(3,))) is not None
    # no instruction shaped like the state; no decode-only step; no peaks
    assert read(made_up_run([event("%f = bf16[48,2048]{1,0} fusion()", 3.1, 0.1)], steps=(3,))) is None
    assert read(made_up_run([state_op], admitted={3: [200]})) is None
    assert read(made_up_run([state_op], steps=(3,), peaks=False)) is None
    # a configuration whose count module keeps nothing by row (a decoder)
    decoder = spec.opcount(ROOT, BENCH, "decoder")
    assert read(made_up_run([state_op], steps=(3,), counts=decoder)) is None


def test_ssd_prefill_roofline_times_the_scan_inside_the_prefill_program():
    modules = [
        event("jit_prefill_forward(123)", 7.2, 0.5),
        event("jit_decode_step_paged(456)", 7.75, 0.2),
    ]
    ops = [
        # the 420-token prompt pads to 432, the scan to two chunks of 256: y
        # from the chunk's scores, the chunks' states, the carried state
        event("%fusion.7 = f32[1,1,2,64,8,8,256]{6,5,4,3,2,1,0} fusion(f32[2,256,64]{2,1,0} %a, f32[2,256,256]{2,1,0} %s, pred[256,256]{1,0} %m)", 7.21, 0.002),
        event("%fusion.8 = f32[2,1,1,64,64,128]{5,4,3,2,1,0} fusion(bf16[2,256,128,1]{3,2,1,0} %b, f32[2,256,64]{2,1,0} %d)", 7.22, 0.001),
        event("%fusion.9 = f32[1,1,64,64,128]{4,3,2,1,0} fusion(f32[64]{0} %decay, pred[] %p)", 7.23, 0.001),
        # out_proj reads y in float32 but multiplies by a stacked weight; the
        # MLP's matmul touches nothing of the scan's: neither is the scan
        event("%fusion.10 = (f32[432]{0}, bf16[1,432,2048]{2,1,0}) fusion(bf16[36,4096,2048]{2,1,0} %w, f32[1,512,4096]{2,1,0} %y)", 7.25, 0.05),
        event("%fusion.11 = bf16[432,8192]{1,0} fusion(bf16[40,2048,8192]{2,1,0} %w, bf16[1,432,2048]{2,1,0} %x)", 7.3, 0.05),
        # shaped like a chunk's state, but in the decode program
        event("%fusion.12 = f32[1,64,64,128]{3,2,1,0} fusion()", 7.8, 0.1),
    ]
    run = made_up_run(ops, modules=modules, admitted={7: [420]})
    flops = 36 * 512 * 4096 * 2 * (256 + 256)
    bytes_ = 36 * (512 * (2 * 4096 + 2 * 128) * 2 + 2 * 2**20)
    assert flops / 197e12 < bytes_ / 819e9  # just under the ridge: by its bytes
    assert reader("ssd_prefill_roofline").read(run) == pytest.approx(
        100.0 * (bytes_ / 819e9) / 0.004, rel=1e-9
    )
    # a 100-token prompt pads to 112, one chunk: further under it
    short = made_up_run(
        [event("%fusion.7 = f32[1,1,1,64,8,8,112]{6,5,4,3,2,1,0} fusion(f32[1,112,112]{2,1,0} %s)", 7.21, 0.002)],
        modules=modules, admitted={7: [100]},
    )
    bytes_ = 36 * (112 * (2 * 4096 + 2 * 128) * 2 + 2 * 2**20)
    assert 36 * 112 * 4096 * 2 * (112 + 256) / 197e12 < bytes_ / 819e9
    assert reader("ssd_prefill_roofline").read(short) == pytest.approx(
        100.0 * (bytes_ / 819e9) / 0.002, rel=1e-9
    )


def test_ssd_prefill_roofline_reads_nothing_where_there_is_nothing_to_read():
    read = reader("ssd_prefill_roofline").read
    modules = [event("jit_prefill_forward(123)", 7.2, 0.5)]
    scan = event("%fusion.8 = f32[64,64,128]{2,1,0} fusion()", 7.22, 0.001)
    assert read(made_up_run([scan], modules=modules, admitted={7: [200]})) is not None
    assert read(made_up_run([scan], modules=modules, steps=(7,))) is None  # no admission
    assert read(made_up_run([scan], modules=[], admitted={7: [200]})) is None  # a parent's names
    assert read(made_up_run([scan], modules=modules, admitted={7: [200]}, peaks=False)) is None
    decoder = spec.opcount(ROOT, BENCH, "decoder")
    assert read(made_up_run([scan], modules=modules, admitted={7: [200]}, counts=decoder)) is None


def test_admit_state_ms_p50_is_the_median_seed_state_span():
    read = reader("admit_state_ms_p50").read
    host = [
        event("serve.admit", 7.1, 0.03, req=1, prompt_tokens=200, pages=21),
        event("serve.admit.seed_state", 7.12, 0.0004, rows=1, bytes=76_437_504),
        event("serve.admit.seed_state", 7.32, 0.0006, rows=1, bytes=76_437_504),
        event("serve.admit.seed_state", 7.52, 0.0011, rows=1, bytes=76_437_504),
        event("serve.admit.seed_state", 9.5, 0.5, rows=1, bytes=76_437_504),  # outside
    ]
    assert read(made_up_run([], host=host, admitted={7: [200]})) == pytest.approx(0.6)
    # a program without the span (a parent commit, a decoder): nothing
    assert read(made_up_run([], host=host[:1], admitted={7: [200]})) is None
    assert read(dataclasses.replace(made_up_run([], admitted={7: [200]}), trace=None)) is None


# ---------------------------------- a tiny hybrid cell through the harness


def tiny_hybrid() -> dict:
    pattern = ["mamba", "attention", "mamba"] * 2
    group = {
        "layer_types": pattern, "mamba_n_heads": 8, "mamba_d_head": 16,
        "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_chunk_size": 64, "attention_multiplier": 0.0625,
        "embedding_multiplier": 12, "residual_multiplier": 0.22, "logits_scaling": 8,
    }
    return {
        "name": "tiny-hybrid", "source": "a test", "reference": "granitehybrid",
        "opcount": "granitehybrid",
        "hidden_size": 64, "intermediate_size": 128, "shared_intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 6,
        "vocab_size": 256, "max_position_embeddings": 704, "rms_norm_eps": 1e-05,
        "hidden_act": "silu", "tie_word_embeddings": True,
        "position_embedding_type": "nope", **group,
        "reduced": [], "assumed": [], "deployment": "a test", "chips": 1, "mesh": None,
        "pool": dict(CFG["pool"]),
        "transformer_config": {
            "vocab_size": 256, "d_model": 64, "n_layers": 6, "n_heads": 4,
            "n_kv_heads": 2, "d_ff": 128, "max_seq_len": 704, "dtype": "bfloat16",
            "position_embedding": "nope", "tie_embeddings": True, **group,
        },
        "keymap": CFG["keymap"],
    }


@pytest.fixture(scope="module")
def tiny_results(tmp_path_factory) -> dict:
    root = tmp_path_factory.mktemp("hybrid_root")
    write_root(root, [tiny_hybrid()], [{
        "name": "tiny_chat48", "config": "tiny-hybrid", "traffic": "chat48",
        "chips": 1, "why": "a test",
    }])
    logs: list[str] = []
    out = {
        trace: harness.run_cell(
            # a seed beyond 32 signed bits, as the driver's are
            root, "tiny_chat48", 2**31 + 29, 1.0, trace, platform="cpu",
            log=logs.append, clock=ticking_clock(),
        )
        for trace in (False, True)
    }
    out["logs"] = logs
    return out


@pytest.mark.parametrize("trace", [False, True])
def test_the_tiny_hybrid_cell_runs_correct_with_no_request_failed(tiny_results, trace):
    result = tiny_results[trace]
    assert result["correct"] is True and result["failed"] == 0, tiny_results["logs"]
    assert result["attempted"] >= 48
    median, limit = result["compared"]["logprob_diff_median"]
    assert 0 < median < limit == 0.009
    assert result["compared"]["solo_rerun_differs"] == [0, 0]


def test_the_tiny_hybrid_cell_reports_what_it_can_off_the_chip(tiny_results):
    untraced, traced = tiny_results[False]["metrics"], tiny_results[True]["metrics"]
    assert set(untraced) == {m["name"] for m in BENCH["end_to_end"]}
    # the span is there; the two shares need a chip's peaks and are left out
    assert traced["admit_state_ms_p50"]["value"] > 0
    assert "ssm_state_roofline" not in traced and "ssd_prefill_roofline" not in traced
    assert traced["compiles_in_window"]["value"] == 0
    assert traced["batch_occupancy"]["value"] > 90


def test_a_tied_head_is_accepted_where_the_group_states_it_and_refused_elsewhere():
    """What the frozen case ``test_what_the_program_fixes_is_refused_until_
    it_has_the_field[tie_word_embeddings...]`` says of a later program,
    held against the program itself now that it has the field (the case's
    first half asserts the field away: ``tests/conftest.py``
    ``RETIRED_CASES``)."""
    from bee_code_interpreter_tpu.models import transformer as T
    from test_benchmark_counts import tiny

    tied = harness.transformer_config(
        T, tiny({"tie_embeddings": True}, tie_word_embeddings=True)
    )
    assert tied.tie_embeddings is True
    # published as tied, and the group sets none or another
    with pytest.raises(harness.CellError, match="untied embeddings only"):
        harness.transformer_config(T, tiny(tie_word_embeddings=True))
    with pytest.raises(
        harness.CellError, match="tie_word_embeddings=.* but transformer_config"
    ):
        harness.transformer_config(
            T, tiny({"tie_embeddings": False}, tie_word_embeddings=True)
        )
    # the value the program had fixed passes, set or left out
    assert harness.transformer_config(
        T, tiny(tie_word_embeddings=False)
    ).tie_embeddings is False
    untold = tiny()
    untold.pop("tie_word_embeddings", None)
    assert harness.transformer_config(T, untold).tie_embeddings is False
