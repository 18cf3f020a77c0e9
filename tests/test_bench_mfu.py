"""bench-mfu.py payload mechanics on CPU: a tiny-config variant must run
through the identical sandbox path and print both result markers (the real
run differs only in shapes and backend)."""

import asyncio
import importlib.util
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


bench = load("bench", REPO / "bench.py")
bench_mfu = load("bench_mfu", REPO / "scripts" / "bench-mfu.py")


def test_payload_is_valid_python():
    compile(bench_mfu.build_payload(), "<mfu payload>", "exec")


def test_tiny_payload_runs_end_to_end():
    tiny = dict(vocab_size=64, d_model=16, n_layers=1, n_heads=2,
                n_kv_heads=2, d_ff=32, max_seq_len=64)
    src = bench_mfu.build_payload(
        CONFIG=tiny, B=1, L=16, N_TRAIN=6, B_DEC=1, L_PROMPT=4, N_DEC=24
    )
    # chain_diff's jitter guard can legitimately trip at toy shapes on a
    # loaded box (e.g. the full suite running in parallel); mechanics
    # (payload runs, markers parse) are the point, so chains are long for
    # margin and the whole payload retries before failing.
    for attempt in range(3):
        try:
            results = asyncio.run(
                bench.run_payload_multi(
                    src, {"JAX_PLATFORMS": "cpu"}, 240.0,
                    ("RESULT_TRAIN", "RESULT_DECODE"),
                )
            )
            break
        except bench.PayloadError:
            if attempt:
                raise
    per_step_ms, tflops, n_params = results["RESULT_TRAIN"]
    assert per_step_ms > 0 and tflops > 0
    assert n_params > tiny["vocab_size"] * tiny["d_model"]
    per_tok_ms, tps = results["RESULT_DECODE"]
    assert per_tok_ms > 0 and tps > 0


def test_peak_comes_from_one_table_keyed_by_device_kind():
    import pytest

    results = {"RESULT_TRAIN": [100.0, 98.5, 8e8], "RESULT_DECODE": [2.0, 4000.0]}
    v5e = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    train, decode = bench_mfu.results_rows(v5e, results)
    assert train["peak_flops"] == 197e12 and train["mfu"] == 0.5
    assert train["device"] == decode["device"] == v5e
    # an unknown kind is an error, never a default peak
    with pytest.raises(KeyError, match="no published bf16 peak"):
        bench_mfu.results_rows({**v5e, "kind": "TPU v99"}, results)
    # and a host run is not an MFU at all
    with pytest.raises(RuntimeError, match="not a TPU"):
        bench_mfu.results_rows(
            {"platform": "cpu", "kind": "cpu", "count": 1}, results
        )
