"""Mamba-2 layers beside attention layers through ``Engine``, the batcher and
the paged pool, at a tiny size on the CPU: the served path against the plain
reference (``benchmarks/reference/granitehybrid.py``) on the benchmark's
seeded weights, by the comparison that decides a cell's ``correct``
(``benchmarks/lib/check.py``), and what state kept by row must hold.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import check
from benchmarks.lib.params import seeded_params
from benchmarks.reference import granitehybrid as reference
from bee_code_interpreter_tpu.models import mamba
from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.engine import Engine
from bee_code_interpreter_tpu.models.serving import (
    ContinuousBatcher,
    SamplingParams,
)
from bee_code_interpreter_tpu.ops.paged_kv_cache import alloc_paged_cache

PATTERN = ("mamba", "attention", "mamba") * 2
CONFIG = T.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=6, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=256, layer_types=PATTERN, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=16, mamba_chunk_size=32, embedding_multiplier=12,
    residual_multiplier=0.22, attention_multiplier=1 / 16, logits_scaling=8,
    position_embedding="nope", tie_embeddings=True, dtype=jnp.float32,
)
# the published keys the reference reads, for the same model
PUBLISHED = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "rms_norm_eps": 1e-5,
    "attention_multiplier": 1 / 16, "residual_multiplier": 0.22,
    "embedding_multiplier": 12, "logits_scaling": 8,
    "layer_types": list(PATTERN),
}
POOL = {"max_batch": 4, "page_size": 16, "max_pages_per_seq": 12, "n_pages": 64}
# float32 on both sides: what is left is the order of the sums
EXACT = {**reference.TOLERANCE, "logprob_median": 2e-5, "logprob_abs": 1e-4,
         "argmax_margin": 1e-4}


@pytest.fixture(scope="module")
def params():
    return seeded_params(T.init_params, CONFIG, 29)


def batcher_for(params, config=CONFIG, **pool):
    return ContinuousBatcher(params, config, **{**POOL, **pool})


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CONFIG.vocab_size, n, dtype=np.int32) for n in lengths]


def compared(engine, params, prompts, tolerance):
    """The harness's own comparison under ``tolerance``."""
    module = dataclasses.make_dataclass("Ref", [])()
    module.forward, module.TOLERANCE = reference.forward, tolerance
    verdict, _ = check.against_reference(
        engine, module, params, PUBLISHED, SamplingParams, 5, prompts
    )
    return verdict


# 100: no page multiple (padded to 112) and longer than a chunk of 32; 21:
# inside one chunk; 2: shorter than the conv kernel; 64: whole chunks and
# whole pages; 150: a last chunk cut short
@pytest.mark.parametrize("length", [100, 21, 2, 64, 150])
def test_served_path_agrees_with_the_reference(params, length):
    """Prefill, seeding at the true length and decoding through the pool
    give the log-probabilities the reference's full forward pass gives."""
    engine = Engine(batcher_for(params))
    verdict = compared(engine, params, prompts_of([length] * 6, length), EXACT)
    assert verdict["problems"] == [], verdict
    assert verdict["positions"] == 6 * check.NEW_TOKENS


def test_bf16_model_is_inside_the_references_tolerance(params):
    """The dtype the cell runs, the state kept in it too: inside the limits
    the reference states for the chip."""
    config = dataclasses.replace(CONFIG, dtype=jnp.bfloat16)
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    engine = Engine(batcher_for(bf16, config))
    verdict = compared(
        engine, bf16, prompts_of([100] * 8, 3), reference.TOLERANCE
    )
    assert verdict["problems"] == [], verdict


def run_all(batcher, prompts, new_tokens=6):
    """Each prompt through ``batcher`` (greedy, log-probabilities on), as
    many at once as it has rows: [(tokens, logprobs)]."""
    engine = Engine(batcher)
    tickets = [
        engine.submit(p, new_tokens, sampling=SamplingParams(logprobs=True))
        for p in prompts
    ]
    engine.run_to_completion()
    return [(engine.result(t), engine.result_logprobs(t)) for t in tickets]


def test_a_reused_row_gives_what_a_fresh_batcher_gives(params):
    """Two rows serve six requests: the later tenants of a row see nothing
    of the earlier ones' state."""
    prompts = prompts_of([40, 9, 33, 100, 3, 57], 1)
    reused = run_all(batcher_for(params, max_batch=2), prompts)
    for prompt, (tokens, logprobs) in zip(prompts, reused):
        (fresh_tokens, fresh_logprobs), = run_all(batcher_for(params, max_batch=2), [prompt])
        assert tokens == fresh_tokens
        np.testing.assert_allclose(logprobs, fresh_logprobs, atol=1e-5)


def test_rows_are_independent(params):
    """A request alone equals the same request in a full batch."""
    prompts = prompts_of([50, 20, 77, 5], 2)
    together = run_all(batcher_for(params), prompts)
    for prompt, (tokens, logprobs) in zip(prompts, together):
        (solo_tokens, solo_logprobs), = run_all(batcher_for(params), [prompt])
        assert tokens == solo_tokens
        np.testing.assert_allclose(logprobs, solo_logprobs, atol=1e-5)


def test_snapshot_and_restore_mid_decode_continue_identically(params):
    prompts = prompts_of([37, 80, 12], 4)
    first = batcher_for(params)
    sampling = SamplingParams(temperature=0.9, seed=11, logprobs=True)
    ids = [first.submit(p, 12, sampling=sampling) for p in prompts]
    for _ in range(5):
        first.step()
    snapshot = first.state_dict()
    assert {"k", "v", "ssm", "conv"} <= set(snapshot["device"]["cache"])
    second = batcher_for(params)
    second.load_state_dict(snapshot)
    first.run_to_completion()
    second.run_to_completion()
    for request in ids:
        assert second.result(request) == first.result(request)
        assert second.result_logprobs(request) == first.result_logprobs(request)


@pytest.mark.parametrize("length, chunk", [(64, 16), (50, 16), (7, 16), (96, 32)])
def test_chunked_scan_equals_the_stepwise_recurrence(length, chunk):
    """Random inputs, a tail of positions that must leave the state alone
    (dt = 0), fast and slow heads."""
    rng = np.random.default_rng(length)
    B, G, R, P, N = 2, 2, 3, 4, 8
    x = rng.standard_normal((B, length, G, R, P)).astype(np.float32)
    b = rng.standard_normal((B, length, G, N)).astype(np.float32)
    c = rng.standard_normal((B, length, G, N)).astype(np.float32)
    dt = rng.uniform(0.01, 3.0, (B, length, G, R)).astype(np.float32)
    dt[:, length - 3:] = 0.0
    a = -rng.uniform(0.1, 6.0, (G, R)).astype(np.float32)
    y, last = mamba.ssd_chunked(x, dt, a, b, c, chunk, jnp.float32)
    state = jnp.zeros((B, G, R, P, N), jnp.float32)
    for t in range(length):
        if t == length - 3:
            at_true_length = state
        y_t, state = mamba.ssm_step(state, x[:, t], dt[:, t], a, b[:, t], c[:, t])
        np.testing.assert_allclose(y[:, t], y_t, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(last, state, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(last, at_true_length, rtol=2e-4, atol=2e-4)


def test_the_allocator_gives_the_batcher_and_the_rehearsal_one_tree(params):
    """``tests/benchmark/test_benchmark_v5e_compile.py`` calls the allocator
    with what its signature takes of a file's ``pool`` group."""
    taken = inspect.signature(alloc_paged_cache).parameters
    rehearsed = jax.eval_shape(lambda: alloc_paged_cache(
        CONFIG, **{k: v for k, v in POOL.items() if k in taken}
    ))
    served = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batcher_for(params).cache
    )
    assert rehearsed == served
    assert served["k"].shape == (2, 64, 2, 16, 16)  # the attention layers alone
    assert served["ssm"].shape == (4, 4, 8, 16, 16)
    assert served["conv"].shape == (4, 4, 3, 160)
    # what a row keeps is in the model's dtype
    for dtype in (jnp.float32, jnp.bfloat16):
        kept = jax.eval_shape(lambda: alloc_paged_cache(  # noqa: B023
            dataclasses.replace(CONFIG, dtype=dtype), 8, 16, max_batch=2
        ))
        assert kept["ssm"].dtype == kept["conv"].dtype == kept["k"].dtype == dtype
    with pytest.raises(ValueError, match="needs max_batch"):
        alloc_paged_cache(CONFIG, 8, 16)


# What keeping the state in a narrower dtype costs, alone: a float32 model
# (nothing else rounds) with decay rates as ``init_mixer_params`` draws them
# (dt A 0.003 to 0.8 a token: heads that remember for hundreds of tokens),
# 200 decoded tokens after a 100-token prompt, the pool's state leaf planted
# in ``kept``. bf16, what a bf16 model keeps, stays a tenth of what such a
# model's own rounding gives (a median of 0.0007, models/mamba.py); an 8-bit
# float is told apart. Read, median and largest of 800 positions: bf16
# 0.00002 and 0.0010, float8_e4m3fn 0.00066 and 0.0078; the limits lie
# between.
@pytest.mark.parametrize("kept, inside", [
    (jnp.bfloat16, True), (jnp.float8_e4m3fn, False),
])
def test_the_state_is_held_to_bf16_over_200_decoded_tokens(monkeypatch, kept, inside):
    config = dataclasses.replace(CONFIG, max_seq_len=320)
    trained_like = T.init_params(config, jax.random.PRNGKey(3))
    alloc = mamba.alloc_state

    def planted(*args):
        state = alloc(*args)
        return {**state, "ssm": state["ssm"].astype(kept)}

    monkeypatch.setattr(mamba, "alloc_state", planted)
    monkeypatch.setattr(check, "NEW_TOKENS", 200)
    batcher = batcher_for(trained_like, config, max_pages_per_seq=20, n_pages=88)
    assert batcher.cache["ssm"].dtype == kept
    limits = {**reference.TOLERANCE, "logprob_median": 1.5e-4, "logprob_abs": 3e-3,
              "argmax_margin": 3e-3}
    verdict = compared(Engine(batcher), trained_like, prompts_of([100] * 4, 7), limits)
    assert verdict["positions"] == 4 * 200
    assert (verdict["problems"] == []) == inside, verdict["problems"]


def tp_mesh():
    from bee_code_interpreter_tpu.parallel import make_mesh

    return make_mesh({"tp": 2}, devices=jax.devices()[:2])


@pytest.mark.parametrize("kwargs, name", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"draft_params": "params", "draft_config": CONFIG},
     r"draft_params \(speculative mode\)"),
    ({"adapters": [{"wq": {}}]}, "adapters"),
    ({"mesh": tp_mesh}, r"mesh \(tp > 1\)"),
])
def test_what_cannot_hold_with_state_by_row_is_refused_at_construction(
    params, kwargs, name
):
    kwargs = {
        k: params if v == "params" else v() if callable(v) else v
        for k, v in kwargs.items()
    }
    with pytest.raises(NotImplementedError, match=f"{name} is not supported over mamba"):
        batcher_for(params, **kwargs)


@pytest.mark.parametrize("kwargs", [{"prefill_chunk": 16}, {"interleave_admission": 16}])
def test_chunked_and_interleaved_admission_are_refused_by_name(params, kwargs):
    (name,) = kwargs
    engine = Engine(batcher_for(params))
    for submit in (engine.submit, engine.batcher.submit):
        with pytest.raises(NotImplementedError, match=f"{name} is not supported over mamba"):
            submit(prompts_of([40])[0], 4, **kwargs)


def drop_true_length(batcher):
    """The fault: the state is seeded at the padded width."""
    prefill = batcher._prefill
    batcher._prefill = lambda params, tokens, length: prefill(params, tokens)


def zero_one_rows_state(batcher):
    """The fault: a row's state is lost between admission and decoding."""
    seed = batcher._seed_state
    batcher._seed_state = lambda cache, row, ssm, conv: seed(
        cache, row, jnp.zeros_like(ssm), conv
    )


# seeding at the padded width is caught in the cell's dtype under the limits
# the reference states for the chip; a zeroed state moves a tiny model of
# four fast-decaying mamba layers by less (0.05 at most), and is caught in
# float32, where nothing else differs
@pytest.mark.parametrize("plant, dtype, tolerance", [
    (drop_true_length, jnp.bfloat16, reference.TOLERANCE),
    (drop_true_length, jnp.float32, EXACT),
    (zero_one_rows_state, jnp.float32, EXACT),
])
def test_a_planted_fault_puts_the_check_outside_the_tolerance(
    params, plant, dtype, tolerance
):
    config = dataclasses.replace(CONFIG, dtype=dtype)
    cast = jax.tree.map(lambda x: x.astype(dtype), params)
    prompts = prompts_of([100] * 8, 3)
    sound = compared(Engine(batcher_for(cast, config)), cast, prompts, tolerance)
    assert sound["problems"] == [], sound
    batcher = batcher_for(cast, config)
    plant(batcher)
    faulty = compared(Engine(batcher), cast, prompts, tolerance)
    assert faulty["problems"], faulty
    assert faulty["logprob_diff_max"] > 5 * sound["logprob_diff_max"]


def test_spans_telemetry_scopes_and_program_names(params, monkeypatch):
    batcher = batcher_for(params)
    assert batcher._seed_state.name == "seed_state"
    assert batcher._decode.name == "decode_step_paged"
    spans = []

    class Recorded:
        def __init__(self, name, **stats):
            spans.append((name, stats))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorded)
    batcher.submit(prompts_of([40])[0], 4)
    names = [name for name, _ in spans]
    assert names.index("serve.admit") < names.index("serve.admit.seed_state")
    stats = dict(spans)["serve.admit.seed_state"]
    per_row = mamba.state_bytes_per_row(CONFIG)
    assert stats == {}  # what it moves is the telemetry's, by row
    telemetry = batcher.kv_telemetry()
    assert telemetry["state_bytes_per_row"] == per_row == 4 * (8 * 16 * 16 * 4 + 3 * 160 * 4)
    assert telemetry["state_rows_live"] == 1
    assert telemetry["state_bytes"] == 4 * per_row

    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    decode = jax.jit(functools.partial(T.decode_step_paged, config=CONFIG)).lower(
        shapes, ints(4, 1), ints(4), jax.eval_shape(lambda: batcher.cache), ints(4, 12)
    ).as_text(debug_info=True)
    assert "ssm.step" in decode and "ssm.conv" in decode
    prefill = jax.jit(functools.partial(T.forward, config=CONFIG, return_kv=True)).lower(
        shapes, ints(1, 48), length=ints()
    ).as_text(debug_info=True)
    assert "ssm.scan" in prefill and "ssm.conv" in prefill


def test_a_config_without_mamba_layers_reports_no_state(params):
    dense = T.TransformerConfig.tiny()
    batcher = ContinuousBatcher(
        T.init_params(dense, jax.random.PRNGKey(0)), dense, **POOL
    )
    telemetry = batcher.kv_telemetry()
    assert telemetry["state_bytes"] == telemetry["state_rows_live"] == 0
    assert set(batcher.cache) == {"k", "v"} and batcher._seed_state is None
