"""Routed experts under Mamba-2 mixers through ``Engine``, the batcher and
the paged pool, at a tiny size on the CPU (granite-4.0-h-small's
architecture): the served path against the plain reference
(``benchmarks/reference/granitemoehybrid.py``) by the comparison that decides
a cell's ``correct``, the chip's share of the experts against the uncut
layer, the softmax router in the sorted layer, and which dispatch each
configuration takes (``TransformerConfig.sorted_experts``).
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import check, harness
from benchmarks.lib.params import seeded_params
from benchmarks.reference import granitemoehybrid as reference
from bee_code_interpreter_tpu.models import moe
from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.engine import Engine
from bee_code_interpreter_tpu.models.serving import (
    ContinuousBatcher,
    SamplingParams,
)
from bee_code_interpreter_tpu.ops import grouped_matmul

ROOT = Path(__file__).resolve().parents[1]
PATTERN = ("mamba", "attention", "mamba") * 2
# 6 layers, 16 experts of 16 top-4 of which 8 are held (experts 8 to 15),
# beside a shared MLP of twice an expert's width; float32, so that what
# differs from the reference is the order of the sums
CONFIG = T.TransformerConfig(
    vocab_size=256, d_model=64, n_layers=6, n_heads=4, n_kv_heads=2, d_ff=16,
    max_seq_len=256, layer_types=PATTERN, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=16, mamba_chunk_size=32, embedding_multiplier=12,
    residual_multiplier=0.22, attention_multiplier=1 / 16, logits_scaling=8,
    position_embedding="nope", tie_embeddings=True, dtype=jnp.float32,
    n_experts=16, moe_top_k=4, moe_held_experts=8, moe_held_from=8,
    moe_shared_experts=2,
)
POOL = {"max_batch": 4, "page_size": 16, "max_pages_per_seq": 12, "n_pages": 64}
# float32 on both sides
EXACT = {**reference.TOLERANCE, "logprob_median": 2e-5, "logprob_abs": 1e-4,
         "argmax_margin": 1e-4, "tie_gap": None, "out_share_close": 0.0}


def published(config: T.TransformerConfig) -> dict:
    """The published keys the reference reads, for ``config``."""
    c = config
    return {
        "hidden_size": c.d_model, "num_attention_heads": c.n_heads,
        "num_key_value_heads": c.n_kv_heads, "mamba_n_heads": c.mamba_n_heads,
        "mamba_d_head": c.mamba_d_head, "mamba_d_state": c.mamba_d_state,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "rms_norm_eps": 1e-5,
        "attention_multiplier": c.attention_multiplier,
        "residual_multiplier": c.residual_multiplier,
        "embedding_multiplier": c.embedding_multiplier,
        "logits_scaling": c.logits_scaling, "layer_types": list(c.layer_types),
        "num_hidden_layers": c.n_layers, "num_local_experts": c.held_experts,
        "experts_held_from": c.moe_held_from, "num_experts_per_tok": c.moe_top_k,
    }


@pytest.fixture(scope="module")
def params():
    return seeded_params(T.init_params, CONFIG, 39)


def prompts_of(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CONFIG.vocab_size, n, dtype=np.int32) for n in lengths]


def compared(params, config, prompts, tolerance):
    """The harness's own comparison under ``tolerance``, through Engine."""
    module = dataclasses.make_dataclass("Ref", [])()
    module.forward, module.TOLERANCE = reference.forward, tolerance
    engine = Engine(ContinuousBatcher(params, config, **POOL))
    verdict, _ = check.against_reference(
        engine, module, params, published(config), SamplingParams, 5, prompts
    )
    return verdict


# --------------------------------------- the served path and the reference


def test_prefill_logits_are_the_references(params):
    tokens = prompts_of([37], 1)[0]
    (want, margins), = reference.forward(params, [tokens], published(CONFIG))
    got = T.forward(params, jnp.asarray(tokens)[None], CONFIG)[0]
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert margins.shape == (6, 37) and bool(jnp.all(margins >= 0))


# 100: no page multiple and longer than a scan chunk; 21: inside one chunk
@pytest.mark.parametrize("length", [100, 21])
def test_prefill_then_decode_through_the_pool_agrees_with_the_reference(
    params, length
):
    """Prefill, the state seeded at the true length and the K/V in their
    pages, then 8 decode steps, four rows at once, greedy and sampled: the
    log-probabilities the reference's full forward pass gives."""
    verdict = compared(params, CONFIG, prompts_of([length] * 6, length), EXACT)
    assert verdict["problems"] == [], verdict
    assert verdict["positions"] == 6 * check.NEW_TOKENS


def test_the_bf16_model_is_inside_the_references_tolerance(params):
    """The dtype the cell runs, state and pages in it too: inside the limits
    the reference states for the chip."""
    config = dataclasses.replace(CONFIG, dtype=jnp.bfloat16)
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    verdict = compared(bf16, config, prompts_of([100] * 8, 3), reference.TOLERANCE)
    assert verdict["problems"] == [], verdict


def test_a_request_alone_equals_it_in_a_full_batch(params):
    """``moe_exact``: the sorted dispatch computes a pair's row from its
    token alone, so the harness's solo re-run holds."""
    assert CONFIG.moe_exact and CONFIG.sorted_experts

    def run_all(prompts):
        engine = Engine(ContinuousBatcher(params, CONFIG, **POOL))
        tickets = [engine.submit(p, 8) for p in prompts]
        engine.run_to_completion()
        return [engine.result(t) for t in tickets]

    prompts = prompts_of([50, 20, 77, 5], 2)
    together = run_all(prompts)
    for prompt, tokens in zip(prompts, together):
        assert run_all([prompt]) == [tokens]


@pytest.mark.parametrize("left_out", ["shared_mlp", "routed_in_float8"])
def test_leaving_out_the_shared_mlp_or_rounding_the_experts_fails(
    params, left_out, monkeypatch
):
    """What the reference's limits are to tell apart, here at a tiny size:
    a system without the shared MLP, or whose routed MLP takes its operand
    through float8_e4m3's rounding, fails the float32 comparison by its
    median."""
    config = CONFIG
    if left_out == "shared_mlp":
        config = dataclasses.replace(CONFIG, moe_shared_experts=0)
    else:
        plain = moe.grouped_experts

        def rounded(x, *args, **kwargs):
            low = jnp.float8_e4m3fn
            return plain(x.astype(low).astype(x.dtype), *args, **kwargs)

        monkeypatch.setattr(moe, "grouped_experts", rounded)
    verdict = compared(params, config, prompts_of([100] * 4, 4), EXACT)
    assert verdict["logprob_diff_median"] > 5 * EXACT["logprob_median"]
    assert verdict["problems"]


# ------------------------------------------------------------ the share test


def test_two_halves_and_the_shared_mlp_once_are_the_uncut_layer(params):
    """Two chips hold 8 of the 16 experts each. The two partial expert sums,
    with the shared MLP counted once, are what the plain reference gives for
    the whole layer with every expert held; either half alone is not."""
    c = CONFIG
    whole = dataclasses.replace(c, moe_held_experts=16, moe_held_from=0)
    layer = jax.tree.map(
        lambda x: x[1], T.init_params(whole, jax.random.PRNGKey(5))["layers"]
    )
    h = jax.random.normal(jax.random.PRNGKey(4), (24, c.d_model))
    y = T.rms_norm(h, layer["ln2"], c.rms_norm_eps)

    def half(first, shared):
        held = dataclasses.replace(
            c, moe_held_from=first, moe_shared_experts=2 if shared else 0
        )
        mine = {
            **layer["moe"],
            **{n: layer["moe"][n][first:first + 8] for n in moe.EXPERT_STACKS},
        }
        return moe.held_experts_mlp(mine, y[None], held)[0]

    total = half(0, shared=True) + half(8, shared=False)
    with jax.default_matmul_precision("highest"):
        want, margin = reference.expert_mlp(
            y, layer["moe"], lambda name, e: layer["moe"][name][e],
            published(whole),
        )
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert margin.shape == (24,) and bool(jnp.all(margin >= 0))
    for alone in (half(0, shared=True), half(8, shared=True)):
        assert float(jnp.abs(alone - want).max()) > 0.05


# ------------------------------------------------ the router and the dispatch


def test_route_softmax_is_a_softmax_over_the_top_k_logits():
    """Against softmax(top-k(logits)) written out, and against Mixtral's
    softmax over every expert renormalised over the kept (the GShard
    router's ``_route_group``): the same choice, the same weights."""
    c = dataclasses.replace(CONFIG, n_experts=72, moe_top_k=10)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, c.d_model))
    router = jax.random.normal(jax.random.PRNGKey(2), (c.d_model, 72)) / 8
    chosen, weights = moe.route_softmax(x, {"router": router}, c)
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    top = np.argsort(-logits, axis=-1)[:, :10]
    kept = np.take_along_axis(logits, top, axis=-1)
    want = np.exp(kept - kept.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    assert np.asarray(chosen).tolist() == top.tolist()
    np.testing.assert_allclose(weights, want, atol=1e-6)
    # Mixtral's: softmax over all 72, top-10, renormalised over the kept
    dispatch, combine, _ = moe._route_group(
        x, router, n_experts=72, top_k=10, capacity=32
    )
    per_expert = np.asarray(combine.sum(axis=-1))  # [T, E]
    mine = np.zeros((32, 72))
    np.put_along_axis(mine, np.asarray(chosen), np.asarray(weights), axis=-1)
    np.testing.assert_allclose(per_expert, mine, atol=1e-6)
    assert set(moe.ROUTERS) == {"softmax", "sigmoid"}


def _bench_config(name: str) -> T.TransformerConfig:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == name)
    return harness.transformer_config(T, json.loads((ROOT / entry["file"]).read_text()))


def test_each_configuration_takes_the_dispatch_it_took_before(monkeypatch):
    """Mixtral (softmax, every expert, nothing beside) keeps the GShard
    dispatch; the sigmoid configurations and granite-4.0-h-small (softmax, a
    held share, a shared MLP) take the sorted one. No option chooses it.
    Its grouped matmul: ``ragged_dot`` off a TPU; on one, the kernel at a
    decode-sized buffer (8 tokens: 4 rows a held expert) and ``ragged_dot``
    at a prefill's (96 tokens: 48 rows an expert)."""
    assert not _bench_config("mixtral-8x7b").sorted_experts
    assert not T.TransformerConfig.mixtral_8x7b().sorted_experts
    for name in ("sarvam-105b", "k-exaone-236b-a23b", "granite-4.0-h-small"):
        assert _bench_config(name).sorted_experts, name
    granite = _bench_config("granite-4.0-h-small")
    assert (granite.moe_scoring, granite.held_experts, granite.n_experts) == (
        "softmax", 36, 72
    )

    def program(config, tokens=8):
        params = T.init_params(config, jax.random.PRNGKey(0))
        return str(jax.make_jaxpr(lambda p, t: T.forward(p, t, config))(
            params, jnp.zeros((1, tokens), jnp.int32)
        ))

    gshard = T.TransformerConfig.tiny_moe()
    assert "ragged_dot" not in program(gshard)
    shared = dataclasses.replace(gshard, moe_shared_experts=1)
    assert shared.sorted_experts and "ragged_dot" in program(shared)
    assert "pallas_call" not in program(shared)
    monkeypatch.setattr(grouped_matmul, "on_tpu", lambda: True)
    decode_sized, prefill_sized = program(shared, 8), program(shared, 96)
    assert "pallas_call" in decode_sized and "ragged_dot" not in decode_sized
    assert "ragged_dot" in prefill_sized and "pallas_call" not in prefill_sized
    # what only the sigmoid router has is refused under the softmax one
    for field, value in (("moe_router_bias", True), ("moe_routed_scaling", 2.5)):
        with pytest.raises(NotImplementedError, match=field):
            dataclasses.replace(shared, **{field: value})
    with pytest.raises(NotImplementedError, match="n_experts 0"):
        T.TransformerConfig(moe_shared_experts=1)


def test_the_pool_and_the_records_describe_the_expert_layer(params):
    batcher = ContinuousBatcher(params, CONFIG, **POOL)
    # 6 expert layers x top-4 x 8 held of 16: 12 pairs a token
    assert batcher._held_pairs_stat(10) == {"held_expert_pairs": 120}
    described = batcher.kv_telemetry()
    assert {k: described[k] for k in (
        "expert_layers", "held_experts", "router_width", "experts_per_token",
        "router",
    )} == {
        "expert_layers": 6, "held_experts": 8, "router_width": 16,
        "experts_per_token": 4, "router": "softmax",
    }
    assert described["state_bytes_per_row"] == 4 * (
        8 * 16 * 16 + 3 * (128 + 2 * 16)
    ) * 4
