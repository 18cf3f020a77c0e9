"""Latent attention (MLA) and the sorted expert layer through the normal
path, at a tiny size on the CPU: the paged latent pool against the plain
reference's full forward, the absorbed form against the published one, the
chip's share of the experts against the uncut layer, what the sorted
dispatch promises, what each part of the mathematics is worth, the pool's
indexing, and what a latent pool refuses by name.

Outside the slow lane (``tests/conftest.py`` ``SLOW_TEST_MODULES``): every
program here is tiny.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import spec
from bee_code_interpreter_tpu.models import moe
from bee_code_interpreter_tpu.models import transformer as T
from bee_code_interpreter_tpu.models.engine import Engine
from bee_code_interpreter_tpu.models.serving import (
    ContinuousBatcher,
    SamplingParams,
)
from bee_code_interpreter_tpu.ops import paged_attention
from bee_code_interpreter_tpu.ops.paged_kv_cache import (
    alloc_paged_cache,
    seed_prefill,
)

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
REFERENCE = spec.reference(ROOT, BENCH, "sarvam_mla")

YARN = {
    "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
    "mscale_all_dim": 1, "original_max_position_embeddings": 64,
    "type": "deepseek_yarn",
}
# 3 layers (1 dense), 4 heads of [16 | 8] on a latent of 32, 16 experts top-4
# of which 4 are held beside a shared one; float32, so that what differs from
# the reference is arithmetic order alone
TINY = T.TransformerConfig(
    vocab_size=128, d_model=64, n_layers=3, n_heads=4, d_ff=128,
    max_seq_len=128, rope_theta=10000.0, dtype=jnp.float32, rms_norm_eps=1e-6,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    qk_norm=True, rope_yarn=YARN, n_dense_layers=1, n_experts=16, moe_top_k=4,
    moe_scoring="sigmoid", moe_held_experts=4, moe_held_from=4, moe_d_ff=32,
    moe_shared_experts=1, moe_routed_scaling=2.5, moe_router_bias=True,
)
POOL = {"max_batch": 4, "n_pages": 40, "page_size": 8, "max_pages_per_seq": 6}


def published(config: T.TransformerConfig) -> dict:
    """The published keys the reference reads, for ``config``."""
    c = config
    return {
        "num_hidden_layers": c.n_layers, "num_attention_heads": c.n_heads,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim, "q_head_dim": c.qk_head_dim,
        "v_head_dim": c.v_head_dim, "kv_lora_rank": c.kv_lora_rank,
        "rms_norm_eps": c.rms_norm_eps, "use_qk_norm": c.qk_norm,
        "rope_theta": c.rope_theta, "rope_scaling": YARN,
        "first_k_dense_replace": c.n_dense_layers,
        "num_experts": c.held_experts, "experts_held_from": c.moe_held_from,
        "num_experts_per_tok": c.moe_top_k,
        "num_shared_experts": c.moe_shared_experts,
        "routed_scaling_factor": c.moe_routed_scaling,
        "moe_router_enable_expert_bias": c.moe_router_bias,
    }


def seeded(config: T.TransformerConfig, seed: int = 0):
    """``init_params`` with a router bias that tilts the selection (the
    program's own starts at zero, as an untrained one does)."""
    params = T.init_params(config, jax.random.PRNGKey(seed))
    if config.moe_router_bias:  # the router leaf's last row
        router = params["layers"]["moe"]["router"]
        params["layers"]["moe"]["router"] = router.at[:, -1].set(
            0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1), router[:, -1].shape)
        )
    return params


@pytest.fixture(scope="module")
def params():
    return seeded(TINY)


def reference_logprobs(params, config, prompt, tokens):
    """log p the plain reference gives each of ``tokens`` after ``prompt``,
    and its argmax at the same positions."""
    sequence = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    (logits, margins), = REFERENCE.forward(params, [sequence], published(config))
    rows = jax.nn.log_softmax(logits[len(prompt) - 1:], axis=-1)
    assert margins.shape == (config.n_layers, len(sequence))
    assert bool(jnp.all(jnp.isinf(margins[:config.n_dense_layers])))
    return (
        np.asarray([rows[j, t] for j, t in enumerate(tokens)]),
        np.asarray(jnp.argmax(rows, axis=-1)),
    )


def served(params, config, prompts, n_new=9, **batcher):
    engine = Engine(ContinuousBatcher(params, config, **{**POOL, **batcher}))
    tickets = [
        engine.submit(p, n_new, sampling=SamplingParams(logprobs=True))
        for p in prompts
    ]
    engine.run_to_completion()
    return [
        (np.asarray(engine.result(t)), np.asarray(engine.result_logprobs(t)))
        for t in tickets
    ]


PROMPTS = [
    np.random.default_rng(7).integers(0, 128, n).astype(np.int32)
    for n in (16, 11, 24)
]


def worst_difference(system_params, config, params, reference_config):
    """The largest difference between the log-probabilities ``config`` over
    ``system_params`` serves and those the reference gives the same tokens
    under ``reference_config`` over ``params``."""
    worst = 0.0
    for prompt, (tokens, logprobs) in zip(
        PROMPTS, served(system_params, config, PROMPTS)
    ):
        want, _ = reference_logprobs(params, reference_config, prompt, tokens)
        worst = max(worst, float(np.abs(want - logprobs).max()))
    return worst


# ------------------------------------- (a) the served path and the reference


@pytest.mark.parametrize("in_place", [False, True], ids=["slices", "kernel"])
def test_prefill_and_decode_through_the_latent_pool_match_the_reference(
    params, in_place, monkeypatch
):
    """Prefill (published form, K and V per head from the latent), the
    latent seeded into its pages, then 8 decode steps in the absorbed form
    over the pool, three rows of different lengths together: every token's
    log-probability is the plain reference's, and every greedy token its
    argmax. Both ways the decode step addresses the pool: a layer's slice
    scattered into and gathered, and the Pallas kernel (interpreted here)
    on the stacked leaf."""
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: in_place)
    batcher = ContinuousBatcher(params, TINY, **POOL)
    telemetry = batcher.kv_telemetry()
    assert telemetry["decode_attention"] == (
        "pages_in_place" if in_place else "gathered"
    )
    assert telemetry["cache_kind"] == "latent"
    # 3 layers x a slot of 128 (32 + 8, padded to the lane tile) x float32
    assert telemetry["cache_bytes_per_token"] == 3 * 128 * 4
    for prompt, (tokens, logprobs) in zip(PROMPTS, served(params, TINY, PROMPTS)):
        want, best = reference_logprobs(params, TINY, prompt, tokens)
        np.testing.assert_allclose(logprobs, want, atol=2e-4)
        assert tokens.tolist() == best.tolist()


# --------------------------------------- (e) what each part of it is worth


def without_yarn_scale(config):
    return dataclasses.replace(
        config, rope_yarn={**YARN, "mscale": 0, "mscale_all_dim": 0}
    )


def unnormed_latent(monkeypatch):
    plain = T.rms_norm

    def rms_norm(x, scale, eps=1e-5):
        if scale.shape[-1] == TINY.kv_lora_rank:
            return x
        return plain(x, scale, eps)

    monkeypatch.setattr(T, "rms_norm", rms_norm)


@pytest.mark.parametrize("left_out", [
    "router_bias", "routed_scaling", "shared_expert", "latent_norm", "yarn_scale",
])
def test_leaving_a_part_of_the_mathematics_out_fails_the_comparison(
    params, left_out, monkeypatch
):
    """The comparison of (a) holds the system to 2e-4; a system that leaves
    out the router's bias, the scaling factor, the shared expert, the
    latent's norm or YaRN's scale of the scores is out by a hundred times
    that or more."""
    config = {
        "router_bias": dataclasses.replace(TINY, moe_router_bias=False),
        "routed_scaling": dataclasses.replace(TINY, moe_routed_scaling=1.0),
        "shared_expert": dataclasses.replace(TINY, moe_shared_experts=0),
        "latent_norm": TINY,
        "yarn_scale": without_yarn_scale(TINY),
    }[left_out]
    system_params = params
    if left_out == "router_bias":
        system_params = jax.tree.map(lambda x: x, params)
        router = system_params["layers"]["moe"]["router"]
        system_params["layers"]["moe"]["router"] = router[:, :-1]
    if left_out == "latent_norm":
        unnormed_latent(monkeypatch)
    assert T._score_scale(without_yarn_scale(TINY)) == pytest.approx(24 ** -0.5)
    assert T._score_scale(TINY) == pytest.approx(
        24 ** -0.5 * (0.1 * np.log(40) + 1) ** 2
    )
    assert worst_difference(system_params, config, params, TINY) > 0.02


# ----------------------------------------------- (b) absorbed and published


def test_absorbed_attention_equals_published_attention_on_the_same_latent(params):
    c = TINY
    layer = T._take_layer(
        {n: params["layers"][n] for n in T.LATENT_LEAVES}, 1
    )
    B, L = 2, 20
    x = jax.random.normal(jax.random.PRNGKey(3), (B, L, c.d_model))
    positions = jnp.broadcast_to(jnp.arange(L), (B, L))
    q_nope, q_rope, latent = T._latent_projections(x, layer, c, positions)
    assert latent.shape == (B, L, c.kv_lora_rank + c.qk_rope_head_dim)
    want = T._latent_attention_published(q_nope, q_rope, latent, layer, c, None)

    padded = T._pad_latent(latent, c)  # as the pool keeps it
    q = T._absorbed_query(q_nope, q_rope, layer, c)
    assert padded.shape[-1] == q.shape[-1] == c.latent_width == 128
    scores = jnp.einsum("bhqw,bkw->bhqk", q, padded) * T._score_scale(c)
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o_latent = jnp.einsum("bhqk,bkc->bhqc", weights, padded[..., :c.kv_lora_rank])
    got = T._absorbed_output(o_latent, layer, c)
    np.testing.assert_allclose(got, want, atol=2e-5)


# ------------------------------------------------------ (c) the share test


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer(params):
    """Four chips hold 4 of the 16 experts each. The four partial expert
    sums, with the shared expert counted once, are what the plain reference
    gives for the whole layer with every expert held."""
    c = TINY
    whole = dataclasses.replace(c, moe_held_experts=16, moe_held_from=0)
    layer = jax.tree.map(
        lambda x: x[0], T.init_params(whole, jax.random.PRNGKey(5))["layers"]
    )
    layer["moe"]["router"] = layer["moe"]["router"].at[-1].set(
        0.1 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    )
    h = jax.random.normal(jax.random.PRNGKey(4), (24, c.d_model))
    y = T.rms_norm(h, layer["ln2"], c.rms_norm_eps)[None]

    total = jnp.zeros_like(y)
    for share in range(4):
        held = dataclasses.replace(
            c, moe_held_from=4 * share, moe_shared_experts=int(share == 0)
        )
        mine = {
            **layer["moe"],
            **{n: layer["moe"][n][4 * share:4 * share + 4] for n in moe.EXPERT_STACKS},
        }
        total = total + moe.held_experts_mlp(mine, y, held)

    with jax.default_matmul_precision("highest"):
        want, margin = REFERENCE.expert_mlp(
            h, layer, lambda name, e: layer["moe"][name][e], published(whole)
        )
    np.testing.assert_allclose(h + total[0], want, atol=1e-5)
    assert margin.shape == (24,) and bool(jnp.all(margin >= 0))
    # and a share alone is NOT the layer: three quarters of the pairs are gone
    alone = moe.held_experts_mlp(
        {**layer["moe"], **{n: layer["moe"][n][:4] for n in moe.EXPERT_STACKS}},
        y, dataclasses.replace(c, moe_held_from=0),
    )
    assert float(jnp.abs(h + alone[0] - want).max()) > 0.05


# ------------------------------------------------- (d) the sorted dispatch


def dense_experts(moe_params, x, config):
    """Every pair of every held expert, the plain way."""
    chosen, weights = moe.route_sigmoid(x, moe_params, config)
    out = jnp.zeros_like(x)
    for e in range(config.held_experts):
        weight = jnp.where(
            chosen == config.moe_held_from + e, weights, 0.0
        ).sum(axis=-1, keepdims=True)
        y = jax.nn.silu(x @ moe_params["we_gate"][e]) * (x @ moe_params["we_up"][e])
        out = out + weight * (y @ moe_params["we_down"][e])
    return out


@pytest.fixture(scope="module")
def one_expert_layer(params):
    held = dataclasses.replace(TINY, moe_shared_experts=0)
    layer = jax.tree.map(lambda x: x[0], params["layers"]["moe"])
    return held, layer


def test_the_sorted_dispatch_drops_no_pair(one_expert_layer):
    c, layer = one_expert_layer
    x = jax.random.normal(jax.random.PRNGKey(8), (96, c.d_model))
    chosen, _ = moe.route_sigmoid(x, layer, c)
    order, sizes = moe.sort_pairs(chosen, c)
    local = np.asarray(chosen).reshape(-1) - c.moe_held_from
    is_held = (local >= 0) & (local < c.held_experts)
    assert int(sizes.sum()) == int(is_held.sum()) > 0
    assert sizes.tolist() == np.bincount(local[is_held], minlength=4).tolist()
    # the held pairs first, by expert, in token order within one
    first = np.asarray(order)[: int(is_held.sum())]
    assert is_held[first].all()
    keys = local[first]
    assert (np.diff(keys) >= 0).all()
    assert all((np.diff(first[keys == e]) > 0).all() for e in range(4))
    got = moe.held_experts_mlp(layer, x[None], c)[0]
    np.testing.assert_allclose(got, dense_experts(layer, x, c), atol=1e-5)


def test_a_batch_with_more_pairs_than_the_common_buffer_is_still_whole(
    one_expert_layer,
):
    """A bias that sends every token to the held experts: four times the
    expected pairs. The buffer of every pair takes them."""
    c, layer = one_expert_layer
    bias = jnp.full((16,), -1.0).at[4:8].set(1.0)
    layer = {**layer, "router": layer["router"].at[-1].set(bias)}
    x = jax.random.normal(jax.random.PRNGKey(9), (96, c.d_model))
    chosen, _ = moe.route_sigmoid(x, layer, c)
    _, sizes = moe.sort_pairs(chosen, c)
    assert int(sizes.sum()) == 96 * 4 > moe.sorted_rows(96, c)
    got = moe.held_experts_mlp(layer, x[None], c)[0]
    np.testing.assert_allclose(got, dense_experts(layer, x, c), atol=1e-5)


def test_a_token_gets_the_same_output_alone_and_in_a_batch(one_expert_layer):
    """As serving's solo re-run asks it: the same program at the same
    shapes, the other rows idle (zeros) or busy with other tokens. The
    token's row of the sorted buffer sits elsewhere among other rows, and
    its output is the same to the bit."""
    c, layer = one_expert_layer
    x = jax.random.normal(jax.random.PRNGKey(10), (4, 24, c.d_model))
    together = moe.held_experts_mlp(layer, x, c)
    for b, l in ((0, 0), (2, 7), (3, 23)):
        alone = jnp.zeros_like(x).at[b, l].set(x[b, l])
        np.testing.assert_array_equal(
            moe.held_experts_mlp(layer, alone, c)[b, l], together[b, l]
        )
        others = jax.random.normal(jax.random.PRNGKey(b), x.shape).at[b, l].set(x[b, l])
        np.testing.assert_array_equal(
            moe.held_experts_mlp(layer, others, c)[b, l], together[b, l]
        )
    assert TINY.moe_exact  # so the harness's solo re-run covers this dispatch


def test_the_sorted_buffer_holds_a_quarter_more_than_the_expected_pairs():
    """Rows computed: the expected pairs, a quarter more and 32, never more
    than every pair; at the benchmark's sizes 1.26 to 1.5 of the expected."""
    big = dataclasses.replace(
        TINY, n_experts=128, moe_held_experts=32, moe_held_from=0, moe_top_k=8
    )
    for tokens, rows in ((64, 192), (512, 1312), (1024, 2592), (2048, 5152)):
        assert moe.sorted_rows(tokens, big) == rows
        assert 1.25 <= rows / (tokens * 8 * 32 / 128) <= 1.5
    assert moe.sorted_rows(1, big) == 8  # one token's 8 pairs: every pair
    # the grouped matmul's operand is the buffer, not the pairs of every expert
    c = dataclasses.replace(TINY, moe_shared_experts=0)
    layer = jax.tree.map(
        lambda x: x[0], T.init_params(c, jax.random.PRNGKey(0))["layers"]["moe"]
    )
    text = str(jax.make_jaxpr(
        lambda x: moe.held_experts_mlp(layer, x, c)
    )(jnp.zeros((1, 96, c.d_model))))
    rows = moe.sorted_rows(96, c)
    assert rows == 152 < 96 * 4
    assert f"f32[{rows},32]" in text and f"f32[{96 * 4},32]" in text  # both branches


# ------------------------------------------------- (f) the pool's indexing


def test_a_permuted_block_table_gives_the_same_logits(params):
    c = TINY
    tokens = jax.random.randint(jax.random.PRNGKey(11), (1, 21), 0, c.vocab_size)
    logits, (latent,) = T.forward(params, tokens[:, :16], c, return_kv=True)
    assert latent.shape == (3, 1, 16, c.latent_width)

    def decode(pages):
        cache = seed_prefill(
            alloc_paged_cache(c, 16, 8), jnp.asarray(pages[:2]), latent[:, 0]
        )
        table = jnp.zeros((1, 4), jnp.int32).at[0, :3].set(jnp.asarray(pages))
        out = []
        for t in range(16, 21):
            step, cache = T.decode_step_paged(
                params, tokens[:, t:t + 1], jnp.asarray([t]), cache, table, c
            )
            out.append(step[0, 0])
        return jnp.stack(out)

    one, other = decode([3, 5, 7]), decode([12, 2, 9])
    np.testing.assert_array_equal(one, other)
    full = T.forward(params, tokens, c)
    np.testing.assert_allclose(one, full[0, 16:], atol=2e-5)


# ------------------------------------------------ what a latent pool refuses


def test_what_a_latent_pool_cannot_do_yet_is_refused_by_name(params):
    refused = {
        "prefix_cache": {"prefix_cache": True},
        "draft_params": {"draft_params": params, "draft_config": TINY},
        "adapters": {"adapters": [{}]},
    }
    for name, asked in refused.items():
        with pytest.raises(
            NotImplementedError, match=f"{name}.* not supported over a latent cache"
        ):
            ContinuousBatcher(params, TINY, **POOL, **asked)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    with pytest.raises(NotImplementedError, match="mesh .*one KV head"):
        ContinuousBatcher(params, TINY, **POOL, mesh=mesh)
    with pytest.raises(NotImplementedError, match="int8 latent cache"):
        dataclasses.replace(TINY, kv_cache_dtype="int8")
    batcher = ContinuousBatcher(params, TINY, **POOL)
    for asked in ({"prefill_chunk": 8}, {"interleave_admission": 8}):
        with pytest.raises(
            NotImplementedError, match="not supported over a latent cache"
        ):
            batcher.submit(PROMPTS[0], 4, **asked)
    # the paths that assume K and V per head under one scan
    cache = T.alloc_decode_cache(dataclasses.replace(TINY, kv_lora_rank=0,
                                 qk_norm=False), 1, 8)
    with pytest.raises(NotImplementedError, match="a latent cache"):
        T.decode_window(params, jnp.zeros((1, 1), jnp.int32), 0, cache, TINY)
    # fields of the sorted expert layer under the GShard dispatch
    with pytest.raises(NotImplementedError, match="moe_shared_experts"):
        T.TransformerConfig(n_experts=8, moe_shared_experts=1)
    with pytest.raises(ValueError, match="deepseek_yarn"):
        T.TransformerConfig(rope_yarn={"type": "linear", "factor": 2})


def test_the_step_record_and_the_admission_carry_the_held_pairs(params):
    batcher = ContinuousBatcher(params, TINY, **POOL)
    # 2 expert layers x top-4 x 4 held of 16: 2 pairs a token
    assert batcher._held_pairs_stat(16) == {"held_expert_pairs": 32}
    dense = ContinuousBatcher(
        T.init_params(T.TransformerConfig.tiny(), jax.random.PRNGKey(0)),
        T.TransformerConfig.tiny(), **POOL,
    )
    assert dense._held_pairs_stat(16) == {}
    assert dense.kv_telemetry()["cache_kind"] == "kv_heads"
    # K and V of 2 layers x 4 heads x 16, bf16
    assert dense.kv_telemetry()["cache_bytes_per_token"] == 2 * 2 * 4 * 16 * 2


# --------------------------------------------------- the seeded router bias


def test_seeded_the_held_experts_take_a_quarter_of_the_pairs():
    """``benchmarks/lib/params.py`` fills a leaf at variance 1 / its
    second-to-last dimension: the bias is the router leaf's last row, so
    that its seeded values are a router weight's (a few hundredths at
    these widths, 0.0156 at 4096), beside sigmoid scores that spread by 0.2.
    Seeded so, the 32 held experts of 128 take a quarter of the pairs within
    0.03, and none over three times its even share."""
    from benchmarks.lib.params import seeded_params

    c = dataclasses.replace(
        TINY, d_model=4096, n_experts=128, moe_held_experts=32, moe_held_from=0,
        moe_top_k=8, dtype=jnp.bfloat16,
    )
    shapes = jax.eval_shape(lambda k: T.init_params(TINY, k), jax.random.PRNGKey(0))
    assert shapes["layers"]["moe"]["router"].shape == (2, 65, 16)
    assert "router_bias" not in shapes["layers"]["moe"]
    assert {"ln_q", "ln_kv"} <= set(shapes["layers"])  # norm scales: filled with ones

    def routers(config, key):  # the routers alone, at the published width
        return {"layers": {"moe": {"router": jnp.zeros((3, 4097, 128))}}}

    for seed in (3, 2**31 + 11):
        params = seeded_params(routers, c, seed)
        bias = np.asarray(params["layers"]["moe"]["router"], np.float32)[:, -1]
        assert 0.012 < bias.std() < 0.02 and abs(bias).max() < 0.03
        for layer in range(3):
            moe_layer = jax.tree.map(lambda x: x[layer], params["layers"]["moe"])
            x = jax.random.normal(jax.random.PRNGKey(layer), (4096, c.d_model))
            chosen, weights = moe.route_sigmoid(x, moe_layer, c)
            np.testing.assert_allclose(weights.sum(axis=-1), 2.5, rtol=1e-5)
            share = np.bincount(np.asarray(chosen).reshape(-1), minlength=128) / (4096 * 8)
            assert abs(share[:32].sum() - 0.25) < 0.03
            assert share[:32].max() < 3 / 128


# ------------------------------------- the first token's nucleus, on the host


@pytest.mark.parametrize("vocab, spread", [
    (7, 1.0), (513, 0.1), (2048, 30.0), (32000, 1.0), (65536, 1.0), (65536, 5.0),
])
def test_the_binned_nucleus_keeps_what_a_stable_sort_of_the_row_keeps(vocab, spread):
    """``filtered_probs_host`` finds the nucleus without sorting the row
    (9.4 ms at a vocabulary of 65,536 became 2.9: every sampling request's
    first token). The distribution is, to the bit, the one a stable
    descending argsort of the whole row gives: with ties, with top-k before
    it, at a top_p of 0 (the top token alone)."""
    from bee_code_interpreter_tpu.models.serving import filtered_probs_host

    def sorted_whole(logits, params):
        lg = logits.astype(np.float64) / params.temperature
        if params.top_k is not None:
            kth = np.partition(lg, -params.top_k)[-params.top_k]
            lg = np.where(lg < kth, -np.inf, lg)
        order = np.argsort(-lg, kind="stable")
        probs = np.exp(lg[order] - lg[order[0]])
        probs /= probs.sum()
        keep = np.cumsum(probs) - probs < params.top_p
        keep[0] = True
        lg[order[~keep]] = -np.inf
        probs = np.exp(lg - lg.max())
        return probs / probs.sum()

    rng = np.random.default_rng(vocab)
    for trial in range(12):
        row = (rng.normal(size=vocab) * spread).astype(np.float32)
        if trial % 3 == 0:
            row = np.round(row)  # many exact ties
        if trial == 11:
            row[:] = 0.0  # one bin holds the whole row
        for top_p in (0.0, 0.1, 0.9, 0.95):
            for top_k in (None, 5):
                params = SamplingParams(
                    temperature=(0.3, 0.8, 1.5)[trial % 3], top_p=top_p, top_k=top_k
                )
                got = filtered_probs_host(row, params)
                np.testing.assert_array_equal(got, sorted_whole(row, params))
                assert got.sum() == pytest.approx(1.0)
