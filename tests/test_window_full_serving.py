"""Window layers that keep only their window by row beside full layers in the
paged pool, a head size of its own, and a layer pattern over expert layers,
through the normal path at a tiny size on the CPU: prefill then decode
through ``Engine`` and the two-part pool against the plain reference's full
forward (across wraps of the ring, rows of different lengths together, a row
recycled to a shorter request), the ring against the mask-only
``sliding_window`` path, the chip's share of the experts against the uncut
layer, the page arithmetic, the seeding, the spans and telemetry, and what
is refused over rings by name.

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
    seed_rings,
)

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
REFERENCE = spec.reference(ROOT, BENCH, "exaone_moe")

WINDOW = 8
KINDS = ("sliding_attention",) * 3 + ("full_attention",) + ("sliding_attention",)
# K-EXAONE's cut in small: 5 layers (layer 0 dense and a window layer, then
# one period sliding, sliding, full, sliding), 4 heads / 2 KV heads of 8 on a
# hidden size of 48 (so head_dim is not d_model / heads = 12), a window of 8,
# 16 experts top-4 of which 4 are held beside a shared one; float32, so that
# what differs from the reference is arithmetic order alone
TINY = T.TransformerConfig(
    vocab_size=128, d_model=48, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=8,
    d_ff=96, max_seq_len=128, rope_theta=10000.0, dtype=jnp.float32,
    sliding_window=WINDOW, layer_types=KINDS, position_embedding="rope_window",
    qk_norm=True, n_dense_layers=1, n_experts=16, moe_top_k=4,
    moe_scoring="sigmoid", moe_held_experts=4, moe_held_from=4, moe_d_ff=32,
    moe_shared_experts=1, moe_routed_scaling=2.5, moe_router_bias=True,
)
POOL = {"max_batch": 4, "n_pages": 48, "page_size": 4, "max_pages_per_seq": 16}
RNG = np.random.default_rng(7)
# shorter than the window, the window, and several times it
PROMPTS = [RNG.integers(0, 128, n).astype(np.int32) for n in (5, 8, 21, 35)]
N_NEW = 20  # from a prompt of 5: slots 5..7, then two whole wraps of 8


def published(config: T.TransformerConfig, **other) -> dict:
    """The published keys the reference reads, for ``config``."""
    c = config
    return {
        "num_hidden_layers": c.n_layers, "num_attention_heads": c.n_heads,
        "num_key_value_heads": c.kv_heads, "head_dim": c.head_dim,
        "rms_norm_eps": c.rms_norm_eps, "use_qk_norm": c.qk_norm,
        "layer_types": list(c.layer_types), "sliding_window": c.sliding_window,
        "position_embedding": c.position_embedding,
        "rope_parameters": {"rope_theta": c.rope_theta, "rope_type": "default"},
        "first_k_dense_replace": c.n_dense_layers,
        "num_experts": c.held_experts, "experts_held_from": c.moe_held_from,
        "num_experts_per_tok": c.moe_top_k,
        "num_shared_experts": c.moe_shared_experts,
        "routed_scaling_factor": c.moe_routed_scaling,
        "moe_router_enable_expert_bias": c.moe_router_bias,
        **other,
    }


def seeded(config: T.TransformerConfig, seed: int = 0):
    """``init_params`` with norm scales that are not all ones (so that a
    scale left out shows) and a router bias that tilts the selection."""
    params = T.init_params(config, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)
    for tree in (params["layers"], params.get("dense_layers", {})):
        for name in ("ln_q", "ln_k"):
            if name in tree:
                key, sub = jax.random.split(key)
                tree[name] = 1.0 + 0.3 * jax.random.normal(sub, tree[name].shape)
    if config.moe_router_bias:  # the router leaf's last row
        router = params["layers"]["moe"]["router"]
        params["layers"]["moe"]["router"] = router.at[:, -1].set(
            0.1 * jax.random.normal(key, router[:, -1].shape)
        )
    return params


@pytest.fixture(scope="module")
def params():
    return seeded(TINY)


def reference_logprobs(params, cfg: dict, prompt, tokens):
    """log p the plain reference gives each of ``tokens`` after ``prompt``,
    and its argmax at the same positions."""
    sequence = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    (logits, margins), = REFERENCE.forward(params, [sequence], cfg)
    rows = jax.nn.log_softmax(logits[len(prompt) - 1:], axis=-1)
    assert margins.shape == (cfg["num_hidden_layers"], len(sequence))
    assert bool(jnp.all(jnp.isinf(margins[:cfg["first_k_dense_replace"]])))
    return (
        np.asarray([rows[j, t] for j, t in enumerate(tokens)]),
        np.asarray(jnp.argmax(rows, axis=-1)),
    )


def served(params, config, prompts, n_new=N_NEW, **batcher):
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


def worst_difference(system_params, system_config, params, cfg: dict) -> float:
    """The largest |log p(system) - log p(reference)| over the prompts."""
    worst = 0.0
    for prompt, (tokens, logprobs) in zip(
        PROMPTS, served(system_params, system_config, PROMPTS, n_new=12)
    ):
        want, _ = reference_logprobs(params, cfg, prompt, tokens)
        worst = max(worst, float(np.abs(logprobs - want).max()))
    return worst


# ------------------------------------- (a) the served path and the reference


@pytest.mark.parametrize("head_dim, in_place", [(8, False), (128, True)],
                         ids=["slices", "kernel"])
def test_prefill_and_decode_through_pages_and_rings_match_the_reference(
    head_dim, in_place, monkeypatch
):
    """Prefill (each layer's flash window its own), the full layer seeded
    into its pages and the window layers into the rows' rings, then 19
    decode steps, four rows of different lengths together (a prompt shorter
    than the window, one as long, two several times it), every row across at
    least two wraps of its rings: every token's log-probability is the plain
    reference's, and every greedy token its argmax. Both ways the full layer
    addresses its pages: a layer's slice scattered into and gathered, and
    the Pallas kernel (interpreted here, at a head of 128) on the leaf
    stacked over the full layers alone."""
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: in_place)
    config = dataclasses.replace(TINY, head_dim=head_dim)
    params = seeded(config)
    batcher = ContinuousBatcher(params, config, **POOL)
    telemetry = batcher.kv_telemetry()
    assert telemetry["decode_attention"] == (
        "pages_in_place" if in_place else "gathered"
    )
    assert telemetry["decode_attention_by_kind"] == {
        "full_attention": telemetry["decode_attention"],
        "sliding_attention": "ring_by_row",
    }
    cfg = published(config)
    for prompt, (tokens, logprobs) in zip(PROMPTS, served(params, config, PROMPTS)):
        want, best = reference_logprobs(params, cfg, prompt, tokens)
        np.testing.assert_allclose(logprobs, want, atol=2e-4)
        assert tokens.tolist() == best.tolist()


def test_a_row_recycled_to_a_shorter_request_reads_no_stale_ring(params):
    """One row: a request of 35 + 12 tokens fills its rings many times over,
    then a prompt of 3 (shorter than the window) takes the row. Its rings
    hold the first tenant's keys beyond slot 2; none of them is read."""
    engine = Engine(ContinuousBatcher(params, TINY, **{**POOL, "max_batch": 1}))
    cfg = published(TINY)
    for prompt in (PROMPTS[3], PROMPTS[0][:3], PROMPTS[2]):
        ticket = engine.submit(prompt, 12, sampling=SamplingParams(logprobs=True))
        engine.run_to_completion()
        tokens = np.asarray(engine.result(ticket))
        want, best = reference_logprobs(params, cfg, prompt, tokens)
        np.testing.assert_allclose(engine.result_logprobs(ticket), want, atol=2e-4)
        assert tokens.tolist() == best.tolist()
        engine.release(ticket)


def test_layers_left_over_after_the_last_whole_period_run_after_the_scan():
    """The published pattern [sliding x 3, full] x n after a dense layer 0 is
    no whole number of periods: 12 layers are the dense one, two periods
    (sliding, sliding, full, sliding) and three layers more (the published
    48: eleven periods and three)."""
    config = dataclasses.replace(TINY, n_layers=12, layer_types=KINDS[:4] * 3)
    assert (config.layer_period, T._n_periods(config)) == (4, (2, 3))
    assert config.paged_layers == (3, 7, 11) and len(config.window_layers) == 9
    whole = dataclasses.replace(TINY, n_layers=48, layer_types=KINDS[:4] * 12)
    assert (whole.layer_period, T._n_periods(whole)) == (4, (11, 3))
    params = seeded(config)
    cfg = published(config)
    for prompt, (tokens, logprobs) in zip(
        PROMPTS[1:3], served(params, config, PROMPTS[1:3], n_new=10)
    ):
        want, best = reference_logprobs(params, cfg, prompt, tokens)
        np.testing.assert_allclose(logprobs, want, atol=2e-4)
        assert tokens.tolist() == best.tolist()


@pytest.mark.parametrize("control", [
    "window_mask_dropped", "rotary_in_the_full_layer", "a_ring_of_7_slots",
    "no_qk_norm",
])
def test_a_wrong_window_ring_or_rotary_fails_the_comparison(params, control):
    """The comparison of (a) holds the system to 2e-4; a system whose window
    layers see everything, that rotates the full layer's q and k, whose ring
    is a slot short or that leaves the per-head norms out is out by a
    hundred times that or more."""
    system = {
        # every layer full: the K/V of all tokens kept and attended over
        # (and rotary where the reference has it, in the window layers)
        "window_mask_dropped": dataclasses.replace(TINY, sliding_window=64),
        "rotary_in_the_full_layer": dataclasses.replace(
            TINY, position_embedding="rope"
        ),
        "a_ring_of_7_slots": dataclasses.replace(TINY, sliding_window=WINDOW - 1),
        "no_qk_norm": dataclasses.replace(TINY, qk_norm=False),
    }[control]
    assert worst_difference(params, system, params, published(TINY)) > 0.02


# ------------------------- (b) the ring against the mask-only window path


def test_the_ring_gives_what_the_mask_over_pages_that_keep_everything_gives():
    """The oracle that was there: ONE ``sliding_window`` for a model whose
    layers are not told apart, every token's K/V kept in pages and the
    window a mask over the gathered table. A model of window layers alone
    keeps 8 slots a row a layer and no page at all, and decodes the same
    tokens at the same log-probabilities."""
    plain = T.TransformerConfig(
        vocab_size=128, d_model=48, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=96, max_seq_len=128, rope_theta=10000.0,
        dtype=jnp.float32, sliding_window=WINDOW,
    )
    rings = dataclasses.replace(plain, layer_types=("sliding_attention",) * 3)
    params = T.init_params(plain, jax.random.PRNGKey(2))
    assert jax.tree.structure(params) == jax.tree.structure(
        T.init_params(rings, jax.random.PRNGKey(2))
    )
    assert rings.paged_window is None and plain.paged_window == WINDOW
    masked = served(params, plain, PROMPTS)
    ringed = served(params, rings, PROMPTS)
    for (want_tokens, want), (tokens, got) in zip(masked, ringed):
        assert tokens.tolist() == want_tokens.tolist()
        np.testing.assert_allclose(got, want, atol=2e-5)
    pool = alloc_paged_cache(rings, 8, 4, max_batch=2)
    assert pool["k"].shape[0] == 0 and pool["wk"].shape == (3, 2, WINDOW, 2, 8)


# ------------------------------------------------ (c) a head size of its own


def test_head_dim_is_a_field_and_none_is_the_derived_size():
    assert TINY.head_dim == 8 != TINY.d_model // TINY.n_heads
    shapes = jax.eval_shape(lambda k: T.init_params(TINY, k), jax.random.PRNGKey(0))
    assert shapes["layers"]["wq"].shape == (4, 48, 4 * 8)
    assert shapes["layers"]["wk"].shape == (4, 48, 2 * 8)
    assert shapes["layers"]["wo"].shape == (4, 4 * 8, 48)
    assert shapes["dense_layers"]["wq"].shape == (1, 48, 4 * 8)
    assert shapes["layers"]["ln_q"].shape == shapes["layers"]["ln_k"].shape == (4, 8)
    assert T._score_scale(TINY) == pytest.approx(8 ** -0.5)
    # none given: d_model // n_heads, and it still is after a replace that
    # changes either (the field reads as a number and stays "none given")
    tiny = T.TransformerConfig.tiny()
    assert tiny.head_dim == 16 and isinstance(tiny.head_dim, int)
    wider = dataclasses.replace(tiny, d_model=256, n_heads=2)
    assert wider.head_dim == 128
    assert dataclasses.replace(TINY, d_model=96).head_dim == 8  # given: kept
    assert dataclasses.replace(tiny) == tiny and hash(dataclasses.replace(tiny)) == hash(tiny)
    assert dataclasses.replace(tiny, head_dim=16) == tiny
    assert dataclasses.replace(tiny, head_dim=32) != tiny
    assert [f.default for f in dataclasses.fields(tiny) if f.name == "head_dim"] == [None]
    assert "head_dim=16" in repr(tiny)


def test_a_plain_decoder_with_a_head_of_its_own_decodes_what_it_prefills():
    """No pattern, no experts: the one ``lax.scan`` over stacked layers, the
    contiguous cache and the paged pool at a head of 8 on a hidden size of
    48."""
    config = T.TransformerConfig(
        vocab_size=128, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=8, d_ff=96, max_seq_len=64, dtype=jnp.float32, qk_norm=True,
    )
    params = seeded(config)
    prompt = jnp.asarray(PROMPTS[1])[None]
    model = T.Transformer(config)
    full = model.generate(params, prompt, max_new_tokens=6)
    cached = model.generate_cached(params, prompt, max_new_tokens=6)
    assert np.asarray(full).tolist() == np.asarray(cached).tolist()
    (tokens, _), = served(params, config, [PROMPTS[1]], n_new=6)
    assert tokens.tolist() == np.asarray(full)[0, len(PROMPTS[1]):].tolist()


# ------------------------------------------------------ (d) the share test


def test_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Four chips hold 4 of the 16 experts each (the benchmark's eight hold
    16 of 128). The four partial expert sums, with the shared expert
    counted once, are what the plain reference gives for the whole layer
    with every expert held."""
    c = TINY
    whole = dataclasses.replace(c, moe_held_experts=16, moe_held_from=0)
    layer = jax.tree.map(
        lambda x: x[1], T.init_params(whole, jax.random.PRNGKey(5))["layers"]
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


# ------------------------------------- (e) the pool, its pages and its rings


@pytest.mark.parametrize("kinds", [
    ("full_attention",) * 5,
    KINDS,
    ("sliding_attention",) * 4 + ("full_attention",),
], ids=["no_window_layer", "four_of_five", "four_of_five_again"])
def test_a_row_of_l_tokens_holds_ceil_l_over_16_pages_whatever_the_window_layers(
    params, kinds
):
    config = dataclasses.replace(TINY, layer_types=kinds)
    batcher = ContinuousBatcher(
        seeded(config), config, max_batch=2, n_pages=24, page_size=16,
        max_pages_per_seq=8,
    )
    for prompt_tokens, new_tokens in ((1, 1), (16, 16), (17, 16), (90, 30)):
        assert batcher.validate_request(
            np.zeros(prompt_tokens, np.int32), new_tokens
        ) == -(-(prompt_tokens + new_tokens) // 16)
    n_window = kinds.count("sliding_attention")
    cache = batcher.cache
    assert cache["k"].shape == cache["v"].shape == (5 - n_window, 24, 2, 16, 8)
    if n_window:
        assert cache["wk"].shape == cache["wv"].shape == (n_window, 2, WINDOW, 2, 8)
    else:
        assert set(cache) == {"k", "v"} and batcher._seed_rings is None
    request = batcher.submit(PROMPTS[2], 6)
    telemetry = batcher.kv_telemetry()
    assert telemetry["pages_held"] == -(-(21 + 6) // 16) == 2
    ring_row = n_window * 2 * 2 * WINDOW * 8 * 4  # K and V, 2 heads, float32
    assert telemetry["paged_layers"] == 5 - n_window
    assert telemetry["window_layers"] == n_window
    assert telemetry["window_slots"] == (WINDOW if n_window else 0)
    assert telemetry["ring_bytes_per_row"] == ring_row
    assert telemetry["state_bytes_per_row"] == ring_row
    assert telemetry["state_bytes"] == 2 * ring_row
    assert telemetry["cache_bytes_per_token"] == (5 - n_window) * 2 * 2 * 8 * 4
    batcher.run_to_completion()
    assert len(batcher.result(request)) == 6


@pytest.mark.parametrize("length", [1, 3, 8, 9, 21, 24])
def test_seed_rings_puts_the_last_window_of_the_true_length_at_position_mod_window(
    length,
):
    """``length`` is the prompt's TRUE length, the K/V come padded to a
    page multiple: slot s holds the last position below ``length`` that is s
    mod 8, and what the row's last tenant left is gone."""
    padded = -(-length // 4) * 4
    k = jax.random.normal(jax.random.PRNGKey(length), (5, 1, 2, padded, 8))
    v = jax.random.normal(jax.random.PRNGKey(length + 50), (5, 1, 2, padded, 8))
    cache = jax.tree.map(
        lambda x: x + 7.0, alloc_paged_cache(TINY, 8, 4, max_batch=3)
    )
    seeded_cache = seed_rings(
        cache, jnp.int32(1), k, v, jnp.int32(length), TINY.window_layers
    )
    assert seeded_cache["k"] is cache["k"]  # the pages are seed_prefill's
    for name, pre in (("wk", k), ("wv", v)):
        ring = np.asarray(seeded_cache[name])
        assert (ring[:, 0] == 7.0).all() and (ring[:, 2] == 7.0).all()
        for position in range(max(0, length - WINDOW), length):
            np.testing.assert_array_equal(
                ring[:, 1, position % WINDOW],
                np.asarray(pre)[list(TINY.window_layers), 0, :, position],
            )
        assert not (ring[:, 1] == 7.0).any()


# --------------------------------- (f) spans, scopes, names and telemetry


class SpanSpy:
    spans: list = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, stats

    def __enter__(self):
        SpanSpy.spans.append((self.name, self.stats))

    def __exit__(self, *exc):
        pass


def test_the_admission_seeds_the_rings_under_a_span_of_its_own(params, monkeypatch):
    monkeypatch.setattr(SpanSpy, "spans", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", SpanSpy)
    batcher = ContinuousBatcher(params, TINY, **POOL)
    assert batcher._seed_rings.name == "seed_rings"
    batcher.submit(PROMPTS[2], 3)
    batcher.run_to_completion()
    names = [name for name, _ in SpanSpy.spans]
    assert names[:6] == [
        "serve.admit", "serve.admit.prefill", "serve.admit.seed_window",
        "serve.admit.seed_pool", "serve.admit.pull", "serve.admit.activate",
    ]
    assert dict(SpanSpy.spans)["serve.admit.seed_window"] == {}
    # what it moves is the telemetry's, by row: 4 window layers x K and V x
    # 2 heads x 8 slots x 8 x float32
    assert batcher.kv_telemetry()["ring_bytes_per_row"] == 4 * 2 * 2 * 8 * 8 * 4
    # a configuration without window layers has no such span
    monkeypatch.setattr(SpanSpy, "spans", [])
    full = dataclasses.replace(TINY, layer_types=("full_attention",) * 5)
    plain = ContinuousBatcher(seeded(full), full, **POOL)
    plain.submit(PROMPTS[2], 3)
    plain.run_to_completion()
    assert "serve.admit.seed_window" not in [name for name, _ in SpanSpy.spans]


def test_the_decode_program_is_one_and_scopes_name_the_two_kinds(params):
    batcher = ContinuousBatcher(params, TINY, **POOL)
    lowered = batcher._decode.lower(
        batcher.params, jnp.asarray(batcher.current), jnp.asarray(batcher.pos),
        batcher.cache, jnp.asarray(batcher.block_table),
    )
    assert lowered.as_text().startswith("module @jit_decode_step_paged ")
    text = lowered.as_text(debug_info=True)
    assert "attn.window" in text and "attn.full" in text
    # the pool is donated whole: pages and rings are one tree
    assert set(batcher.cache) == {"k", "v", "wk", "wv"}
    batcher.submit(PROMPTS[1], 4)
    batcher.run_to_completion()
    assert batcher._decode._cache_size() == 1


# ------------------------------------------ (g) what is refused, by name


def test_what_rings_cannot_be_served_with_is_refused_by_name(params):
    def batcher(**kw):
        return ContinuousBatcher(params, TINY, **{**POOL, **kw})

    over = "is not supported over window layers' rings"
    with pytest.raises(NotImplementedError, match=f"prefix_cache {over}: a shared page"):
        batcher(prefix_cache=True)
    with pytest.raises(NotImplementedError, match="draft_params .* rejected draft"):
        batcher(draft_params=params, draft_config=TINY)
    with pytest.raises(NotImplementedError, match=f"adapters {over}"):
        batcher(adapters=[{}])
    with pytest.raises(NotImplementedError, match="mesh .*rings are kept whole"):
        ContinuousBatcher._refuse_over_state(
            TINY, prefix_cache=False, draft_params=None, adapters=None,
            mesh=object(),
        )
    plain = batcher()
    for name in ("prefill_chunk", "interleave_admission"):
        with pytest.raises(NotImplementedError, match=f"{name} {over}"):
            plain.validate_request(PROMPTS[2], 4, **{name: 8})
    # a window of several tokens, asked of the program itself
    with pytest.raises(NotImplementedError, match="decode one token a row"):
        T.decode_window_paged(
            params, jnp.zeros((4, 2), jnp.int32), jnp.zeros(4, jnp.int32),
            plain.cache, jnp.asarray(plain.block_table), TINY,
        )
    with pytest.raises(NotImplementedError, match="no mesh over mamba layers or over window"):
        alloc_paged_cache(TINY, 8, 4, sharding=object(), max_batch=2)
    with pytest.raises(ValueError, match="needs max_batch"):
        alloc_paged_cache(TINY, 8, 4)


@pytest.mark.parametrize("fields, words", [
    ({"sliding_window": None}, "needs sliding_window"),
    ({"kv_cache_dtype": "int8"}, "no int8 cache"),
    ({"layer_types": ("attention",) + KINDS[1:]}, "tells its attention layers apart"),
    ({"layer_types": ("mamba",) + KINDS[1:]}, "a leading dense layer is an attention layer"),
    ({"layer_types": KINDS[:4]}, "for each of 5 layers, got 4"),
    ({"layer_types": ("local",) * 5}, r"with \['local'\]"),
    ({"layer_types": None}, "'rope_window' needs layer_types"),
    ({"moe_scoring": "softmax", "moe_held_experts": None, "moe_shared_experts": 0,
      "moe_routed_scaling": 1.0, "moe_router_bias": False, "n_dense_layers": 0},
     "a layer pattern with expert MLPs takes the sorted expert layer"),
    ({"position_embedding": "alibi"}, "'rope', 'nope' or 'rope_window'"),
])
def test_a_pattern_the_program_cannot_run_is_refused_by_name(fields, words):
    with pytest.raises(ValueError, match=words):
        dataclasses.replace(TINY, **fields)


def test_what_this_pr_lifted_now_runs():
    """Refused until PR 35, each by name: leading dense layers together with
    a declared layer pattern, and a layer pattern with expert MLPs."""
    assert TINY.n_dense_layers == 1 and TINY.layer_types == KINDS
    assert TINY.n_experts == 16 and TINY.moe_exact
    assert (TINY.n_attention_layers, TINY.n_mamba_layers) == (5, 0)
    assert (TINY.layer_period, T._n_periods(TINY)) == (4, (1, 0))
    assert (TINY.window_layers, TINY.paged_layers) == ((0, 1, 2, 4), (3,))
    logits = T.forward(seeded(TINY), jnp.asarray(PROMPTS[2])[None], TINY)
    assert logits.shape == (1, 21, 128) and bool(jnp.isfinite(logits).all())
